"""cohere2_moe's parallel block (command-a-plus-05-2026: window or full
attention and sigmoid-routed SwiGLU experts beside averaged shared ones,
both off ONE LayerNorm and added together; rope on lane pairs on the
window layers and no positional term on the full ones; a tied head) on
the served path, against its plain reference
(benchmarks/references/cohere2_moe.py, which imports nothing of the
program), at a tiny size on the CPU with seeded weights: two periods,
window 32, pages of 16, 8 experts top-2, two shared experts.

Tolerances, on logits whose spread is ~1.0:

  VS_REFERENCE 2e-3   the float32 preset against the float32 reference.
      They differ by the order of float32 sums only (the program's paged
      attention and dropless experts against the reference's dense
      forms): 3e-5 measured over 72 decode steps. Anything of the
      mathematics left out reads tenths to units (every control below),
      and ONE norm computed in bfloat16 where float32 is stated reads
      over ten times the tolerance (`test_a_bfloat16_norm_fails...`).
  SAME_PROGRAM 1e-5   the same program on the same values by another
      route (a replayed request, a row beside other rows).
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_mellum_model import (
    GREEDY,
    PAGE,
    SLOTS,
    WIDTH,
    WINDOW,
    Collect,
    Row,
    prompt_of,
    request,
    table_for,
    wait_for,
)

from dynamo_tpu.engine import (
    InferenceScheduler,
    ModelRunner,
    PrefillRow,
    RunnerConfig,
)
from dynamo_tpu.engine.pages import WindowPool
from dynamo_tpu.models import get_config
from dynamo_tpu.models.config import cache_plan, cut_config
from dynamo_tpu.parallel import MeshConfig, make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VS_REFERENCE = 2e-3
SAME_PROGRAM = 1e-5
CONFIG = dataclasses.replace(get_config("tiny-cohere2-test"), dtype="float32")
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "command-a-plus-ep8.json")) as _f:
    # the five controls of the architecture's own, as the cell's file has
    # them (tests/bench/test_bench_command_a_plus.py holds their names)
    CONTROLS = json.load(_f)["check"]["further_controls"]


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "cohere2_reference",
        os.path.join(ROOT, "benchmarks", "references", "cohere2_moe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference_config(c, weight_seed=0) -> dict:
    """The configuration-file keys the reference reads, for a preset (or
    a cut of one: the experts held, the vocabulary rows, the blocks)."""
    kinds = {"W": "sliding_attention", "*": "full_attention"}
    lo, hi = c.held_experts
    return {
        "dtype": c.dtype, "weight_seed": weight_seed,
        "hidden_size": c.hidden, "head_dim": c.head_dim,
        "num_attention_heads": c.n_q_heads,
        "num_key_value_heads": c.n_kv_heads,
        "layer_types": [kinds[k] for k in c.layer_pattern[::2]],
        "sliding_window": c.sliding_window, "layer_norm_eps": c.rms_eps,
        "num_experts": hi - lo, "num_experts_published": c.n_experts,
        "experts_held": [lo, hi],
        "num_experts_per_tok": c.n_experts_active,
        "num_shared_experts": c.n_shared_experts,
        "intermediate_size": c.expert_mlp_hidden,
        "norm_topk_prob": c.moe_norm_topk, "vocab_size": c.vocab_size,
        "rope_theta": c.rope_theta, "tie_word_embeddings": True,
        "use_parallel_block": True, "logit_scale": 1,
        "shared_expert_combination_strategy": "average",
    }


def make_runner(config=CONFIG, buckets=(16, 32), window_pages=16,
                num_pages=64):
    return ModelRunner(
        config,
        RunnerConfig(page_size=PAGE, num_pages=num_pages, max_batch=SLOTS,
                     max_pages_per_seq=WIDTH, prefill_buckets=buckets,
                     window_pages=window_pages),
        make_mesh(MeshConfig()), seed=0)


@pytest.fixture(scope="module")
def runner():
    return make_runner()


@pytest.fixture(scope="module")
def reference():
    return load_reference()


def reference_logits(reference, config, prompt, served, lower=None):
    return reference.logits_for(
        [{"prompt": list(map(int, prompt)),
          "served": list(map(int, served))}],
        reference_config(config), 256, lower)[0]


# -- the served path against the reference ------------------------------------


def test_one_prefill_launch_gives_the_references_first_logits(runner,
                                                              reference):
    """A 29-token prompt in ONE launch (inside the window: no mask bites,
    so this holds the block, the norm, the pair rope and the experts
    alone), then two steps."""
    prompt = prompt_of(29, seed=3)
    row = Row(runner, WindowPool(16, PAGE, WINDOW), 0, prompt)
    first = row.prefill([29])
    got = [row.decode() for _ in range(2)]
    want = reference_logits(reference, CONFIG, prompt,
                            row.tokens[len(prompt):])
    assert want.std() > 0.5  # a spread of ~1: the tolerance means something
    assert first == int(want[0].argmax())
    np.testing.assert_allclose(np.stack(got), want[1:], atol=VS_REFERENCE)


def test_chunks_that_straddle_the_window_then_decode_past_twice_the_window(
        runner, reference):
    """A 119-token prompt in chunks of 32 (the second chunk's queries see
    keys of the first through the window's lower edge), then 2 x window +
    8 = 72 decode steps through both page groups: every step's logits
    against the reference's one full forward; the window group never
    holds more than its bound, and what fell behind went back. Each of
    the architecture's own controls FAILS the same comparison: a program
    that leaves the mechanism out could not pass it."""
    pool = WindowPool(16, PAGE, WINDOW)
    prompt = prompt_of(119)
    row = Row(runner, pool, 1, prompt)
    first = row.prefill([32, 32, 32, 23])
    got = np.stack([row.decode() for _ in range(2 * WINDOW + 8)])
    served = row.tokens[len(prompt):]
    want = reference_logits(reference, CONFIG, prompt, served)
    assert want.std() > 0.5
    assert first == int(want[0].argmax())
    np.testing.assert_allclose(got, want[1:], atol=VS_REFERENCE)
    assert max(row.held[:4]) <= pool.bound(32)
    assert max(row.held[4:]) <= pool.bound(1)
    assert pool.freed_behind["prefill"] > 0 and pool.freed_behind["decode"] > 0
    for name, lower in CONTROLS.items():
        moved = reference_logits(reference, CONFIG, prompt, served, lower)
        assert np.abs(got - moved[1:]).max() > 50 * VS_REFERENCE, name
    pool.release(row.lease)
    assert pool.free_count() == 15 and pool.unreserved() == 15


def test_rows_of_unequal_lengths_in_one_launch_and_one_step(runner,
                                                            reference):
    """Three rows prefilled in ONE batched launch at 9, 31 and 20 tokens
    (padding behind each), then decoded TOGETHER for 40 steps while their
    contexts pass the window at different steps: each row's logits are
    those of the row alone in the reference."""
    pool = WindowPool(16, PAGE, WINDOW)
    prompts = [prompt_of(n, seed=20 + n) for n in (9, 31, 20)]
    leases = [pool.reserve(pool.bound(1)) for _ in prompts]
    rows = []
    for slot, (prompt, lease) in enumerate(zip(prompts, leases)):
        assert pool.advance(lease, 0, len(prompt) - 1, "prefill")
        rows.append(PrefillRow(
            np.asarray(prompt, np.int32), 0, table_for(slot), len(prompt),
            GREEDY, slot=slot, window=(list(lease.pages), 0)))
    firsts = [int(t) for t in runner.prefill_chunk_batch(rows)]
    tokens = [list(p) + [t] for p, t in zip(prompts, firsts)]
    got = [[] for _ in prompts]
    for _ in range(40):
        toks, at = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
        tables = np.zeros((SLOTS, WIDTH), np.int32)
        win = np.zeros((SLOTS, runner.window_table_width), np.int32)
        bases, lens = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
        active = np.zeros(SLOTS, bool)
        for s, (seq, lease) in enumerate(zip(tokens, leases)):
            pos = len(seq) - 1
            assert pool.advance(lease, max(0, pos - WINDOW + 1), pos,
                                "decode")
            toks[s], at[s], lens[s], active[s] = seq[-1], pos, pos + 1, True
            tables[s], bases[s] = table_for(s), lease.first * PAGE
            win[s, :len(lease.pages)] = lease.pages
        runner.decode(
            toks, at, (tables, win, bases), lens, active,
            np.zeros(SLOTS, np.float32), np.ones(SLOTS, np.float32),
            np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.uint32),
            want_logits=True)
        for s, seq in enumerate(tokens):
            logits = np.asarray(runner.last_decode_logits[s])
            got[s].append(logits)
            seq.append(int(logits.argmax()))
    for prompt, seq, first, rows_got in zip(prompts, tokens, firsts, got):
        want = reference_logits(reference, CONFIG, prompt,
                                seq[len(prompt):])
        assert first == int(want[0].argmax())
        np.testing.assert_allclose(np.stack(rows_got), want[1:],
                                   atol=VS_REFERENCE)


def test_a_chips_share_serves_what_the_reference_gives_the_same_share(
        reference):
    """The cell's cut at the tiny size: one period (4 blocks), experts
    2:6 of 8, the leading 256 of 512 vocabulary rows. Tokens routed to an
    absent expert get nothing from it, in the program and in the
    reference alike; and that is another model than the uncut one."""
    cut = cut_config(CONFIG, layers=4, experts="2:6", vocab_rows=256)
    assert cut.layer_pattern == "WEWEWE*E" and cut.held_experts == (2, 6)
    share = make_runner(cut)
    prompt = np.random.default_rng(8).integers(0, 256, 70).tolist()
    row = Row(share, WindowPool(16, PAGE, WINDOW), 2, prompt)
    first = row.prefill([32, 32, 6])
    got = np.stack([row.decode() for _ in range(20)])
    assert got.shape == (20, 256)
    served = row.tokens[len(prompt):]
    want = reference_logits(reference, cut, prompt, served)
    assert first == int(want[0].argmax())
    np.testing.assert_allclose(got, want[1:], atol=VS_REFERENCE)
    whole = dataclasses.replace(cut, experts_held=None)
    other = reference_logits(reference, whole, prompt, served)
    assert np.abs(got - other[1:]).max() > 50 * VS_REFERENCE


def test_a_preempted_request_resumes_by_recomputation(runner):
    """A request preempted after 9 tokens (its slot and both groups'
    pages given to someone else) comes back as prompt + generated,
    prefilled from nothing in another slot in chunks that cross the
    window, and continues on the logits it would have had."""
    pool = WindowPool(16, PAGE, WINDOW)
    prompt = prompt_of(50, seed=31)
    row = Row(runner, pool, 3, prompt)
    row.prefill([32, 18])
    for _ in range(8):
        row.decode()
    generated = row.tokens[len(prompt):]
    uninterrupted = row.decode()
    pool.release(row.lease)
    Row(runner, pool, 3, prompt_of(40, seed=32)).prefill([32, 8])
    replay = Row(runner, pool, 0, prompt + generated[:-1])
    assert replay.prefill([32, 26]) == generated[-1]
    np.testing.assert_allclose(replay.decode(), uninterrupted,
                               atol=SAME_PROGRAM)


def test_a_bfloat16_norm_fails_the_comparison(reference, monkeypatch):
    """The LayerNorm is stated in float32. With its input rounded to
    bfloat16 first (what a bf16 norm would read) and nothing else
    changed, the float32 program is OUTSIDE the tolerance it is held
    to."""
    from dynamo_tpu.models import hybrid

    sound = hybrid.layer_norm

    def rounded(x, weight, eps):
        return sound(x.astype(jnp.bfloat16).astype(x.dtype), weight, eps)

    monkeypatch.setattr(hybrid, "layer_norm", rounded)
    prompt = prompt_of(40, seed=6)
    row = Row(make_runner(), WindowPool(16, PAGE, WINDOW), 0, prompt)
    row.prefill([32, 8])
    got = np.stack([row.decode() for _ in range(6)])
    want = reference_logits(reference, CONFIG, prompt,
                            row.tokens[len(prompt):])
    assert np.abs(got - want[1:]).max() > 10 * VS_REFERENCE


# -- the scheduler ---------------------------------------------------------------


def test_the_scheduler_serves_it_through_both_page_groups(reference):
    """Five requests of two to five windows over four slots and a window
    group of 22 pages: chunked and batched prefill, the fused block, a
    slot reused, pages freed behind while rows decode. Every stream is
    the reference's greedy continuation; no prefix hit is taken though a
    prompt comes twice; both free lists end whole."""
    stored = []
    sched = InferenceScheduler(make_runner(window_pages=23),
                               on_stored=lambda h, p: stored.append(h))
    sched.decode_block = 4
    sched._win_lookahead = 4 * max(1, sched.decode_pipeline)
    prompts = [prompt_of(n, seed=60 + i)
               for i, n in enumerate((150, 70, 97, 41))]
    prompts.append(prompts[0])
    collectors = [Collect() for _ in prompts]
    sched.start()
    try:
        for p, c in zip(prompts, collectors):
            sched.submit(request(p, 40), c)
        wait_for(collectors)
    finally:
        sched.stop()
    pool = sched.win_pool
    assert [c.finish for c in collectors] == ["length"] * 5
    assert stored == [] and sched.pool.cached_count() == 0
    assert collectors[4].tokens() == collectors[0].tokens()
    for p, c in zip(prompts[:4], collectors):
        want = reference_logits(reference, CONFIG, p, c.tokens())
        gap = want.max(-1) - want[np.arange(40), c.tokens()]
        assert gap.max() < VS_REFERENCE
    assert pool.freed_behind["decode"] > 0 and pool.freed_behind["prefill"] > 0
    assert pool.free_count() == 22 and pool.unreserved() == 22
    assert sched.pool.free_count() == 63
    assert sched.stats.window_reserved_page_ms > 0
    # the expert counters count for this model too
    assert sched.runner.moe_stats is not None


# -- the share adds up -----------------------------------------------------------


def expert_layer(config, seed=4):
    from dynamo_tpu.models.hybrid import init_hybrid_layer

    idx = config.layer_pattern.index("E")
    return init_hybrid_layer(jax.random.PRNGKey(seed), config, idx)


def test_the_eight_shares_and_the_shared_experts_once_are_the_uncut_layer(
        reference):
    """At the tiny size the 8 published experts are eight shares of one:
    the routed part each share computes (its output less the shared
    experts', which every chip computes alike) summed over the shares,
    plus the shared experts' averaged output ONCE, is the whole layer, in
    the program; and that is the uncut REFERENCE's expert layer on the
    same weights."""
    from dynamo_tpu.models.hybrid import moe_mixer

    x = jax.random.normal(jax.random.PRNGKey(9), (2, 11, CONFIG.hidden))
    valid = jnp.ones((2, 11), bool)
    whole = expert_layer(CONFIG)
    assert "norm" not in whole  # a parallel block's second mixer has none
    out, stats = moe_mixer(x, whole, CONFIG, valid, "xla")
    assert int(stats[:8].sum()) == 2 * 11 * 2 and int(stats[8]) == 0
    shared = (jnp.einsum(
        "btm,mh->bth",
        jax.nn.silu(x @ whole["s_up"][:, :96]) * (x @ whole["s_up"][:, 96:]),
        whole["s_down"]) / 2)
    total = np.asarray(shared)
    for e in range(8):
        cut = cut_config(CONFIG, experts=f"{e}:{e + 1}")
        part, _ = moe_mixer(x, expert_layer(cut), cut, valid, "xla")
        total = total + (np.asarray(part) - np.asarray(shared))
    np.testing.assert_allclose(total, np.asarray(out), atol=1e-4)
    idx = CONFIG.layer_pattern.index("E")
    cfg = reference_config(CONFIG)
    w = reference.expert_weights(jax.random.PRNGKey(4), cfg, idx)
    with jax.default_matmul_precision("highest"):
        want = reference.moe_mixer(x.reshape(22, -1), w, cfg, {})
    np.testing.assert_allclose(np.asarray(out).reshape(22, -1),
                               np.asarray(want), atol=1e-4)


def test_the_vocabulary_slice_gives_the_uncut_logits_first_rows(reference):
    """The leading 256 rows of the tied matrix are the whole draw's first
    256: with prompt ids from the slice, the slice's logits are the
    uncut model's first 256 columns."""
    prompt = np.random.default_rng(2).integers(0, 256, 40).tolist()
    served = np.random.default_rng(3).integers(0, 256, 5).tolist()
    whole = reference_logits(reference, CONFIG, prompt, served)
    part = reference_logits(reference, cut_config(CONFIG, vocab_rows=256),
                            prompt, served)
    assert whole.shape == (5, 512) and part.shape == (5, 256)
    np.testing.assert_allclose(part, whole[:, :256], atol=1e-5)
    # and the program's embedding likewise
    from dynamo_tpu.models.transformer import init_top_params

    k = jax.random.PRNGKey(0)
    np.testing.assert_array_equal(
        init_top_params(k, k, cut_config(CONFIG, vocab_rows=256))["embed"],
        init_top_params(k, k, CONFIG)["embed"][:256])


# -- parts ---------------------------------------------------------------------


def lowered_prefill(config):
    """`forward_hybrid` of a preset over abstract shapes, lowered: the
    program's text with the scopes' names in its locations."""
    from dynamo_tpu.models.hybrid import forward_hybrid, make_state_cache
    from dynamo_tpu.models.transformer import init_params, make_kv_cache

    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                config))
    kv = jax.eval_shape(lambda: make_kv_cache(config, 16, PAGE))
    state = jax.eval_shape(lambda: make_state_cache(config, 2))
    window = None
    if config.window_kv_layers:
        window = (jax.eval_shape(lambda: make_kv_cache(
            config, 16, PAGE, group="window")),
            jnp.zeros((2, 8), jnp.int32), jnp.zeros((2,), jnp.int32))

    def prefill(params, kv, state, window):
        tokens = jnp.zeros((2, 16), jnp.int32)
        return forward_hybrid(
            params, config, tokens, tokens, kv, state,
            jnp.arange(2), jnp.zeros((2, 8), jnp.int32),
            jnp.full((2,), 16), jnp.ones((2, 16), bool),
            jnp.full((2,), 15), window=window)

    return jax.jit(prefill).lower(params, kv, state, window).as_text(
        debug_info=True)


@pytest.mark.parametrize("preset,norms", [
    # ONE norm a block and the final one: 8 blocks of two mixers
    ("tiny-cohere2-test", 8 + 1),
    # a norm a mixer and the final one (PR 47's rule: what a non-parallel
    # preset traces is what it traced)
    ("tiny-mellum-test", 16 + 1),
    # ... and q and k normed per head in its 2 attention mixers
    ("tiny-lfm2-test", 16 + 1 + 2 * 2),
    # ... and a second norm a mixer, and two latent norms in 5 mixers
    ("tiny-pangu-test", 2 * 10 + 1 + 2 * 5),
])
def test_a_parallel_block_traces_one_norm_and_the_others_what_they_did(
        preset, norms):
    """Count the norms of a prefill program by their `rsqrt` (every norm
    has one and nothing else in these stacks has). The parallel block
    has ONE for its two mixers, under the scope `block_norm` in the
    lowered program's names; a preset that is not parallel has no such
    scope, no mean taken off, and a norm a mixer as it had."""
    text = lowered_prefill(get_config(preset))
    assert text.count("stablehlo.rsqrt") == norms
    parallel = get_config(preset).parallel_block
    assert ("block_norm" in text) == parallel


def test_rope_turns_lane_pairs_and_only_the_window_kind_has_a_table():
    """`apply_rope(interleaved=True)` against the rotation written out
    for one head: lanes (2i, 2i+1) by position x theta^(-2i/d). The
    rotate-half form pairs (i, i + d/2) and gives something else."""
    from dynamo_tpu.models.hybrid import apply_rope, rope_tables

    c = get_config("command-a-plus-05-2026")
    assert rope_tables(c, "*") is None
    inv_freq, factor = rope_tables(c, "W")
    d, theta = 128, 5e4
    np.testing.assert_allclose(
        np.asarray(inv_freq), [theta ** (-2 * i / d) for i in range(d // 2)],
        rtol=1e-5)
    assert factor == 1.0
    x = np.random.default_rng(0).normal(size=(1, 3, 1, d)).astype(np.float32)
    positions = np.asarray([[0, 7, 4099]], np.int32)
    got = np.asarray(apply_rope(jnp.asarray(x), jnp.asarray(positions),
                                (inv_freq, factor), True))
    want = np.zeros_like(x)
    for t, pos in enumerate(positions[0]):
        for i in range(d // 2):
            angle = np.float32(pos) * np.float32(theta ** (-2 * i / d))
            a, b = x[0, t, 0, 2 * i], x[0, t, 0, 2 * i + 1]
            want[0, t, 0, 2 * i] = a * np.cos(angle) - b * np.sin(angle)
            want[0, t, 0, 2 * i + 1] = b * np.cos(angle) + a * np.sin(angle)
    # an angle of 4,099 radians is good to 4e-4 in float32
    np.testing.assert_allclose(got[:, :2], want[:, :2], atol=2e-5)
    np.testing.assert_allclose(got, want, atol=1e-3)
    np.testing.assert_array_equal(got[0, 0], x[0, 0])  # position 0
    halves = np.asarray(apply_rope(jnp.asarray(x), jnp.asarray(positions),
                                   (inv_freq, factor)))
    assert np.abs(halves - want).max() > 0.1
    # mellum keeps a table a kind, and the stacks without rope none
    assert rope_tables(get_config("mellum2-12b-a2.5b"), "*") is not None
    assert rope_tables(get_config("granite-4.0-h-small"), "*") is None


def one_layer_last_output(kind, tokens, reference, lower=None):
    """The LAST position's attention output of one layer of `kind` over
    `tokens`' embeddings, from the reference's mixer."""
    cfg = reference_config(CONFIG)
    keys = reference.model_keys(cfg)
    x = reference.embedding(keys[0], cfg)[jnp.asarray(tokens)]
    w = reference.attention_weights(keys[1], cfg, 0)
    with jax.default_matmul_precision("highest"):
        u = reference.norm(x, cfg["layer_norm_eps"], {})
        return np.asarray(reference.attention_mixer(
            u, w, kind, cfg, lower or {}))[-1]


def test_a_full_layer_cannot_tell_the_order_of_earlier_tokens(runner,
                                                              reference):
    """NoPE: swap two earlier tokens of a prompt and the LAST position's
    output of a single full layer is unchanged (a causal layer with no
    positional term sees a SET of earlier keys), while a window layer's
    changes; under `rope-all` both change. The program agrees: through
    `_qkv` a full layer's keys are the projections themselves."""
    tokens = prompt_of(24, seed=77)
    swapped = list(tokens)
    swapped[5], swapped[11] = swapped[11], swapped[5]
    full = one_layer_last_output("full_attention", tokens, reference)
    np.testing.assert_allclose(
        one_layer_last_output("full_attention", swapped, reference), full,
        atol=1e-5)
    window = one_layer_last_output("sliding_attention", tokens, reference)
    assert np.abs(one_layer_last_output("sliding_attention", swapped,
                                        reference) - window).max() > 1e-2
    roped = one_layer_last_output("full_attention", tokens, reference,
                                  {"rope": "all"})
    assert np.abs(one_layer_last_output(
        "full_attention", swapped, reference,
        {"rope": "all"}) - roped).max() > 1e-2
    # the program's own projections: a full layer's q and k do not move
    # with the position, a window layer's do
    from dynamo_tpu.models.hybrid import _qkv

    h = jax.random.normal(jax.random.PRNGKey(1), (1, 4, CONFIG.hidden))
    for kind, layer in (("*", 6), ("W", 0)):
        lp = runner.params["layers"][layer]
        at = [_qkv(h, lp, CONFIG, kind, jnp.full((1, 4), p, jnp.int32))[1]
              for p in (0, 9)]
        assert (np.abs(np.asarray(at[0]) - np.asarray(at[1])).max()
                > 1e-2) == (kind == "W")


def test_the_layer_norm_takes_the_mean_off_and_the_recipe_gives_it_one():
    """`layer_norm` against numpy; and the seeded residual writers give
    the stream a mean over the lanes (half lane 0's column on every
    column), which a LayerNorm takes off and an RMS norm would not."""
    from dynamo_tpu.models.hybrid import (
        STREAM_MEAN,
        init_hybrid_layer,
        layer_norm,
    )
    from dynamo_tpu.models.transformer import rms_norm

    x = np.random.default_rng(1).normal(1.5, 2.0, (5, 64)).astype(np.float32)
    g = np.random.default_rng(2).normal(1.0, 0.1, 64).astype(np.float32)
    want = (x - x.mean(-1, keepdims=True)) / np.sqrt(
        x.var(-1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(layer_norm(jnp.asarray(x), g, 1e-5), want,
                               atol=1e-5)
    assert np.abs(np.asarray(rms_norm(jnp.asarray(x), g, 1e-5))
                  - want).max() > 0.3
    plain = dataclasses.replace(CONFIG, norm_kind="rms")
    for idx, name in ((0, "wo"), (1, "e_down"), (1, "s_down")):
        with_mean = np.asarray(init_hybrid_layer(
            jax.random.PRNGKey(5), CONFIG, idx)[name])
        without = np.asarray(init_hybrid_layer(
            jax.random.PRNGKey(5), plain, idx)[name])
        np.testing.assert_allclose(
            with_mean, without + STREAM_MEAN * without[..., :1], atol=1e-6)
        # centred over the input axes either way: no common direction
        axes = tuple(range(without.ndim - 1))[-2 if name == "wo" else -1:]
        assert np.abs(with_mean.mean(axis=axes)).max() < 1e-6


def test_the_programs_seeded_weights_are_the_references_recipe(reference):
    """Same seed, same numbers, each from its own code: every leaf of the
    program's random tree against the reference's recipe (the program
    keeps a SwiGLU's gate and up as one matrix, an expert's output-major,
    the four shared experts as one SwiGLU; only a block's first mixer
    has a norm)."""
    from dynamo_tpu.models.hybrid import branch_gain, score_gain
    from dynamo_tpu.models.transformer import init_params

    cut = cut_config(CONFIG, layers=4, experts="2:6", vocab_rows=256)
    cfg = reference_config(cut)
    params = init_params(jax.random.PRNGKey(0), cut)
    keys = reference.model_keys(cfg)
    assert len(keys) == cut.n_layers + 2 == 10
    assert set(params) == {"embed", "final_norm", "layers"}
    np.testing.assert_array_equal(reference.embedding(keys[0], cfg),
                                  params["embed"])
    assert score_gain(cut) == reference.SHARP_QK_GAIN == 1.5
    for i, lp in enumerate(params["layers"]):
        assert branch_gain(cut, i) == pytest.approx(
            reference.branch_gain(i)) == pytest.approx(1.23 ** i)
        if i % 2 == 0:
            want = reference.attention_weights(keys[i + 1], cfg, i)
            assert set(lp) == {"norm", "wq", "wk", "wv", "wo"}
            for name in ("wq", "wk", "wv", "wo"):
                np.testing.assert_array_equal(want[name], lp[name])
        else:
            want = reference.expert_weights(keys[i + 1], cfg, i)
            assert set(lp) == {"router", "e_up", "e_down", "s_up", "s_down"}
            assert lp["router"].shape == (64, 8)  # all published experts
            np.testing.assert_array_equal(want["router"], lp["router"])
            np.testing.assert_array_equal(want["down"], lp["e_down"])
            fused = np.concatenate([np.swapaxes(want["gate"], 1, 2),
                                    np.swapaxes(want["up"], 1, 2)], axis=1)
            np.testing.assert_array_equal(fused, lp["e_up"])
            assert lp["e_up"].shape == (4, 2 * 48, 64)  # the four held
            np.testing.assert_array_equal(
                np.concatenate([want["s_gate"], want["s_up"]], axis=1),
                lp["s_up"])
            np.testing.assert_array_equal(want["s_down"], lp["s_down"])
    # rounded to bf16 as `astype` rounds
    raw = jax.random.normal(jax.random.PRNGKey(9), (64, 64))
    np.testing.assert_array_equal(
        reference._rounded(raw, "bfloat16"),
        raw.astype(jnp.bfloat16).astype(jnp.float32))
    # the other tied recipes are where they were
    granite = get_config("granite-4.0-h-small")
    assert branch_gain(granite, 3) == pytest.approx(3.0 / 0.22 * 1.23 ** 3)
    assert score_gain(get_config("mellum2-12b-a2.5b")) == 1.0


# -- the preset, its cut, its cache and what it is refused ----------------------


def test_the_preset_is_the_published_model_and_cuts_to_the_cells_share():
    c = get_config("command-a-plus-05-2026")
    assert (c.n_layers, c.mixers_per_layer, c.layer_pattern) == (
        64, 2, "WEWEWE*E" * 8)
    assert (c.parallel_block, c.norm_kind, c.rope_kinds,
            c.rope_interleaved, c.shared_expert_mean) == (
        True, "layer", "W", True, True)
    assert (c.hidden, c.n_q_heads, c.n_kv_heads, c.head_dim) == (
        4096, 128, 8, 128)
    assert (c.n_experts, c.n_experts_active, c.expert_mlp_hidden,
            c.n_shared_experts) == (128, 8, 4096, 4)
    assert (c.moe_scoring, c.moe_selection_bias, c.moe_norm_topk,
            c.moe_routed_scale) == ("sigmoid", False, True, 1.0)
    assert c.tie_embeddings and c.sliding_window == 4096
    assert [c.layer_sliding_window(i) for i in range(8)] == [
        4096, 0, 4096, 0, 4096, 0, 0, 0]
    assert len(c.kv_layers) == 8 and len(c.window_kv_layers) == 24
    cut = cut_config(c, layers=4, experts="0:16", vocab_rows=32768)
    assert cut.layer_pattern == "WEWEWE*E" and cut.n_layers == 8
    assert cut.held_experts == (0, 16) and cut.vocab_size == 32768
    assert cut.n_experts == 128 and cut.hidden == 4096  # no width changes
    assert len(cut.kv_layers) == 1 and len(cut.window_kv_layers) == 3
    for bad in (dict(layers=33), dict(experts="120:129"),
                dict(vocab_rows=0)):
        with pytest.raises(ValueError):
            cut_config(c, **bad)
    # every other preset keeps the defaults of the four new facts
    for name in ("mellum2-12b-a2.5b", "granite-4.0-h-small", "lfm2-8b-a1b",
                 "openpangu-ultra-moe-718b", "nemotron3-nano-30b-a3b"):
        other = get_config(name)
        assert (other.parallel_block, other.norm_kind, other.rope_kinds,
                other.rope_interleaved, other.shared_expert_mean) == (
            False, "rms", "", False, False)


def test_the_cache_plan_is_two_page_groups_and_the_one_table_refuses():
    """PR 47's seam: nothing under `engine/` asks which family this is.
    The plan has a window group, so prefix hits, page moves, speculation
    and an int8 pool are refused by flag with the reason, as for mellum."""
    from dynamo_tpu.engine.worker import recurrent_state_refusals

    c = get_config("command-a-plus-05-2026")
    plan = cache_plan(c)
    assert (plan.groups, plan.state, plan.launch_bound) == (
        ("full", "window"), False, "always")
    for trait in ("reuse_prefix", "move_pages", "score_positions", "shard",
                  "int8_pool", "quantized_weights"):
        assert c.name in getattr(plan, trait), trait
    assert "last 4096 positions" in plan.reuse_prefix
    recurrent_state_refusals(c)
    for flags, said in (({"mode": "prefill"}, "two page groups"),
                        ({"kvbm": True}, "--kvbm-host-blocks"),
                        ({"spec": True}, "multi-position"),
                        ({"kv_dtype": "int8"}, "--kv-dtype int8"),
                        ({"weight_dtype": "int4"}, "has expert matrices"),
                        ({"devices": 4}, "--tp/--sp/--dp")):
        with pytest.raises(ValueError, match=said) as refused:
            recurrent_state_refusals(c, **flags)
        assert c.name in str(refused.value)
    with pytest.raises(ValueError, match="--window-pages"):
        make_runner(window_pages=0)
    runner = make_runner()
    (full, window), _ = runner.cache
    assert full.shape[0] == 2 and window.shape[0] == 6
    assert runner.window_table_width == 8
    with pytest.raises(RuntimeError, match="two page groups"):
        runner.gather_pages_device(np.asarray([1, 2]))
    assert not runner.supports_spec


def test_the_runner_draws_the_same_tree_a_layer_kind_a_program():
    from dynamo_tpu.models.transformer import init_params

    runner = make_runner()
    want = init_params(jax.random.PRNGKey(0), CONFIG)
    assert (jax.tree.structure(runner.params)
            == jax.tree.structure(want))
    # a compiled draw rounds a product's last bit otherwise than an eager
    # one (1e-7 relative); a gain off by a mixer would read 23%
    for got, leaf in zip(jax.tree.leaves(runner.params),
                         jax.tree.leaves(want)):
        scale = float(np.abs(np.asarray(leaf)).max())
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(leaf) / scale, atol=1e-6)
