"""phi4flash's stack (Phi-4-mini-flash-reasoning, SambaY: Mamba-1 mixers,
differential attention on a window and, ONE layer, on everything, gated
memory units fed by one scan, cross-attention onto that one layer's
pages; LayerNorms with a bias, no positional term, a tied head) on the
served path, against its plain reference
(benchmarks/references/phi4flash.py, which imports nothing of the
program), at a tiny size on the CPU with seeded weights: 12 blocks by the
published rule, window 32, pages of 16.

Tolerances, on logits whose spread is ~1.0:

  VS_REFERENCE 2e-4   the float32 preset against the float32 reference.
      They differ by the order of float32 sums only (the program's paged
      attention in the wide-row form against the reference's two
      softmaxes): 1.4e-5 measured over 30 decode steps behind a prompt
      in four launches. Anything of the mathematics left out reads units
      (every architecture control below; matmul inputs in fp8 0.9), and
      the scan's state kept in bfloat16 where float32 is stated reads
      0.0116, fifty-eight times the tolerance.
  SAME_PROGRAM 1e-5   the same program on the same values by another
      route (a freed slot, a row beside other rows).
  SAME_VALUES 2e-4    two programs of the same mathematics (one launch
      against three, the tail on one position against all): float32 sums
      in another order.
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
    PAGE,
    SLOTS,
    WIDTH,
    WINDOW,
    Collect,
    Row,
    prompt_of,
    request,
    wait_for,
)

from dynamo_tpu.engine import InferenceScheduler, ModelRunner, RunnerConfig
from dynamo_tpu.engine.pages import WindowPool
from dynamo_tpu.models import get_config, hybrid
from dynamo_tpu.models.config import cache_plan
from dynamo_tpu.models.transformer import init_params, make_kv_cache
from dynamo_tpu.parallel import MeshConfig, make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VS_REFERENCE = 2e-4
SAME_PROGRAM = 1e-5
SAME_VALUES = 5e-5
CONFIG = dataclasses.replace(get_config("tiny-phi4flash-test"),
                             dtype="float32")
assert CONFIG.sliding_window == WINDOW
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "phi4-mini-flash.json")) as _f:
    _FILE = json.load(_f)
    # the controls as the cell's file has them
    # (tests/bench/test_bench_phi4flash.py holds their names)
    CONTROLS = {**_FILE["check"]["controls"],
                **_FILE["check"]["further_controls"]}


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "phi4flash_reference",
        os.path.join(ROOT, "benchmarks", "references", "phi4flash.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference_config(c, weight_seed=0) -> dict:
    """The configuration-file keys the reference reads, for a preset."""
    return {
        "dtype": c.dtype, "weight_seed": weight_seed,
        "hidden_size": c.hidden, "num_attention_heads": c.n_q_heads,
        "num_key_value_heads": c.n_kv_heads,
        "num_hidden_layers": c.n_layers // c.mixers_per_layer,
        "mb_per_layer": 2, "intermediate_size": c.mlp_hidden,
        "sliding_window": c.sliding_window, "layer_norm_eps": c.rms_eps,
        "vocab_size": c.vocab_size, "tie_word_embeddings": True,
        "mlp_bias": False, "mamba_expand": c.mamba_inner // c.hidden,
        "mamba_d_state": c.ssm_state, "mamba_dt_rank": c.mamba_dt_rank,
        "mamba_d_conv": c.conv_kernel,
    }


def make_runner(config=CONFIG, buckets=(16, 32, 128), window_pages=16,
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


@pytest.fixture(scope="module")
def served(runner):
    """A 119-token prompt in four launches (state carried three times,
    nearly four windows), then 30 decode steps: (prompt, the row)."""
    prompt = prompt_of(119)
    row = Row(runner, WindowPool(16, PAGE, WINDOW), 1, prompt)
    row.first = row.prefill([32, 32, 32, 23])
    row.logits = np.stack([row.decode() for _ in range(30)])
    return prompt, row


def reference_logits(reference, config, prompt, tokens, lower=None):
    return reference.logits_for(
        [{"prompt": list(map(int, prompt)),
          "served": list(map(int, tokens))}],
        reference_config(config), 256, lower)[0]


# -- the served path against the reference ------------------------------------


def test_chunked_prefill_then_decode_equals_the_references_forward(
        served, reference):
    """Prefill through both page groups and the slot's state, then decode
    steps, against the reference's ONE full forward in which every
    position passes all 12 blocks."""
    prompt, row = served
    want = reference_logits(reference, CONFIG, prompt,
                            row.tokens[len(prompt):])
    assert 0.5 < want.std() < 2.0  # a spread of ~1: the tolerance bites
    assert row.first == int(want[0].argmax())
    np.testing.assert_allclose(row.logits, want[1:], atol=VS_REFERENCE)


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_a_control_of_the_reference_reads_far_outside_the_tolerance(
        served, reference, name):
    """Each control of the cell's file, at the small size: the stated
    precision a step down (act-fp8, state-bf16) and the four that hold
    the architecture (lambda at 0, the memory from two blocks early,
    cross-attention held to a window, the window doubled). A program that
    did any of them could not pass the comparison above."""
    prompt, row = served
    moved = reference_logits(reference, CONFIG, prompt,
                             row.tokens[len(prompt):], CONTROLS[name])
    assert np.abs(row.logits - moved[1:]).max() > 40 * VS_REFERENCE


def test_three_launches_with_carried_state_equal_one_launch(runner, served):
    prompt, row = served
    whole = Row(runner, WindowPool(16, PAGE, WINDOW), 2, prompt[:96])
    parts = Row(runner, WindowPool(16, PAGE, WINDOW), 3, prompt[:96])
    assert whole.prefill([96]) == parts.prefill([32, 32, 32])
    np.testing.assert_allclose(whole.decode(), parts.decode(),
                               atol=SAME_VALUES)


def test_a_freed_and_reused_slot_starts_from_zero_state(runner, served):
    """Slot 1 held the long request's state; a new prompt in it reads
    what it reads in a slot nothing has used."""
    prompt = prompt_of(40, seed=9)
    used = Row(runner, WindowPool(16, PAGE, WINDOW), 1, prompt)
    fresh = Row(make_runner(), WindowPool(16, PAGE, WINDOW), 1, prompt)
    assert used.prefill([32, 8]) == fresh.prefill([32, 8])
    np.testing.assert_allclose(used.decode(), fresh.decode(),
                               atol=SAME_PROGRAM)


# -- the forward's own forms --------------------------------------------------


def _forward(params, tokens, all_logits, config=CONFIG):
    """One row of `tokens` from position 0 through `forward_hybrid`."""
    t = len(tokens)
    pools = (make_kv_cache(config, 16, PAGE),
             make_kv_cache(config, 16, PAGE, group="window"))
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]
    positions = jnp.arange(t, dtype=jnp.int32)[None]
    out = hybrid.forward_hybrid(
        params, config, jnp.asarray(tokens, jnp.int32)[None], positions,
        pools[0], hybrid.make_state_cache(config, 2), jnp.zeros((1,),
                                                                jnp.int32),
        table, jnp.asarray([t], jnp.int32), jnp.ones((1, t), bool),
        jnp.asarray([t - 1], jnp.int32), all_logits=all_logits,
        window=(pools[1], table, jnp.zeros((1,), jnp.int32)))
    return out


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CONFIG)


def test_the_last_position_cut_equals_all_logits_at_that_position(params):
    """Blocks behind the shared layer cache nothing and carry nothing in
    time: run on the last position alone (one query a row through the
    decode form of attention) they give the logits that running them on
    every position gives there."""
    tokens = prompt_of(50, seed=4)
    (full_pool, _), _, last, _ = _forward(params, tokens, False)
    _, _, every, _ = _forward(params, tokens, True)
    assert last.shape == (1, CONFIG.vocab_size)
    np.testing.assert_allclose(last[0], every[0, -1], atol=SAME_VALUES)
    # and the cross-attention mixers wrote nothing: the full group has
    # ONE cache layer, whatever reads it
    assert full_pool.shape[0] == 1


# 12 query heads over 12 kv heads = 6 KV pairs: two rows of three pairs,
# a row's six query heads padded to eight, as the published 40 over 20
# lie in two rows of five pairs with 20 query heads padded to 32
WIDE = dataclasses.replace(CONFIG, hidden=768, n_q_heads=12, n_kv_heads=12)


@pytest.mark.parametrize("config", [CONFIG, WIDE], ids=["one-row", "rows"])
def test_the_wide_row_form_equals_two_softmaxes_and_a_subtraction(
        config, reference):
    """`_qkv`'s queries, each as wide as a row of KV pairs with its
    values in its own kv head's lanes, over the rows as the pool holds
    them, scored as plain grouped-query attention at 1/sqrt(head_dim),
    then `_attention_out`'s epilogue, against the reference's two
    softmaxes as written, on the weights of one window layer."""
    t, layer = 48, config.layer_pattern.index("W")
    a = jax.random.normal(jax.random.PRNGKey(5), (t, config.hidden))
    cfg = reference_config(config)
    keys = reference.model_keys(cfg)
    lp = hybrid.init_hybrid_layer(keys[layer + 1], config, layer)
    w = reference.attention_weights(keys[layer + 1], cfg,
                                    reference.branch_gains(cfg)[layer],
                                    False)
    with jax.default_matmul_precision("highest"):
        k, v = reference.keys_values(a, w, cfg, {})
        want = reference.diff_attention(
            a, w, k, v, reference.lambda_init(layer // 2),
            config.sliding_window, cfg, {})
        q, kp, vp = hybrid._qkv(a[None], lp, config, "W",
                                jnp.arange(t)[None])
        qh, kh, hd = config.attn_geometry
        assert q.shape[2:] == (qh, hd) and kp.shape[2:] == (kh, hd)
        assert (kh, hd) == (config.kv_cache_heads, config.kv_cache_head_dim)
        scores = jnp.einsum("tkgd,skd->kgts",
                            q[0].reshape(t, kh, qh // kh, hd), kp[0])
        scores = scores * hybrid.attention_scale(config)["sm_scale"]
        at = jnp.arange(t)
        seen = ((at[None, :] <= at[:, None])
                & (at[None, :] > at[:, None] - config.sliding_window))
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        attn = jnp.einsum("kgts,skd->tkgd", probs, vp[0]).reshape(t, qh, hd)
        got = hybrid._attention_out(attn[None], lp, config, layer)[0]
    assert float(jnp.std(want)) > 0.1
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_published_heads_lie_in_two_rows_of_five_pairs():
    config = get_config("phi4-mini-flash-reasoning")
    assert config.diff_rows == 2
    assert config.attn_geometry == (64, 2, 640)
    assert (config.kv_cache_heads, config.kv_cache_head_dim) == (2, 640)
    assert hybrid._diff_layout(config) == (2, 5, 4, 20, 32)
    # the same bytes a token a layer as 20 heads of 64
    assert 2 * 640 == config.n_kv_heads * config.head_dim


@pytest.mark.parametrize("keys", [WINDOW - 1, WINDOW, WINDOW + 1])
def test_a_window_edge(runner, reference, keys):
    """A decode step that sees `keys` keys: one under the window (no
    mask), exactly the window, and one over (the oldest key is cut)."""
    prompt = prompt_of(keys - 1, seed=20 + keys)
    row = Row(runner, WindowPool(16, PAGE, WINDOW), 0, prompt)
    row.prefill([keys - 1])
    got = row.decode()  # position keys - 1: sees `keys` keys
    want = reference_logits(reference, CONFIG, prompt,
                            row.tokens[len(prompt):])
    np.testing.assert_allclose(got, want[1], atol=VS_REFERENCE)


def _entry_of(layer):
    """(index into `params["layers"]`, repeat or None) of mixer `layer`:
    a rolled section's mixers lie stacked in their period's entries."""
    for e, (first, repeats, stride) in enumerate(CONFIG.layer_entries):
        if repeats == 1 and first == layer:
            return e, None
        if repeats > 1 and layer >= first and (layer - first) % stride == 0 \
                and (layer - first) // stride < repeats:
            return e, (layer - first) // stride
    raise AssertionError(layer)


def _changed(params, layer, change):
    """`params` with `change(name, leaf)` applied to mixer `layer`'s
    leaves (its slice of a stacked entry's)."""
    e, r = _entry_of(layer)
    layers = list(params["layers"])
    layers[e] = {
        k: (change(k, v) if r is None
            else v.at[r].set(change(k, v[r])))
        for k, v in layers[e].items()}
    return {**params, "layers": layers}


def _zeroed(params, layer, names):
    return _changed(params, layer, lambda k, v: (
        jnp.zeros_like(v) if k in names else v))


def test_the_memory_is_the_memory_layers_scan_output_before_the_gate(params):
    """With W_x and D of the memory layer zeroed its scan reads y = 0, so
    every gated memory unit writes exactly 0: the logits are those of a
    model whose memory units' W_2 are zero. Zeroed in the Mamba layer two
    blocks EARLIER the units still write; and with the memory layer's
    GATE zeroed (z = 0: its own output is 0, its y is not) they still
    write: m is y before the gate."""
    tokens = prompt_of(40, seed=6)
    memory = CONFIG.memory_layer
    assert memory == CONFIG.n_layers // 2  # block n/2's token mixer
    early = CONFIG.layer_pattern.rfind("S", 0, memory)
    silent = params
    for layer, kind in enumerate(CONFIG.layer_pattern):
        if kind == "G":
            silent = _zeroed(silent, layer, ("g_out",))
    inner = CONFIG.mamba_inner

    def logits(p):
        return np.asarray(_forward(p, tokens, False)[2][0])

    no_scan = ("x_proj", "d_skip")
    np.testing.assert_allclose(
        logits(_zeroed(params, memory, no_scan)),
        logits(_zeroed(silent, memory, no_scan)), atol=SAME_PROGRAM)
    assert np.abs(logits(_zeroed(params, early, no_scan))
                  - logits(_zeroed(silent, early, no_scan))).max() > 0.1

    def with_gate_off(p):
        return _changed(p, memory, lambda k, v: (
            v.at[:, inner:].set(0.0) if k == "in_proj" else v))

    assert np.abs(logits(with_gate_off(params))
                  - logits(with_gate_off(silent))).max() > 0.1


# -- the cache plan, the parameter count ---------------------------------------


def test_cross_attention_owns_no_pages_and_the_full_group_has_one_layer(
        runner):
    plan = cache_plan(CONFIG)
    assert plan.groups == ("full", "window") and plan.state
    n_cross = CONFIG.layer_pattern.count("X")
    n_window = CONFIG.layer_pattern.count("W")
    assert plan.group_layers == (1, n_window)
    assert plan.group_readers == (1 + n_cross, n_window)
    (full, window), state = runner.cache
    assert full.shape[0] == 1 and window.shape[0] == n_window
    # a KV pair side by side is 128 lanes of a row of the pool
    assert full.shape[4:] == (1, 2 * CONFIG.head_dim)
    # a rolled section's three Mamba-1 layers stacked, then the memory's
    assert [a.shape for a in state["ssm"]] == [
        (3, SLOTS, CONFIG.ssm_state, CONFIG.mamba_inner),
        (SLOTS, CONFIG.ssm_state, CONFIG.mamba_inner)]
    assert state["ssm"][0].dtype == jnp.float32
    for layer in CONFIG.shared_kv_readers:
        assert CONFIG.shared_kv_layer(layer) == 0
        assert not {"wk", "wv", "bk", "bv"} & set(
            hybrid.hybrid_layer_axes(CONFIG, layer))


def test_the_parameter_count_at_the_published_widths():
    """3,852 M (ISSUE 52's table, reckoned anew), by the shapes the
    program would allocate."""
    config = get_config("phi4-mini-flash-reasoning")
    assert config.layer_pattern == "SDWD" * 8 + "SD*D" + "GDXD" * 7
    shapes = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), config))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert abs(count - 3852e6) / 3852e6 < 1e-3
    plan = cache_plan(config)
    assert plan.group_layers == (1, 8) and plan.group_readers == (8, 8)
    assert config.memory_layer == 32 and config.cross_decoder_start == 36
    assert hybrid.state_slot_bytes(config) == 9 * (327680 + 30720)


# -- the scheduler ---------------------------------------------------------------


def test_the_scheduler_serves_it_through_page_groups_and_slot_state(
        reference):
    """Five requests of two to five windows over four slots: chunked and
    batched prefill, the fused block, a slot reused, window pages freed
    behind while rows decode. Every stream is the reference's greedy
    continuation; both free lists end whole; the counters of what this
    stack adds count."""
    sched = InferenceScheduler(make_runner(window_pages=23))
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
    assert [c.finish for c in collectors] == ["length"] * 5
    assert sched.pool.cached_count() == 0
    assert collectors[4].tokens() == collectors[0].tokens()
    for p, c in zip(prompts[:4], collectors):
        want = reference_logits(reference, CONFIG, p, c.tokens())
        gap = want.max(-1) - want[np.arange(40), c.tokens()]
        assert gap.max() < VS_REFERENCE
    assert sched.win_pool.free_count() == 22
    assert sched.pool.free_count() == 63
    stats, runner = sched.stats, sched.runner
    assert stats.state_slot_ms > 0 and stats.window_reserved_page_ms > 0
    # every prompt ends once; the longer ones took earlier chunks too
    assert stats.prefill_rows_last == 5 and stats.prefill_rows_earlier > 0
    reads = runner.page_layer_reads
    n_cross = CONFIG.layer_pattern.count("X")
    assert reads["owner"] > 0
    assert reads["shared"] * (1 + CONFIG.layer_pattern.count("W")) \
        == reads["owner"] * n_cross
    assert runner.ssm_prefill_positions["continued"] > 0
