"""Window and full attention side by side (mellum: three window layers and
a full one a period, each block an attention mixer then SwiGLU experts)
on the served path, against its plain reference
(benchmarks/references/mellum.py, which imports nothing of the program),
at a tiny size on the CPU with seeded weights: two periods, window 32,
pages of 16, 8 experts top-2.

Tolerances, on logits whose spread is ~1.0: the float32 preset agrees
with the float32 reference to 2e-3; the reference with the window
treated as full differs from it by tenths at every position past the
window, which is what the comparison has to catch.
"""

import dataclasses
import importlib.util
import math
import os
import time
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import InferenceScheduler, ModelRunner, RunnerConfig
from dynamo_tpu.engine.pages import WindowPool
from dynamo_tpu.llm.protocols import (
    EngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import get_config
from dynamo_tpu.models.config import cut_config
from dynamo_tpu.parallel import MeshConfig, make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VS_REFERENCE = 2e-3
PAGE, WIDTH, SLOTS, WINDOW = 16, 16, 4, 32
GREEDY = (0.0, 1.0, 0, 0)
CONFIG = dataclasses.replace(get_config("tiny-mellum-test"), dtype="float32")


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "mellum_reference",
        os.path.join(ROOT, "benchmarks", "references", "mellum.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference_config(c, weight_seed=0) -> dict:
    """The configuration-file keys the reference reads, for a preset."""
    kinds = {"W": "sliding_attention", "*": "full_attention"}
    return {
        "dtype": c.dtype, "weight_seed": weight_seed,
        "hidden_size": c.hidden, "head_dim": c.head_dim,
        "num_attention_heads": c.n_q_heads,
        "num_key_value_heads": c.n_kv_heads,
        "layer_types": [kinds[k] for k in c.layer_pattern[::2]],
        "sliding_window": c.sliding_window, "rms_norm_eps": c.rms_eps,
        "num_experts": c.n_experts,
        "num_experts_per_tok": c.n_experts_active,
        "moe_intermediate_size": c.expert_mlp_hidden,
        "norm_topk_prob": c.moe_norm_topk, "vocab_size": c.vocab_size,
        "rope_parameters": {
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": c.rope_theta},
            "full_attention": {
                "rope_type": "yarn", "rope_theta": c.rope_theta,
                "factor": c.rope_yarn_factor,
                "original_max_position_embeddings": c.rope_yarn_orig_max,
                "beta_fast": c.rope_yarn_beta_fast,
                "beta_slow": c.rope_yarn_beta_slow,
                "attention_factor": 0.1 * math.log(c.rope_yarn_factor) + 1}},
    }


def make_runner(config=CONFIG, buckets=(16, 32), window_pages=16):
    return ModelRunner(
        config,
        RunnerConfig(page_size=PAGE, num_pages=64, max_batch=SLOTS,
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


def prompt_of(n: int, seed: int = 0) -> list[int]:
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


def table_for(slot: int) -> np.ndarray:
    """The full group's table of a slot (page 0 is the scratch page)."""
    table = np.zeros(WIDTH, np.int32)
    n = WIDTH - 1
    table[:n] = 1 + slot * n + np.arange(n)
    return table


class Row:
    """One sequence driven by hand through both page groups: the full
    group's pages are the slot's own, the window group's come from a
    `WindowPool` ahead of each launch and go back behind the window."""

    def __init__(self, runner, pool, slot, prompt):
        self.runner, self.pool, self.slot = runner, pool, slot
        self.tokens = list(prompt)
        self.lease = pool.reserve(pool.bound(1))
        self.held = []  # pages the lease held at each launch

    def _window(self, first_pos, last_pos, phase):
        assert self.pool.advance(
            self.lease, max(0, first_pos - WINDOW + 1), last_pos, phase)
        self.held.append(len(self.lease.pages))
        return list(self.lease.pages), self.lease.first * PAGE

    def prefill(self, chunks):
        start, token = 0, None
        for size in chunks:
            window = self._window(start, start + size - 1, "prefill")
            token = self.runner.prefill_chunk(
                np.asarray(self.tokens[start:start + size], np.int32),
                start, table_for(self.slot), start + size, GREEDY,
                slot=self.slot, window=window)
            start += size
        assert start == len(self.tokens)
        self.tokens.append(int(token))
        return int(token)

    def decode(self):
        """One step on the last token; returns its logits and appends
        their arg-max."""
        pos = len(self.tokens) - 1
        pages, base = self._window(pos, pos, "decode")
        toks, at = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
        tables = np.zeros((SLOTS, WIDTH), np.int32)
        win = np.zeros((SLOTS, self.runner.window_table_width), np.int32)
        bases = np.zeros(SLOTS, np.int32)
        lens, active = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, bool)
        s = self.slot
        toks[s], at[s], lens[s], active[s] = self.tokens[-1], pos, pos + 1, 1
        tables[s], bases[s] = table_for(s), base
        win[s, :len(pages)] = pages
        self.runner.decode(
            toks, at, (tables, win, bases), lens, active,
            np.zeros(SLOTS, np.float32), np.ones(SLOTS, np.float32),
            np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.uint32),
            want_logits=True)
        row = self.runner.last_decode_logits[s]
        self.tokens.append(int(row.argmax()))
        return row


def test_chunked_prefill_then_decode_through_both_page_groups(runner,
                                                              reference):
    """A 119-token prompt in chunks of 32, then 40 decode steps: nearly
    five windows of context. Every decode step's logits against the
    reference's one full forward; the window group never holds more than
    its bound, and what fell behind the window went back."""
    pool = WindowPool(16, PAGE, WINDOW)
    prompt = prompt_of(119)
    row = Row(runner, pool, 1, prompt)
    first = row.prefill([32, 32, 32, 23])
    got = [row.decode() for _ in range(40)]
    served = row.tokens[len(prompt):]
    want = reference_logits(reference, CONFIG, prompt, served)
    assert want.std() > 0.5  # a spread of ~1: the tolerance means something
    assert first == int(want[0].argmax())
    np.testing.assert_allclose(np.stack(got), want[1:], atol=VS_REFERENCE)
    assert max(row.held[:4]) <= pool.bound(32)
    assert max(row.held[4:]) <= pool.bound(1)
    assert pool.freed_behind["prefill"] > 0 and pool.freed_behind["decode"] > 0
    # the same comparison with the window treated as full attention FAILS:
    # a program that left the mask out could not pass it
    full = reference_logits(reference, CONFIG, prompt, served,
                            {"window": "full"})
    assert np.abs(np.stack(got) - full[1:]).max() > 50 * VS_REFERENCE
    pool.release(row.lease)
    assert pool.free_count() == 15 and pool.unreserved() == 15


def test_a_context_inside_the_window_needs_no_mask(runner, reference):
    prompt = prompt_of(20, seed=5)
    row = Row(runner, WindowPool(16, PAGE, WINDOW), 0, prompt)
    row.prefill([16, 4])
    got = [row.decode() for _ in range(6)]
    want = reference_logits(reference, CONFIG, prompt,
                            row.tokens[len(prompt):])
    np.testing.assert_allclose(np.stack(got), want[1:], atol=VS_REFERENCE)


def test_the_fused_block_equals_single_steps(runner):
    """Eight steps in one program over both tables: the window's lower
    edge moves inside the block while the table stays."""
    pool = WindowPool(16, PAGE, WINDOW)
    prompt = prompt_of(75, seed=2)
    row = Row(runner, pool, 2, prompt)
    row.prefill([32, 32, 11])
    pos = len(prompt)
    assert pool.advance(row.lease, pos - WINDOW + 1, pos + 7, "decode")
    toks, at = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    tables = np.zeros((SLOTS, WIDTH), np.int32)
    win = np.zeros((SLOTS, runner.window_table_width), np.int32)
    bases, lens = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    active = np.zeros(SLOTS, bool)
    toks[2], at[2], lens[2], active[2] = row.tokens[-1], pos, pos + 1, True
    tables[2], bases[2] = table_for(2), row.lease.first * PAGE
    win[2, :len(row.lease.pages)] = row.lease.pages
    block = runner.decode_multi(
        toks, at, (tables, win, bases), lens, active,
        np.zeros(SLOTS, np.float32), np.ones(SLOTS, np.float32),
        np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.uint32), k=8)
    singles = [int(row.decode().argmax()) for _ in range(8)]
    assert [int(t) for t in block[:, 2]] == singles


# -- the scheduler -------------------------------------------------------------


class Collect:
    def __init__(self):
        self.outputs: list[EngineOutput] = []

    def __call__(self, out: EngineOutput):
        self.outputs.append(out)

    def tokens(self):
        return [t for o in self.outputs for t in o.token_ids]

    @property
    def finish(self):
        return next((o.finish_reason for o in self.outputs
                     if o.finish_reason), None)


def request(prompt, max_tokens):
    return PreprocessedRequest(
        request_id=uuid.uuid4().hex, token_ids=list(prompt),
        sampling=SamplingOptions(max_tokens=max_tokens, temperature=0.0),
        stop=StopConditions(ignore_eos=True))


def wait_for(collectors, seconds=240):
    deadline = time.time() + seconds
    while (any(c.finish is None for c in collectors)
           and time.time() < deadline):
        time.sleep(0.02)
    assert all(c.finish is not None for c in collectors)


def test_the_scheduler_frees_behind_and_another_sequence_reuses(reference,
                                                                monkeypatch):
    """Five requests of three to six windows over four slots and a window
    group of 22 pages (four rows' reservations of 5 and two pages more):
    chunked and batched prefill, the fused 8-step block, a slot reused.
    A page that fell behind one sequence's window is allocated to another
    while the first still decodes; every stream is the reference's greedy
    continuation; no prefix hit is taken though a prompt comes twice; a
    row never holds more than its bound; both free lists end whole."""
    held = []  # (lease id, its pages) after every allocation
    advance = WindowPool.advance

    def recording(self, lease, lo, hi, phase):
        ok = advance(self, lease, lo, hi, phase)
        held.append((id(lease), phase, tuple(lease.pages)))
        return ok

    monkeypatch.setattr(WindowPool, "advance", recording)
    stored = []
    sched = InferenceScheduler(make_runner(window_pages=23),
                               on_stored=lambda h, p: stored.append(h))
    sched.decode_block = 8
    sched._win_lookahead = 8 * max(1, sched.decode_pipeline)
    prompts = [prompt_of(n, seed=40 + i)
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
    # a row's bound, by the phase of the launch the pages were taken for
    bound = {"prefill": pool.bound(32),
             "decode": pool.bound(sched._win_lookahead)}
    assert all(len(pages) <= bound[phase] for _, phase, pages in held)
    assert pool.freed_behind["decode"] > 0 and pool.freed_behind["prefill"] > 0
    # one page goes back for every 16 positions a window's edge moves
    for phase in ("prefill", "decode"):
        per = pool.freed_behind[phase] / pool.edge_tokens[phase]
        assert 0.8 / PAGE < per < 1.2 / PAGE
    # reuse while the first owner lives: page p with lease A, then with
    # lease B, and A still allocating afterwards
    reused = False
    for i, (a, _, pages_a) in enumerate(held):
        later = held[i + 1:]
        for j, (b, _, pages_b) in enumerate(later):
            if b != a and set(pages_a) & set(pages_b):
                reused = reused or any(c == a for c, _, _ in later[j + 1:])
    assert reused
    assert pool.free_count() == 22 and pool.unreserved() == 22
    assert sched.pool.free_count() == 63
    assert sched.stats.window_reserved_page_ms > 0
    assert sched.stats.reserved_page_ms > 0


# -- parts ---------------------------------------------------------------------


def test_yarn_tables_against_the_formula_restated():
    """The full layers' table at the published sizes, against HF's
    `_compute_yarn_parameters` written out: theta 500000, factor 16 over
    8192, beta 32 / 1, whole-dimension correction range."""
    from dynamo_tpu.models.hybrid import rope_tables

    c = get_config("mellum2-12b-a2.5b")
    inv_freq, factor = rope_tables(c, "*")
    d, theta = 128, 5e5

    def dim_of(rotations):
        return d * math.log(8192 / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low, high = math.floor(dim_of(32)), math.ceil(dim_of(1))
    assert (low, high) == (18, 35)
    want = []
    for i in range(d // 2):
        plain = theta ** (-2 * i / d)
        ramp = min(1.0, max(0.0, (i - low) / (high - low)))
        want.append((1 - ramp) * plain + ramp * plain / 16)
    np.testing.assert_allclose(np.asarray(inv_freq), want, rtol=1e-5)
    assert factor == pytest.approx(1.2772588722239782)
    plain, one = rope_tables(c, "W")
    np.testing.assert_allclose(
        np.asarray(plain), [theta ** (-2 * i / d) for i in range(d // 2)],
        rtol=1e-5)
    assert one == 1.0
    # the two kinds share the fast lanes and part at the slow ones
    np.testing.assert_allclose(inv_freq[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[35:], plain[35:] / 16, rtol=1e-6)


def expert_layer(config, seed=4):
    from dynamo_tpu.models.hybrid import init_hybrid_layer

    idx = config.layer_pattern.index("E")
    return init_hybrid_layer(jax.random.PRNGKey(seed), config, idx)


def test_the_swiglu_experts_equal_a_loop_and_the_shares_add_up():
    """All eight held: the dropless layer against a loop over tokens and
    their two experts; then the shares 0:4 and 4:8 of an expert-parallel
    pair add up to it (no shared expert to count once)."""
    from dynamo_tpu.models.hybrid import moe_mixer
    from dynamo_tpu.models.transformer import _routing_weights

    x = jax.random.normal(jax.random.PRNGKey(9), (2, 11, CONFIG.hidden))
    valid = jnp.ones((2, 11), bool)
    whole = expert_layer(CONFIG)
    out, stats = moe_mixer(x, whole, CONFIG, valid, "xla")
    weights, chosen = _routing_weights(x, whole, CONFIG)
    m = CONFIG.expert_mlp_hidden
    want = np.zeros(x.shape, np.float32)
    for b in range(2):
        for t in range(11):
            for w, e in zip(np.asarray(weights[b, t]),
                            np.asarray(chosen[b, t])):
                u = np.asarray(whole["e_up"][e]) @ np.asarray(x[b, t])
                mid = u[:m] / (1 + np.exp(-u[:m])) * u[m:]
                want[b, t] += w * (mid @ np.asarray(whole["e_down"][e]))
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)
    assert int(stats[:8].sum()) == 2 * 11 * 2 and int(stats[8]) == 0
    assert np.asarray(weights).sum(-1) == pytest.approx(1.0)
    total = np.zeros_like(want)
    for share in ("0:4", "4:8"):
        cut = cut_config(CONFIG, experts=share)
        part, _ = moe_mixer(x, expert_layer(cut), cut, valid, "xla")
        total += np.asarray(part)
    np.testing.assert_allclose(total, np.asarray(out), atol=1e-4)


def test_the_windowed_pool_kernel_equals_the_xla_path():
    """`paged_decode_attention_window` (interpreted) over a table that
    starts mid-sequence against the masked XLA reference: the oldest
    page is cut by the window's lower edge."""
    from dynamo_tpu.models.transformer import paged_attention_decode_xla
    from dynamo_tpu.ops.paged_attention import paged_attention_decode_pool

    rng = np.random.default_rng(3)
    b, qh, kh, hd, pages = 3, 8, 2, 128, 8
    cache = jnp.asarray(rng.normal(size=(2, 2, 32, PAGE, kh, hd)),
                        jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, 1, qh, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, 1, kh, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, 1, kh, hd)), jnp.float32)
    tables = jnp.asarray(rng.permutation(31)[:b * pages].reshape(b, pages)
                         + 1, jnp.int32)
    lens = jnp.asarray([75, 0, 33], jnp.int32)  # in the table's frame
    args = (q, cache, 1, tables, lens, k, v)
    want = paged_attention_decode_xla(*args, window=64)
    got = paged_attention_decode_pool(*args, window=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[[0, 2]],
                               np.asarray(want)[[0, 2]], atol=2e-5)
    unmasked = paged_attention_decode_xla(*args)
    assert np.abs(np.asarray(unmasked)[0] - np.asarray(want)[0]).max() > 1e-2


@pytest.mark.parametrize("window", [0, 24])
def test_prefill_attention_by_blocks_equals_the_whole_table(monkeypatch,
                                                            window):
    """Three rows of a launch at different depths of their contexts, one
    with padding behind its chunk: 8 query positions at a time, a window
    layer over a slice of its table and a full layer over the narrowest
    of three table prefixes, against one pass over the whole table."""
    from dynamo_tpu.models import hybrid
    from dynamo_tpu.models.transformer import paged_attention_xla

    monkeypatch.setattr(hybrid, "PREFILL_Q_BLOCK", 8)
    monkeypatch.setattr(hybrid, "FULL_TABLE_PAGES", (2, 4))
    rng = np.random.default_rng(7)
    b, t, qh, kh, hd, width = 3, 32, 4, 2, 16, 8
    cache = jnp.asarray(rng.normal(size=(2, 2, 40, PAGE, kh, hd)),
                        jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, t, qh, hd)), jnp.float32)
    tables = jnp.asarray(rng.permutation(39)[:b * width].reshape(b, width)
                         + 1, jnp.int32)
    start, valid = np.asarray([0, 41, 90]), np.asarray([32, 32, 20])
    positions = np.zeros((b, t), np.int32)
    for i in range(b):
        positions[i, :valid[i]] = start[i] + np.arange(valid[i])
    kv_lens = jnp.asarray(start + valid, jnp.int32)
    args = (q, cache, 1, tables, jnp.asarray(positions), kv_lens)
    want = np.asarray(paged_attention_xla(*args, window=window))
    got = np.asarray(jax.jit(hybrid.prefill_attention,
                             static_argnames=("layer", "window"))(
        q, cache, layer=1, block_tables=tables,
        positions=jnp.asarray(positions), kv_lens=kv_lens, window=window))
    for i in range(b):  # the padding's rows are nobody's
        np.testing.assert_allclose(got[i, :valid[i]], want[i, :valid[i]],
                                   atol=2e-5)
    if window:
        assert np.abs(want - np.asarray(paged_attention_xla(*args))).max() > .1


def test_both_page_groups_run_the_prefill_kernel_where_it_admits_them(
        monkeypatch):
    """The preset at head_dim 128 in bf16 (the kernel's geometry) through
    the runner twice: `attention_fn` as on the chip (here the Pallas
    interpreter) and the CPU's XLA form. A 119-token prompt in chunks of
    32 (the window group's frame starts past 0 from the third on), then
    decode steps whose logits rest on every layer's prefill attention.
    The launches count under `kernel`, a page group's blocks apart.
    Dense mixers in the experts' place: in bf16 a top-2 of 8 flips on
    the last bit of a score (the decode kernels move these logits by 1.7
    against XLA with the experts in, by 0.04 without)."""
    config = dataclasses.replace(get_config("tiny-mellum-test"),
                                 head_dim=128, dtype="bfloat16",
                                 layer_pattern="WDWDWD*D" * 2)
    prompt = prompt_of(119, seed=5)

    def logits(runner):
        pool = WindowPool(16, PAGE, WINDOW)
        row = Row(runner, pool, 1, prompt)
        row.prefill([32, 32, 32, 23])
        return np.stack([row.decode() for _ in range(4)])

    xla = make_runner(config)
    assert xla.prefill_attention_tiles(32) is None
    monkeypatch.setenv("DYNT_ATTENTION", "pallas")  # here: the interpreter
    kernel = make_runner(config)
    assert kernel.window_prefill_width(32) == 16
    assert kernel.prefill_attention_tiles(32) == (32, 256)
    assert kernel.prefill_attention_tiles(32, window=True) == (32, 256)
    want, got = logits(xla), logits(kernel)
    assert want.std() > 0.5
    # bf16 weights and activations on both sides; the kernel's bf16
    # probabilities against float32 ones
    np.testing.assert_allclose(got, want, atol=0.08)
    assert (got.argmax(-1) == want.argmax(-1)).all()
    assert xla.prefill_attn_launches == {"kernel": 0, "xla": 4}
    assert kernel.prefill_attn_launches == {"kernel": 4, "xla": 0}
    # one query block x one key chunk a launch in either group
    assert kernel.prefill_attn_blocks == {"live": 4, "skipped": 0}
    assert kernel.prefill_attn_window_blocks == {"live": 4, "skipped": 0}
    assert xla.prefill_attn_window_blocks == {"live": 0, "skipped": 0}


# -- what it is refused ----------------------------------------------------------


def test_the_runner_wants_a_second_page_group_and_moves_no_pages():
    with pytest.raises(ValueError, match="--window-pages"):
        make_runner(window_pages=0)
    runner = make_runner()
    (full, window), _ = runner.cache
    assert full.shape[0] == 2 and window.shape[0] == 6
    assert full.shape[2] == 64 and window.shape[2] == 16
    with pytest.raises(RuntimeError, match="two page groups"):
        runner.gather_pages_device(np.asarray([1, 2]))
    assert not runner.supports_spec


def test_the_layers_kind_gives_its_window_and_gpt_oss_keeps_its_rule():
    c = get_config("mellum2-12b-a2.5b")
    assert [c.layer_sliding_window(i) for i in range(8)] == [
        1024, 0, 1024, 0, 1024, 0, 0, 0]
    assert len(c.kv_layers) == 7 and len(c.window_kv_layers) == 21
    oss = get_config("tiny-gptoss-test")
    assert [oss.layer_sliding_window(i) for i in range(4)] == [16, 0, 16, 0]
    assert get_config("tiny-hybrid-test").layer_sliding_window(3) == 0
    stage = cut_config(c, layers=8)
    assert stage.layer_pattern == "WEWEWE*EWEWEWE*E" and stage.n_layers == 16
    assert len(stage.kv_layers) == 2 and len(stage.window_kv_layers) == 6


def test_a_prefill_launch_is_held_to_the_token_budgets_positions():
    runner = make_runner(buckets=(16, 32, 64))
    assert runner.prefill_launch_fits([64])
    assert runner.prefill_launch_fits([20, 30])  # 2 x 32
    assert not runner.prefill_launch_fits([20, 40])  # 2 x 64
    assert runner.prefill_launch_fits([16, 16, 16])  # 4 x 16
    assert not runner.prefill_launch_fits([16] * 5)  # 8 x 16
