"""Latent attention over a single-stack latent page pool, sandwich-normed
blocks, a leading dense block and routed experts with a shared one
(pangu_ultra_moe) on the served path, against its plain reference
(benchmarks/references/pangu.py, which imports nothing of the program),
at a tiny size on the CPU with seeded weights: one dense and four expert
blocks as the benchmark's cell cuts the model, 4 heads of 16 | 8 and 16,
ranks 24 / 32, 8 experts top-2, pages of 16.

Tolerances, on logits whose spread is ~1.0: the float32 preset agrees
with the float32 reference to 2e-3 in prefill and in decode alike (the
absorbed decode form reorders float32 sums, no more); the reference with
one piece of the mathematics left out (the post-branch norms, the rope
lanes in the softmax scale, the sigmoid router) differs from it by
tenths or more, which is what the comparison has to catch.
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
PAGE, WIDTH, SLOTS = 16, 16, 4
GREEDY = (0.0, 1.0, 0, 0)
CONFIG = dataclasses.replace(get_config("tiny-pangu-test"), dtype="float32")


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "pangu_reference",
        os.path.join(ROOT, "benchmarks", "references", "pangu.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference_config(c, weight_seed=0) -> dict:
    """The configuration-file keys the reference reads, for a preset."""
    return {
        "dtype": c.dtype, "weight_seed": weight_seed,
        "hidden_size": c.hidden, "num_attention_heads": c.n_q_heads,
        "q_lora_rank": c.mla_q_lora_rank, "kv_lora_rank": c.mla_kv_lora_rank,
        "qk_nope_head_dim": c.mla_nope_head_dim,
        "qk_rope_head_dim": c.mla_rope_head_dim,
        "v_head_dim": c.mla_v_head_dim, "intermediate_size": c.mlp_hidden,
        "moe_intermediate_size": c.expert_mlp_hidden,
        "n_shared_experts": c.n_shared_experts,
        "n_routed_experts_published": c.n_experts,
        "experts_held": list(c.held_experts),
        "num_experts_per_tok": c.n_experts_active,
        "norm_topk_prob": c.moe_norm_topk,
        "routed_scaling_factor": c.moe_routed_scale,
        "rms_norm_eps": c.rms_eps, "rope_theta": c.rope_theta,
        "vocab_size": c.vocab_size,
        "served_layers": c.n_layers // c.mixers_per_layer,
        "first_k_dense_replace": c.layer_pattern.count("D"),
    }


def make_runner(config=CONFIG, buckets=(16, 32)):
    return ModelRunner(
        config,
        RunnerConfig(page_size=PAGE, num_pages=64, max_batch=SLOTS,
                     max_pages_per_seq=WIDTH, prefill_buckets=buckets),
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
    """A slot's table (page 0 is the scratch page)."""
    table = np.zeros(WIDTH, np.int32)
    n = WIDTH - 1
    table[:n] = 1 + slot * n + np.arange(n)
    return table


class Row:
    """One sequence driven by hand through the latent pool."""

    def __init__(self, runner, slot, prompt):
        self.runner, self.slot = runner, slot
        self.tokens = list(prompt)

    def prefill(self, chunks):
        start, token = 0, None
        for size in chunks:
            token = self.runner.prefill_chunk(
                np.asarray(self.tokens[start:start + size], np.int32),
                start, table_for(self.slot), start + size, GREEDY,
                slot=self.slot)
            start += size
        assert start == len(self.tokens)
        self.tokens.append(int(token))
        return int(token)

    def step_args(self):
        pos = len(self.tokens) - 1
        toks, at = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
        tables = np.zeros((SLOTS, WIDTH), np.int32)
        lens, active = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, bool)
        s = self.slot
        toks[s], at[s], lens[s], active[s] = self.tokens[-1], pos, pos + 1, 1
        tables[s] = table_for(s)
        return (toks, at, tables, lens, active,
                np.zeros(SLOTS, np.float32), np.ones(SLOTS, np.float32),
                np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.uint32))

    def decode(self):
        """One step on the last token; returns its logits and appends
        their arg-max."""
        self.runner.decode(*self.step_args(), want_logits=True)
        row = self.runner.last_decode_logits[self.slot]
        self.tokens.append(int(row.argmax()))
        return row


def test_chunked_prefill_then_decode_through_the_latent_pool(runner,
                                                             reference):
    """A 119-token prompt in chunks of 32 (each later chunk rebuilds the
    earlier ones' keys and values from their cached latents), then 40
    absorbed decode steps. Every decode step's logits against the
    reference's one full forward, which absorbs nothing and caches
    nothing; the counters say what each path was asked for."""
    before = (runner.latent_decode_tokens, runner.latent_prefill_expand_tokens)
    prompt = prompt_of(119)
    row = Row(runner, 1, prompt)
    first = row.prefill([32, 32, 32, 23])
    got = [row.decode() for _ in range(40)]
    served = row.tokens[len(prompt):]
    want = reference_logits(reference, CONFIG, prompt, served)
    assert want.std() > 0.5  # a spread of ~1: the tolerance means something
    assert first == int(want[0].argmax())
    np.testing.assert_allclose(np.stack(got), want[1:], atol=VS_REFERENCE)
    # five latent layers: a decode step at context c reads c cached rows
    assert runner.latent_decode_tokens - before[0] == 5 * sum(
        range(119, 159))
    # and on the XLA path, which this runner's launches take, a launch
    # rebuilds its row's whole context from the latents
    assert runner.prefill_attention_path() == "xla"
    assert runner.latent_prefill_expand_tokens - before[1] == 5 * (
        32 + 64 + 96 + 119)
    # the same comparison with a piece of the mathematics left out FAILS
    for lower in ({"post_norm": "none"}, {"scale": "nope"},
                  {"router": "softmax"}):
        off = reference_logits(reference, CONFIG, prompt, served, lower)
        assert np.abs(np.stack(got) - off[1:]).max() > 50 * VS_REFERENCE


def test_the_absorbed_decode_equals_the_prefill_that_does_not_absorb(runner):
    """Position 47's logits from a prefill chunk that ends there (keys
    and values rebuilt, scores 16 + 8 lanes wide) and from a decode step
    at the same position (queries absorbed into the latent space, scores
    over the cached rows themselves)."""
    from dynamo_tpu.models.hybrid import forward_hybrid

    prompt = prompt_of(48, seed=3)
    row = Row(runner, 2, prompt[:47])
    row.prefill([32, 15])
    row.tokens[-1] = prompt[47]  # decode the prompt's own next token
    pos = jnp.arange(48)[None]
    _, _, by_prefill, _ = jax.jit(
        lambda kv, state: forward_hybrid(
            runner.params, CONFIG, jnp.asarray(prompt)[None], pos, kv, state,
            jnp.asarray([3]), jnp.asarray(table_for(3))[None],
            jnp.asarray([48]), jnp.ones((1, 48), bool), jnp.asarray([47])))(
        *runner.cache[0], runner.cache[1])
    by_decode = row.decode()
    np.testing.assert_allclose(by_decode, np.asarray(by_prefill)[0],
                               atol=VS_REFERENCE)


def test_the_fused_block_equals_single_steps(runner):
    prompt = prompt_of(75, seed=2)
    row = Row(runner, 2, prompt)
    row.prefill([32, 32, 11])
    block = runner.decode_multi(*row.step_args(), k=8)
    singles = [int(row.decode().argmax()) for _ in range(8)]
    assert [int(t) for t in block[:, 2]] == singles


# -- the kernel ------------------------------------------------------------------


def _latent_case(lens, pages, seed=3, behind=None):
    """(args of the oracle, kw, the rows' own tokens) for rows of history
    + current token `lens` over tables `pages` wide: rows 40 wide padded
    to 128 lanes, 32 of them values, 4 heads, two layers. `behind`: the
    value of every table entry past a row's last live page (the pool's
    last page, filled with it)."""
    rng = np.random.default_rng(seed)
    b, heads, width = len(lens), 4, 128
    n_pages = b * pages + 2
    cache = np.zeros((2, 1, n_pages, PAGE, 1, width), np.float32)
    cache[..., :40] = rng.normal(size=cache.shape[:-1] + (40,))
    q = np.zeros((b, heads, width), np.float32)
    q[..., :40] = rng.normal(size=(b, heads, 40))
    cur = np.zeros((b, width), np.float32)
    cur[:, :40] = rng.normal(size=(b, 40))
    tables = (rng.permutation(n_pages - 2)[:b * pages].reshape(b, pages)
              + 1).astype(np.int32)
    if behind is not None:
        cache[:, :, n_pages - 1] = behind
        for i, n in enumerate(lens):
            tables[i, -(-max(n - 1, 0) // PAGE):] = n_pages - 1
    return ((jnp.asarray(q), jnp.asarray(cache), 1, jnp.asarray(tables),
             jnp.asarray(lens, jnp.int32), jnp.asarray(cur)),
            {"rank": 32, "sm_scale": 1 / math.sqrt(24)}, cur)


# The walk's edges, at the kernel's own tiles (blocks of 256 tokens, and
# with pages_per_chunk 64 chunks of 1,024: four blocks a chunk, two a
# flash update): (lengths, table pages, pages_per_chunk[, what lies
# behind a row's last live page]). Lengths INCLUDE the current token, so
# a history is one less.
_BLOCK, _CHUNK, _WIDE = 256, 1024, 128  # tokens, tokens, pages
LATENT_WALKS = {
    "one-chunk-a-row": ([75, 0, 33, 1, 128], 8, None),
    "a-page-pair-a-chunk": ([75, 0, 33, 1, 128], 8, 2),
    "around-a-block": ([2, _BLOCK, _BLOCK + 1, _BLOCK + 2], _WIDE, 64),
    "around-a-chunk": ([_CHUNK, _CHUNK + 1, _CHUNK + 2, _BLOCK // 2],
                       _WIDE, 64),
    "the-whole-table": ([_WIDE * PAGE + 1, _CHUNK + _BLOCK + 1,
                         _WIDE * PAGE - 5], _WIDE, 64),
    "first-row-empty": ([1, 300, 20, 700], 64, 64),
    "last-row-empty": ([300, 20, 700, 0], 64, 64),
    "two-empty-rows-in-a-row": ([300, 0, 1, 700], 64, 64),
    "every-row-empty": ([0, 1, 0, 1], 64, 64),
    "a-stale-length-past-the-table": ([300, 5000, 20], 64, 64),
    "nan-pages-behind-a-ragged-end": (
        [300, 2, _BLOCK + 1, _BLOCK + 18, 0], 64, 64, np.nan),
}


@pytest.mark.parametrize("walk", sorted(LATENT_WALKS))
def test_the_latent_kernel_equals_the_xla_oracle(walk):
    """`paged_decode_attention_latent` (interpreted) against the oracle
    that gathers the whole table: ragged lengths, inactive rows wherever
    the walk to the next row with a history can meet them, a row with
    the current token alone, a table wider than any context, histories
    one under, at and one over a block and a chunk and as wide as the
    table, a length past the table (which reads the table's width, as
    the oracle does). `behind`: every table entry past a row's last live
    page names a page nobody has written, NaN all over; a ragged block
    copies such pages whole, and the answer is the one over zeros there
    (0 x NaN would be NaN in P V; the oracle's own would be)."""
    from dynamo_tpu.models.hybrid import paged_attention_decode_latent_xla
    from dynamo_tpu.ops.paged_attention import paged_attention_decode_latent

    ops = importlib.import_module(paged_attention_decode_latent.__module__)
    assert (ops._LATENT_BLOCK_TOKENS, ops._LATENT_CHUNK_TOKENS) == (
        _BLOCK, _CHUNK)  # the edges above are the kernel's own
    lens, pages, chunk, behind = (*LATENT_WALKS[walk], None)[:4]
    args, kw, cur = _latent_case(lens, pages, behind=behind)
    clean = (args if behind is None
             else _latent_case(lens, pages, behind=0.0)[0])
    want = np.asarray(paged_attention_decode_latent_xla(*clean, **kw))
    got = np.asarray(paged_attention_decode_latent(
        *args, **kw, pages_per_chunk=chunk, interpret=True))
    assert got.shape == (len(lens), 4, 32)
    live = [i for i, n in enumerate(lens) if n > 0]
    assert np.isfinite(got[live]).all()
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    # a row with no history attends to its own token alone
    for i in (i for i, n in enumerate(lens) if n == 1):
        np.testing.assert_allclose(
            got[i], np.broadcast_to(cur[i, :32], (4, 32)), atol=1e-6)
    # and the other layer's rows would have given another answer
    other = np.asarray(paged_attention_decode_latent_xla(
        clean[0], clean[1], 0, *clean[3:], **kw))
    deep = max(live, key=lambda i: lens[i])
    assert lens[deep] < 2 or np.abs(other[deep] - want[deep]).max() > 1e-3


def test_prefill_attention_by_key_blocks_equals_one_pass(monkeypatch):
    """Three rows of a launch at different depths of their contexts, one
    with padding behind its chunk, two key blocks of 32 and a ragged
    third, against the absorbed form over the whole table in one pass
    (`_mla_attention_block`'s mathematics, restated)."""
    from dynamo_tpu.models import hybrid

    monkeypatch.setattr(hybrid, "LATENT_KEY_BLOCK", 32)
    rng = np.random.default_rng(7)
    c = CONFIG
    b, t, width = 3, 32, 8
    heads, nope, rd, rank = 4, 16, 8, 32
    cache = np.zeros((2, 1, 40, PAGE, 1, 128), np.float32)
    cache[..., :40] = rng.normal(size=cache.shape[:-1] + (40,))
    q_nope = jnp.asarray(rng.normal(size=(b, t, heads, nope)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(b, t, heads, rd)), jnp.float32)
    w_uk = jnp.asarray(rng.normal(size=(heads, nope, rank)), jnp.float32)
    w_uv = jnp.asarray(rng.normal(size=(heads, rank, 16)), jnp.float32)
    tables = jnp.asarray(rng.permutation(39)[:b * width].reshape(b, width)
                         + 1, jnp.int32)
    start, valid = np.asarray([0, 41, 90]), np.asarray([32, 32, 20])
    positions = np.zeros((b, t), np.int32)
    for i in range(b):
        positions[i, :valid[i]] = start[i] + np.arange(valid[i])
    kv_lens = jnp.asarray(start + valid, jnp.int32)
    got = np.asarray(jax.jit(
        hybrid.latent_prefill_attention, static_argnames=("layer", "config"))(
        q_nope, q_rope, jnp.asarray(cache), layer=1, block_tables=tables,
        positions=jnp.asarray(positions), kv_lens=kv_lens, w_uk=w_uk,
        w_uv=w_uv, config=c))
    rows = cache[1, 0][np.asarray(tables)].reshape(b, width * PAGE, 128)
    q_abs = np.einsum("bthn,hnr->bthr", q_nope, w_uk)
    scores = (np.einsum("bthr,bsr->bths", q_abs, rows[..., :rank])
              + np.einsum("bthr,bsr->bths", q_rope, rows[..., rank:40])
              ) / math.sqrt(nope + rd)
    kv_pos = np.arange(width * PAGE)
    seen = ((kv_pos[None, None] <= positions[..., None])
            & (kv_pos[None, None] < np.asarray(kv_lens)[:, None, None]))
    scores = np.where(seen[:, :, None], scores, -1e30)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.einsum("bthr,hrv->bthv",
                     np.einsum("bths,bsr->bthr", probs, rows[..., :rank]),
                     w_uv)
    for i in range(b):  # the padding's rows are nobody's
        np.testing.assert_allclose(got[i, :valid[i]], want[i, :valid[i]],
                                   atol=2e-4, rtol=2e-4)


# name: (positions a row (the bucket), table width in pages, rows as (first
# position, valid positions)); query blocks of 16 positions, chunks of 32
# keys, head groups of 2 of the 4 heads
LATENT_LAUNCHES = {
    "fresh": (32, 16, [(0, 32)]),
    "second-chunk-over-cached-history": (32, 16, [(32, 32)]),
    "two-rows-of-unequal-length-and-padding": (32, 16, [(0, 20), (37, 27)]),
    "a-row-of-length-0": (16, 8, [(0, 0), (5, 16)]),
    # 13 pages, a prime: no chunk of two pages divides the table, so
    # its chunks are one page, and the row's keys end inside the last
    "ragged-table-of-one-page-chunks": (32, 13, [(170, 30)]),
    "eight-short-rows": (16, 16, [(0, 16), (0, 9), (64, 16), (128, 5),
                                  (32, 16), (0, 16), (230, 16), (0, 0)]),
}


def attention_ops():
    """The module (`dynamo_tpu.ops` exports a function of its name)."""
    import importlib

    return importlib.import_module("dynamo_tpu.ops.paged_attention")


@pytest.fixture
def latent_tiles(monkeypatch):
    """The latent prefill kernel's tiles for one test: small enough that
    a tiny launch walks several query blocks, key chunks and head
    groups. The jitted entry reads them while it traces, so its traces
    go before the next test's."""
    ops = attention_ops()

    def set_tiles(positions, chunk, heads=16):
        monkeypatch.setattr(ops, "_LATENT_PREFILL_POSITIONS", positions)
        monkeypatch.setattr(ops, "_LATENT_PREFILL_CHUNK_TOKENS", chunk)
        monkeypatch.setattr(ops, "_LATENT_PREFILL_HEADS", heads)
        ops.paged_prefill_attention_latent.clear_cache()

    yield set_tiles
    ops.paged_prefill_attention_latent.clear_cache()


def latent_launch(case, dtype, seed=0):
    """`latent_prefill_attention`'s arguments for a launch over a pool
    of rows 40 wide padded to 128 lanes, 32 of them values."""
    t, width, rows = LATENT_LAUNCHES[case]
    rng = np.random.default_rng(seed)
    c = CONFIG
    heads, nope, rd = c.n_q_heads, c.mla_nope_head_dim, c.mla_rope_head_dim
    rank = c.mla_kv_lora_rank
    b, n_pages = len(rows), len(rows) * width + 1
    cache = np.zeros((2, 1, n_pages, PAGE, 1, 128), np.float32)
    cache[..., :rank + rd] = rng.normal(size=cache.shape[:-1] + (rank + rd,))
    q_nope = jnp.asarray(rng.normal(size=(b, t, heads, nope)), dtype)
    q_rope = jnp.asarray(rng.normal(size=(b, t, heads, rd)), dtype)
    w_uk = jnp.asarray(rng.normal(size=(heads, nope, rank))
                       / math.sqrt(nope), dtype)
    w_uv = jnp.asarray(rng.normal(size=(heads, rank, c.mla_v_head_dim))
                       / math.sqrt(rank), dtype)
    tables = np.zeros((b, width), np.int32)  # a padded row: the scratch page
    positions = np.zeros((b, t), np.int32)
    kv_lens = np.zeros(b, np.int32)
    pages = rng.permutation(np.arange(1, n_pages))
    for i, (start, n) in enumerate(rows):
        if n:
            tables[i] = pages[i * width:(i + 1) * width]
            positions[i, :n] = np.arange(start, start + n)
            kv_lens[i] = start + n
    return (q_nope, q_rope, jnp.asarray(cache, dtype), 1,
            jnp.asarray(tables), jnp.asarray(positions),
            jnp.asarray(kv_lens), w_uk, w_uv, c)


@pytest.mark.parametrize("case", sorted(LATENT_LAUNCHES))
def test_the_latent_prefill_kernel_equals_the_xla_form(case, latent_tiles):
    """`paged_prefill_attention_latent` (interpreted, behind
    `paged_attention_latent`: a chunk's keys and values rebuilt a head
    in the kernel, flash state in scratch) against
    `latent_prefill_attention`, the same algebra in XLA, at bf16 inputs;
    query blocks of 16 and key chunks of 32, so every launch walks
    several of each, for two head groups. The output's spread is ~1; on
    the chip the two differ by 0.004-0.008 at the cell's shapes
    (PERF.md, PR 50). A row's padding is nobody's; a row of length 0
    comes back zeros."""
    from dynamo_tpu.models.hybrid import latent_prefill_attention

    ops = attention_ops()
    latent_tiles(16, 32, heads=2)
    _, width, rows = LATENT_LAUNCHES[case]
    args = latent_launch(case, jnp.bfloat16)
    want = np.asarray(latent_prefill_attention(*args), np.float32)
    got = np.asarray(ops.paged_attention_latent(*args, interpret=True),
                     np.float32)
    assert got.shape == want.shape
    for i, (_, n) in enumerate(rows):
        if n:
            assert np.abs(want[i, :n]).max() > 0.5
            assert np.abs(got[i, :n] - want[i, :n]).max() < 0.01
            assert np.abs(got[i, :n] - want[i, :n]).mean() < 0.001
        else:
            assert not got[i].any()
    # float32 inputs: the order of the running softmax's sums alone
    args = latent_launch(case, jnp.float32)
    want = np.asarray(latent_prefill_attention(*args))
    got = np.asarray(ops.paged_attention_latent(*args, interpret=True))
    for i, (_, n) in enumerate(rows):
        np.testing.assert_allclose(got[i, :n], want[i, :n], atol=2e-5)


def test_the_other_layers_rows_would_have_given_another_answer():
    ops = attention_ops()
    args = latent_launch("second-chunk-over-cached-history", jnp.float32)
    got = [np.asarray(ops.paged_attention_latent(
        *args[:3], layer, *args[4:], interpret=True)) for layer in (0, 1)]
    assert np.abs(got[0] - got[1]).max() > 1e-2


# -- the expert layer and the cut -----------------------------------------------


def expert_layer(config, seed=4):
    from dynamo_tpu.models.hybrid import init_hybrid_layer

    idx = config.layer_pattern.index("E")
    return init_hybrid_layer(jax.random.PRNGKey(seed), config, idx)


def test_the_shares_add_up_with_the_shared_expert_counted_once(reference):
    """The share tied to the model: the routed parts of every share (the
    tiny model's four of two experts each, as the deployment's sixteen
    of sixteen) plus the shared expert ONCE equal the uncut reference's
    whole expert layer, which holds all experts."""
    from dynamo_tpu.models.hybrid import moe_mixer

    x = jax.random.normal(jax.random.PRNGKey(9), (2, 11, CONFIG.hidden))
    valid = jnp.ones((2, 11), bool)
    whole, stats = moe_mixer(x, expert_layer(CONFIG), CONFIG, valid, "xla")
    assert int(stats[:8].sum()) == 2 * 11 * 2 and int(stats[8]) == 0
    no_shared = dataclasses.replace(CONFIG, n_shared_experts=0)
    shared = np.asarray(whole) - np.asarray(moe_mixer(
        x, {k: v for k, v in expert_layer(CONFIG).items()
            if not k.startswith("s_")}, no_shared, valid, "xla")[0])
    total = shared.copy()
    for lo in range(0, 8, 2):
        cut = cut_config(no_shared, experts=f"{lo}:{lo + 2}")
        layer = {k: v for k, v in expert_layer(
            cut_config(CONFIG, experts=f"{lo}:{lo + 2}")).items()
            if not k.startswith("s_")}
        total += np.asarray(moe_mixer(x, layer, cut, valid, "xla")[0])
    np.testing.assert_allclose(total, np.asarray(whole), atol=1e-4)
    # the uncut reference's layer, from its own recipe's weights
    cfg = reference_config(CONFIG)
    weights = reference.expert_weights(jax.random.PRNGKey(4), cfg)
    with jax.default_matmul_precision("highest"):
        want = reference.moe_mixer(x.reshape(22, -1), weights, cfg, {})
    np.testing.assert_allclose(np.asarray(whole).reshape(22, -1),
                               np.asarray(want), atol=1e-4)
    assert np.abs(shared).max() > 0.1  # there is a shared expert to count


def test_the_cut_serves_one_dense_block_then_expert_blocks():
    c = get_config("openpangu-ultra-moe-718b")
    assert c.layer_pattern == "LD" * 3 + "LE" * 58 and c.n_layers == 122
    assert len(c.kv_layers) == 61 and c.kv_cache_kv_dims == 1
    cell = cut_config(c, layers=5, experts="0:16", vocab_rows=19200)
    assert cell.layer_pattern == "LD" + "LE" * 4 and cell.n_layers == 10
    assert cell.kv_layers == (0, 2, 4, 6, 8) and cell.held_experts == (0, 16)
    assert (cell.hidden, cell.n_q_heads, cell.mlp_hidden,
            cell.expert_mlp_hidden, cell.n_experts, cell.n_experts_active
            ) == (7680, 128, 18432, 2048, 256, 8)  # no width is cut
    # a cached row: 512 latent + 64 rope-key values, padded to 128 lanes
    assert cell.kv_cache_head_dim == 640 and cell.kv_cache_heads == 1
    assert get_config("deepseek-v3").kv_cache_head_dim == 576  # the loader's
    assert cut_config(c, layers=1).layer_pattern == "LD"
    assert cut_config(c, layers=59).layer_pattern == "LD" + "LE" * 58
    with pytest.raises(ValueError, match="one dense block"):
        cut_config(c, layers=60)
    tiny = get_config("tiny-pangu-test")
    assert tiny.layer_pattern == cell.layer_pattern
    assert cut_config(tiny, layers=3).layer_pattern == "LDLELE"


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


def test_the_engine_serves_rows_at_different_contexts(reference):
    """Four requests of different lengths through the scheduler: chunked
    and batched prefill (a launch's rows x bucket held to the budget),
    the fused 8-step block with rows at different contexts, a prompt
    sent twice. ONE pool, so the one-pool path's prefix cache applies:
    the repeated prompt's pages are found again and its stream is the
    same. Every stream is the reference's greedy continuation."""
    stored = []
    sched = InferenceScheduler(make_runner(),
                               on_stored=lambda h, p: stored.append(h))
    sched.decode_block = 8
    prompts = [prompt_of(n, seed=40 + i) for i, n in enumerate((150, 70, 97))]
    collectors = [Collect() for _ in range(4)]
    sched.start()
    try:
        for p, c in zip(prompts, collectors):
            sched.submit(request(p, 40), c)
        wait_for(collectors[:3])
        sched.submit(request(prompts[0], 40), collectors[3])
        wait_for(collectors)
    finally:
        sched.stop()
    assert [c.finish for c in collectors] == ["length"] * 4
    assert collectors[3].tokens() == collectors[0].tokens()
    # the second time only the prompt's last block is prefilled again
    assert stored and sched.stats.prefill_tokens == 150 + 70 + 97 + 6
    for p, c in zip(prompts, collectors):
        want = reference_logits(reference, CONFIG, p, c.tokens())
        gap = want.max(-1) - want[np.arange(40), c.tokens()]
        assert gap.max() < VS_REFERENCE
    assert sched.runner.latent_decode_tokens > 0
    # the XLA path's launches: 13 of them, none the kernel's
    assert sched.runner.prefill_attn_launches["kernel"] == 0
    assert sched.runner.latent_prefill_expand_tokens > 0
    assert sched.pool.free_count() + sched.pool.cached_count() == 63


# -- what it is refused ----------------------------------------------------------


def test_the_runner_holds_one_latent_stack_and_moves_no_pages(runner):
    (pool,), _ = runner.cache
    assert pool.shape == (5, 1, 64, PAGE, 1, 128)
    assert runner.cache[1]["conv"] == [] and not runner.supports_spec
    with pytest.raises(RuntimeError, match="single-stack latent pool"):
        runner.gather_pages_device(np.asarray([1, 2]))
    # a launch's rows x bucket stay inside the token budget
    assert runner.bounds_prefill_launches
    assert runner.prefill_launch_fits([20]) and runner.prefill_launch_fits(
        [16, 16])
    assert not runner.prefill_launch_fits([16, 20])  # 2 x 32 > 32
    # a model with Mamba layers is bounded only where a context runs past
    # one launch (PR 42); the hybrid cell's contexts fit one, as here
    assert not make_runner(get_config("tiny-hybrid-test"),
                           buckets=(16, 32, PAGE * WIDTH)
                           ).bounds_prefill_launches
    assert make_runner(get_config("tiny-hybrid-test")
                       ).bounds_prefill_launches


def test_the_runner_takes_the_latent_kernel_where_it_is_asked_to(
        runner, monkeypatch):
    """DYNT_ATTENTION=pallas off the TPU is the interpreter: the decode
    slot of a model with latent layers is the latent pool's kernel, and
    a step through it gives the XLA oracle's logits."""
    monkeypatch.setenv("DYNT_ATTENTION", "pallas")
    kernel = make_runner()
    assert kernel.kernel_paths()["decode_attention"] == "interpret"
    assert runner.kernel_paths()["decode_attention"] == "xla"
    assert kernel.kernel_paths()["prefill_attention"] == "kernel"
    assert runner.kernel_paths()["prefill_attention"] == "xla"
    prompt = prompt_of(37, seed=11)
    rows = [Row(r, 1, prompt) for r in (runner, kernel)]
    assert rows[0].prefill([32, 5]) == rows[1].prefill([32, 5])
    for _ in range(2):
        np.testing.assert_allclose(rows[1].decode(), rows[0].decode(),
                                   atol=1e-4)


def test_chunked_prefill_then_decode_reaches_the_same_tokens_on_both_paths(
        runner, monkeypatch, latent_tiles):
    """A 119-token prompt in chunks of 32 and 24 decode steps through a
    runner whose latent layers prefill in the kernel (interpreted) and
    through the XLA form's: the same tokens, logits within float32's
    order of sums; and the counters say which path a launch took: the
    kernel's launches count their (query block, key chunk) pairs and the
    positions those rebuild (a chunk a pair), the XLA form's the rows'
    contexts."""
    from dynamo_tpu.ops.paged_attention import count_prefill_blocks

    monkeypatch.setenv("DYNT_ATTENTION", "pallas")
    latent_tiles(8, 64)
    kernel = make_runner()
    assert kernel.prefill_attention_path() == "kernel"
    assert kernel.prefill_attention_tiles(32) == (8, 64)
    before = (dict(runner.prefill_attn_launches),
              runner.latent_prefill_expand_tokens)
    prompt = prompt_of(119, seed=5)
    rows = [Row(r, 1, prompt) for r in (runner, kernel)]
    chunks = [32, 32, 32, 23]
    assert rows[0].prefill(chunks) == rows[1].prefill(chunks)
    for _ in range(24):
        np.testing.assert_allclose(rows[1].decode(), rows[0].decode(),
                                   atol=1e-4)
    assert rows[0].tokens == rows[1].tokens
    assert kernel.prefill_attn_launches == {"kernel": 4, "xla": 0}
    assert kernel.latent_decode_tokens == 5 * sum(range(119, 143))
    live = sum(count_prefill_blocks([s], [e], 1, 32, 8, 64, PAGE * WIDTH)[0]
               for s, e in ((0, 32), (32, 64), (64, 96), (96, 119)))
    # four query blocks a launch (the last launch's fourth is padding)
    # over one and two key chunks of the table's four
    assert live == 4 * 1 + 4 * 1 + 4 * 2 + 3 * 2
    assert kernel.prefill_attn_blocks == {"live": live,
                                          "skipped": 4 * 4 * 4 - live}
    assert kernel.latent_prefill_expand_tokens == 5 * live * 64
    assert runner.prefill_attn_launches["xla"] - before[0]["xla"] == 4
    assert runner.prefill_attn_launches["kernel"] == before[0]["kernel"] == 0
    assert runner.latent_prefill_expand_tokens - before[1] == 5 * (
        32 + 64 + 96 + 119)
