"""The lfm2_moe stack (LFM2-8B-A1B: a gated short convolution or
grouped-query attention with normed q and k, then a dense SwiGLU or
sigmoid-routed SwiGLU experts with a selection bias, a tied head) on the
served path, against its plain reference (benchmarks/references/lfm2.py,
which imports nothing of the program), at a tiny size on the CPU with
seeded weights: two periods of the published pattern, both dense blocks,
head_dim 64 as published (so the pool packs two kv heads a lane tile), 8
experts top-2.

Tolerances as in test_hybrid_model.py, on logits whose spread is ~1.0:
the float32 preset agrees with the float32 reference to 2e-3 (two
float32 programs that order their sums differently: measured 6e-5); two
paths of the PROGRAM that must compute the same thing (a prompt in two or
three launches against one, a kernel under the interpreter against its
XLA form) agree to 1e-4. Where an array must not have moved at all
(Mamba's conv after `causal_conv` gave up its bias and activation, a
slot's carry across another slot's step) the comparison is exact.
"""

import dataclasses
import importlib.util
import os
import time
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import (
    InferenceScheduler,
    ModelRunner,
    PrefillRow,
    RunnerConfig,
)
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
VS_REFERENCE, SAME_PROGRAM = 2e-3, 1e-4
PAGE, WIDTH, SLOTS = 4, 24, 4
GREEDY = (0.0, 1.0, 0, 0)
TYPES = {"C": "conv", "*": "full_attention"}


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "lfm2_reference",
        os.path.join(ROOT, "benchmarks", "references", "lfm2.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference_config(c, weight_seed=0) -> dict:
    """The configuration-file keys the reference reads, for a preset."""
    lo, hi = c.held_experts
    return {
        "dtype": c.dtype, "weight_seed": weight_seed,
        "hidden_size": c.hidden,
        "layer_types": [TYPES[k] for k in c.layer_pattern[::2]],
        "conv_L_cache": c.conv_kernel,
        "num_attention_heads": c.n_q_heads,
        "num_key_value_heads": c.n_kv_heads,
        "num_dense_layers": c.layer_pattern.count("D"),
        "intermediate_size": c.mlp_hidden,
        "moe_intermediate_size": c.expert_mlp_hidden,
        "num_experts": hi - lo, "num_experts_published": c.n_experts,
        "experts_held": [lo, hi],
        "num_experts_per_tok": c.n_experts_active,
        "use_expert_bias": c.moe_selection_bias,
        "norm_topk_prob": c.moe_norm_topk,
        "routed_scaling_factor": c.moe_routed_scale,
        "rope_theta": c.rope_theta, "norm_eps": c.rms_eps,
        "tie_word_embeddings": c.tie_embeddings,
        "vocab_size": c.vocab_size,
    }


CONFIG = dataclasses.replace(get_config("tiny-lfm2-test"), dtype="float32")
N_CONV = CONFIG.layer_pattern.count("C")


def make_runner(config=CONFIG, buckets=(16, 32, 64), params=None):
    return ModelRunner(
        config,
        RunnerConfig(page_size=PAGE, num_pages=96, max_batch=SLOTS,
                     max_pages_per_seq=WIDTH, prefill_buckets=buckets),
        make_mesh(MeshConfig()), seed=0, params=params)


@pytest.fixture(scope="module")
def runner():
    return make_runner()


@pytest.fixture(scope="module")
def reference():
    return load_reference()


def table_for(slot: int) -> np.ndarray:
    """A block table of its own per slot (page 0 is the scratch page)."""
    table = np.zeros(WIDTH, np.int32)
    n = WIDTH - 4
    table[:n] = 1 + slot * n + np.arange(n)
    return table


def decode_logits(runner, rows: dict) -> np.ndarray:
    """One decode step; rows = {slot: (last token, its position)}.
    Returns the raw logits [SLOTS, vocab]."""
    tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    tables = np.zeros((SLOTS, WIDTH), np.int32)
    lens, active = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, bool)
    for slot, (token, at) in rows.items():
        tokens[slot], pos[slot], lens[slot] = token, at, at + 1
        tables[slot], active[slot] = table_for(slot), True
    runner.decode(tokens, pos, tables, lens, active,
                  np.zeros(SLOTS, np.float32), np.ones(SLOTS, np.float32),
                  np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.uint32),
                  want_logits=True)
    return runner.last_decode_logits


def prefill(runner, prompt, slot, start=0, chunks=None, between=None) -> int:
    """Prefill `prompt` into `slot` in the given chunk sizes (each a
    launch of its own, padded to its bucket); `between` runs between two
    launches."""
    token = None
    for size in chunks or [len(prompt) - start]:
        if start and between is not None:
            between()
        token = runner.prefill_chunk(
            np.asarray(prompt[start:start + size], np.int32), start,
            table_for(slot), start + size, GREEDY, slot=slot)
        start += size
    assert start == len(prompt)
    return token


def reference_logits(reference, config, prompt, served, lower=None):
    return reference.logits_for(
        [{"prompt": list(map(int, prompt)),
          "served": list(map(int, served))}],
        reference_config(config), 128, lower)[0]


def prompt_of(n: int, seed: int = 0) -> list[int]:
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


# -- against the reference ------------------------------------------------------


def test_prefill_then_decode_agrees_with_the_reference(runner, reference):
    """Through the page pool (two kv heads a lane tile) and the conv
    carries: a prefill in two launches, then six decode steps
    teacher-forced on the tokens the runner sampled; each step's logits
    against the reference's one full forward pass."""
    prompt = prompt_of(43)
    served = [prefill(runner, prompt, slot=2, chunks=[32, 11])]
    got = []
    for _ in range(6):
        row = decode_logits(runner, {2: (served[-1],
                                         len(prompt) + len(served) - 1)})[2]
        got.append(row)
        served.append(int(row.argmax()))
    want = reference_logits(reference, CONFIG, prompt, served)
    assert 0.5 < want.std() < 2.0  # a spread of ~1: the tolerance means something
    assert served[0] == int(want[0].argmax())
    np.testing.assert_allclose(np.stack(got), want[1:], atol=VS_REFERENCE)
    # a tied head that does not answer every token with itself: the
    # self-logit lies inside twice the spread at 16 mixers and hidden 256
    # (`branch_gain`; under a third of it at the published 24 and 2048)
    ids = np.asarray(prompt[-1:] + served[:-1])
    assert np.abs(want[np.arange(len(ids)), ids]).mean() < 2.0


def all_logits(config, params, prompt):
    """The program's forward over one whole prompt, every position's
    logits, under `config` with weights that may be another's."""
    from dynamo_tpu.models.hybrid import forward_hybrid, make_state_cache
    from dynamo_tpu.models.transformer import make_kv_cache

    t = len(prompt)
    pages = -(-t // PAGE)
    _, _, logits, _ = forward_hybrid(
        params, config, jnp.asarray([prompt], jnp.int32),
        jnp.arange(t)[None], make_kv_cache(config, pages + 1, PAGE),
        make_state_cache(config, 1), jnp.asarray([0]),
        jnp.arange(1, pages + 1)[None], jnp.asarray([t]),
        jnp.ones((1, t), bool), jnp.asarray([t - 1]), all_logits=True)
    return np.asarray(logits[0])


MOVED = {
    "qk_norm": dict(qk_norm=False),
    "moe_selection_bias": dict(moe_selection_bias=False),
    "moe_scoring": dict(moe_scoring="softmax"),
    "conv_kernel": dict(conv_kernel=2),
}


@pytest.mark.parametrize("field", [None, *sorted(MOVED)])
def test_the_norms_the_bias_and_the_taps_are_applied(reference, field):
    """The sound weights under a config with ONE thing of this family
    moved: the sound config agrees with the reference at every position;
    without the q and k norms, without the selection bias (it changes
    which experts a token is given), with a softmax router or with two
    taps of the three it does not."""
    from dynamo_tpu.models.transformer import init_params

    prompt = prompt_of(48, seed=2)
    params = init_params(jax.random.PRNGKey(0), CONFIG)
    want = reference_logits(reference, CONFIG, prompt[:1], prompt[1:] + [0])
    if field is None:
        got = all_logits(CONFIG, params, prompt)
        np.testing.assert_allclose(got, want, atol=VS_REFERENCE)
        return
    moved = dataclasses.replace(CONFIG, **MOVED[field])
    if field == "conv_kernel":  # the newest two taps of the three
        params = {**params, "layers": [
            {**lp, "conv_w": lp["conv_w"][1:]} if "conv_w" in lp else lp
            for lp in params["layers"]]}
    if field in ("moe_selection_bias", "moe_scoring"):
        params = {**params, "layers": [
            {k: v for k, v in lp.items() if k != "e_bias"}
            for lp in params["layers"]]}
    got = all_logits(moved, params, prompt)
    assert np.abs(got - want).max() > 0.05, field


def test_the_expert_bias_changes_the_choice(reference):
    """The seeded bias (0.02 x normal beside scores of spread 0.2) gives
    a stated share of tokens another set of experts than the raw scores
    would: the program's router and the reference's agree on every
    token's set, and for 7% of tokens here (8 experts top-2; 36% at the
    published 32 top-4, where four cuts lie among closer scores) that set
    is not the unbiased one. Without it the bias would be untested."""
    from dynamo_tpu.models.hybrid import init_hybrid_layer
    from dynamo_tpu.models.transformer import _routing_weights

    mixer = CONFIG.layer_pattern.index("E")
    lp = init_hybrid_layer(jax.random.PRNGKey(4), CONFIG, mixer)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 512, CONFIG.hidden))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))
    _, with_bias = _routing_weights(x, lp, CONFIG)
    _, without = _routing_weights(
        x, {k: v for k, v in lp.items() if k != "e_bias"}, CONFIG)
    moved = np.mean(np.any(np.sort(with_bias[0], -1)
                           != np.sort(without[0], -1), axis=-1))
    assert 0.03 < moved < 0.3, moved
    cfg = reference_config(CONFIG)
    w = reference.expert_weights(jax.random.PRNGKey(4), cfg, mixer)
    np.testing.assert_array_equal(np.asarray(lp["e_bias"]),
                                  np.asarray(w["bias"]))
    with jax.default_matmul_precision("highest"):
        per_expert = np.asarray(reference.routing(x[0], w, cfg, {}))
    chosen = np.zeros_like(per_expert, bool)
    np.put_along_axis(chosen, np.asarray(with_bias[0]), True, axis=-1)
    assert ((per_expert > 0) == chosen).mean() > 0.999  # ties aside


def test_the_references_controls_move_it(reference):
    """Each control of the configuration's file changes one thing in the
    reference, and only then; an unknown value is refused."""
    prompt, served = prompt_of(60, seed=4), prompt_of(9, seed=5)
    sound = reference_logits(reference, CONFIG, prompt, served)
    again = reference_logits(reference, CONFIG, prompt, served, lower={})
    np.testing.assert_array_equal(sound, again)
    for lower in ({"act": "fp8"}, {"conv_gate": "off"}, {"conv_taps": 2},
                  {"qk_norm": "off"}, {"router_bias": "off"},
                  {"router": "softmax"}, {"kv_bits": 8}):
        low = reference_logits(reference, CONFIG, prompt, served, lower)
        assert np.abs(low - sound).max() > 1e-3, lower
    for bad in ({"act": "int3"}, {"conv_gate": "on"}, {"conv_taps": 1},
                {"qk_norm": "x"}, {"router_bias": "x"},
                {"router": "tanh"}, {"kv_bits": 3}):
        with pytest.raises(ValueError):
            reference_logits(reference, CONFIG, prompt, served, bad)


# -- the conv carry from launch to launch ---------------------------------------


@pytest.mark.parametrize("chunks", [[32, 21], [16, 32, 5], [27, 13, 13]])
def test_a_prompt_in_two_or_three_launches_equals_one(runner, chunks):
    """The conv carry from one launch to the next: every launch is
    padded to its bucket (27 and 13 to 32 and 16, 21 to 32, 5 to 16),
    the padding advances nothing, and a decode step of another slot
    between two launches leaves the waiting slot's carry alone, to the
    bit."""
    prompt = prompt_of(53, seed=1)
    whole = prefill(runner, prompt, slot=0)
    other = prefill(runner, prompt_of(6, seed=14), slot=3)
    kept = []

    def idle_step():
        before = [np.asarray(c[1]) for c in runner.cache[1]["conv"]]
        decode_logits(runner, {3: (other, 6)})  # slot 1 is not active
        kept.extend(np.array_equal(b, np.asarray(c[1])) for b, c in zip(
            before, runner.cache[1]["conv"]))

    parts = prefill(runner, prompt, slot=1, chunks=chunks, between=idle_step)
    assert whole == parts and kept and all(kept)
    logits = decode_logits(runner, {0: (whole, 53), 1: (parts, 53)})
    np.testing.assert_allclose(logits[0], logits[1], atol=SAME_PROGRAM)
    # a carry a conv MIXER and no SSM state at all
    assert len(runner.cache[1]["conv"]) == N_CONV == 6
    assert runner.cache[1]["ssm"] == []
    for layer in range(N_CONV):
        assert runner.cache[1]["conv"][layer].shape == (SLOTS, 2, CONFIG.hidden)
        np.testing.assert_allclose(runner.cache[1]["conv"][layer][0],
                                   runner.cache[1]["conv"][layer][1],
                                   atol=SAME_PROGRAM)


def test_a_reused_slot_starts_from_zero(runner):
    """A row at position 0 starts from zero state whatever its slot
    held: the same prompt after another sequence ran in the slot gives
    the token and the carries it gave in a clean one."""
    prompt = prompt_of(21, seed=30)
    clean = make_runner()
    want = prefill(clean, prompt, slot=1)
    dirty = prefill(runner, prompt_of(40, seed=31), slot=1)
    decode_logits(runner, {1: (dirty, 40)})
    assert prefill(runner, prompt, slot=1) == want
    for got, ref in zip(runner.cache[1]["conv"], clean.cache[1]["conv"]):
        np.testing.assert_allclose(got[1], ref[1], atol=SAME_PROGRAM)


def test_a_batch_of_fresh_and_continued_rows_equals_each_alone(runner):
    """Three rows of 5, 16 and 11 tokens in one [4, 16] launch, the
    middle one a continuation at position 32: it takes its slot's carry
    up, the others start from zero, the empty row's write is dropped and
    advances nothing; and the launches are counted by carry."""
    prompts = [prompt_of(5, 10), prompt_of(48, 11), prompt_of(11, 12)]
    alone = [prefill(runner, p, slot=i) for i, p in enumerate(prompts)]
    want = decode_logits(runner, {i: (alone[i], len(p))
                                  for i, p in enumerate(prompts)})
    prefill(runner, prompts[1][:32], slot=1)
    idle = [np.asarray(c[3]) for c in runner.cache[1]["conv"]]
    before = (dict(runner.ssm_prefill_positions),
              dict(runner.ssm_prefill_rows))
    rows = [PrefillRow(np.asarray(p[start:], np.int32), start,
                       table_for(slot), len(p), GREEDY, 0, slot)
            for slot, (p, start) in enumerate(zip(prompts, (0, 32, 0)))]
    tokens = np.asarray(runner.prefill_chunk_batch(rows))
    assert tokens[:3].tolist() == alone
    for was, conv in zip(idle, runner.cache[1]["conv"]):
        np.testing.assert_array_equal(was, np.asarray(conv[3]))
    got = decode_logits(runner, {i: (alone[i], len(p))
                                 for i, p in enumerate(prompts)})
    np.testing.assert_allclose(got[:3], want[:3], atol=SAME_PROGRAM)
    # valid positions x 6 conv mixers, and rows, by carry; no scan ran
    assert runner.ssm_prefill_positions == {
        "fresh": before[0]["fresh"] + (5 + 11) * N_CONV,
        "continued": before[0]["continued"] + 16 * N_CONV}
    assert runner.ssm_prefill_rows == {
        "fresh": before[1]["fresh"] + 2,
        "continued": before[1]["continued"] + 1}
    assert runner.ssm_scan_launches == {"kernel": 0, "xla": 0}
    assert "ssm_scan" not in runner.kernel_paths()


def test_the_fused_block_equals_single_steps(runner):
    prompt = prompt_of(12, seed=5)
    args = (np.zeros(SLOTS, np.float32), np.ones(SLOTS, np.float32),
            np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.uint32))

    def batch(token, at):
        tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
        tables = np.zeros((SLOTS, WIDTH), np.int32)
        lens, active = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, bool)
        tokens[1], pos[1], lens[1], active[1] = token, at, at + 1, True
        tables[1] = table_for(1)
        return tokens, pos, tables, lens, active

    first = prefill(runner, prompt, slot=1)
    singles, token = [], first
    for i in range(8):
        token = int(runner.decode(*batch(token, 12 + i), *args)[1])
        singles.append(token)
    carry_after = [np.asarray(c[1]) for c in runner.cache[1]["conv"]]
    assert prefill(runner, prompt, slot=1) == first  # from zero again
    fused = runner.decode_multi(*batch(first, 12), *args, k=8)
    assert fused[:, 1].tolist() == singles
    for layer, want in enumerate(carry_after):
        np.testing.assert_allclose(runner.cache[1]["conv"][layer][1], want,
                                   atol=SAME_PROGRAM)


def test_the_kernels_through_the_runner_give_the_xla_paths_answers(
        monkeypatch):
    """head_dim 64: the pool holds a token as [kv heads / 2, 128] and the
    decode kernel under the Pallas interpreter, through the runner,
    gives the XLA path's tokens and logits, and the conv carries beside
    it to the bit; prefill takes the blocked XLA form on both (no
    prefill kernel takes the geometry), and the engine says which path
    each took."""
    prompt = prompt_of(37, seed=40)
    plain = make_runner()
    assert plain.cache[0][0].shape == (2, 2, 96, PAGE, 1, 128)
    assert CONFIG.kv_heads_per_lane_tile == 2
    want = prefill(plain, prompt, slot=1, chunks=[32, 5])
    want_logits = decode_logits(plain, {1: (want, 37)})[1]
    assert plain.kernel_paths()["prefill_attention"] == "xla"
    monkeypatch.setenv("DYNT_ATTENTION", "pallas")
    runner = make_runner()
    paths = runner.kernel_paths()
    assert (paths["decode_attention"], paths["prefill_attention"]) == (
        "interpret", "xla")
    assert prefill(runner, prompt, slot=1, chunks=[32, 5]) == want
    assert runner.prefill_attn_launches == {"kernel": 0, "xla": 2}
    got = decode_logits(runner, {1: (want, 37)})[1]
    np.testing.assert_allclose(got, want_logits, atol=SAME_PROGRAM)
    for kernel, xla in zip(runner.cache[1]["conv"], plain.cache[1]["conv"]):
        np.testing.assert_allclose(kernel[1], xla[1], atol=SAME_PROGRAM)


@pytest.mark.parametrize("slots,hidden", [(4, 256), (48, 384), (256, 2048)])
def test_the_conv_mixers_decode_step_is_a_prefill_of_one_token(slots, hidden):
    """`short_conv_decode` over [slots] against `short_conv_prefill` over
    [slots, 1]: one token joins each slot's carry under the same taps,
    so the mixer's output and the carry coming out are the prefill's to
    the bit, and an inactive slot (no valid position) keeps its carry."""
    import dataclasses

    from dynamo_tpu.models.hybrid import short_conv_decode, short_conv_prefill

    config = dataclasses.replace(CONFIG, hidden=hidden)
    ks = jax.random.split(jax.random.PRNGKey(slots), 6)

    def draw(key, shape, fan_in):
        return (jax.random.normal(key, shape) / fan_in ** 0.5
                ).astype(jnp.bfloat16)

    lp = {"in_proj": draw(ks[0], (hidden, 3 * hidden), hidden),
          "conv_w": draw(ks[1], (3, hidden), 3),
          "out_proj": draw(ks[2], (hidden, hidden), hidden)}
    carry = draw(ks[3], (slots, 2, hidden), 1)
    x = draw(ks[4], (slots, hidden), 1)
    active = jax.random.bernoulli(ks[5], 0.7, (slots,))
    want, want_carry = short_conv_prefill(x[:, None], lp, config, carry,
                                          active[:, None])
    got, got_carry = short_conv_decode(x, lp, config, carry, active)
    live = np.asarray(active)
    np.testing.assert_array_equal(np.asarray(got, np.float32)[live],
                                  np.asarray(want[:, 0], np.float32)[live])
    np.testing.assert_array_equal(np.asarray(got_carry, np.float32),
                                  np.asarray(want_carry, np.float32))
    np.testing.assert_array_equal(np.asarray(got_carry, np.float32)[~live],
                                  np.asarray(carry, np.float32)[~live])


@pytest.mark.parametrize("kh,group", [(8, 4), (2, 2), (4, 1)])
def test_the_packed_pool_kernel_agrees_with_its_oracle(kh, group):
    """`paged_attention_decode_pool` over a pool of 128-lane rows that
    hold two kv heads each, against `paged_attention_decode_xla` over the
    same pool: ragged histories, an empty row, a row of one token; bf16
    operands, so a rounding of the probabilities apart."""
    from dynamo_tpu.models.transformer import paged_attention_decode_xla
    from dynamo_tpu.ops.paged_attention import paged_attention_decode_pool

    b, hd, width = 6, 64, 8
    pages = b * width + 1
    ks = jax.random.split(jax.random.PRNGKey(kh), 4)

    def draw(key, shape):
        return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)

    pool = draw(ks[0], (2, 2, pages, 16, kh // 2, 128))
    q = draw(ks[1], (b, 1, kh * group, hd))
    k_cur, v_cur = draw(ks[2], (b, 1, kh, hd)), draw(ks[3], (b, 1, kh, hd))
    tables = jnp.asarray(1 + np.random.default_rng(0).permutation(
        pages - 1).reshape(b, width), jnp.int32)
    lens = jnp.asarray([1, 0, 17, 128, 77, 100], jnp.int32)
    got = paged_attention_decode_pool(q, pool, 1, tables, lens, k_cur,
                                      v_cur, interpret=True)
    want = paged_attention_decode_xla(q, pool, 1, tables, lens, k_cur, v_cur)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               atol=2e-2)


def test_mambas_conv_gives_the_bits_it_gave():
    """`causal_conv` hands back the taps' sum in float32 and its callers
    add bias and activation: a Mamba mixer's prefill is, to the bit, what
    it was when silu(conv + b) was baked into the conv (restated here as
    it stood), and the carry too."""
    from dynamo_tpu.models.hybrid import init_hybrid_layer, mamba_prefill
    from dynamo_tpu.ops import ssm

    config = get_config("tiny-granite-test")  # bf16, as served
    lp = init_hybrid_layer(jax.random.PRNGKey(3), config, 0)
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 32, config.hidden),
                          jnp.float32).astype(jnp.bfloat16)
    conv = jax.random.normal(
        jax.random.PRNGKey(6), (3, config.conv_kernel - 1,
                                config.mamba_conv_dim),
        jnp.float32).astype(jnp.bfloat16)
    state = jnp.zeros((3, config.mamba_heads, config.mamba_head_dim,
                       config.ssm_state), jnp.float32)
    valid = jnp.arange(32)[None, :] < jnp.asarray([32, 20, 0])[:, None]

    def as_it_stood(carry, x, weight, bias, n_valid):
        k = weight.shape[0]
        prev = carry.astype(x.dtype)
        seq = jnp.concatenate([prev, x], axis=1)
        t = x.shape[1]
        out = sum(seq[:, i:i + t].astype(jnp.float32)
                  * weight[i].astype(jnp.float32) for i in range(k))
        out = jax.nn.silu(out + bias.astype(jnp.float32)).astype(x.dtype)
        idx = n_valid[:, None] + jnp.arange(k - 1)[None, :]
        new_carry = jnp.where(
            (idx >= k - 1)[:, :, None],
            jnp.take_along_axis(
                x, jnp.clip(idx - (k - 1), 0, t - 1)[:, :, None], axis=1),
            jnp.take_along_axis(prev, jnp.minimum(idx, k - 2)[:, :, None],
                                axis=1))
        return out, new_carry.astype(carry.dtype)

    xbc = jax.random.normal(jax.random.PRNGKey(7),
                            (3, 32, config.mamba_conv_dim),
                            jnp.float32).astype(jnp.bfloat16)
    n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
    old_out, old_carry = jax.jit(as_it_stood)(conv, xbc, lp["conv_w"],
                                              lp["conv_b"], n_valid)

    @jax.jit
    def now(conv, xbc, n_valid):
        out, carry = ssm.causal_conv(conv, xbc, lp["conv_w"], n_valid)
        return jax.nn.silu(out + lp["conv_b"].astype(jnp.float32)
                           ).astype(xbc.dtype), carry

    new_out, new_carry = now(conv, xbc, n_valid)
    np.testing.assert_array_equal(np.asarray(old_out, np.float32),
                                  np.asarray(new_out, np.float32))
    np.testing.assert_array_equal(np.asarray(old_carry, np.float32),
                                  np.asarray(new_carry, np.float32))
    # and through the mixer: finite, the carry of a row without a valid
    # position (n_valid 0) is the one it came with
    out, carry, _ = mamba_prefill(x, lp, config, conv, state, valid)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(carry[2], np.float32),
                                  np.asarray(conv[2], np.float32))


# -- the expert layer -------------------------------------------------------------


def expert_layer(config, seed=4):
    from dynamo_tpu.models.hybrid import init_hybrid_layer

    return init_hybrid_layer(jax.random.PRNGKey(seed), config,
                             CONFIG.layer_pattern.index("E"))


def test_the_shares_add_up_to_the_uncut_references_layer(reference):
    """Experts 0:4 on one chip, 4:8 on the other, each with the router
    and its bias whole: the two partial results are the uncut
    REFERENCE's layer (sigmoid scores, the top-2 of scores + bias, the
    unbiased scores renormalised over their sum + 1e-6; SwiGLU
    experts, none shared)."""
    from dynamo_tpu.models.hybrid import moe_mixer

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, CONFIG.hidden))
    valid = jnp.ones((2, 9), bool)
    parts = []
    for share in ("0:4", "4:8"):
        config = cut_config(CONFIG, experts=share)
        out, stats = moe_mixer(x, expert_layer(config), config, valid, "xla")
        assert np.asarray(stats)[-3:].tolist()[0] == 0  # none dropped
        parts.append(out)
    mixer = CONFIG.layer_pattern.index("E")
    want_w = reference.expert_weights(jax.random.PRNGKey(4),
                                      reference_config(CONFIG), mixer)
    with jax.default_matmul_precision("highest"):
        want = reference.moe_mixer(x.reshape(18, -1), want_w,
                                   reference_config(CONFIG), {})
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(
        (parts[0] + parts[1]).reshape(18, -1) / scale, want / scale,
        atol=1e-5)


# -- the preset, its cut, what is refused ------------------------------------------


def test_the_published_preset_and_its_stage():
    config = get_config("lfm2-8b-a1b")
    pattern = config.layer_pattern
    assert (len(pattern), config.mixers_per_layer) == (48, 2)
    assert (pattern.count("C"), pattern.count("*"), pattern.count("D"),
            pattern.count("E")) == (18, 6, 2, 22)
    assert [i // 2 for i, k in enumerate(pattern) if k == "*"] == [
        2, 6, 10, 14, 18, 21]
    assert pattern[1] == pattern[3] == "D" and "D" not in pattern[4:]
    assert (config.head_dim, config.n_q_heads, config.n_kv_heads) == (
        64, 32, 8)
    assert config.qk_norm and config.tie_embeddings and config.use_rope
    assert (config.conv_kernel, config.moe_renorm_eps) == (3, 1e-6)
    assert not config.multipliers
    # the pool: two kv heads a 128-lane row
    assert (config.kv_cache_heads, config.kv_cache_head_dim) == (4, 128)
    stage = cut_config(config, layers=12)
    assert stage.layer_pattern == "CDCD*ECECECE*ECECECE*ECE"
    assert stage.n_layers == 24 and stage.n_experts == 32
    assert stage.state_layers == (0, 2, 6, 8, 10, 14, 16, 18, 22)
    assert stage.ssm_layers == () and stage.state_layers
    assert stage.kv_layers == (4, 12, 20) and stage.window_kv_layers == ()
    from dynamo_tpu.models.hybrid import state_slot_bytes

    # 9 carries of [2, 2048] bf16 a slot: 73,728 B, and no SSM state
    assert state_slot_bytes(stage) == 9 * 2 * 2048 * 2 == 73728
    for bad in (dict(layers=25), dict(experts="16:33"), dict(vocab_rows=0)):
        with pytest.raises(ValueError):
            cut_config(config, **bad)


def test_a_cut_keeps_pangus_one_dense_block_and_this_familys_stage():
    """`--serve-layers` of a stack whose blocks all have the same token
    mixer keeps ONE leading dense block and then the expert blocks
    (pangu_ultra_moe's cell: 1 + 4 of 61); a stack whose token mixers
    differ by block is cut as it lies, both dense blocks in it."""
    pangu = get_config("openpangu-ultra-moe-718b")
    assert cut_config(pangu, layers=5).layer_pattern == "LDLELELELE"
    assert cut_config(pangu, layers=1).layer_pattern == "LD"
    with pytest.raises(ValueError, match="one dense block"):
        cut_config(pangu, layers=60)
    lfm2 = get_config("lfm2-8b-a1b")
    assert cut_config(lfm2, layers=4).layer_pattern == "CDCD*ECE"
    assert cut_config(lfm2, layers=24).layer_pattern == lfm2.layer_pattern
    tiny = get_config("tiny-lfm2-test")
    assert tiny.layer_pattern == "CDCD*ECECECE*ECE"
    assert cut_config(tiny, layers=3).layer_pattern == "CDCD*E"


def test_pages_alone_are_not_handed_over_and_launches_are_bounded(runner):
    with pytest.raises(RuntimeError, match="recurrent state"):
        runner.gather_pages([1, 2])
    # contexts to 96 run past the 64-token budget: rows x bucket bounded
    assert runner.bounds_prefill_launches
    assert runner.prefill_launch_fits([30, 30])
    assert not runner.prefill_launch_fits([40, 10])
    # no Mamba layer: no bucket is held back for a scan's chunk
    assert runner.config.prefill_buckets == (16, 32, 64)


# -- the scheduler --------------------------------------------------------------


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


def wait_for(collectors, seconds=180):
    deadline = time.time() + seconds
    while (any(c.finish is None for c in collectors)
           and time.time() < deadline):
        time.sleep(0.02)
    assert all(c.finish is not None for c in collectors)


def test_the_scheduler_carries_the_conv_state_and_publishes_it(reference):
    """Five requests over four slots through the real loop, three of them
    past the 32-token budget: bounded launches, batched prefill, decode
    blocks between a prompt's launches, a slot reused. Every stream is
    the reference's greedy continuation; the launches were counted by
    carry; and the worker publishes the slot-milliseconds under the conv
    gauge alone (there is no SSM state to count)."""
    import types

    from dynamo_tpu.engine.worker import TpuWorker
    from dynamo_tpu.runtime.metrics import REGISTRY

    runner = make_runner(buckets=(16, 32))
    sched = InferenceScheduler(runner)
    sched.decode_block = 4
    prompts = [prompt_of(n, seed=20 + i)
               for i, n in enumerate((21, 70, 9, 50, 75))]
    collectors = [Collect() for _ in prompts]
    sched.start()
    try:
        for p, c in zip(prompts, collectors):
            sched.submit(request(p, 10), c)
        wait_for(collectors)
    finally:
        sched.stop()
    assert [c.finish for c in collectors] == ["length"] * 5
    for p, c in zip(prompts, collectors):
        want = reference_logits(reference, CONFIG, p, c.tokens())
        gap = want.max(-1) - want[np.arange(10), c.tokens()]
        assert gap.max() < VS_REFERENCE
    assert sched.stats.state_slot_ms > 0
    rows, positions = runner.ssm_prefill_rows, runner.ssm_prefill_positions
    assert rows["fresh"] == 5 and rows["continued"] >= 2 + 1 + 2
    assert positions["fresh"] + positions["continued"] == N_CONV * sum(
        len(p) for p in prompts)
    TpuWorker._publish_engine_gauges(types.SimpleNamespace(
        scheduler=types.SimpleNamespace(stats=sched.stats, win_pool=None),
        runner=runner, instance_id=0x1f2, model_config=CONFIG,
        mesh=types.SimpleNamespace(local_devices=[]),
        outbox=types.SimpleNamespace(handovers=0)))
    assert REGISTRY.get_sample_value(
        "dynamo_ssm_state_slot_ms", {"worker": "1f2"}) == pytest.approx(
            sched.stats.state_slot_ms)
    assert REGISTRY.get_sample_value(
        "dynamo_ssm_prefill_positions_total",
        {"worker": "1f2", "carry": "continued"}) == positions["continued"]
    assert REGISTRY.get_sample_value(
        "dynamo_ssm_scan_launches_total",
        {"worker": "1f2", "path": "xla"}) is None
