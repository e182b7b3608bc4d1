"""The granitemoehybrid stack (granite-4.0-h-small: a Mamba-2 or
attention mixer then SwiGLU experts with a shared one in every block,
the family's four multipliers, a tied head) on the served path, against
its plain reference (benchmarks/references/granite_h.py, which imports
nothing of the program), at a tiny size on the CPU with seeded weights:
one period of the published pattern, 4 Mamba heads in one group, 8
experts top-3, all four multipliers off 1.

Tolerances as in test_hybrid_model.py, on logits whose spread is ~1.0:
the float32 preset agrees with the float32 reference to 2e-3; two paths
of the PROGRAM that must compute the same thing (a prompt in two or three
launches against one, batched against alone, fused against single steps)
agree to 1e-4.
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
TYPES = {"M": "mamba", "*": "attention"}


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "granite_h_reference",
        os.path.join(ROOT, "benchmarks", "references", "granite_h.py"))
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
        "mamba_n_heads": c.mamba_heads, "mamba_d_head": c.mamba_head_dim,
        "mamba_n_groups": c.ssm_groups, "mamba_d_state": c.ssm_state,
        "mamba_d_conv": c.conv_kernel,
        "num_attention_heads": c.n_q_heads,
        "num_key_value_heads": c.n_kv_heads,
        "num_local_experts": hi - lo, "num_local_experts_published":
        c.n_experts, "experts_held": [lo, hi],
        "intermediate_size": c.expert_mlp_hidden,
        "shared_intermediate_size": c.shared_expert_hidden,
        "num_experts_per_tok": c.n_experts_active,
        "embedding_multiplier": c.embedding_multiplier,
        "residual_multiplier": c.residual_multiplier,
        "attention_multiplier": c.attention_multiplier,
        "logits_scaling": c.logits_scaling,
        "tie_word_embeddings": c.tie_embeddings,
        "rms_norm_eps": c.rms_eps,
        "time_step_min": c.ssm_dt_min, "time_step_max": c.ssm_dt_max,
        "time_step_floor": c.ssm_dt_floor, "vocab_size": c.vocab_size,
    }


CONFIG = dataclasses.replace(get_config("tiny-granite-test"), dtype="float32")


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
    """Through the page pool and the state cache: a prefill in two
    launches, then six decode steps teacher-forced on the tokens the
    runner sampled; each step's logits against the reference's one full
    forward pass."""
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
    # a tied head of spread 1 that does not answer every token with
    # itself: the self-logit lies inside the spread (`branch_gain`)
    ids = np.asarray(prompt[-1:] + served[:-1])
    assert np.abs(want[np.arange(len(ids)), ids]).mean() < 1.0


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
    "embedding_multiplier": dict(embedding_multiplier=1.0),
    "residual_multiplier": dict(residual_multiplier=1.0),
    "attention_multiplier": dict(attention_multiplier=0.0),
    "logits_scaling": dict(logits_scaling=1.0),
    "tie_embeddings": dict(tie_embeddings=False),
}


@pytest.mark.parametrize("field", [None, *sorted(MOVED)])
def test_each_multiplier_and_the_tie_is_applied(reference, field):
    """The sound weights under a config with ONE of the four multipliers
    or the tie moved to what every other family has: the sound config
    agrees with the reference at every position, each moved one does not
    (an untied head is read from a matrix of its own)."""
    from dynamo_tpu.models.transformer import init_params, init_top_params

    prompt = prompt_of(48, seed=2)  # whole chunks of the scan
    params = init_params(jax.random.PRNGKey(0), CONFIG)
    want = reference_logits(reference, CONFIG, prompt[:1], prompt[1:] + [0])
    if field is None:
        got = all_logits(CONFIG, params, prompt)
        np.testing.assert_allclose(got, want, atol=VS_REFERENCE)
        return
    moved = dataclasses.replace(CONFIG, **MOVED[field])
    if field == "tie_embeddings":
        keys = jax.random.split(jax.random.PRNGKey(0), CONFIG.n_layers + 2)
        params = {**params, "lm_head": init_top_params(
            keys[0], keys[-1], moved)["lm_head"]}
    got = all_logits(moved, params, prompt)
    assert np.abs(got - want).max() > 0.1, field


def test_the_references_controls_move_it(reference):
    """Each control of the configuration's file changes one thing in the
    reference, and only then; an unknown value is refused."""
    prompt, served = prompt_of(60, seed=4), prompt_of(9, seed=5)
    sound = reference_logits(reference, CONFIG, prompt, served)
    again = reference_logits(reference, CONFIG, prompt, served, lower={})
    np.testing.assert_array_equal(sound, again)
    for lower in ({"act": "fp8"}, {"residual": "one"},
                  {"attn_scale": "rsqrt"}, {"embed": "unscaled"},
                  {"router": "sigmoid"}, {"ssm_state": "bfloat16"},
                  {"kv_bits": 8}):
        low = reference_logits(reference, CONFIG, prompt, served, lower)
        assert np.abs(low - sound).max() > 1e-3, lower
    for bad in ({"act": "int3"}, {"residual": "two"}, {"attn_scale": "x"},
                {"embed": "x"}, {"router": "tanh"}, {"ssm_state": "int8"},
                {"kv_bits": 3}):
        with pytest.raises(ValueError):
            reference_logits(reference, CONFIG, prompt, served, bad)


# -- state carried from launch to launch ----------------------------------------


@pytest.mark.parametrize("chunks", [[32, 21], [16, 32, 5], [27, 13, 13]])
def test_a_prompt_in_two_or_three_launches_equals_one(runner, chunks):
    """State and conv carry from one launch to the next: every launch is
    padded to its bucket (27 and 13 to 32 and 16, 21 to 32, 5 to 16),
    the padding advances nothing, and a decode step of another slot
    between two launches leaves the waiting slot's state alone."""
    prompt = prompt_of(53, seed=1)
    whole = prefill(runner, prompt, slot=0)
    other = prefill(runner, prompt_of(6, seed=14), slot=3)

    def idle_step():
        decode_logits(runner, {3: (other, 6)})  # slot 1 is not active

    parts = prefill(runner, prompt, slot=1, chunks=chunks, between=idle_step)
    assert whole == parts
    logits = decode_logits(runner, {0: (whole, 53), 1: (parts, 53)})
    np.testing.assert_allclose(logits[0], logits[1], atol=SAME_PROGRAM)
    assert len(runner.cache[1]["ssm"]) == 9  # a state a Mamba MIXER
    for layer in range(9):
        np.testing.assert_allclose(runner.cache[1]["ssm"][layer][0],
                                   runner.cache[1]["ssm"][layer][1],
                                   atol=SAME_PROGRAM)
        np.testing.assert_allclose(runner.cache[1]["conv"][layer][0],
                                   runner.cache[1]["conv"][layer][1],
                                   atol=SAME_PROGRAM)


def test_a_batch_of_fresh_and_continued_rows_equals_each_alone(runner):
    """Three rows of 5, 16 and 11 tokens in one [4, 16] launch, the
    middle one a continuation at position 32: it takes its slot's state
    up, the others start from zero, the empty row's write is dropped;
    and the launches are counted by carry."""
    prompts = [prompt_of(5, 10), prompt_of(48, 11), prompt_of(11, 12)]
    alone = [prefill(runner, p, slot=i) for i, p in enumerate(prompts)]
    want = decode_logits(runner, {i: (alone[i], len(p))
                                  for i, p in enumerate(prompts)})
    prefill(runner, prompts[1][:32], slot=1)
    before = (dict(runner.ssm_prefill_positions),
              dict(runner.ssm_prefill_rows))
    rows = [PrefillRow(np.asarray(p[start:], np.int32), start,
                       table_for(slot), len(p), GREEDY, 0, slot)
            for slot, (p, start) in enumerate(zip(prompts, (0, 32, 0)))]
    tokens = np.asarray(runner.prefill_chunk_batch(rows))
    assert tokens[:3].tolist() == alone
    got = decode_logits(runner, {i: (alone[i], len(p))
                                 for i, p in enumerate(prompts)})
    np.testing.assert_allclose(got[:3], want[:3], atol=SAME_PROGRAM)
    # valid positions x 9 Mamba mixers, and rows, by carry
    assert runner.ssm_prefill_positions == {
        "fresh": before[0]["fresh"] + (5 + 11) * 9,
        "continued": before[0]["continued"] + 16 * 9}
    assert runner.ssm_prefill_rows == {
        "fresh": before[1]["fresh"] + 2,
        "continued": before[1]["continued"] + 1}


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
    state_after = [np.asarray(s[1]) for s in runner.cache[1]["ssm"]]
    assert prefill(runner, prompt, slot=1) == first  # from zero again
    fused = runner.decode_multi(*batch(first, 12), *args, k=8)
    assert fused[:, 1].tolist() == singles
    for layer, want in enumerate(state_after):
        np.testing.assert_allclose(runner.cache[1]["ssm"][layer][1], want,
                                   atol=SAME_PROGRAM)


def test_the_kernels_take_the_models_score_scale(monkeypatch):
    """The pool decode kernel and the blocked prefill kernel under the
    Pallas interpreter, through the runner, at this model's score scale
    (1/32 at head_dim 16, not 1/4): the XLA path's tokens and logits."""
    prompt = prompt_of(37, seed=40)
    plain = make_runner()
    want = prefill(plain, prompt, slot=1, chunks=[32, 5])
    want_logits = decode_logits(plain, {1: (want, 37)})[1]
    monkeypatch.setenv("DYNT_ATTENTION", "pallas")
    monkeypatch.setenv("DYNT_SSM", "pallas")
    runner = make_runner()
    paths = runner.kernel_paths()
    assert (paths["decode_attention"], paths["ssm_update"]) == (
        "interpret", "interpret")
    assert prefill(runner, prompt, slot=1, chunks=[32, 5]) == want
    got = decode_logits(runner, {1: (want, 37)})[1]
    np.testing.assert_allclose(got, want_logits, atol=SAME_PROGRAM)


# -- the expert layer -------------------------------------------------------------


def expert_layer(config, seed=4):
    from dynamo_tpu.models.hybrid import init_hybrid_layer

    return init_hybrid_layer(jax.random.PRNGKey(seed), config, 1)


def test_the_shares_add_up_to_the_uncut_references_layer(reference):
    """Experts 0:4 on one chip, 4:8 on the other, each with the router
    and the shared expert whole: the two partial results, the shared
    expert counted once, are the uncut REFERENCE's layer (softmax over
    the three chosen logits; SwiGLU experts and shared expert)."""
    from dynamo_tpu.models.hybrid import moe_mixer

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, CONFIG.hidden))
    valid = jnp.ones((2, 9), bool)
    parts = []
    for share in ("0:4", "4:8"):
        config = cut_config(CONFIG, experts=share)
        out, stats = moe_mixer(x, expert_layer(config), config, valid, "xla")
        assert np.asarray(stats)[-3:].tolist()[0] == 0  # none dropped
        parts.append(out)
    lp = expert_layer(CONFIG)
    u = jnp.einsum("bth,hm->btm", x, lp["s_up"])
    m = u.shape[-1] // 2
    shared = jnp.einsum("btm,mh->bth",
                        jax.nn.silu(u[..., :m]) * u[..., m:], lp["s_down"])
    want_w = reference.expert_weights(jax.random.PRNGKey(4),
                                      reference_config(CONFIG), 1)
    with jax.default_matmul_precision("highest"):
        want = reference.moe_mixer(x.reshape(18, -1), want_w,
                                   reference_config(CONFIG), {})
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(
        (parts[0] + parts[1] - shared).reshape(18, -1) / scale,
        want / scale, atol=1e-5)


# -- the preset, its cut, what is refused ------------------------------------------


def test_the_published_preset_and_its_cut():
    config = get_config("granite-4.0-h-small")
    pattern = config.layer_pattern
    assert (len(pattern), config.mixers_per_layer) == (80, 2)
    assert (pattern.count("M"), pattern.count("*"), pattern.count("E")) == (
        36, 4, 40)
    assert [i // 2 for i, k in enumerate(pattern) if k == "*"] == [
        5, 15, 25, 35]
    assert (config.mamba_inner, config.mamba_conv_dim) == (8192, 8448)
    assert config.multipliers == {
        "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
        "attention_multiplier": 0.0078125, "logits_scaling": 16.0}
    assert config.tie_embeddings and not config.use_rope
    cut = cut_config(config, layers=10, experts="0:36", vocab_rows=50176)
    assert cut.layer_pattern == "MEMEMEMEME*EMEMEMEME" and cut.n_layers == 20
    # counted over mixers, not blocks
    assert cut.state_layers == (0, 2, 4, 6, 8, 12, 14, 16, 18)
    assert cut.kv_layers == (10,) and cut.window_kv_layers == ()
    assert cut.held_experts == (0, 36) and cut.n_experts == 72
    assert cut.hidden == 4096 and cut.vocab_size == 50176
    # 38.2 MB of recurrent state a slot: 9 x (4.19 MB float32 + 50.7 KB)
    from dynamo_tpu.models.hybrid import state_slot_bytes

    assert state_slot_bytes(cut) == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert round(state_slot_bytes(cut) / 1e6, 1) == 38.2
    for bad in (dict(layers=41), dict(experts="36:73"), dict(vocab_rows=0)):
        with pytest.raises(ValueError):
            cut_config(config, **bad)
    # every other preset leaves all four where they were
    from dynamo_tpu.models.config import PRESETS

    assert {name for name, c in PRESETS.items() if c.multipliers} == {
        "granite-4.0-h-small", "tiny-granite-test"}


def test_a_tied_head_is_honoured_by_every_layer_pattern_preset():
    """`init` and `_head` read `tie_embeddings` for a `layer_pattern`
    preset: tied, the tree has no `lm_head` and the logits come off the
    embedding; untied (every preset before this one) nothing moved."""
    from dynamo_tpu.models.transformer import init_params, param_axes

    for name in ("tiny-hybrid-test", "tiny-mellum-test", "tiny-pangu-test",
                 "tiny-granite-test"):
        config = get_config(name)
        params = init_params(jax.random.PRNGKey(0), config)
        assert ("lm_head" in params) == (not config.tie_embeddings)
        assert ("lm_head" in param_axes(config)) == (
            not config.tie_embeddings)
        tied = dataclasses.replace(config, tie_embeddings=True)
        assert "lm_head" not in init_params(jax.random.PRNGKey(0), tied)
        assert "lm_head" not in param_axes(tied)
    assert get_config("tiny-granite-test").tie_embeddings


def test_the_dense_decoder_refuses_a_multiplier_by_name():
    from dynamo_tpu.models.transformer import forward, param_axes

    for field, value in (("embedding_multiplier", 12.0),
                         ("residual_multiplier", 0.22),
                         ("attention_multiplier", 0.0078125),
                         ("logits_scaling", 16.0)):
        config = dataclasses.replace(get_config("tiny-test"),
                                     **{field: value})
        with pytest.raises(ValueError, match=field):
            param_axes(config)
        with pytest.raises(ValueError, match="dense decoder"):
            forward({}, config, None, None, None, None, None)
        with pytest.raises(ValueError, match=field):
            ModelRunner(config, RunnerConfig(page_size=PAGE, num_pages=16,
                                             max_batch=2),
                        make_mesh(MeshConfig()))


def test_launches_are_bounded_where_a_context_runs_past_one():
    """Mamba layers and contexts longer than the token budget: a launch's
    rows x bucket stay inside the budget, and `--prewarm full` walks that
    grid and no other. A model whose contexts fit one launch (the hybrid
    cell's) keeps its rows."""
    runner = make_runner(buckets=(16, 32, 64))  # contexts to 96 > 64
    assert runner.bounds_prefill_launches
    assert runner.prefill_launch_fits([64])
    assert runner.prefill_launch_fits([30, 30])
    assert not runner.prefill_launch_fits([40, 10])  # 2 x 64
    assert runner.prefill_launch_fits([10, 10, 10])  # 4 x 16
    short = ModelRunner(
        CONFIG, RunnerConfig(page_size=PAGE, num_pages=96, max_batch=SLOTS,
                             max_pages_per_seq=16,
                             prefill_buckets=(16, 32, 64)),
        make_mesh(MeshConfig()), seed=0)
    assert not short.bounds_prefill_launches
    hybrid = ModelRunner(
        dataclasses.replace(get_config("tiny-hybrid-test"), dtype="float32"),
        RunnerConfig(page_size=PAGE, num_pages=64, max_batch=SLOTS,
                     max_pages_per_seq=16, prefill_buckets=(16, 32, 64)),
        make_mesh(MeshConfig()), seed=0)
    assert not hybrid.bounds_prefill_launches


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


def test_the_scheduler_carries_state_across_the_launches_of_a_prompt(
        reference):
    """Five requests over four slots through the real loop, three of them
    past the 32-token budget (two, three and three launches): bounded
    launches, batched prefill, decode blocks between a prompt's
    launches, a slot reused. Every stream is the reference's greedy
    continuation, and the launches were counted by carry."""
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
    assert positions["fresh"] + positions["continued"] == 9 * sum(
        len(p) for p in prompts)
    assert positions["continued"] >= 9 * (38 + 18 + 43)
