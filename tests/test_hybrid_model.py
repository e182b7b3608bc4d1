"""The hybrid stack (nemotron_h: Mamba-2, attention and routed-expert
layers, one mixer a layer) on the served path, against its plain
reference (benchmarks/references/nemotron_h.py, which imports nothing of
the program), at a tiny size on the CPU with seeded weights.

Tolerances, on logits whose spread is ~1.0: the float32 preset agrees
with the float32 reference to 2e-3 (the chunked scan and the sequential
one sum in different orders; the program's router and norms are float32
too); two paths of the PROGRAM that must compute the same thing (chunked
vs one launch, batched vs alone, fused vs single steps) agree to 1e-4.
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
PAGE, WIDTH, SLOTS = 4, 16, 4
GREEDY = (0.0, 1.0, 0, 0)


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_reference",
        os.path.join(ROOT, "benchmarks", "references", "nemotron_h.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference_config(c, weight_seed=0) -> dict:
    """The configuration-file keys the reference reads, for a preset."""
    lo, hi = c.held_experts
    return {
        "dtype": c.dtype, "weight_seed": weight_seed,
        "hidden_size": c.hidden,
        "hybrid_override_pattern": c.layer_pattern,
        "mamba_num_heads": c.mamba_heads, "mamba_head_dim": c.mamba_head_dim,
        "n_groups": c.ssm_groups, "ssm_state_size": c.ssm_state,
        "conv_kernel": c.conv_kernel,
        "num_attention_heads": c.n_q_heads,
        "num_key_value_heads": c.n_kv_heads, "head_dim": c.head_dim,
        "n_routed_experts": hi - lo, "n_routed_experts_published": c.n_experts,
        "experts_held": [lo, hi],
        "moe_intermediate_size": c.expert_mlp_hidden,
        "moe_shared_expert_intermediate_size": c.shared_expert_hidden,
        "num_experts_per_tok": c.n_experts_active,
        "routed_scaling_factor": c.moe_routed_scale,
        "norm_topk_prob": c.moe_norm_topk, "layer_norm_epsilon": c.rms_eps,
        "time_step_min": c.ssm_dt_min, "time_step_max": c.ssm_dt_max,
        "time_step_floor": c.ssm_dt_floor, "vocab_size": c.vocab_size,
    }


CONFIG = dataclasses.replace(get_config("tiny-hybrid-test"), dtype="float32")


def make_runner(config=CONFIG, buckets=(16, 32, 64)):
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


def prefill(runner, prompt, slot, start=0, chunks=None) -> int:
    """Prefill `prompt` into `slot` in the given chunk sizes."""
    token = None
    for size in chunks or [len(prompt) - start]:
        token = runner.prefill_chunk(
            np.asarray(prompt[start:start + size], np.int32), start,
            table_for(slot), start + size, GREEDY, slot=slot)
        start += size
    assert start == len(prompt)
    return token


def reference_logits(reference, config, prompt, served):
    with jax.default_matmul_precision("highest"):
        return reference.logits_for(
            [{"prompt": list(map(int, prompt)),
              "served": list(map(int, served))}],
            reference_config(config), 64)[0]


def prompt_of(n: int, seed: int = 0) -> list[int]:
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


def test_prefill_then_decode_agrees_with_the_reference(runner, reference):
    """Through the page pool and the state cache: a prefill, then six
    decode steps teacher-forced on the tokens the runner sampled; each
    step's logits against the reference's one full forward pass."""
    prompt = prompt_of(23)
    served = [prefill(runner, prompt, slot=2)]
    got = []
    for _ in range(6):
        row = decode_logits(runner, {2: (served[-1],
                                         len(prompt) + len(served) - 1)})[2]
        got.append(row)
        served.append(int(row.argmax()))
    want = reference_logits(reference, CONFIG, prompt, served)
    assert want.std() > 0.5  # a spread of ~1: the tolerance means something
    # the prefill's own token is the reference's arg-max at the last
    # prompt position, and every decode step's logits match
    assert served[0] == int(want[0].argmax())
    np.testing.assert_allclose(np.stack(got), want[1:], atol=VS_REFERENCE)


def test_the_bf16_preset_serves_the_references_tokens(reference):
    """The preset as served (bfloat16 activations, float32 state), by the
    benchmark's `gap` (how far the served token's reference logit lies
    under the reference's best), on logits of spread 1: 0 at most
    positions. At 64 hidden units a bf16 rounding can flip which of 8
    experts a token's second choice is, and that position then reads a
    gap of some tenths; garbage reads 2-3 everywhere."""
    config = get_config("tiny-hybrid-test")
    runner = make_runner(config)
    prompt = prompt_of(30, seed=3)
    served = [prefill(runner, prompt, slot=1, chunks=[16, 14])]
    for _ in range(8):
        row = decode_logits(runner, {1: (served[-1],
                                         len(prompt) + len(served) - 1)})[1]
        served.append(int(row.argmax()))
    want = reference_logits(reference, config, prompt, served)
    gap = want.max(-1) - want[np.arange(len(served)), served]
    assert (gap == 0).sum() >= 7 and gap.mean() < 0.15 and gap.max() < 1.5


@pytest.mark.parametrize("chunks", [[16, 7], [8, 8, 7]])
def test_a_prompt_prefilled_in_chunks_equals_one_launch(runner, chunks):
    prompt = prompt_of(23, seed=1)
    whole = prefill(runner, prompt, slot=0)
    parts = prefill(runner, prompt, slot=1, chunks=chunks)
    assert whole == parts
    logits = decode_logits(runner, {0: (whole, 23), 1: (parts, 23)})
    np.testing.assert_allclose(logits[0], logits[1], atol=SAME_PROGRAM)
    for layer in range(len(runner.cache[1]["ssm"])):
        np.testing.assert_allclose(runner.cache[1]["ssm"][layer][0],
                                   runner.cache[1]["ssm"][layer][1],
                                   atol=SAME_PROGRAM)
        np.testing.assert_allclose(runner.cache[1]["conv"][layer][0],
                                   runner.cache[1]["conv"][layer][1],
                                   atol=SAME_PROGRAM)


def test_a_batched_prefill_with_ragged_rows_equals_each_row_alone(runner):
    """Three rows of 5, 16 and 11 tokens in one [4, 16] launch (one row
    and most positions are padding), the middle one a continuation at
    position 16: padding advances no state, and the empty row's write is
    dropped."""
    prompts = [prompt_of(5, 10), prompt_of(32, 11), prompt_of(11, 12)]
    alone = [prefill(runner, p, slot=i) for i, p in enumerate(prompts)]
    want = decode_logits(runner, {i: (alone[i], len(p))
                                  for i, p in enumerate(prompts)})
    want_state = [np.asarray(s) for s in runner.cache[1]["ssm"]]
    # again, batched: row 1's first 16 tokens alone, then the batch
    prefill(runner, prompts[1][:16], slot=1)
    rows = [PrefillRow(np.asarray(p[start:], np.int32), start,
                       table_for(slot), len(p), GREEDY, 0, slot)
            for slot, (p, start) in enumerate(zip(prompts, (0, 16, 0)))]
    tokens = np.asarray(runner.prefill_chunk_batch(rows))
    assert tokens[:3].tolist() == alone
    got = decode_logits(runner, {i: (alone[i], len(p))
                                 for i, p in enumerate(prompts)})
    np.testing.assert_allclose(got[:3], want[:3], atol=SAME_PROGRAM)
    # slot 3 was never written by the padded fourth row
    assert not np.asarray(runner.cache[1]["ssm"][0][3]).any() or np.allclose(
        runner.cache[1]["ssm"][0][3], want_state[0][3])


def test_the_fused_block_equals_single_steps(runner):
    prompt = prompt_of(12, seed=5)
    args = (np.ones(SLOTS, np.float32) * 0, np.ones(SLOTS, np.float32),
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


def test_a_reused_slot_starts_from_zero_and_a_preempted_request_resumes(
        runner):
    """A slot that held another sequence gives the same logits as a
    fresh one (a row at position 0 starts from zero state), and a
    request preempted after 5 tokens and replayed as prompt + generated
    (the cooperative migrate) continues on the logits it would have had."""
    prompt = prompt_of(14, seed=7)
    prefill(runner, prompt_of(29, seed=8), slot=3)  # the slot's last tenant
    fresh = make_runner()
    token = prefill(runner, prompt, slot=3)
    assert token == prefill(fresh, prompt, slot=0)
    generated = [token]
    for i in range(5):
        row = decode_logits(runner, {3: (generated[-1], 14 + i)})[3]
        generated.append(int(row.argmax()))
    uninterrupted = decode_logits(runner, {3: (generated[-1], 19)})[3]
    # preempted: the slot goes to someone else, the request comes back as
    # prompt + generated and is prefilled from zero in another slot
    prefill(runner, prompt_of(9, seed=9), slot=3)
    replay = prompt + generated[:-1]
    assert prefill(runner, replay, slot=0, chunks=[16, 3]) == generated[-1]
    resumed = decode_logits(runner, {0: (generated[-1], 19)})[0]
    np.testing.assert_allclose(resumed, uninterrupted, atol=SAME_PROGRAM)


def test_a_decode_step_leaves_a_slot_between_two_chunks_alone(runner):
    prompt = prompt_of(24, seed=13)
    prefill(runner, prompt[:16], slot=2)
    before = [np.asarray(s[2]) for s in runner.cache[1]["ssm"]]
    other = prefill(runner, prompt_of(6, seed=14), slot=0)
    decode_logits(runner, {0: (other, 6)})  # slot 2 inactive
    for layer, want in enumerate(before):
        np.testing.assert_array_equal(runner.cache[1]["ssm"][layer][2], want)


# -- the expert layer ---------------------------------------------------------


def expert_layer(config, seed=4):
    from dynamo_tpu.models.hybrid import init_hybrid_layer

    layer = config.layer_pattern.index("E")
    return init_hybrid_layer(jax.random.PRNGKey(seed), config, layer)


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0:4 on one chip, 4:8 on the other, each with the router
    and the shared expert whole: the two partial results, the shared
    expert counted once, are the uncut layer's."""
    from dynamo_tpu.models.hybrid import moe_mixer

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, CONFIG.hidden))
    valid = jnp.ones((2, 9), bool)
    # stats: tokens per held expert, then dropped, touched, calls
    whole, stats = moe_mixer(x, expert_layer(CONFIG), CONFIG, valid, "xla")
    counts = stats[:-3]
    parts, held = [], []
    for share in ("0:4", "4:8"):
        config = cut_config(CONFIG, experts=share)
        out, stats = moe_mixer(x, expert_layer(config), config, valid, "xla")
        n, (dropped, touched, calls) = stats[:-3], stats[-3:]
        assert (int(dropped), int(calls)) == (0, 1)
        assert int(touched) == int((np.asarray(n) > 0).sum())
        parts.append(out)
        held.append(np.asarray(n))
    lp = expert_layer(CONFIG)
    shared = jnp.einsum("btm,mh->bth", jnp.square(jax.nn.relu(
        jnp.einsum("bth,hm->btm", x, lp["s_up"]))), lp["s_down"])
    np.testing.assert_allclose(parts[0] + parts[1] - shared, whole,
                               atol=1e-5)
    assert np.concatenate(held).tolist() == np.asarray(counts).tolist()
    assert int(np.asarray(counts).sum()) == 2 * 9 * CONFIG.n_experts_active


@pytest.mark.parametrize("tokens", [7, 64])
def test_dropless_when_every_token_goes_to_one_expert(reference, tokens):
    """Adversarial routing: the selection bias sends every token to
    experts 3 and 5. The capacity path would drop all but a few; here
    every slot is computed, and the layer equals the reference's."""
    from dynamo_tpu.models.hybrid import moe_mixer

    lp = dict(expert_layer(CONFIG))
    lp["e_bias"] = jnp.zeros(8).at[jnp.array([3, 5])].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, tokens, CONFIG.hidden))
    out, stats = moe_mixer(x, lp, CONFIG, jnp.ones((1, tokens), bool), "xla")
    # tokens per expert, then none dropped, two experts touched, one call
    assert np.asarray(stats).tolist() == [0, 0, 0, tokens, 0, tokens, 0, 0,
                                          0, 2, 1]
    w = {**lp, "e_up": jnp.swapaxes(lp["e_up"], 1, 2)}  # stored [E, m, h]
    with jax.default_matmul_precision("highest"):
        want = reference.moe_mixer(x[0], w, reference_config(CONFIG), {})
    np.testing.assert_allclose(out[0], want, atol=1e-4)


def test_the_dropped_counter_counts_rows_the_matmul_was_not_given():
    """`dropped_slots` is no difference of two sums over one mask: it
    asks of each held assignment whether its row in the sorted buffer is
    one the group sizes cover. Group sizes that cover two rows too few
    (a histogram cut short, a capacity) read 2; absent experts' and
    padding's assignments, which sort behind, read nothing."""
    from dynamo_tpu.ops.grouped_matmul import dropped_slots

    here = jnp.array([True, True, False, True, True, False])
    inverse = jnp.array([0, 2, 4, 1, 3, 5])  # held rows 0..3, others behind
    assert int(dropped_slots(here, inverse, 4)) == 0
    assert int(dropped_slots(here, inverse, 2)) == 2
    assert int(dropped_slots(here, inverse, 0)) == 4


def test_the_pallas_state_update_equals_the_reference_path():
    """The in-place decode kernel under the Pallas interpreter: live rows
    updated, dead rows untouched, no live row at all a no-op."""
    from dynamo_tpu.ops.ssm import (
        expand_groups,
        ssm_state_update,
        ssm_state_update_xla,
    )

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    s, h, p, n, g = 6, 4, 16, 32, 2
    state = jax.random.normal(ks[0], (s, h, p, n))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (s, h)) - 3)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,)) * 2)
    x = jax.random.normal(ks[3], (s, h, p))
    b = expand_groups(jax.random.normal(ks[4], (s, g, n)), h)
    c = expand_groups(jax.random.normal(ks[5], (s, g, n)), h)
    for active in ([1, 0, 1, 1, 0, 1], [0] * 6, [1] * 6):
        active = jnp.asarray(active, bool)
        want_s, want_y = ssm_state_update_xla(state, dt, a, x, b, c, active)
        for heads in (None, 2):
            got_s, got_y = ssm_state_update(
                jnp.array(state), dt, a, x, b, c, active,
                heads_per_step=heads, interpret=True)
            np.testing.assert_allclose(got_s, want_s, atol=1e-5)
            np.testing.assert_allclose(got_y, want_y, atol=1e-4)


def test_the_chunked_scan_equals_the_recurrence_a_position_at_a_time():
    """The prefill scan (matmul form, several chunks) against the decode
    update applied position by position: an initial state, padding
    (dt = 0) that advances nothing, a row that is all padding."""
    from dynamo_tpu.ops.ssm import (
        expand_groups,
        ssm_chunk_scan,
        ssm_state_update_xla,
    )

    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    for rows, t, chunk in ((3, 48, 16), (2, 32, 128)):
        h, p, n, g = 4, 16, 32, 2
        state = jax.random.normal(ks[0], (rows, h, p, n))
        valid = jnp.arange(t)[None] < jnp.asarray([t, 20, 0][:rows])[:, None]
        dt = jnp.where(valid[..., None], jax.nn.softplus(
            jax.random.normal(ks[1], (rows, t, h)) - 3), 0.0)
        a = -jnp.exp(jax.random.uniform(ks[2], (h,)) * 2)
        x = jax.random.normal(ks[3], (rows, t, h, p))
        b = jax.random.normal(ks[4], (rows, t, g, n))
        c = jax.random.normal(ks[5], (rows, t, g, n))
        got_s, got_y = ssm_chunk_scan(state, dt, a, x, b, c, chunk=chunk)
        want_s, want_y = state, []
        for i in range(t):
            want_s, y = ssm_state_update_xla(
                want_s, dt[:, i], a, x[:, i], expand_groups(b[:, i], h),
                expand_groups(c[:, i], h), jnp.ones(rows, bool))
            want_y.append(y)
        np.testing.assert_allclose(got_s, want_s, atol=1e-4)
        np.testing.assert_allclose(
            jnp.where(valid[..., None, None],
                      got_y - jnp.stack(want_y, axis=1), 0.0), 0.0, atol=1e-4)
        if rows == 3:  # the all-padding row kept its state
            np.testing.assert_allclose(got_s[2], state[2], atol=1e-6)


def test_the_served_path_with_the_state_kernel_interpreted(monkeypatch):
    """DYNT_SSM=pallas off the TPU runs the decode state-update kernel
    under the interpreter, through the runner: the XLA path's logits."""
    prompt = prompt_of(19, seed=40)
    want = prefill(make_runner(), prompt, slot=1, chunks=[16, 3])
    monkeypatch.setenv("DYNT_SSM", "pallas")
    runner = make_runner()
    # stated at start-up beside the attention paths, so that the
    # interpreter or the XLA fallback never serves unnoticed
    paths = runner.kernel_paths()
    assert (paths["ssm_update"], paths["expert_gmm"]) == ("interpret", "xla")
    assert prefill(runner, prompt, slot=1, chunks=[16, 3]) == want
    a = decode_logits(runner, {1: (want, 19)})[1]
    monkeypatch.delenv("DYNT_SSM")
    other = make_runner()
    prefill(other, prompt, slot=1)
    b = decode_logits(other, {1: (want, 19)})[1]
    np.testing.assert_allclose(a, b, atol=SAME_PROGRAM)


# -- the scheduler ------------------------------------------------------------


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


def request(prompt, max_tokens, **sampling):
    return PreprocessedRequest(
        request_id=uuid.uuid4().hex, token_ids=list(prompt),
        sampling=SamplingOptions(max_tokens=max_tokens, temperature=0.0,
                                 **sampling),
        stop=StopConditions(ignore_eos=True))


def wait_for(collectors, seconds=120):
    deadline = time.time() + seconds
    while (any(c.finish is None for c in collectors)
           and time.time() < deadline):
        time.sleep(0.02)
    assert all(c.finish is not None for c in collectors)


def test_the_scheduler_serves_it_and_never_takes_a_prefix_hit(reference):
    """Six requests over four slots through the real loop: chunked
    prefill (a prompt past the 32-token budget), batched prefill, the
    fused 8-step block, slots reused. The same prompt twice in a row:
    no prefix hit, no `stored` event. Every stream is the reference's
    greedy continuation within the bf16-free tolerance (gap 0)."""
    stored = []
    sched = InferenceScheduler(make_runner(buckets=(16, 32)),
                               on_stored=lambda h, p: stored.append(h))
    sched.decode_block = 8
    prompts = [prompt_of(n, seed=20 + i)
               for i, n in enumerate((21, 9, 33, 12))]
    prompts += [prompts[0], prompts[2]]  # shared prefixes, whole prompts
    collectors = [Collect() for _ in prompts]
    sched.start()
    try:
        for p, c in zip(prompts[:4], collectors):
            sched.submit(request(p, 12), c)
        wait_for(collectors[:4])
        for p, c in zip(prompts[4:], collectors[4:]):
            sched.submit(request(p, 12), c)
        wait_for(collectors[4:])
    finally:
        sched.stop()
    assert stored == [] and sched.pool.cached_count() == 0
    assert [c.finish for c in collectors] == ["length"] * 6
    assert collectors[4].tokens() == collectors[0].tokens()
    assert collectors[5].tokens() == collectors[2].tokens()
    for p, c in zip(prompts[:4], collectors):
        want = reference_logits(reference, CONFIG, p, c.tokens())
        gap = want.max(-1) - want[np.arange(12), c.tokens()]
        assert gap.max() < VS_REFERENCE
    assert sched.stats.state_slot_ms > 0
    # by phase: tokens per held expert, dropped, touched, calls
    counts = sched.runner.moe_stats()
    held = CONFIG.n_experts
    assert counts.shape == (2, held + 3)
    assert (counts[:, :held].sum(axis=1) > 0).all()
    assert (counts[:, held] == 0).all()
    # each call touches between 1 and all of the experts held
    assert (counts[:, held + 2] > 0).all()
    assert (counts[:, held + 1] >= counts[:, held + 2]).all()
    assert (counts[:, held + 1] <= held * counts[:, held + 2]).all()


def compiles() -> float:
    from dynamo_tpu.runtime.metrics import REGISTRY

    return sum(REGISTRY.get_sample_value("dynamo_jit_compiles_total",
                                         {"fn": fn}) or 0.0
               for fn in ("prefill", "prefill_batch", "decode_multi"))


def test_no_bucket_under_a_scan_chunk_and_no_row_past_the_budget():
    """The smallest prefill bucket is one chunk of the scan, and a launch
    holds as many rows as the budget holds of those: the grid of
    (rows, bucket) programs is bounded by the runner's own numbers."""
    assert CONFIG.ssm_chunk == 16
    runner = make_runner(buckets=(4, 8, 16, 32))
    assert runner.config.prefill_buckets == (16, 32)
    assert (runner.max_prefill_chunk, runner.max_prefill_rows) == (32, 2)
    assert make_runner(buckets=(8,)).config.prefill_buckets == (8,)
    sched = InferenceScheduler(runner)
    collectors = [Collect() for _ in range(3)]
    for i, c in enumerate(collectors):  # three prompts of 5: 15 of 32
        sched.submit(request(prompt_of(5, seed=40 + i), 1), c)
    sched._drain_incoming()
    sched._admit()
    assert len(sched._prefill_work(runner.max_prefill_chunk)) == 2


def test_prewarm_full_leaves_served_traffic_nothing_to_compile():
    """`--prewarm full`: after it, lone, chunked and batched prefill and
    the fused block at both of this runner's table widths run compiled."""
    runner = make_runner(buckets=(16, 64))
    before = compiles()
    runner.prewarm(launches=True, block=8)
    if compiles() == before:
        pytest.skip("jax.monitoring compile events not observed")
    sched = InferenceScheduler(runner)
    sched.decode_block = 8
    warm = compiles()
    # a context under 8 pages and one past them; four at once, then two
    lengths = (21, 9, 33, 12, 40, 17)
    collectors = [Collect() for _ in lengths]
    sched.start()
    try:
        for n, c in zip(lengths, collectors):
            sched.submit(request(prompt_of(n, seed=50 + n), 17), c)
        wait_for(collectors)
    finally:
        sched.stop()
    assert sched.stats.prefill_batched_steps > 0
    assert sched.stats.decode_block_launches > 0
    assert compiles() == warm


def test_the_worker_takes_prewarm_as_a_flag_over_the_environment():
    from dynamo_tpu.engine.worker import build_arg_parser

    parse = build_arg_parser().parse_args
    assert parse(["--model", "tiny-hybrid-test"]).prewarm is None
    assert parse(["--model", "tiny-hybrid-test", "--prewarm",
                  "full"]).prewarm == "full"
    with pytest.raises(SystemExit):
        parse(["--model", "tiny-hybrid-test", "--prewarm", "some"])


def test_logits_processors_are_refused_in_band():
    sched = InferenceScheduler(make_runner())
    out = Collect()
    assert sched._prepare(request(prompt_of(5), 4, logit_bias={"3": 1.0}),
                          out) is None
    assert out.finish == "error" and "recurrent state" in out.outputs[0].error


# -- the refusals ---------------------------------------------------------------


def test_the_modules_refuse_it_too():
    from dynamo_tpu.models.quantize import check_quantizable

    config = get_config("tiny-hybrid-test")
    with pytest.raises(ValueError, match="hybrid"):
        check_quantizable(config)
    for bad in (dict(weight_dtype="int8"), dict(kv_dtype="int8"),
                dict(max_loras=2)):
        with pytest.raises(ValueError, match="tiny-hybrid-test"):
            ModelRunner(config, RunnerConfig(page_size=PAGE, num_pages=16,
                                             max_batch=2, **bad),
                        make_mesh(MeshConfig()))
    runner = make_runner()
    with pytest.raises(RuntimeError, match="recurrent state"):
        runner.gather_pages_device(np.asarray([1, 2], np.int32))
    assert not runner.supports_spec


def test_the_published_preset_and_its_cut():
    config = get_config("nemotron3-nano-30b-a3b")
    pattern = config.layer_pattern
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (52, 23, 23, 6)
    assert (config.mamba_inner, config.mamba_conv_dim) == (4096, 6144)
    cut = cut_config(config, layers=14, experts="0:64", vocab_rows=65536)
    assert cut.layer_pattern == "MEMEM*EMEMEM*E"
    assert (cut.kv_layers, cut.state_layers) == ((5, 12),
                                                 (0, 2, 4, 7, 9, 11))
    assert cut.held_experts == (0, 64) and cut.n_experts == 128
    assert cut.hidden == config.hidden and cut.vocab_size == 65536
    for bad in (dict(layers=53), dict(experts="64:129"), dict(experts="3"),
                dict(vocab_rows=0)):
        with pytest.raises(ValueError):
            cut_config(config, **bad)
    with pytest.raises(ValueError, match="dropless"):
        cut_config(get_config("mixtral-8x7b"), experts="0:4")
