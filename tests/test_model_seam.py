"""The seam between the engine and a model (PR 47): what a cache is and
cannot do is said once, by the configuration (`models.config.cache_plan`);
every stack is driven through the same calls with the same row type
(`ModelRunner.prefill_chunk_batch` over `PrefillRow`s, `decode` over a
tuple of tables); and every flag that a cache refuses is refused by one
function over one table (`engine.worker.recurrent_state_refusals`).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import ModelRunner, PrefillRow, RunnerConfig
from dynamo_tpu.engine.worker import recurrent_state_refusals
from dynamo_tpu.models import get_config
from dynamo_tpu.models.config import CachePlan, cache_plan
from dynamo_tpu.parallel import MeshConfig, make_mesh

PAGE, WIDTH, SLOTS = 16, 8, 4
GREEDY = (0.0, 1.0, 0, 0)

# one tiny configuration a kind of stack: (preset, fields replaced,
# RunnerConfig fields)
STACKS = {
    "dense": ("tiny-test", {}, {}),
    "dense-int8-pool": ("tiny-test", {"head_dim": 128},
                        {"kv_dtype": "int8"}),
    "mamba+experts": ("tiny-hybrid-test", {}, {}),
    "window+full": ("tiny-mellum-test", {}, {"window_pages": 16}),
    "latent": ("tiny-pangu-test", {}, {}),
    "short-conv": ("tiny-lfm2-test", {}, {}),
    "parallel-block": ("tiny-cohere2-test", {}, {"window_pages": 16}),
    "shared-kv+mamba1": ("tiny-phi4flash-test", {}, {"window_pages": 16}),
}


def pages_of(slot: int) -> np.ndarray:
    """A slot's own four pages of either group (page 0 is the scratch)."""
    table = np.zeros(WIDTH, np.int32)
    table[:4] = np.arange(4) + 1 + 4 * slot
    return table


def direct_steps(runner, prompts, first, second):
    """The stack's forwards called directly on a fresh cache with the
    arrays unpacked, launch for launch as the runner made them: the
    prefill of `prompts` (row i in slot i), then two decode steps on
    `first` and `second`. Returns the three steps' arg-max tokens."""
    from dynamo_tpu.models.hybrid import (
        forward_hybrid,
        forward_hybrid_decode,
    )
    from dynamo_tpu.models.transformer import forward, forward_decode

    cfg, params = runner.model_config, runner.params
    pools, state = runner._kv_cache_init()()
    n, bucket = len(prompts), 16
    tok = np.zeros((n, bucket), np.int32)
    pos = np.zeros((n, bucket), np.int32)
    valid = np.zeros((n, bucket), bool)
    for i, prompt in enumerate(prompts):
        tok[i, :len(prompt)] = prompt
        pos[i, :len(prompt)] = np.arange(len(prompt))
        valid[i, :len(prompt)] = True
    lens = np.asarray([len(p) for p in prompts], np.int32)
    tables = np.stack([pages_of(i) for i in range(n)])
    window = None
    if len(pools) == 2:  # the window group's short table, from position 0
        width = runner.window_prefill_width(bucket)
        win = np.zeros((n, width), np.int32)
        win[:, :4] = tables[:, :4]
        window = (pools[1], jnp.asarray(win), jnp.zeros(n, jnp.int32))
    if cfg.layer_pattern:
        kv, state, last, _ = forward_hybrid(
            params, cfg, jnp.asarray(tok), jnp.asarray(pos), pools[0], state,
            jnp.arange(n), jnp.asarray(tables), jnp.asarray(lens),
            jnp.asarray(valid), jnp.asarray(lens - 1), window=window)
    else:
        kv, logits = forward(
            params, cfg, jnp.asarray(tok), jnp.asarray(pos), pools[0],
            jnp.asarray(tables), jnp.asarray(lens), valid=jnp.asarray(valid))
        last = logits[np.arange(n), lens - 1]
    out = [np.asarray(jnp.argmax(last, -1))]
    all_tables = np.zeros((SLOTS, WIDTH), np.int32)
    all_tables[:n] = tables
    active = np.arange(SLOTS) < n
    for step, fed in enumerate((first, second)):
        toks = np.zeros(SLOTS, np.int32)
        toks[:n] = fed
        at = np.zeros(SLOTS, np.int32)
        at[:n] = lens + step
        if cfg.layer_pattern:
            if window is not None:
                win = np.zeros((SLOTS, runner.window_table_width), np.int32)
                win[:n, :4] = tables[:, :4]
                window = (kv[1], jnp.asarray(win),
                          jnp.zeros(SLOTS, jnp.int32))
                kv = kv[0]
            kv, state, logits, _ = forward_hybrid_decode(
                params, cfg, jnp.asarray(toks), jnp.asarray(at), kv, state,
                jnp.asarray(all_tables), jnp.asarray(at + 1),
                jnp.asarray(active), window=window)
        else:
            kv, logits = forward_decode(
                params, cfg, jnp.asarray(toks), jnp.asarray(at), kv,
                jnp.asarray(all_tables), jnp.asarray(at + 1),
                jnp.asarray(active))
        out.append(np.asarray(jnp.argmax(logits[:n, 0], -1)))
    return out


@pytest.mark.parametrize("stack", sorted(STACKS))
def test_every_stack_is_driven_by_the_same_calls_and_rows(stack):
    """One batched prefill of two rows and two decode steps, through the
    calls and the row type the scheduler uses for every model: `slot`
    and `window` always filled, `tables` a tuple with an entry a page
    group of the plan (and the window group's base). The sampled tokens
    are those of the stack's own forwards called directly."""
    preset, fields, serve = STACKS[stack]
    cfg = dataclasses.replace(get_config(preset), dtype="float32", **fields)
    runner = ModelRunner(
        cfg, RunnerConfig(page_size=PAGE, num_pages=32, max_batch=SLOTS,
                          max_pages_per_seq=WIDTH, prefill_buckets=(16, 32),
                          **serve),
        make_mesh(MeshConfig()), seed=0)
    groups = runner.cache_plan.groups
    pools, state = runner.cache
    assert len(pools) == len(groups)
    assert (state is not None) == bool(cfg.layer_pattern)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 500, n).astype(np.int32) for n in (11, 7)]
    second_group = len(groups) > 1
    rows = [PrefillRow(p, 0, pages_of(i), len(p), GREEDY, 0, i,
                       (list(pages_of(i)[:4]), 0) if second_group else None)
            for i, p in enumerate(prompts)]
    got = [np.asarray(runner.prefill_chunk_batch(rows))[:2]]
    tables = np.zeros((SLOTS, WIDTH), np.int32)
    tables[:2] = [pages_of(0), pages_of(1)]
    extra = ()
    if second_group:
        win = np.zeros((SLOTS, runner.window_table_width), np.int32)
        win[:2, :4] = tables[:2, :4]
        extra = (win, np.zeros(SLOTS, np.int32))
    active = np.arange(SLOTS) < 2
    for step in range(2):
        toks = np.zeros(SLOTS, np.int32)
        toks[:2] = got[-1]
        at = np.zeros(SLOTS, np.int32)
        at[:2] = [len(p) + step for p in prompts]
        got.append(runner.decode(
            toks, at, (tables, *extra), at + 1, active,
            np.zeros(SLOTS, np.float32), np.ones(SLOTS, np.float32),
            np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.uint32))[:2])
    want = direct_steps(runner, prompts, got[0], got[1])
    for step, (g, w) in enumerate(zip(got, want)):
        assert g.tolist() == w.tolist(), (stack, step)
    # the experts' statistics come back where there are dropless experts
    assert (runner.moe_stats() is not None) == bool(runner._steps.stats_size)


# -- the plan -----------------------------------------------------------------


def test_a_dense_stacks_plan_is_the_plain_one():
    assert cache_plan(get_config("tiny-test")) == CachePlan()
    mla = cache_plan(get_config("tiny-mla-test"))
    assert "MLA" in mla.int8_pool
    assert dataclasses.replace(mla, int8_pool="") == CachePlan()


@pytest.mark.parametrize("preset,groups,state,prefix,bound", [
    ("tiny-hybrid-test", ("full",), True, False, "carried"),
    ("tiny-granite-test", ("full",), True, False, "carried"),
    ("tiny-lfm2-test", ("full",), True, False, "carried"),
    ("tiny-mellum-test", ("full", "window"), False, False, "always"),
    ("tiny-pangu-test", ("full",), False, True, "always"),
    ("tiny-cohere2-test", ("full", "window"), False, False, "always"),
    ("tiny-phi4flash-test", ("full", "window"), True, False, "always"),
])
def test_the_plan_says_what_each_kind_of_layer_brings(preset, groups, state,
                                                      prefix, bound):
    plan = cache_plan(get_config(preset))
    assert (plan.groups, plan.state, plan.launch_bound) == (
        groups, state, bound)
    assert (not plan.reuse_prefix) == prefix
    for trait in ("move_pages", "score_positions", "shard", "int8_pool",
                  "quantized_weights"):
        assert preset in getattr(plan, trait), trait


@pytest.mark.parametrize("preset,layers,readers", [
    ("tiny-test", (), ()),  # a dense stack: every layer its own
    ("tiny-mellum-test", (2, 6), (2, 6)),
    ("tiny-granite-test", (1,), (1,)),
    ("tiny-pangu-test", (5,), (5,)),
    # ONE full layer that three read; three window layers
    ("tiny-phi4flash-test", (1, 3), (3, 3)),
    ("phi4-mini-flash-reasoning", (1, 8), (8, 8)),
])
def test_a_page_group_holds_its_writers_and_counts_its_readers(preset,
                                                               layers,
                                                               readers):
    """A pool has a cache layer for every layer that WRITES its group
    (bytes a token, pool sizes, the wire layout); a layer that reads
    another's pages adds a reader and no pages."""
    from dynamo_tpu.profiler import kv_bytes_per_token

    config = get_config(preset)
    plan = cache_plan(config)
    assert (plan.group_layers, plan.group_readers) == (layers, readers)
    assert kv_bytes_per_token(config) == (
        (sum(layers) or config.n_layers) * config.kv_cache_kv_dims
        * config.kv_cache_heads
        * config.kv_cache_head_dim * 2)


def test_the_scheduler_reads_the_plan_and_a_stub_runner_has_the_plain_one():
    from dynamo_tpu.engine import InferenceScheduler

    class Stub:  # a runner without a configuration, as some tests make
        config = RunnerConfig(page_size=4, num_pages=16, max_batch=2,
                              max_pages_per_seq=8)

    sched = InferenceScheduler(Stub())
    assert sched.cache_plan == CachePlan() and sched.win_pool is None
    assert sched.pool.prefix_cache and sched._win_tables == ()


# -- what a cache is refused, by flag -------------------------------------------

MODE = ["--mode prefill", "kv_transfer"]
SNAPSHOT = [*MODE, "state snapshot"]
KVBM = ["--kvbm-host-blocks"]
SPEC = ["DYNT_SPEC_ENABLE"]
# configuration x flag -> the words the message must hold (the model's
# name is asked of every one)
REFUSALS = [
    # Mamba-2 + experts (tests/test_hybrid_model.py, PR 30)
    ("nemotron3-nano-30b-a3b", dict(mode="prefill"), SNAPSHOT),
    ("nemotron3-nano-30b-a3b", dict(mode="decode"),
     ["--mode decode", "kv_transfer"]),
    ("nemotron3-nano-30b-a3b", dict(kvbm=True), [*KVBM, "recurrent state"]),
    ("nemotron3-nano-30b-a3b", dict(spec=True), [*SPEC, "rolled back"]),
    ("nemotron3-nano-30b-a3b", dict(weight_dtype="int8"),
     ["--weight-dtype int8", "quantize.py"]),
    ("nemotron3-nano-30b-a3b", dict(weight_dtype="int4"),
     ["--weight-dtype int4", "Mamba-2"]),
    ("nemotron3-nano-30b-a3b", dict(kv_dtype="int8"),
     ["--kv-dtype int8", "hybrid"]),
    ("nemotron3-nano-30b-a3b", dict(devices=4),
     ["--tp/--sp", "not sharded"]),
    # Mamba-2 or attention over SwiGLU experts (test_granite_model.py, PR 42)
    ("granite-4.0-h-small", dict(mode="prefill"), SNAPSHOT),
    ("granite-4.0-h-small", dict(kvbm=True), [*KVBM, "recurrent state"]),
    ("granite-4.0-h-small", dict(spec=True), [*SPEC, "rolled back"]),
    ("granite-4.0-h-small", dict(weight_dtype="int4"),
     ["--weight-dtype int4", "Mamba-2"]),
    ("granite-4.0-h-small", dict(kv_dtype="int8"),
     ["--kv-dtype int8", "hybrid"]),
    ("granite-4.0-h-small", dict(devices=4), ["--tp/--sp", "not sharded"]),
    # a stack whose only state is a conv carry (test_lfm2_model.py, PR 44)
    ("lfm2-8b-a1b", dict(mode="prefill"), SNAPSHOT),
    ("lfm2-8b-a1b", dict(kvbm=True), [*KVBM, "recurrent state"]),
    ("lfm2-8b-a1b", dict(spec=True), [*SPEC, "rolled back"]),
    ("lfm2-8b-a1b", dict(weight_dtype="int4"),
     ["--weight-dtype int4", "short-conv"]),
    ("lfm2-8b-a1b", dict(kv_dtype="int8"), ["--kv-dtype int8", "hybrid"]),
    ("lfm2-8b-a1b", dict(devices=4), ["--tp/--sp", "not sharded"]),
    # window and full attention, two page groups (test_mellum_model.py, PR 36)
    ("tiny-mellum-test", dict(mode="prefill"),
     ["--mode prefill", "two page groups"]),
    ("tiny-mellum-test", dict(kvbm=True), [*KVBM, "two page groups"]),
    ("tiny-mellum-test", dict(spec=True), [*SPEC, "multi-position"]),
    ("tiny-mellum-test", dict(kv_dtype="int8"), ["--kv-dtype int8"]),
    ("tiny-mellum-test", dict(weight_dtype="int4"),
     ["--weight-dtype int4", "has expert matrices"]),
    ("tiny-mellum-test", dict(weight_dtype="int8"), ["--weight-dtype int8"]),
    ("tiny-mellum-test", dict(devices=4), ["--tp/--sp/--dp"]),
    # a parallel block over the same two page groups: nothing new to
    # refuse, and nothing refused anew (test_cohere2_model.py, PR 49)
    ("command-a-plus-05-2026", dict(mode="prefill"),
     ["--mode prefill", "two page groups"]),
    ("command-a-plus-05-2026", dict(kvbm=True), [*KVBM, "two page groups"]),
    ("command-a-plus-05-2026", dict(spec=True), [*SPEC, "multi-position"]),
    ("command-a-plus-05-2026", dict(kv_dtype="int8"), ["--kv-dtype int8"]),
    ("command-a-plus-05-2026", dict(weight_dtype="int4"),
     ["--weight-dtype int4", "has expert matrices"]),
    ("command-a-plus-05-2026", dict(devices=4), ["--tp/--sp/--dp"]),
    # Mamba-1 state beside two page groups, one of whose layers seven
    # others read: the window group refuses first, as for mellum
    # (test_phi4flash_model.py, PR 52)
    ("phi4-mini-flash-reasoning", dict(mode="prefill"),
     ["--mode prefill", "two page groups"]),
    ("phi4-mini-flash-reasoning", dict(kvbm=True),
     [*KVBM, "two page groups"]),
    ("phi4-mini-flash-reasoning", dict(spec=True),
     [*SPEC, "multi-position"]),
    ("phi4-mini-flash-reasoning", dict(kv_dtype="int8"),
     ["--kv-dtype int8"]),
    ("phi4-mini-flash-reasoning", dict(weight_dtype="int4"),
     ["--weight-dtype int4", "Mamba-1", "shared-KV cross-attention"]),
    ("phi4-mini-flash-reasoning", dict(devices=4), ["--tp/--sp/--dp"]),
    # latent attention, a single-stack pool (test_pangu_model.py, PR 38)
    ("tiny-pangu-test", dict(mode="prefill"),
     ["--mode prefill", "single-stack latent pool"]),
    ("tiny-pangu-test", dict(mode="decode"), ["--mode decode"]),
    ("tiny-pangu-test", dict(kvbm=True),
     [*KVBM, "single-stack latent pool"]),
    ("tiny-pangu-test", dict(spec=True), [*SPEC, "scores one"]),
    ("tiny-pangu-test", dict(kv_dtype="int8"), ["--kv-dtype int8"]),
    ("tiny-pangu-test", dict(weight_dtype="int4"),
     ["expert and latent-attention matrices"]),
    ("tiny-pangu-test", dict(weight_dtype="int8"), ["--weight-dtype int8"]),
    ("tiny-pangu-test", dict(devices=4), ["--tp/--sp/--dp"]),
    # the weight-dtype refusal names the model's own matrices
    ("tiny-hybrid-test", dict(weight_dtype="int4"),
     ["Mamba-2 and expert matrices"]),
    # a dense stack that caches latents holds no int8 pool
    ("tiny-mla-test", dict(kv_dtype="int8"),
     ["--kv-dtype int8", "MLA's latent cache"]),
]


@pytest.mark.parametrize(
    "preset,flags,words", REFUSALS,
    ids=[f"{p}-{'-'.join(f'{k}={v}' for k, v in f.items())}"
         for p, f, _ in REFUSALS])
def test_a_flag_the_cache_cannot_serve_is_refused_with_the_reason(
        preset, flags, words):
    config = get_config(preset)
    with pytest.raises(ValueError) as err:
        recurrent_state_refusals(config, **flags)
    said = str(err.value)
    assert all(word in said for word in words), said
    assert config.name in said
    recurrent_state_refusals(config)  # aggregated, no extras: served
    if "MLA" not in said:
        recurrent_state_refusals(get_config("tiny-test"), **flags)  # dense


@pytest.mark.parametrize("preset,said", [
    ("tiny-hybrid-test", "recurrent state"),
    ("tiny-mellum-test", "two page groups"),
    ("tiny-pangu-test", "single-stack latent pool"),
])
def test_the_runner_moves_no_page_the_plan_says_it_cannot(preset, said):
    serve = {"window_pages": 16} if preset == "tiny-mellum-test" else {}
    runner = ModelRunner(
        get_config(preset),
        RunnerConfig(page_size=PAGE, num_pages=16, max_batch=2,
                     max_pages_per_seq=WIDTH, prefill_buckets=(16,), **serve),
        make_mesh(MeshConfig()), seed=0)
    with pytest.raises(RuntimeError, match=said):
        runner.gather_pages_device(np.asarray([1, 2], np.int32))
    assert not runner.supports_spec
    assert jax.tree.leaves(runner.cache)  # one attribute holds it all
