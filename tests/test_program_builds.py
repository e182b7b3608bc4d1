"""A build is a span, recorded where jax reports it (PR 40).

Tiers:
  * The listener driven by hand with jax's five events: stages that
    never overlap, `compile + cache_load` equal to
    `dynamo_jit_compile_seconds_total` per entry, a nested trace counted
    once, a build outside any scope named by jax, a bounded ring.
  * Real builds on the CPU (`tiny-test`): a program built twice gives
    one record and one count; a warm-up pass lists its keys at 0
    launches; `/debug/programs` serves the ring and the launches.
  * A second process on the same persistent cache directory: `cache:
    hit`, `cache_load` > 0, `compile` == 0.
  * A bare scheduler: a build inside a request stamps `program_built`
    on that request's timeline and on no other, and the prefill
    entries' launches by key sum to the scheduler's own count.
"""

import json
import os
import subprocess
import sys
import time
import types
import uuid

import numpy as np
import pytest

from dynamo_tpu.engine import model_runner as mr
from dynamo_tpu.llm.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.flight_recorder import get_recorder, reset_recorder
from dynamo_tpu.runtime.metrics import REGISTRY

STAGES = ("trace", "lower", "compile", "cache_load")
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


def sample(name, **labels):
    return REGISTRY.get_sample_value(name, labels) or 0.0


def stage_seconds(fn):
    return {s: sample("dynamo_jit_stage_seconds_total", fn=fn, stage=s)
            for s in STAGES}


def builds_of(fn):
    return [b for b in mr.programs_snapshot()["builds"] if b["fn"] == fn]


def mark():
    return mr.programs_snapshot()["builds_total"]


def builds_since(mark):
    """Records that closed after `mark()` (other tests of this process
    build the same keys on runners of their own)."""
    snap = mr.programs_snapshot()
    new = snap["builds_total"] - mark
    return snap["builds"][-new:] if new else []


def tiny_runner():
    from dynamo_tpu.engine import ModelRunner, RunnerConfig
    from dynamo_tpu.models import get_config
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    return ModelRunner(
        get_config("tiny-test"),
        RunnerConfig(page_size=4, num_pages=64, max_batch=4,
                     max_pages_per_seq=16, prefill_buckets=(8, 16, 32)),
        make_mesh(MeshConfig()), seed=0)


# -- the listener, by hand ----------------------------------------------------


def test_a_scope_closes_into_one_record_with_stages_that_never_overlap():
    fn = "byhand-" + uuid.uuid4().hex[:6]
    with mr.compile_scope(fn, fn + "[a]", cause="prewarm"):
        # an entry with two jit keys builds two: one loaded, one compiled
        mr._on_compile_event(mr._TRACE_EVENT, 1.5, fun_name="step")
        mr._on_compile_event(mr._LOWER_EVENT, 2.0)
        mr._on_cache_event(HIT)
        mr._on_compile_event(mr._COMPILE_EVENT, 4.0)
        mr._on_compile_event(mr._LOWER_EVENT, 0.5)
        mr._on_cache_event(MISS)
        mr._on_compile_event(mr._COMPILE_EVENT, 35.0)
        mr._on_compile_event("/jax/some/other_duration", 99.0)
    (rec,) = builds_of(fn)
    assert (rec["key"], rec["cause"], rec["backends"]) == (
        fn + "[a]", "prewarm", 2)
    assert (rec["trace_s"], rec["lower_s"], rec["backend_s"]) == (
        1.5, 2.5, 39.0)
    assert rec["cache"] == "miss"  # any write: the program was not whole
    assert rec["t_end"] >= rec["t_start"]
    assert stage_seconds(fn) == {"trace": 1.5, "lower": 2.5,
                                 "compile": 35.0, "cache_load": 4.0}
    # the identity with the counter that was there before
    assert sample("dynamo_jit_compile_seconds_total", fn=fn) == 39.0
    assert sample("dynamo_jit_compiles_total", fn=fn) == 2
    # a scope in which nothing was built leaves no record
    with mr.compile_scope(fn, fn + "[b]"):
        pass
    assert len(builds_of(fn)) == 1


def test_a_trace_inside_another_trace_is_counted_once():
    """jnp functions are jitted: their traces end, and report, inside
    the trace of the step that calls them, whose duration holds theirs."""
    fn = "nested-" + uuid.uuid4().hex[:6]
    with mr.compile_scope(fn, fn + "[a]", cause="prewarm"):
        time.sleep(0.03)
        mr._on_compile_event(mr._TRACE_EVENT, 0.01, fun_name="sin")
        time.sleep(0.01)
        mr._on_compile_event(mr._TRACE_EVENT, 0.01, fun_name="matmul")
        # the step's trace began before both and ends now
        mr._on_compile_event(mr._TRACE_EVENT, 0.045, fun_name="step")
        mr._on_compile_event(mr._COMPILE_EVENT, 1.0)
        # the next program's trace is a new span
        mr._on_compile_event(mr._TRACE_EVENT, 0.02, fun_name="other")
        mr._on_compile_event(mr._COMPILE_EVENT, 1.0)
    (rec,) = builds_of(fn)
    assert rec["trace_s"] == pytest.approx(0.065)
    assert stage_seconds(fn)["trace"] == pytest.approx(0.065)


def test_a_build_outside_any_scope_is_unscoped_and_named_by_jax():
    name = "jit(loose_" + uuid.uuid4().hex[:6] + ")"
    mr._COMPILE_SCOPE.loose = None  # what an earlier trace left open
    mr.take_builds()  # and what earlier launches of this thread built
    before = sample("dynamo_jit_compiles_total", fn="unscoped")
    mr._on_compile_event(mr._TRACE_EVENT, 0.25, fun_name="loose")
    mr._on_compile_event(mr._LOWER_EVENT, 0.5, fun_name=name)
    mr._on_compile_event(mr._COMPILE_EVENT, 2.0, fun_name=name)
    (rec,) = [b for b in builds_of("unscoped") if b["key"] == name]
    assert (rec["cause"], rec["backends"], rec["cache"]) == (
        "unscoped", 1, "off")
    assert (rec["trace_s"], rec["lower_s"], rec["backend_s"]) == (
        0.25, 0.5, 2.0)
    assert sample("dynamo_jit_compiles_total", fn="unscoped") == before + 1
    assert mr.take_builds() == ()  # nobody's launch


def test_the_ring_is_bounded_and_says_how_many_builds_have_left_it():
    fn = "ring-" + uuid.uuid4().hex[:6]
    total = mr.programs_snapshot()["builds_total"]
    for i in range(mr.BUILD_RING + 40):
        with mr.compile_scope(fn, f"{fn}[{i}]", cause="prewarm"):
            mr._on_compile_event(mr._COMPILE_EVENT, 0.001)
    snap = mr.programs_snapshot()
    assert len(snap["builds"]) == snap["ring"] == mr.BUILD_RING
    assert snap["builds_total"] == total + mr.BUILD_RING + 40
    assert snap["builds"][-1]["key"] == f"{fn}[{mr.BUILD_RING + 39}]"
    assert snap["builds"][0]["key"] == f"{fn}[40]"  # newest last
    json.dumps(snap)  # served as it is


# -- real builds on the CPU -----------------------------------------------------


@pytest.fixture(scope="module")
def runner():
    pre = sample("dynamo_jit_compiles_total", fn="unscoped")
    runner = tiny_runner()
    if sample("dynamo_jit_compiles_total", fn="unscoped") == pre:
        pytest.skip("jax.monitoring compile events not observed")
    return runner


def prefill(runner, n):
    p = runner.config.max_pages_per_seq
    runner.prefill_chunk(
        np.full(n, 2, np.int32), 0,
        np.arange(1, p + 1, dtype=np.int32) % runner.config.num_pages,
        n, (0.0, 1.0, 0, 0))


def test_a_program_built_twice_gives_one_record_and_one_count(runner):
    mr.take_builds()
    before, start = sample("dynamo_jit_compiles_total", fn="prefill"), mark()
    prefill(runner, 5)   # bucket 8: built
    prefill(runner, 7)   # bucket 8 again: jit's own cache
    mine = [b for b in builds_since(start) if b["fn"] == "prefill"]
    assert [b["key"] for b in mine] == ["prefill[8]"]
    assert sample("dynamo_jit_compiles_total", fn="prefill") == before + 1
    rec = mine[0]
    assert rec["cause"] == "launch" and rec["backends"] == 1
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0 and rec["backend_s"] > 0
    # kept for the scheduler to name its requests; then gone
    assert [b["key"] for b in mr.take_builds()] == ["prefill[8]"]
    assert mr.take_builds() == ()
    # two launches of 5 and 7 useful tokens under the key it was built under
    assert runner.program_launches[("prefill", "prefill[8]")] == [2, 12]


def test_compile_plus_cache_load_is_the_old_counter_for_every_entry(runner):
    prefill(runner, 12)
    seen = 0
    for family in REGISTRY.collect():
        if family.name != "dynamo_jit_compile_seconds":
            continue
        for s in family.samples:
            if not s.name.endswith("_total"):
                continue
            split = stage_seconds(s.labels["fn"])
            assert split["compile"] + split["cache_load"] == \
                pytest.approx(s.value), s.labels
            seen += 1
    assert seen >= 2  # unscoped and prefill at the least


def test_a_warm_up_pass_lists_its_keys_and_counts_no_launch(runner):
    mr.take_builds()
    launched = dict((k, list(v)) for k, v in runner.program_launches.items())
    start = mark()
    runner.prewarm(spec_widths=[], launches=True, block=4)
    keys = {key for _fn, key in runner.program_launches}
    assert {"decode[w16]", "prefill[32]", "prefill_batch[4x16]",
            "decode_multi[w8,b4,fed]", "decode_multi[w16,b4,chained]"} <= keys
    for key, row in runner.program_launches.items():
        assert row == launched.get(key, [0, 0]), key
    scoped = [b for b in builds_since(start) if b["fn"] != "unscoped"]
    assert scoped and {b["cause"] for b in scoped} == {"prewarm"}
    assert {b["key"] for b in scoped} <= keys
    assert mr.take_builds() == ()  # a warm-up's builds are nobody's launch


def test_debug_programs_serves_the_ring_and_the_launches(runner):
    from dynamo_tpu.engine.scheduler import SchedulerStats
    from dynamo_tpu.engine.worker import TpuWorker
    from dynamo_tpu.runtime.status import debug_programs_response

    runner.warmup()
    prefill(runner, 5)
    fake = types.SimpleNamespace(
        scheduler=types.SimpleNamespace(stats=SchedulerStats(),
                                        win_pool=None),
        runner=runner, instance_id=0xb111d,
        mesh=types.SimpleNamespace(local_devices=[]),
        outbox=types.SimpleNamespace(handovers=0))
    TpuWorker._publish_engine_gauges(fake)
    out = json.loads(debug_programs_response(None).body)
    assert out["ring"] == mr.BUILD_RING
    assert out["builds"] == mr.programs_snapshot()["builds"]
    for build in out["builds"]:
        assert set(build) == {"fn", "key", "cause", "t_start", "t_end",
                              "trace_s", "lower_s", "backend_s", "backends",
                              "cache"}
    mine = {row["key"]: row for row in out["launches"]
            if row["worker"] == "b111d"}
    launches, tokens = runner.program_launches[("prefill", "prefill[8]")]
    assert mine["prefill[8]"] == {"worker": "b111d", "fn": "prefill",
                                  "key": "prefill[8]", "launches": launches,
                                  "tokens": tokens}
    assert mine["decode[w16]"]["launches"] == 0  # warmed, never launched
    assert "tokens" not in mine["decode[w16]"]


# -- the persistent cache, across two processes ---------------------------------

CHILD = r"""
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from dynamo_tpu.engine import model_runner as mr
from dynamo_tpu.runtime.metrics import REGISTRY
mr._install_compile_listener()
step = jax.jit(lambda x: jnp.tanh(x) @ x.T + 1.0)
with mr.compile_scope("prefill", "prefill[64]", cause="prewarm"):
    step(jnp.ones((64, 64))).block_until_ready()
(rec,) = [b for b in mr.programs_snapshot()["builds"] if b["fn"] == "prefill"]
value = lambda name, **labels: REGISTRY.get_sample_value(name, labels) or 0.0
print(json.dumps({
    "record": rec,
    "stages": {s: value("dynamo_jit_stage_seconds_total", fn="prefill",
                        stage=s)
               for s in ("trace", "lower", "compile", "cache_load")},
    "old": value("dynamo_jit_compile_seconds_total", fn="prefill"),
    "hit": value("dynamo_compile_cache_total", outcome="hit"),
    "miss": value("dynamo_compile_cache_total", outcome="miss")}))
"""


def test_a_second_process_on_the_same_cache_directory_loads(tmp_path):
    def child():
        out = subprocess.run(
            [sys.executable, "-c", CHILD, str(tmp_path / "cache")],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
            text=True, timeout=300,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold, warm = child(), child()
    if not cold["miss"]:
        pytest.skip("this backend writes nothing to the persistent cache")
    assert cold["record"]["cache"] == "miss" and cold["hit"] == 0
    assert cold["stages"]["compile"] > 0
    assert cold["stages"]["cache_load"] == 0
    assert warm["record"]["cache"] == "hit"
    assert warm["hit"] >= 1 and warm["miss"] == 0
    assert warm["stages"]["cache_load"] > 0
    assert warm["stages"]["compile"] == 0
    # tracing and lowering are paid at every start, cache or no cache
    assert warm["stages"]["trace"] > 0 and warm["stages"]["lower"] > 0
    for side in (cold, warm):
        assert side["stages"]["compile"] + side["stages"]["cache_load"] == \
            pytest.approx(side["old"])


# -- under the scheduler ---------------------------------------------------------


def test_a_build_inside_a_request_is_stamped_on_that_request_alone():
    from dynamo_tpu.engine import InferenceScheduler

    reset_recorder()
    rec = get_recorder()
    sched = InferenceScheduler(tiny_runner())
    done = []

    def submit(rid, n_tokens, first):
        rec.start(rid, model="tiny-test")
        sched.submit(
            PreprocessedRequest(
                request_id=rid,
                token_ids=[first] + [100 + j for j in range(n_tokens - 1)],
                sampling=SamplingOptions(max_tokens=6, temperature=0.0),
                stop=StopConditions(ignore_eos=True)),
            lambda out: done.append(out)
            if out.finish_reason is not None else None,
            record_id=rid)

    def wait(n):
        deadline = time.time() + 180
        while len(done) < n and time.time() < deadline:
            time.sleep(0.005)
        assert len(done) == n

    sched.start()
    try:
        submit("first", 10, 1)   # builds prefill[16] and the decode programs
        wait(1)
        submit("second", 11, 2)  # the same shapes: nothing to build
        wait(2)
        submit("third", 20, 3)   # bucket 32: programs of its own
        wait(3)
    finally:
        sched.stop()

    def built(rid):
        return [e for e in rec.get(rid).events
                if e["event"] == "program_built"]

    first, second, third = built("first"), built("second"), built("third")
    assert {e["key"] for e in first} >= {"prefill[16]"}
    assert all(e["seconds"] > 0 and e["key"].startswith(e["fn"] + "[")
               for e in first + third)
    assert second == []
    # a longer context: a new prefill bucket (and wider decode tables)
    assert "prefill[32]" in {e["key"] for e in third}
    assert not {e["key"] for e in third} & {e["key"] for e in first}
    # the ring names the launch's requests as the cause
    causes = {b["key"]: b["cause"] for b in mr.programs_snapshot()["builds"]
              if isinstance(b["cause"], list)}
    assert causes["prefill[32]"] == ["third"]
    assert causes["prefill[16]"] == ["first"]
    # the prefill entries' launches by key are the scheduler's own count
    runner = sched.runner
    by_key = sum(row[0] for (fn, _key), row in runner.program_launches.items()
                 if fn.startswith("prefill"))
    assert by_key == sched.stats.prefill_launches == 3
    assert sum(row[1] for (fn, _k), row in runner.program_launches.items()
               if fn.startswith("prefill")) == sched.stats.prefill_tokens
    reset_recorder()
