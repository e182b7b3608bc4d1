"""Where a request's time goes, counted inside the worker (PR 26).

Tiers:
  * A closed flight-recorder timeline, stamped with an injected clock,
    observed into dynamo_stage_duration_seconds{stage}: the five stages,
    `ingress` only with the frontend's `received_at`, no decode stage on
    a prefill-only leg.
  * `received_at` written by the frontend and carried by the wire codec.
  * dynamo_step_part_ms_total equal to the StepSample sums.
  * A tiny CPU engine: launches and reserved page-ms advance, reserved
    pages are back to zero once the sequences are reaped, and a profiler
    capture holds the `sched.*` sections on the thread that launches the
    programs.
  * Frontend + TpuWorker in one process: a scrape of the registry after
    traffic shows every stage and the three new families; a canary
    observes nothing.
"""

import glob
import os
import time
import types
import uuid

import pytest

from dynamo_tpu.engine.scheduler import SchedulerStats
from dynamo_tpu.engine.worker import TpuWorker, observe_stages
from dynamo_tpu.llm.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.perf.steptrace import StepTrace, annotation
from dynamo_tpu.runtime.codec import pack_body, unpack_body
from dynamo_tpu.runtime.flight_recorder import (
    FlightRecorder,
    reset_recorder,
    stage_durations,
)
from dynamo_tpu.runtime.metrics import REGISTRY

# The clock's origin: a whole second near now (a timeline backdated by
# more than an hour is retired as stale when it opens).
T0 = float(int(time.time()))
# seconds after T0 of each stamp of one request's life
STAMPS = {"received": 0.004, "queued": 0.005, "scheduled": 0.305,
          "prefill_start": 0.555, "first_token": 1.455, "finished": 9.955}
WANT = {"ingress": 0.004, "queue": 0.301, "prefill_wait": 0.250,
        "prefill": 0.900, "decode": 8.500}


def sample(name, **labels):
    return REGISTRY.get_sample_value(name, labels) or 0.0


def closed_timeline(rid="r1"):
    """One request's life on an injected clock: every stamp is given its
    time, none reads the wall clock."""
    rec = FlightRecorder(capacity=4, slow_ms=0)
    rec.start(rid, model="m", received=T0 + STAMPS["received"])
    for phase, at in STAMPS.items():
        if phase not in ("received", "finished"):
            rec.stamp(rid, phase, ts=T0 + at)
    rec.stamp(rid, "finished", ts=T0 + STAMPS["finished"])
    return rec.finish(rid, "ok")


def fake_request(model, **annotations):
    return types.SimpleNamespace(model=model, annotations=annotations)


@pytest.mark.parametrize("stage", sorted(WANT))
def test_a_closed_timeline_is_observed_stage_by_stage(stage):
    model = "stages-" + uuid.uuid4().hex[:8]
    observe_stages(closed_timeline(), fake_request(model, received_at=T0))
    family = "dynamo_stage_duration_seconds"
    assert sample(family + "_count", stage=stage, model=model) == 1
    assert sample(family + "_sum", stage=stage, model=model) == \
        pytest.approx(WANT[stage], abs=1e-6)


@pytest.mark.parametrize("annotations", [
    {}, {"received_at": "yesterday"}, {"received_at": True},
    {"traceparent": "00-ab-cd-01"}],
    ids=["absent", "not-a-number", "a-bool", "only-traceparent"])
def test_ingress_needs_the_frontends_arrival_time(annotations):
    model = "noingress-" + uuid.uuid4().hex[:8]
    observe_stages(closed_timeline(), fake_request(model, **annotations))
    family = "dynamo_stage_duration_seconds_count"
    assert sample(family, stage="ingress", model=model) == 0
    assert sample(family, stage="queue", model=model) == 1


def test_stages_need_both_stamps_and_never_run_backwards():
    phases = {"received": 10.0, "scheduled": 10.5, "first_token": 12.0,
              "finished": 20.0}  # onboarded: no prefill_start
    assert stage_durations(phases) == {"queue": 0.5, "decode": 8.0}
    # a prefill-only leg hands its pages over after first_token: no decode
    assert stage_durations(phases, prefill_only=True) == {"queue": 0.5}
    # a frontend whose clock runs ahead of the worker's reads zero
    assert stage_durations(phases, received_at=10.2)["ingress"] == 0.0
    assert stage_durations({}, received_at=1.0) == {}


def test_received_at_survives_the_wire_codec():
    arrival = 1790561234.5678912
    request = PreprocessedRequest(
        request_id="r", token_ids=[1, 2, 3],
        sampling=SamplingOptions(max_tokens=4), stop=StopConditions(),
        annotations={"traceparent": "00-ab-cd-01", "received_at": arrival})
    wire = unpack_body(pack_body(request.to_wire()))
    back = PreprocessedRequest.from_wire(wire)
    assert back.annotations["received_at"] == arrival
    assert isinstance(back.annotations["received_at"], float)


def test_the_frontend_writes_its_arrival_time_beside_traceparent():
    from dynamo_tpu.llm.http_service import HttpService

    reset_recorder()
    http = types.SimpleNamespace(headers={"traceparent": "00-" + "ab" * 16
                                          + "-" + "12" * 8 + "-01"})
    span = types.SimpleNamespace(traceparent="")
    for received, want in ((1234.5, 1234.5), (None, None)):
        request = PreprocessedRequest(
            request_id=uuid.uuid4().hex, token_ids=[1], model="m",
            sampling=SamplingOptions(max_tokens=4), stop=StopConditions())
        HttpService._open_http_trace(None, http, request, span,
                                     received=received)
        assert request.annotations.get("received_at") == want
        assert request.annotations["traceparent"].startswith("00-abab")
    reset_recorder()


# -- the step's parts ---------------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 50.0

    def __call__(self):
        return self.t


def test_step_part_counters_equal_the_step_sample_sums():
    clk = _Clock()
    st = StepTrace(clock=clk)
    samples = []
    for prep, submit, overlap, blocked, emit in (
            (1, 2, 4, 3, 0.5), (5, 1, 0, 7, 1), (0.5, 0.25, 2, 0, 0.25)):
        st.begin()
        clk.t += prep / 1e3
        with st.dispatch("decode"):
            clk.t += submit / 1e3
        clk.t += overlap / 1e3
        with st.drain("decode"):
            clk.t += blocked / 1e3
        with st.emit():  # the drained tokens committed and handed over
            clk.t += emit / 1e3
        samples.append(st.commit(prep + submit + overlap + blocked + emit))
    fake = types.SimpleNamespace(
        scheduler=types.SimpleNamespace(
            steptrace=st, stats=SchedulerStats(),
            active_kv_tokens=lambda: 0),
        instance_id=0xabc, runner=types.SimpleNamespace(decode_steps=0),
        _roof_prev=None, _roofline=None)
    family = "dynamo_step_part_ms_total"
    before = {p: sample(family, part=p)
              for p in ("wall", "prep", "dispatch", "drain_wait", "emit")}
    TpuWorker._publish_steptrace_metrics(fake)
    grew = {p: sample(family, part=p) - before[p] for p in before}
    assert grew["prep"] == pytest.approx(sum(s.prep_ms for s in samples))
    assert grew["prep"] == pytest.approx(6.5)
    assert grew["dispatch"] == pytest.approx(
        sum(s.dispatch_ms for s in samples))
    assert grew["dispatch"] == pytest.approx(3.25)
    assert grew["drain_wait"] == pytest.approx(
        sum(s.drain_ms for s in samples))
    assert grew["drain_wait"] == pytest.approx(10.0)
    assert grew["emit"] == pytest.approx(sum(s.emit_ms for s in samples))
    assert grew["emit"] == pytest.approx(1.75)
    # the denominator its readers use: the steps' wall itself
    assert grew["wall"] == pytest.approx(sum(s.wall_ms for s in samples))
    assert grew["wall"] == pytest.approx(10.5 + 14 + 3)
    # drained: a second publish adds nothing
    TpuWorker._publish_steptrace_metrics(fake)
    assert sample(family, part="prep") - before["prep"] == \
        pytest.approx(6.5)


def test_a_section_is_a_trace_annotation_and_a_dispatch_a_step_annotation():
    from jax import profiler

    assert isinstance(annotation("sched.admit", section=True),
                      profiler.TraceAnnotation)
    assert not isinstance(annotation("sched.admit", section=True),
                          profiler.StepTraceAnnotation)
    assert isinstance(annotation("decode", 3), profiler.StepTraceAnnotation)
    with annotation("sched.idle", section=True):
        pass  # no profiler session: a no-op


# -- a tiny CPU engine ----------------------------------------------------------


def tiny_scheduler():
    from dynamo_tpu.engine import InferenceScheduler, ModelRunner, RunnerConfig
    from dynamo_tpu.models import get_config
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    runner = ModelRunner(
        get_config("tiny-test"),
        RunnerConfig(page_size=4, num_pages=64, max_batch=4,
                     max_pages_per_seq=16, prefill_buckets=(8, 16, 32)),
        make_mesh(MeshConfig()), seed=0)
    return InferenceScheduler(runner)


def serve(sched, n_requests, max_tokens=12, watch=None, first=1):
    """Prompts of 10, 11, ... tokens that share no prefix (each starts
    with a token of its own), run to their ends."""
    done = []
    for i in range(n_requests):
        request = PreprocessedRequest(
            request_id=uuid.uuid4().hex,
            token_ids=[first + i] + [100 + j for j in range(9 + i)],
            sampling=SamplingOptions(max_tokens=max_tokens, temperature=0.0),
            stop=StopConditions(ignore_eos=True))
        sched.submit(request, lambda out: done.append(out)
                     if out.finish_reason is not None else None)
    deadline = time.time() + 180
    while len(done) < n_requests and time.time() < deadline:
        if watch is not None:
            watch()
        time.sleep(0.002)
    assert len(done) == n_requests
    return done


@pytest.fixture(scope="module")
def served():
    """Three requests through a bare scheduler, once for this module,
    inside a profiler capture of the process."""
    import jax

    sched = tiny_scheduler()
    seen = {"reserved_max": 0}

    def watch():
        seen["reserved_max"] = max(seen["reserved_max"],
                                   sched.reserved_pages())

    trace_dir = os.path.join(
        os.environ.get("TMPDIR", "/tmp"), "stage-metrics-" + uuid.uuid4().hex)
    sched.start()
    try:
        serve(sched, 1)  # compile outside the capture
        jax.profiler.start_trace(trace_dir)
        try:
            serve(sched, 3, watch=watch, first=11)
            time.sleep(0.12)  # two idle waits of the loop
        finally:
            jax.profiler.stop_trace()
        # let the loop reap the last sequence
        deadline = time.time() + 10
        while sched.reserved_pages() and time.time() < deadline:
            time.sleep(0.01)
    finally:
        sched.stop()
    seen["sched"] = sched
    seen["xplane"] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                               recursive=True)
    yield seen
    import shutil

    shutil.rmtree(trace_dir, ignore_errors=True)


def test_launches_are_counted_where_the_tokens_are(served):
    sched = served["sched"]
    stats, runner = sched.stats, sched.runner
    assert stats.prefill_launches >= 2  # the warm request, then the three
    assert stats.decode_block_launches >= 2
    # a fused block of k counts k device decode steps
    assert runner.decode_steps >= stats.decode_block_launches
    # four requests x (12 tokens, the first from the prefill)
    assert stats.decode_tokens == 4 * 11
    assert stats.prefill_tokens == 10 + 10 + 11 + 12
    # useful rows per device decode step: between none and the batch
    assert 0 < stats.decode_tokens / runner.decode_steps <= sched.max_batch


def test_reserved_pages_are_back_to_zero_when_the_sequences_are_reaped(
        served):
    sched = served["sched"]
    assert served["reserved_max"] > 0
    assert served["reserved_max"] <= sched.pool.num_pages
    assert sched.reserved_pages() == 0
    assert all(s is None for s in sched._slots)
    # page-time grew, and by no more than the whole pool over the steps
    wall = sched.steptrace.device_ms_total + sched.steptrace.host_ms_total
    assert 0 < sched.stats.reserved_page_ms <= sched.pool.num_pages * wall


def test_the_engine_gauges_carry_the_schedulers_counts(served):
    sched = served["sched"]
    fake = types.SimpleNamespace(
        scheduler=sched, runner=sched.runner, instance_id=0xfeed,
        mesh=types.SimpleNamespace(local_devices=[]),
        outbox=types.SimpleNamespace(handovers=7))
    TpuWorker._publish_engine_gauges(fake)
    # four requests: a frame for each first token, then one a drained
    # block a sequence, never one a token
    frames = sample("dynamo_engine_emit_frames_total", worker="feed")
    assert frames == sched.stats.emit_frames
    assert 4 + 4 <= frames < 4 + sched.stats.decode_tokens
    assert sample("dynamo_engine_emit_handovers_total", worker="feed") == 7
    launches = "dynamo_engine_launches"
    assert sample(launches, worker="feed", kind="prefill") == \
        sched.stats.prefill_launches
    assert sample(launches, worker="feed", kind="decode_block") == \
        sched.stats.decode_block_launches
    assert sample(launches, worker="feed", kind="decode_step") == \
        sched.runner.decode_steps
    assert sample("dynamo_kv_reserved_page_ms", worker="feed") == \
        pytest.approx(sched.stats.reserved_page_ms)


def test_a_prefill_launch_counts_its_positions_and_row_blocks():
    """Three prompts of 736 tokens on int4 weights under a budget of
    2,048: the first launch is [736, 736, 576] over 4 x 1024 positions
    (9 blocks of 256 live, 7 padding only), the second the 160 left, one
    row of 256 (one block, no map)."""
    from dynamo_tpu.engine import InferenceScheduler, ModelRunner, RunnerConfig
    from dynamo_tpu.models import get_config
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    runner = ModelRunner(
        get_config("tiny-test"),
        RunnerConfig(page_size=16, num_pages=256, max_batch=4,
                     max_pages_per_seq=64, prefill_buckets=(256, 1024, 2048),
                     weight_dtype="int4"),
        make_mesh(MeshConfig()), seed=0)
    sched = InferenceScheduler(runner)
    done = []
    for i in range(3):  # handed in before the loop runs: one admission
        sched.submit(PreprocessedRequest(
            request_id=uuid.uuid4().hex,
            token_ids=[1 + i] + [100 + j % 300 for j in range(735)],
            sampling=SamplingOptions(max_tokens=2, temperature=0.0),
            stop=StopConditions(ignore_eos=True)),
            lambda out: done.append(out)
            if out.finish_reason is not None else None)
    sched.start()
    try:
        deadline = time.time() + 180
        while len(done) < 3 and time.time() < deadline:
            time.sleep(0.005)
    finally:
        sched.stop()
    assert len(done) == 3
    assert sched.stats.prefill_launches == 2
    assert sched.stats.prefill_tokens == 2048 + 160
    assert runner.prefill_positions == 4096 + 256
    assert runner.prefill_row_blocks == {"live": 9 + 1, "skipped": 7}
    fake = types.SimpleNamespace(
        scheduler=sched, runner=runner, instance_id=0xb10c,
        mesh=types.SimpleNamespace(local_devices=[]),
        outbox=types.SimpleNamespace(handovers=0))
    TpuWorker._publish_engine_gauges(fake)
    assert sample("dynamo_engine_positions", worker="b10c",
                  kind="prefill") == 4352
    assert sample("dynamo_engine_tokens", worker="b10c",
                  kind="prefill") == 2208
    blocks = "dynamo_prefill_row_blocks_total"
    assert sample(blocks, worker="b10c", state="live") == 10
    assert sample(blocks, worker="b10c", state="skipped") == 7


def test_row_blocks_are_counted_with_int4_weights_only(served):
    runner = served["sched"].runner
    assert runner.prefill_positions > 0
    assert runner.prefill_row_blocks == {"live": 0, "skipped": 0}
    TpuWorker._publish_engine_gauges(types.SimpleNamespace(
        scheduler=served["sched"], runner=runner, instance_id=0xbf16,
        mesh=types.SimpleNamespace(local_devices=[]),
        outbox=types.SimpleNamespace(handovers=0)))
    assert sample("dynamo_engine_positions", worker="bf16",
                  kind="prefill") == runner.prefill_positions
    assert REGISTRY.get_sample_value(
        "dynamo_prefill_row_blocks_total",
        {"worker": "bf16", "state": "live"}) is None


def test_a_capture_holds_the_sched_sections_on_the_launching_thread(served):
    """On the profiler's clock, in the same .xplane.pb as the operations:
    the thread whose line holds the engine's `decode` step annotation
    also holds the scheduler's sections."""
    from jax.profiler import ProfileData

    assert served["xplane"], "the capture wrote no .xplane.pb"
    lines = [line for plane in ProfileData.from_file(
        served["xplane"][0]).planes if plane.name.startswith("/host:")
        for line in plane.lines]
    launching = [{e.name for e in line.events} for line in lines]
    launching = [names for names in launching if "decode" in names]
    assert launching, "no line holds the engine's decode annotation"
    names = set().union(*launching)
    assert {"sched.drain_incoming", "sched.admit", "sched.decode_prep",
            "sched.prefill_prep", "sched.gap", "sched.finalize_prefill",
            "sched.drain_wait", "sched.emit", "sched.reap",
            "sched.idle"} <= names, sorted(
        n for n in names if n.startswith("sched."))


# -- frontend + worker, one process ---------------------------------------------


def test_a_scrape_after_traffic_shows_the_stages_and_the_new_families(
        run, mem_runtime_config):
    import asyncio

    import aiohttp

    from dynamo_tpu.engine import RunnerConfig
    from dynamo_tpu.frontend import Frontend
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.metrics import render

    reset_recorder()
    family = "dynamo_stage_duration_seconds_count"
    stages = ("ingress", "queue", "prefill_wait", "prefill", "decode")
    before = {s: sample(family, stage=s, model="tiny-test") for s in stages}
    seen = {}

    async def body():
        cfg = mem_runtime_config()
        rt = await DistributedRuntime(cfg).start()
        worker = TpuWorker(
            rt, model_name="tiny-test", warmup=False,
            runner_config=RunnerConfig(
                page_size=4, num_pages=128, max_batch=2,
                max_pages_per_seq=32, prefill_buckets=(16, 32, 64, 128)))
        await worker.start()
        frt = await DistributedRuntime(mem_runtime_config(
            cfg.discovery_path)).start()
        frontend = Frontend(frt, host="127.0.0.1", port=0,
                            router_mode="round_robin")
        await frontend.start()
        for _ in range(100):
            if frontend.manager.get("tiny-test") is not None:
                break
            await asyncio.sleep(0.05)
        url = f"http://127.0.0.1:{frontend.port}/v1/completions"
        async with aiohttp.ClientSession() as session:
            for i in range(2):
                async with session.post(url, json={
                        "model": "tiny-test", "prompt": [5 + i, 6, 7, 8, 9],
                        "max_tokens": 10, "temperature": 0,
                        "ignore_eos": True}) as resp:
                    assert resp.status == 200, await resp.text()
                    await resp.json()
        seen["after_traffic"] = {
            s: sample(family, stage=s, model="tiny-test") for s in stages}
        # a canary opens no timeline and observes nothing
        canary = PreprocessedRequest(
            request_id="_canary", token_ids=[1, 2, 3], model="tiny-test",
            sampling=SamplingOptions(max_tokens=1, temperature=0.0),
            stop=StopConditions(),
            annotations={"canary": True, "received_at": time.time()})
        async for _ in worker.generate(canary.to_wire()):
            pass
        seen["after_canary"] = {
            s: sample(family, stage=s, model="tiny-test") for s in stages}
        await asyncio.sleep(0.7)  # one 0.5 s tick of the drain task
        seen["page"] = render().decode()
        seen["worker"] = f"{worker.instance_id:x}"
        await frontend.close()
        await frt.shutdown()
        await worker.close()
        await rt.shutdown()

    run(body(), timeout=300)
    reset_recorder()
    grew = {s: seen["after_traffic"][s] - before[s] for s in stages}
    assert grew == {s: 2 for s in stages}, grew
    assert seen["after_canary"] == seen["after_traffic"]
    page, worker = seen["page"], seen["worker"]
    for kind in ("prefill", "decode_block", "decode_step"):
        row = f'dynamo_engine_launches{{kind="{kind}",worker="{worker}"}}'
        assert row in page, kind
        assert float(page.split(row)[1].split()[0]) > 0
    row = f'dynamo_engine_positions{{kind="prefill",worker="{worker}"}}'
    assert float(page.split(row)[1].split()[0]) >= 2 * 16  # two prompts of 5, each in a bucket of 16
    assert f'dynamo_kv_reserved_page_ms{{worker="{worker}"}}' in page
    for part in ("wall", "prep", "dispatch", "drain_wait"):
        assert f'dynamo_step_part_ms_total{{part="{part}"}}' in page


def test_prefill_attention_launches_and_blocks_are_host_arithmetic(
        monkeypatch):
    """`ModelRunner._count_prefill` on hand-made lengths, no launch: which
    path a launch's attention layers take follows from the geometry the
    step program hands `attention_fn`, and on the kernel's path the
    (query block, key chunk) pairs it scores and skips add up to the
    dense rows x bucket x table grid."""
    import dataclasses

    from dynamo_tpu.engine import ModelRunner, RunnerConfig
    from dynamo_tpu.models import get_config
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    def runner(head_dim, **kw):
        return ModelRunner(
            dataclasses.replace(get_config("tiny-test"), head_dim=head_dim),
            RunnerConfig(page_size=16, num_pages=80, max_batch=4,
                         max_pages_per_seq=64, prefill_buckets=(256, 1024),
                         **kw),
            make_mesh(MeshConfig()), seed=0)

    # the CPU's default is the XLA reference: no attention_fn at all
    xla = runner(128)
    assert xla.prefill_attention_tiles(1024) is None
    xla._count_prefill([0], [736], 1, 1024)
    assert xla.prefill_attn_launches == {"kernel": 0, "xla": 1}
    assert xla.prefill_attn_blocks == {"live": 0, "skipped": 0}

    monkeypatch.setenv("DYNT_ATTENTION", "pallas")  # here: the interpreter
    # head_dim under a lane tile: `paged_attention` takes XLA whatever the
    # option says
    assert runner(16).prefill_attention_tiles(1024) is None
    # 4 heads over 2: two int8 kv heads are half a 32-bit word -> XLA
    assert runner(128, kv_dtype="int8").prefill_attention_tiles(1024) is None
    r = runner(128)
    assert r.prefill_attention_tiles(1024) == (512, 256)
    # a full bucket skips the causal half only: 2 + 4 of 2 x 4 pairs
    r._count_prefill([0], [1024], 1, 1024)
    assert r.prefill_attn_blocks == {"live": 6, "skipped": 2}
    # [4, 1024] holding 736, 736, 576: 2 + 3 chunks a row of 736 (its
    # second block sees 736 keys), 2 + 3 for 576; one row of padding
    r._count_prefill([0, 0, 0], [736, 736, 576], 4, 1024)
    assert r.prefill_attn_blocks == {"live": 6 + 15, "skipped": 2 + 17}
    # a continuation sees its prefix: positions 512.. of 1,024 keys
    r._count_prefill([512], [512], 1, 1024)
    assert r.prefill_attn_blocks == {"live": 21 + 4, "skipped": 19 + 4}
    assert r.prefill_attn_launches == {"kernel": 3, "xla": 0}
    assert sum(r.prefill_attn_blocks.values()) == (1 + 4 + 1) * 2 * 4
    TpuWorker._publish_engine_gauges(types.SimpleNamespace(
        scheduler=types.SimpleNamespace(stats=SchedulerStats(),
                                        win_pool=None),
        runner=r, instance_id=0xa77,
        mesh=types.SimpleNamespace(local_devices=[]),
        outbox=types.SimpleNamespace(handovers=0)))
    launches = "dynamo_prefill_attn_launches_total"
    assert sample(launches, worker="a77", path="kernel") == 3
    assert REGISTRY.get_sample_value(
        launches, {"worker": "a77", "path": "xla"}) == 0
    blocks = "dynamo_prefill_attn_blocks_total"
    assert sample(blocks, worker="a77", state="live", group="full") == 25
    assert sample(blocks, worker="a77", state="skipped", group="full") == 23
    # one page group: no series for a window group
    assert REGISTRY.get_sample_value(blocks, {
        "worker": "a77", "state": "live", "group": "window"}) is None
