"""Device-time attribution plane (perf/steptrace.py, "dynaprof").

Tiers:
  * StepTrace unit decomposition with an injected clock — the
    host+device==wall invariant, prev-step drains counting only their
    blocked wait, the host-bound verdict streak.
  * Real-engine integration (tiny-test, CPU): scheduler steps commit
    samples whose stamps sum to the step wall, and per-request device
    windows flow flight recorder -> /debug/requests snapshot ->
    planner PhaseBreakdownSource.
  * Mocker simulation: the same flow chip-free, with modeled device
    time.
  * Span parentage: worker.device_execute nests under the synthesized
    worker.prefill / worker.decode phase spans.
  * E2E (frontend + mocker, in-process planes): frontend TTFT
    decomposes into queue/host/device summing within 10% of the
    timeline TTFT, and dynamo_ttft_device_ms exports with a trace-id
    exemplar.
"""

import asyncio
import http.server
import json
import threading
import time
import uuid

import aiohttp
import pytest

from dynamo_tpu.llm.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.perf.steptrace import (
    HOST_BOUND_STEPS,
    LiveRoofline,
    StepTrace,
    detect_chip,
)
from dynamo_tpu.planner.metrics_source import PhaseBreakdownSource
from dynamo_tpu.runtime.flight_recorder import get_recorder, reset_recorder


@pytest.fixture(autouse=True)
def _fresh_recorder():
    reset_recorder()
    yield
    reset_recorder()


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, secs):
        self.t += secs


class TestStepTraceUnit:
    def test_decomposition_sums_to_wall(self):
        clk = _Clock()
        st = StepTrace(clock=clk)
        st.begin()
        clk.advance(0.001)  # host prep: 1ms
        with st.dispatch("decode") as d:
            clk.advance(0.002)  # submit cost: 2ms
        assert d.submit_end == clk.t
        clk.advance(0.004)  # overlapped host work while device busy
        with st.drain("decode") as drain:
            clk.advance(0.003)  # blocked readback
        # device window = submit end -> drain end = 4 + 3 ms
        assert drain.device_ms == pytest.approx(7.0)
        sample = st.commit(10.0)
        assert sample.prep_ms == pytest.approx(1.0)
        assert sample.dispatch_ms == pytest.approx(2.0)
        assert sample.device_ms == pytest.approx(7.0)
        assert sample.drain_ms == pytest.approx(3.0)
        # The invariant the plane is built on.
        assert sample.host_ms + sample.device_ms == pytest.approx(
            sample.wall_ms)
        assert sample.kind == "decode"
        assert st.device_ms_by_phase["decode"] == pytest.approx(7.0)

    def test_prev_step_drain_counts_blocked_wait_only(self):
        clk = _Clock()
        st = StepTrace(clock=clk)
        st.begin()
        clk.advance(0.002)
        # No prefill submit THIS step (the chunk was dispatched last
        # step): only the blocked wait may count, or the window would
        # exceed the step wall.
        with st.drain("prefill") as drain:
            clk.advance(0.001)
        assert drain.device_ms == pytest.approx(1.0)
        sample = st.commit(3.0)
        assert sample.device_ms == pytest.approx(1.0)
        assert sample.host_ms == pytest.approx(2.0)

    def test_unanchored_drain_ignores_other_works_submit(self):
        clk = _Clock()
        st = StepTrace(clock=clk)
        st.begin()
        # Another sequence's chunk dispatched THIS step...
        with st.dispatch("prefill"):
            clk.advance(0.001)
        clk.advance(0.005)  # host work between submit and the ripe loop
        # ...must not inflate the deferred finalize's window: only its
        # own blocked wait counts (anchored=False).
        with st.drain("prefill", anchored=False) as drain:
            clk.advance(0.002)
        assert drain.device_ms == pytest.approx(2.0)

    def test_sync_scope_is_all_device(self):
        clk = _Clock()
        st = StepTrace(clock=clk)
        st.begin()
        with st.sync("decode") as sc:
            clk.advance(0.005)
        assert sc.device_ms == pytest.approx(5.0)
        sample = st.commit(6.0)
        assert sample.device_ms == pytest.approx(5.0)

    def test_device_clamped_to_wall(self):
        clk = _Clock()
        st = StepTrace(clock=clk)
        st.begin()
        with st.dispatch("decode"):
            clk.advance(0.001)
        with st.drain("decode"):
            clk.advance(0.004)
        with st.dispatch("prefill"):
            clk.advance(0.001)
        with st.drain("prefill"):
            clk.advance(0.004)
        # Overlapping phase windows can sum past the wall; commit clamps.
        sample = st.commit(5.0)
        assert sample.device_ms == pytest.approx(5.0)
        assert sample.host_ms == 0.0

    def test_host_bound_verdict_needs_persistence(self):
        clk = _Clock()
        st = StepTrace(clock=clk)
        for _ in range(HOST_BOUND_STEPS - 1):
            st.begin()
            st.commit(5.0)  # all-host step
            assert not st.host_bound
        st.begin()
        st.commit(5.0)
        assert st.host_bound
        # One device-dominant step resets the streak.
        st.begin()
        with st.sync("decode"):
            clk.advance(0.004)
        st.commit(5.0)
        assert not st.host_bound

    def test_drain_samples_drains(self):
        st = StepTrace(clock=_Clock())
        st.begin()
        st.commit(1.0)
        st.begin()
        st.commit(2.0)
        samples = st.drain_samples()
        assert [s.wall_ms for s in samples] == [1.0, 2.0]
        assert st.drain_samples() == []
        assert st.steps == 2


class TestLiveRoofline:
    def test_fraction_and_mfu_bounds(self):
        from dynamo_tpu.models import get_config
        from dynamo_tpu.profiler.chips import CHIPS

        roof = LiveRoofline(get_config("tiny-test"), chip=CHIPS["cpu"])
        mfu, frac = roof.observe(
            prefill_tokens=512, decode_tokens=64, decode_steps=64,
            active_kv_tokens=1024, device_s=0.5)
        assert mfu > 0
        assert 0 < frac <= 1.0
        # Faster measured device time -> higher roofline fraction.
        _, frac_fast = roof.observe(
            prefill_tokens=512, decode_tokens=64, decode_steps=64,
            active_kv_tokens=1024, device_s=0.25)
        assert frac_fast >= frac
        # Zero device time never divides.
        assert roof.observe(prefill_tokens=1, decode_tokens=1,
                            decode_steps=1, active_kv_tokens=1,
                            device_s=0.0) == (0.0, 0.0)

    def test_detect_chip_falls_back_to_cpu(self):
        assert detect_chip().name == "cpu"


def _collect_factory():
    class _Collect:
        def __init__(self):
            self.outputs = []

        def __call__(self, out):
            self.outputs.append(out)

        @property
        def finish(self):
            for o in self.outputs:
                if o.finish_reason:
                    return o.finish_reason
            return None

    return _Collect()


class TestSchedulerDecomposition:
    def _engine(self):
        from dynamo_tpu.engine import (
            InferenceScheduler,
            ModelRunner,
            RunnerConfig,
        )
        from dynamo_tpu.models import get_config
        from dynamo_tpu.parallel import MeshConfig, make_mesh

        runner = ModelRunner(
            get_config("tiny-test"),
            RunnerConfig(page_size=4, num_pages=64, max_batch=4,
                         max_pages_per_seq=16, prefill_buckets=(8, 16, 32)),
            make_mesh(MeshConfig()),
            seed=0,
        )
        return InferenceScheduler(runner)

    def test_steps_commit_invariant_and_recorder_flow(self):
        sched = self._engine()
        recorder = get_recorder()
        rid = uuid.uuid4().hex
        recorder.start(rid, model="tiny-test")
        recorder.stamp(rid, "queued")
        collect = _collect_factory()
        request = PreprocessedRequest(
            request_id=rid, token_ids=list(range(1, 11)),
            sampling=SamplingOptions(max_tokens=12, temperature=0.0),
            stop=StopConditions(ignore_eos=True),
        )
        sched.start()
        try:
            sched.submit(request, collect, record_id=rid)
            deadline = time.time() + 120
            while collect.finish is None and time.time() < deadline:
                time.sleep(0.02)
            assert collect.finish is not None
        finally:
            sched.stop()
        trace = sched.steptrace
        assert trace.steps > 0
        last = trace.last
        # The decomposition invariant: stamps sum to the step wall.
        assert last.host_ms + last.device_ms == pytest.approx(
            last.wall_ms, abs=1e-6)
        assert last.prep_ms + last.dispatch_ms <= last.wall_ms + 1e-3
        assert trace.device_ms_total > 0
        assert "decode" in trace.device_ms_by_phase
        # Stats mirror what LoadMetrics publishes.
        assert sched.stats.device_ms_last_step == pytest.approx(
            last.device_ms)
        assert sched.stats.host_ms_last_step == pytest.approx(
            last.host_ms)
        # Per-request windows reached the timeline.
        tl = recorder.get(rid)
        assert tl is not None
        assert tl.device.get("prefill_device_ms", 0) > 0
        assert tl.device.get("decode_device_ms", 0) > 0
        # ... and flow into the planner's breakdown source.
        recorder.finish(rid, "ok")
        breakdown = PhaseBreakdownSource("unused").ingest(
            recorder.snapshot())
        assert breakdown.samples == 1
        assert breakdown.prefill_device_ms > 0
        assert breakdown.decode_device_ms > 0
        assert breakdown.device_fraction() is not None
        assert breakdown.host_ms() >= 0


class TestMockerDecomposition:
    def test_simulated_device_time_flows_to_breakdown(self, run):
        from dynamo_tpu.mocker.engine import MockerConfig, MockerEngine

        async def body():
            recorder = get_recorder()
            eng = MockerEngine(MockerConfig(
                prefill_us_per_token=500.0, decode_base_ms=20.0,
                max_prefill_tokens_per_step=64))
            rid = uuid.uuid4().hex
            recorder.start(rid, model="mock-model")
            recorder.stamp(rid, "queued")
            request = PreprocessedRequest(
                request_id=rid, token_ids=list(range(64)),
                sampling=SamplingOptions(max_tokens=3, temperature=0.0),
                stop=StopConditions(),
            )
            first_token_at = None
            async for out in eng.generate(request.to_wire()):
                if out.get("t") and first_token_at is None:
                    first_token_at = time.time()
                    recorder.stamp(rid, "first_token", ts=first_token_at)
            await eng.close()
            tl = recorder.finish(rid, "ok")
            assert tl.device.get("prefill_device_ms", 0) > 0
            assert tl.device.get("decode_device_ms", 0) > 0
            # Simulated prefill burn is bounded by the observed TTFT
            # (device + host can never exceed the wall it models).
            ttft_ms = (first_token_at - tl.phases["received"]) * 1e3
            burn = (tl.device["prefill_device_ms"]
                    + tl.device.get("prefill_host_ms", 0.0))
            assert burn <= ttft_ms * 1.25 + 5.0
            breakdown = PhaseBreakdownSource("unused").ingest(
                recorder.snapshot())
            assert breakdown.samples == 1
            assert breakdown.prefill_device_ms > 0
            assert breakdown.decode_device_ms > 0

        run(body(), timeout=60)


class _Collector(http.server.BaseHTTPRequestHandler):
    def do_POST(self):
        length = int(self.headers.get("Content-Length", 0))
        self.server.captured.append(json.loads(self.rfile.read(length)))
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.end_headers()
        self.wfile.write(b"{}")

    def log_message(self, *a):
        pass


def _start_collector():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Collector)
    srv.captured = []
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


def _spans_of(srv):
    spans = []
    for payload in srv.captured:
        for rs in payload.get("resourceSpans", []):
            for ss in rs.get("scopeSpans", []):
                spans.extend(ss.get("spans", []))
    return spans


class TestDeviceExecuteSpanParentage:
    def test_device_execute_nests_under_phase_spans(self):
        from dynamo_tpu.engine.worker import TpuWorker
        from dynamo_tpu.runtime.flight_recorder import RequestTimeline
        from dynamo_tpu.runtime.otel import Tracer

        srv, endpoint = _start_collector()
        tracer = Tracer(endpoint)
        worker_span = tracer.start_span("worker.generate", kind=2)
        now = time.time()
        timeline = RequestTimeline(request_id="r1")
        timeline.phases = {
            "received": now - 1.0, "queued": now - 0.9,
            "scheduled": now - 0.8, "prefill_start": now - 0.7,
            "first_token": now - 0.5, "finished": now,
        }
        timeline.device = {"prefill_device_ms": 120.0,
                           "decode_device_ms": 300.0}
        TpuWorker._record_phase_trace(
            object(), tracer, worker_span, timeline, False)
        worker_span.end()
        assert tracer.flush() > 0
        spans = _spans_of(srv)
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        assert "worker.prefill" in by_name
        assert "worker.decode" in by_name
        devs = by_name.get("worker.device_execute", [])
        assert len(devs) == 2
        by_id = {s["spanId"]: s for s in spans}
        parents = {by_id[d["parentSpanId"]]["name"] for d in devs}
        assert parents == {"worker.prefill", "worker.decode"}
        for d in devs:
            parent = by_id[d["parentSpanId"]]
            assert d["traceId"] == parent["traceId"]
            # The device slice lies inside its phase segment.
            assert int(d["startTimeUnixNano"]) >= \
                int(parent["startTimeUnixNano"])
            assert int(d["endTimeUnixNano"]) <= \
                int(parent["endTimeUnixNano"])
        srv.shutdown()


def _mem_cfg(cluster):
    from dynamo_tpu.runtime import RuntimeConfig

    cfg = RuntimeConfig.from_env()
    cfg.discovery_backend = "mem"
    cfg.discovery_path = cluster
    cfg.request_plane = "mem"
    cfg.event_plane = "mem"
    cfg.system_enabled = False
    return cfg


class TestDeviceTtftE2E:
    def test_frontend_ttft_decomposes_with_exemplar(self, run,
                                                    monkeypatch):
        from dynamo_tpu.runtime.otel import reset_tracer

        srv, endpoint = _start_collector()
        monkeypatch.setenv("DYNT_OTLP_ENDPOINT", endpoint)
        monkeypatch.setenv("DYNT_DEBUG_ENDPOINTS", "1")
        reset_tracer()

        async def body():
            from dynamo_tpu.frontend import Frontend
            from dynamo_tpu.mocker import MockerConfig, MockerWorker
            from dynamo_tpu.runtime import DistributedRuntime

            rt = await DistributedRuntime(
                _mem_cfg(uuid.uuid4().hex)).start()
            # Big modeled step times: the 10% sum tolerance must dwarf
            # asyncio sleep jitter (prefill ~100ms, decode 15ms/step).
            worker = MockerWorker(rt, model_name="mock-model",
                                  config=MockerConfig(
                                      prefill_us_per_token=400.0,
                                      decode_base_ms=15.0,
                                      max_prefill_tokens_per_step=128,
                                      num_blocks=256))
            await worker.start()
            frontend = Frontend(rt, host="127.0.0.1", port=0,
                                router_mode="round_robin")
            await frontend.start()
            for _ in range(100):
                if frontend.manager.get("mock-model") is not None:
                    break
                await asyncio.sleep(0.05)
            base = f"http://127.0.0.1:{frontend.port}"
            payload = {
                "model": "mock-model",
                "messages": [{"role": "user", "content": "x" * 256}],
                "max_tokens": 4,
            }
            async with aiohttp.ClientSession() as session:
                async with session.post(f"{base}/v1/chat/completions",
                                        json=payload) as resp:
                    assert resp.status == 200, await resp.text()
                async with session.get(f"{base}/debug/requests") as resp:
                    snap = await resp.json()
                async with session.get(
                        f"{base}/metrics",
                        headers={"Accept":
                                 "application/openmetrics-text"}) as resp:
                    metrics_text = await resp.text()
            await frontend.close()
            await worker.close()
            await rt.shutdown()
            return snap, metrics_text

        try:
            snap, metrics_text = run(body(), timeout=120)
        finally:
            monkeypatch.delenv("DYNT_OTLP_ENDPOINT", raising=False)
            reset_tracer()
            srv.shutdown()
        done = [tl for tl in snap["completed"]
                if tl["status"] == "ok" and tl["phases"].get("first_token")]
        assert done, snap
        tl = done[0]
        phases, device = tl["phases"], tl["device"]
        ttft_ms = (phases["first_token"] - phases["received"]) * 1e3
        queue_ms = (phases.get("scheduled", phases["received"])
                    - phases["received"]) * 1e3
        host_ms = device.get("prefill_host_ms", 0.0)
        device_ms = device["prefill_device_ms"]
        assert device_ms > 0
        # The acceptance bar: queue + host + device within 10% of the
        # measured TTFT.
        total = queue_ms + host_ms + device_ms
        assert abs(total - ttft_ms) <= 0.10 * ttft_ms, \
            (total, ttft_ms, tl)
        # Device-time TTFT exported with a trace-id exemplar.
        ttft_lines = [line for line in metrics_text.splitlines()
                      if line.startswith("dynamo_ttft_device_ms")]
        assert ttft_lines
        assert any("# {" in line and "trace_id=" in line
                   for line in ttft_lines), ttft_lines[:5]


class TestProfileEndpoint:
    @pytest.mark.parametrize("query,extra", [
        ("", {"trace.json.gz"}), ("&export=xplane", set())])
    def test_capture_returns_artifact(self, run, tmp_path,
                                      monkeypatch, query, extra):
        """The capture holds this process's named sections, and the
        answer says what starting and stopping the profiler cost;
        `export=xplane` writes the .xplane.pb and nothing beside it."""
        monkeypatch.setenv("DYNT_PROF_DIR", str(tmp_path))
        import jax.numpy as jnp

        from dynamo_tpu.perf.steptrace import annotation

        async def work():
            for _ in range(30):
                with annotation("sched.probe", section=True):
                    jnp.zeros(8).block_until_ready()
                await asyncio.sleep(0.005)

        async def body():
            from dynamo_tpu.runtime.status import SystemStatusServer

            server = SystemStatusServer(port=0, host="127.0.0.1")
            await server.start()
            base = f"http://127.0.0.1:{server.port}"
            async with aiohttp.ClientSession() as session:
                busy = asyncio.ensure_future(work())
                async with session.get(
                        f"{base}/debug/profile?duration_ms=60"
                        + query) as resp:
                    body_json = await resp.json()
                    status = resp.status
                await busy
            await server.close()
            return status, body_json

        status, body_json = run(body(), timeout=90)
        assert status == 200, body_json
        assert body_json["trace_dir"].startswith(str(tmp_path))
        import os

        from jax.profiler import ProfileData

        assert os.path.isdir(body_json["trace_dir"])
        xplane = [f for f in body_json["files"] if f.endswith(".xplane.pb")]
        assert xplane, body_json["files"]
        names = {e.name for plane in ProfileData.from_file(os.path.join(
            body_json["trace_dir"], xplane[0])).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
        assert "sched.probe" in names
        assert body_json["start_s"] >= 0 and body_json["stop_s"] > 0
        assert {f.split(".", 1)[1] for f in body_json["files"]} == {
            "xplane.pb"} | extra

    def test_bad_duration_rejected(self, run, monkeypatch):
        async def body():
            from dynamo_tpu.runtime.status import SystemStatusServer

            server = SystemStatusServer(port=0, host="127.0.0.1")
            await server.start()
            base = f"http://127.0.0.1:{server.port}"
            async with aiohttp.ClientSession() as session:
                async with session.get(
                        f"{base}/debug/profile?duration_ms=bogus") as resp:
                    status = resp.status
            await server.close()
            return status

        assert run(body(), timeout=30) == 400
