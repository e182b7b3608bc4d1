"""Cold-start ladder (docs/elasticity.md): phase accounting, the
process-wide EWMA the planner consumes as scale-up lead time, the
planner's ramp projection, and the mocker's calibrated cold-start model
(the CPU-testable A/B behind bench.py's cold_start block)."""

import asyncio
import time

import pytest

from dynamo_tpu.engine.coldstart import (
    PHASES,
    ColdStartLadder,
    ColdStartLadder as _Ladder,
    last_cold_start_secs,
    observed_cold_start_secs,
    process_started,
    reset_observations,
)


@pytest.fixture(autouse=True)
def _fresh_observations():
    reset_observations()
    yield
    reset_observations()


class TestLadder:
    def test_phase_accounting_and_residual(self):
        lad = ColdStartLadder("w1", source="peer_striped")
        lad.mark("fetch", 0.5)
        lad.mark("load", 0.25)
        lad.mark("compile", 0.0)
        total = lad.first_token()
        assert total is not None and total >= 0.0
        rep = lad.report()
        assert rep["source"] == "peer_striped"
        assert rep["phases"]["fetch"] == pytest.approx(0.5)
        # first_token is the residual: total minus the accounted phases
        assert rep["phases"]["first_token"] is not None
        assert set(rep["phases"]) == set(PHASES)

    def test_boot_comes_first_and_the_phases_sum_to_the_total(
            self, monkeypatch):
        """The ladder's clock is the process's: what ran before the
        ladder was made is `boot`, marked first and once, and the total
        is process start to first token, the sum of the six phases."""
        from dynamo_tpu.runtime import conformance

        monkeypatch.setenv("DYNT_CONFORMANCE", "1")
        conformance.reset_monitor()
        age = time.monotonic() - process_started()
        lad = ColdStartLadder("boot-w")
        assert list(lad.phases) == ["boot"]
        assert age <= lad.phases["boot"] <= age + 1.0
        for name in ("fetch", "load", "compile", "register"):
            with lad.phase(name):
                time.sleep(0.01)
        time.sleep(0.01)  # registered, waiting for a client
        total = lad.first_token()
        assert list(lad.phases) == list(PHASES) and PHASES[0] == "boot"
        assert lad.phases["first_token"] >= 0.01
        assert sum(lad.phases.values()) == pytest.approx(total, abs=1e-3)
        assert total == pytest.approx(age + 0.05, abs=1.0)
        # a second boot, behind another rung, is an order the spec refuses
        late = ColdStartLadder("boot-late", started=time.monotonic())
        late.mark("fetch", 0.1)
        assert conformance.get_monitor().snapshot()["total_violations"] == 0
        late.mark("boot", 0.1)
        assert conformance.get_monitor().snapshot()["total_violations"] == 1
        conformance.reset_monitor()

    def test_process_start_is_the_kernels_and_falls_back_to_the_import(
            self, monkeypatch):
        import builtins
        import os

        import dynamo_tpu

        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        assert time.monotonic() - process_started() == pytest.approx(
            age, abs=0.5)
        # the interpreter started before the package was imported
        assert process_started() <= dynamo_tpu.IMPORTED_AT
        real_open = builtins.open

        def no_proc(path, *args, **kwargs):
            if str(path).startswith("/proc/"):
                raise OSError("no procfs here")
            return real_open(path, *args, **kwargs)

        monkeypatch.setattr(builtins, "open", no_proc)
        assert process_started() == dynamo_tpu.IMPORTED_AT

    def test_a_modeled_walk_starts_where_it_says_and_marks_its_own_boot(
            self):
        t0 = time.monotonic()
        lad = ColdStartLadder("model-w", started=t0)
        assert lad.phases == {} and lad.started == t0
        lad.mark("boot", 0.2)
        lad.mark("fetch", 0.1)
        total = lad.first_token()
        # a few milliseconds old, whatever the test process's age
        assert total < 5.0
        assert lad.phases["boot"] == 0.2

    def test_phase_contextmanager_accumulates(self):
        lad = ColdStartLadder("w2")
        with lad.phase("fetch"):
            time.sleep(0.01)
        with lad.phase("fetch"):
            time.sleep(0.01)
        assert lad.phases["fetch"] >= 0.02

    def test_first_token_idempotent(self):
        lad = ColdStartLadder("w3")
        t1 = lad.first_token()
        time.sleep(0.01)
        assert lad.first_token() == t1

    def test_observed_ewma_feeds_planner_lead(self):
        assert observed_cold_start_secs() is None
        a = ColdStartLadder("a")
        a.first_token()
        assert observed_cold_start_secs() == pytest.approx(a.total)
        assert last_cold_start_secs() == pytest.approx(a.total)
        b = ColdStartLadder("b")
        b.first_token()
        # EWMA of two observations lies between them
        lo, hi = sorted([a.total, b.total])
        assert lo <= observed_cold_start_secs() <= hi
        reset_observations()
        assert observed_cold_start_secs() is None


class TestPlannerLeadProjection:
    def _planner(self, **cfg_kwargs):
        from dynamo_tpu.planner.core import PlannerConfig, SlaPlanner
        from dynamo_tpu.planner.connectors import CallbackConnector

        cfg = PlannerConfig(adjustment_interval=10.0, **cfg_kwargs)
        return SlaPlanner(cfg, CallbackConnector(lambda c, n: None),
                          disagg=False)

    def test_rising_ramp_projects_ahead_by_lead(self):
        pl = self._planner(coldstart_lead_secs=20.0)
        assert pl._project_ahead(100.0, observed=100.0) == 100.0  # no prev
        # +50 req over a 10s interval = 5 req/s growth; 20s lead -> +100
        assert pl._project_ahead(150.0, observed=150.0) == \
            pytest.approx(250.0)

    def test_falling_ramp_never_projects_down(self):
        pl = self._planner(coldstart_lead_secs=20.0)
        pl._project_ahead(100.0, observed=100.0)
        assert pl._project_ahead(60.0, observed=60.0) == 60.0

    def test_disabled_or_no_observation_is_identity(self):
        pl = self._planner(coldstart_lead=False)
        pl._project_ahead(100.0, observed=100.0)
        assert pl._project_ahead(200.0, observed=200.0) == 200.0
        pl2 = self._planner()  # enabled, but nothing observed yet
        pl2._project_ahead(100.0, observed=100.0)
        assert pl2._project_ahead(200.0, observed=200.0) == 200.0

    def test_measured_ladder_drives_lead(self):
        lad = ColdStartLadder("lead")
        lad.mark("fetch", 0.0)
        lad.first_token()
        pl = self._planner()  # coldstart_lead_secs=0 -> use observed
        assert pl._lead_secs() == pytest.approx(observed_cold_start_secs())


class TestMockerColdStartModel:
    def _cfg(self, **kw):
        from dynamo_tpu.mocker.engine import MockerConfig, TIMING_PRESETS

        return MockerConfig(**{**TIMING_PRESETS["tpu-v5e-coldstart"], **kw})

    def test_v5e_preset_walks_all_rungs(self):
        from dynamo_tpu.mocker.engine import coldstart_phases

        phases = coldstart_phases(self._cfg())
        assert list(phases) == ["boot", "fetch", "load", "compile",
                                "register"]
        assert all(v > 0 for v in phases.values())

    def test_striped_strictly_faster_than_single_source(self):
        from dynamo_tpu.mocker.engine import coldstart_phases

        striped = coldstart_phases(self._cfg(fetch_striped=True))
        single = coldstart_phases(self._cfg(fetch_striped=False))
        assert striped["fetch"] < single["fetch"]
        assert sum(striped.values()) < sum(single.values())

    def test_warm_cache_strictly_faster_than_cold(self):
        from dynamo_tpu.mocker.engine import coldstart_phases

        warm = coldstart_phases(self._cfg(compile_cache_warm=True))
        cold = coldstart_phases(self._cfg(compile_cache_warm=False))
        assert warm["compile"] < cold["compile"]
        assert sum(warm.values()) < sum(cold.values())

    def test_mocker_worker_walk_marks_scaled_phases(self, run,
                                                    mem_runtime_config):
        """A cold mocker arrival walks the ladder before registering:
        the ladder carries every modeled rung (scaled by speedup_ratio)
        and closes on the first served token."""
        import uuid

        from dynamo_tpu.mocker.engine import MockerConfig
        from dynamo_tpu.mocker.worker import MockerWorker
        from dynamo_tpu.runtime import DistributedRuntime

        cfg = MockerConfig(coldstart=True, boot_ms=15.0, weight_bytes=1e6,
                           fetch_gbps_per_donor=1.0, load_ms=20.0,
                           compile_cache_warm=True, compile_warm_ms=30.0,
                           register_ms=10.0)

        async def body():
            rt = await DistributedRuntime(
                mem_runtime_config(uuid.uuid4().hex)).start()
            worker = MockerWorker(rt, model_name="cold-mock", config=cfg)
            t0 = time.monotonic()
            await worker.start()
            walked = time.monotonic() - t0
            try:
                rep = worker.coldstart.report()
                assert rep["total_secs"] is None  # no token served yet
                for rung in ("boot", "fetch", "load", "compile",
                             "register"):
                    assert (rep["phases"][rung] or 0.0) > 0.0
                # modeled: the boot is the configuration's, not the age
                # of the process the test runs in
                assert rep["phases"]["boot"] == pytest.approx(0.015)
                assert list(worker.coldstart.phases)[0] == "boot"
                # the walk really slept the modeled (scaled) time
                assert walked >= 0.05
            finally:
                await worker.close()
                await rt.shutdown()

        run(body(), timeout=60)
