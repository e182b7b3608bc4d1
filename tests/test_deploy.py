"""Deployment controller tests (ref surface: deploy/operator DGD CRD +
reconcile loop). Controller logic runs against cheap stub commands; one
E2E brings up a real mocker+frontend graph and follows a planner decision."""

import asyncio
import json
import os
import sys
import uuid

import pytest
import yaml

from dynamo_tpu.deploy import (
    GraphDeploymentSpec,
    LocalDeploymentController,
    render_k8s_manifests,
)
from dynamo_tpu.deploy.spec import ServiceSpec
from dynamo_tpu.planner.connectors import TargetReplica, VirtualConnector
from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig
from jax_capabilities import requires_multicore

SLEEP_CMD = [sys.executable, "-c",
             "import time\ntime.sleep(600)"]
CRASH_CMD = [sys.executable, "-c", "import sys; sys.exit(3)"]


def _spec(**services):
    return GraphDeploymentSpec(
        name="t", namespace="dynamo",
        services={name: svc for name, svc in services.items()},
    )


class TestSpec:
    def test_yaml_parse(self, tmp_path):
        path = tmp_path / "g.yaml"
        path.write_text(yaml.safe_dump({
            "name": "demo",
            "namespace": "ns1",
            "env": {"DYNT_DISCOVERY_PATH": "/tmp/x"},
            "services": {
                "frontend": {"kind": "frontend", "replicas": 1,
                             "args": ["--port", 8000]},
                "decode": {"kind": "mocker", "replicas": 2,
                           "env": {"A": "b"}},
            },
        }))
        spec = GraphDeploymentSpec.from_yaml(str(path))
        assert spec.name == "demo" and spec.namespace == "ns1"
        assert spec.services["decode"].replicas == 2
        assert spec.services["frontend"].argv()[1:3] == [
            "-m", "dynamo_tpu.frontend"]
        assert spec.services["frontend"].args == ["--port", "8000"]

    def test_bad_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown kind"):
            ServiceSpec(name="x", kind="bogus")

    def test_command_override(self):
        svc = ServiceSpec(name="x", command=["/bin/echo"], args=["hi"])
        assert svc.argv() == ["/bin/echo", "hi"]


class TestManifests:
    def test_render(self):
        spec = _spec(
            frontend=ServiceSpec(name="frontend", kind="frontend",
                                 replicas=1, args=["--port", "8123"]),
            decode=ServiceSpec(name="decode", kind="worker", replicas=3),
        )
        docs = list(yaml.safe_load_all(render_k8s_manifests(spec)))
        kinds = [(d["kind"], d["metadata"]["name"]) for d in docs]
        assert ("Deployment", "t-frontend") in kinds
        assert ("Deployment", "t-decode") in kinds
        assert ("Service", "t-frontend") in kinds  # frontends get a Service
        dep = next(d for d in docs if d["metadata"]["name"] == "t-decode"
                   and d["kind"] == "Deployment")
        assert dep["spec"]["replicas"] == 3
        svc = next(d for d in docs if d["kind"] == "Service")
        assert svc["spec"]["ports"][0]["port"] == 8123


class TestControllerReconcile:
    def test_spawn_scale_and_drain(self, run):
        async def body():
            spec = _spec(app=ServiceSpec(name="app", command=SLEEP_CMD,
                                         replicas=2))
            ctl = LocalDeploymentController(spec, reconcile_interval=0.1)
            await ctl.reconcile_once()
            assert ctl.observed("app") == 2
            ctl.set_replicas("app", 3)
            await ctl.reconcile_once()
            assert ctl.observed("app") == 3
            ctl.set_replicas("app", 1)
            await ctl.reconcile_once()
            assert ctl.observed("app") == 1
            status = ctl.status()
            assert status["services"]["app"]["running"] == 1
            await ctl.close()
            assert ctl.observed("app") == 0

        run(body(), timeout=60)

    def test_crash_restart_with_backoff(self, run):
        async def body():
            spec = _spec(app=ServiceSpec(name="app", command=CRASH_CMD,
                                         replicas=1))
            ctl = LocalDeploymentController(spec, reconcile_interval=0.05)
            await ctl.reconcile_once()
            deadline = asyncio.get_running_loop().time() + 30
            while (ctl.restarts < 2
                   and asyncio.get_running_loop().time() < deadline):
                await asyncio.sleep(0.05)
                await ctl.reconcile_once()
            assert ctl.restarts >= 2
            # crash streak recorded and backoff engaged
            assert ctl.status()["services"]["app"]["crash_streak"] >= 2
            assert ctl._backoff_until["app"] > 0
            await ctl.close()

        run(body(), timeout=60)

    def test_follows_virtual_connector_decision(self, run):
        async def body():
            cfg = RuntimeConfig.from_env()
            cfg.discovery_backend = "mem"
            cfg.discovery_path = uuid.uuid4().hex
            cfg.request_plane = "mem"
            cfg.event_plane = "mem"
            cfg.system_enabled = False
            rt = await DistributedRuntime(cfg).start()
            spec = _spec(decode=ServiceSpec(name="decode",
                                            command=SLEEP_CMD, replicas=1))
            ctl = LocalDeploymentController(spec, runtime=rt,
                                            reconcile_interval=0.1)
            await ctl.reconcile_once()
            assert ctl.observed("decode") == 1
            # planner publishes a decision through its VirtualConnector
            connector = VirtualConnector(rt, namespace="dynamo")
            await connector.set_component_replicas(
                [TargetReplica(component="decode", desired_replicas=3)])
            await ctl.reconcile_once()
            assert ctl.desired["decode"] == 3
            assert ctl.observed("decode") == 3
            # stale decision ids are not re-applied
            ctl.set_replicas("decode", 1)
            await ctl.reconcile_once()
            assert ctl.desired["decode"] == 1
            # a RESTARTED planner's counter resets to 1 — its decisions
            # must still apply (value comparison, not monotonic)
            connector2 = VirtualConnector(rt, namespace="dynamo")
            await connector2.set_component_replicas(
                [TargetReplica(component="decode", desired_replicas=2)])
            await ctl.reconcile_once()
            assert ctl.desired["decode"] == 2
            await ctl.close()
            await rt.shutdown()

        run(body(), timeout=60)


class TestDeployE2E:
    def test_mocker_frontend_graph_serves(self, run, tmp_path):
        """Deploy a real graph (mocker + frontend) from a YAML spec and
        serve a chat request through it."""
        disc = str(tmp_path / "disc")
        port = 8400 + (uuid.uuid4().int % 200)
        spec_path = tmp_path / "graph.yaml"
        spec_path.write_text(yaml.safe_dump({
            "name": "e2e",
            "namespace": "dynamo",
            "env": {
                "DYNT_DISCOVERY_BACKEND": "file",
                "DYNT_DISCOVERY_PATH": disc,
                "DYNT_LOG_LEVEL": "WARNING",
                "JAX_PLATFORMS": "cpu",
            },
            "services": {
                "mocker": {"kind": "mocker", "replicas": 1,
                           "args": ["--model-name", "mock-model",
                                    "--speedup-ratio", "100"]},
                "frontend": {"kind": "frontend", "replicas": 1,
                             "args": ["--port", str(port)]},
            },
        }))

        async def body():
            import aiohttp

            spec = GraphDeploymentSpec.from_yaml(str(spec_path))
            ctl = LocalDeploymentController(
                spec, log_dir=str(tmp_path / "logs"))
            ctl.start()
            try:
                base = f"http://127.0.0.1:{port}"
                async with aiohttp.ClientSession() as session:
                    deadline = asyncio.get_running_loop().time() + 60
                    while True:
                        try:
                            async with session.get(
                                    f"{base}/v1/models") as resp:
                                models = await resp.json()
                                if models.get("data"):
                                    break
                        except aiohttp.ClientError:
                            pass
                        if asyncio.get_running_loop().time() > deadline:
                            pytest.fail("graph never became ready")
                        await asyncio.sleep(0.5)
                    async with session.post(
                        f"{base}/v1/chat/completions",
                        json={"model": "mock-model",
                              "messages": [{"role": "user",
                                            "content": "hi"}],
                              "max_tokens": 4},
                    ) as resp:
                        assert resp.status == 200
                        data = await resp.json()
                        assert data["choices"][0]["finish_reason"]
            finally:
                await ctl.close()

        run(body(), timeout=180)


class TestMultihostGang:
    def test_gang_renders_parallel_statefulset(self):
        """A multihost service renders one Parallel StatefulSet +
        headless Service per GANG with coscheduling pod-group
        annotations (the Grove PodCliqueSet analog)."""
        spec = _spec(
            big=ServiceSpec(name="big", kind="worker", replicas=2,
                            args=["--model", "tiny-test"], multihost=4,
                            multihost_port=7901),
        )
        docs = list(yaml.safe_load_all(render_k8s_manifests(spec)))
        stss = [d for d in docs if d["kind"] == "StatefulSet"]
        heads = [d for d in docs if d["kind"] == "Service"]
        assert {d["metadata"]["name"] for d in stss} == {"t-big-g0",
                                                         "t-big-g1"}
        assert {d["metadata"]["name"] for d in heads} == {"t-big-g0",
                                                          "t-big-g1"}
        sts = stss[0]
        assert sts["spec"]["replicas"] == 4  # N ranks per gang
        assert sts["spec"]["podManagementPolicy"] == "Parallel"
        ann = sts["spec"]["template"]["metadata"]["annotations"]
        assert ann["pod-group.scheduling.sigs.k8s.io/min-available"] == "4"
        cmd = " ".join(sts["spec"]["template"]["spec"]["containers"][0]
                       ["command"])
        assert "--multihost" in cmd and "/4@t-big-g0-0.t-big-g0" in cmd
        # no plain Deployment for the gang service
        assert not any(d["kind"] == "Deployment"
                       and "big" in d["metadata"]["name"] for d in docs)

    def test_local_controller_spawns_full_gangs(self, run):
        """Locally, one multihost replica = N co-spawned rank processes;
        observed() counts only COMPLETE gangs."""
        async def body():
            spec = _spec(g=ServiceSpec(
                name="g", command=SLEEP_CMD, replicas=1, multihost=2))
            ctl = LocalDeploymentController(spec, reconcile_interval=0.1)
            await ctl.reconcile_once()
            procs = ctl._replicas["g"]
            assert len(procs) == 2  # both ranks spawned together
            assert ctl.observed("g") == 1  # ONE complete gang
            # rank wiring: each process got its own --multihost r/N flag
            # (command override: flags appended after the sleep argv)
            await ctl.close()

        run(body(), timeout=60)

    def test_gang_argv_wiring(self):
        svc = ServiceSpec(name="w", kind="worker", replicas=1,
                          args=["--model", "m"], multihost=3,
                          multihost_port=7800)
        argv = svc.gang_argv(2, "127.0.0.1:7800")
        assert argv[-2:] == ["--multihost", "2/3@127.0.0.1:7800"]


class TestGangE2E:
    @requires_multicore
    def test_deployed_gang_serves(self, run, tmp_path):
        """The deploy controller brings up a 2-rank multihost worker
        GANG (driver + follower spanning one engine over
        jax.distributed) plus a frontend from one spec, and chat flows —
        the local realization of Grove gang scheduling."""
        disc = str(tmp_path / "disc")
        salt = uuid.uuid4().int
        port = 8650 + (salt % 150)
        mh_port = 21600 + (salt % 150) * 2
        spec = GraphDeploymentSpec.from_dict({
            "name": "gang",
            "env": {
                "DYNT_DISCOVERY_BACKEND": "file",
                "DYNT_DISCOVERY_PATH": disc,
                "DYNT_LOG_LEVEL": "INFO",
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                "DYNT_SYSTEM_ENABLED": "false",
            },
            "services": {
                "worker": {"kind": "worker", "replicas": 1,
                           "multihost": 2, "multihost_port": mh_port,
                           "args": ["--model", "tiny-test",
                                    "--page-size", "4",
                                    "--num-pages", "64",
                                    "--max-batch", "2",
                                    "--max-pages-per-seq", "16",
                                    "--tp", "2", "--dp", "2"]},
                "frontend": {"kind": "frontend", "replicas": 1,
                             "args": ["--port", str(port)]},
            },
        })

        async def body():
            import aiohttp

            from tests.chaos_util import chat, wait_models

            ctl = LocalDeploymentController(
                spec, log_dir=str(tmp_path / "logs"))
            ctl.start()
            try:
                assert ctl.observed("worker") in (0, 1)
                base = f"http://127.0.0.1:{port}"
                async with aiohttp.ClientSession() as session:
                    ok = await wait_models(session, base, "tiny-test",
                                           timeout=240.0)
                    if not ok:
                        logs = tmp_path / "logs"
                        detail = "".join(
                            f"== {p.name}\n" + p.read_text()[-1500:]
                            for p in sorted(logs.glob("*.log")))
                        pytest.fail("gang never served:\n" + detail)
                    out = await chat(session, base, "tiny-test",
                                     "gang hello", max_tokens=4,
                                     timeout=120)
                    assert out
                    # the gang is COMPLETE (both ranks alive)
                    assert ctl.observed("worker") == 1
                    assert len([r for r in ctl._replicas["worker"]
                                if r.proc.returncode is None]) == 2
            finally:
                await ctl.close()

        run(body(), timeout=420)

    def test_overlapping_gang_ports_rejected(self):
        with pytest.raises(ValueError, match="overlapping coordinator"):
            GraphDeploymentSpec.from_dict({
                "name": "p", "services": {
                    "a": {"kind": "worker", "multihost": 2,
                          "multihost_port": 7777},
                    "b": {"kind": "worker", "multihost": 2,
                          "multihost_port": 7779},
                }})

    def test_broken_gang_restarts_as_unit(self, run):
        """When one rank of a gang dies, the survivors are drained so
        the gang respawns WHOLE (jax.distributed has no elastic
        rejoin)."""
        async def body():
            spec = _spec(g=ServiceSpec(
                name="g", command=SLEEP_CMD, replicas=1, multihost=2))
            ctl = LocalDeploymentController(spec, reconcile_interval=0.1)
            await ctl.reconcile_once()
            procs = list(ctl._replicas["g"])
            assert len(procs) == 2
            pids = {r.index: r.proc.pid for r in procs}
            # kill rank 1 only
            os.kill(pids[1], 9)
            for _ in range(50):
                if procs[1].proc.returncode is not None:
                    break
                await asyncio.sleep(0.1)
            await ctl.reconcile_once()  # reap + drain survivor
            # rank 0's ORIGINAL process must be gone too (gang-unit)
            assert all(r.proc.pid != pids[0]
                       for r in ctl._replicas["g"])
            # after backoff both ranks respawn together
            ctl._backoff_until["g"] = 0.0
            await ctl.reconcile_once()
            assert ctl.observed("g") == 1
            await ctl.close()

        run(body(), timeout=60)
