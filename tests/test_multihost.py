"""Multi-host worker: one engine spanning 2 OS processes via
jax.distributed (VERDICT round-3 ask #2).

The e2e tier spawns a driver (rank 0, serves endpoints) + a follower
(rank 1, engine-only) with 4 virtual CPU devices EACH — an 8-device
global mesh no single process could build — plus a frontend, and chats
through it. A single-process 8-device worker with the same mesh shape
serves as the numerical oracle: greedy completions must match exactly
(same mesh -> same partitioning -> same numerics).

Ref analog: vLLM headless multi-node mode
(components/src/dynamo/vllm/main.py:79-110)."""

import asyncio
import os
import subprocess
import sys
import time
import uuid

import numpy as np
import pytest

from dynamo_tpu.parallel.multihost import MultihostConfig, _dec, _enc
from jax_capabilities import requires_multicore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(
    os.environ.get("DYNT_SKIP_CHAOS") == "1",
    reason="multi-process tier disabled")


class TestPlanCodec:
    def test_roundtrip(self):
        try:
            import ml_dtypes
            bf16 = np.dtype(ml_dtypes.bfloat16)
        except ImportError:
            bf16 = np.dtype(np.float16)
        obj = {
            "arr": np.arange(12, dtype=np.int32).reshape(3, 4),
            "f32": np.ones(3, np.float32),
            "bf16": np.ones((2, 2)).astype(bf16),
            "scalar": np.int32(7),
            "tup": (1, 2.5, "x", None, True),
            "nested": [{"a": np.zeros(2, np.uint32)}, b"raw"],
        }
        out = _dec(_enc(obj))
        assert isinstance(out["tup"], tuple)
        np.testing.assert_array_equal(out["arr"], obj["arr"])
        assert out["arr"].dtype == np.int32
        assert out["bf16"].dtype == bf16
        assert out["scalar"] == 7 and isinstance(out["scalar"], np.int32)
        assert out["nested"][1] == b"raw"

    def test_rejects_unencodable(self):
        with pytest.raises(TypeError):
            _enc(object())


class TestConfigParse:
    def test_parse(self):
        cfg = MultihostConfig.parse("1/4@10.0.0.9:8476")
        assert cfg.process_id == 1 and cfg.num_processes == 4
        assert cfg.coordinator == "10.0.0.9:8476"
        assert cfg.plan_host_port == ("10.0.0.9", 8477)
        assert not cfg.is_driver

    def test_bad_spec(self):
        with pytest.raises(ValueError):
            MultihostConfig.parse("nope")


def _spawn(module, *args, env, log_path):
    f = open(log_path, "w")
    return subprocess.Popen(
        [sys.executable, "-m", module, *args],
        stdout=f, stderr=subprocess.STDOUT, env=env, cwd=REPO)


async def _wait_models(session, base, model, timeout=240.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            async with session.get(base + "/v1/models") as resp:
                body = await resp.json()
                if any(m["id"] == model for m in body.get("data", [])):
                    return True
        except Exception:  # noqa: BLE001 — not up yet
            pass
        await asyncio.sleep(0.5)
    return False


def _worker_flags():
    return ["--model", "tiny-test", "--page-size", "4", "--num-pages", "64",
            "--max-batch", "4", "--max-pages-per-seq", "16",
            "--dp", "4", "--tp", "2"]


REQ = {
    "model": "tiny-test",
    "messages": [{"role": "user", "content": "abcdefgh"}],
    "max_tokens": 8,
    "temperature": 0.0,
    "seed": 0,
}


@requires_multicore
class TestTwoProcessWorker:
    def test_spans_processes_and_matches_single_process(self, run,
                                                        tmp_path):
        import aiohttp

        salt = uuid.uuid4().int
        mh_port = 18700 + (salt % 200)
        fe_port = 18950 + (salt % 200)
        fe2_port = 19150 + (salt % 200)

        def _env(disc, devices):
            env = dict(os.environ)
            env.update({
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS":
                    f"--xla_force_host_platform_device_count={devices}",
                "PYTHONPATH": REPO,
                "DYNT_DISCOVERY_BACKEND": "file",
                "DYNT_DISCOVERY_PATH": str(tmp_path / disc),
                "DYNT_REQUEST_PLANE": "tcp",
                "DYNT_EVENT_PLANE": "zmq",
                "DYNT_SYSTEM_ENABLED": "false",
                "DYNT_LOG_LEVEL": "INFO",
            })
            return env

        env_mh = _env("disc_mh", 4)   # 4 local devices per process
        env_one = _env("disc_one", 8)  # oracle: all 8 in one process
        logs = tmp_path / "logs"
        logs.mkdir()
        procs = []
        try:
            follower = _spawn(
                "dynamo_tpu.worker", *_worker_flags(),
                "--multihost", f"1/2@127.0.0.1:{mh_port}",
                env=env_mh, log_path=logs / "follower.log")
            driver = _spawn(
                "dynamo_tpu.worker", *_worker_flags(),
                "--multihost", f"0/2@127.0.0.1:{mh_port}",
                env=env_mh, log_path=logs / "driver.log")
            fe = _spawn("dynamo_tpu.frontend", "--port", str(fe_port),
                        env=env_mh, log_path=logs / "fe.log")
            oracle = _spawn("dynamo_tpu.worker", *_worker_flags(),
                            env=env_one, log_path=logs / "oracle.log")
            fe2 = _spawn("dynamo_tpu.frontend", "--port", str(fe2_port),
                         env=env_one, log_path=logs / "fe2.log")
            procs = [follower, driver, fe, oracle, fe2]

            async def body():
                base = f"http://127.0.0.1:{fe_port}"
                base2 = f"http://127.0.0.1:{fe2_port}"
                async with aiohttp.ClientSession() as session:
                    ok = await _wait_models(session, base, "tiny-test")
                    for p, name in [(follower, "follower"),
                                    (driver, "driver")]:
                        assert p.poll() is None, (
                            f"{name} died:\n"
                            + (logs / f"{name}.log").read_text()[-3000:])
                    assert ok, ("model never appeared: \n"
                                + (logs / "driver.log").read_text()[-3000:])
                    async with session.post(
                            base + "/v1/chat/completions", json=REQ) as r:
                        assert r.status == 200
                        multi = await r.json()
                    assert await _wait_models(session, base2, "tiny-test")
                    async with session.post(
                            base2 + "/v1/chat/completions", json=REQ) as r:
                        assert r.status == 200
                        single = await r.json()
                    multi_text = multi["choices"][0]["message"]["content"]
                    single_text = single["choices"][0]["message"]["content"]
                    # Same global mesh shape => identical partitioning =>
                    # bit-identical greedy sampling across the two setups.
                    assert multi_text == single_text
                    assert multi["usage"]["completion_tokens"] >= 1
                    assert (multi["usage"]["completion_tokens"]
                            == single["usage"]["completion_tokens"])
                    # second request exercises steady-state decode reuse
                    async with session.post(
                            base + "/v1/chat/completions", json=REQ) as r:
                        assert r.status == 200
                        again = await r.json()
                    assert (again["choices"][0]["message"]["content"]
                            == multi_text)

            run(body(), timeout=420.0)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            deadline = time.time() + 10
            for p in procs:
                try:
                    p.wait(timeout=max(0.1, deadline - time.time()))
                except subprocess.TimeoutExpired:
                    p.kill()
