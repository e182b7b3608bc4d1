"""The rules `chip_smoke.py` lives by, checked without a chip.

A chip belongs to one process, so the smoke's own process and the
frontend must never import JAX; with no TPU the default invocation must
fail in seconds, naming what it found, and print no result; a device
nobody has peaks for is an error, not a CPU row; and a worker whose
engine thread dies must fail its requests and exit non-zero — the smoke
would then fail, not hang. The full tiny-test rehearsal is `slow`.
"""

import asyncio
import json
import os
import subprocess
import sys
import time
import types
import uuid

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.update(extra)
    return env


def test_smoke_and_frontend_never_import_jax():
    code = ("import sys; import chip_smoke; "
            "import dynamo_tpu.frontend.service; "
            "import dynamo_tpu.deploy.controller; "
            "import dynamo_tpu.faults.service; "
            "bad = [m for m in ('jax', 'jaxlib') if m in sys.modules]; "
            "raise SystemExit(f'imported {bad}' if bad else 0)")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=_env(),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-600:]


def test_default_invocation_without_a_tpu_fails_fast():
    t0 = time.monotonic()
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         env=_env(), capture_output=True, text=True,
                         timeout=240)
    assert out.returncode == 2
    assert out.stdout.strip() == ""  # no result to misread
    assert "platform='cpu'" in out.stderr
    assert time.monotonic() - t0 < 120


def test_last_line_is_the_verdict_and_nothing_else():
    """The driver refuses a last line with any key beyond these."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    probe = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
             "ids": [0], "versions": {"jax": "0.9.0"}}
    line = json.loads(json.dumps(chip_smoke.verdict(1, probe)))
    assert line == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_profile_endpoint_never_imports_jax():
    """`/debug/profile` on a process that has not imported JAX (a
    frontend) must answer 503 without importing it."""
    code = (
        "import asyncio, sys\n"
        "from dynamo_tpu.runtime.status import profile_response\n"
        "req = type('R', (), {'query': {}})()\n"
        "resp = asyncio.run(profile_response(req))\n"
        "assert resp.status == 503, resp.status\n"
        "assert 'jax' not in sys.modules\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=_env(),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-600:]


class TestUnknownDevices:
    @staticmethod
    def _fake(monkeypatch, platform, kind):
        import jax

        device = types.SimpleNamespace(platform=platform, device_kind=kind)
        monkeypatch.setattr(jax, "devices", lambda *a, **k: [device])
        monkeypatch.setattr(jax, "default_backend", lambda: platform)

    def test_detect_chip(self, monkeypatch):
        from dynamo_tpu.perf.steptrace import detect_chip

        assert detect_chip().name == "cpu"  # tests keep their row
        self._fake(monkeypatch, "tpu", "TPU v5 lite")
        assert detect_chip().name == "v5e"
        self._fake(monkeypatch, "tpu", "TPU v9 mega")
        with pytest.raises(ValueError, match="TPU v9 mega"):
            detect_chip()
        self._fake(monkeypatch, "gpu", "cpu-lookalike")
        with pytest.raises(ValueError, match="no peaks"):
            detect_chip()

    def test_kernel_path(self, monkeypatch):
        from dynamo_tpu.ops import kernel_path

        assert kernel_path("DYNT_ATTENTION") == "xla"  # cpu, auto
        monkeypatch.setenv("DYNT_ATTENTION", "pallas")
        assert kernel_path("DYNT_ATTENTION") == "interpret"
        monkeypatch.setenv("DYNT_ATTENTION", "fast")
        with pytest.raises(ValueError, match="DYNT_ATTENTION"):
            kernel_path("DYNT_ATTENTION")
        monkeypatch.delenv("DYNT_ATTENTION")
        self._fake(monkeypatch, "tpu", "TPU v5 lite")
        assert kernel_path("DYNT_ATTENTION") == "pallas"
        self._fake(monkeypatch, "gpu", "some gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            kernel_path("DYNT_ATTENTION")  # no silent reference kernel
        monkeypatch.setenv("DYNT_ATTENTION", "xla")
        assert kernel_path("DYNT_ATTENTION") == "xla"  # chosen knowingly


# A worker whose runner refuses every real prefill (warm-up's one-token
# chunk still compiles): what a Mosaic refusal at the first request
# looks like to the scheduler thread.
_RAISING_WORKER = """
import asyncio
from dynamo_tpu.engine import model_runner
real = model_runner.ModelRunner.prefill_chunk
def refuse(self, tokens, *args, **kwargs):
    if len(tokens) > 1:
        raise RuntimeError("injected compiler refusal")
    return real(self, tokens, *args, **kwargs)
model_runner.ModelRunner.prefill_chunk = refuse
from dynamo_tpu.engine.worker import main
asyncio.run(main(["--model", "tiny-test", "--page-size", "4",
                  "--num-pages", "32", "--max-batch", "2",
                  "--max-pages-per-seq", "8"]))
"""


def test_engine_thread_death_fails_requests_and_the_process(run, tmp_path):
    from dynamo_tpu.llm.protocols import (
        EngineOutput,
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.push_router import PushRouter

    async def body():
        disc = str(tmp_path / "disc")
        cfg = RuntimeConfig()
        cfg.discovery_backend = "file"
        cfg.discovery_path = disc
        cfg.system_enabled = False
        rt = await DistributedRuntime(cfg).start()
        proc = subprocess.Popen(
            [sys.executable, "-c", _RAISING_WORKER], cwd=REPO,
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
            env=_env(DYNT_DISCOVERY_BACKEND="file",
                     DYNT_DISCOVERY_PATH=disc, DYNT_SYSTEM_ENABLED="0",
                     DYNT_PREWARM="0"))
        try:
            endpoint = rt.namespace("dynamo").component("backend") \
                         .endpoint("generate")
            router = PushRouter(endpoint.client(), mode="round_robin")
            await router.client.start()
            await router.client.wait_for_instances(timeout=150)
            request = PreprocessedRequest(
                request_id=uuid.uuid4().hex, token_ids=list(range(30, 42)),
                sampling=SamplingOptions(max_tokens=4, temperature=0.0),
                stop=StopConditions(ignore_eos=True))
            last = None
            async for frame in router.generate(request.to_wire()):
                last = EngineOutput.from_wire(frame)
                if last.finish_reason is not None:
                    break
            # 1. The request was failed in-band with the engine's error,
            #    not left waiting.
            assert last.finish_reason == "error"
            assert "injected compiler refusal" in last.error
            await router.client.close()
            # 2. The process ends, non-zero, inside the drain deadline —
            #    it does not idle registered and healthy-looking.
            code = await asyncio.to_thread(proc.wait, 40)
            assert code not in (0, None)
            assert "engine thread died" in proc.stderr.read()
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=20)
            await rt.shutdown()

    run(body(), timeout=240)


def test_the_kernel_phases_matmul_cases_are_sound_interpreted(monkeypatch):
    """`chip_smoke.py`'s kernels phase is `ops.selfcheck`: on the tiny
    preset, interpreted, every weight-matmul case it would send to the
    chip matches its oracle, a q8 and a q4 case for each projection
    shape and row count (the attention cases are the slow rehearsal's)."""
    from dynamo_tpu.ops import selfcheck

    monkeypatch.setattr(selfcheck, "_attention_cases",
                        lambda *args, **kwargs: [])
    report = selfcheck.run_selfcheck("tiny-test", interpret=True)
    failed = [case for case in report["cases"] if not case["ok"]]
    assert report["ok"] and not failed, failed
    names = [case["name"] for case in report["cases"]]
    assert names == [
        f"{kernel}/{shape}/m{rows}"
        for shape in ("wq", "wkv", "w_up", "w_down")
        for rows in (8, 512) for kernel in ("q8_matmul", "q4_matmul")]


@pytest.mark.slow
def test_cpu_rehearsal_runs_every_phase_and_never_reads_as_a_pass():
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse-cpu"], cwd=REPO,
        env=_env(), capture_output=True, text=True, timeout=900)
    report, last = map(json.loads, out.stdout.strip().splitlines()[-2:])
    assert out.returncode == 10, out.stderr[-1500:]
    assert last == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    result = report
    assert result["ok"] is False and result["rehearsal_passed"] is True
    assert result["device"]["platform"] == "cpu"
    assert result["native"] is True
    serve = result["phases"]["serve"]
    assert serve["repeat_identical"]
    assert all(r["ok"] for r in serve["requests"])
    assert serve["engines"][0]["decode_attention"] == "interpret"
    assert result["phases"]["shutdown"]["ok"]
