"""The system under test as child processes: one worker, one frontend.

The skeleton is `chip_smoke.py`'s (JAX-free parent, native build first,
spawn -> model listed -> scrape `/metrics` -> SIGTERM, wait for clean
exits). This process never imports JAX: a chip belongs to one process.
"""

from __future__ import annotations

import glob
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

EXIT_DEADLINE_SECS = 35.0  # the program's drain deadline + teardown slack


class FleetError(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: str, n: int = 30) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def http_get(url: str, timeout: float = 10.0) -> tuple[int, bytes]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read()


def parse_metrics(text: str) -> dict:
    """{sample name: [(labels, value)]} from a Prometheus text page."""
    from prometheus_client.parser import text_string_to_metric_families

    out: dict = {}
    for family in text_string_to_metric_families(text):
        for sample in family.samples:
            out.setdefault(sample.name, []).append(
                (dict(sample.labels), sample.value))
    return out


def build_native(root: str, env: dict) -> None:
    """Build `dynamo_tpu/_native` from csrc/ once if it is missing, so
    that children do not race the import-time build."""
    if glob.glob(os.path.join(root, "dynamo_tpu", "_native*.so")):
        return
    build = subprocess.run(
        [sys.executable, "setup.py", "build_ext", "--inplace"], cwd=root,
        env=env, capture_output=True, text=True, timeout=300)
    if build.returncode or not glob.glob(
            os.path.join(root, "dynamo_tpu", "_native*.so")):
        raise FleetError("native extension did not build: "
                         + (build.stderr or build.stdout)[-600:])


class Fleet:
    def __init__(self, root: str, env: dict, logdir: str,
                 worker_args: list[str], record_path: str) -> None:
        self.root = root
        self.logdir = logdir
        self.front_port = free_port()
        self.status_port = free_port()
        self.base = f"http://127.0.0.1:{self.front_port}"
        self.status = f"http://127.0.0.1:{self.status_port}"
        self.procs: dict[str, subprocess.Popen] = {}
        self.logs: dict[str, str] = {}
        self._env = env
        self._worker_args = worker_args
        self._record_path = record_path

    def start(self) -> None:
        self._spawn("worker", [sys.executable, "-m", "dynamo_tpu.worker",
                               *self._worker_args],
                    dict(self._env, DYNT_SYSTEM_PORT=str(self.status_port)))
        self._spawn("frontend", [
            sys.executable, "-m", "dynamo_tpu.frontend", "--host",
            "127.0.0.1", "--port", str(self.front_port), "--router-mode",
            "kv", "--record", self._record_path],
            dict(self._env, DYNT_SYSTEM_PORT=str(free_port())))

    def _spawn(self, name: str, cmd: list[str], env: dict) -> None:
        path = os.path.join(self.logdir, f"{name}.log")
        self.logs[name] = path
        with open(path, "w") as out:
            self.procs[name] = subprocess.Popen(
                cmd, cwd=self.root, env=env, stdout=out,
                stderr=subprocess.STDOUT)

    def check_alive(self) -> None:
        for name, proc in self.procs.items():
            if proc.poll() is not None:
                raise FleetError(
                    f"{name} exited with {proc.returncode}:\n"
                    f"{tail(self.logs[name])}")

    def scrape(self) -> dict:
        status, body = http_get(f"{self.status}/metrics")
        if status != 200:
            raise FleetError(f"worker /metrics -> {status}")
        return parse_metrics(body.decode())

    def wait_for_model(self, model: str, deadline_s: float) -> dict:
        """Until the frontend lists the model and the engine reports in;
        returns the engine's `dynamo_engine_info` labels."""
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            self.check_alive()
            try:
                _, body = http_get(f"{self.base}/v1/models", timeout=5)
                listed = model in [m["id"]
                                   for m in json.loads(body)["data"]]
                metrics = self.scrape()
                info = metrics.get("dynamo_engine_info", [])
                if listed and info and metrics.get("dynamo_drain_state"):
                    return info[0][0]
            except (OSError, ValueError, KeyError, FleetError):
                pass  # not listening yet
            time.sleep(0.5)
        raise FleetError(f"{model} not listed after {deadline_s:.0f}s:\n"
                         f"{tail(self.logs['worker'])}")

    def profile(self, duration_ms: int, xplane_only: bool = False) -> dict:
        """The worker's own /debug/profile: a device trace with the
        program's StepTraceAnnotation spans on the same clock. With
        `xplane_only` (--trace 2) the worker is asked to write the
        `.xplane.pb`, which is all the reduction reads, and to skip the
        conversion to `trace.json.gz`, which is most of what a stop costs."""
        status, body = http_get(
            f"{self.status}/debug/profile?duration_ms={duration_ms}"
            + ("&export=xplane" if xplane_only else ""),
            timeout=duration_ms / 1e3 + 120)
        if status != 200:
            raise FleetError(f"/debug/profile -> {status}: {body[:200]!r}")
        return json.loads(body)

    def stop(self) -> dict:
        """SIGTERM both; clean exits inside the drain deadline."""
        result: dict = {"ok": True}
        for name in ("frontend", "worker"):
            if name in self.procs and self.procs[name].poll() is None:
                self.procs[name].send_signal(signal.SIGTERM)
        for name in ("frontend", "worker"):
            proc = self.procs.get(name)
            if proc is None:
                continue
            try:
                proc.wait(timeout=EXIT_DEADLINE_SECS)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=30)
                result["ok"] = False
            result[name] = proc.returncode
            if proc.returncode != 0:
                result["ok"] = False
        return result

    def kill(self) -> None:
        for proc in self.procs.values():
            if proc.poll() is None:
                proc.kill()
        for proc in self.procs.values():
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
