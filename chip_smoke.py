#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call:

    python -m dynamo_tpu.frontend --router-mode kv
        -> KV router -> python -m dynamo_tpu.worker
           (scheduler thread -> paged cache -> ModelRunner -> Pallas kernels)

at the full width and depth of mistral-7b (random weights from the seed,
byte tokenizer) in the flagship precision `--weight-dtype int4 --kv-dtype
int8`, with the worker's default scheduler settings.

    python chip_smoke.py              one chip (what the driver runs)
    python chip_smoke.py --chips 4    one host's four chips: four one-chip
                                      replicas behind the KV router, then
                                      one --tp 4 worker (bf16 weights)
    python chip_smoke.py --rehearse-cpu
                                      tiny-test on the CPU with interpreted
                                      kernels, to debug this script; prints
                                      "ok": false and never exits 0

Phases, each a child process that has exited (or been stopped) before the
next one starts — this process never imports JAX, because a chip belongs
to one process and a parent that touched JAX would hold it:

    native    build dynamo_tpu/_native from csrc/ once, so children do not
              race the import-time build and fall back to Python silently
    probe     what JAX finds; no TPU ends the run here, in seconds
    kernels   dynamo_tpu.ops.selfcheck: every Pallas kernel compiled by
              Mosaic at the model's shapes against its XLA oracle
    serve     worker + frontend children; requests over HTTP; /metrics
    shutdown  SIGTERM both; clean exits inside the drain deadline

Stdout carries two JSON lines: the report (platform, device kind, count
and ids, versions, every phase), then — the last line, and nothing more
than this — the verdict the driver reads:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Exit code 0 only when every phase passed on a TPU. With no accelerator
nothing is printed to stdout.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import glob
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

# Exit codes: 0 = every phase passed on a TPU; 1 = a phase failed;
# 2 = no accelerator (nothing on stdout); 10 = the CPU rehearsal passed
# (never 0: a rehearsal must not read as a chip result).
EXIT_FAILED, EXIT_NO_CHIP, EXIT_REHEARSAL_PASSED = 1, 2, 10

MODEL_WAIT_SECS = 600.0  # weights + warm-up at 7B, cold cache
# The first requests compile what prewarm does not cover (decode_multi
# per table width, batched prefill per (batch, bucket)): minutes at 7B.
REQUEST_TIMEOUT_SECS = 600.0
# DYNT_DRAIN_DEADLINE_SECS default (runtime/config.py) + teardown slack.
EXIT_DEADLINE_SECS = 20.0 + 15.0

PROBE = """
import importlib.metadata as md, json, jax, jaxlib
def version(dist):
    try:
        return md.version(dist)
    except md.PackageNotFoundError:
        return None
devices = jax.devices()
print(json.dumps({
    "platform": devices[0].platform, "kind": devices[0].device_kind,
    "count": len(devices), "ids": [d.id for d in devices],
    "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                 "libtpu": version("libtpu")}}))
"""


class PhaseFailed(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def last_json_line(text: str) -> dict:
    for line in reversed(text.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise PhaseFailed(f"child printed no JSON: {text[-500:]!r}")


def http(method: str, url: str, body: dict | None = None,
         timeout: float = 10.0) -> tuple[int, bytes]:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read()


# -- phases ------------------------------------------------------------------


def phase_native(env: dict) -> dict:
    """Build the extension once, up front, from csrc/ — a clean checkout
    has no *.so, and children racing native.py's import-time build would
    leave the loser on the Python paths without a word."""
    build = subprocess.run(
        [sys.executable, "setup.py", "build_ext", "--inplace"], cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    built = glob.glob(os.path.join(ROOT, "dynamo_tpu", "_native*.so"))
    check = subprocess.run(
        [sys.executable, "-c",
         "from dynamo_tpu.native import get_native; "
         "raise SystemExit(0 if get_native() is not None else 1)"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    if build.returncode or not built or check.returncode:
        raise PhaseFailed(
            f"native extension did not build/load (build rc="
            f"{build.returncode}, import rc={check.returncode}): "
            f"{(build.stderr or check.stderr)[-800:]}")
    return {"ok": True, "native": True}


def phase_probe(env: dict) -> dict:
    out = subprocess.run([sys.executable, "-c", PROBE], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    if out.returncode:
        raise PhaseFailed(f"JAX did not start: {out.stderr[-800:]}")
    return last_json_line(out.stdout)


def phase_kernels(env: dict, model: str, interpret: bool,
                  logdir: str) -> dict:
    cmd = [sys.executable, "-m", "dynamo_tpu.ops.selfcheck", "--model",
           model, "--out", os.path.join(logdir, "kernels.json")]
    if interpret:
        cmd.append("--interpret")
    with open(os.path.join(logdir, "kernels.log"), "w") as err:
        out = subprocess.run(cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE,
                             stderr=err, text=True, timeout=900)
    report = last_json_line(out.stdout)
    failed = [c for c in report["cases"] if not c["ok"]]
    for case in failed:
        log(f"kernel FAILED {case['name']}: {case.get('error', '')[:300]}")
    want_mode = "interpret" if interpret else "compiled"
    return {
        "ok": bool(report["ok"] and report["mode"] == want_mode
                   and out.returncode == 0),
        "mode": report["mode"], "platform": report["platform"],
        "cases": len(report["cases"]),
        "failed": [c["name"] for c in failed],
        "worst_rel_rms_err": max(
            (c.get("rel_rms_err", 0.0) for c in report["cases"]),
            default=0.0),
    }


class Fleet:
    """The worker and frontend children of one serve phase."""

    def __init__(self, env: dict, logdir: str, tag: str,
                 worker_args: list[str]) -> None:
        self.tag = tag
        self.logdir = logdir
        self.front_port = free_port()
        self.status_port = free_port()
        self.base = f"http://127.0.0.1:{self.front_port}"
        self.status = f"http://127.0.0.1:{self.status_port}"
        self.procs: dict[str, subprocess.Popen] = {}
        self.logs: dict[str, str] = {}
        self._env = env
        self._worker_args = worker_args

    def start(self) -> None:
        self._spawn("worker", [sys.executable, "-m", "dynamo_tpu.worker",
                               *self._worker_args],
                    dict(self._env, DYNT_SYSTEM_PORT=str(self.status_port)))
        self._spawn("frontend", [sys.executable, "-m", "dynamo_tpu.frontend",
                                 "--host", "127.0.0.1", "--port",
                                 str(self.front_port), "--router-mode", "kv"],
                    dict(self._env, DYNT_SYSTEM_PORT=str(free_port())))

    def _spawn(self, name: str, cmd: list[str], env: dict) -> None:
        path = os.path.join(self.logdir, f"{self.tag}-{name}.log")
        self.logs[name] = path
        with open(path, "w") as out:
            self.procs[name] = subprocess.Popen(
                cmd, cwd=ROOT, env=env, stdout=out, stderr=subprocess.STDOUT)
        log(f"{self.tag}: started {name} pid={self.procs[name].pid}")

    def check_alive(self) -> None:
        for name, proc in self.procs.items():
            if proc.poll() is not None:
                raise PhaseFailed(
                    f"{name} exited with {proc.returncode} while serving:"
                    f"\n{tail(self.logs[name])}")

    def wait_for_model(self, model: str, replicas: int) -> None:
        """Until the frontend lists the model and every replica's engine
        reports in on the worker's /metrics."""
        deadline = time.monotonic() + MODEL_WAIT_SECS
        while time.monotonic() < deadline:
            self.check_alive()
            try:
                _, body = http("GET", f"{self.base}/v1/models", timeout=5)
                listed = model in [m["id"] for m in json.loads(body)["data"]]
                serving = len(scrape(self.status).get(
                    "dynamo_drain_state", []))
                if listed and serving >= replicas:
                    return
            except (OSError, ValueError, KeyError):
                pass  # not listening yet
            time.sleep(1.0)
        raise PhaseFailed(
            f"{model} not listed after {MODEL_WAIT_SECS:.0f}s:\n"
            f"{tail(self.logs['worker'])}")

    def stop(self) -> dict:
        """SIGTERM both; clean exits inside the drain deadline."""
        result: dict = {"ok": True}
        for name in ("frontend", "worker"):
            proc = self.procs[name]
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for name in ("frontend", "worker"):
            proc = self.procs[name]
            t0 = time.monotonic()
            try:
                proc.wait(timeout=EXIT_DEADLINE_SECS)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=30)
                result["ok"] = False
                result[name] = "killed: no exit inside the drain deadline"
                continue
            result[name] = {"exit_code": proc.returncode,
                            "secs": round(time.monotonic() - t0, 1)}
            if proc.returncode != 0:
                result["ok"] = False
                log(f"{name} exit {proc.returncode}:\n"
                    f"{tail(self.logs[name], 15)}")
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


def scrape(status_url: str) -> dict:
    """{sample name: [(labels, value)]} from a Prometheus text page."""
    from prometheus_client.parser import text_string_to_metric_families

    _, body = http("GET", f"{status_url}/metrics", timeout=10)
    out: dict = {}
    for family in text_string_to_metric_families(body.decode()):
        for sample in family.samples:
            out.setdefault(sample.name, []).append(
                (dict(sample.labels), sample.value))
    return out


def compiles_by_fn(metrics: dict) -> dict:
    return {labels["fn"]: int(v)
            for labels, v in metrics.get("dynamo_jit_compiles_total", [])}


def compile_secs_by_fn(metrics: dict) -> dict:
    return {labels["fn"]: round(v, 1) for labels, v in
            metrics.get("dynamo_jit_compile_seconds_total", [])}


def grown(after: dict, before: dict) -> dict:
    """Per-key growth of a counter snapshot, keys that did not grow left
    out."""
    return {key: round(v - before.get(key, 0), 1)
            for key, v in after.items() if v - before.get(key, 0) >= 0.05}


def histogram_mean_ms(metrics: dict, family: str) -> dict:
    sums = {labels["phase"]: v
            for labels, v in metrics.get(f"{family}_sum", [])}
    counts = {labels["phase"]: v
              for labels, v in metrics.get(f"{family}_count", [])}
    return {phase: round(sums[phase] / counts[phase], 3)
            for phase in sums if counts.get(phase)}


def complete(base: str, model: str, prompt: str, max_tokens: int) -> dict:
    status, body = http("POST", f"{base}/v1/completions", {
        "model": model, "prompt": prompt, "max_tokens": max_tokens,
        "temperature": 0, "ignore_eos": True}, REQUEST_TIMEOUT_SECS)
    if status != 200:
        raise PhaseFailed(f"/v1/completions -> {status}: {body[:300]!r}")
    doc = json.loads(body)
    choice = doc["choices"][0]
    return {"text": choice["text"], "finish": choice["finish_reason"],
            "tokens": doc["usage"]["completion_tokens"],
            "prompt_tokens": doc["usage"]["prompt_tokens"]}


def chat_stream(base: str, model: str, content: str,
                max_tokens: int) -> dict:
    req = urllib.request.Request(
        f"{base}/v1/chat/completions", method="POST",
        headers={"Content-Type": "application/json"},
        data=json.dumps({
            "model": model, "max_tokens": max_tokens, "temperature": 0,
            "ignore_eos": True, "stream": True,
            "stream_options": {"include_usage": True},
            "messages": [{"role": "user", "content": content}]}).encode())
    text, finish, usage, done, deltas = [], None, None, False, 0
    with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_SECS) as resp:
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                done = True
                break
            chunk = json.loads(line[6:])
            if "error" in chunk:
                raise PhaseFailed(f"stream error frame: {chunk}")
            usage = chunk.get("usage") or usage
            for choice in chunk.get("choices", []):
                piece = (choice.get("delta") or {}).get("content")
                if piece:
                    text.append(piece)
                    deltas += 1
                finish = choice.get("finish_reason") or finish
    if not done or usage is None:
        raise PhaseFailed("chat stream ended without [DONE] and usage")
    return {"text": "".join(text), "finish": finish, "deltas": deltas,
            "tokens": usage["completion_tokens"],
            "prompt_tokens": usage["prompt_tokens"]}


def drive_requests(fleet: Fleet, model: str, fanout: int) -> dict:
    """A handful of requests. Every new (batch, bucket) or table width is
    a compile of minutes at 7B, so the mix touches as few as it can while
    still crossing two prefill buckets and two block-table widths:

      * one short prompt twice, in sequence, at temperature 0: the
        32-token bucket and the 8-page table (56 tokens with its 24
        generated); the second answer must equal the first byte for byte;
      * then, concurrently, two long completions and one streamed chat,
        all in the 256-token bucket and the 16-page table (up to ~220
        tokens each with theirs) — `fanout` such trios, with distinct
        prompts, so a multi-replica fleet has something to spread.

    `ignore_eos` with a fixed `max_tokens` makes every count checkable."""
    t0 = time.monotonic()
    checks: list[dict] = []

    def record(name: str, want: int, got: dict) -> dict:
        ok = got["tokens"] == want and got["finish"] == "length"
        checks.append({"name": name, "ok": ok, "want_tokens": want,
                       "got_tokens": got["tokens"],
                       "prompt_tokens": got["prompt_tokens"],
                       "finish": got["finish"]})
        return got

    prompt = "A chip belongs to one process."
    first = record("repeat/1", 24, complete(fleet.base, model, prompt, 24))
    again = record("repeat/2", 24, complete(fleet.base, model, prompt, 24))
    identical = first["text"] == again["text"]
    fleet.check_alive()

    filler = "pages stream from HBM to VMEM; "  # 31 byte-tokens
    jobs = []
    for i in range(fanout):
        jobs += [
            (f"long_a/{i}", 40, complete, f"{i:02d}a " + filler * 5),
            (f"long_b/{i}", 24, complete, f"{i:02d}b " + filler * 6),
            (f"chat/{i}", 32, chat_stream, f"{i:02d}c " + filler * 3),
        ]
    with concurrent.futures.ThreadPoolExecutor(len(jobs)) as pool:
        futures = {name: (want, pool.submit(fn, fleet.base, model, p, want))
                   for name, want, fn, p in jobs}
        for name, (want, future) in futures.items():
            record(name, want, future.result(REQUEST_TIMEOUT_SECS + 30))
    fleet.check_alive()
    return {
        "ok": identical and all(c["ok"] for c in checks),
        "repeat_identical": identical,
        "requests": checks,
        "serve_secs": round(time.monotonic() - t0, 1),
    }


def engine_rows(metrics: dict) -> list[dict]:
    """One row per replica from the worker's /metrics: what it runs on,
    the kernel paths it took, tokens served, HBM in use."""
    tokens: dict = {}
    for labels, v in metrics.get("dynamo_engine_tokens", []):
        tokens.setdefault(labels["worker"], {})[labels["kind"]] = int(v)
    hbm: dict = {}
    for labels, v in metrics.get("dynamo_device_hbm_bytes", []):
        if labels["kind"] == "in_use":
            hbm.setdefault(labels["worker"], {})[labels["device"]] = int(v)
    rows = []
    for labels, _ in metrics.get("dynamo_engine_info", []):
        worker = labels.pop("worker")
        rows.append({
            "worker": worker, **labels,
            "devices": [int(d) for d in labels["devices"].split(",")],
            "native": labels["native"] == "true",
            "tokens": tokens.get(worker, {}),
            "hbm_bytes_in_use": hbm.get(worker, {})})
    return rows


def phase_serve(env: dict, logdir: str, tag: str, model: str,
                worker_args: list[str], replicas: int, rehearsal: bool,
                need_pallas: tuple[str, ...]) -> tuple[dict, dict]:
    """Returns (serve result, shutdown result)."""
    result: dict = {"ok": False, "worker_args": worker_args}
    t_spawn = time.monotonic()
    fleet = Fleet(env, logdir, tag, ["--model", model, *worker_args])
    try:
        fleet.start()
        fleet.wait_for_model(model, replicas)
        result["setup_secs"] = round(time.monotonic() - t_spawn, 1)
        before = scrape(fleet.status)
        result["compiles_at_setup"] = compiles_by_fn(before)
        secs_before = compile_secs_by_fn(before)
        result["compile_secs_at_setup"] = secs_before
        log(f"{tag}: model listed after {result['setup_secs']}s; "
            f"compiles at set-up {result['compiles_at_setup']} "
            f"({sum(secs_before.values()):.0f}s)")
        result.update(drive_requests(fleet, model, fanout=replicas))
        time.sleep(1.5)  # the worker publishes its gauges every ~0.5s
        after = scrape(fleet.status)
        result["compiles_while_serving"] = grown(
            compiles_by_fn(after), result["compiles_at_setup"])
        result["compile_secs_while_serving"] = grown(
            compile_secs_by_fn(after), secs_before)
        result["step_device_ms_mean"] = histogram_mean_ms(
            after, "dynamo_step_device_ms")
        result["step_host_ms_mean"] = histogram_mean_ms(
            after, "dynamo_step_host_ms")
        result["host_bound"] = {
            labels["worker"]: int(v)
            for labels, v in after.get("dynamo_host_bound", [])}
        rows = engine_rows(after)
        result["engines"] = rows
        problems = []
        if len(rows) != replicas:
            problems.append(f"{len(rows)} engines reported, want {replicas}")
        chips = [d for row in rows for d in row["devices"]]
        if len(chips) != len(set(chips)):
            problems.append(f"replicas share a chip: {chips}")
        for row in rows:
            if not row["native"]:
                problems.append(f"{row['worker']}: native extension absent")
            if not row["tokens"].get("decode"):
                problems.append(f"{row['worker']}: served no tokens")
            for slot in need_pallas:
                want = "interpret" if rehearsal else "pallas"
                if row[slot] != want:
                    problems.append(
                        f"{row['worker']}: {slot}={row[slot]}, want {want}")
            if not rehearsal and not row["hbm_bytes_in_use"]:
                problems.append(f"{row['worker']}: no HBM reading")
        result["problems"] = problems
        result["ok"] = bool(result["ok"] and not problems)
        shutdown = fleet.stop()
    except (PhaseFailed, OSError, ValueError, KeyError,
            concurrent.futures.TimeoutError) as exc:
        result["error"] = f"{type(exc).__name__}: {str(exc)[-1500:]}"
        log(f"{tag}: serve FAILED: {result['error']}")
        shutdown = {"ok": False, "error": "not reached"}
    finally:
        fleet.kill()
    return result, shutdown


# -- main --------------------------------------------------------------------


def verdict(ok: bool, device: dict) -> dict:
    """The contract's last line of stdout: exactly these keys, the device
    as JAX reports it."""
    return {"ok": bool(ok),
            "device": {"platform": device["platform"],
                       "kind": device["kind"], "count": device["count"]}}


def main() -> int:
    parser = argparse.ArgumentParser("chip_smoke")
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4))
    parser.add_argument("--shape", default="both",
                        choices=("both", "replicas", "tp4"),
                        help="with --chips 4: which four-chip shape(s)")
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="debug this script on the CPU with tiny-test "
                             "and interpreted kernels; never exits 0")
    args = parser.parse_args()
    rehearsal = args.rehearse_cpu

    env = dict(
        os.environ, PYTHONPATH=ROOT,
        # Two deployment settings sized for a cold 7B, where ONE program
        # compiles for minutes (PERF.md, Where the time goes). Warm-up
        # keeps to decode + the smallest bucket: the full prewarm (seven
        # prefill buckets) alone would spend this script's whole time
        # limit. And a request may wait two compiles for its next frame,
        # past the 120 s default at which the frontend declares the
        # worker black-holed — the setting's own text says it must
        # exceed a cold compile.
        DYNT_PREWARM="0",
        DYNT_STREAM_IDLE_TIMEOUT_SECS="900")
    if rehearsal:
        env.update(JAX_PLATFORMS="cpu", DYNT_ATTENTION="pallas",
                   DYNT_Q4_MATMUL="pallas", DYNT_Q8_MATMUL="pallas",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
    model = "tiny-test" if rehearsal else "mistral-7b"
    # Before anything may print a result, two things must hold: JAX finds
    # the device this run is for, and the program is here and builds.
    want = "cpu" if rehearsal else "tpu"
    try:
        device = phase_probe(env)
        log(f"JAX finds {device}")
        if device["platform"] != want or device["count"] < args.chips:
            print(f"chip_smoke: needs {args.chips} {want} device(s); JAX "
                  f"finds platform={device['platform']!r} "
                  f"kind={device['kind']!r} count={device['count']} — "
                  "nothing was run and there is no result",
                  file=sys.stderr)
            return EXIT_NO_CHIP
        phases: dict = {"probe": {"ok": True}, "native": phase_native(env)}
        log("native extension built and importable")
    except (PhaseFailed, subprocess.TimeoutExpired, OSError) as exc:
        print(f"chip_smoke: {exc} — nothing was run and there is no "
              "result", file=sys.stderr)
        return EXIT_FAILED
    logdir = os.path.join(ROOT, "chiprun_out", "chip_smoke")
    os.makedirs(logdir, exist_ok=True)
    scratch = tempfile.mkdtemp(prefix="chip_smoke_")
    env.update(
        DYNT_DISCOVERY_BACKEND="file",
        DYNT_DISCOVERY_PATH=os.path.join(scratch, "discovery"),
        # One persistent compile cache for every child of the run.
        DYNT_COMPILE_CACHE_DIR=os.path.join(scratch, "compile_cache"))
    try:
        if args.chips == 1:
            # The four-chip shapes run the same kernels (the one-chip
            # run also compiles what one shard of a --tp 4 worker sees).
            phases["kernels"] = phase_kernels(env, model, rehearsal, logdir)
            log(f"kernels: {phases['kernels']}")
        small = (["--page-size", "4", "--num-pages", "256", "--max-batch",
                  "4", "--max-pages-per-seq", "64"] if rehearsal else [])
        # int8 KV needs head_dim 128; the rehearsal's tiny-test has 16.
        flagship = ["--weight-dtype", "int4",
                    "--kv-dtype", "model" if rehearsal else "int8"]
        all_slots = ("decode_attention", "spec_attention", "weight_matmul")
        if args.chips == 1:
            phases["serve"], phases["shutdown"] = phase_serve(
                env, logdir, "one", model, flagship + small, 1, rehearsal,
                all_slots)
        else:
            # (a) four one-chip replicas of the flagship behind the KV
            # router; (b) one --tp 4 worker (quantised weights are
            # single-device, so bf16) serving the same requests.
            if args.shape in ("both", "replicas"):
                phases["serve_replicas"], phases["shutdown_replicas"] = \
                    phase_serve(env, logdir, "replicas", model,
                                flagship + small + ["--replicas", "4"], 4,
                                rehearsal, all_slots)
            if args.shape in ("both", "tp4"):
                # (the rehearsal's tiny-test has two kv heads to shard)
                tp = "2" if rehearsal else "4"
                phases["serve_tp4"], phases["shutdown_tp4"] = phase_serve(
                    env, logdir, "tp4", model, small + ["--tp", tp], 1,
                    rehearsal, ("decode_attention",))
    except (PhaseFailed, subprocess.TimeoutExpired) as exc:
        phases["error"] = f"{type(exc).__name__}: {str(exc)[-1500:]}"
        log(f"FAILED: {phases['error']}")
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    passed = "error" not in phases and all(
        p.get("ok") for p in phases.values() if isinstance(p, dict))
    serve = (phases.get("serve") or phases.get("serve_replicas")
             or phases.get("serve_tp4") or {})
    engines = serve.get("engines") or [{}]
    report = dict(
        verdict(passed and not rehearsal, device),
        platform=device["platform"], device_kind=device["kind"],
        device_count=device["count"], device_ids=device["ids"],
        versions=device["versions"],
        rehearsal=rehearsal, chips=args.chips, model=model,
        native=all(e.get("native") for e in engines),
        phases=phases, total_secs=round(time.monotonic() - T0, 1))
    if rehearsal:
        report["rehearsal_passed"] = passed
    with open(os.path.join(logdir, "result.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    # The last line is the verdict alone: the driver refuses any other key.
    print(json.dumps(verdict(passed and not rehearsal, device)), flush=True)
    if rehearsal:
        return EXIT_REHEARSAL_PASSED if passed else EXIT_FAILED
    return 0 if passed else EXIT_FAILED


if __name__ == "__main__":
    T0 = time.monotonic()
    sys.exit(main())
