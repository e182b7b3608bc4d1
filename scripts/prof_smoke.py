#!/usr/bin/env python
"""Prof smoke: device-time attribution plane end-to-end, chip-free.

CI entrypoint (the `prof-smoke` job): bring up a mocker worker and the
OpenAI frontend on in-process planes with sizeable modeled step times,
run a short burst of chat requests, then assert

  * the per-request decomposition invariant — every ok timeline's
    queue + host + device components sum to within tolerance of its
    measured TTFT (the attributable TTFT),
  * `dynamo_ttft_device_ms` exported with a `trace_id` exemplar on the
    OpenMetrics scrape,
  * `/debug/profile` runs an on-demand jax.profiler capture and
    returns a trace artifact directory with files in it,

and write the capture manifest + recorder snapshot as CI artifacts.
Exits nonzero on any violated invariant.

Usage: python scripts/prof_smoke.py [--requests N] [--out DIR]
"""

from __future__ import annotations

import argparse
import asyncio
import http.server
import json
import os
import pathlib
import sys
import threading
import uuid

# Runnable as `python scripts/prof_smoke.py` from the repo root.
sys.path.insert(0, str(pathlib.Path(__file__).parent.parent))

PASS_TIMEOUT = 120.0
# Sum tolerance: modeled step times are ~100ms so CI sleep jitter sits
# well inside 10%; keep a small absolute floor for the queue edge.
SUM_TOLERANCE_FRAC = 0.10
SUM_TOLERANCE_ABS_MS = 5.0


def start_collector():
    class Collector(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            length = int(self.headers.get("Content-Length", 0))
            self.rfile.read(length)
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.end_headers()
            self.wfile.write(b"{}")

        def log_message(self, *args):
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Collector)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


async def run_pass(n_requests: int):
    import aiohttp

    from dynamo_tpu.frontend import Frontend
    from dynamo_tpu.mocker import MockerConfig, MockerWorker
    from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig

    cfg = RuntimeConfig.from_env()
    cfg.discovery_backend = "mem"
    cfg.discovery_path = uuid.uuid4().hex
    cfg.request_plane = "mem"
    cfg.event_plane = "mem"
    cfg.system_enabled = False

    rt = await DistributedRuntime(cfg).start()
    worker = MockerWorker(
        rt, model_name="mock-model",
        config=MockerConfig(prefill_us_per_token=400.0,
                            decode_base_ms=15.0,
                            max_prefill_tokens_per_step=128,
                            num_blocks=512),
        load_publish_interval=0.2)
    await worker.start()
    frontend = Frontend(rt, host="127.0.0.1", port=0,
                        router_mode="round_robin")
    await frontend.start()
    for _ in range(100):
        if frontend.manager.get("mock-model") is not None:
            break
        await asyncio.sleep(0.05)
    else:
        raise RuntimeError("mocker never registered with the frontend")

    base = f"http://127.0.0.1:{frontend.port}"

    async def one_request(session, i):
        payload = {
            "model": "mock-model",
            "messages": [{"role": "user",
                          "content": f"prof smoke {i} " + "x" * 200}],
            "max_tokens": 4,
        }
        async with session.post(f"{base}/v1/chat/completions",
                                json=payload) as resp:
            body = await resp.json()
            assert resp.status == 200, body
            return body

    async with aiohttp.ClientSession() as session:
        await asyncio.gather(*[one_request(session, i)
                               for i in range(n_requests)])
        # On-demand capture WHILE the serving process is alive.
        async with session.get(
                f"{base}/debug/profile?duration_ms=200") as resp:
            profile = await resp.json()
            profile["_status"] = resp.status
        async with session.get(f"{base}/debug/requests") as resp:
            snapshot = await resp.json()
        async with session.get(
                f"{base}/metrics",
                headers={"Accept":
                         "application/openmetrics-text"}) as resp:
            metrics_text = await resp.text()

    await frontend.close()
    await worker.close()
    await rt.shutdown()
    return profile, snapshot, metrics_text


def check_decomposition(snapshot) -> tuple[list[dict], list[str]]:
    """The invariant the plane exists for: every ok timeline's
    queue + host + device sums to its measured TTFT within tolerance."""
    rows, failures = [], []
    done = [tl for tl in snapshot.get("completed", [])
            if tl.get("status") == "ok"
            and tl.get("phases", {}).get("first_token")]
    if not done:
        return rows, ["no ok timelines with a first_token phase"]
    for tl in done:
        phases, device = tl["phases"], tl.get("device", {})
        ttft_ms = (phases["first_token"] - phases["received"]) * 1e3
        queue_ms = (phases.get("scheduled", phases["received"])
                    - phases["received"]) * 1e3
        host_ms = device.get("prefill_host_ms", 0.0)
        device_ms = device.get("prefill_device_ms", 0.0)
        total = queue_ms + host_ms + device_ms
        row = {"request_id": tl["request_id"],
               "ttft_ms": round(ttft_ms, 3),
               "queue_ms": round(queue_ms, 3),
               "host_ms": round(host_ms, 3),
               "device_ms": round(device_ms, 3),
               "sum_ms": round(total, 3)}
        rows.append(row)
        if device_ms <= 0:
            failures.append(f"{tl['request_id']}: no device time "
                            "attributed")
        tol = SUM_TOLERANCE_FRAC * ttft_ms + SUM_TOLERANCE_ABS_MS
        if abs(total - ttft_ms) > tol:
            failures.append(
                f"{tl['request_id']}: decomposition sum {total:.1f}ms "
                f"vs TTFT {ttft_ms:.1f}ms exceeds tolerance {tol:.1f}ms")
    return rows, failures


def main() -> int:
    parser = argparse.ArgumentParser("prof_smoke")
    parser.add_argument("--requests", type=int, default=8)
    parser.add_argument("--out", default=".",
                        help="artifact directory (prof-smoke-manifest."
                             "json + prof-smoke-recorder.json)")
    args = parser.parse_args()

    srv, endpoint = start_collector()
    # Before the first get_tracer()/get_recorder(): exemplars need a
    # live trace context, the debug endpoints need the opt-in.
    os.environ["DYNT_OTLP_ENDPOINT"] = endpoint
    os.environ["DYNT_DEBUG_ENDPOINTS"] = "1"
    os.environ.setdefault("DYNT_PROF_DIR",
                          str(pathlib.Path(args.out) / "captures"))

    profile, snapshot, metrics_text = asyncio.run(
        asyncio.wait_for(run_pass(args.requests), PASS_TIMEOUT))
    srv.shutdown()

    rows, failures = check_decomposition(snapshot)

    if profile.get("_status") != 200:
        failures.append(f"/debug/profile answered {profile}")
    elif not profile.get("files"):
        failures.append(f"profile capture wrote no files: {profile}")

    ttft_lines = [line for line in metrics_text.splitlines()
                  if line.startswith("dynamo_ttft_device_ms")]
    if not ttft_lines:
        failures.append("dynamo_ttft_device_ms missing from /metrics")
    elif not any("# {" in line and "trace_id=" in line
                 for line in ttft_lines):
        failures.append("dynamo_ttft_device_ms carries no trace_id "
                        "exemplar")

    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / "prof-smoke-manifest.json").write_text(json.dumps({
        "profile": profile,
        "decomposition": rows,
        "failures": failures,
    }, indent=2))
    (out / "prof-smoke-recorder.json").write_text(
        json.dumps(snapshot, indent=2))

    print(f"prof-smoke: {len(rows)} decomposed timelines, capture at "
          f"{profile.get('trace_dir')!r} "
          f"({len(profile.get('files') or [])} files)")
    for failure in failures:
        print(f"FAIL: {failure}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
