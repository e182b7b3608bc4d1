"""System status server: /health, /live, /metrics, /debug/requests,
/debug/profile, /fleet, /debug/alerts.

Every runtime process exposes liveness, endpoint health, Prometheus
metrics, and its flight-recorder timelines on an HTTP port (ref:
lib/runtime/src/system_status_server.rs:131-178). /metrics negotiates
OpenMetrics (exemplars) via the Accept header; /debug/requests returns
the per-request phase timelines (filterable:
?status=&tenant=&model=&slow=&limit=&offset=); /debug/profile runs an
on-demand jax.profiler capture in THIS process and returns the trace
artifact path; /fleet and /debug/alerts serve the observatory's
rollup pane and alert log when one is installed
(docs/observability.md).
"""

from __future__ import annotations

import asyncio
import threading
import time
from typing import Callable, Optional

from aiohttp import web

from . import metrics
from .config import env
from .flight_recorder import get_recorder
from .logging import get_logger

log = get_logger("status")

# One capture at a time per process: jax.profiler.start_trace is a
# process-global session, and a second starter would raise (or worse,
# interleave two operators' captures).
_PROFILE_LOCK = threading.Lock()


def _stop_trace_xplane_only(trace_dir: str) -> None:
    """Stop the running profiler session and write its `.xplane.pb`
    where `jax.profiler.stop_trace` would, without the `trace.json.gz`
    that jax converts every capture into as well (most of what a stop
    costs; nothing but the old trace viewer reads it). jax offers the
    two steps only on its session object, so where that is not as
    expected the capture is stopped the public way."""
    import os
    import socket

    from jax import profiler

    try:
        from jax._src import profiler as jax_profiler

        state = jax_profiler._profile_state
        with state.lock:
            xspace = state.profile_session.stop()
            state.reset()
    except AttributeError:
        profiler.stop_trace()
        return
    run_dir = os.path.join(trace_dir, "plugins", "profile",
                           time.strftime("%Y_%m_%d_%H_%M_%S"))
    os.makedirs(run_dir, exist_ok=True)
    with open(os.path.join(run_dir, socket.gethostname() + ".xplane.pb"),
              "wb") as f:
        f.write(xspace)


async def profile_response(request: web.Request) -> web.Response:
    """Shared /debug/profile responder (status server + opt-in
    frontend): run `jax.profiler.start_trace` / `stop_trace` for
    ?duration_ms= (default DYNT_PROF_DEFAULT_MS, clamped to
    DYNT_PROF_MAX_MS) and answer with the capture directory. The
    engine's dispatch scopes carry StepTraceAnnotation marks and the
    scheduler's host sections TraceAnnotation marks (`sched.*`,
    perf/steptrace.py), so the capture attributes device ops to
    decode/prefill/spec phases and idle gaps to what the host was
    doing. The answer says what the capture cost: `start_s` (the
    profiler's start: the traced span begins that long after the
    request came) and `stop_s` (collecting and writing the trace,
    which is nearly all of it). `&export=xplane` writes the
    `.xplane.pb` alone (what XProf and a reduction read) and skips
    jax's conversion to `trace.json.gz`, the larger and less steady
    part of a stop. 409 while another capture runs; 503 in a process
    that has not imported JAX (it is never imported here)."""
    try:
        duration = float(request.query.get(
            "duration_ms", env("DYNT_PROF_DEFAULT_MS")))
    except ValueError:
        return web.json_response(
            {"error": "duration_ms must be a number"}, status=400)
    duration = max(1.0, min(duration, float(env("DYNT_PROF_MAX_MS"))))
    if not _PROFILE_LOCK.acquire(blocking=False):
        return web.json_response(
            {"error": "a profile capture is already running"}, status=409)
    try:
        import os
        import sys
        import uuid

        if "jax" not in sys.modules:
            # A frontend or launcher that never imported JAX has no
            # device to trace — and importing it here would open the
            # chip in a process that must not hold it (a chip belongs
            # to one process; the worker next door would lose it).
            return web.json_response(
                {"error": "this process runs no JAX engine; capture on "
                          "a worker's status port"}, status=503)
        from jax import profiler

        # Unique per capture (sub-second repeats must not share a dir —
        # the returned manifest has to identify THIS capture's files).
        trace_dir = os.path.join(
            env("DYNT_PROF_DIR"),
            time.strftime("%Y%m%d-%H%M%S") + f"-{uuid.uuid4().hex[:6]}")
        os.makedirs(trace_dir, exist_ok=True)
        # start/stop serialize trace buffers to disk — seconds for a
        # long capture — and must never freeze the serving event loop
        # (token streams, /health, the metrics drain all live on it).
        t_start = time.monotonic()
        try:
            await asyncio.to_thread(profiler.start_trace, trace_dir)
        except Exception as exc:  # noqa: BLE001 — backend refused
            return web.json_response(
                {"error": f"start_trace failed: {exc!r}"}, status=503)
        start_s = time.monotonic() - t_start
        try:
            await asyncio.sleep(duration / 1e3)
        finally:
            t_stop = time.monotonic()
            try:
                if request.query.get("export") == "xplane":
                    await asyncio.to_thread(_stop_trace_xplane_only,
                                            trace_dir)
                else:
                    await asyncio.to_thread(profiler.stop_trace)
            except Exception as exc:  # noqa: BLE001 — a failed stop
                # still ends the session server-side; report it
                return web.json_response(
                    {"error": f"stop_trace failed: {exc!r}",
                     "trace_dir": trace_dir}, status=500)

        def _walk() -> list[str]:
            out = []
            for root, _dirs, names in os.walk(trace_dir):
                out.extend(os.path.join(os.path.relpath(root, trace_dir),
                                        name) for name in names)
            return out

        files = await asyncio.to_thread(_walk)
        return web.json_response({
            "trace_dir": trace_dir,
            "duration_ms": duration,
            "start_s": round(start_s, 3),
            "stop_s": round(time.monotonic() - t_stop, 3),
            "files": sorted(files),
        })
    finally:
        _PROFILE_LOCK.release()


def metrics_response(request: web.Request) -> web.Response:
    """Shared /metrics responder (status server + frontend): OpenMetrics
    when the scraper asks for it (the only format carrying exemplars),
    classic Prometheus text otherwise."""
    if "application/openmetrics-text" in request.headers.get("Accept", ""):
        return web.Response(
            body=metrics.render_openmetrics(),
            headers={"Content-Type": metrics.OPENMETRICS_CONTENT_TYPE})
    return web.Response(body=metrics.render(), content_type="text/plain",
                        charset="utf-8")


def _timeline_matches(timeline: dict, status: str, tenant: str,
                      model: str, slow: str) -> bool:
    if status and timeline.get("status") != status:
        return False
    if tenant and timeline.get("tenant") != tenant:
        return False
    if model and timeline.get("model") != model:
        return False
    if slow and not timeline.get("slow"):
        return False
    return True


def debug_programs_response(_request: web.Request) -> web.Response:
    """/debug/programs: the compiled programs this process built, oldest
    first (`builds`: entry, key, cause, start and end, the seconds jax
    reported for tracing, lowering and the backend step, backend events
    inside, the persistent cache's outcome), and each key's launches as
    the worker last published them (`launches`: the samples of
    dynamo_program_launches / dynamo_program_tokens). Kept in memory,
    written out when asked. A process that runs no engine answers with
    empty lists and imports nothing."""
    import sys

    runner = sys.modules.get("dynamo_tpu.engine.model_runner")
    out = (runner.programs_snapshot() if runner is not None
           else {"builds": [], "builds_total": 0, "ring": 0})
    launches: dict[tuple, dict] = {}
    for field, gauge in (("launches", metrics.PROGRAM_LAUNCHES),
                         ("tokens", metrics.PROGRAM_TOKENS)):
        for family in gauge.collect():
            for sample in family.samples:
                row = launches.setdefault(
                    tuple(sorted(sample.labels.items())),
                    dict(sample.labels))
                row[field] = int(sample.value)
    out["launches"] = list(launches.values())
    return web.json_response(out)


def debug_requests_response(request: web.Request) -> web.Response:
    """Shared /debug/requests responder: the flight recorder's inflight
    + recently-completed request timelines.

    At flood scale the unfiltered dump is unusable, so the responder
    filters and paginates: ``?status=error&tenant=acme&model=m&slow=1``
    narrow by timeline fields, ``?limit=&offset=`` page through each
    list in the recorder's order (completed newest first), applied
    after filtering. The response carries the pre-pagination totals so
    callers know what they are missing.
    """
    query = request.query
    status = query.get("status", "")
    tenant = query.get("tenant", "")
    model = query.get("model", "")
    slow = query.get("slow", "")
    try:
        limit = int(query.get("limit", 0))
        offset = int(query.get("offset", 0))
    except ValueError:
        return web.json_response(
            {"error": "limit/offset must be integers"}, status=400)
    snapshot = get_recorder().snapshot()
    out: dict = {}
    for section in ("inflight", "completed"):
        rows = [t for t in snapshot.get(section, [])
                if _timeline_matches(t, status, tenant, model, slow)]
        out[f"total_{section}"] = len(rows)
        if offset:
            rows = rows[offset:]
        if limit > 0:
            rows = rows[:limit]
        out[section] = rows
    return web.json_response(out)


def fleet_response(_request: web.Request) -> web.Response:
    """Shared /fleet responder: the observatory's rollup pane (404
    until an Observatory is installed in this process)."""
    from ..observatory.service import get_observatory

    obs = get_observatory()
    if obs is None:
        return web.json_response(
            {"error": "no observatory in this process"}, status=404)
    return web.json_response(obs.status_json())


def debug_alerts_response(_request: web.Request) -> web.Response:
    """Shared /debug/alerts responder: active alerts + the bounded
    transition log."""
    from ..observatory.service import get_observatory

    obs = get_observatory()
    if obs is None:
        return web.json_response(
            {"error": "no observatory in this process"}, status=404)
    return web.json_response(obs.alerts_json())


class SystemStatusServer:
    def __init__(self, port: int = 0, host: str = "0.0.0.0") -> None:
        self._port = port
        self._host = host
        self._runner: Optional[web.AppRunner] = None
        self.port: Optional[int] = None
        # Health callbacks: name -> () -> bool (endpoints register themselves)
        self._health_checks: dict[str, Callable[[], bool]] = {}
        # Graceful-drain control verb (engine/drain.py): the hosting
        # worker registers an async () -> dict that runs the departure
        # ladder and returns the drain report. POST /drain without a
        # registered drainer is a 404 (frontends/routers have nothing
        # to drain through this verb).
        self._drain_fn = None

    def register_health(self, name: str, check: Callable[[], bool]) -> None:
        self._health_checks[name] = check

    def unregister_health(self, name: str) -> None:
        self._health_checks.pop(name, None)

    async def _health(self, _request: web.Request) -> web.Response:
        results = {name: bool(check()) for name, check in self._health_checks.items()}
        healthy = all(results.values()) if results else True
        return web.json_response(
            {"status": "healthy" if healthy else "unhealthy", "endpoints": results},
            status=200 if healthy else 503,
        )

    async def _live(self, _request: web.Request) -> web.Response:
        return web.json_response({"status": "live"})

    async def _metrics(self, request: web.Request) -> web.Response:
        return metrics_response(request)

    async def _debug_requests(self, request: web.Request) -> web.Response:
        return debug_requests_response(request)

    async def _debug_programs(self, request: web.Request) -> web.Response:
        return debug_programs_response(request)

    async def _debug_profile(self, request: web.Request) -> web.Response:
        return await profile_response(request)

    async def _fleet(self, request: web.Request) -> web.Response:
        return fleet_response(request)

    async def _debug_alerts(self, request: web.Request) -> web.Response:
        return debug_alerts_response(request)

    def register_drain(self, fn) -> None:
        """fn: async () -> dict — runs the component's graceful drain
        (idempotent; a second POST while draining awaits the first) and
        returns its report. Single slot, LAST registration wins: a main
        hosting several drainable components (the comesh prefill+decode
        pair) must register ONE composed drainer that runs its ladder in
        the right order — per-worker auto-registrations would otherwise
        silently shadow each other."""
        self._drain_fn = fn

    async def _drain(self, _request: web.Request) -> web.Response:
        if self._drain_fn is None:
            return web.json_response(
                {"error": "no drainable component registered"}, status=404)
        report = await self._drain_fn()
        return web.json_response(report)

    async def start(self) -> None:
        app = web.Application()
        app.router.add_get("/health", self._health)
        app.router.add_get("/live", self._live)
        app.router.add_get("/metrics", self._metrics)
        # Mutating + terminal (a drained worker never rejoins routing),
        # so unlike the read-only surface it gets an off switch for
        # deployments where this port is reachable beyond the operators.
        if env("DYNT_DRAIN_HTTP"):
            app.router.add_post("/drain", self._drain)
        app.router.add_get("/debug/requests", self._debug_requests)
        app.router.add_get("/debug/programs", self._debug_programs)
        app.router.add_get("/debug/profile", self._debug_profile)
        app.router.add_get("/fleet", self._fleet)
        app.router.add_get("/debug/alerts", self._debug_alerts)
        self._runner = web.AppRunner(app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self._host, self._port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]  # type: ignore[union-attr]

    async def close(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None
