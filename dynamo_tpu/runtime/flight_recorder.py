"""Per-request flight recorder: a bounded ring of request timelines.

When a request burns its deadline budget, metrics say *that* it was slow
and traces say so only if the collector kept the sample — this recorder
answers *where the time went* from inside the process, with zero external
dependencies. Every component stamps coarse phases on a shared timeline
(received -> queued -> scheduled -> prefill_start -> first_token ->
finished) keyed by request id, and appends structured events for the
interesting detours (retries, breaker trips, migrations, KV-transfer
legs). The result is the black-box flight recorder of the serving plane:

  * `/debug/requests` (system status server and the frontend) returns the
    inflight timelines plus the last N completed ones;
  * any request that finishes in a non-ok state is auto-dumped to the log;
  * `DYNT_SLOW_TRACE_MS` force-samples slow-but-successful requests the
    same way (the tail you cannot reproduce on demand).

Stamps are first-write-wins (phases are facts, not counters) and the
whole structure is thread-safe: the engine scheduler stamps from its own
thread while the asyncio side reads snapshots. Request ids default to the
`current_request_id` contextvar so most call sites stamp with no plumbing.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import threading
import time
from typing import Optional

from . import conformance
from .config import env
from .logging import current_request_id, get_logger

log = get_logger("flight_recorder")

# Canonical phase order (docs/observability.md). A timeline holds any
# subset: a prefill-only leg never decodes, a shed request never queues.
PHASES = ("received", "queued", "scheduled", "prefill_start",
          "first_token", "finished")

# A closed timeline as stages of dynamo_stage_duration_seconds
# (docs/observability.md): each is end - start of two stamps of one
# request id. `ingress` starts at the frontend's arrival time, which
# rides the request annotations ("received_at") to the worker.
STAGES = (("queue", "received", "scheduled"),
          ("prefill_wait", "scheduled", "prefill_start"),
          ("prefill", "prefill_start", "first_token"),
          ("decode", "first_token", "finished"))


def stage_durations(phases: dict, received_at: Optional[float] = None,
                    prefill_only: bool = False) -> dict:
    """{stage: seconds} for every stage whose two stamps the timeline
    holds. `ingress` only when the frontend's arrival time came with the
    request; both clocks are time.time(), so across hosts it is good to
    their clock sync (never negative here). A prefill-only leg never
    decodes: its first_token -> finished is the transfer hand-off."""
    out = {}
    if received_at is not None and "received" in phases:
        out["ingress"] = max(0.0, phases["received"] - received_at)
    for stage, start, end in STAGES:
        if stage == "decode" and prefill_only:
            continue
        if start in phases and end in phases:
            out[stage] = max(0.0, phases[end] - phases[start])
    return out


# Inflight entries older than this are presumed leaked (a peer that
# stamped but never finished — e.g. a prefill pool whose decode side
# died) and retired so the inflight map stays bounded.
STALE_INFLIGHT_SECS = 3600.0


@dataclasses.dataclass
class RequestTimeline:
    """One request's observed life inside this process."""

    request_id: str
    model: str = ""
    trace_id: str = ""
    tenant: str = ""
    started: float = dataclasses.field(default_factory=time.time)
    phases: dict = dataclasses.field(default_factory=dict)
    events: list = dataclasses.field(default_factory=list)
    # Device-time attribution (perf/steptrace.py): accumulated
    # "<phase>_device_ms" / "<phase>_host_ms" per engine phase, so the
    # host wall-clock phases above can be split into host vs device
    # burn (/debug/requests -> planner PhaseBreakdownSource).
    device: dict = dataclasses.field(default_factory=dict)
    status: Optional[str] = None  # None while inflight
    slow: bool = False

    def elapsed_ms(self) -> float:
        end = self.phases.get("finished", time.time())
        return max(0.0, (end - self.started) * 1e3)

    def to_json(self) -> dict:
        return {
            "request_id": self.request_id,
            "model": self.model,
            "trace_id": self.trace_id,
            "tenant": self.tenant,
            "status": self.status or "inflight",
            "slow": self.slow,
            "elapsed_ms": round(self.elapsed_ms(), 3),
            "phases": {k: round(v, 6) for k, v in self.phases.items()},
            "device": {k: round(v, 3) for k, v in self.device.items()},
            "events": list(self.events),
        }


class FlightRecorder:
    """Thread-safe inflight map + completed ring (capacity from
    DYNT_FLIGHT_RECORDER_SIZE when not given)."""

    def __init__(self, capacity: Optional[int] = None,
                 slow_ms: Optional[float] = None) -> None:
        if capacity is None:
            capacity = env("DYNT_FLIGHT_RECORDER_SIZE")
        self.slow_ms = (env("DYNT_SLOW_TRACE_MS") if slow_ms is None
                        else slow_ms)
        self._inflight: dict[str, RequestTimeline] = {}
        self._completed: collections.deque = collections.deque(
            maxlen=max(1, capacity))
        self._lock = threading.Lock()

    @staticmethod
    def _resolve(request_id: Optional[str]) -> Optional[str]:
        return request_id if request_id else current_request_id.get()

    # -- producer side -----------------------------------------------------

    def start(self, request_id: str, model: str = "",
              trace_id: str = "", tenant: str = "",
              received: Optional[float] = None) -> None:
        """Open (or enrich) a timeline. Idempotent: the first opener sets
        `received`; later openers only fill in missing identity fields, so
        frontend and worker can both call it in shared-process setups.
        `received` backdates the timeline to the true wire-arrival time —
        tokenization happens before the request gets an id, and a cold
        tokenizer can burn a visible slice of the deadline budget that
        would otherwise be missing from the timeline."""
        with self._lock:
            tl = self._inflight.get(request_id)
            if tl is None:
                tl = RequestTimeline(request_id, model=model,
                                     trace_id=trace_id, tenant=tenant)
                if received is not None:
                    tl.started = received
                tl.phases["received"] = tl.started
                self._inflight[request_id] = tl
                self._evict_stale_locked()
                return
            if model and not tl.model:
                tl.model = model
            if trace_id and not tl.trace_id:
                tl.trace_id = trace_id
            if tenant and not tl.tenant:
                tl.tenant = tenant

    def stamp(self, request_id: Optional[str], phase: str,
              ts: Optional[float] = None) -> None:
        """Record a phase timestamp (first write wins). No-op for unknown
        requests — canaries and bare-scheduler tests never pollute."""
        rid = self._resolve(request_id)
        if rid is None:
            return
        with self._lock:
            tl = self._inflight.get(rid)
            if tl is not None and phase not in tl.phases:
                tl.phases[phase] = time.time() if ts is None else ts
                if phase != "received":
                    # Accepted first-write stamps replay against the
                    # canonical phase machine (tools/dynastate/
                    # protocols/flight_recorder.json); "received" is
                    # the initial state, not an event. Observed under
                    # the recorder lock so the monitor sees stamps in
                    # acceptance order.
                    conformance.observe("flight_recorder", rid, phase)

    def device(self, request_id: Optional[str], phase: str,
               device_ms: float = 0.0, host_ms: float = 0.0) -> None:
        """Accumulate device/host burn for an engine phase ("prefill" /
        "decode") onto the timeline (perf/steptrace.py attribution).
        No-op for unknown requests, like stamp()."""
        rid = self._resolve(request_id)
        if rid is None:
            return
        with self._lock:
            tl = self._inflight.get(rid)
            if tl is None:
                return
            if device_ms:
                key = f"{phase}_device_ms"
                tl.device[key] = tl.device.get(key, 0.0) + device_ms
            if host_ms:
                key = f"{phase}_host_ms"
                tl.device[key] = tl.device.get(key, 0.0) + host_ms

    def event(self, request_id: Optional[str], name: str, **attrs) -> None:
        """Append a structured event (retry, migration, kv_pull, ...)."""
        rid = self._resolve(request_id)
        if rid is None:
            return
        with self._lock:
            tl = self._inflight.get(rid)
            if tl is not None:
                tl.events.append({"ts": round(time.time(), 6),
                                  "event": name, **attrs})

    def finish(self, request_id: Optional[str],
               status: str = "ok") -> Optional[RequestTimeline]:
        """Close a timeline and move it to the completed ring. First call
        wins; the auto-dump fires for every non-ok status and — when
        DYNT_SLOW_TRACE_MS is set — for slow successes too."""
        rid = self._resolve(request_id)
        if rid is None:
            return None
        with self._lock:
            tl = self._inflight.pop(rid, None)
            if tl is None:
                return None
            tl.status = status
            tl.phases.setdefault("finished", time.time())
            tl.slow = bool(self.slow_ms) and tl.elapsed_ms() >= self.slow_ms
            self._completed.append(tl)
            conformance.observe("flight_recorder", rid, "finished")
        if status not in ("ok", "cancelled", "shed"):
            # Errors and deadline overruns auto-dump; plain client
            # cancellations are normal stream teardown (e.g. a prefill
            # leg whose consumer got its params) and would be noise.
            # Admission sheds ("shed") are DELIBERATE bounded
            # degradation — dumping each one would storm the log at
            # exactly the moment the system is overloaded.
            log.warning("flight record (%s): %s", status,
                        json.dumps(tl.to_json()))
        elif tl.slow:
            log.warning("flight record (slow: %.0fms >= %.0fms): %s",
                        tl.elapsed_ms(), self.slow_ms,
                        json.dumps(tl.to_json()))
        return tl

    def _evict_stale_locked(self) -> None:
        now = time.time()
        stale = [rid for rid, tl in self._inflight.items()
                 if now - tl.started > STALE_INFLIGHT_SECS]
        for rid in stale:
            tl = self._inflight.pop(rid)
            tl.status = "stale"
            tl.phases.setdefault("finished", now)
            self._completed.append(tl)

    # -- consumer side -----------------------------------------------------

    def get(self, request_id: str) -> Optional[RequestTimeline]:
        """Inflight entry, or the most recent completed one by that id.
        Inflight timelines are returned as a shallow COPY taken under
        the lock — the scheduler thread keeps stamping the original,
        and a reader iterating live phase/event containers (the worker
        synthesizing phase spans, a /debug scrape) would race those
        mutations. Completed entries are immutable after finish() and
        returned as-is."""
        with self._lock:
            tl = self._inflight.get(request_id)
            if tl is not None:
                return dataclasses.replace(tl, phases=dict(tl.phases),
                                           device=dict(tl.device),
                                           events=list(tl.events))
            for done in reversed(self._completed):
                if done.request_id == request_id:
                    return done
        return None

    def snapshot(self) -> dict:
        """JSON shape served at /debug/requests: inflight first, then
        completed newest-first. Serialization happens OUTSIDE the lock —
        hot-path stamp() from the engine step thread must never wait out
        a debug scrape. Inflight timelines are still mutating, so their
        phase/event containers are shallow-copied under the lock;
        completed ones are immutable after finish()."""
        with self._lock:
            inflight = [dataclasses.replace(tl, phases=dict(tl.phases),
                                            device=dict(tl.device),
                                            events=list(tl.events))
                        for tl in self._inflight.values()]
            completed = list(reversed(self._completed))
        return {
            "inflight": [tl.to_json() for tl in inflight],
            "completed": [tl.to_json() for tl in completed],
        }


_GLOBAL: Optional[FlightRecorder] = None
_GLOBAL_LOCK = threading.Lock()


def get_recorder() -> FlightRecorder:
    """Process-wide recorder (always on — it is a fixed-size ring whose
    hot-path cost is a dict write under an uncontended lock)."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = FlightRecorder()
        return _GLOBAL


def reset_recorder() -> None:
    """Testing hook: drop the cached recorder so env changes take effect."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        _GLOBAL = None
