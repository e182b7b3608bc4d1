"""Cold-start ladder — arrival-side observability (docs/elasticity.md).

A joining worker walks `boot -> fetch -> load -> compile -> register ->
first_token`, from the process's own start (`boot`: interpreter,
imports, JAX's start, the native build, up to the engine build's first
line); each rung is stamped into the flight recorder and the
`dynamo_coldstart_*` metric families, and the completed total feeds the
planner as SCALE-UP LEAD TIME: a planner that knows arrivals take T
seconds projects demand T seconds ahead, so capacity lands when the
ramp needs it instead of T seconds late (planner/core.py). The mocker
walks the same ladder with modeled latencies (mocker/worker.py), so
the chaos-spot gate and the bench cold_start block exercise this
exact code chip-free.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Optional

from .. import IMPORTED_AT
from ..runtime import conformance
from ..runtime.flight_recorder import get_recorder
from ..runtime.logging import get_logger
from ..runtime.metrics import (
    COLDSTART_ARRIVALS,
    COLDSTART_PHASE_SECONDS,
    COLDSTART_TOTAL_SECONDS,
)

log = get_logger("engine.coldstart")

PHASES = ("boot", "fetch", "load", "compile", "register", "first_token")


def process_started() -> float:
    """This process's start on `time.monotonic()`'s clock, as the kernel
    has it (`/proc/self/stat` field 22 against `/proc/uptime`); the
    package's import time where that cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            # the command may hold spaces and parentheses: split behind it
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if age >= 0:
            return time.monotonic() - age
    except (OSError, ValueError, IndexError):
        pass
    return IMPORTED_AT

# Latest completed ladder totals, process-wide: the planner's lead-time
# source and the chaos/bench assertions' read side. Guarded by a lock —
# ladders complete on worker event loops, the planner may read from
# another thread.
_lock = threading.Lock()
_last_total: Optional[float] = None
_ewma_total: Optional[float] = None
_EWMA_ALPHA = 0.3


def _record_total(total: float) -> None:
    global _last_total, _ewma_total
    with _lock:
        _last_total = total
        _ewma_total = (total if _ewma_total is None
                       else _EWMA_ALPHA * total
                       + (1.0 - _EWMA_ALPHA) * _ewma_total)


def observed_cold_start_secs() -> Optional[float]:
    """Smoothed cold-start total across this process's completed
    arrivals (None until one completes). The planner's lead time."""
    with _lock:
        return _ewma_total


def last_cold_start_secs() -> Optional[float]:
    with _lock:
        return _last_total


def reset_observations() -> None:
    """Test isolation hook."""
    global _last_total, _ewma_total
    with _lock:
        _last_total = None
        _ewma_total = None


class ColdStartLadder:
    """One worker's walk up the arrival ladder, from the process's
    start: the seconds before the ladder was made are its first phase,
    `boot`. Phases may be stamped with the `phase` context manager or
    recorded directly with `mark`; `first_token()` closes the ladder.
    A modeled walk (the mocker's, whose process is no arrival's) passes
    `started`, the monotonic time its walk begins, and marks its own
    `boot`."""

    def __init__(self, worker: str, source: str = "unknown",
                 started: Optional[float] = None) -> None:
        self.worker = worker
        self.source = source        # weights source the fetch resolved
        self.phases: dict[str, float] = {}
        self.total: Optional[float] = None
        if started is None:
            self.started = process_started()
            self.mark("boot", max(0.0, time.monotonic() - self.started))
        else:
            self.started = started

    @contextlib.contextmanager
    def phase(self, name: str):
        assert name in PHASES, name
        t0 = time.monotonic()
        try:
            yield self
        finally:
            self.mark(name, time.monotonic() - t0)

    def mark(self, name: str, seconds: float) -> None:
        assert name in PHASES, name
        if self.total is not None:
            # Ladder closed (first_token published the total + planner
            # EWMA): a late mark — a lazy per-shape recompile after the
            # first served token — must not mutate the settled record.
            return
        conformance.observe("coldstart", f"{self.worker}:{id(self)}", name)
        self.phases[name] = self.phases.get(name, 0.0) + seconds
        COLDSTART_PHASE_SECONDS.labels(
            worker=self.worker, phase=name).set(self.phases[name])
        get_recorder().event(None, "coldstart_phase", worker=self.worker,
                             phase=name,
                             seconds=round(self.phases[name], 4))

    def first_token(self) -> Optional[float]:
        """Stamp the terminal rung and publish the total. Idempotent —
        only the FIRST served token closes the ladder."""
        if self.total is not None:
            return self.total
        now = time.monotonic()
        accounted = sum(self.phases.values())
        self.mark("first_token", max(0.0, (now - self.started) - accounted))
        self.total = now - self.started
        COLDSTART_TOTAL_SECONDS.labels(worker=self.worker).set(self.total)
        COLDSTART_ARRIVALS.labels(source=self.source).inc()
        _record_total(self.total)
        get_recorder().event(None, "coldstart_complete",
                             worker=self.worker, source=self.source,
                             total_secs=round(self.total, 4),
                             **{f"{k}_secs": round(v, 4)
                                for k, v in self.phases.items()})
        log.info("cold start complete in %.2fs (%s): %s", self.total,
                 self.source,
                 " ".join(f"{k}={self.phases.get(k, 0.0):.2f}s"
                          for k in PHASES))
        from ..runtime.config import env

        budget = float(env("DYNT_COLDSTART_BUDGET_SECS"))
        if budget > 0 and self.total > budget:
            log.warning(
                "cold start %.2fs exceeded the pinned budget %.2fs "
                "(DYNT_COLDSTART_BUDGET_SECS); slowest phase: %s",
                self.total, budget,
                max(self.phases, key=lambda k: self.phases[k]))
        return self.total

    def report(self) -> dict:
        return {"worker": self.worker, "source": self.source,
                "total_secs": self.total,
                "phases": {k: self.phases.get(k) for k in PHASES}}
