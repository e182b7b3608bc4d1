"""dynaprof: device-time attribution for the serving step loop.

Every latency number the system emitted before this plane was host
wall-clock (`last_step_wall_ms`, flight-recorder phases, frontend TTFT),
which cannot say whether the host or the device spent the time. This
module decomposes each scheduler step into the pieces the dispatch
model actually has, with ZERO added device syncs:

  host-prep   step start -> first dispatch submit (admission, buffer
              fill, proposer mining)
  dispatch    host time spent inside runner submit calls (trace +
              transfer enqueue)
  device      first dispatch submitted -> drain complete — the window
              the device (or its queue) owns the step; host overlap
              work (prefill prep, late admission, gap callbacks) runs
              inside it
  drain-wait  the blocking readback slice of the device window (host
              idle, waiting on results)
  emit        host time committing drained tokens and handing their
              frames to the owner (the `sched.emit` sections, and the
              hand-over of first tokens that ends
              `sched.finalize_prefill`)

The invariant `host_ms + device_ms == wall_ms` holds per step by
construction (host is the residual of the measured device window), and
`prep + dispatch <= host + device` pins the measured sub-pieces.

Measurement contract: dispatch scopes stamp at submit start/end and
enter a `jax.profiler.StepTraceAnnotation` (so an on-demand
`/debug/profile` capture attributes device ops to engine phases); drain
scopes stamp at drain-complete and mark the blocked readback as the
host section `sched.drain_wait` in such a capture. A phase's per-step device window runs
from ITS OWN submit end this step to its drain end — a readback of work
submitted last step (deferred prefill tokens) contributes only its
blocked-wait slice, keeping every window inside the step wall.

The same definitions serve the live MFU / roofline gauges
(`LiveRoofline` vs `profiler/timing_model.py`), so serving metrics and
analytical-model comparisons share ONE measurement meaning.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Callable, Optional

PHASES = ("decode", "prefill", "spec")

# Consecutive steps host residual must exceed the device window before
# the verdict gauge flips host-bound (transients must not flap it).
HOST_BOUND_STEPS = 8


def annotation(phase: str, step: Optional[int] = None,
               section: bool = False):
    """`jax.profiler.StepTraceAnnotation` scope for one engine dispatch
    or, with `section`, a plain `TraceAnnotation` for a named host
    section of the scheduler loop (`sched.admit`, `sched.drain_wait`,
    ...: docs/observability.md), so an on-demand capture names what the
    host was doing on the device trace's clock — a no-op unless a
    profiler trace is active, and a nullcontext for consumers without
    jax (the mocker's CI installs none)."""
    try:
        from jax import profiler
    except ImportError:
        return contextlib.nullcontext()
    if section:
        return profiler.TraceAnnotation(phase)
    if step is None:
        return profiler.StepTraceAnnotation(phase)
    return profiler.StepTraceAnnotation(phase, step_num=step)


@dataclasses.dataclass
class StepSample:
    """One committed step's decomposition (all milliseconds)."""

    wall_ms: float
    host_ms: float  # residual: wall - device (prep + dispatch + overlap)
    prep_ms: float  # measured: step start -> first submit
    dispatch_ms: float  # measured: host time inside submit calls
    device_ms: float  # measured: submit end -> drain complete, summed
    drain_ms: float  # measured: blocked readback slice of device_ms
    emit_ms: float = 0.0  # measured: committing + handing over tokens
    device_by_phase: dict = dataclasses.field(default_factory=dict)

    @property
    def kind(self) -> str:
        """Dominant phase label for per-phase metric families."""
        if not self.device_by_phase:
            return "host"
        return max(self.device_by_phase, key=self.device_by_phase.get)


class _DispatchScope:
    """Stamps submit start/end around one runner dispatch and enters the
    profiler step annotation. `submit_end` (monotonic seconds) is the
    per-request attribution anchor callers may keep."""

    def __init__(self, trace: "StepTrace", phase: str,
                 step: Optional[int]) -> None:
        self._trace = trace
        self._phase = phase
        self._ann = annotation(phase, step)
        self.submit_end = 0.0

    def __enter__(self) -> "_DispatchScope":
        t = self._trace._clock()
        if self._trace._first_submit is None:
            self._trace._first_submit = t
        self._start = t
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._ann.__exit__(exc_type, exc, tb)
        end = self._trace._clock()
        self.submit_end = end
        self._trace._dispatch_ms += (end - self._start) * 1e3
        self._trace._submit_end[self._phase] = end
        return False


class _DrainScope:
    """Stamps the blocking drain; on exit `device_ms` holds this step's
    device window for the phase (its submit end -> drain complete). A
    drain of work submitted in a PREVIOUS step must pass
    `anchored=False` and counts only its blocked wait — this step's
    submit stamp (if any) belongs to DIFFERENT in-flight work, and
    anchoring there would credit host-overlap time as device."""

    def __init__(self, trace: "StepTrace", phase: str,
                 anchored: bool = True) -> None:
        self._trace = trace
        self._phase = phase
        self._anchored = anchored
        self._ann = annotation("sched.drain_wait", section=True)
        self.device_ms = 0.0

    def __enter__(self) -> "_DrainScope":
        self._start = self._trace._clock()
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._ann.__exit__(exc_type, exc, tb)
        end = self._trace._clock()
        anchor = self._start
        if self._anchored:
            anchor = self._trace._submit_end.get(self._phase,
                                                 self._start)
        self.device_ms = max(0.0, (end - anchor) * 1e3)
        self._trace._drain_ms += (end - self._start) * 1e3
        self._trace._device_by_phase[self._phase] = (
            self._trace._device_by_phase.get(self._phase, 0.0)
            + self.device_ms)
        return False


class _SyncScope:
    """Dispatch + execute + readback in ONE host call (host-sampling
    decode, logprob prefill): the whole duration is the device window
    (the host was blocked on the chip for all of it)."""

    def __init__(self, trace: "StepTrace", phase: str,
                 step: Optional[int]) -> None:
        self._trace = trace
        self._phase = phase
        self._ann = annotation(phase, step)
        self.device_ms = 0.0

    def __enter__(self) -> "_SyncScope":
        t = self._trace._clock()
        if self._trace._first_submit is None:
            self._trace._first_submit = t
        self._start = t
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._ann.__exit__(exc_type, exc, tb)
        end = self._trace._clock()
        self.device_ms = (end - self._start) * 1e3
        self._trace._drain_ms += self.device_ms
        self._trace._device_by_phase[self._phase] = (
            self._trace._device_by_phase.get(self._phase, 0.0)
            + self.device_ms)
        return False


class _EmitScope:
    """Times one emitting section of the loop into the step's `emit`
    part; with `section`, also names it `sched.emit` in a capture."""

    def __init__(self, trace: "StepTrace", section: bool) -> None:
        self._trace = trace
        self._ann = (annotation("sched.emit", section=True) if section
                     else contextlib.nullcontext())

    def __enter__(self) -> "_EmitScope":
        self._start = self._trace._clock()
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._ann.__exit__(exc_type, exc, tb)
        self._trace._emit_ms += (self._trace._clock() - self._start) * 1e3
        return False


class StepTrace:
    """Per-scheduler step decomposition accumulator.

    Producer side (scheduler thread): begin() -> dispatch()/sync()/
    drain() scopes -> commit(wall_ms). Consumer side (worker drain task)
    reads totals and drain_samples() under the lock. The injectable
    clock keeps the unit tier deterministic."""

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 capacity: int = 1024) -> None:
        self._clock = clock
        self._lock = threading.Lock()
        self._samples: collections.deque = collections.deque(
            maxlen=max(1, capacity))
        # cumulative totals (read cross-thread; float writes are atomic
        # enough under the GIL for gauges)
        self.steps = 0
        self.device_ms_total = 0.0
        self.host_ms_total = 0.0
        self.device_ms_by_phase: dict[str, float] = {}
        # persistence streak behind the host-bound verdict
        self._host_over_device = 0
        self.last: Optional[StepSample] = None
        self._reset_step()

    def _reset_step(self) -> None:
        self._first_submit: Optional[float] = None
        self._dispatch_ms = 0.0
        self._drain_ms = 0.0
        self._emit_ms = 0.0
        self._submit_end: dict[str, float] = {}
        self._device_by_phase: dict[str, float] = {}
        self._t0 = 0.0

    # -- producer (scheduler thread) ---------------------------------------

    def begin(self) -> None:
        self._reset_step()
        self._t0 = self._clock()

    def dispatch(self, phase: str,
                 step: Optional[int] = None) -> _DispatchScope:
        return _DispatchScope(self, phase, step)

    def drain(self, phase: str, anchored: bool = True) -> _DrainScope:
        return _DrainScope(self, phase, anchored)

    def sync(self, phase: str, step: Optional[int] = None) -> _SyncScope:
        return _SyncScope(self, phase, step)

    def emit(self, section: bool = True) -> _EmitScope:
        return _EmitScope(self, section)

    def commit(self, wall_ms: float) -> StepSample:
        """Close the step: device is the measured window sum (clamped to
        the wall — phase windows can overlap when a deferred prefill
        drain rides a decode block), host is the residual."""
        device = min(sum(self._device_by_phase.values()), wall_ms)
        prep = 0.0
        if self._first_submit is not None:
            prep = max(0.0, (self._first_submit - self._t0) * 1e3)
        sample = StepSample(
            wall_ms=wall_ms,
            host_ms=max(0.0, wall_ms - device),
            prep_ms=prep,
            dispatch_ms=self._dispatch_ms,
            device_ms=device,
            drain_ms=self._drain_ms,
            emit_ms=self._emit_ms,
            device_by_phase=dict(self._device_by_phase),
        )
        with self._lock:
            self._samples.append(sample)
            self.steps += 1
            self.device_ms_total += sample.device_ms
            self.host_ms_total += sample.host_ms
            for phase, ms in sample.device_by_phase.items():
                self.device_ms_by_phase[phase] = (
                    self.device_ms_by_phase.get(phase, 0.0) + ms)
            if sample.host_ms > sample.device_ms:
                self._host_over_device += 1
            else:
                self._host_over_device = 0
            self.last = sample
        return sample

    # -- consumer (metrics drain task) -------------------------------------

    def drain_samples(self) -> list[StepSample]:
        """Committed samples since the previous call (bounded buffer:
        a stalled consumer loses oldest steps, never memory)."""
        with self._lock:
            out = list(self._samples)
            self._samples.clear()
        return out

    @property
    def host_bound(self) -> bool:
        """True once host residual has exceeded the device window for
        HOST_BOUND_STEPS consecutive committed steps — the verdict that
        says scaling chips will not move this pool's latency."""
        return self._host_over_device >= HOST_BOUND_STEPS


def detect_chip():
    """ChipSpec of the local accelerator for the live roofline gauges.
    Platform `cpu` (tests, dev boxes) has its own row. Any other device
    must be in the table (profiler/chips.py): publishing MFU against
    peaks assumed for a chip we do not know would be a wrong number
    that looks measured."""
    import jax

    from ..profiler.chips import CHIPS

    device = jax.devices()[0]
    if device.platform == "cpu":
        return CHIPS["cpu"]
    kind = device.device_kind.lower().replace(" ", "").replace("lite", "e")
    for key in ("v6e", "v5p", "v5e"):
        if key in kind:
            return CHIPS[key]
    raise ValueError(
        f"no peaks for device kind {device.device_kind!r} (platform "
        f"{device.platform!r}); add its row to profiler/chips.py")


class LiveRoofline:
    """Live MFU / roofline-fraction from serving-interval deltas.

    Compares measured device time against the analytical roofline model
    (`profiler/timing_model.py`) for the work actually done, so the
    0.443-class regressions `bench.py` finds offline show up on
    `/metrics` in production:

      mfu               achieved fraction of peak matmul FLOPs
                        (2 * params * tokens / (device_s * peak))
      roofline_fraction ideal device time at the roofline for the
                        interval's steps / measured device time
                        (prefill compute-bound + decode HBM-bound)
    """

    def __init__(self, model_config, num_chips: int = 1, chip=None,
                 weight_bytes_per_param: float = 2.0,
                 kv_dtype_bytes: int = 2) -> None:
        from ..profiler.timing_model import param_count

        self.model = model_config
        self.chip = chip if chip is not None else detect_chip()
        self.num_chips = max(1, num_chips)
        self.params = param_count(model_config)
        self.weight_bytes = self.params * weight_bytes_per_param
        self.kv_dtype_bytes = kv_dtype_bytes

    def observe(self, *, prefill_tokens: float, decode_tokens: float,
                decode_steps: float, active_kv_tokens: float,
                device_s: float) -> tuple[float, float]:
        """(mfu, roofline_fraction) for one interval. decode_steps is
        the number of device decode steps executed (a fused block
        counts k); active_kv_tokens is the KV working set each decode
        step streams."""
        from ..profiler.timing_model import kv_bytes_per_token

        if device_s <= 0:
            return 0.0, 0.0
        tokens = prefill_tokens + decode_tokens
        peak = self.chip.bf16_tflops * 1e12 * self.num_chips
        mfu = (2.0 * self.params * tokens) / (device_s * peak)
        ideal_s = 0.0
        if prefill_tokens:
            ideal_s += 2.0 * self.params * prefill_tokens / peak
        if decode_steps:
            kv_bytes = active_kv_tokens * kv_bytes_per_token(
                self.model, self.kv_dtype_bytes)
            bw = self.chip.hbm_gbps * 1e9 * self.num_chips
            ideal_s += decode_steps * (self.weight_bytes + kv_bytes) / bw
        return mfu, min(1.0, ideal_s / device_s)
