"""From a profiler trace (`.xplane.pb`) to numbers. Runs as a child held
to the CPU: `python trace_reduce.py <trace.xplane.pb> <out.json>`.

What it gives, per the `on-chip-measurement` guide:

  window_s   the traced window: first to last event of the device planes'
             operation lines (the host planes run on past the device
             tracer, through the trace's own serialisation); the span of
             all planes where there is no device plane
  busy_s     seconds in which an operation ran on the device: the union
             of the device's "XLA Ops" intervals, averaged over the
             device planes that ran anything
  ops        per operation name: count and summed device seconds (an
             operation's own time: nested operations are taken out)
  modules    per compiled program ("XLA Modules" line): count, summed
             device seconds (one event = one launch of the program)
  breakdown  the ten operations that took most device time, and the idle
             time of device 0 by what the host was doing meanwhile (the
             innermost host span over each gap's midpoint: the program's
             StepTraceAnnotation / TraceMe spans first, Python frames
             otherwise)
"""

from __future__ import annotations

import bisect
import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
MIN_GAP_NS = 20_000  # gaps under 20 us are launch overhead, not the host


def union_ns(intervals: list[tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def gaps_ns(intervals: list[tuple[float, float]], lo: float,
            hi: float) -> list[tuple[float, float]]:
    """The complement of the union inside [lo, hi)."""
    out, reach = [], lo
    for start, end in sorted(intervals):
        if start > reach:
            out.append((reach, min(start, hi)))
        reach = max(reach, end)
        if reach >= hi:
            break
    if reach < hi:
        out.append((reach, hi))
    return [(a, b) for a, b in out if b > a]


def self_times(events: list[tuple[float, float, str]]) -> list[float]:
    """Each event's own time: its duration less that of the events nested
    directly inside it (a `while` holds its body's operations)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [e[1] - e[0] for e in events]
    stack: list[int] = []
    for i in order:
        start, end, _ = events[i]
        while stack and events[stack[-1]][1] <= start:
            stack.pop()
        if stack and end <= events[stack[-1]][1]:
            own[stack[-1]] -= end - start
        stack.append(i)
    return own


def base_name(name: str) -> str:
    """`%fusion.123 = ...` / `fusion.123` -> `fusion`; kernels and named
    scopes keep their own names, numbered copies fold together."""
    name = name.split(" = ")[0].lstrip("%")
    return re.sub(r"([.\-_]\d+)?(\.remat\d*)?(\.clone)?$", "", name)


def read_planes(path: str) -> list[dict]:
    """[{name, lines: [{name, events: [(start, end, name)]}]}] with
    times in ns on one clock."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                       e.name) for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


class HostSpans:
    """Which host span covers an instant, on the threads that launch
    device programs (those with a `PjitFunction(...)` span; every host
    thread if none has one). Named spans (TraceMe and the program's
    StepTraceAnnotation) win over Python frames (`$file:line fn`); among
    those of one kind, the innermost."""

    def __init__(self, planes: list[dict]) -> None:
        lines = [line for plane in planes
                 if plane["name"].startswith("/host:")
                 for line in plane["lines"]]
        launching = [line for line in lines if any(
            e[2].startswith("PjitFunction") for e in line["events"])]
        self.lines = []
        for line in launching or lines:
            events = sorted(e for e in line["events"] if e[1] > e[0])
            if events:
                self.lines.append(([e[0] for e in events], events))

    def covering(self, at: float) -> str:
        best_named, best_frame = None, None
        for starts, events in self.lines:
            i = bisect.bisect_right(starts, at)
            for start, end, name in reversed(events[max(0, i - 200):i]):
                if end <= at:
                    continue
                span = end - start
                if name.startswith("$"):
                    if best_frame is None or span < best_frame[0]:
                        best_frame = (span, name)
                elif best_named is None or span < best_named[0]:
                    best_named = (span, name)
        if best_named:
            return best_named[1]
        if best_frame:
            return best_frame[1].lstrip("$")
        return "no host span"


def reduce(planes: list[dict]) -> dict:
    every = [e for p in planes for ln in p["lines"] for e in ln["events"]]
    if not every:
        raise ValueError("the trace holds no events")
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    on_device = [e for p in devices for ln in p["lines"]
                 if ln["name"] == OPS_LINE for e in ln["events"]]
    lo = min(e[0] for e in on_device or every)
    hi = max(e[1] for e in on_device or every)
    busy, ops, modules = [], {}, {}
    first_ops: list = []
    for plane in devices:
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                spans = [(s, e) for s, e, _ in line["events"]]
                if spans:
                    busy.append(union_ns(spans))
                    if not first_ops:
                        first_ops = spans
                own = self_times(line["events"])
                for (_s, _e, name), ns in zip(line["events"], own):
                    row = ops.setdefault(base_name(name), [0, 0.0])
                    row[0] += 1
                    row[1] += ns / 1e9
            elif line["name"] == MODULES_LINE:
                for s, e, name in line["events"]:
                    row = modules.setdefault(
                        re.sub(r"\(\d+\)$", "", name), [0, 0.0])
                    row[0] += 1
                    row[1] += (e - s) / 1e9
    idle: dict = {}
    if first_ops:
        host = HostSpans(planes)
        for a, b in gaps_ns(first_ops, lo, hi):
            if b - a >= MIN_GAP_NS:
                name = host.covering((a + b) / 2)
                idle[name] = idle.get(name, 0.0) + (b - a) / 1e9
    top = sorted(ops.items(), key=lambda kv: -kv[1][1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": (sum(busy) / len(busy) / 1e9) if busy else 0.0,
        "device_planes": [p["name"] for p in devices],
        "ops": {k: {"count": v[0], "seconds": v[1]} for k, v in ops.items()},
        "modules": {k: {"count": v[0], "seconds": v[1]}
                    for k, v in modules.items()},
        "breakdown": {
            "device_ops": [[k, v[1]] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps]},
    }


def main(argv: list[str]) -> int:
    out = reduce(read_planes(argv[1]))
    with open(argv[2], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
