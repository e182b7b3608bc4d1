"""Two tools for benchmark PRs, each one server lifetime: a sweep of
open-loop rates (where is the knee?) and the readings a limit is set from
(sound runs over many seeds, and the lower-precision control)."""

from __future__ import annotations

import asyncio
import json
import time

from . import stats
from .client import Client


def _in_flight(timelines: list, at: float) -> int:
    return sum(1 for t in timelines
               if t.sent <= at and (t.end is None or t.end > at))


async def _sweep(run, args, log) -> list[dict]:
    async with Client(run.fleet.base, run.model) as warm:
        await run.warm_up(warm)
    rows = []
    base_mix = run.plan.mix
    for rate in [float(r) for r in args.sweep.split(",")]:
        run.plan.mix = dict(base_mix, loop="open", rate_rps=rate)
        async with Client(run.fleet.base, run.model) as client:
            window = await run.traffic_window(
                client, args.seed, args.seconds, f"r{rate}")
            summary = stats.window_summary(
                client.timelines, window["t0"], args.seconds)
            t0, t1 = window["t0"], window["t0"] + args.seconds
            row = {"rate_rps": rate, **summary["metrics"],
                   "completed": summary["completed"],
                   "failed": summary["failed"],
                   "in_flight_at_start": _in_flight(client.timelines, t0),
                   "in_flight_at_end": _in_flight(client.timelines,
                                                  t1 - 0.01),
                   "lag_ms_max": summary["generator_lag_ms_max"]}
        log(f"sweep {json.dumps(row)}")
        rows.append(row)
    run.plan.mix = base_mix
    return rows


async def _windows(run, args, log) -> list[dict]:
    async with Client(run.fleet.base, run.model) as warm:
        await run.warm_up(warm)
    sets = []
    seeds = [int(s) for s in args.check_seeds.split(",")]
    for i, seed in enumerate(seeds):
        tag = f"s{seed}"
        async with Client(run.fleet.base, run.model) as client:
            window = await run.traffic_window(client, seed, args.seconds,
                                              tag)
            summary = stats.window_summary(
                client.timelines, window["t0"], args.seconds)
            picked = run.sample(client.timelines, window, seed, tag)
        log(f"seed {seed}: {summary['completed']} completed, "
            f"{summary['failed']} failed; {json.dumps(summary['metrics'])}")
        sets.append({"label": tag, "picked": picked,
                     "control": i < args.control,
                     "failed": summary["failed"]})
    return sets


def main(run, args, log) -> int:
    t0 = time.monotonic()
    if args.sweep:
        rows = asyncio.run(_sweep(run, args, log))
        run.fleet.stop()
        print(json.dumps({"sweep": rows}))
        return 0
    sets = asyncio.run(_windows(run, args, log))
    run.fleet.stop()
    served = run.served_tokens()
    for entry in sets:
        entry["samples"] = [
            {"prompt": p["prompt"], "served": served.get(p["tag"], [])}
            for p in entry.pop("picked")]
    log(f"windows done in {time.monotonic() - t0:.0f}s; reference over "
        f"{len(sets)} samples, control on {args.control}")
    ref = run.reference(sets)
    for entry, row in zip(sets, ref["sets"]):
        row["failed"] = entry["failed"]
        print(json.dumps(row))
    print(json.dumps({"reference_seconds": ref["seconds"],
                      "device": ref["device"]}))
    return 0
