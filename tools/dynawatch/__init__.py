"""dynawatch — chip-free perf-regression gate over the bench dry run.

`scripts/bench_dry_run.py` exercises every modeled-performance subsystem
(cold start, drain handoff, spec decode, kvbm offload, two-class
goodput, session cache, disagg) on CPU and emits one JSON
report. dynawatch pins that report to blessed baselines so a refactor
that silently changes a modeled closed-form (cold-start totals, fetch
striping speedups) or drops a drain handoff fails CI
*before* anyone burns chips reproducing it.

Two classes of metric, declared in SPEC below:

  * deterministic anchors — closed-form model outputs, integer event
    counts, pass/fail booleans. Tight or exact envelopes: any drift is
    a semantic change that must be blessed deliberately.
  * measured values — wall-clock latencies from the CPU mocker runs.
    Loose envelopes only (shared CI hosts are noisy); these catch
    catastrophic regressions, not percent-level ones.

Workflow:

    python scripts/bench_dry_run.py --json out.json
    python -m tools.dynawatch --report out.json             # gate
    python -m tools.dynawatch --report out.json --baseline-update
    python -m tools.dynawatch --validate                    # structure only

`--baseline-update` re-blesses `tools/dynawatch/baselines/*.json` from
the report (commit the diff — that IS the review surface for a perf
change). `--validate` checks the baseline files cover the SPEC without
running anything — cheap enough for the dependency-free lint job.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
from typing import Any, List, Optional, Sequence, Tuple

BASELINE_DIR = pathlib.Path(__file__).resolve().parent / "baselines"

# Comparison kinds:
#   exact — report value must equal the blessed value (ints, bools,
#           pinned floats like the SLO threshold).
#   rel   — |report - baseline| <= tol * max(|baseline|, 1e-9); for a
#           zero baseline the tolerance is absolute.

# (block, dotpath, kind, tol). Blocks mirror the dry-run report's seven
# scenario sections; dotpaths index into each block's JSON.
SPEC: List[Tuple[str, str, str, float]] = [
    # -- cold start: closed-form model + measured spot-join smoke ------
    ("cold_start", "modeled.striped_warm.total_s", "rel", 0.02),
    ("cold_start", "modeled.single_warm.total_s", "rel", 0.02),
    ("cold_start", "modeled.striped_cold.total_s", "rel", 0.02),
    ("cold_start", "modeled.single_cold.total_s", "rel", 0.02),
    ("cold_start", "striped_fetch_speedup", "rel", 0.05),
    ("cold_start", "warm_cache_speedup", "rel", 0.05),
    ("cold_start", "measured_spot.passed", "exact", 0.0),
    # -- drain: event counts are exact facts of the scenario -----------
    ("drain", "passed", "exact", 0.0),
    ("drain", "handoff_path.handoff", "exact", 0.0),
    ("drain", "handoff_path.replay", "exact", 0.0),
    ("drain", "handoff_path.errored", "exact", 0.0),
    ("drain", "handoff_path.reprefill_tokens", "exact", 0.0),
    ("drain", "replay_fallback.replay", "exact", 0.0),
    ("drain", "replay_fallback.errored", "exact", 0.0),
    # How far generation got before the kill landed is wall-clock
    # sensitive, so the replayed-token volume gets an envelope.
    ("drain", "replay_fallback.reprefill_tokens", "rel", 0.25),
    ("drain", "bit_identical", "exact", 0.0),
    # -- speculative decode: proposal accounting -------------------------
    ("spec", "max_k", "exact", 0.0),
    ("spec", "k", "exact", 0.0),
    ("spec", "steps", "exact", 0.0),
    ("spec", "proposed", "exact", 0.0),
    # -- kvbm offload: block accounting ----------------------------------
    ("kvbm_offload", "offloaded_blocks", "exact", 0.0),
    ("kvbm_offload", "offloaded_mb", "rel", 0.05),
    # -- two-class goodput: scheduler invariants + loose volume ----------
    # The scenario's all-or-nothing verdict (and the exact interactive
    # shed count inside it) flexes with host load, so the gate pins the
    # structural facts instead: where FCFS knees, that shedding falls
    # on batch, and that interactive sheds stay near zero (a zero
    # baseline makes the rel tolerance absolute: <= 2 requests).
    ("two_class_goodput", "slo_ttft_ms", "exact", 0.0),
    ("two_class_goodput", "knee_bucket", "exact", 0.0),
    ("two_class_goodput", "tenant_shed.batch", "rel", 0.25),
    ("two_class_goodput", "tenant_shed.interactive", "rel", 2.0),
    ("two_class_goodput", "good_total_qos", "rel", 0.25),
    # -- session cache: correctness exact, the latency RATIO loose -------
    # (absolute ttft-ms swings 2-3x with box load; the cached/cold
    # ratio self-normalizes)
    ("session_cache", "errors", "exact", 0.0),
    ("session_cache", "cached_speedup", "rel", 0.75),
    # -- disagg: measured mocker latencies, loose envelopes --------------
    ("disagg", "pipelined_ttft_ms.p50", "rel", 0.75),
    ("disagg", "serial_ttft_ms.p50", "rel", 0.75),
    ("disagg", "pipelined_itl_ms.p50", "rel", 0.75),
    ("disagg", "serial_itl_ms.p50", "rel", 0.75),
]

REQUIRED_BLOCKS = tuple(sorted({block for block, *_ in SPEC}))


def _resolve(obj: Any, dotpath: str) -> Any:
    """Index `a.b.c` into nested dicts; None when any hop is missing."""
    for hop in dotpath.split("."):
        if not isinstance(obj, dict) or hop not in obj:
            return None
        obj = obj[hop]
    return obj


def compare(kind: str, tol: float, baseline: Any, observed: Any
            ) -> Optional[str]:
    """None when within the envelope, else a human-readable reason."""
    if observed is None:
        return "missing from report"
    if kind == "exact":
        if observed != baseline:
            return f"observed {observed!r} != blessed {baseline!r}"
        return None
    if kind == "rel":
        try:
            b, o = float(baseline), float(observed)
        except (TypeError, ValueError):
            return f"non-numeric: observed {observed!r} vs {baseline!r}"
        bound = tol * max(abs(b), 1e-9) if b else tol
        if abs(o - b) > bound:
            pct = (o - b) / b * 100.0 if b else float("inf")
            return (f"observed {o:g} vs blessed {b:g} "
                    f"({pct:+.1f}%, envelope ±{tol * 100:.0f}%)")
        return None
    return f"unknown comparison kind {kind!r}"


def baseline_path(block: str, baseline_dir: pathlib.Path) -> pathlib.Path:
    return baseline_dir / f"{block}.json"


def load_baseline(block: str, baseline_dir: pathlib.Path) -> Optional[dict]:
    path = baseline_path(block, baseline_dir)
    if not path.is_file():
        return None
    return json.loads(path.read_text())


def bless(report: dict, baseline_dir: pathlib.Path) -> List[str]:
    """Write blessed envelopes for every SPEC block from `report`.
    Returns the per-block file names written (relative to the dir)."""
    baseline_dir.mkdir(parents=True, exist_ok=True)
    written = []
    for block in REQUIRED_BLOCKS:
        metrics = {}
        for blk, dotpath, kind, tol in SPEC:
            if blk != block:
                continue
            value = _resolve(report.get(block) or {}, dotpath)
            if value is None:
                raise SystemExit(
                    f"dynawatch: cannot bless — report is missing "
                    f"{block}.{dotpath}")
            metrics[dotpath] = {"value": value, "kind": kind, "tol": tol}
        path = baseline_path(block, baseline_dir)
        path.write_text(json.dumps(
            {"block": block, "metrics": metrics}, indent=2, sort_keys=True)
            + "\n")
        written.append(path.name)
    return written


def gate(report: dict, baseline_dir: pathlib.Path) -> List[str]:
    """Compare `report` to the blessed baselines; returns the failures
    (empty list == gate passes). Every failure line carries the blessed
    value, the observed one, and the envelope — the CI log IS the diff."""
    failures: List[str] = []
    for block in REQUIRED_BLOCKS:
        base = load_baseline(block, baseline_dir)
        if base is None:
            failures.append(
                f"{block}: no baseline (run --baseline-update and commit "
                f"{baseline_path(block, baseline_dir)})")
            continue
        if block not in report:
            failures.append(f"{block}: block missing from report")
            continue
        blessed = base.get("metrics", {})
        for blk, dotpath, kind, tol in SPEC:
            if blk != block:
                continue
            entry = blessed.get(dotpath)
            if entry is None:
                failures.append(
                    f"{block}.{dotpath}: not in baseline — re-bless")
                continue
            # The blessed file pins kind/tol too, so a stale baseline
            # written under an older SPEC fails loudly instead of
            # silently gating with the wrong envelope.
            if entry.get("kind") != kind or entry.get("tol") != tol:
                failures.append(
                    f"{block}.{dotpath}: baseline envelope drift "
                    f"(blessed {entry.get('kind')}/{entry.get('tol')} vs "
                    f"SPEC {kind}/{tol}) — re-bless")
                continue
            observed = _resolve(report[block], dotpath)
            reason = compare(kind, tol, entry.get("value"), observed)
            if reason:
                failures.append(f"{block}.{dotpath}: {reason}")
    return failures


def validate(baseline_dir: pathlib.Path) -> List[str]:
    """Structural check (no report needed): every SPEC block has a
    parseable baseline covering every SPEC metric with the current
    envelope. Cheap enough for the dependency-free lint job."""
    problems: List[str] = []
    for block in REQUIRED_BLOCKS:
        try:
            base = load_baseline(block, baseline_dir)
        except (OSError, json.JSONDecodeError) as exc:
            problems.append(f"{block}: unreadable baseline ({exc})")
            continue
        if base is None:
            problems.append(f"{block}: baseline file missing")
            continue
        blessed = base.get("metrics", {})
        for blk, dotpath, kind, tol in SPEC:
            if blk != block:
                continue
            entry = blessed.get(dotpath)
            if entry is None:
                problems.append(f"{block}.{dotpath}: not blessed")
            elif entry.get("kind") != kind or entry.get("tol") != tol:
                problems.append(
                    f"{block}.{dotpath}: envelope drift — re-bless")
            elif entry.get("value") is None:
                problems.append(f"{block}.{dotpath}: blessed value is null")
        for dotpath in blessed:
            if not any(b == block and d == dotpath
                       for b, d, _k, _t in SPEC):
                problems.append(
                    f"{block}.{dotpath}: blessed but not in SPEC — "
                    f"re-bless to drop it")
    return problems


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="dynawatch",
        description="chip-free perf-regression gate over the bench dry run")
    parser.add_argument("--report", help="bench_dry_run.py JSON report")
    parser.add_argument("--baseline-update", action="store_true",
                        help="bless baselines from --report instead of gating")
    parser.add_argument("--validate", action="store_true",
                        help="structural baseline check only (no report)")
    parser.add_argument("--baseline-dir", default=str(BASELINE_DIR),
                        help="baseline directory (default: bundled)")
    args = parser.parse_args(argv)
    baseline_dir = pathlib.Path(args.baseline_dir)

    if args.validate:
        problems = validate(baseline_dir)
        for line in problems:
            print(f"dynawatch: {line}", file=sys.stderr)
        if problems:
            print(f"dynawatch: validate FAILED ({len(problems)} problems)",
                  file=sys.stderr)
            return 1
        print(f"dynawatch: baselines valid "
              f"({len(SPEC)} metrics across {len(REQUIRED_BLOCKS)} blocks)")
        return 0

    if not args.report:
        parser.error("--report is required unless --validate")
    try:
        report = json.loads(pathlib.Path(args.report).read_text())
    except (OSError, json.JSONDecodeError) as exc:
        print(f"dynawatch: cannot read report: {exc}", file=sys.stderr)
        return 2

    if args.baseline_update:
        written = bless(report, baseline_dir)
        print(f"dynawatch: blessed {len(written)} baselines in "
              f"{baseline_dir}: {', '.join(written)}")
        return 0

    failures = gate(report, baseline_dir)
    for line in failures:
        print(f"dynawatch: FAIL {line}", file=sys.stderr)
    if failures:
        print(f"dynawatch: gate FAILED ({len(failures)}/{len(SPEC)} "
              f"metrics out of envelope)", file=sys.stderr)
        return 1
    print(f"dynawatch: gate passed ({len(SPEC)} metrics across "
          f"{len(REQUIRED_BLOCKS)} blocks within envelope)")
    return 0
