"""dynawatch perf gate (tools/dynawatch): the shipped baselines
validate, a report matching them passes the gate, perturbations fail
with per-metric diffs, bless/validate round-trips in a temp dir, and
envelope drift (stale baselines under a newer SPEC) is caught."""

import copy
import json
import pathlib
import subprocess
import sys

import pytest

import tools.dynawatch as dw

REPO = pathlib.Path(__file__).parent.parent


def synth_report():
    """A report whose every SPEC metric equals the blessed value — what
    a perfectly-on-baseline bench dry run would emit."""
    report = {}
    for block in dw.REQUIRED_BLOCKS:
        base = dw.load_baseline(block, dw.BASELINE_DIR)
        assert base is not None, block
        blockd = report.setdefault(block, {})
        for dotpath, entry in base["metrics"].items():
            hops = dotpath.split(".")
            node = blockd
            for hop in hops[:-1]:
                node = node.setdefault(hop, {})
            node[hops[-1]] = entry["value"]
    return report


class TestShippedBaselines:
    def test_baselines_validate(self):
        assert dw.validate(dw.BASELINE_DIR) == []

    def test_spec_covers_all_required_blocks(self):
        assert set(dw.REQUIRED_BLOCKS) == {
            "cold_start", "drain", "spec", "kvbm_offload",
            "two_class_goodput", "session_cache", "disagg"}

    def test_on_baseline_report_passes_the_gate(self):
        assert dw.gate(synth_report(), dw.BASELINE_DIR) == []


class TestGateCatchesDrift:
    def test_rel_metric_out_of_envelope(self):
        report = synth_report()
        node = report["cold_start"]["modeled"]["striped_warm"]
        node["total_s"] *= 1.10  # 10% drift vs a 2% envelope
        failures = dw.gate(report, dw.BASELINE_DIR)
        (line,) = failures
        assert line.startswith("cold_start.modeled.striped_warm.total_s:")
        assert "+10.0%" in line and "envelope ±2%" in line

    def test_rel_metric_inside_envelope_passes(self):
        report = synth_report()
        report["disagg"]["pipelined_ttft_ms"]["p50"] *= 1.05  # ±75% env
        assert dw.gate(report, dw.BASELINE_DIR) == []

    def test_exact_metric_any_drift_fails(self):
        report = synth_report()
        report["drain"]["handoff_path"]["handoff"] += 1
        failures = dw.gate(report, dw.BASELINE_DIR)
        (line,) = failures
        assert "drain.handoff_path.handoff" in line
        assert "!= blessed" in line

    def test_a_failed_scenario_verdict_fails_the_gate(self):
        """The chaos-backed blocks carry their own pass verdict, nested
        one level down for the spot join: a false one is drift."""
        report = synth_report()
        report["cold_start"]["measured_spot"]["passed"] = False
        failures = dw.gate(report, dw.BASELINE_DIR)
        (line,) = failures
        assert line.startswith("cold_start.measured_spot.passed:")
        assert "observed False != blessed True" in line

    def test_missing_block_and_metric_reported(self):
        report = synth_report()
        del report["spec"]
        del report["kvbm_offload"]["offloaded_blocks"]
        failures = dw.gate(report, dw.BASELINE_DIR)
        assert "spec: block missing from report" in failures
        assert any("kvbm_offload.offloaded_blocks" in f
                   and "missing from report" in f for f in failures)


class TestCompare:
    def test_rel_zero_baseline_uses_absolute_tolerance(self):
        assert dw.compare("rel", 0.05, 0.0, 0.04) is None
        assert dw.compare("rel", 0.05, 0.0, 0.06) is not None

    def test_rel_non_numeric_is_a_failure(self):
        assert "non-numeric" in dw.compare("rel", 0.1, 1.0, "fast")

    def test_exact_bools(self):
        assert dw.compare("exact", 0.0, True, True) is None
        assert dw.compare("exact", 0.0, True, False) is not None


class TestBlessRoundTrip:
    def test_bless_then_gate_then_validate(self, tmp_path):
        report = synth_report()
        written = dw.bless(report, tmp_path)
        assert sorted(written) == sorted(
            f"{b}.json" for b in dw.REQUIRED_BLOCKS)
        assert dw.gate(report, tmp_path) == []
        assert dw.validate(tmp_path) == []

    def test_bless_refuses_an_incomplete_report(self, tmp_path):
        report = synth_report()
        del report["drain"]["bit_identical"]
        with pytest.raises(SystemExit, match="cannot bless"):
            dw.bless(report, tmp_path)

    def test_envelope_drift_fails_gate_and_validate(self, tmp_path):
        """A baseline blessed under an older SPEC (different tol) must
        fail loudly instead of silently gating with the wrong
        envelope."""
        report = synth_report()
        dw.bless(report, tmp_path)
        path = dw.baseline_path("spec", tmp_path)
        data = json.loads(path.read_text())
        data["metrics"]["k"]["tol"] = 0.5
        path.write_text(json.dumps(data))
        assert any("spec.k" in f and "envelope drift" in f
                   for f in dw.gate(report, tmp_path))
        assert any("spec.k" in f and "envelope drift" in f
                   for f in dw.validate(tmp_path))

    def test_blessed_but_not_in_spec_flagged(self, tmp_path):
        dw.bless(synth_report(), tmp_path)
        path = dw.baseline_path("drain", tmp_path)
        data = json.loads(path.read_text())
        data["metrics"]["ghost_metric"] = {
            "value": 1, "kind": "exact", "tol": 0.0}
        path.write_text(json.dumps(data))
        assert any("drain.ghost_metric" in p and "not in SPEC" in p
                   for p in dw.validate(tmp_path))

    def test_missing_baseline_file(self, tmp_path):
        dw.bless(synth_report(), tmp_path)
        dw.baseline_path("disagg", tmp_path).unlink()
        assert any(f.startswith("disagg: no baseline")
                   for f in dw.gate(synth_report(), tmp_path))
        assert "disagg: baseline file missing" in dw.validate(tmp_path)


class TestCli:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable, "-m", "tools.dynawatch", *args],
            capture_output=True, text=True, cwd=REPO)

    def test_validate_shipped_baselines(self):
        proc = self._run("--validate")
        assert proc.returncode == 0, proc.stderr
        assert "baselines valid" in proc.stdout

    def test_gate_pass_and_fail(self, tmp_path):
        report = synth_report()
        ok = tmp_path / "ok.json"
        ok.write_text(json.dumps(report))
        proc = self._run("--report", str(ok))
        assert proc.returncode == 0, proc.stderr
        assert "gate passed" in proc.stdout

        bad = copy.deepcopy(report)
        bad["cold_start"]["striped_fetch_speedup"] *= 2.0
        bad_path = tmp_path / "bad.json"
        bad_path.write_text(json.dumps(bad))
        proc = self._run("--report", str(bad_path))
        assert proc.returncode == 1
        assert "FAIL cold_start.striped_fetch_speedup" in proc.stderr
        assert "gate FAILED" in proc.stderr

    def test_unreadable_report_is_exit_2(self, tmp_path):
        proc = self._run("--report", str(tmp_path / "nope.json"))
        assert proc.returncode == 2
        assert "cannot read report" in proc.stderr
