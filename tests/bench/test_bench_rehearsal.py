"""One whole run, rehearsed on the CPU with tiny-test: the harness's look
for a chip is skipped (`--rehearse-cpu`) and everything else is driven:
children, warm-up, window, recording, reference child, trace reduction,
the last line. A rehearsal can never read as a chip result: platform cpu
on the line, exit code 10."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT

HERE = os.path.dirname(os.path.abspath(__file__))


def rehearse(tmp_path, trace, seed, env=None, traffic="rehearsal"):
    bench = {
        "command": ["python3", "benchmarks/run.py"],
        "paths": ["benchmarks", "tests/bench"], "run_seconds": 5,
        "configs": [{"name": "tiny-test", "source": "the program's preset",
                     "file": "benchmarks/configs/tiny-test.json",
                     "reduced": [], "why": "toy"}],
        "workloads": [{"name": "tiny." + traffic, "config": "tiny-test",
                       "traffic": traffic, "chips": 1, "why": "toy"}],
    }
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    bench["end_to_end"] = [dict(m) for m in real["end_to_end"]]
    bench["per_layer"] = [dict(m) for m in real["per_layer"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)  # the toy cell reports every metric
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    if not os.path.exists(tmp_path / "benchmarks"):
        os.symlink(BENCH, tmp_path / "benchmarks")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark-json",
         str(tmp_path / "BENCHMARK.json"), "--workload", "tiny." + traffic,
         "--seed", str(seed), "--seconds", "5", "--trace", str(trace),
         "--rehearse-cpu"], cwd=ROOT, env=dict(os.environ, **(env or {})),
        capture_output=True, text=True, timeout=300)
    lines = out.stdout.strip().splitlines()
    assert lines, out.stderr[-2000:]
    return out, json.loads(lines[-1]), lines


@pytest.mark.slow
@pytest.mark.parametrize("trace,traffic", [(0, "rehearsal"),
                                           (1, "rehearsal-open")])
def test_a_rehearsed_run_prints_the_contracts_line(tmp_path, trace, traffic):
    out, line, lines = rehearse(tmp_path, trace, 2**31 + 77, traffic=traffic)
    assert out.returncode == 10, out.stderr[-2000:]
    assert set(line) - {"breakdown"} == {"correct", "attempted", "failed",
                                         "metrics", "device"}
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    names = set(line["metrics"])
    if trace:
        assert {"sched_host_share_pct", "window_compiles",
                "kv_pool_live_pct", "preempts", "ttft_p50_ms",
                "ttft_p95_ms", "tpot_p50_ms"} <= names
        assert "out_tok_s" not in names and "tpot_p95_ms" not in names
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert names == {"out_tok_s", "tpot_p95_ms", "setup_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())
    # every number compared is printed beside its limit
    assert any(ln.startswith("compared gap_max:") for ln in lines)


@pytest.mark.slow
def test_a_broken_timed_path_comes_out_as_not_correct(tmp_path):
    """Every fifth token altered where the scheduler produces it: the
    counts still match, the requests still complete, and `correct` is
    false because the served tokens lie far below the reference's best."""
    path = os.pathsep.join(
        p for p in (os.path.join(HERE, "broken_path"),
                    os.environ.get("PYTHONPATH")) if p)
    out, line, lines = rehearse(
        tmp_path, 0, 5, env={"PYTHONPATH": path,
                             "BENCH_TEST_BREAK_TOKENS": "1"})
    assert out.returncode == 10
    assert line["failed"] == 0 and line["attempted"] > 5
    assert line["correct"] is False
    assert any(ln.startswith("compared gap_max:") and "OUTSIDE" in ln
               for ln in lines)
