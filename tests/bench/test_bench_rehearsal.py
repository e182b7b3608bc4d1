"""One whole run, rehearsed on the CPU with tiny-test: the harness's look
for a chip is skipped (`--rehearse-cpu`) and everything else is driven:
children, warm-up, window, recording, reference child, trace reduction,
the last line. A rehearsal can never read as a chip result: platform cpu
on the line, exit code 10."""

import glob
import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT

HERE = os.path.dirname(os.path.abspath(__file__))


def rehearse(tmp_path, trace, seed, env=None, traffic="rehearsal",
             config_file="benchmarks/configs/tiny-test.json"):
    bench = {
        "command": ["python3", "benchmarks/run.py"],
        "paths": ["benchmarks", "tests/bench"], "run_seconds": 5,
        "configs": [{"name": "tiny-test", "source": "the program's preset",
                     "file": config_file, "reduced": [], "why": "toy"}],
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
        os.makedirs(tmp_path / "tests")
        os.symlink(HERE, tmp_path / "tests" / "bench")
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
                                           (1, "rehearsal-open"),
                                           (2, "rehearsal")])
def test_a_rehearsed_run_prints_the_contracts_line(tmp_path, trace, traffic):
    out, line, lines = rehearse(tmp_path, trace, 2**31 + 77, traffic=traffic)
    assert out.returncode == 10, out.stderr[-2000:]
    assert set(line) - {"breakdown"} == {"correct", "attempted", "failed",
                                         "metrics", "device", "compared"}
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    names = set(line["metrics"])
    end_to_end = {"out_tok_s", "tpot_p95_ms", "setup_s"}
    if trace:
        assert {"sched_host_share_pct", "window_compiles",
                "kv_pool_live_pct", "preempts", "ttft_p50_ms",
                "ttft_p95_ms", "tpot_p50_ms",
                # counted inside the worker (PR 26)
                "ingress_mean_ms", "queue_wait_mean_ms",
                "prefill_wait_mean_ms", "prefill_span_mean_ms",
                "decode_rows_mean", "prefill_launch_tokens_mean",
                "kv_reserved_pct", "runner_dispatch_share_pct"} <= names
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    if trace == 1:
        assert not names & end_to_end
    elif trace == 0:
        assert names == end_to_end
    if trace != 1:
        assert all(line["metrics"][n]["value"] > 0 for n in end_to_end)
    if trace == 2:
        # one run that measures, then traces: both kinds side by side,
        # the window's numbers taken before the profiler ever started
        assert end_to_end < names
        report = json.loads(lines[0])
        tail = report["tail"]
        assert tail["capture_s"] >= 2.5 and tail["profiler_warm_s"] > 0
        # the traffic did not stop with the window: it lasted the traced
        # span, which is shorter than the capture took to come back
        assert tail["traced_out_tok_s"] > 0
        assert tail["traffic_covered_the_span"] is True
        assert 2.5 <= tail["traced_span_s"] <= tail["capture_s"]
        assert tail["capture_start_s"] >= 0 and tail["capture_stop_s"] > 0
        assert 0 < line["metrics"]["decode_rows_mean"]["value"] <= 4
        assert 0 < line["metrics"]["kv_reserved_pct"]["value"] <= 100
        # the scheduler's sections are in the capture's host plane (a
        # rehearsal keeps its capture; a chip run deletes it once reduced)
        from dtbench import trace_reduce

        found = glob.glob(os.path.join(
            ROOT, ".bench_cache", "run", "tiny." + traffic, "profile",
            "**", "*.xplane.pb"), recursive=True)
        assert len(found) == 1, found  # the profiler's warm-up is gone
        host = {e[2] for plane in trace_reduce.read_planes(found[0])
                if plane["name"].startswith("/host:")
                for ln in plane["lines"] for e in ln["events"]}
        assert {"sched.drain_incoming", "sched.admit", "sched.decode_prep",
                "sched.prefill_prep", "sched.gap", "sched.finalize_prefill",
                "sched.drain_wait", "sched.emit", "sched.reap", "decode",
                "prefill"} <= host, sorted(
            n for n in host if not n.startswith("$"))[:60]
    # every number compared is printed beside its limit: on standard
    # output, as the last lines of standard error, and last in the line
    assert any(ln.startswith("compared gap_max:") for ln in lines)
    assert [ln.split(":")[0] for ln in out.stderr.strip().splitlines()[-2:]
            ] == ["compared gap_max", "compared gap_mean"]
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == {"gap_max", "gap_mean"}
    assert line["compared"]["gap_max"]["limit"] == 0.25
    assert 0 <= line["compared"]["gap_max"]["value"] <= 0.25
    # the dense reference decided it, and the run's log says so
    assert "reference dtbench/reference.py (dense):" in out.stderr


@pytest.mark.slow
def test_a_configuration_is_served_and_checked_through_what_it_names(
        tmp_path):
    """The fixture of test_bench_named.py through a whole run: its worker
    gets its further flags, and `correct` is decided by the reference it
    names (every served token reads exactly 0.125 below that stand-in's
    best, where the dense reference reads 0 to 0.06 on tiny-test)."""
    out, line, lines = rehearse(
        tmp_path, 2, 7, config_file="tests/bench/named/config.json")
    assert out.returncode == 10, out.stderr[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"] == {
        "gap_max": {"value": 0.125, "limit": 0.25},
        "gap_mean": {"value": 0.125, "limit": 0.2}}
    assert f"reference {tmp_path}/tests/bench/named/reference.py:" in (
        out.stderr)
    assert json.loads(lines[0])["worker_flags"][-4:] == [
        "--max-pages-per-seq", "64", "--kvbm-host-blocks", "16"]


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
