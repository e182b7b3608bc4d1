"""One run that measures, then traces (`--trace 2`, `trace_in_run`), and
the readers of what the program counts inside the worker (PR 26): each on
synthetic before/after scrapes, and None where its family is missing, as
it is on a program from before the PR."""

import asyncio
import json
import os
import subprocess
import sys
import time

import pytest

from bench_paths import BENCH, ROOT
from dtbench import scrapes, traffic
from dtbench.client import Client
from dtbench.stats import Timeline

STAGES = "dynamo_stage_duration_seconds"
MODEL = {"model": "mistral-7b"}


def stage_rows(suffix, values):
    return [(dict(MODEL, stage=stage), v) for stage, v in values.items()
            ] + [({"stage": "preprocess", "model": "x"}, 99.0)]


def scrape(stage_sum, stage_count, decode_tokens, prefill_tokens, launches,
           reserved, host, device, parts):
    """A page as Fleet.scrape() gives it: {sample: [(labels, value)]}."""
    w = {"worker": "ab"}
    return {
        STAGES + "_sum": stage_rows("_sum", stage_sum),
        STAGES + "_count": stage_rows("_count", stage_count),
        "dynamo_engine_tokens": [(dict(w, kind="decode"), decode_tokens),
                                 (dict(w, kind="prefill"), prefill_tokens)],
        "dynamo_engine_launches": [(dict(w, kind=k), v)
                                   for k, v in launches.items()],
        "dynamo_kv_reserved_page_ms": [(w, reserved)],
        "dynamo_step_host_ms_sum": [({"phase": "decode"}, host[0]),
                                    ({"phase": "prefill"}, host[1])],
        "dynamo_step_device_ms_sum": [({"phase": "decode"}, device[0]),
                                      ({"phase": "prefill"}, device[1])],
        "dynamo_step_part_ms_total": [({"part": p}, v)
                                      for p, v in parts.items()],
    }
    # host + device sums overcount the wall (phase windows overlap) and
    # are not what the readers divide by


BEFORE = scrape(
    {"ingress": 1.0, "queue": 10.0, "prefill_wait": 5.0, "prefill": 20.0,
     "decode": 100.0},
    dict.fromkeys(("ingress", "queue", "prefill_wait", "prefill", "decode"),
                  100),
    decode_tokens=10_000, prefill_tokens=50_000,
    launches={"prefill": 40, "decode_block": 100, "decode_step": 800},
    reserved=1.0e6, host=(100.0, 50.0), device=(4000.0, 5850.0),
    parts={"wall": 7000.0, "prep": 10.0, "dispatch": 30.0,
           "drain_wait": 9000.0})
AFTER = scrape(
    {"ingress": 1.8, "queue": 70.0, "prefill_wait": 45.0, "prefill": 180.0,
     "decode": 1700.0},
    dict.fromkeys(("ingress", "queue", "prefill_wait", "prefill", "decode"),
                  260),
    decode_tokens=31_600, prefill_tokens=170_000,
    launches={"prefill": 120, "decode_block": 200, "decode_step": 1600},
    reserved=1.0e6 + 1792 * 50_000.0, host=(400.0, 250.0),
    device=(24_000.0, 57_350.0),
    parts={"wall": 57_000.0, "prep": 60.0, "dispatch": 280.0,
           "drain_wait": 58_000.0})
# the steps' wall grew by 50,000 ms; the host and device sums by 72,000
WANT = {
    "ingress_mean_ms": 0.8 / 160 * 1e3,
    "queue_wait_mean_ms": 60.0 / 160 * 1e3,
    "prefill_wait_mean_ms": 40.0 / 160 * 1e3,
    "prefill_span_mean_ms": 160.0 / 160 * 1e3,
    "decode_rows_mean": 21_600 / 800,
    "prefill_launch_tokens_mean": 120_000 / 80,
    "kv_reserved_pct": 100.0 * 1792 / 5120,
    "runner_dispatch_share_pct": 100.0 * 250 / 50_000,
}
# the sample each reader cannot do without
NEEDS = {
    "ingress_mean_ms": STAGES + "_sum",
    "queue_wait_mean_ms": STAGES + "_count",
    "prefill_wait_mean_ms": STAGES + "_sum",
    "prefill_span_mean_ms": STAGES + "_sum",
    "decode_rows_mean": "dynamo_engine_launches",
    "prefill_launch_tokens_mean": "dynamo_engine_launches",
    "kv_reserved_pct": "dynamo_kv_reserved_page_ms",
    "runner_dispatch_share_pct": "dynamo_step_part_ms_total",
}


def reader_ctx(before, after):
    import run as harness

    ctx = {"window": {"before": before, "after": after, "t0": 0.0,
                      "seconds": 50.0},
           "config": {"serve": {"num_pages": 5120, "page_size": 16}}}
    ctx["read"] = lambda name: harness.Plan.reader(name)(ctx)
    ctx["layer"] = harness.Plan.layer
    return ctx


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_takes_the_growth_over_the_window(name):
    assert reader_ctx(BEFORE, AFTER)["read"](name) == pytest.approx(
        WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_returns_none_where_its_family_is_missing(name):
    """The parent's program has none of these families: the reader finds
    nothing, returns None and does not raise; nor where nothing grew."""
    old = {k: v for k, v in AFTER.items() if k != NEEDS[name]}
    assert reader_ctx(BEFORE, old)["read"](name) is None
    assert reader_ctx({}, {})["read"](name) is None
    assert reader_ctx(AFTER, AFTER)["read"](name) is None


def test_every_new_metric_is_declared_for_the_cell_with_its_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["trace_in_run"] is True
    declared = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"][:12]}
    for name in WANT:
        m = declared[name]
        assert m["workloads"] == ["m7b-w4kv8.chunk-sat"]
        assert m["moves"] == "out_tok_s" and m["layer"] in layers | {
            "Frontend, router, request plane"}
        assert m["source"] in ("program_span", "program_counter")
        assert os.path.isfile(os.path.join(BENCH, "layers", name + ".py"))
    # appended: what the benchmark had keeps its place
    assert [m["name"] for m in bench["per_layer"][-8:]] == [
        "ingress_mean_ms", "queue_wait_mean_ms", "prefill_wait_mean_ms",
        "prefill_span_mean_ms", "decode_rows_mean",
        "prefill_launch_tokens_mean", "kv_reserved_pct",
        "runner_dispatch_share_pct"]


def test_scrape_arithmetic_reads_a_missing_family_as_none_not_zero():
    page = {"x_total": [({"kind": "a"}, 3.0), ({"kind": "b"}, 4.0)]}
    assert scrapes.total(page, "x_total") == 7.0
    assert scrapes.total(page, "x_total", kind="b") == 4.0
    assert scrapes.total(page, "x_total", kind="c") is None
    assert scrapes.total(page, "y_total") is None
    window = {"before": {}, "after": page}
    assert scrapes.growth(window, "x_total", kind="a") == 3.0  # from nothing
    assert scrapes.growth({"before": page, "after": {}}, "x_total") is None
    assert scrapes.ratio(1.0, 0.0) is None and scrapes.ratio(None, 2.0) is None
    assert scrapes.ratio(1.0, 4.0, 100.0) == 25.0
    assert scrapes.step_wall_ms(window) is None
    parts = {"dynamo_step_part_ms_total": [({"part": "wall"}, 9.0),
                                           ({"part": "prep"}, 1.0)]}
    assert scrapes.step_wall_ms({"before": {}, "after": parts}) == 9.0


# -- the switch ---------------------------------------------------------------


@pytest.mark.parametrize("trace,refused", [(2, False), (3, True)])
def test_the_argument_parser_takes_trace_2_and_nothing_above(trace, refused):
    """No chip here: a run the parser accepts ends as `no result` in
    seconds; one it refuses never gets that far."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         "3", "--seconds", "1", "--trace", str(trace)], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=60)
    assert out.returncode == 2 and out.stdout == ""
    assert ("invalid choice" in out.stderr) is refused
    assert ("no result" in out.stderr) is not refused


def test_a_tail_asks_for_more_requests_and_the_window_gets_the_same():
    """--trace 2 generates a tail's worth more: the window's requests are
    the same ones (the generator is prefix-stable in its count)."""
    with open(os.path.join(BENCH, "mixes", "chunk-sat.json")) as f:
        mix = json.load(f)
    short = traffic.requests(mix, 32768, 2**31 + 9, 60)
    longer = traffic.requests(mix, 32768, 2**31 + 9, 60 + 45 * 12)
    assert longer[:60] == short


class FakeClient(Client):
    """`send` without a server: a request takes 10 ms."""

    async def send(self, req, due, tag):
        t = Timeline(index=req.index, due=due, sent=time.monotonic(),
                     n_prompt=len(req.prompt), want_tokens=req.max_tokens,
                     tag=tag)
        self.timelines.append(t)
        await asyncio.sleep(0.01)
        t.end, t.got_tokens = time.monotonic(), req.max_tokens
        return t


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_traffic_ends_when_the_requests_do_not_at_the_latest_stop(loop):
    """The tail's traffic ends with the capture: the harness stops
    handing out requests, and the loop returns then, not at `stop_at`."""
    reqs = [traffic.Request(i, 0.01 * i, (1, 2, 3), 4) for i in range(40)]
    state = {"handed": 0}

    def until_done():
        for req in reqs:
            if state["handed"] >= 12:
                return
            state["handed"] += 1
            yield req

    async def body():
        client = FakeClient("http://unused", "m")
        start = time.monotonic()
        if loop == "closed":
            await client.closed_loop(until_done(), 3, start + 30.0, "t")
        else:
            await client.open_loop(until_done(), start, start + 30.0, "t")
        return client, time.monotonic() - start

    client, took = asyncio.run(body())
    assert took < 5.0
    assert len(client.timelines) == 12
    assert all(t.ok for t in client.timelines)
