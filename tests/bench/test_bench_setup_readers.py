"""The seven readers of set-up (`benchmarks/layers/setup_*.py`, PR 40):
each on made-up scrapes of the window's start, an absent family reads
None (the parent's program), and once on the text the program's own
registry prints, so the labels the readers ask for are the labels the
program sets. No entry of BENCHMARK.json declares them yet (PERF.md
section 7 (b) writes the entries out for B0)."""

import json
import os
import re

import pytest

from bench_paths import BENCH, ROOT
from dtbench import fleet

READERS = ("setup_boot_s", "setup_weights_s", "setup_trace_lower_s",
           "setup_cache_load_s", "setup_compile_s", "setup_programs",
           "setup_programs_used_pct")


def rows(family: str, label: str, **values) -> dict:
    return {family: [({"worker": "w", label: k}, v)
                     for k, v in values.items()]}


def stages(**by_stage) -> dict:
    """Two entry points' rows a stage, as the program prints them."""
    return {"dynamo_jit_stage_seconds_total": [
        ({"fn": fn, "stage": stage}, seconds * share)
        for stage, seconds in by_stage.items()
        for fn, share in (("prefill_batch", 0.75), ("decode_multi", 0.25))]}


def launches(**by_key) -> dict:
    return {"dynamo_program_launches": [
        ({"worker": "w", "fn": key.split("[")[0], "key": key}, count)
        for key, count in by_key.items()]}


def read(name: str, before: dict, after: dict = None):
    import run as harness

    ctx = {"window": {"before": before, "after": after or before,
                      "t0": 0.0, "seconds": 50.0},
           "layer": harness.Plan.layer}
    ctx["read"] = lambda metric: harness.Plan.reader(metric)(ctx)
    return ctx["read"](name)


LADDER = rows("dynamo_coldstart_phase_seconds", "phase", boot=9.5,
              fetch=3.0, load=38.0, compile=120.0, register=0.4,
              first_token=14.0)
BUILDS = {**stages(trace=36.0, lower=46.8, cache_load=65.0, compile=1.5),
          "dynamo_jit_compiles_total": [({"fn": "unscoped"}, 31.0),
                                        ({"fn": "prefill_batch"}, 14.0),
                                        ({"fn": "decode_multi"}, 3.0)]}


@pytest.mark.parametrize("name, before, expected", [
    ("setup_boot_s", LADDER, 9.5),
    ("setup_weights_s", LADDER, 41.0),
    ("setup_trace_lower_s", BUILDS, 82.8),
    ("setup_cache_load_s", BUILDS, 65.0),
    ("setup_compile_s", BUILDS, 1.5),
    ("setup_programs", BUILDS, 48.0),
])
def test_a_reader_sums_its_rows_at_the_windows_start(name, before, expected):
    # what grows inside the window is not set-up: the later scrape is
    # never read
    later = {family: [(labels, value * 3) for labels, value in rows_]
             for family, rows_ in before.items()}
    assert read(name, before, later) == pytest.approx(expected)


@pytest.mark.parametrize("name", READERS)
def test_an_absent_family_reads_none(name):
    assert read(name, {}) is None
    # the families of a program from before PR 40: the ladder without
    # `boot`, builds counted and not split by stage, launches by kind
    parent = {
        **rows("dynamo_coldstart_phase_seconds", "phase", fetch=3.0,
               load=38.0),
        "dynamo_jit_compiles_total": [({"fn": "prefill"}, 17.0)],
        "dynamo_jit_compile_seconds_total": [({"fn": "prefill"}, 65.0)],
        "dynamo_engine_launches": [({"worker": "w", "kind": "prefill"},
                                    120.0)]}
    old = {"setup_weights_s": 41.0, "setup_programs": 17.0}
    assert read(name, parent) == old.get(name)


def test_weights_leave_out_the_builds_of_engine_construction():
    """`load` 38 s of a cold start, 31.5 s of it building the programs
    that make the parameters: those seconds are the build readers'."""
    unscoped = {"dynamo_jit_stage_seconds_total": [
        ({"fn": "unscoped", "stage": "trace"}, 0.5),
        ({"fn": "unscoped", "stage": "lower"}, 1.0),
        ({"fn": "unscoped", "stage": "compile"}, 30.0),
        ({"fn": "prefill", "stage": "compile"}, 400.0)]}
    assert read("setup_weights_s", {**LADDER, **unscoped}) == \
        pytest.approx(41.0 - 31.5)
    # never negative: a ladder that closed before the builds were counted
    short = rows("dynamo_coldstart_phase_seconds", "phase", load=2.0)
    assert read("setup_weights_s", {**short, **unscoped}) == 0.0


def test_a_stage_that_never_ran_reads_zero_where_the_family_is_there():
    cold = stages(trace=36.0, lower=46.8, compile=610.0)
    assert read("setup_cache_load_s", cold) == 0.0
    warm = stages(trace=36.0, lower=46.8, cache_load=65.0)
    assert read("setup_compile_s", warm) == 0.0


def test_used_share_counts_the_keys_that_grew_inside_the_window():
    """Four keys listed at the window's start: two warmed and never
    launched, one launched before and again inside, one launched before
    only. A key first listed inside the window was not set-up's."""
    before = launches(**{"prefill_batch[4x1024]": 3, "prefill[1024]": 1,
                         "prefill_batch[8x512]": 0,
                         "decode_multi[w64,b8,fed]": 0})
    after = launches(**{"prefill_batch[4x1024]": 60, "prefill[1024]": 1,
                        "prefill_batch[8x512]": 0,
                        "decode_multi[w64,b8,fed]": 130,
                        "decode_multi[w64,b8,chained]": 130})
    assert read("setup_programs_used_pct", before, after) == 50.0
    assert read("setup_programs_used_pct", before, before) == 0.0
    assert read("setup_programs_used_pct", {}, after) is None


def test_the_readers_ask_for_the_labels_the_program_sets():
    """The listener driven by hand with jax's five events, a ladder and
    a runner's launch counts published as the worker does; the page the
    registry prints, parsed as the harness parses it."""
    from prometheus_client import generate_latest

    from dynamo_tpu.engine import model_runner as mr
    from dynamo_tpu.engine.coldstart import ColdStartLadder
    from dynamo_tpu.runtime.metrics import (
        PROGRAM_LAUNCHES,
        REGISTRY,
    )

    def page() -> dict:
        return fleet.parse_metrics(generate_latest(REGISTRY).decode())

    base = page()
    ladder = ColdStartLadder("readers-w")
    ladder.mark("fetch", 2.0)
    ladder.mark("load", 5.0)
    with mr.compile_scope("prefill", "prefill[64]", cause="prewarm"):
        mr._on_compile_event(mr._TRACE_EVENT, 1.25)
        mr._on_compile_event(mr._LOWER_EVENT, 0.5)
        mr._on_cache_event("/jax/compilation_cache/cache_hits")
        mr._on_compile_event(mr._COMPILE_EVENT, 4.0)
        mr._on_compile_event(mr._TRACE_EVENT, 0.25)
        mr._on_compile_event(mr._LOWER_EVENT, 0.25)
        mr._on_compile_event(mr._COMPILE_EVENT, 8.0)
    PROGRAM_LAUNCHES.labels(worker="readers-w", fn="prefill",
                            key="prefill[64]").set(0)
    now = page()

    def grown(name: str) -> float:
        return read(name, now) - (read(name, base) or 0.0)

    # only this ladder's rows
    mine = {k: [(lab, v) for lab, v in rws
                if lab.get("worker", "readers-w") == "readers-w"]
            for k, rws in now.items()}
    assert read("setup_boot_s", mine) == pytest.approx(
        ladder.phases["boot"])
    # less what this process built outside any entry before this test
    loose = sum(v for lab, v in now["dynamo_jit_stage_seconds_total"]
                if lab["fn"] == "unscoped")
    assert read("setup_weights_s", mine) == pytest.approx(
        max(0.0, 7.0 - loose))
    assert grown("setup_trace_lower_s") == pytest.approx(2.25)
    assert grown("setup_cache_load_s") == pytest.approx(4.0)
    assert grown("setup_compile_s") == pytest.approx(8.0)
    assert grown("setup_programs") == 2
    later = {**mine, **launches(**{"prefill[64]": 2})}
    assert read("setup_programs_used_pct", mine, later) == 100.0


def test_the_entries_wait_in_perf_md_and_keep_the_contracts_form():
    """The seven entries B0 is to paste are written out in PERF.md as
    JSON: each names a reader in the tree, moves `setup_s`, lists all
    four cells, and has just the keys a per-layer entry may have; none
    is declared yet."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    block = re.search(r"```json\n(\[\n.*?\n\])\n```", text, re.S)
    assert block, "PERF.md section 7 (b) lost its JSON block"
    entries = json.loads(block.group(1))
    assert [e["name"] for e in entries] == list(READERS)
    cells = [w["name"] for w in bench["workloads"]]
    layers = {m["layer"] for m in bench["per_layer"]}
    declared = {m["name"] for m in bench["per_layer"]}
    for entry in entries:
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert entry["moves"] == "setup_s"
        assert entry["workloads"] == cells
        assert entry["layer"] in layers
        assert entry["source"] in ("program_span", "program_counter")
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", entry["unit"])
        assert entry["name"] not in declared
        assert os.path.isfile(
            os.path.join(BENCH, "layers", entry["name"] + ".py"))
