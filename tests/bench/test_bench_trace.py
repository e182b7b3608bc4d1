"""The trace reduction: on hand-made planes, and on a small recorded
`.xplane.pb` from the chip kept with the benchmark."""

import os

import pytest

from bench_paths import BENCH
from dtbench import trace_reduce as tr

RECORDED = os.path.join(BENCH, "data", "small.xplane.pb")


def test_union_and_gaps():
    spans = [(0, 10), (5, 12), (20, 30), (30, 31), (25, 26)]
    assert tr.union_ns(spans) == 12 + 11
    assert tr.gaps_ns(spans, -5, 40) == [(-5, 0), (12, 20), (31, 40)]
    assert tr.gaps_ns([], 0, 5) == [(0, 5)]
    assert tr.union_ns([]) == 0.0


def test_self_time_takes_nested_operations_out():
    events = [(0, 100, "while.1"), (10, 30, "fusion.1"), (30, 90, "fusion.2"),
              (40, 50, "copy.3"), (200, 220, "fusion.4")]
    assert tr.self_times(events) == [20, 20, 50, 10, 20]


@pytest.mark.parametrize("raw,want", [
    ("%fusion.123 = f32[8]{0} fusion(...)", "fusion"), ("fusion.7", "fusion"),
    ("paged_attention_decode_pool", "paged_attention_decode_pool"),
    ("q4_matmul_kernel.3", "q4_matmul_kernel"), ("while", "while")])
def test_numbered_copies_fold_together(raw, want):
    assert tr.base_name(raw) == want


def planes():
    ms = 1_000_000.0
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                (0, 10 * ms, "jit_multi(1)"), (20 * ms, 30 * ms, "jit_multi(2)"),
                (40 * ms, 44 * ms, "jit_prefill_step(7)")]},
            {"name": "XLA Ops", "events": [
                (0, 10 * ms, "%while.1 = ..."), (1 * ms, 7 * ms, "%fusion.5 = ..."),
                (20 * ms, 30 * ms, "%while.1 = ..."),
                (21 * ms, 29 * ms, "%fusion.5 = ..."),
                (40 * ms, 44 * ms, "%fusion.9 = ...")]},
            {"name": "Steps", "events": [(0, 44 * ms, "0")]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "engine", "events": [
                (-2 * ms, 50 * ms, "$scheduler.py:100 run"),
                (9 * ms, 21 * ms, "$scheduler.py:1600 _drain_decode"),
                (29 * ms, 41 * ms, "prefill"),
                (30 * ms, 40 * ms, "$model_runner.py:1000 prefill_chunk")]}]},
    ]


def test_reduce_gives_busy_idle_sums_and_the_breakdown():
    out = tr.reduce(planes())
    assert out["window_s"] == pytest.approx(0.044)  # the device's span
    assert out["busy_s"] == pytest.approx(0.024)
    assert out["modules"]["jit_multi"] == {
        "count": 2, "seconds": pytest.approx(0.020)}
    assert out["modules"]["jit_prefill_step"]["count"] == 1
    assert out["ops"]["fusion"]["seconds"] == pytest.approx(0.018)
    assert out["ops"]["while"]["seconds"] == pytest.approx(0.006)
    assert sum(op["seconds"] for op in out["ops"].values()) == \
        pytest.approx(out["busy_s"])
    assert out["breakdown"]["device_ops"][0] == ["fusion",
                                                 pytest.approx(0.018)]
    idle = dict(out["breakdown"]["idle_gaps"])
    # 10-20 ms under the drain frame, 30-40 ms under the program's
    # own `prefill` span (a named span wins over a Python frame)
    assert idle == {"scheduler.py:1600 _drain_decode": pytest.approx(0.010),
                    "prefill": pytest.approx(0.010)}
    assert sum(idle.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])


def test_a_trace_with_no_device_plane_reads_as_no_busy_time():
    host_only = [p for p in planes() if p["name"].startswith("/host")]
    out = tr.reduce(host_only)
    assert out["busy_s"] == 0.0 and out["breakdown"]["device_ops"] == []
    assert out["window_s"] == pytest.approx(0.052)  # all planes' span


def test_the_recorded_trace_from_the_chip():
    """A few launches recorded on a TPU v5e (my chip run, PR 25), cut to
    the first events of each line; the expected numbers are re-derived
    here from the raw events, by other code than the reduction's."""
    if not os.path.isfile(RECORDED):
        pytest.fail("benchmarks/data/small.xplane.pb is missing")
    raw = tr.read_planes(RECORDED)
    out = tr.reduce(raw)
    device = next(p for p in raw if p["name"] == "/device:TPU:0")
    ops = next(ln for ln in device["lines"] if ln["name"] == "XLA Ops")
    # busy time by a millisecond grid of 1 us cells, not by sorting
    lo = min(e[0] for e in ops["events"])
    hi = max(e[1] for e in ops["events"])
    cells = bytearray(int((hi - lo) / 1000) + 2)
    for s, e, _ in ops["events"]:
        for c in range(int((s - lo) / 1000), int((e - lo) / 1000) + 1):
            cells[c] = 1
    assert out["busy_s"] == pytest.approx(sum(cells) * 1e-6, rel=0.05)
    assert 0 < out["busy_s"] <= out["window_s"] == pytest.approx(
        (hi - lo) / 1e9)
    assert sum(op["seconds"] for op in out["ops"].values()) == \
        pytest.approx(out["busy_s"], rel=0.02)
    assert sum(op["count"] for op in out["ops"].values()) == len(
        ops["events"])
    assert out["modules"] and out["breakdown"]["device_ops"]
    assert out["modules"]["jit_step"]["count"] == 1


# -- the readers over a reduced trace -----------------------------------------


def reader_ctx(config):
    import run as harness
    from dtbench import shapes, stats

    ctx = {"config": config, "trace": tr.reduce(planes()), "shapes": shapes,
           "stats": stats, "peaks": {"hbm_bytes_per_s": 819e9},
           "window": {"t0": 0.0, "seconds": 1.0, "capture_at": 0.0,
                      "capture_end": 1.0},
           "timelines": [stats.Timeline(
               index=0, due=0.0, sent=0.0, n_prompt=300, want_tokens=100,
               first=-1.0, last=None, end=None, tag="t-0")]}
    ctx["read"] = lambda name: harness.Plan.reader(name)(ctx)
    ctx["layer"] = harness.Plan.layer
    return ctx


TOY = {"num_hidden_layers": 2, "num_key_value_heads": 2, "head_dim": 16,
       "serve": {"kv_dtype": "model", "num_pages": 100, "page_size": 4,
                 "decode_block": 8}}


@pytest.mark.parametrize("names,step_ms,prefill_pct", [
    ({}, 1.25, None),  # jit_multi: 2 launches, 20 ms, 8 steps each
    ({"decode_module": "jit_prefill_step", "prefill_modules": "^jit_multi$"},
     0.5, 100.0 * 20 / 24)],
    ids=["the-names-a-v5e-trace-prints", "a-configurations-own-names"])
def test_readers_find_programs_by_their_own_or_the_configurations_names(
        names, step_ms, prefill_pct):
    """Each reader carries the name it looks for; a configuration whose
    programs are named otherwise overrides it in its own file."""
    ctx = reader_ctx(dict(TOY, trace_names=names))
    assert ctx["read"]("decode_step_dev_ms") == pytest.approx(step_ms)
    share = ctx["read"]("prefill_dev_share_pct")
    if prefill_pct is None:  # `^jit_step$` matches none of the toy's names
        assert share == 0.0
    else:
        assert share == pytest.approx(prefill_pct)
    assert ctx["read"]("paged_attn_roofline_pct") is None  # no such kernel
    ctx = reader_ctx(dict(TOY, trace_names=dict(
        names, attention_kernels="^fusion$")))
    # 300 live tokens x 2 layers x K and V x 2 heads x 16 x 2 B over 819 GB/s,
    # against the kernel's 18 ms over the decode steps of the capture
    steps = {1.25: 16, 0.5: 8}[step_ms]
    assert ctx["read"]("paged_attn_roofline_pct") == pytest.approx(
        100.0 * (300 * 256 / 819e9) / (0.018 / steps))


def test_the_pool_share_counts_live_tokens_not_residue():
    """One sequence decoding through the whole window with a 300-token
    prompt and nothing streamed yet, in a pool of 400 tokens: 75%."""
    ctx = reader_ctx(TOY)
    assert ctx["read"]("kv_pool_live_pct") == pytest.approx(75.0)
    ctx["timelines"] = []
    assert ctx["read"]("kv_pool_live_pct") is None
