"""The nemotron_h configuration (`nemotron3-nano-ep2`) and its cell
(`chat128-sat`): the file against the program's preset and the worker's
flags, the reference against its contract and the program's weights, the
counts against numbers worked out by hand, the warm list against the
scheduler's chunking rule at 128 rows, the new readers on made-up
numbers. What the dense architecture's tests do for `mistral-7b-w4kv8`,
in a file of this architecture's own."""

import ast
import json
import os

import numpy as np
import pytest

from bench_paths import BENCH, ROOT
from dtbench import stats, traffic
from test_bench_contract import (BUDGET, bench, bucket, load, pow2, simulate,
                                 table_width)

CONFIG = "nemotron3-nano-ep2"
REFERENCE = os.path.join(BENCH, "references", "nemotron_h.py")


def body():
    return load("configs", CONFIG + ".json")


def module(path, name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def counts():
    return module(os.path.join(BENCH, "shapes", "nemotron_h.py"), "counts")


# -- the file against the program ---------------------------------------------


def served_config():
    from dynamo_tpu.engine.worker import build_arg_parser
    from dynamo_tpu.models.config import cut_config, get_config

    serve = body()["serve"]
    args = build_arg_parser().parse_args(
        ["--model", serve["model"], *serve["worker_args"]])
    return cut_config(get_config(args.model), args.serve_layers,
                      args.experts_held, args.vocab_rows)


def test_the_file_states_what_the_preset_and_the_flags_run():
    """The reference is built from the file, the server from the preset
    cut by `serve.worker_args`: every size the one reads is the other's."""
    b, cfg = body(), served_config()
    # the depth served is the pattern's length; `num_hidden_layers` keeps
    # the published count (the contract test's width pattern refuses a
    # reduced key with "hidden" in it)
    assert (b["hidden_size"], b["served_layers"],
            len(b["hybrid_override_pattern"]), b["hybrid_override_pattern"],
            b["vocab_size"]) == (
        cfg.hidden, cfg.n_layers, cfg.n_layers, cfg.layer_pattern,
        cfg.vocab_size)
    assert (b["mamba_num_heads"], b["mamba_head_dim"], b["n_groups"],
            b["ssm_state_size"], b["conv_kernel"], b["chunk_size"]) == (
        cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups, cfg.ssm_state,
        cfg.conv_kernel, cfg.ssm_chunk)
    assert (b["num_attention_heads"], b["num_key_value_heads"],
            b["head_dim"]) == (cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim)
    assert not cfg.use_rope and not b["tie_word_embeddings"]
    assert b["mlp_hidden_act"] == cfg.mlp_act == "relu2"
    assert (b["n_routed_experts_published"], tuple(b["experts_held"]),
            b["n_routed_experts"]) == (cfg.n_experts, cfg.held_experts, 64)
    assert (b["num_experts_per_tok"], b["moe_intermediate_size"],
            b["moe_shared_expert_intermediate_size"], b["n_shared_experts"],
            b["routed_scaling_factor"], b["norm_topk_prob"]) == (
        cfg.n_experts_active, cfg.expert_mlp_hidden,
        cfg.shared_expert_hidden, cfg.n_shared_experts,
        cfg.moe_routed_scale, cfg.moe_norm_topk)
    assert (b["n_group"], b["topk_group"]) == (cfg.moe_n_group,
                                              cfg.moe_topk_group) == (1, 1)
    assert cfg.moe_scoring == "sigmoid"
    assert b["layer_norm_epsilon"] == b["norm_eps"] == cfg.rms_eps
    assert (b["time_step_min"], b["time_step_max"], b["time_step_floor"]) \
        == (cfg.ssm_dt_min, cfg.ssm_dt_max, cfg.ssm_dt_floor)
    assert b["reference"]["dtype"] == cfg.dtype == b["torch_dtype"]
    assert cfg.ssm_state_dtype == "float32"
    assert b["serve"]["weight_dtype"] == b["reference"]["weights"] == "model"
    assert b["max_position_embeddings"] == (
        b["serve"]["page_size"] * b["serve"]["max_pages_per_seq"])
    # the published sizes, stated beside the cut ones, are the preset's
    from dynamo_tpu.models.config import get_config

    whole, pub = get_config(b["serve"]["model"]), b["published"]
    assert (b["num_hidden_layers"], pub["hybrid_override_pattern"],
            pub["n_routed_experts"], pub["vocab_size"],
            pub["max_position_embeddings"]) == (
        whole.n_layers, whole.layer_pattern, whole.n_experts,
        whole.vocab_size, whole.max_context)
    assert whole.layer_pattern.startswith(b["hybrid_override_pattern"])


def test_the_cut_keeps_to_the_floors_and_no_width_differs():
    b = body()
    pattern, pub = b["hybrid_override_pattern"], b["published"]
    assert set(pattern) == {"M", "E", "*"} and pattern.count("*") >= 2
    assert len(pattern) > 9  # the published pattern's longest period
    assert b["n_routed_experts"] >= 8
    assert b["vocab_size"] * 8 >= pub["vocab_size"]
    assert set(pub) == set(b["reduced_why"])  # what an entry's `reduced` lists
    for key in ("deployment", "memory"):
        assert "64 of the 128" in b[key] or "64 experts" in b[key]
    assert "HALF" in b["deployment"]  # each expert sees half its load
    assert {"attention_positions", "ssm_state_dtype", "weights"} <= set(
        b["assumed"])


def test_the_cell_is_declared_as_entries_and_the_generator_can_feed_it():
    """BENCHMARK.json names the configuration and the cell, last in their
    lists, and the cell on the lists of PR 25's per-layer metrics that
    move `out_tok_s`. Not on PR 26's eight, and none of this
    architecture's own readers is declared: `test_bench_trace_in_run`
    holds `per_layer[-8:]` to PR 26's names and their `workloads` to the
    dense cell alone, and new entries must come last (PERF.md section 7).

    `run.py` hands a closed loop callers + 12 requests for each second of
    ramp, window and tail, and this server closes some 15.5 a second
    (3,450-3,570 tok/s over answers of mean 238: my chip runs, PR 30), so
    a --trace 0 run (no tail) must not run dry inside its window: the
    callers start over the whole ramp, and one that starts late consumes
    late."""
    import re

    b = bench()
    assert b["configs"][-1]["name"] == CONFIG
    assert b["configs"][-1]["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert set(b["configs"][-1]["reduced"]) == set(body()["reduced_why"])
    cell = b["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG + ".chat128-sat", CONFIG, "chat128-sat", 1)
    reported = {m["name"]: m for group in ("end_to_end", "per_layer")
                for m in b[group]
                if "workloads" not in m or cell["name"] in m["workloads"]}
    assert {"out_tok_s", "setup_s"} == {
        m["name"] for m in b["end_to_end"] if m["name"] in reported}
    assert all(m.get("moves", "out_tok_s") == "out_tok_s"
               for m in reported.values())
    assert {"decode_hbm_roofline_pct", "window_compiles", "preempts",
            "device_idle_pct"} <= set(reported)
    assert not set(reported) & set(NEW)
    assert not {m["name"] for m in b["per_layer"]} & set(NEW)

    with open(os.path.join(BENCH, "run.py")) as f:
        per_second = int(re.search(r"count = int\(callers \+ total \* (\d+)\)",
                                   f.read()).group(1))
    mix = load("mixes", "chat128-sat.json")
    pairs = traffic.population(mix, 1)
    answer = sum(o for _, o in pairs) / len(pairs)
    assert 236 < answer < 240
    ramp, spread = mix["ramp_seconds"], mix["start_spread_seconds"]
    assert spread <= ramp
    span = ramp + b["run_seconds"]
    handed = 128 + per_second * span

    def taken(tok_s: float) -> int:
        """Requests 128 callers have asked for by the window's end, each
        taking 128 / (closed a second) seconds a request."""
        cycle = 128 / (tok_s / answer)
        return sum(1 + int((span - i * spread / 128) / cycle)
                   for i in range(128))

    assert taken(3570) < handed < taken(1.15 * 3570)  # some room, not much
    # chunk-sat's ramp and spread would not do: dry before the window ends
    span, spread = 20 + b["run_seconds"], 10
    assert taken(3450) > 128 + per_second * span


# -- the reference ------------------------------------------------------------


def test_the_reference_keeps_its_contract():
    with open(REFERENCE) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0
            imported.add(node.module.split(".")[0])
    assert imported <= {"__future__", "math", "jax", "numpy"}
    fn = next(n for n in tree.body if isinstance(n, ast.FunctionDef)
              and n.name == "logits_for")
    assert [a.arg for a in fn.args.args] == ["samples", "cfg", "pad_to",
                                             "lower"]
    source = open(REFERENCE).read()
    assert 'default_matmul_precision("highest")' in source
    assert body()["reference"]["module"] == os.path.relpath(REFERENCE, ROOT)


def tiny():
    """(program config, the file keys the reference reads) at the CPU
    tests' size: every layer kind, 8 experts of which 2:6 are held, a
    sliced vocabulary."""
    import dataclasses

    from dynamo_tpu.models.config import cut_config, get_config

    cfg = cut_config(dataclasses.replace(get_config("tiny-hybrid-test"),
                                         dtype="float32"),
                     layers=6, experts="2:6", vocab_rows=256)
    lo, hi = cfg.held_experts
    return cfg, {
        "dtype": cfg.dtype, "weight_seed": 0, "hidden_size": cfg.hidden,
        "hybrid_override_pattern": cfg.layer_pattern,
        "mamba_num_heads": cfg.mamba_heads,
        "mamba_head_dim": cfg.mamba_head_dim, "n_groups": cfg.ssm_groups,
        "ssm_state_size": cfg.ssm_state, "conv_kernel": cfg.conv_kernel,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "n_routed_experts": hi - lo,
        "n_routed_experts_published": cfg.n_experts,
        "experts_held": [lo, hi],
        "moe_intermediate_size": cfg.expert_mlp_hidden,
        "moe_shared_expert_intermediate_size": cfg.shared_expert_hidden,
        "num_experts_per_tok": cfg.n_experts_active,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "norm_topk_prob": cfg.moe_norm_topk,
        "layer_norm_epsilon": cfg.rms_eps,
        "time_step_min": cfg.ssm_dt_min, "time_step_max": cfg.ssm_dt_max,
        "time_step_floor": cfg.ssm_dt_floor, "vocab_size": cfg.vocab_size}


def test_the_programs_seeded_weights_are_the_references_recipe():
    """Same seed, same numbers, each from its own code: every leaf of the
    program's random tree against the reference's recipe (the program
    keeps an expert's up-projection output-major and A as its log)."""
    import jax

    from dynamo_tpu.models.transformer import init_params

    ref = module(REFERENCE, "nemotron_h_reference")
    cfg, file_cfg = tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    keys = ref.model_keys(file_cfg)
    names = {"in_proj": "in_proj", "conv_w": "conv_w", "conv_b": "conv_b",
             "dt_bias": "dt_bias", "out_proj": "out_proj", "wq": "wq",
             "wk": "wk", "wv": "wv", "wo": "wo", "router": "router",
             "e_bias": "e_bias", "e_down": "e_down", "s_up": "s_up",
             "s_down": "s_down"}
    for i, lp in enumerate(params["layers"]):
        kind = cfg.layer_kind(i)
        want = ref.layer_weights(keys[i + 1], kind, file_cfg)
        for theirs, ours in names.items():
            if theirs in want:
                np.testing.assert_array_equal(want[theirs], lp[ours])
        if kind == "M":
            np.testing.assert_allclose(want["a"], -np.exp(lp["a_log"]),
                                       rtol=1e-6)
            assert np.all(np.asarray(lp["d_skip"]) == 1)
            assert np.all(np.asarray(lp["ssm_norm"]) == 1)
            dt = np.log1p(np.exp(np.asarray(lp["dt_bias"])))
            assert dt.min() >= 0.001 * 0.999 and dt.max() <= 0.1 * 1.001
        if kind == "E":
            np.testing.assert_array_equal(
                np.swapaxes(want["e_up"], 1, 2), lp["e_up"])
            assert lp["e_up"].shape[0] == 4 and np.std(lp["e_bias"]) > 0.01
        assert np.all(np.asarray(lp["norm"]) == 1)
    h, v = cfg.hidden, cfg.vocab_size
    np.testing.assert_array_equal(
        ref._dense(keys[0], (v, h), h, "float32"), params["embed"])
    np.testing.assert_array_equal(
        ref._dense(keys[-1], (h, v), h, "float32"), params["lm_head"])


def test_each_control_lowers_one_precision_and_only_then():
    import jax

    ref = module(REFERENCE, "nemotron_h_reference")
    _cfg, file_cfg = tiny()
    rng = np.random.default_rng(0)
    sample = [{"prompt": rng.integers(0, 256, 20).tolist(),
               "served": rng.integers(0, 256, 9).tolist()}]
    with jax.default_matmul_precision("highest"):
        sound = ref.logits_for(sample, file_cfg, 32)[0]
        again = ref.logits_for(sample, file_cfg, 32, lower={})[0]
        assert sound.shape == (9, 256) and sound.dtype == np.float32
        np.testing.assert_array_equal(sound, again)
        assert 0.5 < sound.std() < 2.0
        check = body()["check"]
        assert set(check["further_controls"]) == {"state-bf16", "kv-int8"}
        for name, lower in {**check["controls"],
                            **check["further_controls"]}.items():
            assert len(lower) == 1  # one axis at a time
            low = ref.logits_for(sample, file_cfg, 32, lower)[0]
            moved = np.abs(low - sound)
            # moved, and still this model's logits (at 64 hidden units a
            # rounding can flip an expert choice: tenths at a position)
            assert moved.max() > 1e-6 and moved.mean() < 0.5, (name, moved)


# -- the counts ---------------------------------------------------------------


def test_the_counts_are_the_ones_worked_out_by_hand():
    c, b = counts(), body()
    p = c.matmul_params(b)
    assert p["mamba"] == 2688 * (4096 + 6144 + 64) + 4096 * 2688 == 38707200
    assert p["attention"] == (2688 * 4096 + 2 * 2688 * 256
                              + 4096 * 2688) == 23396352
    assert p["expert"] == 2 * 2688 * 1856 == 9977856
    assert p["shared"] == 2 * 2688 * 3712 and p["router"] == 2688 * 128
    assert p["head"] == p["embed"] == 65536 * 2688
    # 6 M, 6 E, 2 *: 4.585 B parameters = 9.17 GB in bf16
    total = c.total_params(b)
    assert total == (6 * (38707200 + 5 * 6144) + 2 * 23396352
                     + 6 * (64 * 9977856 + 19955712 + 344064)
                     + 2 * 176160768)
    assert round(total / 1e9, 3) == 4.585
    assert c.kv_bytes_per_token(b) == 2 * 2 * 2 * 128 * 2 == 2048
    assert c.ssm_state_bytes_per_row(b) == 6 * 64 * 64 * 128 * 4
    assert c.state_bytes_per_row(b) == 6 * (2097152 + 3 * 6144 * 2)
    # under uniform routing (ISSUE 30's arithmetic, and what the seeded
    # recipe comes to within 2%) an expert is missed by 128 rows with
    # probability (1 - 6/128)^128
    u = b
    assert "routing" not in b  # no constant fitted to a reading
    assert abs(c.touched_experts(u, 128) - 64 * (1 - 0.953125 ** 128)) < 1e-9
    assert 63.8 < c.touched_experts(u, 128) < 64 == c.touched_experts(u, 1e9)
    assert abs(c.touched_experts(u, 1) - 3.0) < 1e-9  # 6 of 128, half held
    # at 128 rows: 8.8 GB of weights, 3.3 GB of state, 15 ms at 819 GB/s
    assert round(c.weight_bytes_per_step(u, 128) / 1e9, 1) == 8.8
    assert round(128 * 2 * c.state_bytes_per_row(u) / 1e9, 1) == 3.3
    step = c.decode_step_bytes(u, 128 * 600, rows=128)
    assert 14.5 < step / 819e9 * 1e3 < 15.5
    # handed tokens alone it counts the fewest rows: never over
    assert c.decode_step_bytes(u, 128 * 600) < step
    assert c.decode_step_bytes(u, 128 * 600) > 0.85 * step
    assert c.attention_step_bytes(b, 1000) == 1000 * 2048
    assert c.ssm_decode_kernel_bytes(b, 100) == 100 * 2 * 6 * 2097152
    # 48 calls of an expert layer in decode steps that touch 30 experts
    # each, 12 in launches that touch 64; 400 token-slots a call
    floor = c.expert_gmm_floor(b, decode_calls=48, decode_touched=30,
                               prefill_calls=12, prefill_touched=64,
                               slots_per_call=400)
    assert floor["flops"] == 60 * 400 * 2 * 9977856
    assert floor["bytes"] == (48 * 30 + 12 * 64) * 2 * 9977856
    assert c.flops_per_token(b, 512) > 2 * (6 * p["mamba"] + p["head"])
    import dtbench.shapes as dense

    assert all(callable(getattr(c, fn)) for fn in dense.INTERFACE)


# -- the mix and its warm list ------------------------------------------------


def test_the_worker_warms_every_program_the_mix_reaches_at_128_rows():
    """`test_bench_contract`'s model of the scheduler's chunking rule, in
    this cell's closed loop of 128 callers that all start at once. The
    worker compiles its own grid before it serves (`--prewarm full`):
    the shapes the model finds must lie inside it, and `warm` lists
    them."""
    from dynamo_tpu.engine.model_runner import (DEFAULT_PREFILL_BUCKETS,
                                                 bucket_table_width)

    mix = load("mixes", "chat128-sat.json")
    warm, serve = mix["warm"], body()["serve"]
    assert mix["callers"] == "max_batch" and serve["max_batch"] == 128
    assert serve["worker_args"] == [
        "--serve-layers", "14", "--experts-held", "0:64", "--vocab-rows",
        "65536", "--prewarm", "full"]  # the cut, and the worker's warm-up
    # the worker's grid, as ModelRunner.prewarm derives it: no bucket
    # under one chunk of the scan or past the context, rows to the
    # budget over the smallest bucket
    chunk, context = body()["chunk_size"], (
        serve["max_pages_per_seq"] * serve["page_size"])
    buckets = [b for b in DEFAULT_PREFILL_BUCKETS if b >= chunk]
    assert buckets[-1] == BUDGET
    max_rows = BUDGET // buckets[0]
    buckets = [b for i, b in enumerate(buckets)
               if i == 0 or buckets[i - 1] < context]
    assert (buckets, max_rows) == ([128, 256, 512, 1024], 16)
    grid = {(r, b) for r in (1, 2, 4, 8, 16) for b in buckets}
    listed = {tuple(s) for s in warm["prefill_shapes"]}
    seen: dict = {}
    for seed in (1, 2):
        pairs = traffic.population(mix, seed)
        for (rows, b), n in simulate(pairs, "closed", 50000, seed,
                                     rows=128).items():
            shape = (rows, max(b, buckets[0]))
            seen[shape] = seen.get(shape, 0) + n
    assert set(seen) <= listed <= grid, sorted(set(seen) - listed)
    assert listed - set(seen) <= {(4, 128)}  # met under 1 in 10,000
    widths = {bucket_table_width(w, serve["max_pages_per_seq"])
              for w in range(1, serve["max_pages_per_seq"] + 1)}
    lo = mix["prompt_tokens"]["min"] + 1
    reach = {table_width(kv + 16) for kv in range(
        lo, mix["max_total_tokens"] + 1)}
    assert reach == set(warm["table_widths"]) == {16, 32, 64} <= widths
    assert {table_width(n + 1 + 16) for n in warm["decode"]} == reach
    assert all(table_width(n + 1 + 16) == table_width(
        n + warm["decode_tokens"] + 16) for n in warm["decode"])
    assert mix["max_total_tokens"] + 16 <= context
    assert warm["blocker"] < 1024 and max(warm["lone_prefill"]) < 1024
    # every sequence's pages are there however the lengths pair up
    assert serve["num_pages"] > 128 * serve["max_pages_per_seq"]
    # the crafted requests, run once as the cross-check: one for every
    # listed shape (what they are worth alone on this model: the mix's why)
    crafted = {(1, max(bucket(n), buckets[0])) for n in warm["lone_prefill"]}
    for group in warm["groups"]:
        assert sum(group) <= BUDGET
        crafted.add((pow2(len(group)), max(bucket(max(group)), buckets[0])))
    assert crafted == listed


def test_the_mix_is_the_issues():
    mix = load("mixes", "chat128-sat.json")
    assert (mix["loop"], mix["population"], mix["max_total_tokens"]) == (
        "closed", 512, 1008)
    assert mix["prompt_tokens"] == {"median": 320, "sigma": 0.45,
                                    "min": 128, "max": 768}
    assert mix["output_tokens"] == {"median": 224, "sigma": 0.4,
                                    "min": 96, "max": 448}
    pairs = traffic.population(mix, 3)
    assert len(pairs) == 512 and max(p + o for p, o in pairs) <= 1008
    assert sorted(p for p, _ in pairs) == sorted(
        p for p, _ in traffic.population(mix, 4))
    reqs = traffic.requests(mix, body()["vocab_size"], 2**31 + 7, 40)
    assert max(max(r.prompt) for r in reqs) < 65536


# -- the new readers ------------------------------------------------------------


def reader(name):
    return module(os.path.join(BENCH, "layers", name + ".py"), name)


NEW = ("ssm_decode_roofline_pct", "expert_gmm_roofline_pct",
       "ssm_dev_share_pct", "moe_dev_share_pct", "expert_load_imbalance",
       "moe_dropped_slots", "ssm_state_live_pct", "expert_touched_pct")


def context(trace=None, before=None, after=None):
    import run as harness

    t0 = 100.0
    timelines = [stats.Timeline(index=i, due=t0, sent=t0, n_prompt=300,
                                want_tokens=200, tag=f"t-{i}")
                 for i in range(100)]
    for i, t in enumerate(timelines):
        t.first, t.chunk_times = t0 + 0.5 + 0.01 * i, [t0 + 0.5 + 0.01 * i]
    ctx = {"config": body(), "shapes": counts(), "stats": stats,
           "layer": harness.Plan.layer, "timelines": timelines,
           "peaks": load("peaks.json")["TPU v5e"], "trace": trace,
           "window": {"capture_at": t0 + 2.0, "capture_end": t0 + 4.5,
                      "before": before or {}, "after": after or {}}}
    return ctx


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_finds_nothing_on_a_program_without_it(name):
    """The parent's program has no such kernel or counter: the reader
    returns None and does not raise, traced or not."""
    assert reader(name).read(context()) is None
    empty = {"ops": {"fusion": {"count": 3, "seconds": 0.5}},
             "modules": {"jit_multi": {"count": 10, "seconds": 1.0}}}
    assert reader(name).read(context(trace=empty)) is None


def moe_scrape(tokens, dropped, touched, calls):
    """touched, calls: (prefill, decode)."""
    by_phase = lambda pair: [({"worker": "a", "phase": phase}, n)
                             for phase, n in zip(("prefill", "decode"), pair)]
    return {"dynamo_moe_expert_tokens_total": [
                ({"worker": "a", "expert": str(e)}, n)
                for e, n in enumerate(tokens)],
            "dynamo_moe_dropped_slots_total": [({"worker": "a"}, dropped)],
            "dynamo_moe_experts_touched_total": by_phase(touched),
            "dynamo_moe_expert_layer_calls_total": by_phase(calls)}


def test_the_roofline_readers_on_made_up_numbers():
    c, b = counts(), body()
    # 10 fused blocks of 8 steps and 2 launches among the programs; one
    # block straddles the capture's edge, so the operations hold 450 of its
    # 480 state updates and 902 of 984 matmuls; 100 rows live throughout
    trace = {"ops": {"ssm_state_update": {"count": 450, "seconds": 0.4},
                     "gmm": {"count": 902, "seconds": 1.0},
                     "fusion": {"count": 99, "seconds": 0.6}},
             "modules": {"jit_multi": {"count": 10, "seconds": 2.0},
                         "jit_step": {"count": 2, "seconds": 0.3}}}
    # over the window: 1000 decode calls touched 30 experts each, 100
    # prefill calls 60 each; 440,000 token-slots in the 1100 calls
    ctx = context(trace=trace, before=moe_scrape([0] * 4, 0, (0, 0), (0, 0)),
                  after=moe_scrape([110000] * 4, 0, (6000, 30000),
                                   (100, 1000)))
    got = reader("ssm_decode_roofline_pct").read(ctx)
    want = 100 * (100 * 2 * 2097152 / 819e9) / (0.4 / 450)
    assert abs(got - want) < 1e-9 and 0 < got < 100
    floor = c.expert_gmm_floor(b, decode_calls=451 * 80 / 82,
                               decode_touched=30,
                               prefill_calls=451 * 2 / 82, prefill_touched=60,
                               slots_per_call=400)
    got = reader("expert_gmm_roofline_pct").read(ctx)
    assert abs(got - 100 * max(floor["bytes"] / 819e9,
                               floor["flops"] / 197e12) / 1.0) < 1e-9
    assert 0 < got < 100
    assert abs(reader("expert_touched_pct").read(ctx) - 100 * 30 / 64) < 1e-9
    assert abs(reader("ssm_dev_share_pct").read(ctx) - 20.0) < 1e-9
    assert abs(reader("moe_dev_share_pct").read(ctx) - 50.0) < 1e-9
    # a program that counts tokens but not touched experts (none has been
    # accepted; a reader still may not guess): no roofline
    old = {k: v for k, v in ctx["window"]["after"].items()
           if "touched" not in k}
    assert reader("expert_gmm_roofline_pct").read(
        context(trace=trace, after=old)) is None


def test_the_counter_readers_on_made_up_scrapes():
    def scrape(tokens, dropped, slot_ms, wall):
        return {**moe_scrape(tokens, dropped, (0, 0), (0, 0)),
                "dynamo_ssm_state_slot_ms": [({"worker": "a"}, slot_ms)],
                "dynamo_step_part_ms_total": [({"part": "wall"}, wall)]}

    ctx = context(before=scrape([10, 10, 10, 10], 0, 1000.0, 100.0),
                  after=scrape([110, 210, 60, 60], 0, 1000.0 + 96 * 900,
                               1000.0))
    assert reader("expert_load_imbalance").read(ctx) == 200 / 100
    assert reader("moe_dropped_slots").read(ctx) == 0
    assert abs(reader("ssm_state_live_pct").read(ctx) - 75.0) < 1e-9
    assert reader("expert_touched_pct").read(ctx) is None  # no call grew


# -- one whole run, rehearsed on the CPU ----------------------------------------


def tiny_file() -> dict:
    """A configuration file for `tiny-hybrid-test` cut by flags as the
    cell's is: this architecture's reference, counts and worker flags."""
    _cfg, keys = tiny()
    keys.pop("dtype")
    real = body()
    return {
        **keys, "name": "tiny-hybrid", "source": "the program's preset",
        "chunk_size": 16, "torch_dtype": "bfloat16",
        "serve": {"model": "tiny-hybrid-test", "weight_dtype": "model",
                  "kv_dtype": "model", "page_size": 4, "num_pages": 256,
                  "max_batch": 4, "max_pages_per_seq": 64,
                  "worker_args": ["--serve-layers", "6", "--experts-held",
                                  "2:6", "--vocab-rows", "256",
                                  "--prewarm", "full"]},
        "shapes": real["shapes"], "trace_names": real["trace_names"],
        "reference": {"module": real["reference"]["module"],
                      "dtype": "bfloat16", "weights": "model",
                      "weight_seed": 0},
        "check": {"sample": 4, "limits": {"gap_mean": 0.3, "gap_max": 3.0},
                  "controls": real["check"]["controls"]}}


@pytest.mark.slow
def test_a_rehearsed_run_of_this_architecture(tmp_path):
    """tiny-hybrid-test through the whole harness on the CPU (`--trace
    2`): the worker takes the cut's flags, the window's tokens are
    checked against THIS reference."""
    from test_bench_rehearsal import rehearse

    with open(tmp_path / "case.json", "w") as f:
        json.dump(tiny_file(), f)
    out, line, lines = rehearse(tmp_path, 2, 2**31 + 91,
                                config_file="case.json")
    assert out.returncode == 10, out.stderr[-3000:]
    report = json.loads(lines[-2 - len(line["compared"])])
    assert report["worker_flags"][-8:] == tiny_file()["serve"]["worker_args"]
    assert line["correct"] is True and line["failed"] == 0
    # the new readers are declared by no entry of BENCHMARK.json yet, so
    # the line holds the accepted metrics alone
    assert not set(NEW) & set(line["metrics"])
    assert line["metrics"]["preempts"]["value"] == 0
