"""The mellum configuration (`mellum2-12b-pp4`) and its cell
(`codemix-sat`): the file against the program's preset and the worker's
flags, key by key; the counts against numbers worked out by hand; the
reference against its contract and the program's weights; the supply of
requests against `ramp_why`; the mix's shapes against the grid the worker
compiles; the four waiting readers on made-up numbers. What
`test_bench_nemotron_h.py` does for `nemotron3-nano-ep2`, in a file of
this architecture's own.

BENCHMARK.json names the configuration and its cell as its last entries
(fourth hand-in: the driver refuses a `model_config` PR whose
BENCHMARK.json gains none). ENTRY, CELL_ENTRY and NINE below are those
entries, letter for letter, and everything here holds them by name,
never by position: PR 30's `test_bench_nemotron_h.py` holds
`configs[-1]` and `workloads[-1]` to its own entries, new entries must
come last, and no PR but a `benchmark` PR may edit a file the benchmark
has, so that one test of PR 30 is red from this PR on (PERF.md section
7 (b), ROADMAP B0 (b): four lines to find their entries by name)."""

import ast
import os
import re

import numpy as np
import pytest

from bench_paths import BENCH, ROOT
from dtbench import stats, traffic
from test_bench_contract import bench, load

CONFIG = "mellum2-12b-pp4"
CELL = CONFIG + ".codemix-sat"
REFERENCE = os.path.join(BENCH, "references", "mellum.py")
SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
          "blob/main/config.json")
WAITING = ("window_attn_roofline_pct", "window_attn_dev_share_pct",
           "kv_window_reserved_pct", "kv_window_freed_per_token")
ENTRY = {
    "name": CONFIG, "source": SOURCE,
    "file": f"benchmarks/configs/{CONFIG}.json",
    "reduced": ["layer_types", "mlp_layer_types", "max_position_embeddings"],
    "why": "window and full attention layers side by side, each with its "
           "own page group, over 64 SwiGLU experts top-8: one pipeline "
           "stage of four on a v5e-4 host, published widths, 8 of 28 layers"}
CELL_ENTRY = {
    "name": CELL, "config": CONFIG, "traffic": "codemix-sat", "chips": 1,
    "why": "closed loop, 64 callers = slots: prompts 256-7424 (median "
           "2048), answers 64-704, contexts to 8176, unshared: window page "
           "group freed behind, 1024+chunk-key prefill, window decode "
           "kernel, 64 experts"}
# PR 25's per-layer metrics that move `out_tok_s`: the cell joins their lists
NINE = ("sched_host_share_pct", "window_compiles", "kv_pool_live_pct",
        "preempts", "decode_step_dev_ms", "prefill_dev_share_pct",
        "decode_hbm_roofline_pct", "paged_attn_roofline_pct",
        "device_idle_pct")


def body():
    return load("configs", CONFIG + ".json")


def module(path, name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def counts():
    return module(os.path.join(BENCH, "shapes", "mellum.py"), "counts")


def worker_args():
    from dynamo_tpu.engine.worker import build_arg_parser

    serve = body()["serve"]
    return build_arg_parser().parse_args(
        ["--model", serve["model"], "--page-size", str(serve["page_size"]),
         "--num-pages", str(serve["num_pages"]),
         "--max-batch", str(serve["max_batch"]),
         "--max-pages-per-seq", str(serve["max_pages_per_seq"]),
         *serve["worker_args"]])


def served_config():
    from dynamo_tpu.models.config import cut_config, get_config

    args = worker_args()
    return cut_config(get_config(args.model), args.serve_layers,
                      args.experts_held, args.vocab_rows)


# -- the file against the program ---------------------------------------------


def test_the_file_states_what_the_preset_and_the_flags_run():
    """The reference is built from the file, the server from the preset
    cut by `serve.worker_args`: every size the one reads is the other's,
    and no width differs from the source."""
    from dynamo_tpu.models.config import get_config
    from dynamo_tpu.models.hybrid import rope_tables

    b, cfg = body(), served_config()
    whole = get_config(b["serve"]["model"])
    assert b["source"] == SOURCE and b["model_type"] == "mellum"
    kinds = {"sliding_attention": "W", "full_attention": "*"}
    pattern = "".join(kinds[k] + "E" for k in b["layer_types"])
    assert (cfg.layer_pattern, cfg.n_layers, cfg.mixers_per_layer) == (
        pattern, 2 * b["served_layers"], 2)
    assert len(b["layer_types"]) == len(b["mlp_layer_types"]) == 8
    assert set(b["mlp_layer_types"]) == {"sparse"}
    assert b["layer_types"] == ["sliding_attention"] * 3 + [
        "full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert whole.layer_pattern == "WEWEWE*E" * 7
    assert (b["num_hidden_layers"], whole.n_layers) == (28, 56)
    assert (b["hidden_size"], b["num_attention_heads"],
            b["num_key_value_heads"], b["head_dim"], b["vocab_size"]) == (
        cfg.hidden, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim,
        cfg.vocab_size) == (2304, 32, 4, 128, 98304)
    assert (b["num_experts"], b["num_experts_per_tok"],
            b["moe_intermediate_size"], b["norm_topk_prob"]) == (
        cfg.n_experts, cfg.n_experts_active, cfg.expert_mlp_hidden,
        cfg.moe_norm_topk) == (64, 8, 896, True)
    assert cfg.held_experts == (0, 64) and cfg.n_shared_experts == 0
    assert cfg.moe_scoring == "softmax" and cfg.moe_routed_scale == 1.0
    assert cfg.mlp_act == "swiglu" and b["hidden_act"] == "silu"
    assert (b["intermediate_size"], cfg.mlp_hidden) == (7168, 7168)  # unused
    assert b["rms_norm_eps"] == cfg.rms_eps == 1e-6
    assert not b["attention_bias"] and not cfg.attn_bias and not cfg.qk_norm
    assert not b["tie_word_embeddings"] and not cfg.tie_embeddings
    assert b["sliding_window"] == cfg.sliding_window == 1024
    assert [cfg.layer_sliding_window(i) for i in range(0, 16, 2)] == [
        1024, 1024, 1024, 0] * 2
    yarn = b["rope_parameters"]["full_attention"]
    plain = b["rope_parameters"]["sliding_attention"]
    assert (yarn["rope_type"], plain["rope_type"]) == ("yarn", "default")
    assert yarn["rope_theta"] == plain["rope_theta"] == cfg.rope_theta == 5e5
    assert (yarn["factor"], yarn["original_max_position_embeddings"],
            yarn["beta_fast"], yarn["beta_slow"]) == (
        cfg.rope_yarn_factor, cfg.rope_yarn_orig_max,
        cfg.rope_yarn_beta_fast, cfg.rope_yarn_beta_slow) == (16, 8192, 32, 1)
    assert cfg.use_rope and cfg.rope_yarn_truncate
    assert rope_tables(cfg, "*")[1] == pytest.approx(
        yarn["attention_factor"]) == pytest.approx(1.2772588722239782)
    assert rope_tables(cfg, "W")[1] == 1.0
    assert b["reference"]["dtype"] == cfg.dtype == b["torch_dtype"]
    assert b["serve"]["weight_dtype"] == b["reference"]["weights"] == "model"
    assert b["max_position_embeddings"] == (
        b["serve"]["page_size"] * b["serve"]["max_pages_per_seq"]) == 8192
    assert whole.max_context == b["published"]["max_position_embeddings"]
    assert set(b["published"]) == set(b["reduced_why"]) == {
        "layer_types", "mlp_layer_types", "max_position_embeddings"}
    assert {"qk_norm", "shared_expert", "prediction_module", "rope",
            "router", "weights"} <= set(b["assumed"])
    assert "8, 8, 8 and 4" in b["deployment"]


def test_every_number_of_the_source_is_in_the_file():
    """The catalog's `config` of this architecture, key for key, but for
    the three keys `reduced` lists."""
    import json

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["source_url"] == SOURCE)
    b = body()
    for key, value in row["config"].items():
        if key in b["reduced_why"]:
            continue
        assert b[key] == value, key
    assert b["layer_types"] == row["config"]["layer_types"][:8]
    assert b["mlp_layer_types"] == row["config"]["mlp_layer_types"][:8]


def test_the_workers_flags_are_the_files():
    b, args = body(), worker_args()
    serve = b["serve"]
    assert serve["worker_args"] == [
        "--serve-layers", "8", "--window-pages", "5120",
        "--prefill-buckets", "512,1024,2048", "--prewarm", "full"]
    assert (args.serve_layers, args.window_pages, args.prewarm) == (
        8, 5120, "full")
    assert (serve["page_size"], serve["num_pages"], serve["max_batch"],
            serve["max_pages_per_seq"], serve["decode_block"]) == (
        16, 32768, 64, 512, 2)
    # blocks of 2: a full batch then needs 64 x 2 / 287 requests' prompts
    # a step, 1,194 tokens, which one 2,048-token launch supplies; blocks
    # of 8 would need 4,777 (PERF.md section 6: the slots ran a third full)
    assert 64 * 2 / 287 * 2678 < 0.6 * 2048 < 64 * 8 / 287 * 2678
    # the full group holds every row at 8,192 positions; the window group
    # every decoding row's 66 pages and a prefill launch's rows besides
    assert serve["num_pages"] == serve["max_batch"] * serve["max_pages_per_seq"]
    from dynamo_tpu.engine.pages import WindowPool

    pool = WindowPool(args.window_pages, 16, 1024)
    assert pool.bound(16) == 1024 // 16 + 2 == 66  # blocks of 8
    assert pool.bound(2 * 2) == 66  # this cell's blocks of 2
    assert pool.bound(2048) == (1024 + 2048) // 16 + 1 == 193
    spare = args.window_pages - 1 - 64 * 66
    assert spare == 895 > 2048 // 16 + 4 * 2  # a launch's rows beyond theirs
    cell = next(w for w in bench()["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


# -- the counts ---------------------------------------------------------------


def test_the_counts_are_the_ones_worked_out_by_hand():
    c, b = counts(), body()
    p = c.matmul_params(b)
    assert p["attention"] == 2304 * 4096 * 2 + 2304 * 512 * 2 == 21233664
    assert p["expert"] == 3 * 2304 * 896 == 6193152
    assert p["router"] == 2304 * 64 and p["head"] == p["embed"] == 226492416
    total = c.total_params(b)
    assert total == 8 * (21233664 + 147456 + 64 * 6193152) + 2 * 226492416
    assert round(total * 2 / 1e9, 2) == 7.59  # GB of bf16 weights
    # a 16-token page of each group
    per_layer = c.kv_bytes_per_token_layer(b)
    assert per_layer == 2 * 4 * 128 * 2 == 2048
    z = c.sizes(b)
    assert (z["n_full"], z["n_window"], z["held"]) == (2, 6, 64)
    assert 16 * z["n_full"] * per_layer == 65536
    assert 16 * z["n_window"] * per_layer == 196608
    assert c.kv_bytes_per_token(b) == 8 * 2048
    # 58 rows x 8 of 64 touch every expert: 6.34 GB of experts, 0.34 of
    # attention projections, 0.45 of head
    assert 63.9 < c.touched_experts(b, 58) < 64
    assert abs(c.touched_experts(b, 1) - 8.0) < 1e-9
    assert round(c.weight_bytes_per_step(b, 58) / 1e9, 2) == 7.13
    # the window-aware KV count at three contexts, against the row-aware
    # count of one row of that context: never above it
    for context in (600, 2850, 8176):
        row_aware = (2 * context * per_layer
                     + 6 * c.window_layer_kv_bytes(b, [context]))
        counted = c.attention_step_bytes(b, context)
        assert counted <= row_aware * (1 + 1e-9), context
        assert counted == context * per_layer * (2 + 6 * 1024 / 8176)
    assert c.attention_step_bytes(b, 8176) == pytest.approx(
        2 * 8176 * 2048 + 6 * 1024 * 2048)  # exact at the longest context
    assert c.window_layer_kv_bytes(b, [600, 2850, 8176]) == (
        600 + 1024 + 1024) * 2048
    # 58 rows of mean context 2,850: a third under the row-aware count,
    # and an every-token count would read 2.2 times the window-aware one
    live = 58 * 2850
    row_aware = 2 * live * 2048 + 6 * c.window_layer_kv_bytes(b, [2850] * 58)
    assert 0.6 < c.attention_step_bytes(b, live) / row_aware < 0.75
    assert live * c.kv_bytes_per_token(b) > 2 * c.attention_step_bytes(b, live)
    step = c.decode_step_bytes(b, live, rows=58)
    assert c.decode_step_bytes(b, live) < step
    assert 9.5 < step / 819e9 * 1e3 < 10.5  # ms at the HBM peak
    floor = c.expert_gmm_floor(b, decode_calls=48, decode_touched=60,
                               prefill_calls=12, prefill_touched=64,
                               slots_per_call=464)
    assert floor["flops"] == 60 * 464 * 2 * 6193152
    assert floor["bytes"] == (48 * 60 + 12 * 64) * 2 * 6193152
    assert c.flops_per_token(b, 512) < c.flops_per_token(b, 4096)
    import dtbench.shapes as dense

    assert all(callable(getattr(c, fn)) for fn in dense.INTERFACE)


# -- the reference ------------------------------------------------------------


def test_the_reference_keeps_its_contract():
    with open(REFERENCE) as f:
        source = f.read()
    tree = ast.parse(source)
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
    assert 'default_matmul_precision("highest")' in source
    assert body()["reference"]["module"] == os.path.relpath(REFERENCE, ROOT)


def tiny():
    """(program config, the file keys the reference reads) at the CPU
    tests' size: one period and a half, window 32, 8 experts top-2."""
    import dataclasses
    import math

    from dynamo_tpu.models.config import cut_config, get_config

    cfg = cut_config(dataclasses.replace(get_config("tiny-mellum-test"),
                                         dtype="float32"), layers=6)
    kinds = {"W": "sliding_attention", "*": "full_attention"}
    return cfg, {
        "dtype": cfg.dtype, "weight_seed": 0, "hidden_size": cfg.hidden,
        "head_dim": cfg.head_dim, "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "layer_types": [kinds[k] for k in cfg.layer_pattern[::2]],
        "sliding_window": cfg.sliding_window, "rms_norm_eps": cfg.rms_eps,
        "num_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.n_experts_active,
        "moe_intermediate_size": cfg.expert_mlp_hidden,
        "norm_topk_prob": cfg.moe_norm_topk, "vocab_size": cfg.vocab_size,
        "rope_parameters": {
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg.rope_theta},
            "full_attention": {
                "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                "factor": cfg.rope_yarn_factor,
                "original_max_position_embeddings": cfg.rope_yarn_orig_max,
                "beta_fast": cfg.rope_yarn_beta_fast,
                "beta_slow": cfg.rope_yarn_beta_slow,
                "attention_factor": 0.1 * math.log(cfg.rope_yarn_factor)
                + 1}}}


def test_the_programs_seeded_weights_are_the_references_recipe():
    """Same seed, same numbers, each from its own code: every leaf of the
    program's random tree against the reference's recipe (the program
    keeps an expert's gate and up as one output-major matrix)."""
    import jax

    from dynamo_tpu.models.transformer import init_params

    ref = module(REFERENCE, "mellum_reference")
    cfg, file_cfg = tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    keys = ref.model_keys(file_cfg)
    assert len(keys) == cfg.n_layers + 2 == 14
    for i, lp in enumerate(params["layers"]):
        assert np.all(np.asarray(lp["norm"]) == 1)
        if cfg.layer_kind(i) in "W*":
            want = ref.attention_weights(keys[i + 1], file_cfg)
            assert set(lp) == {"norm", "wq", "wk", "wv", "wo"}
            for name in ("wq", "wk", "wv", "wo"):
                np.testing.assert_array_equal(want[name], lp[name])
        else:
            want = ref.expert_weights(keys[i + 1], file_cfg)
            assert set(lp) == {"norm", "router", "e_up", "e_down"}
            np.testing.assert_array_equal(want["router"], lp["router"])
            np.testing.assert_array_equal(want["down"], lp["e_down"])
            fused = np.concatenate([np.swapaxes(want["gate"], 1, 2),
                                    np.swapaxes(want["up"], 1, 2)], axis=1)
            np.testing.assert_array_equal(fused, lp["e_up"])
            assert lp["e_up"].shape == (8, 2 * 48, 64)
    h, v = cfg.hidden, cfg.vocab_size
    np.testing.assert_array_equal(
        ref._dense(keys[0], (v, h), h, "float32"), params["embed"])
    np.testing.assert_array_equal(
        ref._dense(keys[-1], (h, v), h, "float32"), params["lm_head"])


def test_each_control_changes_one_thing_and_only_then():
    ref = module(REFERENCE, "mellum_reference")
    _cfg, file_cfg = tiny()
    rng = np.random.default_rng(0)
    sample = [{"prompt": rng.integers(0, 512, 90).tolist(),
               "served": rng.integers(0, 512, 9).tolist()}]
    sound = ref.logits_for(sample, file_cfg, 128)[0]
    again = ref.logits_for(sample, file_cfg, 128, lower={})[0]
    assert sound.shape == (9, 512) and sound.dtype == np.float32
    np.testing.assert_array_equal(sound, again)
    assert 0.5 < sound.std() < 2.0
    check = body()["check"]
    assert set(check["controls"]) == {"act-fp8"}
    assert set(check["further_controls"]) == {"window-full", "rope-one-table"}
    assert set(check["limits"]) == {"gap_mean"}
    for name, lower in {**check["controls"],
                        **check["further_controls"]}.items():
        assert len(lower) == 1  # one axis at a time
        low = ref.logits_for(sample, file_cfg, 128, lower)[0]
        assert np.abs(low - sound).max() > 1e-3, name
    # inside the window the mask changes nothing
    short = [{"prompt": sample[0]["prompt"][:20], "served": [1, 2, 3]}]
    np.testing.assert_array_equal(
        ref.logits_for(short, file_cfg, 32)[0],
        ref.logits_for(short, file_cfg, 32, {"window": "full"})[0])
    with pytest.raises(ValueError):
        ref.logits_for(short, file_cfg, 32, {"act": "int3"})


# -- the cell, the mix and its supply -----------------------------------------


def test_the_cells_entries_keep_the_contract_and_join_the_nine_lists():
    """The configuration and the cell as new entries (found by name:
    whatever a later PR appends behind them changes nothing here), the
    cell on the lists of PR 25's nine per-layer metrics that move
    `out_tok_s`; on no list of a client tail, and none of this PR's
    readers is declared (PERF.md section 7 (b)). The benchmark's own
    contract (`test_bench_contract.py`) holds the file with them in it."""
    b = bench()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry == ENTRY
    assert set(entry["reduced"]) == set(body()["reduced_why"])
    assert next(w for w in b["workloads"] if w["name"] == CELL) == CELL_ENTRY
    reported = {m["name"] for group in ("end_to_end", "per_layer")
                for m in b[group]
                if "workloads" not in m or CELL in m["workloads"]}
    assert reported == {"out_tok_s", "setup_s", *NINE}
    for m in b["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] == "out_tok_s"
    declared_metrics = {m["name"] for m in b["per_layer"]}
    assert not declared_metrics & set(WAITING)
    for name in WAITING:  # their readers wait in the tree
        assert os.path.isfile(os.path.join(BENCH, "layers", name + ".py"))


def test_the_harness_finds_the_cells_files_by_the_entries_names():
    """`run.py`'s `Plan` resolves the cell from BENCHMARK.json by the
    entries' names, and the cells the benchmark had report what the
    parent's file has them report."""
    import run as harness

    declared = os.path.join(ROOT, "BENCHMARK.json")
    plan = harness.Plan(declared, CELL)
    assert plan.config["serve"]["model"] == "mellum2-12b-a2.5b"
    assert plan.mix["callers"] == "max_batch"
    assert {m["name"] for m in plan.metrics("per_layer")} == set(NINE)
    assert {m["name"] for m in plan.metrics("end_to_end")} == {
        "out_tok_s", "setup_s"}
    for name, layers in (("m7b-w4kv8.chunk-sat", 20),
                         ("nemotron3-nano-ep2.chat128-sat", 9)):
        old = harness.Plan(declared, name)
        assert len(old.metrics("per_layer")) == layers


def test_the_mix_is_the_issues_and_the_supply_outlasts_the_window():
    mix, b = load("mixes", "codemix-sat.json"), bench()
    assert (mix["loop"], mix["callers"], mix["population"]) == (
        "closed", "max_batch", 512)
    assert mix["prompt_tokens"] == {"median": 2048, "sigma": 0.85,
                                    "min": 256, "max": 7424}
    assert mix["output_tokens"] == {"median": 256, "sigma": 0.5,
                                    "min": 64, "max": 704}
    assert mix["max_total_tokens"] == 8176 < 8192
    pairs = traffic.population(mix, 1)
    prompts = [p for p, _ in pairs]
    answers = [o for _, o in pairs]
    assert 2650 < sum(prompts) / 512 < 2700  # mean 2,678
    assert 285 < sum(answers) / 512 < 290  # mean 287
    assert max(p + o for p, o in pairs) <= 8176  # no answer is cut
    assert sorted(answers) == sorted(
        traffic._lognormal_quantiles(mix["output_tokens"], 512))
    past = sum(p > 1024 for p in prompts) / 512
    assert 0.77 < past < 0.81  # 79% run past the window
    assert 0.19 < sum(p > 4096 for p in prompts) / 512 < 0.22
    assert 0.15 < sum(p < 880 for p in prompts) / 512 < 0.18
    assert 0.15 < sum(p > 4800 for p in prompts) / 512 < 0.18
    # every sample past 1,024 of context needs prompts over it: the
    # window's longest is always sampled, and 79% of the rest
    # `ramp_why`: run.py hands a closed loop callers + 12 a second
    with open(os.path.join(BENCH, "run.py")) as f:
        source = f.read()
    per_second = int(re.search(r"count = int\(callers \+ total \* (\d+)\)",
                               source).group(1))
    tail = float(re.search(r"TAIL_MAX_SECS = ([\d.]+)", source).group(1))
    ramp, spread = mix["ramp_seconds"], mix["start_spread_seconds"]
    assert (ramp, spread, per_second, tail) == (30, 20, 12, 45.0)
    assert spread <= ramp
    for span in (ramp + b["run_seconds"], ramp + b["run_seconds"] + tail):
        handed = 64 + per_second * span
        # 64 callers each on their first request, then 9 closed a second:
        # the upper end of ISSUE 36's estimate, with a quarter to spare
        asked = 64 + 9 * span
        assert asked * 1.25 < handed
    assert "784" in mix["ramp_why"] and "1,189" in mix["ramp_why"]


def test_the_mixs_prefill_shapes_are_the_workers_prewarm_grid():
    """`ModelRunner.prewarm(launches=True)` derives its grid from the
    runner's buckets and token budget: rows to a power of two up to
    budget / smallest bucket, by every bucket, as far as rows x bucket
    stays inside the budget (`ModelRunner.prefill_launch_fits`, a model
    with window layers). The mix lists that grid, every
    chunk the scheduler can cut lies in it, and the table widths the
    contexts reach are widths the worker compiles."""
    from dynamo_tpu.engine.model_runner import bucket_table_width

    mix, args = load("mixes", "codemix-sat.json"), worker_args()
    warm, serve = mix["warm"], body()["serve"]
    buckets = sorted(int(x) for x in args.prefill_buckets.split(","))
    budget = buckets[-1]
    assert (buckets, budget) == ([512, 1024, 2048], 2048)
    grid = {(rows, b) for rows in (1, 2, 4, 8) for b in buckets
            if rows <= budget // buckets[0] and rows * b <= budget}
    assert len(grid) == 6
    assert {tuple(s) for s in warm["prefill_shapes"]} == grid

    def bucket(n):
        return next(b for b in buckets if n <= b)

    # whatever lengths a launch's rows have, the scheduler admits a row
    # only while pow2(rows) x bucket(longest) stays inside the budget
    rng = np.random.default_rng(0)
    for _ in range(2000):
        rows, left = [], budget
        for chunk in rng.integers(16, budget + 1, 8):
            chunk = int(min(chunk, left))
            if chunk < 16:
                break
            trial = rows + [chunk]
            n = 1 << (len(trial) - 1).bit_length()
            if n * bucket(max(trial)) > budget:
                continue
            rows, left = trial, left - chunk
        n = 1 << (len(rows) - 1).bit_length()
        assert (n, bucket(max(rows))) in grid
    widths = set()
    width = 8
    while width <= serve["max_pages_per_seq"]:
        widths.add(width)
        width *= 2
    lo = mix["prompt_tokens"]["min"] + 1
    reach = {bucket_table_width(-(-(kv + 16) // 16),
                                serve["max_pages_per_seq"])
             for kv in range(lo, mix["max_total_tokens"] + 1)}
    assert reach == set(warm["table_widths"]) == {32, 64, 128, 256, 512}
    assert reach <= widths
    assert {bucket_table_width(-(-(n + 1 + 16) // 16), 512)
            for n in warm["decode"]} == reach
    assert max(warm["decode"]) + warm["decode_tokens"] <= 8176
    for group in warm["groups"]:
        n = 1 << (len(group) - 1).bit_length()
        assert (n, bucket(max(group))) in grid and sum(group) <= budget
    assert {bucket(n) for n in warm["lone_prefill"]} == set(buckets)


# -- the waiting readers, on made-up numbers --------------------------------------


def scrape(reserved, wall, freed, edge, fails=0):
    return {
        "dynamo_kv_window_reserved_page_ms": [({"worker": "w"}, reserved)],
        "dynamo_step_part_ms_total": [({"worker": "w", "part": "wall"}, wall)],
        "dynamo_kv_window_pages_freed_total": [
            ({"worker": "w", "phase": "decode"}, freed),
            ({"worker": "w", "phase": "prefill"}, 10 * freed)],
        "dynamo_kv_window_edge_tokens_total": [
            ({"worker": "w", "phase": "decode"}, edge),
            ({"worker": "w", "phase": "prefill"}, 160 * freed)],
        "dynamo_kv_window_alloc_fail_total": [({"worker": "w"}, fails)],
    }


def reader_ctx(before, after, **more):
    import run as harness

    ctx = {"window": {"before": before, "after": after, "t0": 0.0,
                      "seconds": 50.0}, "config": body(),
           "shapes": counts(), "stats": stats, **more}
    ctx["read"] = lambda name: harness.Plan.reader(name)(ctx)
    ctx["layer"] = harness.Plan.layer
    return ctx


def test_the_counter_readers_take_the_growth_over_the_window():
    before = scrape(1.0e6, 7000.0, 100, 1500)
    after = scrape(1.0e6 + 4100 * 50_000.0, 57_000.0, 100 + 1000,
                   1500 + 16_400)
    ctx = reader_ctx(before, after)
    # 4,100 pages held on average of the group's 5,120
    assert ctx["read"]("kv_window_reserved_pct") == pytest.approx(
        100.0 * 4100 / 5120)
    # 1,000 pages back for 16,400 positions the windows moved
    assert ctx["read"]("kv_window_freed_per_token") == pytest.approx(
        1000 / 16_400)
    for name in ("kv_window_reserved_pct", "kv_window_freed_per_token"):
        assert reader_ctx({}, {})["read"](name) is None  # the parent
        assert reader_ctx(after, after)["read"](name) is None
    layer = reader_ctx({}, {})["layer"]("kv_window_reserved_pct")
    assert layer.window_pages({"serve": {}}) is None


def test_the_trace_readers_find_the_window_kernel_by_its_name():
    """Two rows decoding through the capture, contexts 600 and 3,000: a
    sliding layer's step reads (600 + 1024) x 2,048 B; 120 events of the
    window kernel in 0.012 s are 100 us each."""
    rows = [stats.Timeline(index=i, due=0.0, sent=0.0, n_prompt=n,
                           want_tokens=10, first=1.0, end=None)
            for i, n in enumerate((600, 3000))]
    trace = {"ops": {
        "paged_decode_attention_window": {"seconds": 0.012, "count": 120},
        "paged_decode_attention_pool": {"seconds": 0.004, "count": 40},
        "fusion": {"seconds": 0.084, "count": 900}}}
    ctx = reader_ctx({}, {}, trace=trace, timelines=rows,
                     peaks={"hbm_bytes_per_s": 819e9})
    ctx["window"].update(capture_at=10.0, capture_end=12.5)
    least_s = (600 + 1024) * 2048 / 819e9
    assert ctx["read"]("window_attn_roofline_pct") == pytest.approx(
        100.0 * least_s / 1e-4)
    assert ctx["read"]("window_attn_dev_share_pct") == pytest.approx(12.0)
    # the accepted share reads both kernels (`trace_names.attention_kernels`)
    pattern = re.compile(body()["trace_names"]["attention_kernels"])
    assert sum(pattern.search(n) is not None for n in trace["ops"]) == 2
    # a program without the kernel (the parent): nothing, not an error
    bare = reader_ctx({}, {}, trace={"ops": {"fusion": trace["ops"]["fusion"]}},
                      timelines=rows, peaks={"hbm_bytes_per_s": 819e9})
    bare["window"].update(capture_at=10.0, capture_end=12.5)
    assert bare["read"]("window_attn_roofline_pct") is None
    assert bare["read"]("window_attn_dev_share_pct") is None
