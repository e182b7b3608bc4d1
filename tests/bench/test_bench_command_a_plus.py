"""The cohere2_moe configuration (`command-a-plus-ep8`) and its cell
(`ragagent-sat`): the file against the catalog's published keys, the
program's preset and the worker's flags; the counts against the numbers
ISSUE 49's table works out by hand; the reference against its contract
and its controls at a toy size; the mix's lengths and its supply
arithmetic; the harness resolving every file of the cell by name; the
accepted and the waiting readers on made-up numbers. What
`test_bench_lfm2.py` does for `lfm2-8b-a1b-pp2`, in a file of this
architecture's own.

Everything here finds the entries BENCHMARK.json gained BY NAME and pins
no position and no count (PERF.md section 7 B0 (b))."""

import ast
import json
import os

import numpy as np
import pytest

from bench_paths import BENCH, ROOT
from dtbench import stats, traffic
from test_bench_contract import bench, load

CONFIG = "command-a-plus-ep8"
CELL = CONFIG + ".ragagent-sat"
MIX = "ragagent-sat"
REFERENCE = os.path.join(BENCH, "references", "cohere2_moe.py")
SOURCE = ("https://huggingface.co/CohereLabs/command-a-plus-05-2026/"
          "blob/main/config.json")
REDUCED = ["layer_types", "num_experts", "vocab_size",
           "max_position_embeddings"]
NINE = ("sched_host_share_pct", "window_compiles", "kv_pool_live_pct",
        "preempts", "decode_step_dev_ms", "prefill_dev_share_pct",
        "decode_hbm_roofline_pct", "paged_attn_roofline_pct",
        "device_idle_pct")
WAITING = ("window_attn_roofline_pct", "window_attn_dev_share_pct",
           "kv_window_reserved_pct", "kv_window_freed_per_token",
           "expert_gmm_roofline_pct", "expert_touched_pct",
           "expert_load_imbalance", "moe_dev_share_pct",
           "moe_dropped_slots")
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
FURTHER = {"block-sequential": {"block": "sequential"},
           "norm-rms": {"norm": "rms"}, "rope-all": {"rope": "all"},
           "window-full": {"window": "full"},
           "shared-sum": {"shared": "sum"}}


def body():
    return load("configs", CONFIG + ".json")


def module(path, name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def counts():
    return module(os.path.join(BENCH, "shapes", "cohere2_moe.py"), "counts")


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


# -- the file against the source and the program ----------------------------------


def test_every_number_of_the_source_is_in_the_file():
    """The catalog's `config` of this architecture, key for key, but for
    the four keys `reduced` lists."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["source_url"] == SOURCE)
    b = body()
    assert set(b["published"]) == set(b["reduced_why"]) == set(REDUCED)
    for key, value in row["config"].items():
        if key in b["reduced_why"]:
            if not isinstance(value, list):
                assert b["published"][key] == value, key
            continue
        assert b[key] == value, key
    assert row["config"]["layer_types"] == PERIOD * 8
    assert b["layer_types"] == PERIOD
    assert row["config"]["first_k_dense_replace"] == 0  # no dense block


def test_the_file_states_what_the_preset_and_the_flags_run():
    """The reference is built from the file, the server from the preset
    cut by `serve.worker_args`: every size the one reads is the other's,
    and no width differs from the source."""
    from dynamo_tpu.models.config import get_config

    b, cfg = body(), served_config()
    whole = get_config(b["serve"]["model"])
    assert b["source"] == SOURCE and b["model_type"] == "cohere2_moe"
    kinds = {"sliding_attention": "W", "full_attention": "*"}
    assert whole.layer_pattern == "".join(
        kinds[t] + "E" for t in PERIOD * 8)
    assert (b["num_hidden_layers"], whole.n_layers,
            whole.mixers_per_layer) == (32, 64, 2)
    assert cfg.layer_pattern == "".join(
        kinds[t] + "E" for t in b["layer_types"]) == "WEWEWE*E"
    assert (cfg.n_layers, b["served_layers"]) == (8, 4)
    assert (len(cfg.kv_layers), len(cfg.window_kv_layers),
            len(cfg.state_layers)) == (1, 3, 0)
    assert (b["hidden_size"], b["num_attention_heads"],
            b["num_key_value_heads"], b["head_dim"]) == (
        cfg.hidden, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim) == (
        4096, 128, 8, 128)
    assert b["sliding_window"] == cfg.sliding_window == 4096
    assert b["intermediate_size"] == cfg.expert_mlp_hidden == 4096
    assert (b["num_experts_published"], b["num_experts_per_tok"],
            b["num_shared_experts"]) == (
        cfg.n_experts, cfg.n_experts_active, cfg.n_shared_experts) == (
        128, 8, 4)
    assert tuple(b["experts_held"]) == cfg.held_experts == (0, 16)
    assert b["num_experts"] == 16 == cfg.held_experts[1] - cfg.held_experts[0]
    assert b["vocab_size"] == cfg.vocab_size == 32768 == 262144 // 8
    assert whole.vocab_size == b["published"]["vocab_size"] == 262144
    # the four facts no field said before this configuration
    assert b["use_parallel_block"] is True and cfg.parallel_block
    assert cfg.norm_kind == "layer" and b["layer_norm_eps"] == cfg.rms_eps
    assert b["rms_norm_eps"] is None
    assert b["position_embedding_type"] == "rope_gptj"
    assert (cfg.rope_kinds, cfg.rope_interleaved, cfg.use_rope) == (
        "W", True, True)
    assert b["rope_theta"] == cfg.rope_theta == 50000 and b["rotary_pct"] == 1
    assert b["shared_expert_combination_strategy"] == "average"
    assert cfg.shared_expert_mean
    assert (cfg.moe_scoring, cfg.moe_selection_bias, cfg.moe_norm_topk,
            cfg.moe_routed_scale, cfg.mlp_act) == (
        b["expert_selection_fn"], False, b["norm_topk_prob"], 1.0, "swiglu")
    assert (cfg.moe_n_group, cfg.moe_topk_group) == (1, 1)
    assert b["tie_word_embeddings"] is True and cfg.tie_embeddings
    assert b["logit_scale"] == 1 == cfg.logits_scaling
    assert not cfg.qk_norm and b["use_qk_norm"] is False
    assert not cfg.multipliers and not cfg.attn_bias and not cfg.sandwich_norm
    assert b["reference"]["dtype"] == cfg.dtype == b["torch_dtype"]
    assert b["serve"]["weight_dtype"] == b["reference"]["weights"] == "model"
    assert b["serve"]["kv_dtype"] == "model"
    assert b["max_position_embeddings"] == 12288 == (
        b["serve"]["page_size"] * b["serve"]["max_pages_per_seq"])
    assert whole.max_context == b["published"]["max_position_embeddings"]
    assert {"expert_width", "shared_expert_combination_strategy",
            "dense_prefix", "window", "rope", "router", "norm", "head",
            "vision_tower", "weights"} <= set(b["assumed"])
    assert "MEAN" in b["assumed"]["shared_expert_combination_strategy"]
    assert "not taken" in b["assumed"]["shared_expert_combination_strategy"]
    assert "left out" in b["assumed"]["vision_tower"]
    for said in ("v5e-64", "eight pipeline stages", "EIGHT expert-parallel",
                 "16 of the 128", "an eighth of the vocabulary",
                 "No code stands in"):
        assert said in b["deployment"], said


def test_the_workers_flags_are_the_files():
    b, args = body(), worker_args()
    serve = b["serve"]
    assert serve["worker_args"][:6] == [
        "--serve-layers", "4", "--experts-held", "0:16", "--vocab-rows",
        "32768"]
    assert serve["worker_args"][-2:] == ["--prewarm", "full"]
    assert (args.serve_layers, args.experts_held, args.vocab_rows,
            args.prewarm) == (4, "0:16", 32768, "full")
    assert (serve["page_size"], serve["max_pages_per_seq"]) == (16, 768)
    # ISSUE 49's sizes, or its one stated fallback with the evidence
    assert serve["max_batch"] in (32, 24)
    assert serve["max_batch"] != 24 or "24 rows" in b["serve_why"]
    # every row at the longest context the cell serves; every decoding
    # row's window + 2 pages, and room for the rows inside a launch
    assert serve["max_batch"] * 768 == serve["num_pages"]
    per_row = 4096 // 16 + 2
    assert per_row == 258
    spare = args.window_pages - 1 - serve["max_batch"] * per_row
    chunk_more = -(-(4096 + 2048 - 1) // 16) + 1 - per_row
    assert chunk_more == 127 and 7 * chunk_more <= spare <= 9 * chunk_more
    assert serve["decode_block"] in (2, 4) and "decode_block" in b["serve_why"]
    cell = next(w for w in bench()["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


@pytest.mark.parametrize("flags,said", [
    ({"kv_dtype": "int8"}, "--kv-dtype int8"),
    ({"weight_dtype": "int4"}, "--weight-dtype int4"),
    ({"devices": 4}, "--tp/--sp/--dp"),
    ({"spec": True}, "DYNT_SPEC_ENABLE"),
    ({"mode": "decode"}, "--mode decode"),
    ({"mode": "prefill"}, "--mode prefill"),
    ({"kvbm": True}, "--kvbm-host-blocks"),
])
def test_what_its_cache_cannot_do_is_refused_at_start(flags, said):
    from dynamo_tpu.engine.worker import recurrent_state_refusals

    cfg = served_config()
    recurrent_state_refusals(cfg)
    with pytest.raises(ValueError, match=said):
        recurrent_state_refusals(cfg, **flags)


# -- the counts ---------------------------------------------------------------


def test_the_counts_are_the_ones_worked_out_by_hand():
    """ISSUE 49's table, line by line, and the file's `memory`."""
    c, b = counts(), body()
    p, z = c.matmul_params(b), c.sizes(b)
    # attention: wq and wo 4096 x 16,384, wk and wv 4096 x 1,024
    assert p["attention"] == 2 * 4096 * 16384 + 2 * 4096 * 1024 == 142_606_336
    assert (p["norm"], p["router"]) == (4096, 4096 * 128) == (4096, 524_288)
    # four shared experts = one SwiGLU 16,384 wide; an expert 3 x 4096^2
    assert p["shared"] == 3 * 4096 * 16384 == 201_326_592
    assert p["expert"] == 3 * 4096 * 4096 == 50_331_648
    assert 16 * p["expert"] == 805_306_368
    assert c.block_params(b) == (142_606_336 + 4096 + 524_288 + 201_326_592
                                 + 805_306_368) == 1_149_767_680
    assert p["tied"] + p["norm"] == 32768 * 4096 + 4096 == 134_221_824
    assert (z["layers"], z["n_window"], z["n_full"], z["held"],
            z["experts"], z["k"], z["shared"]) == (4, 3, 1, 16, 128, 8, 4)
    total = c.total_params(b)
    assert total == 4 * 1_149_767_680 + 134_221_824 == 4_733_292_544
    assert round(total * 2 / 1e9, 2) == 9.47
    # the whole model by the same count: the published 218B-A25B
    whole = dict(b, layer_types=PERIOD * 8, num_experts=128,
                 experts_held=[0, 128], vocab_size=262144)
    assert round(c.total_params(whole) / 1e9, 1) == 218.3
    active = (32 * c.block_params(whole, experts=8)
              + c.matmul_params(whole)["tied"])
    assert round(active / 1e9, 1) == 25.0
    # one whole expert layer does not fit a chip: hence the share
    assert round(128 * p["expert"] * 2 / 1e9, 1) == 12.9
    # KV: 4,096 B a token a layer; a 16-token page of each group
    assert c.kv_bytes_per_token_layer(b) == 2 * 8 * 128 * 2 == 4096
    assert c.kv_bytes_per_token(b) == 16384
    serve = b["serve"]
    full = serve["num_pages"] * 16 * 4096 * 1
    window_pages = int(serve["worker_args"][
        serve["worker_args"].index("--window-pages") + 1])
    window = window_pages * 16 * 4096 * 3
    assert (16 * 4096, 3 * 16 * 4096) == (65_536, 196_608)
    assert round(full / 1e9, 2) == 1.61 and round(window / 1e9, 2) == 1.82
    held = total * 2 + full + window
    assert round(held / 1e9, 1) == 12.9 and 0.74 < held / 2 ** 34 < 0.76
    # one pool for all four layers would not fit beside the weights
    assert round(serve["max_batch"] * 12288 * 16384 / 1e9, 1) == 6.4
    for said in ("142,606,336", "201,326,592", "805,306,368",
                 "1,149,767,680", "134,221,824", "4,733,292,544",
                 "9.47 GB", "65,536 B", "196,608 B", "1.61 GB", "1.82 GB",
                 "12.9 GB"):
        assert said in b["memory"], said
    # a decode step at 32 rows: 13.97 of the 16 held experts touched
    assert round(c.touched_experts(b, 32), 2) == 13.97
    assert round(c.touched_experts(b, 1), 3) == 1.0  # 16 x 8 / 128
    weights = c.weight_bytes_per_step(b, rows=32)
    assert weights == 2.0 * (4 * (142_606_336 + 524_288 + 201_326_592)
                             + 32768 * 4096
                             + 4 * c.touched_experts(b, 32) * 50_331_648)
    assert round(weights / 1e9, 1) == 8.6
    # 32 rows at the mix's mean context (6,578 + 345 / 2): a sliding
    # layer reads min(context, 4096) of each, the full layer all of it
    contexts = [6750] * 32
    assert c.window_layer_kv_bytes(b, contexts) == 32 * 4096 * 4096
    seen = 32 * 6750 * 4096 + 3 * c.window_layer_kv_bytes(b, contexts)
    assert round(seen / 1e9, 1) == 2.5  # "2.5 GB of cache"
    # the interface's form (no rows) never reads over the row-aware one
    live = 32 * 6750.0
    assert c.attention_step_bytes(b, live) == pytest.approx(
        live * 4096 * (1 + 3 * 4096 / 12272))
    assert c.attention_step_bytes(b, live) < seen
    assert c.decode_step_bytes(b, live, rows=32) == pytest.approx(
        weights + c.attention_step_bytes(b, live))
    assert c.decode_step_bytes(b, live) < c.decode_step_bytes(b, live, 32)
    # a prompt token's arithmetic: 3.2 GFLOP of matrices (one routed
    # expert of its eight is here on average) and attention over what
    # its layers see: some 3.9 GFLOP a token over a 6.6k prompt
    matrices = 4 * (142_606_336 + 524_288 + 201_326_592 + 50_331_648)
    at = c.flops_per_token(b, 3300.0)
    assert at == 2.0 * (matrices + 32768 * 4096) + 4 * 128 * 128 * (
        3300 + 3 * 3300)
    mean = sum(c.flops_per_token(b, float(n)) - 2.0 * 32768 * 4096
               for n in range(1, 6579, 13)) / len(range(1, 6579, 13))
    assert 3.8e9 < mean < 4.0e9
    assert 0.18 < (mean - 2.0 * matrices) / mean < 0.22  # "about a fifth"
    floor = c.expert_gmm_floor(b, decode_calls=80, decode_touched=13.97,
                               prefill_calls=4, prefill_touched=16.0,
                               slots_per_call=300.0)
    assert floor["bytes"] == (80 * 13.97 + 4 * 16.0) * 2 * 50_331_648
    assert floor["flops"] == 84 * 300.0 * 2 * 50_331_648


def test_the_shapes_keep_the_interface_and_import_no_jax():
    import sys

    from dtbench import shapes

    had = "jax" in sys.modules
    c = counts()
    assert had or "jax" not in sys.modules
    for fn in shapes.INTERFACE:
        assert callable(getattr(c, fn))
    for fn in ("sizes", "window_layer_kv_bytes", "expert_gmm_floor",
               "touched_experts"):  # what the waiting readers call
        assert callable(getattr(c, fn))
    assert body()["shapes"] == "benchmarks/shapes/cohere2_moe.py"


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
    assert "reduce_precision" in source
    b = body()
    assert b["reference"]["module"] == os.path.relpath(REFERENCE, ROOT)
    for key in ("layer_types", "sliding_window", "layer_norm_eps",
                "num_experts", "num_experts_published", "experts_held",
                "num_experts_per_tok", "num_shared_experts",
                "intermediate_size", "norm_topk_prob", "rope_theta",
                "tie_word_embeddings", "use_parallel_block", "logit_scale",
                "shared_expert_combination_strategy"):
        assert key in b and f'"{key}"' in source, key


def tiny():
    """(program config, the file keys the reference reads) at the CPU
    tests' size: the tiny preset cut as the cell's share is cut (one
    period of four blocks, experts 0:4 of 8, 256 of 512 rows)."""
    import dataclasses

    from dynamo_tpu.models.config import cut_config, get_config

    cfg = cut_config(dataclasses.replace(get_config("tiny-cohere2-test"),
                                         dtype="float32"),
                     layers=4, experts="0:4", vocab_rows=256)
    kinds = {"W": "sliding_attention", "*": "full_attention"}
    return cfg, {
        "dtype": cfg.dtype, "weight_seed": 0, "hidden_size": cfg.hidden,
        "head_dim": cfg.head_dim, "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "layer_types": [kinds[k] for k in cfg.layer_pattern[::2]],
        "sliding_window": cfg.sliding_window, "layer_norm_eps": cfg.rms_eps,
        "num_experts": 4, "num_experts_published": cfg.n_experts,
        "experts_held": [0, 4],
        "num_experts_per_tok": cfg.n_experts_active,
        "num_shared_experts": cfg.n_shared_experts,
        "intermediate_size": cfg.expert_mlp_hidden, "norm_topk_prob": True,
        "rope_theta": cfg.rope_theta, "tie_word_embeddings": True,
        "use_parallel_block": True, "logit_scale": 1,
        "shared_expert_combination_strategy": "average",
        "vocab_size": cfg.vocab_size}


def test_the_controls_are_the_issues_and_each_moves_the_reference():
    """`act-fp8` is what the harness runs; the five of this architecture's
    own wait under `further_controls`, one axis each, and at a toy size
    every one of the six moves the logits by far more than a rounding."""
    check = body()["check"]
    assert check["controls"] == {"act-fp8": {"act": "fp8"}}
    assert check["further_controls"] == FURTHER
    assert set(check["limits"]) == {"gap_mean"}
    assert 4 <= check["sample"] <= 6
    ref = module(REFERENCE, "cohere2_reference")
    _cfg, file_cfg = tiny()
    rng = np.random.default_rng(0)
    sample = [{"prompt": rng.integers(0, 256, 90).tolist(),
               "served": rng.integers(0, 256, 9).tolist()}]
    sound = ref.logits_for(sample, file_cfg, 128)[0]
    assert sound.shape == (9, 256) and sound.dtype == np.float32
    assert 0.5 < sound.std() < 2.0
    for name, lower in {**check["controls"],
                        **check["further_controls"]}.items():
        low = ref.logits_for(sample, file_cfg, 128, lower)[0]
        assert np.abs(low - sound).max() > 0.1, name
    with pytest.raises(ValueError):  # a key's other values are mistakes
        ref.logits_for(sample, file_cfg, 128, {"norm": "none"})


def test_the_references_blocked_window_equals_one_pass_over_all_keys():
    """A sliding layer scores Q_BLOCK queries against window + block keys
    (a slice of the zero-fronted keys): the same numbers as every key
    under the mask, for a sequence of many blocks and windows."""
    import jax

    ref = module(REFERENCE, "cohere2_reference")
    _cfg, file_cfg = tiny()
    keys = ref.model_keys(file_cfg)
    w = ref.attention_weights(keys[1], file_cfg, 0)
    u = jax.random.normal(jax.random.PRNGKey(3), (128, 64))
    want = ref.attention_mixer(u, w, "sliding_attention", file_cfg, {})
    small = module(REFERENCE, "cohere2_reference_blocked")
    small.Q_BLOCK = 16  # eight blocks, a window of two
    got = small.attention_mixer(u, w, "sliding_attention", file_cfg, {})
    np.testing.assert_allclose(got, want, atol=2e-5)
    unmasked = small.attention_mixer(u, w, "sliding_attention", file_cfg,
                                     {"window": "full"})
    assert np.abs(np.asarray(unmasked) - np.asarray(want)).max() > 1e-2


# -- the cell, the mix and its supply -----------------------------------------


def test_the_cells_entries_keep_the_contract_and_join_the_nine_lists():
    """The configuration and the cell as new entries (found by name), the
    cell on the lists of PR 25's nine per-layer metrics that move
    `out_tok_s`; on no list of a client tail, and no new per-layer
    metric is declared (PERF.md section 7 B0 (b))."""
    b = bench()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE and len(entry["source"]) < 200
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["reduced"] == REDUCED
    assert set(entry["reduced"]) == set(body()["reduced_why"])
    assert len(entry["why"]) <= 200 and set(entry) == {
        "name", "source", "file", "reduced", "why"}
    for said in ("parallel block", "ONE LayerNorm", "window", "128 q heads",
                 "sigmoid", "4 shared", "8 EP chips", "4 of 32 blocks"):
        assert said in entry["why"], said
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": MIX,
                    "chips": 1, "why": cell["why"]}
    for said in ("32 callers", "2048-11264", "128-768", "unshared",
                 "258-page", "1/8", "attention its share"):
        assert said in cell["why"], said
    assert len(cell["why"]) <= 200
    reported = {m["name"] for group in ("end_to_end", "per_layer")
                for m in b[group]
                if "workloads" not in m or CELL in m["workloads"]}
    assert reported == {"out_tok_s", "setup_s", *NINE}
    for m in b["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] == "out_tok_s"
            assert m["workloads"].count(CELL) == 1
    declared_metrics = {m["name"] for m in b["per_layer"]}
    assert not declared_metrics & set(WAITING)
    for name in WAITING:  # their readers wait in the tree
        assert os.path.isfile(os.path.join(BENCH, "layers", name + ".py"))
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 0
    # one cell of this configuration, and no other configuration's file
    assert [w["name"] for w in b["workloads"]
            if w["config"] == CONFIG] == [CELL]
    assert sum(c["file"] == entry["file"] for c in b["configs"]) == 1


def test_the_harness_finds_the_cells_files_by_the_entries_names():
    import run as harness

    declared = os.path.join(ROOT, "BENCHMARK.json")
    plan = harness.Plan(declared, CELL)
    assert plan.config["serve"]["model"] == "command-a-plus-05-2026"
    assert plan.mix["callers"] == "max_batch" and plan.mix["name"] == MIX
    assert plan.shapes.__file__.endswith("shapes/cohere2_moe.py")
    assert plan.reference_module.endswith("references/cohere2_moe.py")
    assert {m["name"] for m in plan.metrics("per_layer")} == set(NINE)
    assert {m["name"] for m in plan.metrics("end_to_end")} == {
        "out_tok_s", "setup_s"}
    flags = plan.worker_flags()
    assert flags[:2] == ["--model", "command-a-plus-05-2026"]
    assert flags[-2:] == ["--prewarm", "full"]
    job = plan.reference_job([])
    assert job["pad_to"] == 12288 and job["module"] == plan.reference_module
    assert set(job["controls"]) == {"act-fp8"}
    assert job["file"]["experts_held"] == [0, 16]
    for name in WAITING:
        assert callable(harness.Plan.reader(name))


def test_the_mix_is_the_issues_and_its_supply_outlasts_both_windows():
    mix, b = load("mixes", MIX + ".json"), bench()
    serve = body()["serve"]
    assert (mix["loop"], mix["callers"], mix["population"]) == (
        "closed", "max_batch", 512)
    assert (mix["ramp_seconds"], mix["start_spread_seconds"]) == (30, 20)
    assert mix["prompt_tokens"] == {"median": 6144, "sigma": 0.5,
                                    "min": 2048, "max": 11264}
    assert mix["output_tokens"] == {"median": 320, "sigma": 0.4,
                                    "min": 128, "max": 768}
    assert mix["max_total_tokens"] == 12272 <= 768 * 16
    pairs = traffic.population(mix, 1)
    prompts = [p for p, _ in pairs]
    answers = [o for _, o in pairs]
    assert round(sum(prompts) / 512) == 6578
    assert round(sum(answers) / 512) == 345
    assert max(p + o for p, o in pairs) <= 12272
    assert (min(prompts), max(prompts)) == (2048, 11264)
    assert (min(answers), max(answers)) == (128, 768)
    # 79% of the prompts run past the window; every context ends past
    # 2,176; a prompt takes 1 to 6 launches of 2,048, 3.7 on average
    assert round(100 * sum(p > 4096 for p in prompts) / 512) == 79
    assert min(p + o for p, o in pairs) > 2176
    launches = [-(-p // 2048) for p in prompts]
    assert (min(launches), max(launches)) == (1, 6)
    assert round(sum(launches) / 512, 1) == 3.7
    # nineteen prompt tokens a token out: prefill is most of the work
    assert round(sum(prompts) / sum(answers)) == 19
    # `run.py` hands a closed loop callers + 12 requests for each second
    # of ramp, window and tail: 992 under --trace 0 and 1,532 under
    # --trace 2, whose 10.1 M prompt ids are drawn before the ramp
    callers = serve["max_batch"]
    ramp = mix["ramp_seconds"]
    handed = {tail: int(callers + 12 * (ramp + b["run_seconds"] + tail))
              for tail in (0.0, 45.0)}
    assert handed == {0.0: callers + 960, 45.0: callers + 1500}
    assert callers != 32 or handed[45.0] == 1532
    assert 10.0e6 < handed[45.0] * sum(prompts) / 512 < 10.2e6
    # at 3 requests closed a second (PERF.md: 2 to 3) the server has
    # asked for callers + 3 x 125 by the end of a --trace 2 tail
    for tail, supply in handed.items():
        asked = callers + 3 * (ramp + b["run_seconds"] + tail)
        assert asked <= 32 + 3 * 125 and asked < supply / 3
    # ids from the held rows of the vocabulary
    reqs = traffic.requests(mix, body()["vocab_size"], 2**31 + 7, 3)
    assert all(0 <= t < 32768 for r in reqs for t in r.prompt)


def test_the_mixs_prefill_shapes_are_the_workers_prewarm_grid():
    """`ModelRunner.prewarm(launches=True)` derives its grid from the
    runner's buckets and token budget, rows x bucket inside the budget
    (`bounds_prefill_launches`: a window group). The mix lists that grid,
    and the table widths its contexts reach are widths the worker
    compiles: 22 programs or so, which is the cell's set-up budget."""
    from dynamo_tpu.engine.model_runner import bucket_table_width

    mix, args = load("mixes", MIX + ".json"), worker_args()
    warm, serve = mix["warm"], body()["serve"]
    buckets = sorted(int(x) for x in args.prefill_buckets.split(","))
    budget = buckets[-1]
    assert (buckets, budget) == ([512, 1024, 2048], 2048)
    grid = {(rows, b) for rows in (1, 2, 4, 8) for b in buckets
            if rows <= budget // buckets[0] and rows * b <= budget}
    assert len(grid) == 6
    assert {tuple(s) for s in warm["prefill_shapes"]} == grid
    cap = serve["max_pages_per_seq"]
    widths, width = [], bucket_table_width(1, cap)
    while True:
        widths.append(width)
        if width >= cap:
            break
        width = bucket_table_width(width + 1, cap)
    assert widths == [8, 16, 32, 64, 128, 256, 512, 768]
    assert len(grid) + 2 * len(widths) + 1 <= 23
    lo = mix["prompt_tokens"]["min"] + 1
    reach = {bucket_table_width(-(-(kv + 16) // 16), cap)
             for kv in range(lo, mix["max_total_tokens"] + 1, 7)}
    assert reach == set(warm["table_widths"]) == {256, 512, 768}
    assert {bucket_table_width(-(-(n + 1 + 16) // 16), cap)
            for n in warm["decode"]} == reach
    assert max(warm["decode"]) + warm["decode_tokens"] <= 12272

    def bucket(n):
        return next(b for b in buckets if n <= b)

    for group in warm["groups"]:
        n = 1 << (len(group) - 1).bit_length()
        assert (n, bucket(max(group))) in grid and sum(group) <= budget
    assert {bucket(n) for n in warm["lone_prefill"]} == set(buckets)


# -- the readers, on made-up numbers -------------------------------------------


def reader_ctx(**more):
    import run as harness

    ctx = {"window": {"before": {}, "after": {}, "t0": 0.0, "seconds": 50.0},
           "config": body(), "shapes": counts(), "stats": stats, **more}
    ctx["read"] = lambda name: harness.Plan.reader(name)(ctx)
    ctx["layer"] = harness.Plan.layer
    return ctx


def test_the_accepted_and_the_waiting_readers_read_this_architectures_counts():
    """The nine declared readers' rooflines and the waiting window and
    expert readers find what they call in this configuration's counts
    and `trace_names`: a window layer's min(context, 4096) keys, the held
    experts' floor, both decode kernels under one pattern."""
    rows = [stats.Timeline(index=i, due=0.0, sent=0.0, n_prompt=n,
                           want_tokens=10, first=1.0, end=None)
            for i, n in enumerate((2500, 5000, 7500, 10000))]
    trace = {"ops": {
        "paged_decode_attention_pool": {"seconds": 0.0010, "count": 20},
        "paged_decode_attention_window": {"seconds": 0.0024, "count": 60},
        "gmm": {"seconds": 0.036, "count": 160},
        "fusion": {"seconds": 0.0806, "count": 900}},
        "modules": {"jit_multi": {"count": 10, "seconds": 0.12}}}
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    ctx = reader_ctx(trace=trace, timelines=rows, peaks=peaks)
    ctx["config"] = dict(body(), serve=dict(body()["serve"], decode_block=2))
    ctx["window"].update(capture_at=10.0, capture_end=12.5)
    c, b = counts(), body()
    live = stats.mean_live_decode_tokens(rows, 10.0, 12.5)
    assert ctx["read"]("decode_step_dev_ms") == pytest.approx(6.0)
    assert ctx["read"]("paged_attn_roofline_pct") == pytest.approx(
        100 * c.attention_step_bytes(b, live) / 819e9 / (0.0034 / 20))
    assert ctx["read"]("decode_hbm_roofline_pct") == pytest.approx(
        100 * c.decode_step_bytes(b, live) / 819e9 / 0.006)
    assert ctx["read"]("moe_dev_share_pct") == pytest.approx(30.0)
    # one window layer a step: 4 rows x min(context, 4096) x 4,096 B
    contexts = [t.n_prompt for t in rows]  # no token has come yet
    assert ctx["read"]("window_attn_roofline_pct") == pytest.approx(
        100 * c.window_layer_kv_bytes(b, contexts) / 819e9 / (0.0024 / 60))
    assert ctx["read"]("window_attn_dev_share_pct") == pytest.approx(2.0)
    names = b["trace_names"]
    assert names["expert_kernels"] == "^(expert_)?gmm"
    assert names["attention_kernels"] == "^paged_decode_attention"
    assert names["window_attention_kernels"] == (
        "^paged_decode_attention_window")
    assert "ssm_decode_kernels" not in names  # no state, no such kernel
    # the window group's share held: 32 rows x 258 pages of 9,279
    ctx["window"]["before"] = {
        "dynamo_kv_window_reserved_page_ms": [({}, 0.0)],
        "dynamo_step_part_ms_total": [({"part": "wall"}, 0.0)]}
    ctx["window"]["after"] = {
        "dynamo_kv_window_reserved_page_ms": [({}, 32 * 258 * 1000.0)],
        "dynamo_step_part_ms_total": [({"part": "wall"}, 1000.0)]}
    assert ctx["read"]("kv_window_reserved_pct") == pytest.approx(
        100 * 32 * 258 / 9280)


# -- one whole run, rehearsed on the CPU ----------------------------------------


def tiny_file() -> dict:
    """A configuration file for `tiny-cohere2-test` cut by the flags the
    cell's is: this architecture's reference, counts and worker flags."""
    _cfg, keys = tiny()
    keys.pop("dtype")
    real = body()
    return {
        **keys, "name": "tiny-cohere2", "source": "the program's preset",
        "max_position_embeddings": 256, "torch_dtype": "bfloat16",
        "serve": {"model": "tiny-cohere2-test", "weight_dtype": "model",
                  "kv_dtype": "model", "page_size": 4, "num_pages": 256,
                  "max_batch": 4, "max_pages_per_seq": 64,
                  "worker_args": ["--serve-layers", "4", "--experts-held",
                                  "0:4", "--vocab-rows", "256",
                                  "--window-pages", "64", "--prewarm",
                                  "full"]},
        "shapes": real["shapes"], "trace_names": real["trace_names"],
        "reference": {"module": real["reference"]["module"],
                      "dtype": "bfloat16", "weights": "model",
                      "weight_seed": 0},
        "check": {"sample": 4, "limits": {"gap_mean": 0.5},
                  "controls": real["check"]["controls"]}}


@pytest.mark.slow
def test_a_rehearsed_run_of_this_architecture(tmp_path):
    """tiny-cohere2-test through the whole harness on the CPU (`--trace
    2`): the worker takes the share's flags, says its block is parallel
    and its norm a LayerNorm, and the window's tokens are checked against
    THIS reference."""
    from test_bench_rehearsal import rehearse

    with open(tmp_path / "case.json", "w") as f:
        json.dump(tiny_file(), f)
    out, line, lines = rehearse(tmp_path, 2, 2**31 + 49,
                                config_file="case.json")
    assert out.returncode == 10, out.stderr[-3000:]
    report = json.loads(lines[-2 - len(line["compared"])])
    assert report["worker_flags"][-10:] == tiny_file()["serve"]["worker_args"]
    assert line["correct"] is True and line["failed"] == 0
    assert "'block': 'parallel'" in out.stderr
    assert "'norm': 'layer'" in out.stderr
    assert "'prefill_attention': 'xla+xla'" in out.stderr
    assert not set(WAITING) & set(line["metrics"])
    assert line["metrics"]["preempts"]["value"] == 0
