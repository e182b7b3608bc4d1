"""The granitemoehybrid configuration (`granite4-h-small-ep2`) and its
cell (`docqa-sat`): the file against the catalog's published keys, the
program's preset and the worker's flags; the counts against the numbers
ISSUE 42's table works out by hand; the reference against its contract
and the program's weights; the mix's lengths and how many of its prompts
run past one launch; the harness resolving every file of the cell by
name; the two waiting readers on made-up scrapes. What
`test_bench_pangu.py` does for `openpangu-718b-ep16`, in a file of this
architecture's own.

Everything here finds the entries BENCHMARK.json gained BY NAME and pins
no position and no count (PERF.md section 7 B0 (b))."""

import ast
import math
import os

import numpy as np
import pytest

from bench_paths import BENCH, ROOT
from dtbench import stats, traffic
from test_bench_contract import bench, load

CONFIG = "granite4-h-small-ep2"
CELL = CONFIG + ".docqa-sat"
REFERENCE = os.path.join(BENCH, "references", "granite_h.py")
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-small/"
          "blob/main/config.json")
REDUCED = ["layer_types", "num_local_experts", "vocab_size",
           "max_position_embeddings"]
WAITING = ("ssm_prefill_continued_pct", "ssm_prefill_launches_per_prompt")
NINE = ("sched_host_share_pct", "window_compiles", "kv_pool_live_pct",
        "preempts", "decode_step_dev_ms", "prefill_dev_share_pct",
        "decode_hbm_roofline_pct", "paged_attn_roofline_pct",
        "device_idle_pct")
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def body():
    return load("configs", CONFIG + ".json")


def module(path, name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def counts():
    return module(os.path.join(BENCH, "shapes", "granite_h.py"), "counts")


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
    import json

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
    assert row["config"]["layer_types"] == PERIOD * 4
    assert b["layer_types"] == row["config"]["layer_types"][:10] == PERIOD


def test_the_file_states_what_the_preset_and_the_flags_run():
    """The reference is built from the file, the server from the preset
    cut by `serve.worker_args`: every size the one reads is the other's,
    and no width differs from the source."""
    from dynamo_tpu.models.config import get_config

    b, cfg = body(), served_config()
    whole = get_config(b["serve"]["model"])
    assert b["source"] == SOURCE and b["model_type"] == "granitemoehybrid"
    assert whole.layer_pattern == ("ME" * 5 + "*E" + "ME" * 4) * 4
    assert (b["num_hidden_layers"], whole.n_layers,
            whole.mixers_per_layer) == (40, 80, 2)
    kinds = {"mamba": "M", "attention": "*"}
    assert cfg.layer_pattern == "".join(
        kinds[t] + "E" for t in b["layer_types"])
    assert (cfg.n_layers, b["served_layers"]) == (20, 10)
    assert (len(cfg.state_layers), len(cfg.kv_layers)) == (9, 1)
    assert (b["hidden_size"], b["num_attention_heads"],
            b["num_key_value_heads"], b["vocab_size"]) == (
        cfg.hidden, cfg.n_q_heads, cfg.n_kv_heads, cfg.vocab_size) == (
        4096, 32, 8, 50176)
    assert cfg.head_dim == b["hidden_size"] // b["num_attention_heads"] == 128
    assert (b["mamba_n_heads"], b["mamba_d_head"], b["mamba_n_groups"],
            b["mamba_d_state"], b["mamba_d_conv"]) == (
        cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups, cfg.ssm_state,
        cfg.conv_kernel) == (128, 64, 1, 128, 4)
    assert cfg.mamba_inner == b["mamba_expand"] * b["hidden_size"] == 8192
    assert cfg.mamba_conv_dim == 8448
    assert b["mamba_conv_bias"] and not b["mamba_proj_bias"]
    assert (b["intermediate_size"], b["shared_intermediate_size"]) == (
        cfg.expert_mlp_hidden, cfg.shared_expert_hidden) == (768, 1536)
    assert (b["num_local_experts_published"], b["num_experts_per_tok"]) == (
        cfg.n_experts, cfg.n_experts_active) == (72, 10)
    assert tuple(b["experts_held"]) == cfg.held_experts == (0, 36)
    assert b["num_local_experts"] == 36
    assert (cfg.moe_scoring, cfg.moe_norm_topk, cfg.moe_routed_scale,
            cfg.mlp_act) == ("softmax", True, 1.0, "swiglu")
    assert b["hidden_act"] == "silu" and cfg.n_shared_experts == 1
    assert (b["embedding_multiplier"], b["residual_multiplier"],
            b["attention_multiplier"], b["logits_scaling"]) == (
        cfg.embedding_multiplier, cfg.residual_multiplier,
        cfg.attention_multiplier, cfg.logits_scaling) == (
        12, 0.22, 0.0078125, 16)
    assert b["attention_multiplier"] == 1 / 128 != 1 / math.sqrt(128)
    assert b["tie_word_embeddings"] is True and cfg.tie_embeddings
    assert b["position_embedding_type"] == "nope" and not cfg.use_rope
    assert b["rms_norm_eps"] == cfg.rms_eps == 1e-5
    assert (b["time_step_min"], b["time_step_max"], b["time_step_floor"]) == (
        cfg.ssm_dt_min, cfg.ssm_dt_max, cfg.ssm_dt_floor)
    assert cfg.ssm_state_dtype == "float32" and cfg.ssm_chunk == 128
    assert not cfg.qk_norm and not cfg.attn_bias and not b["attention_bias"]
    assert b["reference"]["dtype"] == cfg.dtype == b["torch_dtype"]
    assert b["serve"]["weight_dtype"] == b["reference"]["weights"] == "model"
    assert b["serve"]["kv_dtype"] == "model"
    assert b["max_position_embeddings"] == 8192 == (
        b["serve"]["page_size"] * b["serve"]["max_pages_per_seq"])
    assert whole.max_context == b["published"]["max_position_embeddings"]
    assert whole.vocab_size == b["published"]["vocab_size"] == 100352
    assert whole.n_experts == b["published"]["num_local_experts"] == 72
    assert {"expert_width", "router", "ssm_state_dtype", "gated_norm",
            "attention_positions", "mamba_chunk_size", "multipliers",
            "weights"} <= set(b["assumed"])
    assert "2 expert-parallel chips" in b["deployment"]
    assert "HALF" in b["deployment"]


def test_the_workers_flags_are_the_files():
    b, args = body(), worker_args()
    serve = b["serve"]
    assert serve["worker_args"][:6] == [
        "--serve-layers", "10", "--experts-held", "0:36",
        "--vocab-rows", "50176"]
    assert serve["worker_args"][-2:] == ["--prewarm", "full"]
    assert (args.serve_layers, args.experts_held, args.vocab_rows,
            args.prewarm) == (10, "0:36", 50176, "full")
    assert (serve["page_size"], serve["num_pages"],
            serve["max_pages_per_seq"]) == (16, 24576, 512)
    assert serve["max_batch"] in (48, 32)  # 32 only with the analysis shown
    # every row at the longest context the cell serves
    assert serve["max_batch"] * 512 <= serve["num_pages"]
    assert serve["decode_block"] in (8, 4, 2) and "decode_block" in (
        b["serve_why"])
    cell = next(w for w in bench()["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


def test_what_is_not_built_for_the_family_is_refused_at_start():
    from dynamo_tpu.engine.worker import recurrent_state_refusals

    cfg = served_config()
    recurrent_state_refusals(cfg)
    for flags, said in (({"kv_dtype": "int8"}, "--kv-dtype int8"),
                        ({"weight_dtype": "int4"}, "--weight-dtype int4"),
                        ({"devices": 4}, "--tp/--sp/--dp"),
                        ({"spec": True}, "DYNT_SPEC_ENABLE"),
                        ({"mode": "decode"}, "--mode decode"),
                        ({"kvbm": True}, "--kvbm-host-blocks")):
        with pytest.raises(ValueError, match=said):
            recurrent_state_refusals(cfg, **flags)


# -- the counts ---------------------------------------------------------------


def test_the_counts_are_the_ones_worked_out_by_hand():
    """ISSUE 42's table, line by line, and the file's `memory`."""
    c, b = counts(), body()
    p = c.matmul_params(b)
    h = 4096
    mamba = (h * 16768 + (8448 * 4 + 8448) + 384 + 8192 + 8192 * h + h)
    assert 8192 + 8448 + 128 == 16768
    assert p["mamba"] + c.mamba_small_params(b) + h == mamba == 102_291_072
    attention = 2 * h * h + 2 * h * 1024 + h
    assert p["attention"] + h == attention == 41_947_136  # 41.95 M
    assert p["expert"] == 3 * h * 768 == 9_437_184  # 9.437 M each
    assert p["shared"] == 3 * h * 1536 and p["router"] == h * 72
    layer = p["router"] + 36 * p["expert"] + p["shared"] + h
    assert layer == 358_912_000  # 358.91 M
    period = 9 * mamba + attention + 10 * layer
    assert round(period / 1e6, 1) == 4551.7
    tied = 50176 * h + h
    assert p["head"] + h == tied and round(tied / 1e6, 1) == 205.5
    total = c.total_params(b)
    assert total == period + tied == 4_757_211_776
    assert round(total / 1e6) == 4757 and round(total * 2 / 1e9, 2) == 9.51
    # the whole model by the same count: 32.2 B, as published
    whole = (36 * mamba + 4 * attention
             + 40 * (p["router"] + 72 * p["expert"] + p["shared"] + h)
             + 100352 * h + h)
    assert round(whole / 1e9, 1) == 32.2
    z = c.sizes(b)
    assert (z["layers"], z["n_m"], z["n_a"], z["n_e"], z["held"],
            z["published"], z["k"]) == (10, 9, 1, 10, 36, 72, 10)
    # state a slot: 9 x (4.19 MB float32 + 50.7 KB conv) = 38.2 MB
    assert c.ssm_state_bytes_per_row(b) == 9 * 128 * 64 * 128 * 4
    assert c.conv_state_bytes_per_row(b) == 9 * 3 * 8448 * 2
    assert c.state_bytes_per_row(b) == 9 * 4_244_992 == 38_204_928
    # KV a token: one attention layer x 2 x 8 x 128 x 2 B
    assert c.kv_bytes_per_token(b) == 4096
    # more state than KV at any context under 9,300 tokens
    assert 9300 < c.state_bytes_per_row(b) / c.kv_bytes_per_token(b) < 9400
    serve = b["serve"]
    state = 48 * c.state_bytes_per_row(b)
    pool = serve["num_pages"] * serve["page_size"] * c.kv_bytes_per_token(b)
    assert round(state / 1e9, 2) == 1.83 and round(pool / 1e9, 2) == 1.61
    before = total * 2 + state + pool
    assert round(before / 1e9, 2) == 12.96 and 0.80 < before / 16e9 < 0.82
    for said in ("102.29 M", "41.95 M", "358.91 M", "4,551.7 M", "205.5 M",
                 "9.51 GB", "38.2 MB", "4,096 B", "1.83 GB", "1.61 GB",
                 "12.95 GB", "81%"):
        assert said in b["memory"], said
    # an expert in a full decode step: 48 x 10 / 72 = 6.7 tokens
    assert 48 * 10 / 72 == pytest.approx(6.67, abs=0.01)
    assert c.touched_experts(b, 48) == pytest.approx(35.97, abs=0.01)
    assert c.touched_experts(b, 1) == pytest.approx(5.0)
    # a decode step: every matrix but the experts missed, the tied one once
    dense = 9 * p["mamba"] + p["attention"] + 10 * (
        p["shared"] + p["router"]) + p["head"]
    assert c.weight_bytes_per_step(b, 48) == pytest.approx(
        2.0 * (dense + 10 * 35.9725 * p["expert"]), rel=1e-5)
    assert c.weight_bytes_per_step(b, 48) < total * 2
    # never over: without rows, the fewest rows the live tokens can be
    live = 150_000.0
    assert c.attention_step_bytes(b, live) == live * 4096
    assert c.decode_step_bytes(b, live) == pytest.approx(
        c.weight_bytes_per_step(b, live / 8176)
        + live / 8176 * 2 * 38_204_928 + live * 4096)
    assert c.decode_step_bytes(b, live) < c.decode_step_bytes(b, live, 40)
    # the state kernels of a step: read and written once a live row
    assert c.ssm_decode_kernel_bytes(b, 40) == 40 * 2 * 9 * 4_194_304
    # a prompt token at half the mix's mean prompt: 3.75 GFLOP
    per_token = c.flops_per_token(b, 1757)
    experts = 10 * 36 / 72 * p["expert"]
    matrices = (9 * p["mamba"] + p["attention"]
                + 10 * (experts + p["shared"] + p["router"]) + p["head"])
    assert per_token == pytest.approx(
        2 * matrices + 4 * 32 * 128 * 1757 + 9 * 6 * 128 * 64 * 128)
    assert per_token == pytest.approx(3.75e9, rel=5e-3)
    floor = c.expert_gmm_floor(b, 8, 30.0, 1, 36, 400)
    assert floor["bytes"] == (8 * 30.0 + 36) * 2 * p["expert"]
    assert floor["flops"] == 9 * 400 * 2 * p["expert"]


def test_the_shapes_keep_the_interface_and_import_no_jax():
    import sys

    from dtbench import shapes

    had = "jax" in sys.modules
    c = counts()
    assert had or "jax" not in sys.modules
    for fn in shapes.INTERFACE:
        assert callable(getattr(c, fn))
    assert body()["shapes"] == "benchmarks/shapes/granite_h.py"


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
    assert "jax.lax.scan(step" in source  # position by position
    assert body()["reference"]["module"] == os.path.relpath(REFERENCE, ROOT)
    b = body()
    for key in ("layer_types", "experts_held", "num_local_experts_published",
                "embedding_multiplier", "residual_multiplier",
                "attention_multiplier", "logits_scaling",
                "tie_word_embeddings", "shared_intermediate_size",
                "time_step_floor"):
        assert key in b and f'"{key}"' in source, key


def tiny():
    """(program config, the file keys the reference reads) at the CPU
    tests' size: the tiny preset with experts 2:6 held, 384 rows."""
    import dataclasses

    from dynamo_tpu.models.config import cut_config, get_config

    cfg = cut_config(dataclasses.replace(get_config("tiny-granite-test"),
                                         dtype="float32"),
                     layers=10, experts="2:6", vocab_rows=384)
    kinds = {"M": "mamba", "*": "attention"}
    return cfg, {
        "dtype": cfg.dtype, "weight_seed": 0, "hidden_size": cfg.hidden,
        "layer_types": [kinds[k] for k in cfg.layer_pattern[::2]],
        "mamba_n_heads": cfg.mamba_heads, "mamba_d_head": cfg.mamba_head_dim,
        "mamba_n_groups": cfg.ssm_groups, "mamba_d_state": cfg.ssm_state,
        "mamba_d_conv": cfg.conv_kernel,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.expert_mlp_hidden,
        "shared_intermediate_size": cfg.shared_expert_hidden,
        "num_local_experts": 4,
        "num_local_experts_published": cfg.n_experts,
        "experts_held": list(cfg.held_experts),
        "num_experts_per_tok": cfg.n_experts_active,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attention_multiplier,
        "logits_scaling": cfg.logits_scaling,
        "tie_word_embeddings": True, "rms_norm_eps": cfg.rms_eps,
        "time_step_min": cfg.ssm_dt_min, "time_step_max": cfg.ssm_dt_max,
        "time_step_floor": cfg.ssm_dt_floor, "vocab_size": cfg.vocab_size}


def test_the_programs_seeded_weights_are_the_references_recipe():
    """Same seed, same numbers, each from its own code: every leaf of the
    program's random tree against the reference's recipe (the program
    keeps a SwiGLU's gate and up as one matrix, an expert's output-major;
    the held experts are the PUBLISHED indices 2..5; the residual
    writers' gain grows with the mixer's index; the tied matrix is drawn
    once, 16 / sqrt(h) wide, and there is no `lm_head`). Drawn as the
    runner draws them too: one program a layer kind, the gain an operand."""
    import jax

    from dynamo_tpu.models.hybrid import branch_gain, score_gain
    from dynamo_tpu.models.transformer import init_params

    ref = module(REFERENCE, "granite_h_reference")
    cfg, file_cfg = tiny()
    assert cfg.layer_pattern == "MEMEMEMEME*EMEMEMEME"
    params = init_params(jax.random.PRNGKey(0), cfg)
    keys = ref.model_keys(file_cfg)
    assert len(keys) == cfg.n_layers + 2 == 22
    assert ref.mixers(file_cfg) == [
        k for t in file_cfg["layer_types"] for k in (t, "experts")]
    assert set(params) == {"embed", "final_norm", "layers"}
    np.testing.assert_array_equal(ref.embedding(keys[0], file_cfg),
                                  params["embed"])
    assert params["embed"].shape == (384, 64)
    assert np.std(np.asarray(params["embed"])) == pytest.approx(
        16 / 8, rel=0.02)
    for i, (kind, lp) in enumerate(zip(ref.mixers(file_cfg),
                                       params["layers"])):
        assert np.all(np.asarray(lp["norm"]) == 1)
        assert ref.branch_gain(file_cfg, i) == branch_gain(cfg, i)
        want = ref.KINDS[kind][0](keys[i + 1], file_cfg, i)
        if kind == "mamba":
            assert set(lp) == {"norm", "in_proj", "conv_w", "conv_b",
                               "dt_bias", "a_log", "d_skip", "ssm_norm",
                               "out_proj"}
            for name in ("in_proj", "conv_w", "conv_b", "dt_bias",
                         "out_proj"):
                np.testing.assert_array_equal(want[name], lp[name])
            np.testing.assert_allclose(want["a"], -np.exp(lp["a_log"]),
                                       rtol=1e-6)
            assert np.all(np.asarray(lp["d_skip"]) == 1)
        elif kind == "attention":
            assert set(lp) == {"norm", "wq", "wk", "wv", "wo"}
            for name in ("wq", "wk", "wv", "wo"):
                np.testing.assert_array_equal(want[name], lp[name])
        else:
            assert set(lp) == {"norm", "router", "e_up", "e_down", "s_up",
                               "s_down"}  # a softmax router: no e_bias
            np.testing.assert_array_equal(want["router"], lp["router"])
            assert lp["router"].shape == (64, 8)
            np.testing.assert_array_equal(want["down"], lp["e_down"])
            fused = np.concatenate([np.swapaxes(want["gate"], 1, 2),
                                    np.swapaxes(want["up"], 1, 2)], axis=1)
            np.testing.assert_array_equal(fused, lp["e_up"])
            assert lp["e_up"].shape == (4, 2 * 48, 64)
            np.testing.assert_array_equal(
                np.concatenate([want["s_gate"], want["s_up"]], axis=1),
                lp["s_up"])
            np.testing.assert_array_equal(want["s_down"], lp["s_down"])
    # the gains at the published sizes, as the file's `assumed` says them
    b = body()
    assert ref.branch_gain(b, 0) == pytest.approx(3.0 / 0.22)
    assert ref.branch_gain(b, 19) == pytest.approx(3.0 / 0.22 * 1.23 ** 19)
    assert ref.score_gain(b) == pytest.approx(3.36, abs=0.01)
    assert score_gain(served_config()) == ref.score_gain(b)
    assert "3.36" in b["assumed"]["weights"]


def test_the_runner_draws_the_same_tree_a_layer_kind_a_program():
    import dataclasses

    import jax

    from dynamo_tpu.engine import ModelRunner, RunnerConfig
    from dynamo_tpu.models.config import get_config
    from dynamo_tpu.models.transformer import init_params
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    cfg = dataclasses.replace(get_config("tiny-granite-test"),
                              dtype="float32")
    runner = ModelRunner(cfg, RunnerConfig(page_size=4, num_pages=16,
                                           max_batch=2, max_pages_per_seq=8,
                                           prefill_buckets=(16,)),
                         make_mesh(MeshConfig()), seed=0)
    want = init_params(jax.random.PRNGKey(0), cfg)
    # a compiled draw rounds a product's last bit otherwise than an eager
    # one (1e-7 relative); a gain off by a mixer would read 23%
    for got, leaf in zip(jax.tree.leaves(runner.params),
                         jax.tree.leaves(want)):
        scale = float(np.abs(np.asarray(leaf)).max())
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(leaf) / scale, atol=1e-6)


def test_the_controls_are_the_issues_and_one_axis_each():
    check = body()["check"]
    assert set(check["controls"]) == {"act-fp8"}
    assert list(check["further_controls"]) == [
        "residual-one", "attn-scale-rsqrt", "embed-unscaled",
        "router-sigmoid", "state-bf16", "kv-int8"]
    assert set(check["limits"]) == {"gap_mean"}
    for lower in {**check["controls"], **check["further_controls"]}.values():
        assert len(lower) == 1  # one axis at a time
    ref = module(REFERENCE, "granite_h_reference")
    _cfg, file_cfg = tiny()
    rng = np.random.default_rng(0)
    sample = [{"prompt": rng.integers(0, 384, 90).tolist(),
               "served": rng.integers(0, 384, 9).tolist()}]
    sound = ref.logits_for(sample, file_cfg, 128)[0]
    assert sound.shape == (9, 384) and sound.dtype == np.float32
    assert 0.5 < sound.std() < 2.0
    for name, lower in {**check["controls"],
                        **check["further_controls"]}.items():
        low = ref.logits_for(sample, file_cfg, 128, lower)[0]
        assert np.abs(low - sound).max() > 1e-3, name


# -- the cell, the mix and its supply -----------------------------------------


def test_the_cells_entries_keep_the_contract_and_join_the_nine_lists():
    """The configuration and the cell as new entries (found by name), the
    cell on the lists of PR 25's nine per-layer metrics that move
    `out_tok_s`; on no list of a client tail, and none of this PR's
    readers is declared (PERF.md section 7 B0 (b))."""
    b = bench()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE and len(entry["source"]) < 200
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["reduced"] == REDUCED
    assert set(entry["reduced"]) == set(body()["reduced_why"])
    assert len(entry["why"]) <= 200 and set(entry) == {
        "name", "source", "file", "reduced", "why"}
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "docqa-sat",
                    "chips": 1, "why": cell["why"]}
    assert "48 callers" in cell["why"] and len(cell["why"]) <= 200
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


def test_the_harness_finds_the_cells_files_by_the_entries_names():
    import run as harness

    declared = os.path.join(ROOT, "BENCHMARK.json")
    plan = harness.Plan(declared, CELL)
    assert plan.config["serve"]["model"] == "granite-4.0-h-small"
    assert plan.mix["callers"] == "max_batch" and plan.mix["name"] == (
        "docqa-sat")
    assert plan.shapes.__file__.endswith("shapes/granite_h.py")
    assert plan.reference_module.endswith("references/granite_h.py")
    assert {m["name"] for m in plan.metrics("per_layer")} == set(NINE)
    assert {m["name"] for m in plan.metrics("end_to_end")} == {
        "out_tok_s", "setup_s"}
    flags = plan.worker_flags()
    assert flags[:2] == ["--model", "granite-4.0-h-small"]
    assert flags[-2:] == ["--prewarm", "full"]
    job = plan.reference_job([])
    assert job["pad_to"] == 8192 and job["module"] == plan.reference_module
    assert set(job["controls"]) == {"act-fp8"}
    for name in WAITING:
        assert callable(harness.Plan.reader(name))


def test_the_mix_is_the_issues_and_three_prompts_in_four_pass_one_launch():
    mix, b = load("mixes", "docqa-sat.json"), bench()
    assert (mix["loop"], mix["callers"], mix["population"]) == (
        "closed", "max_batch", 512)
    assert mix["prompt_tokens"] == {"median": 3072, "sigma": 0.6,
                                    "min": 512, "max": 7680}
    assert mix["output_tokens"] == {"median": 160, "sigma": 0.4,
                                    "min": 64, "max": 448}
    assert mix["max_total_tokens"] == 8176 <= 512 * 16
    assert (mix["ramp_seconds"], mix["start_spread_seconds"]) == (30, 20)
    pairs = traffic.population(mix, 1)
    prompts = [p for p, _ in pairs]
    answers = [o for _, o in pairs]
    assert 3450 < sum(prompts) / 512 < 3650  # "about 3,600"
    assert 168 < sum(answers) / 512 < 178  # "about 173"
    assert max(p + o for p, o in pairs) <= 8176
    assert min(prompts) == 512 and max(prompts) == 7680
    budget = 2048
    assert sum(p > budget for p in prompts) / 512 == pytest.approx(0.75,
                                                                   abs=0.01)
    assert sum(p > 4600 for p in prompts) / 512 == pytest.approx(0.25,
                                                                 abs=0.01)
    # twenty prompt tokens for every token out
    assert 19 < sum(prompts) / sum(answers) < 21.5
    # a prompt takes 2.2 launches of the budget; 45.7% of the scanned
    # positions lie behind a first launch of 2,048
    launches = sum(-(-p // budget) for p in prompts) / 512
    assert launches == pytest.approx(2.19, abs=0.01)
    carried = sum(max(0, p - budget) for p in prompts) / sum(prompts)
    assert carried == pytest.approx(0.457, abs=0.002)
    # the supply outlasts the window and the tail at 4 requests a second
    ramp = mix["ramp_seconds"]
    for span in (ramp + b["run_seconds"], ramp + b["run_seconds"] + 45.0):
        assert (48 + 4 * span) * 1.25 < 48 + 12 * span


def test_the_mixs_prefill_shapes_are_the_workers_prewarm_grid():
    """`ModelRunner.prewarm(launches=True)` derives its grid from the
    runner's buckets and token budget, rows x bucket inside the budget
    (`bounds_prefill_launches`: Mamba layers and contexts past one
    launch). The mix lists that grid, and the table widths its contexts
    reach are widths the worker compiles."""
    from dynamo_tpu.engine.model_runner import bucket_table_width

    mix, args = load("mixes", "docqa-sat.json"), worker_args()
    warm, serve = mix["warm"], body()["serve"]
    buckets = sorted(int(x) for x in args.prefill_buckets.split(","))
    budget = buckets[-1]
    assert (buckets, budget) == ([512, 1024, 2048], 2048)
    assert budget < serve["page_size"] * serve["max_pages_per_seq"]
    grid = {(rows, b) for rows in (1, 2, 4, 8) for b in buckets
            if rows <= budget // buckets[0] and rows * b <= budget}
    assert len(grid) == 6
    assert {tuple(s) for s in warm["prefill_shapes"]} == grid
    cap = serve["max_pages_per_seq"]
    lo = mix["prompt_tokens"]["min"] + 1
    reach = {bucket_table_width(-(-(kv + 16) // 16), cap)
             for kv in range(lo, mix["max_total_tokens"] + 1)}
    assert reach == set(warm["table_widths"]) == {64, 128, 256, 512}
    assert {bucket_table_width(-(-(n + 1 + 16) // 16), cap)
            for n in warm["decode"]} == reach
    assert max(warm["decode"]) + warm["decode_tokens"] <= 8176

    def bucket(n):
        return next(b for b in buckets if n <= b)

    for group in warm["groups"]:
        n = 1 << (len(group) - 1).bit_length()
        assert (n, bucket(max(group))) in grid and sum(group) <= budget
    assert {bucket(n) for n in warm["lone_prefill"]} == set(buckets)


# -- the waiting readers, on made-up scrapes --------------------------------------


def scrape(fresh_pos, cont_pos, fresh_rows, cont_rows):
    w = {"worker": "w"}
    return {
        "dynamo_ssm_prefill_positions_total": [
            ({**w, "carry": "fresh"}, fresh_pos),
            ({**w, "carry": "continued"}, cont_pos)],
        "dynamo_ssm_prefill_launch_rows_total": [
            ({**w, "carry": "fresh"}, fresh_rows),
            ({**w, "carry": "continued"}, cont_rows)],
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
    """100 requests began prefilling in the window and took 219 launch
    rows; 351,400 prompt tokens x 9 layers were scanned, 45.7% of them on
    a carried state."""
    before = scrape(9 * 1000.0, 9 * 500.0, 7.0, 3.0)
    after = scrape(9 * (1000.0 + 190_800), 9 * (500.0 + 160_600),
                   7.0 + 100, 3.0 + 119)
    ctx = reader_ctx(before, after)
    assert ctx["read"]("ssm_prefill_continued_pct") == pytest.approx(
        100 * 160_600 / 351_400)
    assert ctx["read"]("ssm_prefill_launches_per_prompt") == pytest.approx(
        2.19)
    # the parent, or a model without Mamba layers: nothing, not an error
    for name in WAITING:
        assert reader_ctx({}, {})["read"](name) is None
        assert reader_ctx(before, before)["read"](name) is None
    # every prompt in one launch: nothing continued
    one = reader_ctx(scrape(0.0, 0.0, 0.0, 0.0), scrape(900.0, 0.0, 4.0, 0.0))
    assert one["read"]("ssm_prefill_continued_pct") == 0.0
    assert one["read"]("ssm_prefill_launches_per_prompt") == 1.0


def test_pr30s_readers_read_this_architectures_counts():
    """The eight readers of PR 30 find what they call in this
    configuration's counts and `trace_names`: four rows decoding through
    the capture, 9 state kernels a step."""
    rows = [stats.Timeline(index=i, due=0.0, sent=0.0, n_prompt=n,
                           want_tokens=10, first=1.0, end=None)
            for i, n in enumerate((600, 3000, 5000, 7000))]
    trace = {"ops": {
        "ssm_state_update": {"seconds": 0.0036, "count": 90},
        "gmm": {"seconds": 0.004, "count": 40},
        "fusion": {"seconds": 0.0324, "count": 900}}}
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    ctx = reader_ctx({}, {}, trace=trace, timelines=rows, peaks=peaks)
    ctx["window"].update(capture_at=10.0, capture_end=12.5)
    # one Mamba layer of one step: 4 rows x 2 x 4.19 MB over 819 GB/s
    least = 4 * 2 * 4_194_304 / 819e9
    assert ctx["read"]("ssm_decode_roofline_pct") == pytest.approx(
        100 * least / (0.0036 / 90))
    assert ctx["read"]("ssm_dev_share_pct") == pytest.approx(9.0)
    names = body()["trace_names"]
    assert names["ssm_decode_kernels"] == "^ssm_state_update"
    assert names["expert_kernels"] == "^(expert_)?gmm"
    assert names["attention_kernels"] == "^paged_decode_attention"
