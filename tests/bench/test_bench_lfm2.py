"""The lfm2_moe configuration (`lfm2-8b-a1b-pp2`) and its cell
(`draft-sat`): the file against the catalog's published keys, the
program's preset and the worker's flags; the counts against the numbers
ISSUE 44's table works out by hand; the reference against its contract
and the program's weights; the mix's lengths and its supply arithmetic;
the harness resolving every file of the cell by name; the waiting reader
`conv_dev_share_pct` on made-up numbers. What `test_bench_granite_h.py`
does for `granite4-h-small-ep2`, in a file of this architecture's own.

Everything here finds the entries BENCHMARK.json gained BY NAME and pins
no position and no count (PERF.md section 7 B0 (b))."""

import ast
import json
import math
import os

import numpy as np
import pytest

from bench_paths import BENCH, ROOT
from dtbench import stats, traffic
from test_bench_contract import bench, load

CONFIG = "lfm2-8b-a1b-pp2"
CELL = CONFIG + ".draft-sat"
REFERENCE = os.path.join(BENCH, "references", "lfm2.py")
SOURCE = "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
REDUCED = ["layer_types", "max_position_embeddings"]
WAITING = ("conv_dev_share_pct",)
NINE = ("sched_host_share_pct", "window_compiles", "kv_pool_live_pct",
        "preempts", "decode_step_dev_ms", "prefill_dev_share_pct",
        "decode_hbm_roofline_pct", "paged_attn_roofline_pct",
        "device_idle_pct")
PERIOD = ["conv", "conv", "full_attention", "conv"]
PUBLISHED = PERIOD * 5 + ["conv", "full_attention", "conv", "conv"]


def body():
    return load("configs", CONFIG + ".json")


def module(path, name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def counts():
    return module(os.path.join(BENCH, "shapes", "lfm2.py"), "counts")


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
    the two keys `reduced` lists."""
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
    assert row["config"]["layer_types"] == PUBLISHED
    assert b["layer_types"] == PUBLISHED[:12] == PERIOD * 3
    assert "tie_word_embeddings" not in row["config"]  # `assumed` says so


def test_the_file_states_what_the_preset_and_the_flags_run():
    """The reference is built from the file, the server from the preset
    cut by `serve.worker_args`: every size the one reads is the other's,
    and no width differs from the source."""
    from dynamo_tpu.models.config import get_config

    b, cfg = body(), served_config()
    whole = get_config(b["serve"]["model"])
    assert b["source"] == SOURCE and b["model_type"] == "lfm2_moe"
    kinds = {"conv": "C", "full_attention": "*"}
    assert whole.layer_pattern == "".join(
        kinds[t] + ("D" if i < 2 else "E") for i, t in enumerate(PUBLISHED))
    assert (b["num_hidden_layers"], whole.n_layers,
            whole.mixers_per_layer) == (24, 48, 2)
    assert cfg.layer_pattern == "".join(
        kinds[t] + ("D" if i < b["num_dense_layers"] else "E")
        for i, t in enumerate(b["layer_types"]))
    assert cfg.layer_pattern == "CDCD*ECECECE*ECECECE*ECE"
    assert (cfg.n_layers, b["served_layers"]) == (24, 12)
    assert (len(cfg.state_layers), len(cfg.ssm_layers),
            len(cfg.kv_layers)) == (9, 0, 3)
    assert (b["hidden_size"], b["num_attention_heads"],
            b["num_key_value_heads"], b["vocab_size"]) == (
        cfg.hidden, cfg.n_q_heads, cfg.n_kv_heads, cfg.vocab_size) == (
        2048, 32, 8, 65536)
    assert cfg.head_dim == b["hidden_size"] // b["num_attention_heads"] == 64
    assert (b["conv_L_cache"], b["conv_bias"]) == (cfg.conv_kernel, False)
    assert cfg.conv_kernel == 3
    assert (b["intermediate_size"], b["moe_intermediate_size"]) == (
        cfg.mlp_hidden, cfg.expert_mlp_hidden) == (7168, 1792)
    assert (b["num_experts"], b["num_experts_per_tok"]) == (
        cfg.n_experts, cfg.n_experts_active) == (32, 4)
    assert cfg.held_experts == (0, 32) and "experts_held" not in b
    assert (cfg.moe_scoring, cfg.moe_selection_bias, cfg.moe_norm_topk,
            cfg.moe_routed_scale, cfg.moe_renorm_eps, cfg.mlp_act) == (
        "sigmoid", b["use_expert_bias"], b["norm_topk_prob"],
        b["routed_scaling_factor"], 1e-6, "swiglu")
    assert cfg.n_shared_experts == 0 and not cfg.shared_expert_hidden
    assert (cfg.moe_n_group, cfg.moe_topk_group) == (1, 1)
    assert b["tie_word_embeddings"] is True and cfg.tie_embeddings
    assert cfg.qk_norm and cfg.use_rope and not cfg.rope_yarn_factor
    assert b["rope_theta"] == cfg.rope_theta == 1e6
    assert b["norm_eps"] == cfg.rms_eps == 1e-5
    assert not cfg.multipliers and not cfg.attn_bias
    assert b["reference"]["dtype"] == cfg.dtype == b["torch_dtype"]
    assert b["serve"]["weight_dtype"] == b["reference"]["weights"] == "model"
    assert b["serve"]["kv_dtype"] == "model"
    assert b["max_position_embeddings"] == 3072 == (
        b["serve"]["page_size"] * b["serve"]["max_pages_per_seq"])
    assert whole.max_context == b["published"]["max_position_embeddings"]
    assert whole.vocab_size == b["vocab_size"]  # the whole vocabulary
    assert {"tie_word_embeddings", "in_proj_thirds", "intermediate_size",
            "router", "rope", "qk_norm", "conv_state", "kv_layout",
            "weights"} <= set(b["assumed"])
    assert "two pipeline stages" in b["deployment"]
    assert "all 32 experts" in b["deployment"]
    assert "half as long" in b["deployment"]


def test_the_workers_flags_are_the_files():
    b, args = body(), worker_args()
    serve = b["serve"]
    assert serve["worker_args"][:2] == ["--serve-layers", "12"]
    assert serve["worker_args"][-2:] == ["--prewarm", "full"]
    assert (args.serve_layers, args.experts_held, args.vocab_rows,
            args.prewarm) == (12, None, None, "full")
    assert (serve["page_size"], serve["max_pages_per_seq"]) == (16, 192)
    # ISSUE 44's sizes, or its one stated fallback with the evidence
    assert (serve["max_batch"], serve["num_pages"]) in ((256, 49152),
                                                        (128, 24576))
    # every row at the longest context the cell serves
    assert serve["max_batch"] * 192 == serve["num_pages"]
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
    """ISSUE 44's table, line by line, and the file's `memory`."""
    c, b = counts(), body()
    p = c.matmul_params(b)
    h = 2048
    # a conv mixer: W_in 2048 x 6144, 3 taps x 2048, W_out 2048 x 2048,
    # the block norm's 2048
    assert p["conv"] == 2048 * 6144 + 2048 * 2048
    assert p["conv"] + c.conv_small_params(b) + h == 16_785_408
    # an attention mixer: wq, wo 2048 x 2048, wk, wv 2048 x 512, the q
    # and k gains 2 x 64, the norm
    assert p["attention"] == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert p["attention"] + c.attention_small_params(b) + h == 10_487_936
    assert p["dense"] + h == 3 * 2048 * 7168 + 2048 == 44_042_240
    assert p["expert"] == 3 * 2048 * 1792 == 11_010_048
    assert c.expert_layer_params(b) == (32 * 11_010_048 + 2048 * 32 + 32
                                        + 2048) == 352_389_152
    assert p["head"] + h == 65536 * 2048 + 2048 == 134_219_776
    z = c.sizes(b)
    assert (z["n_c"], z["n_a"], z["n_d"], z["n_e"]) == (9, 3, 2, 10)
    total = c.total_params(b)
    assert total == (9 * 16_785_408 + 3 * 10_487_936 + 2 * 44_042_240
                     + 10 * 352_389_152 + 134_219_776) == 3_928_728_256
    assert round(total * 2 / 1e9, 2) == 7.86
    whole = dict(b, layer_types=PUBLISHED)
    assert round(c.total_params(whole) / 1e9, 2) == 8.34  # published 8.3 B
    assert round(c.active_params(whole) / 1e9, 2) == 1.56  # published 1.5 B
    # KV: 3 attention layers x K and V x 8 heads x 64 x bf16
    assert c.kv_bytes_per_token(b) == 3 * 2 * 8 * 64 * 2 == 6144
    pool = b["serve"]["num_pages"] * 16 * 6144
    assert round(pool / 1e9, 2) == 4.83 and 16 * 6144 == 98_304
    # the carries: 9 mixers x 2 x 2048 x bf16 a slot; nothing else
    assert c.state_bytes_per_row(b) == c.conv_state_bytes_per_row(b) == 73_728
    state = b["serve"]["max_batch"] * 73_728
    assert round(state / 1e6, 1) == 18.9
    held = total * 2 + pool + state
    assert round(held / 1e9, 2) == 12.71 and round(held / 16e9, 2) == 0.79
    for said in ("16,785,408", "10,487,936", "44,042,240", "352,389,152",
                 "134,219,776", "3,928.7 M", "7.86 GB", "6,144 B",
                 "4.83 GB", "73,728 B", "12.71 GB", "79%"):
        assert said in b["memory"], said
    # a decode step at 256 live rows of mean context 1,030
    assert round(c.touched_experts(b, 256), 6) == 32.0
    step = c.decode_step_bytes(b, 256 * 1030, rows=256)
    assert round(step / 1e9, 1) == 9.5
    assert round(step / 819e9 * 1e3, 1) == 11.6
    # without rows (the interface's form) the same to a third of a percent
    assert 0.997 < c.decode_step_bytes(b, 256 * 1030) / step < 1.0
    assert c.attention_step_bytes(b, 1000.0) == 6_144_000
    # a token's arithmetic: 2 x the parameters it passes through (the
    # stage's 0.93 B) and attention over its context
    at = c.flops_per_token(b, 1030.0)
    active = c.active_params(b) - 2048 * 25 - 9 * 3 * 2048 - 3 * 128 - 320
    assert at == pytest.approx(2.0 * active + 3 * 4 * 32 * 64 * 1030
                               + 9 * 8 * 2048, rel=1e-3)
    floor = c.expert_gmm_floor(b, decode_calls=80, decode_touched=32.0,
                               prefill_calls=0, prefill_touched=0.0,
                               slots_per_call=1024.0)
    assert floor["bytes"] == 80 * 32 * 2 * 11_010_048
    assert floor["flops"] == 80 * 1024 * 2 * 11_010_048


def test_the_shapes_keep_the_interface_and_import_no_jax():
    import sys

    from dtbench import shapes

    had = "jax" in sys.modules
    c = counts()
    assert had or "jax" not in sys.modules
    for fn in shapes.INTERFACE:
        assert callable(getattr(c, fn))
    assert body()["shapes"] == "benchmarks/shapes/lfm2.py"


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
    b = body()
    for key in ("layer_types", "conv_L_cache", "num_dense_layers",
                "intermediate_size", "moe_intermediate_size", "num_experts",
                "num_experts_per_tok", "use_expert_bias", "norm_topk_prob",
                "routed_scaling_factor", "rope_theta", "norm_eps",
                "tie_word_embeddings"):
        assert key in b and f'"{key}"' in source, key


def tiny():
    """(program config, the file keys the reference reads) at the CPU
    tests' size: the tiny preset cut to its first six blocks, as the
    cell's stage is cut (both dense blocks, four expert blocks)."""
    import dataclasses

    from dynamo_tpu.models.config import cut_config, get_config

    cfg = cut_config(dataclasses.replace(get_config("tiny-lfm2-test"),
                                         dtype="float32"), layers=6)
    kinds = {"C": "conv", "*": "full_attention"}
    return cfg, {
        "dtype": cfg.dtype, "weight_seed": 0, "hidden_size": cfg.hidden,
        "layer_types": [kinds[k] for k in cfg.layer_pattern[::2]],
        "conv_L_cache": cfg.conv_kernel,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "num_dense_layers": 2, "intermediate_size": cfg.mlp_hidden,
        "moe_intermediate_size": cfg.expert_mlp_hidden,
        "num_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.n_experts_active,
        "use_expert_bias": True, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "rope_theta": cfg.rope_theta,
        "norm_eps": cfg.rms_eps, "tie_word_embeddings": True,
        "vocab_size": cfg.vocab_size}


def test_the_programs_seeded_weights_are_the_references_recipe():
    """Same seed, same numbers, each from its own code: every leaf of the
    program's random tree against the reference's recipe (the program
    keeps a SwiGLU's gate and up as one matrix, an expert's output-major;
    the residual writers' gain grows with the mixer's index; the tied
    matrix is drawn once, 1 / sqrt(h) wide, and there is no `lm_head`;
    the selection bias is float32)."""
    import jax

    from dynamo_tpu.models.hybrid import branch_gain, score_gain
    from dynamo_tpu.models.transformer import init_params

    ref = module(REFERENCE, "lfm2_reference")
    cfg, file_cfg = tiny()
    assert cfg.layer_pattern == "CDCD*ECECECE"
    params = init_params(jax.random.PRNGKey(0), cfg)
    keys = ref.model_keys(file_cfg)
    assert len(keys) == cfg.n_layers + 2 == 14
    assert ref.mixers(file_cfg) == ["conv", "dense", "conv", "dense",
                                    "full_attention", "experts", "conv",
                                    "experts", "conv", "experts", "conv",
                                    "experts"]
    assert set(params) == {"embed", "final_norm", "layers"}
    np.testing.assert_array_equal(ref.embedding(keys[0], file_cfg),
                                  params["embed"])
    assert np.std(np.asarray(params["embed"])) == pytest.approx(
        1 / 16, rel=0.02)
    # rounded to bf16 as `astype` rounds (the reference rounds by
    # `reduce_precision`, whose excess precision no compiler keeps)
    import jax.numpy as jnp

    raw = jax.random.normal(jax.random.PRNGKey(9), (64, 64))
    np.testing.assert_array_equal(
        ref._stored(raw, jnp.bfloat16),
        raw.astype(jnp.bfloat16).astype(jnp.float32))
    for i, (kind, lp) in enumerate(zip(ref.mixers(file_cfg),
                                       params["layers"])):
        assert np.all(np.asarray(lp["norm"]) == 1)
        assert ref.branch_gain(file_cfg, i) == pytest.approx(
            branch_gain(cfg, i))
        want = ref.KINDS[kind][0](keys[i + 1], file_cfg, i)
        if kind == "conv":
            assert set(lp) == {"norm", "in_proj", "conv_w", "out_proj"}
            assert lp["in_proj"].shape == (256, 768)
            assert lp["conv_w"].shape == (3, 256)
            for name in ("in_proj", "conv_w", "out_proj"):
                np.testing.assert_array_equal(want[name], lp[name])
        elif kind == "full_attention":
            assert set(lp) == {"norm", "wq", "wk", "wv", "wo", "q_norm",
                               "k_norm"}
            for name in ("wq", "wk", "wv", "wo"):
                np.testing.assert_array_equal(want[name], lp[name])
            assert lp["q_norm"].shape == lp["k_norm"].shape == (64,)
            assert np.all(np.asarray(lp["q_norm"]) == 1)
        elif kind == "dense":
            assert set(lp) == {"norm", "d_up", "d_down"}
            np.testing.assert_array_equal(
                np.concatenate([want["gate"], want["up"]], axis=1),
                lp["d_up"])
            np.testing.assert_array_equal(want["down"], lp["d_down"])
        else:
            assert set(lp) == {"norm", "router", "e_bias", "e_up", "e_down"}
            np.testing.assert_array_equal(want["router"], lp["router"])
            np.testing.assert_array_equal(want["bias"], lp["e_bias"])
            assert lp["e_bias"].dtype == np.float32
            assert lp["router"].shape == (256, 8)
            np.testing.assert_array_equal(want["down"], lp["e_down"])
            fused = np.concatenate([np.swapaxes(want["gate"], 1, 2),
                                    np.swapaxes(want["up"], 1, 2)], axis=1)
            np.testing.assert_array_equal(fused, lp["e_up"])
            assert lp["e_up"].shape == (8, 2 * 64, 256)
    # the gains at the published sizes, as the file's `assumed` says them:
    # mixer 0 (a conv) writes 60 x the embedding's spread, every later
    # mixer a quarter of the stream it joins, over its kind's own spread
    b = body()
    s0 = 1 / math.sqrt(2048)
    assert ref.branch_gain(b, 0) == pytest.approx(60 * s0)
    after_first = math.hypot(s0, 60 * s0)
    assert ref.branch_gain(b, 1) == pytest.approx(0.25 * after_first / 0.6)
    stream = after_first * math.sqrt(1 + 0.25 ** 2) ** 22
    assert ref.branch_gain(b, 23) == pytest.approx(0.25 * stream / 0.3)
    assert stream / after_first == pytest.approx(1.95, abs=0.01)
    cut = served_config()
    for m in range(24):
        assert branch_gain(cut, m) == pytest.approx(ref.branch_gain(b, m))
    assert score_gain(cut) == ref.NORMED_QK_GAIN == 2.0
    for said in ("60 times", "0.25", "cubic", "twice as wide"):
        assert said in b["assumed"]["weights"], said
    # granite's recipe is where it was
    from dynamo_tpu.models.config import get_config

    granite = get_config("granite-4.0-h-small")
    assert branch_gain(granite, 3) == pytest.approx(3.0 / 0.22 * 1.23 ** 3)
    assert score_gain(granite) == pytest.approx(3.36, abs=0.01)


def test_the_runner_draws_the_same_tree_a_layer_kind_a_program():
    import dataclasses

    import jax

    from dynamo_tpu.engine import ModelRunner, RunnerConfig
    from dynamo_tpu.models.config import get_config
    from dynamo_tpu.models.transformer import init_params
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    cfg = dataclasses.replace(get_config("tiny-lfm2-test"), dtype="float32")
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
        "conv-ungated", "conv-taps-2", "qk-norm-off", "router-bias-off",
        "router-softmax", "kv-int8"]
    assert set(check["limits"]) == {"gap_mean"}
    for lower in {**check["controls"], **check["further_controls"]}.values():
        assert len(lower) == 1  # one axis at a time
    ref = module(REFERENCE, "lfm2_reference")
    _cfg, file_cfg = tiny()
    rng = np.random.default_rng(0)
    sample = [{"prompt": rng.integers(0, 512, 90).tolist(),
               "served": rng.integers(0, 512, 9).tolist()}]
    sound = ref.logits_for(sample, file_cfg, 128)[0]
    assert sound.shape == (9, 512) and sound.dtype == np.float32
    assert 0.5 < sound.std() < 2.0
    for name, lower in {**check["controls"],
                        **check["further_controls"]}.items():
        low = ref.logits_for(sample, file_cfg, 128, lower)[0]
        assert np.abs(low - sound).max() > 1e-3, name


# -- the cell, the mix and its supply -----------------------------------------


def test_the_cells_entries_keep_the_contract_and_join_the_nine_lists():
    """The configuration and the cell as new entries (found by name), the
    cell on the lists of PR 25's nine per-layer metrics that move
    `out_tok_s`; on no list of a client tail, and this PR's reader is not
    declared (PERF.md section 7 B0 (b))."""
    b = bench()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE and len(entry["source"]) < 200
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["reduced"] == REDUCED
    assert set(entry["reduced"]) == set(body()["reduced_why"])
    assert len(entry["why"]) <= 200 and set(entry) == {
        "name", "source", "file", "reduced", "why"}
    for said in ("two pipeline stages", "12 of 24", "all 32 experts",
                 "whole vocabulary"):
        assert said in entry["why"], said
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "draft-sat",
                    "chips": 1, "why": cell["why"]}
    for said in ("256 callers", "128-768", "512-2288", "unshared",
                 "deployed load", "halve"):
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


def test_the_harness_finds_the_cells_files_by_the_entries_names():
    import run as harness

    declared = os.path.join(ROOT, "BENCHMARK.json")
    plan = harness.Plan(declared, CELL)
    assert plan.config["serve"]["model"] == "lfm2-8b-a1b"
    assert plan.mix["callers"] == "max_batch" and plan.mix["name"] == (
        "draft-sat")
    assert plan.shapes.__file__.endswith("shapes/lfm2.py")
    assert plan.reference_module.endswith("references/lfm2.py")
    assert {m["name"] for m in plan.metrics("per_layer")} == set(NINE)
    assert {m["name"] for m in plan.metrics("end_to_end")} == {
        "out_tok_s", "setup_s"}
    flags = plan.worker_flags()
    assert flags[:2] == ["--model", "lfm2-8b-a1b"]
    assert flags[-2:] == ["--prewarm", "full"]
    job = plan.reference_job([])
    assert job["pad_to"] == 3072 and job["module"] == plan.reference_module
    assert set(job["controls"]) == {"act-fp8"}
    for name in WAITING:
        assert callable(harness.Plan.reader(name))


def test_the_mix_is_the_issues_and_its_supply_outlasts_both_windows():
    mix, b = load("mixes", "draft-sat.json"), bench()
    serve = body()["serve"]
    assert (mix["loop"], mix["callers"], mix["population"]) == (
        "closed", "max_batch", 512)
    assert mix["prompt_tokens"] == {"median": 320, "sigma": 0.5,
                                    "min": 128, "max": 768}
    assert mix["output_tokens"] == {"median": 1280, "sigma": 0.4,
                                    "min": 512, "max": 2288}
    assert mix["max_total_tokens"] == 3056 <= 192 * 16
    pairs = traffic.population(mix, 1)
    prompts = [p for p, _ in pairs]
    answers = [o for _, o in pairs]
    assert 350 < sum(prompts) / 512 < 360  # "about 355"
    assert 1340 < sum(answers) / 512 < 1360  # "about 1,350"
    assert max(p + o for p, o in pairs) <= 3056
    assert (min(prompts), max(prompts)) == (128, 768)
    assert (min(answers), max(answers)) == (512, 2288)
    # every prompt fits one launch of the 2,048-token budget
    assert max(prompts) <= 2048
    # 3.8 tokens out for every prompt token: decode is most of the work
    assert 3.7 < sum(answers) / sum(prompts) < 3.9
    # `run.py` hands a closed loop callers + 12 requests for each second
    # of ramp, window and tail; an answer is 1,352 tokens, so the supply
    # outlasts a --trace 0 run up to 16.0 k tokens/s and a --trace 2 run
    # (45 s of tail) up to 16.2 k: the server closes under 10 k
    callers = serve["max_batch"]
    ramp = mix["ramp_seconds"]
    mean_answer = sum(answers) / 512
    for span in (ramp + b["run_seconds"], ramp + b["run_seconds"] + 45.0):
        supply = callers + 12 * span
        sustained = (supply - callers) / span * mean_answer
        assert sustained > 16_000
        # at 10 k tokens/s: requests closed by the end, and those in flight
        assert 10_000 / mean_answer * span + callers < supply * 0.9


def test_the_mixs_prefill_shapes_are_the_workers_prewarm_grid():
    """`ModelRunner.prewarm(launches=True)` derives its grid from the
    runner's buckets and token budget, rows x bucket inside the budget
    (`bounds_prefill_launches`: recurrent state, here the conv carries,
    and contexts past one launch). The mix lists that grid, and the table
    widths its contexts reach are widths the worker compiles."""
    from dynamo_tpu.engine.model_runner import bucket_table_width

    mix, args = load("mixes", "draft-sat.json"), worker_args()
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
    assert reach == set(warm["table_widths"]) == {16, 32, 64, 128, 192}
    assert {bucket_table_width(-(-(n + 1 + 16) // 16), cap)
            for n in warm["decode"]} == reach
    assert max(warm["decode"]) + warm["decode_tokens"] <= 3056

    def bucket(n):
        return next(b for b in buckets if n <= b)

    for group in warm["groups"]:
        n = 1 << (len(group) - 1).bit_length()
        assert (n, bucket(max(group))) in grid and sum(group) <= budget
    assert {bucket(n) for n in warm["lone_prefill"]} == set(buckets)


# -- the waiting reader, on made-up numbers -----------------------------------------


def reader_ctx(**more):
    import run as harness

    ctx = {"window": {"before": {}, "after": {}, "t0": 0.0, "seconds": 50.0},
           "config": body(), "shapes": counts(), "stats": stats, **more}
    ctx["read"] = lambda name: harness.Plan.reader(name)(ctx)
    ctx["layer"] = harness.Plan.layer
    return ctx


def test_the_conv_reader_reads_operations_named_for_the_mixer_only():
    """The conv mixer is XLA operations under the scope `conv_mixer`, and
    the trace reduction keeps operation names: today's program, the
    parent's, a trace without operations and no trace all read None.
    Where operations bear the scope's name (a reduction that attributes
    scopes), or a name the configuration gives, 3.24 ms of them in
    1.08 s of device time is 0.3%."""
    rest = {"gmm": {"seconds": 0.4, "count": 1440},
            "paged_decode_attention_pool": {"seconds": 0.08, "count": 216},
            "fusion": {"seconds": 0.59676, "count": 9000}}
    for other in ({"ops": dict(rest, fusion={"seconds": 0.6, "count": 9648})},
                  {"ops": {}}, None):
        assert reader_ctx(trace=other)["read"]("conv_dev_share_pct") is None
    assert "conv_kernels" not in body()["trace_names"]
    scoped = {"ops": dict(rest, conv_mixer={"seconds": 0.00324,
                                            "count": 648})}
    assert reader_ctx(trace=scoped)["read"](
        "conv_dev_share_pct") == pytest.approx(0.3)
    named = reader_ctx(trace={"ops": dict(rest, conv_step={
        "seconds": 0.00324, "count": 648})})
    named["config"] = dict(body(), trace_names=dict(
        body()["trace_names"], conv_kernels="^conv_step"))
    assert named["read"]("conv_dev_share_pct") == pytest.approx(0.3)


def test_pr30s_readers_read_this_architectures_counts():
    """The expert readers of PR 30 and the accepted rooflines find what
    they call in this configuration's counts and `trace_names`: a
    64-wide head's bytes, the conv-only state, the experts' floor."""
    rows = [stats.Timeline(index=i, due=0.0, sent=0.0, n_prompt=n,
                           want_tokens=10, first=1.0, end=None)
            for i, n in enumerate((300, 600, 900, 1200))]
    trace = {"ops": {
        "paged_decode_attention_pool": {"seconds": 0.0012, "count": 30},
        "gmm": {"seconds": 0.036, "count": 200},
        "fusion": {"seconds": 0.0828, "count": 900}},
        "modules": {"jit_multi": {"count": 10, "seconds": 0.12}}}
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    ctx = reader_ctx(trace=trace, timelines=rows, peaks=peaks)
    ctx["config"] = dict(body(), serve=dict(body()["serve"], decode_block=1))
    ctx["window"].update(capture_at=10.0, capture_end=12.5)
    assert ctx["read"]("moe_dev_share_pct") == pytest.approx(30.0)
    live = stats.mean_live_decode_tokens(rows, 10.0, 12.5)
    assert ctx["read"]("paged_attn_roofline_pct") == pytest.approx(
        100 * live * 6144 / 819e9 / (0.0012 / 10))
    assert ctx["read"]("decode_step_dev_ms") == pytest.approx(12.0)
    assert ctx["read"]("decode_hbm_roofline_pct") == pytest.approx(
        100 * counts().decode_step_bytes(body(), live) / 819e9 / 0.012)
    names = body()["trace_names"]
    assert names["expert_kernels"] == "^(expert_)?gmm"
    assert names["attention_kernels"] == "^paged_decode_attention"
    assert "ssm_decode_kernels" not in names  # no SSM state, no such kernel


# -- one whole run, rehearsed on the CPU ----------------------------------------


def tiny_file() -> dict:
    """A configuration file for `tiny-lfm2-test` cut by the flag the
    cell's is: this architecture's reference, counts and worker flags."""
    _cfg, keys = tiny()
    keys.pop("dtype")
    real = body()
    return {
        **keys, "name": "tiny-lfm2", "source": "the program's preset",
        "max_position_embeddings": 256, "torch_dtype": "bfloat16",
        "serve": {"model": "tiny-lfm2-test", "weight_dtype": "model",
                  "kv_dtype": "model", "page_size": 4, "num_pages": 256,
                  "max_batch": 4, "max_pages_per_seq": 64,
                  "worker_args": ["--serve-layers", "6", "--prewarm",
                                  "full"]},
        "shapes": real["shapes"], "trace_names": real["trace_names"],
        "reference": {"module": real["reference"]["module"],
                      "dtype": "bfloat16", "weights": "model",
                      "weight_seed": 0},
        "check": {"sample": 4, "limits": {"gap_mean": 0.5},
                  "controls": real["check"]["controls"]}}


@pytest.mark.slow
def test_a_rehearsed_run_of_this_architecture(tmp_path):
    """tiny-lfm2-test through the whole harness on the CPU (`--trace
    2`): the worker takes the stage's flag, says which path its
    attention took, and the window's tokens are checked against THIS
    reference."""
    from test_bench_rehearsal import rehearse

    with open(tmp_path / "case.json", "w") as f:
        json.dump(tiny_file(), f)
    out, line, lines = rehearse(tmp_path, 2, 2**31 + 91,
                                config_file="case.json")
    assert out.returncode == 10, out.stderr[-3000:]
    report = json.loads(lines[-2 - len(line["compared"])])
    assert report["worker_flags"][-4:] == tiny_file()["serve"]["worker_args"]
    assert line["correct"] is True and line["failed"] == 0
    assert "'prefill_attention': 'xla'" in out.stderr
    assert not set(WAITING) & set(line["metrics"])
    assert line["metrics"]["preempts"]["value"] == 0
