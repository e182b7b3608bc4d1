"""The pangu_ultra_moe configuration (`openpangu-718b-ep16`) and its cell
(`reason-sat`): the file against the program's preset and the worker's
flags, key by key; the counts against the numbers ISSUE 38's table works
out by hand; the reference against its contract and the program's
weights; the supply of requests against `ramp_why`; the mix's shapes
against the grid the worker compiles; the three waiting readers on
made-up numbers. What `test_bench_mellum.py` does for
`mellum2-12b-pp4`, in a file of this architecture's own.

ENTRY, CELL_ENTRY and NINE below are the entries BENCHMARK.json gained,
letter for letter, and everything here finds them by name, never by
position (PERF.md section 7 B0 (b))."""

import ast
import os
import re

import numpy as np
import pytest

from bench_paths import BENCH, ROOT
from dtbench import stats, traffic
from test_bench_contract import bench, load

CONFIG = "openpangu-718b-ep16"
CELL = CONFIG + ".reason-sat"
REFERENCE = os.path.join(BENCH, "references", "pangu.py")
SOURCE = ("https://huggingface.co/FreedomIntelligence/"
          "openPangu-Ultra-MoE-718B/blob/main/config.json")
REDUCED = ["first_k_dense_replace", "n_routed_experts", "vocab_size",
           "num_nextn_predict_layers", "max_position_embeddings"]
WAITING = ("latent_attn_roofline_pct", "latent_attn_dev_share_pct",
           "latent_expand_per_prefill_token")
ENTRY = {
    "name": CONFIG, "source": SOURCE,
    "file": f"benchmarks/configs/{CONFIG}.json", "reduced": REDUCED,
    "why": "latent attention over a single-stack latent pool, sandwich "
           "norms, 1 dense + 4 expert blocks of 61, 16 of 256 experts + the "
           "shared one, 1/8 vocabulary: one of 16 expert-parallel chips, "
           "published widths"}
CELL_ENTRY = {
    "name": CELL, "config": CONFIG, "traffic": "reason-sat", "chips": 1,
    "why": "closed loop, 128 callers = slots: prompts 256-4096, answers "
           "384-2560, contexts to 6144, unshared: latent decode kernel, "
           "non-absorbing prefill, sandwich norms; an expert sees 1/16 its "
           "deployed load"}
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
    return module(os.path.join(BENCH, "shapes", "pangu.py"), "counts")


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
    assert b["source"] == SOURCE and b["model_type"] == "pangu_ultra_moe"
    assert whole.layer_pattern == "LD" * 3 + "LE" * 58
    assert (b["num_hidden_layers"], whole.n_layers) == (61, 122)
    assert (cfg.layer_pattern, cfg.n_layers, cfg.mixers_per_layer) == (
        "LD" * b["first_k_dense_replace"]
        + "LE" * (b["served_layers"] - b["first_k_dense_replace"]),
        2 * b["served_layers"], 2) == ("LDLELELELE", 10, 2)
    assert (b["hidden_size"], b["num_attention_heads"],
            b["num_key_value_heads"], b["vocab_size"]) == (
        cfg.hidden, cfg.n_q_heads, cfg.n_kv_heads, cfg.vocab_size) == (
        7680, 128, 128, 19200)
    assert (b["q_lora_rank"], b["kv_lora_rank"], b["qk_nope_head_dim"],
            b["qk_rope_head_dim"], b["v_head_dim"]) == (
        cfg.mla_q_lora_rank, cfg.mla_kv_lora_rank, cfg.mla_nope_head_dim,
        cfg.mla_rope_head_dim, cfg.mla_v_head_dim) == (
        1536, 512, 128, 64, 128)
    assert cfg.head_dim == cfg.mla_qk_head_dim == 192
    assert (b["intermediate_size"], b["moe_intermediate_size"]) == (
        cfg.mlp_hidden, cfg.expert_mlp_hidden) == (18432, 2048)
    assert (b["n_routed_experts_published"], b["num_experts_per_tok"],
            b["n_shared_experts"], b["norm_topk_prob"],
            b["routed_scaling_factor"]) == (
        cfg.n_experts, cfg.n_experts_active, cfg.n_shared_experts,
        cfg.moe_norm_topk, cfg.moe_routed_scale) == (256, 8, 1, True, 2.5)
    assert tuple(b["experts_held"]) == cfg.held_experts == (0, 16)
    assert b["n_routed_experts"] == 16 == cfg.held_experts[1]
    assert cfg.moe_scoring == "sigmoid" and not cfg.moe_selection_bias
    assert cfg.moe_n_group == 1 and cfg.mlp_act == "swiglu"
    assert b["hidden_act"] == "silu" and b["sandwich_norm"] is True
    assert cfg.sandwich_norm and not cfg.qk_norm and not cfg.attn_bias
    assert b["rms_norm_eps"] == cfg.rms_eps == 1e-5
    assert b["rope_theta"] == cfg.rope_theta == 25.6e6
    assert cfg.use_rope and not cfg.rope_yarn_factor
    inv_freq, factor = rope_tables(cfg, "L")
    assert inv_freq.shape == (32,) and factor == 1.0
    np.testing.assert_allclose(
        np.asarray(inv_freq), [25.6e6 ** (-2 * i / 64) for i in range(32)],
        rtol=1e-5)
    assert not b["tie_word_embeddings"] and not cfg.tie_embeddings
    assert b["num_nextn_predict_layers"] == 0
    assert b["reference"]["dtype"] == cfg.dtype == b["torch_dtype"]
    assert b["serve"]["weight_dtype"] == b["reference"]["weights"] == "model"
    assert b["serve"]["kv_dtype"] == "model"
    assert b["max_position_embeddings"] == 8192 >= (
        b["serve"]["page_size"] * b["serve"]["max_pages_per_seq"]) == 6144
    assert whole.max_context == b["published"]["max_position_embeddings"]
    assert whole.vocab_size == b["published"]["vocab_size"] == 153600
    assert set(b["published"]) == set(b["reduced_why"]) == set(REDUCED)
    assert b["published"]["first_k_dense_replace"] == 3
    assert {"router", "norms", "rope", "prediction_module",
            "weights"} <= set(b["assumed"])
    assert "16 expert-parallel chips" in b["deployment"]
    assert "1/16" in b["deployment"]
    # a cached row: 576 values stated, padded to 640 lanes in the pool
    assert cfg.kv_cache_head_dim == 640 and "640" in b["memory"]


def test_every_number_of_the_source_is_in_the_file():
    """The catalog's `config` of this architecture, key for key, but for
    the five keys `reduced` lists."""
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
            assert b["published"][key] == value, key
            continue
        assert b[key] == value, key


def test_the_workers_flags_are_the_files():
    b, args = body(), worker_args()
    serve = b["serve"]
    assert serve["worker_args"] == [
        "--serve-layers", "5", "--experts-held", "0:16",
        "--vocab-rows", "19200",
        "--prefill-buckets", "256,512,1024,1536,2048", "--prewarm", "full"]
    assert (args.serve_layers, args.experts_held, args.vocab_rows,
            args.prewarm) == (5, "0:16", 19200, "full")
    assert (serve["page_size"], serve["max_batch"],
            serve["max_pages_per_seq"], serve["decode_block"]) == (
        16, 128, 384, 8)
    # 128 rows at the mix's mean of 2,460 positions reserve 19,700 pages
    mix = load("mixes", "reason-sat.json")
    pairs = traffic.population(mix, 1)
    mean = sum(p + o for p, o in pairs) / len(pairs)
    assert 2400 < mean < 2480
    assert 128 * mean / 16 < 0.82 * serve["num_pages"]
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
    """ISSUE 38's table, line by line."""
    c, b = counts(), body()
    p = c.matmul_params(b)
    attention = (7680 * 1536 + 1536 * 128 * 192 + 7680 * 512 + 7680 * 64
                 + 2 * 512 * 128 * 128 + 16384 * 7680)
    assert p["attention"] == attention == 196_575_232  # 196.58 M
    assert p["dense"] == 3 * 7680 * 18432 == 424_673_280  # 424.67 M
    assert p["expert"] == p["shared"] == 3 * 7680 * 2048 == 47_185_920
    assert p["router"] == 7680 * 256
    assert p["router"] + p["shared"] == 49_152_000  # 49.15 M
    assert 16 * p["expert"] == 754_974_720  # 754.97 M
    assert p["attention"] + p["dense"] == 621_248_512  # one dense block
    assert (p["attention"] + p["router"] + p["shared"]
            + 16 * p["expert"]) == 1_000_701_952  # one expert block here
    assert p["head"] == p["embed"] == 19200 * 7680
    assert p["head"] + p["embed"] == 294_912_000  # 294.91 M
    total = c.total_params(b)
    assert total == 621_248_512 + 4 * 1_000_701_952 + 294_912_000
    assert total == 4_918_968_320 and round(total * 2 / 1e9, 2) == 9.84
    z = c.sizes(b)
    assert (z["layers"], z["n_dense"], z["n_expert"], z["held"],
            z["published"], z["k"]) == (5, 1, 4, 16, 256, 8)
    # a cached token: 576 values = 1,152 B a layer, 5,760 B in all
    assert c.kv_bytes_per_token_layer(b) == 1152
    assert c.kv_bytes_per_token(b) == 5760
    assert 24576 * 16 * 5760 / 1e9 == pytest.approx(2.265, abs=1e-3)
    assert 128 * 128 * 2 * 2 / 1152 == pytest.approx(56.9, abs=0.1)  # GQA
    # experts touched: 128 rows x 8 of 256 miss a held expert 1.7% of steps
    assert c.touched_experts(b, 128) == pytest.approx(15.72, abs=0.01)
    assert c.touched_experts(b, 1) == pytest.approx(0.5)
    # a decode step of 128 rows reads all but the embedding and 0.28 expert
    dense = (5 * p["attention"] + p["dense"] + 4 * (p["router"] + p["shared"])
             + p["head"])
    assert c.weight_bytes_per_step(b, 128) == pytest.approx(
        2.0 * (dense + 4 * 15.72 * p["expert"]), rel=1e-3)
    assert 9.4e9 < c.weight_bytes_per_step(b, 128) < total * 2 - 2 * p["embed"]
    # never over: without rows, the fewest rows the live tokens can be
    live = 243_000.0
    assert c.attention_step_bytes(b, live) == live * 5760
    assert c.decode_step_bytes(b, live) == pytest.approx(
        c.weight_bytes_per_step(b, live / 6144) + live * 5760)
    assert c.decode_step_bytes(b, live) < c.decode_step_bytes(b, live, 128)
    # prefill, a token at half the mix's mean prompt: 3.96 GFLOP, 56% of
    # it latent attention's projections and scores (ISSUE 38 reckoned
    # "about 3.7" and 61%: PERF.md section 6)
    at = 1310 / 2
    per_token = c.flops_per_token(b, at)
    experts = 8 * 16 / 256 * p["expert"]
    matrices = (5 * p["attention"] + p["dense"]
                + 4 * (experts + p["shared"] + p["router"]) + p["head"])
    assert per_token == pytest.approx(
        2 * matrices + 5 * 2 * 128 * (192 + 128) * at)
    assert per_token == pytest.approx(3.96e9, rel=2e-3)
    attention_share = (2 * 5 * p["attention"]
                       + 5 * 2 * 128 * 320 * at) / per_token
    assert 0.55 < attention_share < 0.58
    # the kernel's two sides: 242 operations a byte against a ridge of 240
    contexts = [1000, 3000]
    assert c.latent_layer_bytes(b, contexts) == 4000 * 1152
    assert c.latent_layer_flops(b, contexts) == 4000 * 128 * (576 + 512) * 2
    per_byte = c.latent_layer_flops(b, contexts) / c.latent_layer_bytes(
        b, contexts)
    assert per_byte == pytest.approx(241.8, abs=0.1)
    assert 197e12 / 819e9 == pytest.approx(240.5, abs=0.1)
    floor = c.expert_gmm_floor(b, 8, 15.7, 1, 16, 500)
    assert floor["bytes"] == (8 * 15.7 + 16) * 2 * p["expert"]
    assert floor["flops"] == 9 * 500 * 2 * p["expert"]


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
    # it reads the file's own keys: the real file drives it at a toy width
    b = body()
    for key in ("served_layers", "first_k_dense_replace", "experts_held",
                "n_routed_experts_published", "routed_scaling_factor"):
        assert key in b and f'"{key}"' in source


def tiny():
    """(program config, the file keys the reference reads) at the CPU
    tests' size: the cell's cut of the tiny preset, experts 2:6 held."""
    import dataclasses

    from dynamo_tpu.models.config import cut_config, get_config

    cfg = cut_config(dataclasses.replace(get_config("tiny-pangu-test"),
                                         dtype="float32"),
                     layers=3, experts="2:6", vocab_rows=384)
    return cfg, {
        "dtype": cfg.dtype, "weight_seed": 0, "hidden_size": cfg.hidden,
        "num_attention_heads": cfg.n_q_heads,
        "q_lora_rank": cfg.mla_q_lora_rank,
        "kv_lora_rank": cfg.mla_kv_lora_rank,
        "qk_nope_head_dim": cfg.mla_nope_head_dim,
        "qk_rope_head_dim": cfg.mla_rope_head_dim,
        "v_head_dim": cfg.mla_v_head_dim,
        "intermediate_size": cfg.mlp_hidden,
        "moe_intermediate_size": cfg.expert_mlp_hidden,
        "n_shared_experts": cfg.n_shared_experts,
        "n_routed_experts_published": cfg.n_experts,
        "experts_held": list(cfg.held_experts),
        "num_experts_per_tok": cfg.n_experts_active,
        "norm_topk_prob": cfg.moe_norm_topk,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
        "vocab_size": cfg.vocab_size, "served_layers": 3,
        "first_k_dense_replace": 1}


def test_the_programs_seeded_weights_are_the_references_recipe():
    """Same seed, same numbers, each from its own code: every leaf of the
    program's random tree against the reference's recipe (the program
    keeps W_uq as one matrix, W_uk and W_uv head-major, a SwiGLU's gate
    and up as one matrix, an expert's output-major; the held experts are
    the PUBLISHED indices 2..5)."""
    import jax

    from dynamo_tpu.models.transformer import init_params

    ref = module(REFERENCE, "pangu_reference")
    cfg, file_cfg = tiny()
    assert cfg.layer_pattern == "LDLELE"
    params = init_params(jax.random.PRNGKey(0), cfg)
    keys = ref.model_keys(file_cfg)
    assert len(keys) == cfg.n_layers + 2 == 8
    for i, lp in enumerate(params["layers"]):
        assert np.all(np.asarray(lp["norm"]) == 1)
        assert np.all(np.asarray(lp["post_norm"]) == 1)
        kind = cfg.layer_kind(i)
        if kind == "L":
            want = ref.attention_weights(keys[i + 1], file_cfg)
            assert set(lp) == {"norm", "post_norm", "q_norm", "kv_norm",
                               *want}
            for name in ("w_dq", "w_dkv", "w_kr", "wo"):
                np.testing.assert_array_equal(want[name], lp[name])
            np.testing.assert_array_equal(
                want["w_uq"].reshape(24, -1), lp["w_uq"])
            np.testing.assert_array_equal(
                np.transpose(want["w_uk"], (1, 2, 0)), lp["w_uk"])
            np.testing.assert_array_equal(
                np.transpose(want["w_uv"], (1, 0, 2)), lp["w_uv"])
            assert np.all(np.asarray(lp["q_norm"]) == 1)
            assert np.all(np.asarray(lp["kv_norm"]) == 1)
        elif kind == "D":
            want = ref.dense_weights(keys[i + 1], file_cfg)
            assert set(lp) == {"norm", "post_norm", "d_up", "d_down"}
            np.testing.assert_array_equal(
                np.concatenate([want["gate"], want["up"]], axis=1),
                lp["d_up"])
            np.testing.assert_array_equal(want["down"], lp["d_down"])
        else:
            want = ref.expert_weights(keys[i + 1], file_cfg)
            assert set(lp) == {"norm", "post_norm", "router", "e_up",
                               "e_down", "s_up", "s_down"}  # no e_bias
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
    h, v = cfg.hidden, cfg.vocab_size
    np.testing.assert_array_equal(
        ref._dense(keys[0], (v, h), h, "float32"), params["embed"])
    np.testing.assert_array_equal(
        ref._dense(keys[-1], (h, v), h, "float32"), params["lm_head"])


def test_each_control_changes_one_thing_and_only_then():
    ref = module(REFERENCE, "pangu_reference")
    _cfg, file_cfg = tiny()
    rng = np.random.default_rng(0)
    sample = [{"prompt": rng.integers(0, 384, 90).tolist(),
               "served": rng.integers(0, 384, 9).tolist()}]
    sound = ref.logits_for(sample, file_cfg, 128)[0]
    again = ref.logits_for(sample, file_cfg, 128, lower={})[0]
    assert sound.shape == (9, 384) and sound.dtype == np.float32
    np.testing.assert_array_equal(sound, again)
    assert 0.5 < sound.std() < 2.0
    check = body()["check"]
    assert set(check["controls"]) == {"act-fp8"}
    assert set(check["further_controls"]) == {
        "latent-int8", "no-post-norm", "scale-nope", "router-softmax"}
    assert set(check["limits"]) == {"gap_mean"}
    for name, lower in {**check["controls"],
                        **check["further_controls"]}.items():
        assert len(lower) == 1  # one axis at a time
        low = ref.logits_for(sample, file_cfg, 128, lower)[0]
        assert np.abs(low - sound).max() > 1e-3, name
    for bad in ({"act": "int3"}, {"latent": "int2"}, {"scale": "rope"},
                {"router": "tanh"}, {"post_norm": "half"}):
        with pytest.raises(ValueError):
            ref.logits_for(sample, file_cfg, 128, bad)


# -- the cell, the mix and its supply -----------------------------------------


def test_the_cells_entries_keep_the_contract_and_join_the_nine_lists():
    """The configuration and the cell as new entries (found by name), the
    cell on the lists of PR 25's nine per-layer metrics that move
    `out_tok_s`; on no list of a client tail, and none of this PR's
    readers is declared (PERF.md section 7 B0 (b))."""
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
            assert m["moves"] == "out_tok_s" and m["workloads"][-1] == CELL
    declared_metrics = {m["name"] for m in b["per_layer"]}
    assert not declared_metrics & set(WAITING)
    for name in WAITING:  # their readers wait in the tree
        assert os.path.isfile(os.path.join(BENCH, "layers", name + ".py"))
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 0


def test_the_harness_finds_the_cells_files_by_the_entries_names():
    import run as harness

    declared = os.path.join(ROOT, "BENCHMARK.json")
    plan = harness.Plan(declared, CELL)
    assert plan.config["serve"]["model"] == "openpangu-ultra-moe-718b"
    assert plan.mix["callers"] == "max_batch"
    assert plan.shapes.__file__.endswith("shapes/pangu.py")
    assert plan.reference_module.endswith("references/pangu.py")
    assert {m["name"] for m in plan.metrics("per_layer")} == set(NINE)
    assert {m["name"] for m in plan.metrics("end_to_end")} == {
        "out_tok_s", "setup_s"}
    flags = plan.worker_flags()
    assert flags[:2] == ["--model", "openpangu-ultra-moe-718b"]
    assert flags[-2:] == ["--prewarm", "full"]
    for name, layers in (("m7b-w4kv8.chunk-sat", 20),
                         ("nemotron3-nano-ep2.chat128-sat", 9),
                         ("mellum2-12b-pp4.codemix-sat", 9)):
        old = harness.Plan(declared, name)
        assert len(old.metrics("per_layer")) == layers


def test_the_mix_is_the_issues_and_the_supply_outlasts_the_window():
    mix, b = load("mixes", "reason-sat.json"), bench()
    assert (mix["loop"], mix["callers"], mix["population"]) == (
        "closed", "max_batch", 512)
    assert mix["prompt_tokens"] == {"median": 1024, "sigma": 0.7,
                                    "min": 256, "max": 4096}
    assert mix["output_tokens"] == {"median": 1024, "sigma": 0.5,
                                    "min": 384, "max": 2560}
    assert mix["max_total_tokens"] == 6144 == 384 * 16
    pairs = traffic.population(mix, 1)
    prompts = [p for p, _ in pairs]
    answers = [o for _, o in pairs]
    assert 1270 < sum(prompts) / 512 < 1315  # "about 1,310"
    assert 1120 < sum(answers) / 512 < 1155  # "about 1,150"
    assert max(p + o for p, o in pairs) <= 6144
    # an answer is cut only where a long prompt meets a long answer
    cut = sum(a != b for a, b in zip(sorted(answers), sorted(
        traffic._lognormal_quantiles(mix["output_tokens"], 512))))
    assert cut <= 512 // 25
    # a block of 8 steps emits 1,024 tokens and closes 0.9 requests, whose
    # prompts (about 1,170 tokens) fit the one 2,048-token prefill launch
    closes = 128 * 8 / (sum(answers) / 512)
    assert 0.85 < closes < 0.95
    assert closes * sum(prompts) / 512 < 0.6 * 2048
    with open(os.path.join(BENCH, "run.py")) as f:
        source = f.read()
    per_second = int(re.search(r"count = int\(callers \+ total \* (\d+)\)",
                               source).group(1))
    tail = float(re.search(r"TAIL_MAX_SECS = ([\d.]+)", source).group(1))
    ramp, spread = mix["ramp_seconds"], mix["start_spread_seconds"]
    assert (ramp, spread, per_second, tail) == (60, 60, 12, 45.0)
    # this server closes 3-4 requests a second; 12 a second are handed
    for span in (ramp + b["run_seconds"], ramp + b["run_seconds"] + tail):
        assert (128 + 5 * span) * 1.25 < 128 + per_second * span


def test_the_mixs_prefill_shapes_are_the_workers_prewarm_grid():
    """`ModelRunner.prewarm(launches=True)` derives its grid from the
    runner's buckets and token budget, rows x bucket inside the budget
    (`bounds_prefill_launches`: latent layers). The mix lists that grid,
    every chunk the scheduler can cut lies in it, and the table widths
    the contexts reach are widths the worker compiles."""
    from dynamo_tpu.engine.model_runner import bucket_table_width

    mix, args = load("mixes", "reason-sat.json"), worker_args()
    warm, serve = mix["warm"], body()["serve"]
    buckets = sorted(int(x) for x in args.prefill_buckets.split(","))
    budget = buckets[-1]
    assert (buckets, budget) == ([256, 512, 1024, 1536, 2048], 2048)
    grid = {(rows, b) for rows in (1, 2, 4, 8, 16) for b in buckets
            if rows <= budget // buckets[0] and rows * b <= budget}
    assert len(grid) == 11
    assert {tuple(s) for s in warm["prefill_shapes"]} == grid

    def bucket(n):
        return next(b for b in buckets if n <= b)

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
    cap = serve["max_pages_per_seq"]
    lo = mix["prompt_tokens"]["min"] + 1
    reach = {bucket_table_width(-(-(kv + 16) // 16), cap)
             for kv in range(lo, mix["max_total_tokens"] + 1)}
    assert reach == set(warm["table_widths"]) == {32, 64, 128, 256, 384}
    assert {bucket_table_width(-(-(n + 1 + 16) // 16), cap)
            for n in warm["decode"]} == reach
    assert max(warm["decode"]) + warm["decode_tokens"] <= 6144
    for group in warm["groups"]:
        n = 1 << (len(group) - 1).bit_length()
        assert (n, bucket(max(group))) in grid and sum(group) <= budget
    assert {bucket(n) for n in warm["lone_prefill"]} == set(buckets)


# -- the waiting readers, on made-up numbers --------------------------------------


def scrape(expand, prefill):
    return {
        "dynamo_latent_prefill_expand_tokens_total": [({"worker": "w"},
                                                       expand)],
        "dynamo_latent_decode_tokens_total": [({"worker": "w"}, 7.0)],
        "dynamo_engine_tokens": [({"worker": "w", "kind": "prefill"}, prefill),
                                 ({"worker": "w", "kind": "decode"}, 9e9)],
    }


def reader_ctx(before, after, **more):
    import run as harness

    ctx = {"window": {"before": before, "after": after, "t0": 0.0,
                      "seconds": 50.0}, "config": body(),
           "shapes": counts(), "stats": stats, **more}
    ctx["read"] = lambda name: harness.Plan.reader(name)(ctx)
    ctx["layer"] = harness.Plan.layer
    return ctx


def test_the_counter_reader_takes_the_growth_over_the_window():
    """60,000 prompt tokens prefilled; their launches rebuilt 81,000
    positions a layer (x 5 layers in the counter): 1.35 a token."""
    ctx = reader_ctx(scrape(5000.0, 1000.0),
                     scrape(5000.0 + 5 * 81_000, 61_000.0))
    assert ctx["read"]("latent_expand_per_prefill_token") == pytest.approx(
        1.35)
    assert reader_ctx({}, {})["read"](
        "latent_expand_per_prefill_token") is None  # the parent
    same = scrape(5.0, 1.0)
    assert reader_ctx(same, same)["read"](
        "latent_expand_per_prefill_token") is None
    # another architecture's counts have no latent layer: nothing
    import run as harness

    other = reader_ctx(scrape(0.0, 0.0), scrape(9.0, 9.0))
    other["shapes"] = harness.shapes
    assert other["read"]("latent_expand_per_prefill_token") is None


def test_the_trace_readers_find_the_latent_kernel_by_its_name():
    """Two rows decoding through the capture, contexts 600 and 3,000: a
    latent layer's step reads 3,600 rows of 1,152 B (5.06 us) and
    computes 3,600 x 128 x 1,088 x 2 operations (5.09 us): the larger;
    100 events of the kernel in 0.002 s are 20 us each."""
    rows = [stats.Timeline(index=i, due=0.0, sent=0.0, n_prompt=n,
                           want_tokens=10, first=1.0, end=None)
            for i, n in enumerate((600, 3000))]
    trace = {"ops": {
        "paged_decode_attention_latent": {"seconds": 0.002, "count": 100},
        "gmm": {"seconds": 0.004, "count": 40},
        "fusion": {"seconds": 0.014, "count": 900}}}
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    ctx = reader_ctx({}, {}, trace=trace, timelines=rows, peaks=peaks)
    ctx["window"].update(capture_at=10.0, capture_end=12.5)
    hbm_s = 3600 * 1152 / 819e9
    mxu_s = 3600 * 128 * 1088 * 2 / 197e12
    assert mxu_s > hbm_s and mxu_s / hbm_s < 1.01
    assert ctx["read"]("latent_attn_roofline_pct") == pytest.approx(
        100.0 * mxu_s / 2e-5)
    assert ctx["read"]("latent_attn_dev_share_pct") == pytest.approx(10.0)
    # the accepted share finds the kernel under `attention_kernels` too
    pattern = re.compile(body()["trace_names"]["attention_kernels"])
    assert sum(pattern.search(n) is not None for n in trace["ops"]) == 1
    assert body()["trace_names"]["latent_attention_kernels"].startswith(
        "^paged_decode_attention_latent")
    # a program without the kernel (the parent): nothing, not an error
    bare = reader_ctx({}, {}, trace={"ops": {"fusion": trace["ops"]["fusion"]}},
                      timelines=rows, peaks=peaks)
    bare["window"].update(capture_at=10.0, capture_end=12.5)
    assert bare["read"]("latent_attn_roofline_pct") is None
    assert bare["read"]("latent_attn_dev_share_pct") is None
