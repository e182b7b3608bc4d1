"""The phi4flash configuration (`phi4-mini-flash`) and its cell
(`longreason-sat`): the file against the catalog's published keys, the
program's preset and the worker's flags; the counts and the memory table
against the numbers ISSUE 52 works out by hand; the reference against its
contract; the mix's lengths, its supply arithmetic and the
prompt-tokens-a-block arithmetic behind `decode_block`; the harness
resolving every file of the cell by name; the accepted and the waiting
readers on made-up numbers; the run's budget as a sum.

Everything here finds the entries BENCHMARK.json gained BY NAME and pins
no position and no count (PERF.md section 7 B0 (b))."""

import ast
import json
import os

import pytest

from bench_paths import BENCH, ROOT
from dtbench import stats, traffic
from test_bench_contract import bench, load

CONFIG = "phi4-mini-flash"
CELL = CONFIG + ".longreason-sat"
MIX = "longreason-sat"
PRESET = "phi4-mini-flash-reasoning"
REFERENCE = os.path.join(BENCH, "references", "phi4flash.py")
SOURCE = ("https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/"
          "blob/main/config.json")
REDUCED = ["max_position_embeddings"]
NINE = ("sched_host_share_pct", "window_compiles", "kv_pool_live_pct",
        "preempts", "decode_step_dev_ms", "prefill_dev_share_pct",
        "decode_hbm_roofline_pct", "paged_attn_roofline_pct",
        "device_idle_pct")
WAITING = ("prefill_cross_wasted_pct", "kv_shared_read_share_pct",
           "ssm_state_live_pct", "ssm_prefill_continued_pct",
           "ssm_prefill_launches_per_prompt", "window_attn_roofline_pct",
           "window_attn_dev_share_pct", "kv_window_reserved_pct",
           "kv_window_freed_per_token")
FURTHER = {"state-bf16": {"state": "bf16"},
           "lambda-zero": {"lambda": "zero"},
           "memory-early": {"memory": "early"},
           "cross-window": {"cross": "window"},
           "window-double": {"window": "double"}}


def body():
    return load("configs", CONFIG + ".json")


def module(path, name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def counts():
    return module(os.path.join(BENCH, "shapes", "phi4flash.py"), "counts")


def worker_args():
    from dynamo_tpu.engine.worker import build_arg_parser

    serve = body()["serve"]
    return build_arg_parser().parse_args(
        ["--model", serve["model"], "--page-size", str(serve["page_size"]),
         "--num-pages", str(serve["num_pages"]),
         "--max-batch", str(serve["max_batch"]),
         "--max-pages-per-seq", str(serve["max_pages_per_seq"]),
         *serve["worker_args"]])


# -- the file -------------------------------------------------------------------


def test_every_number_of_the_source_is_in_the_file():
    """The catalog's `config` of this architecture, key for key, but for
    the one key `reduced` lists. No width, depth, head count or
    vocabulary row is cut."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["source_url"] == SOURCE)
    b = body()
    assert set(b["published"]) == set(b["reduced_why"]) == set(REDUCED)
    for key, value in row["config"].items():
        if key in REDUCED:
            assert b["published"][key] == value and b[key] < value
            continue
        assert b[key] == value, key
    assert b["source"] == SOURCE and b["name"] == CONFIG
    assert b["max_position_embeddings"] == (
        b["serve"]["max_pages_per_seq"] * b["serve"]["page_size"]) == 8960


def test_the_file_states_what_the_preset_runs():
    """Every size the program's preset has is the file's: the published
    keys and the ones `assumed` from the class's defaults."""
    from dynamo_tpu.models.config import cache_plan, get_config

    b, c = body(), get_config(PRESET)
    assert b["serve"]["model"] == c.name
    assert (c.hidden, c.mlp_hidden, c.vocab_size) == (
        b["hidden_size"], b["intermediate_size"], b["vocab_size"])
    assert (c.n_q_heads, c.n_kv_heads, c.head_dim) == (
        b["num_attention_heads"], b["num_key_value_heads"],
        b["hidden_size"] // b["num_attention_heads"])
    assert c.n_layers == b["num_hidden_layers"] * b["mb_per_layer"] == 64
    assert c.mixers_per_layer == b["mb_per_layer"]
    assert (c.sliding_window, c.rms_eps) == (b["sliding_window"],
                                             b["layer_norm_eps"])
    assert (c.mamba_inner, c.ssm_state, c.mamba_dt_rank, c.conv_kernel) == (
        b["mamba_expand"] * b["hidden_size"], b["mamba_d_state"],
        b["mamba_dt_rank"], b["mamba_d_conv"])
    assert c.mamba_dt_rank == -(-b["hidden_size"] // 16)
    assert c.tie_embeddings and b["tie_word_embeddings"]
    assert c.attn_bias and b["attention_bias"] and not b["mlp_bias"]
    assert (c.norm_kind, c.norm_bias, c.diff_attention, c.use_rope) == (
        "layer", True, True, False)
    # which block is which: the file's rule, the reference's and the
    # preset's pattern
    kinds = module(REFERENCE, "reference").block_kinds(b)
    letters = {"mamba": "S", "window": "W", "full": "*", "gmu": "G",
               "cross": "X"}
    assert c.layer_pattern == "".join(letters[k] + "D" for k in kinds)
    assert [i for i, k in enumerate(kinds) if k == "full"] == [17]
    assert c.memory_layer == 2 * 16 and c.cross_decoder_start == 2 * 18
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                "mamba_dt_rank", "attention_bias", "which_blocks",
                "differential_attention", "norm", "window", "prefill",
                "weights", "source_of_these"):
        assert b["assumed"][key], key
    plan = cache_plan(c)
    assert plan.groups == ("full", "window") and plan.state
    assert (plan.group_layers, plan.group_readers) == ((1, 8), (8, 8))
    assert "one v5e holds the model whole" in b["deployment"]
    assert "nothing is shared between chips" in b["deployment"]


def test_the_workers_flags_are_the_files():
    from dynamo_tpu.engine.worker import _runner_config

    args, serve = worker_args(), body()["serve"]
    rc = _runner_config(args)
    assert (rc.page_size, rc.num_pages, rc.max_batch,
            rc.max_pages_per_seq) == (16, serve["num_pages"], 64, 560)
    assert rc.prefill_buckets == (512, 1024, 2048)
    assert rc.window_pages == int(
        serve["worker_args"][serve["worker_args"].index("--window-pages")
                             + 1])
    assert args.prewarm == "full" and serve["decode_block"] == 4
    # no flag an accepted cell's file does not use already
    assert set(serve["worker_args"][::2]) == {
        "--window-pages", "--prefill-buckets", "--prewarm"}
    assert serve["weight_dtype"] == serve["kv_dtype"] == "model"


@pytest.mark.parametrize("flags,said", [
    (dict(mode="prefill"), "two page groups"),
    (dict(kvbm=True), "two page groups"),
    (dict(spec=True), "multi-position"),
    (dict(kv_dtype="int8"), "int8 pool"),
    (dict(weight_dtype="int4"), "Mamba-1"),
    (dict(devices=4), "not sharded"),
])
def test_what_its_cache_cannot_do_is_refused_at_start(flags, said):
    from dynamo_tpu.engine.worker import recurrent_state_refusals
    from dynamo_tpu.models.config import get_config

    with pytest.raises(ValueError, match=said):
        recurrent_state_refusals(get_config(PRESET), **flags)
    recurrent_state_refusals(get_config(PRESET))  # as the cell serves it


# -- the counts -----------------------------------------------------------------


def test_the_counts_are_the_ones_worked_out_by_hand():
    """ISSUE 52's table, reckoned anew at 2 bytes a parameter, and the
    file's `memory` table against the shapes file."""
    c, b = counts(), body()
    z, p = c.sizes(b), c.mixer_params(b)
    assert (z["n_mamba"], z["n_window"], z["n_full"], z["n_gmu"],
            z["n_cross"], z["front"], z["tail"]) == (9, 8, 1, 7, 7, 18, 14)
    assert p["mlp"] == 3 * 2560 * 10240 == 78_643_200
    assert p["mamba"] == (26_214_400 + 20_480 + 5_120 + 983_040 + 819_200
                          + 5_120 + 81_920 + 5_120 + 13_107_200) == 41_241_600
    assert p["attention"] == (13_107_200 + 5_120 + 6_553_600 + 2_560
                              + 384) == 19_668_864
    assert p["gmu"] == 26_214_400
    assert p["cross"] == 2 * (6_553_600 + 2_560) + 384 == 13_112_704
    assert p["tied"] == 200_064 * 2560 == 512_163_840
    total = c.total_params(b)
    assert total == (32 * (78_643_200 + 2 * 5_120) + 9 * 41_241_600
                     + 9 * 19_668_864 + 7 * 26_214_400 + 7 * 13_112_704
                     + 512_163_840 + 5_120) == 3_852_562_944
    assert abs(total - 3852e6) / 3852e6 < 1e-3
    assert round(c.weight_bytes_per_step(b) / 1e9, 2) == 7.71
    # caches: ONE full layer where a plain stack of this geometry would
    # keep sixteen; eight window layers; nine states
    assert c.kv_bytes_per_token_layer(b) == 2 * 20 * 64 * 2 == 5_120
    assert c.kv_bytes_per_token(b) == 9 * 5_120
    assert c.full_readers(b) == 8
    assert c.state_slot_bytes(b) == 9 * (327_680 + 30_720) == 3_225_600
    serve = b["serve"]
    window_pages = int(serve["worker_args"][1])
    full_gb = serve["num_pages"] * 16 * 5_120 / 1e9
    window_gb = window_pages * 16 * 8 * 5_120 / 1e9
    state_gb = 64 * 3_225_600 / 1e9
    assert serve["num_pages"] == 64 * 560 + 16  # every row at 8,960
    assert window_pages >= 64 * (512 // 16 + 2) + 8 * 127 + 1
    assert (round(full_gb, 2), round(window_gb, 2), round(state_gb, 2)) == (
        2.94, 2.10, 0.21)
    for said in ("3,852,562,944", "7.71 GB", "2.94 GB", "2.10 GB",
                 "0.21 GB", "5,120 B", "327,680"):
        assert said in b["memory"], said
    # before the program's temporaries, of the 15.75 GiB the compiler has
    held = 7.71 + full_gb + window_gb + state_gb
    assert 12.9 < held < 13.0 < 15.75 * 2 ** 30 / 1e9 - 1.5
    # a decode step at the mix's 207 k live tokens in 64 rows: the shared
    # pages are the largest line, ahead of the weights
    live = 207_000
    shared = live * 8 * 5_120
    assert shared / 1e9 > 8.4 > c.weight_bytes_per_step(b) / 1e9
    assert c.attention_step_bytes(b, live) == pytest.approx(
        shared + live * 8 * 5_120 * 512 / 8960)
    assert c.decode_step_bytes(b, live, rows=64) == pytest.approx(
        c.weight_bytes_per_step(b) + c.attention_step_bytes(b, live)
        + 2 * 64 * 3_225_600)
    # never over: without rows, the fewest rows the tokens can be
    assert c.decode_step_bytes(b, live) < c.decode_step_bytes(b, live, 64)
    assert c.window_layer_kv_bytes(b, [100, 512, 9000]) == 1124 * 5_120
    # prefill: 18 blocks over every position, 14 and the head over one
    front = 18 * p["mlp"] + 9 * p["mamba"] + 9 * p["attention"]
    tail = 14 * p["mlp"] + 7 * p["gmu"] + 7 * p["cross"] + p["tied"]
    launch = c.prefill_launch_flops(b, 2048, 1, 1024)
    pair = 20 * c.attention_flops_per_key(b)
    assert c.attention_flops_per_key(b) == 2 * (2 * 64 + 2 * 128)
    assert launch == pytest.approx(
        2048 * (2 * front + 9 * 7 * 16 * 5120 + pair * (1024 + 8 * 512))
        + 2 * tail + pair * 7 * 2048)
    assert 2 * tail / (2048 * 2 * front) < 0.001  # the simple form's cost
    scan = c.selective_scan_floor(b, 2048, 1)
    assert scan["flops"] == 9 * 2048 * 7 * 16 * 5120
    assert scan["bytes"] == 9 * (2048 * (10 * 5120 + 64) + 2 * 327_680)
    update = c.selective_update_floor(b, 4, 64)
    assert update["bytes"] == 4 * 64 * 9 * 2 * 327_680


def test_the_shapes_keep_the_interface_and_import_no_jax():
    import sys

    from dtbench import shapes

    had = "jax" in sys.modules
    c = counts()
    assert had or "jax" not in sys.modules
    for fn in shapes.INTERFACE:
        assert callable(getattr(c, fn))
    for fn in ("sizes", "window_layer_kv_bytes", "prefill_launch_flops",
               "selective_scan_floor", "selective_update_floor",
               "state_slot_bytes", "full_readers"):
        assert callable(getattr(c, fn))
    assert body()["shapes"] == "benchmarks/shapes/phi4flash.py"


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
    assert "reduce_precision" in source and "lax.scan" in source
    # two softmaxes and a subtraction, not the padded form
    assert source.count("_softmax_values(q[:, :, ") == 2
    b = body()
    assert b["reference"] == {"module": os.path.relpath(REFERENCE, ROOT),
                              "dtype": "bfloat16", "weights": "model",
                              "weight_seed": 0}
    for key in ("hidden_size", "num_hidden_layers", "mb_per_layer",
                "num_attention_heads", "num_key_value_heads",
                "intermediate_size", "sliding_window", "layer_norm_eps",
                "vocab_size", "tie_word_embeddings", "mamba_expand",
                "mamba_d_state", "mamba_dt_rank", "mamba_d_conv"):
        assert key in b and f'"{key}"' in source, key


def test_the_controls_are_the_issues():
    """`act-fp8` for the limit; under `further_controls` the float32
    state a step down and the four that hold the architecture, by the
    keys the reference module knows (tests/test_phi4flash_model.py runs
    every one at the small size: each moves the logits far outside the
    tolerance)."""
    check = body()["check"]
    assert check["controls"] == {"act-fp8": {"act": "fp8"}}
    assert check["further_controls"] == FURTHER
    assert check["sample"] == 3 and set(check["limits"]) == {"gap_mean"}
    assert 0.0085 * 5 < check["limits"]["gap_mean"] == 0.05 < 0.265 / 5
    with open(REFERENCE) as f:
        source = f.read()
    for control in ({"act": "fp8"}, *FURTHER.values()):
        ((key, value),) = control.items()
        assert f'_choice(lower, "{key}", "{value}")' in source, control
    for name in ("act-fp8", *FURTHER):
        assert name in check["limits_why"] or name in check["controls_why"]


# -- the cell --------------------------------------------------------------------


def test_the_cells_entries_keep_the_contract_and_join_the_nine_lists():
    b = bench()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE and len(entry["source"]) < 200
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["reduced"] == REDUCED == list(body()["reduced_why"])
    assert len(entry["why"]) <= 200 and set(entry) == {
        "name", "source", "file", "reduced", "why"}
    for said in ("Mamba-1", "differential attention", "512 window",
                 "ONE full layer", "cross-attention", "gated memory",
                 "all 32 blocks"):
        assert said in entry["why"], said
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": MIX,
                    "chips": 1, "why": cell["why"]}
    for said in ("64 callers", "512-8192", "192-768", "drain < 30 s",
                 "8 reads of ONE layer's pages", "Mamba-1 state",
                 "18 of 32 blocks"):
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
            assert m["workloads"][-1] == CELL  # appended last
    declared = {m["name"] for m in b["per_layer"]}
    assert not declared & set(WAITING)
    for name in WAITING:  # their readers wait in the tree
        assert os.path.isfile(os.path.join(BENCH, "layers", name + ".py"))
    assert [w["name"] for w in b["workloads"]
            if w["config"] == CONFIG] == [CELL]
    assert sum(c["file"] == entry["file"] for c in b["configs"]) == 1


def test_the_harness_finds_the_cells_files_by_the_entries_names():
    import run as harness

    plan = harness.Plan(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert plan.config["serve"]["model"] == PRESET
    assert plan.mix["callers"] == "max_batch" and plan.mix["name"] == MIX
    assert plan.shapes.__file__.endswith("shapes/phi4flash.py")
    assert plan.reference_module.endswith("references/phi4flash.py")
    assert {m["name"] for m in plan.metrics("per_layer")} == set(NINE)
    assert {m["name"] for m in plan.metrics("end_to_end")} == {
        "out_tok_s", "setup_s"}
    flags = plan.worker_flags()
    assert flags[:2] == ["--model", PRESET]
    assert flags[-2:] == ["--prewarm", "full"]
    job = plan.reference_job([])
    assert job["pad_to"] == 8960 and job["module"] == plan.reference_module
    assert set(job["controls"]) == {"act-fp8"}
    for name in WAITING:
        assert callable(harness.Plan.reader(name))


def test_the_mix_is_the_issues_and_its_supply_outlasts_both_windows():
    mix, b = load("mixes", MIX + ".json"), bench()
    serve = body()["serve"]
    assert (mix["loop"], mix["callers"], mix["population"]) == (
        "closed", "max_batch", 512)
    assert (mix["ramp_seconds"], mix["start_spread_seconds"]) == (60, 60)
    assert mix["prompt_tokens"] == {"median": 2560, "sigma": 0.6,
                                    "min": 512, "max": 8192}
    assert mix["output_tokens"] == {"median": 448, "sigma": 0.4,
                                    "min": 192, "max": 768}
    assert mix["max_total_tokens"] == 8960 == 560 * 16
    pairs = traffic.population(mix, 1)
    prompts = [p for p, _ in pairs]
    answers = [o for _, o in pairs]
    assert round(sum(prompts) / 512) == 3004  # "about 3,000"
    assert round(sum(answers) / 512) == 471  # "about 480"
    assert max(p + o for p, o in pairs) <= 8960
    assert (min(prompts), max(prompts)) == (512, 8192)
    assert (min(answers), max(answers)) == (192, 768)
    # a steady batch of 64 rows each half way through its answer: the
    # live tokens ISSUE 52 plans by
    live = 64 * sum(p + o / 2 for p, o in pairs) / 512
    assert 205_000 < live < 209_000
    # a prompt takes 1 to 4 launches of 2,048, 1.9 on average: half the
    # rows on which the cross-decoder runs are not their prompt's last
    launches = [-(-p // 2048) for p in prompts]
    assert (min(launches), max(launches)) == (1, 4)
    assert round(sum(launches) / 512, 1) == 1.9
    # `run.py` hands a closed loop callers + 12 requests for each second
    # of ramp, window and tail; this server closes under 3 a second
    callers, ramp = serve["max_batch"], mix["ramp_seconds"]
    assert callers == 64
    for tail in (0.0, 45.0):
        total = ramp + b["run_seconds"] + tail
        handed = int(callers + 12 * total)
        asked = callers + 3 * total
        assert asked < handed / 3
    # ids from the whole vocabulary, and a seed past 2**31
    reqs = traffic.requests(mix, body()["vocab_size"], 2**31 + 7, 3)
    assert all(0 <= t < 200064 for r in reqs for t in r.prompt)


def test_the_prompt_tokens_a_block_behind_decode_block():
    """The scheduler makes ONE prefill launch (2,048 positions at most)
    between two fused decode blocks (ROADMAP A7). A block of b steps over
    64 rows emits 64 b tokens, which closes 64 b / 471 requests, each
    replaced by a prompt of 3,004 tokens: at 4 that is 1,634 prompt
    tokens a block, inside one launch; at 8 it is 3,268, which one
    launch does not supply, and rows stand empty."""
    mix, serve = load("mixes", MIX + ".json"), body()["serve"]
    pairs = traffic.population(mix, 1)
    prompt = sum(p for p, _ in pairs) / 512
    answer = sum(o for _, o in pairs) / 512
    budget = 2048

    def needed(block):
        return serve["max_batch"] * block / answer * prompt

    assert round(needed(4)) == 1634 and needed(4) < budget < needed(8)
    assert round(64 * 4 / answer, 2) == 0.54  # requests closed a block
    assert serve["decode_block"] == 4
    for said in ("decode_block 4", "1,634", "3,268"):
        assert said in body()["serve_why"], said


def test_the_mixs_prefill_shapes_are_the_workers_prewarm_grid():
    """`ModelRunner.prewarm(launches=True)` derives its grid from the
    runner's buckets and token budget, rows x bucket inside the budget
    (`bounds_prefill_launches`: a window group); the table widths the
    mix's contexts reach are widths the worker compiles."""
    from dynamo_tpu.engine.model_runner import bucket_table_width

    mix, args = load("mixes", MIX + ".json"), worker_args()
    warm, serve = mix["warm"], body()["serve"]
    buckets = sorted(int(x) for x in args.prefill_buckets.split(","))
    budget = buckets[-1]
    grid = {(rows, b) for rows in (1, 2, 4, 8) for b in buckets
            if rows <= budget // buckets[0] and rows * b <= budget}
    assert {tuple(s) for s in warm["prefill_shapes"]} == grid
    assert len(grid) == 6
    cap = serve["max_pages_per_seq"]
    widths, width = [], bucket_table_width(1, cap)
    while True:
        widths.append(width)
        if width >= cap:
            break
        width = bucket_table_width(width + 1, cap)
    assert widths == [8, 16, 32, 64, 128, 256, 512, 560]
    programs = len(grid) + 2 * len(widths) + 1
    assert programs == 23 and "23 programs" in body()["serve_why"]
    lo = mix["prompt_tokens"]["min"] + 1
    reach = {bucket_table_width(-(-(kv + 16) // 16), cap)
             for kv in range(lo, mix["max_total_tokens"] + 1, 7)}
    assert reach == set(warm["table_widths"]) == {64, 128, 256, 512, 560}
    assert max(warm["decode"]) + warm["decode_tokens"] <= 8960

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
    rows = [stats.Timeline(index=i, due=0.0, sent=0.0, n_prompt=n,
                           want_tokens=10, first=1.0, end=None)
            for i, n in enumerate((600, 2500, 5000, 8000))]
    trace = {"ops": {
        "paged_decode_attention_pool": {"seconds": 0.0160, "count": 320},
        "paged_decode_attention_window": {"seconds": 0.0032, "count": 320},
        "fusion": {"seconds": 0.1408, "count": 900}},
        "modules": {"jit_multi": {"count": 10, "seconds": 0.16}}}
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    ctx = reader_ctx(trace=trace, timelines=rows, peaks=peaks)
    ctx["window"].update(capture_at=10.0, capture_end=12.5)
    c, b = counts(), body()
    live = stats.mean_live_decode_tokens(rows, 10.0, 12.5)
    assert ctx["read"]("decode_step_dev_ms") == pytest.approx(4.0)
    # both decode kernels under one pattern: eight reads of the full
    # group's live rows and eight window reads a step
    assert ctx["read"]("paged_attn_roofline_pct") == pytest.approx(
        100 * c.attention_step_bytes(b, live) / 819e9 / (0.0192 / 40))
    assert ctx["read"]("decode_hbm_roofline_pct") == pytest.approx(
        100 * c.decode_step_bytes(b, live) / 819e9 / 0.004)
    contexts = [t.n_prompt for t in rows]
    assert ctx["read"]("window_attn_roofline_pct") == pytest.approx(
        100 * c.window_layer_kv_bytes(b, contexts) / 819e9
        / (0.0032 / 320))
    assert ctx["read"]("window_attn_dev_share_pct") == pytest.approx(2.0)
    names = b["trace_names"]
    assert names["attention_kernels"] == "^paged_decode_attention"
    assert names["window_attention_kernels"] == (
        "^paged_decode_attention_window")
    # the one-token state update is an XLA fusion: no kernel to name
    assert "ssm_decode_kernels" not in names
    ctx["window"]["before"] = {
        "dynamo_prefill_cross_decoder_rows_total": [
            ({"chunk": "last"}, 10.0), ({"chunk": "earlier"}, 4.0)],
        "dynamo_kv_page_layer_reads_total": [
            ({"by": "owner"}, 900.0), ({"by": "shared"}, 700.0)],
        "dynamo_ssm_state_slot_ms": [({}, 0.0)],
        "dynamo_step_part_ms_total": [({"part": "wall"}, 0.0)]}
    ctx["window"]["after"] = {
        "dynamo_prefill_cross_decoder_rows_total": [
            ({"chunk": "last"}, 110.0), ({"chunk": "earlier"}, 97.0)],
        "dynamo_kv_page_layer_reads_total": [
            ({"by": "owner"}, 9900.0), ({"by": "shared"}, 7700.0)],
        "dynamo_ssm_state_slot_ms": [({}, 60 * 1000.0)],
        "dynamo_step_part_ms_total": [({"part": "wall"}, 1000.0)]}
    assert ctx["read"]("prefill_cross_wasted_pct") == pytest.approx(
        100 * 93 / 193)
    assert ctx["read"]("kv_shared_read_share_pct") == pytest.approx(43.75)
    assert ctx["read"]("ssm_state_live_pct") == pytest.approx(100 * 60 / 64)
    # a program without the counters (the parent): the line leaves them out
    ctx["window"]["before"] = ctx["window"]["after"] = {}
    assert ctx["read"]("prefill_cross_wasted_pct") is None
    assert ctx["read"]("kv_shared_read_share_pct") is None


# -- the run's budget ------------------------------------------------------------


def test_the_runs_budget_is_a_sum_of_measured_parts():
    """Rule 13: process start to last line <= 320 s warm under --trace 2.
    The parts as the builder measured them on the chip (my chip runs,
    PR 52; PERF.md section 4 keeps the readings), each written into the
    file's `budget` beside its sum."""
    mix, b, budget = load("mixes", MIX + ".json"), bench(), body()["budget"]
    parts = budget["warm_trace2_parts_s"]
    assert set(parts) == {"model_listed", "crafted_cross_check",
                          "prompt_ids", "ramp", "window", "tail_and_drain",
                          "reference_and_reduction"}
    assert parts["ramp"] == mix["ramp_seconds"] == 60
    assert parts["window"] == b["run_seconds"] == 50
    assert sum(parts.values()) == pytest.approx(budget["warm_trace2_s"],
                                                abs=1.0)
    assert budget["warm_trace2_s"] <= 320
    assert budget["cold_model_listed_s"] < 900  # the harness's wait
    assert budget["programs"] == 23
