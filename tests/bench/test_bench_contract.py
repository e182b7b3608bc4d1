"""BENCHMARK.json against its contract, the harness against its promises:
data-driven, JAX-free, no result without the chip or the program."""

import json
import os
import random
import re
import shutil
import subprocess
import sys
import uuid

import pytest

from bench_paths import BENCH, ROOT
from dtbench import stats, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_keys_names_and_units():
    b = bench()
    assert set(b) - {"trace_in_run"} == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    # one run that measures, then traces (--trace 2): true or absent
    assert b.get("trace_in_run", True) is True
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert b["paths"] == ["benchmarks", "tests/bench"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in b[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "setup_s" in [m["name"] for m in b["end_to_end"]]
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
    assert len(json.dumps(b)) < 64 * 1024


def test_cells_configs_and_files():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    assert len({c["file"] for c in b["configs"]}) == len(configs)
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in b["workloads"]} == set(configs)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 4)
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert os.path.isfile(os.path.join(BENCH, "mixes",
                                           w["traffic"] + ".json"))
    widths = re.compile(r"hidden|intermediate|latent|state|proj|_dim$|_rank$"
                        r"|head_dim|expan|experts_per_tok")
    for c in b["configs"]:
        assert PATH.match(c["file"]) and c["file"].startswith("benchmarks/")
        assert c["source"].startswith("https://") and len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"] if widths.search(k)]
        body = load(os.path.relpath(os.path.join(ROOT, c["file"]), BENCH))
        assert body["source"] == c["source"]
        assert set(c["reduced"]) == set(body.get("reduced_why", {}))
    for m in b["per_layer"]:  # a reader of its own, or the client's number
        assert m["name"] in stats.CLIENT_METRICS or os.path.isfile(
            os.path.join(BENCH, "layers", m["name"] + ".py")), m["name"]
    for path in b["paths"]:
        for base, _dirs, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in base:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert PATH.match(rel), rel


@pytest.mark.parametrize("name,preset", [
    ("mistral-7b-w4kv8", "mistral-7b"), ("tiny-test", "tiny-test")])
def test_a_configuration_file_states_what_the_program_runs(name, preset):
    """The file's sizes are the program's preset's: the reference is
    built from the file, the server from the preset. This is the dense
    architecture's own test (the keys it reads are the dense block's); a
    configuration of another architecture brings the like in a test file
    of its own, beside the reference and the counts it names."""
    from dynamo_tpu.models.config import get_config

    body, cfg = load("configs", name + ".json"), get_config(preset)
    assert body["serve"]["model"] == preset
    assert (body["hidden_size"], body["intermediate_size"],
            body["num_hidden_layers"], body["num_attention_heads"],
            body["num_key_value_heads"], body["head_dim"],
            body["vocab_size"]) == (
        cfg.hidden, cfg.mlp_hidden, cfg.n_layers, cfg.n_q_heads,
        cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size)
    assert body["rms_norm_eps"] == cfg.rms_eps
    assert body["rope_theta"] == cfg.rope_theta
    assert body["tie_word_embeddings"] == cfg.tie_embeddings
    assert body["qk_norm"] == cfg.qk_norm
    assert body["max_position_embeddings"] == cfg.max_context
    assert body["reference"]["dtype"] == cfg.dtype
    want = {"int4": "int4", "model": "model"}[body["serve"]["weight_dtype"]]
    assert body["reference"]["weights"] == want


def configuration_files():
    """Every configuration BENCHMARK.json has, every file under
    benchmarks/configs/, and the fixture that names all three keys."""
    files = {c["file"] for c in bench()["configs"]}
    files |= {"benchmarks/configs/" + f
              for f in os.listdir(os.path.join(BENCH, "configs"))}
    return sorted(files | {"tests/bench/named/config.json"})


@pytest.mark.parametrize("config_file", configuration_files())
def test_what_a_configuration_names_is_there_and_under_the_paths(config_file):
    """`reference.module`, `shapes` and `serve.worker_args`: each named
    file exists, is named as a file under `paths` must be and lies under
    one of them; the counts expose the readers' five functions without
    JAX; the reference defines `logits_for`. (`Plan` refuses the same at
    run time; this holds what is committed to it.)"""
    import ast

    from dtbench import shapes

    with open(os.path.join(ROOT, config_file)) as f:
        body = json.load(f)
    named = {"reference.module": body["reference"].get("module"),
             "shapes": body.get("shapes")}
    for key, rel in named.items():
        if rel is None:
            continue
        assert PATH.match(rel) and not rel.startswith("/"), (key, rel)
        assert ".." not in rel.split("/") and rel.endswith(".py")
        assert any(rel.startswith(p + "/") for p in bench()["paths"]), rel
        assert os.path.isfile(os.path.join(ROOT, rel)), (key, rel)
    if named["shapes"]:
        code = ("import sys, importlib.util as u; "
                f"spec = u.spec_from_file_location('counts', {named['shapes']!r}); "
                "m = u.module_from_spec(spec); spec.loader.exec_module(m); "
                f"ok = all(callable(getattr(m, fn, None)) for fn in {shapes.INTERFACE!r}); "
                "raise SystemExit(0 if ok and 'jax' not in sys.modules else 1)")
        assert subprocess.run([sys.executable, "-c", code],
                              cwd=ROOT).returncode == 0
    if named["reference.module"]:
        with open(os.path.join(ROOT, named["reference.module"])) as f:
            tree = ast.parse(f.read())
        assert "logits_for" in [n.name for n in tree.body
                                if isinstance(n, ast.FunctionDef)]
    args = body["serve"].get("worker_args", [])
    assert isinstance(args, list) and all(isinstance(a, str) for a in args)
    assert shapes.INTERFACE == (
        "weight_bytes_per_step", "kv_bytes_per_token", "decode_step_bytes",
        "attention_step_bytes", "flops_per_token")
    assert all(callable(getattr(shapes, fn)) for fn in shapes.INTERFACE)


def test_importing_the_harness_leaves_jax_out():
    code = ("import sys; sys.argv=['run.py']; "
            f"sys.path.insert(0, {BENCH!r}); import run; "
            "from dtbench import modes, fleet, client, stats, traffic, shapes; "
            "raise SystemExit(1 if any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules) else 0)")
    assert subprocess.run([sys.executable, "-c", code],
                          cwd=ROOT).returncode == 0


def test_no_chip_is_an_error_in_seconds_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         bench()["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 2 and out.stdout == ""
    assert "no result" in out.stderr


def test_without_the_program_there_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the paths."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         bench()["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=60)
    assert out.returncode not in (0, 10) and out.stdout == ""


# -- driven by data ---------------------------------------------------------


def test_a_new_cell_is_files_and_entries_and_no_edit(tmp_path):
    """A configuration, a mix and a per-layer reader dropped beside the
    real ones are found by the names a BENCHMARK.json gives them."""
    import run as harness

    tag = "tmp" + uuid.uuid4().hex[:8]
    cfg = dict(load("configs", "tiny-test.json"), name=tag, vocab_size=777)
    mix = dict(load("mixes", "rehearsal.json"), name=tag, population=16)
    files = [os.path.join(BENCH, "configs", tag + ".json"),
             os.path.join(BENCH, "mixes", tag + ".json"),
             os.path.join(BENCH, "layers", tag + "_pct.py")]
    b = bench()
    b["configs"].append({"name": tag, "source": "https://example.org/" + tag,
                         "file": f"benchmarks/configs/{tag}.json",
                         "reduced": [], "why": "a later PR's model"})
    b["workloads"].append({"name": f"{tag}.{tag}", "config": tag,
                           "traffic": tag, "chips": 1, "why": "its cell"})
    b["per_layer"].append({"name": tag + "_pct", "unit": "%",
                           "better": "higher", "source": "program_counter",
                           "layer": "Paged cache", "moves": "out_tok_s",
                           "workloads": [f"{tag}.{tag}"]})
    try:
        for path, body in zip(files[:2], (cfg, mix)):
            with open(path, "w") as f:
                json.dump(body, f)
        with open(files[2], "w") as f:
            f.write("def read(ctx):\n"
                    "    return 100.0 * ctx['window']['after']['hits']"
                    " / ctx['mix']['population']\n")
        with open(tmp_path / "BENCHMARK.json", "w") as f:
            json.dump(b, f)
        os.symlink(BENCH, tmp_path / "benchmarks")
        plan = harness.Plan(str(tmp_path / "BENCHMARK.json"), f"{tag}.{tag}")
        assert plan.config["vocab_size"] == 777 and plan.mix["name"] == tag
        names = [m["name"] for m in plan.metrics("per_layer")]
        assert tag + "_pct" in names
        other = harness.Plan(str(tmp_path / "BENCHMARK.json"),
                             b["workloads"][0]["name"])
        assert tag + "_pct" not in [m["name"]
                                    for m in other.metrics("per_layer")]
        ctx = {"window": {"after": {"hits": 4}}, "mix": plan.mix,
               "client": {"ttft_p50_ms": 12.5}}
        assert harness.Plan.reader(tag + "_pct")(ctx) == 25.0
        # a number the client's arithmetic gives needs no file; any other
        # name without a file is an error, not a silent gap in the line
        assert harness.Plan.reader("ttft_p50_ms")(ctx) == 12.5
        with pytest.raises(SystemExit):
            harness.Plan.reader(tag + "_missing")
        reqs = traffic.requests(plan.mix, plan.config["vocab_size"], 1, 20)
        assert len(reqs) == 20 and max(max(r.prompt) for r in reqs) < 777
    finally:
        for path in files:
            if os.path.exists(path):
                os.remove(path)


# -- the warm list covers what the mix can reach ------------------------------

BUCKETS = (32, 64, 128, 256, 512, 1024, 2048)  # the runner's prefill buckets
BUDGET = 2048  # prefill tokens per scheduler iteration, shared by rows


def bucket(n):
    return next(b for b in BUCKETS if n <= b)


def pow2(n):
    return 1 << max(0, n - 1).bit_length()


def table_width(tokens, page=16, cap=64):
    need, width = -(-tokens // page), 8
    while width < need:
        width *= 2
    return min(width, cap)


def prefill_step(slots, seen):
    """One scheduler iteration of `_prefill_some`: rows in slot order,
    each min(budget left, prompt left); stop under one page of budget."""
    spent, rows = 0, []
    for s in slots:
        if s is None or s["left"] == 0:
            continue
        if BUDGET - spent < 16:
            break
        chunk = min(BUDGET - spent, s["left"])
        rows.append(chunk)
        spent += chunk
        s["left"] -= chunk
    if rows:
        shape = (pow2(len(rows)), bucket(max(rows)))
        seen[shape] = seen.get(shape, 0) + 1


def decode_step(slots):
    for i, s in enumerate(slots):
        if s and s["left"] == 0:
            s["blocks"] -= 1
            if s["blocks"] <= 0:
                slots[i] = None


def simulate(pairs, loop, iterations, seed, rows=32, rate=0.8):
    """The shapes a run reaches, counted: a closed loop of `rows` callers
    that all start at once (the worst start), or Poisson arrivals at
    `rate` per iteration; a sequence decodes for ceil(out / 8) blocks."""
    rng = random.Random(seed)
    slots, queue, seen = [None] * rows, [], {}

    def new():
        n_prompt, n_out = rng.choice(pairs)
        return {"left": n_prompt, "blocks": -(-n_out // 8)}

    for _ in range(iterations):
        if loop == "closed":
            queue = [new() for s in slots if s is None]
        else:
            t = 0.0
            while (t := t + rng.expovariate(rate)) < 1.0:
                queue.append(new())
        for i, s in enumerate(slots):
            if s is None and queue:
                slots[i] = queue.pop(0)
        prefill_step(slots, seen)
        decode_step(slots)
    return seen


@pytest.mark.parametrize("name,loop,rows", [
    ("chunk-sat", "closed", 32), ("chunk-sat", "open", 32)])
def test_the_warm_list_covers_every_program_the_mix_can_reach(name, loop,
                                                              rows):
    """In each cell's closed loop, and under Poisson arrivals at four
    fifths of an iteration's capacity (a later open-loop cell)."""
    mix = dict(load("mixes", name + ".json"), loop=loop)
    warm = mix["warm"]
    listed = {tuple(s) for s in warm["prefill_shapes"]}
    rare = {tuple(s) for s in warm["rare_prefill_shapes"]}
    seen = {}
    for seed in (1, 2):
        pairs = traffic.population(mix, seed)
        for shape, n in simulate(pairs, mix["loop"], 50000, seed, rows=rows).items():
            seen[shape] = seen.get(shape, 0) + n
    assert set(seen) <= listed | rare, sorted(set(seen) - listed - rare)
    assert sum(n for s, n in seen.items() if s in rare) < 1e-4 * sum(
        seen.values())
    # every listed shape has a crafted warm-up that lands on it
    crafted = {(1, 32)} | {(1, bucket(n)) for n in warm["lone_prefill"]}
    for group in warm["groups"]:
        assert sum(group) <= BUDGET
        crafted.add((pow2(len(group)), bucket(max(group))))
    assert crafted == listed
    # decode: the fused block's table covers kv + 16 speculated tokens
    lo = mix["prompt_tokens"]["min"] + 1
    reach = {table_width(kv + 16) for kv in range(
        lo, mix["max_total_tokens"] + 1)}
    assert reach == set(warm["table_widths"])
    assert {table_width(n + 1 + 16) for n in warm["decode"]} == reach
    assert all(table_width(n + warm["decode_tokens"] + 16) in reach
               for n in warm["decode"])
    # one width, and it is the cap: no stale slot length can exceed the
    # table in use (the decode attention kernel's prefetch fault, PERF.md)
    cap = load("configs", "mistral-7b-w4kv8.json")["serve"][
        "max_pages_per_seq"]
    assert reach == {cap} and mix["max_total_tokens"] + 16 <= cap * 16
    assert warm["blocker"] < 1024 and max(warm["lone_prefill"]) < 1024
    assert {fn: n for fn, n in warm["programs"].items()} == {
        "prefill": len(warm["lone_prefill"]) + 1,
        "prefill_batch": len(warm["groups"]),
        "decode_multi": 2 * len(warm["table_widths"])}
