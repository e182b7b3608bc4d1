"""What depends on a configuration's architecture is found through the
configuration's own file: its plain reference (`reference.module`), its
byte and operation counts (`shapes`) and its worker's further flags
(`serve.worker_args`). The fixture `tests/bench/named/config.json` is
tiny-test again with all three named (stand-ins under tests/bench/named/:
no model, only numbers that could come from nowhere else). A
configuration that names none is served and checked exactly as it was
before the keys existed."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_paths import BENCH, ROOT
from dtbench import reference, shapes, stats

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = "tests/bench/named/config.json"
ACCEPTED = {"mistral-7b-w4kv8": "chunk-sat", "tiny-test": "rehearsal"}


def checkout(tmp_path, config_file, config=None, traffic="rehearsal"):
    """A BENCHMARK.json of one cell in a directory that holds the
    benchmark's two paths, as a checkout does. `config` (a dict) is
    written there as the configuration's file."""
    if not os.path.exists(tmp_path / "benchmarks"):
        os.symlink(BENCH, tmp_path / "benchmarks")
        os.makedirs(tmp_path / "tests")
        os.symlink(HERE, tmp_path / "tests" / "bench")
    if config is not None:
        config_file = "case.json"
        with open(tmp_path / config_file, "w") as f:
            json.dump(config, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    bench = dict(
        real, run_seconds=5,
        configs=[{"name": "case", "source": "https://example.org/case",
                  "file": config_file, "reduced": [], "why": "a test's"}],
        workloads=[{"name": "case." + traffic, "config": "case",
                    "traffic": traffic, "chips": 1, "why": "a test's"}])
    for group in ("end_to_end", "per_layer"):
        bench[group] = [{k: v for k, v in m.items() if k != "workloads"}
                        for m in real[group]]
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(tmp_path / "BENCHMARK.json"), "case." + traffic


def plan_of(tmp_path, config_file, config=None, traffic="rehearsal"):
    import run as harness

    return harness.Plan(*checkout(tmp_path, config_file, config, traffic))


def fixture():
    with open(os.path.join(ROOT, FIXTURE)) as f:
        return json.load(f)


def samples_for(vocab, seed=11):
    rng = np.random.default_rng(seed)
    return [{"prompt": rng.integers(0, vocab, n_p).tolist(),
             "served": rng.integers(0, vocab, n_s).tolist()}
            for n_p, n_s in ((9, 7), (30, 12))]


def run_child(tmp_path, job):
    """The reference child, as `Run.reference` starts it."""
    with open(tmp_path / "job.json", "w") as f:
        json.dump(job, f)
    child = subprocess.run(
        [sys.executable, os.path.join(BENCH, "dtbench", "reference.py"),
         str(tmp_path / "job.json"), str(tmp_path / "out.json")],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert child.returncode == 0, child.stderr[-2000:]
    with open(tmp_path / "out.json") as f:
        return json.load(f)


# -- (a), (b): the reference, by name, handed the whole file --------------


def test_the_named_reference_decides_the_numbers_not_the_dense_one(tmp_path):
    plan = plan_of(tmp_path, FIXTURE)
    assert plan.reference_module == str(
        tmp_path / "tests/bench/named/reference.py")
    samples = samples_for(plan.config["vocab_size"])
    job = plan.reference_job([
        {"label": "a", "samples": samples, "control": True}])
    out = run_child(tmp_path, job)
    assert out["module"] == plan.reference_module
    served = out["sets"][0]["served"]
    # every served token exactly `rope_scaling.factor` below the best:
    # the stand-in's logits, through the shared `compare`
    assert served == {"positions": 19, "gap_max": 0.125, "gap_mean": 0.125,
                      "off_best_share": 1.0,
                      "logit_std": served["logit_std"]}
    assert set(out["sets"][0]["controls"]) == {"shifted"}
    # the same job without the name is the dense reference's: another answer
    dense = run_child(tmp_path, dict(job, module=None))
    assert dense["module"].startswith("dtbench/reference.py")
    assert dense["sets"][0]["served"]["gap_max"] > 1.0  # random tokens
    assert dense["sets"][0]["served"]["positions"] == 19


def test_a_nested_key_of_the_file_reaches_the_named_module(tmp_path):
    """`rope_scaling` is a dict, and the flat `config` drops it; the
    module's `cfg` is the whole file with the flat one on top."""
    cfg = dict(fixture(), rope_scaling={"type": "fixture", "factor": 0.5})
    plan = plan_of(tmp_path, None, config=cfg)
    job = plan.reference_job([{"label": "a", "samples": samples_for(512)}])
    assert "rope_scaling" not in job["config"]
    assert job["file"]["rope_scaling"]["factor"] == 0.5
    assert job["file"]["serve"] == cfg["serve"]
    assert job["file"]["check"] == cfg["check"]
    out = run_child(tmp_path, job)
    assert out["sets"][0]["served"]["gap_mean"] == 0.5
    # in this process too: `main` is the one program, whoever calls it
    assert reference.main(["reference.py", str(tmp_path / "job.json"),
                           str(tmp_path / "again.json")]) == 0
    with open(tmp_path / "again.json") as f:
        assert json.load(f)["sets"] == out["sets"]


# -- (c): with the keys absent, the job is the parent's ---------------------


def parents_job(config, mix, sets):
    """`Run.reference`'s expression at the parent commit (PR 27)."""
    cfg = dict(config)
    ref = cfg.pop("reference")
    return {
        "config": {**{k: v for k, v in cfg.items()
                      if not isinstance(v, (dict, list))}, **ref},
        "pad_to": -(-int(mix["max_total_tokens"]) // 256) * 256,
        "controls": config["check"]["controls"],
        "sets": sets,
    }


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_without_the_keys_the_job_is_what_it_was(tmp_path, name):
    plan = plan_of(tmp_path, f"benchmarks/configs/{name}.json",
                   traffic=ACCEPTED[name])
    sets = [{"label": "a", "samples": samples_for(64), "control": False}]
    job = plan.reference_job(sets)
    want = parents_job(plan.config, plan.mix, sets)
    assert {k: job[k] for k in want} == want
    assert list(job)[:4] == list(want)  # and in the parent's order
    assert set(job) - set(want) == {"file", "module"}
    assert job["module"] is None and job["file"] == plan.config
    assert plan.reference_module is None and plan.shapes is shapes
    assert "module" not in job["config"] and "shapes" not in job["config"]


def test_the_accepted_cells_job_pinned():
    """The accepted cell, through the real BENCHMARK.json: the numbers
    its reference is built from, pinned."""
    import run as harness

    plan = harness.Plan(os.path.join(ROOT, "BENCHMARK.json"),
                        "m7b-w4kv8.chunk-sat")
    job = plan.reference_job([])
    assert job["pad_to"] == 1024
    assert job["controls"] == {"kv-int4": {"kv_bits": 4},
                               "act-fp8": {"act": "fp8"}}
    assert job["config"] == {
        "name": "mistral-7b-w4kv8",
        "source": "https://huggingface.co/mistralai/Mistral-7B-v0.3/blob/"
                  "main/config.json",
        "model_type": "mistral", "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 14336, "num_hidden_layers": 32,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "head_dim": 128, "vocab_size": 32768, "rms_norm_eps": 1e-06,
        "rope_theta": 1000000.0, "max_position_embeddings": 8192,
        "sliding_window": None, "tie_word_embeddings": False,
        "torch_dtype": "bfloat16", "qk_norm": False,
        "deployment": plan.config["deployment"],
        "memory": plan.config["memory"],
        "dtype": "bfloat16", "weights": "int4", "weight_seed": 0}


# -- (d): the counts, by name -------------------------------------------------


def test_a_reader_calls_the_configurations_own_counts(tmp_path):
    import run as harness

    plan = plan_of(tmp_path, FIXTURE)
    assert plan.shapes.__file__ == str(
        tmp_path / "tests/bench/named/counts.py")
    # a decode block of 8 steps launched 10 times in 0.16 s of device
    # time: 2 ms a step; no request is live in the capture
    ctx = plan.context(
        peaks={"hbm_bytes_per_s": 1.0e6}, timelines=[], client={},
        window={"capture_at": 10.0, "capture_end": 12.5},
        trace={"modules": {"jit_multi": {"count": 10, "seconds": 0.16}},
               "ops": {"paged_decode_attention_pool.3": {"seconds": 0.04}}})
    assert ctx["shapes"] is plan.shapes and ctx["stats"] is stats
    assert harness.Plan.reader("decode_step_dev_ms")(ctx) == pytest.approx(2.0)
    # the fixture's 4096 B of weights over 1e6 B/s = 4.096 ms least, over
    # the step's 2 ms (a stand-in: no real count passes 100%)
    assert harness.Plan.reader("decode_hbm_roofline_pct")(ctx) == (
        pytest.approx(100.0 * 4.096 / 2.0))
    # one live sequence of 700 context tokens all through the capture:
    # 576 B of latent cache a token, as the fixture's file says
    live = stats.Timeline(index=0, due=0.0, sent=0.0, n_prompt=700,
                          want_tokens=100, first=9.0)
    ctx["timelines"] = [live]
    tokens = stats.mean_live_decode_tokens([live], 10.0, 12.5)
    assert tokens == 700
    assert harness.Plan.reader("decode_hbm_roofline_pct")(ctx) == (
        pytest.approx(100.0 * (4096 + 576 * tokens) / 1.0e6 / 2.0e-3))
    # 80 steps, 0.04 s of kernel time: 0.5 ms a step
    assert harness.Plan.reader("paged_attn_roofline_pct")(ctx) == (
        pytest.approx(100.0 * 576 * tokens / 1.0e6 / 0.5e-3))
    # the accepted configuration's readers get the dense counts
    dense = plan_of(tmp_path, "benchmarks/configs/tiny-test.json")
    assert dense.context()["shapes"] is shapes


# -- (e): the worker's further flags ---------------------------------------


def parents_flags(serve):
    """`Run.__init__`'s worker arguments at the parent commit (PR 27)."""
    return ["--model", serve["model"],
            "--weight-dtype", serve["weight_dtype"],
            "--kv-dtype", serve["kv_dtype"],
            "--page-size", str(serve["page_size"]),
            "--num-pages", str(serve["num_pages"]),
            "--max-batch", str(serve["max_batch"]),
            "--max-pages-per-seq", str(serve["max_pages_per_seq"])]


@pytest.mark.parametrize("config_file", [
    FIXTURE, "benchmarks/configs/mistral-7b-w4kv8.json",
    "benchmarks/configs/tiny-test.json"])
def test_worker_args_end_the_workers_argument_list(tmp_path, config_file):
    plan = plan_of(tmp_path, config_file)
    serve = plan.config["serve"]
    flags = plan.worker_flags()
    assert flags[:14] == parents_flags(serve)
    assert flags[14:] == serve.get("worker_args", [])
    if config_file == FIXTURE:
        assert flags[14:] == ["--kvbm-host-blocks", "16"]
    else:
        assert len(flags) == 14 and "worker_args" not in serve


def test_the_accepted_cells_worker_flags_pinned():
    import run as harness

    plan = harness.Plan(os.path.join(ROOT, "BENCHMARK.json"),
                        "m7b-w4kv8.chunk-sat")
    assert plan.worker_flags() == [
        "--model", "mistral-7b", "--weight-dtype", "int4", "--kv-dtype",
        "int8", "--page-size", "16", "--num-pages", "5120", "--max-batch",
        "32", "--max-pages-per-seq", "64"]


# -- (f): a bad name fails in seconds, before any child ------------------------


BAD = {
    "reference-missing": (
        lambda c: c["reference"].update(module="benchmarks/references/no.py"),
        ["'reference.module'", "benchmarks/references/no.py", "is missing"]),
    "reference-without-logits_for": (
        lambda c: c["reference"].update(module="tests/bench/named/counts.py"),
        ["'reference.module'", "tests/bench/named/counts.py",
         "defines no logits_for"]),
    "reference-outside-the-paths": (
        lambda c: c["reference"].update(module="dynamo_tpu/models/config.py"),
        ["'reference.module'", "dynamo_tpu/models/config.py",
         "lies under none of the benchmark's paths"]),
    "reference-leading-out": (
        lambda c: c["reference"].update(
            module="benchmarks/../tests/bench/named/reference.py"),
        ["'reference.module'", "is no relative path"]),
    "reference-absolute": (
        lambda c: c["reference"].update(
            module=os.path.join(ROOT, "tests/bench/named/reference.py")),
        ["'reference.module'", "is no relative path"]),
    "shapes-missing": (
        lambda c: c.update(shapes="benchmarks/shapes/no.py"),
        ["'shapes'", "benchmarks/shapes/no.py", "is missing"]),
    "shapes-without-its-interface": (
        lambda c: c.update(shapes="tests/bench/named/reference.py"),
        ["'shapes'", "tests/bench/named/reference.py", "lacks",
         "kv_bytes_per_token"]),
    "shapes-importing-jax": (
        lambda c: c.update(shapes="benchmarks/dtbench/reference.py"),
        ["'shapes'", "benchmarks/dtbench/reference.py", "imports JAX"]),
    "worker_args-not-a-list": (
        lambda c: c["serve"].update(worker_args="--tp 4"),
        ["'serve.worker_args'", "'--tp 4'", "is not a list of strings"]),
}


@pytest.mark.parametrize("case", sorted(BAD))
def test_a_bad_name_ends_the_run_before_any_child_starts(tmp_path, case):
    """Through the command line, as the driver would meet it: non-zero,
    the configuration, the key and the path in the message, nothing on
    standard output, no worker started (no scratch directory made), and
    in seconds. JAX_PLATFORMS is left unset: the refusal comes before
    the look for a chip."""
    change, said = BAD[case]
    cfg = fixture()
    change(cfg)
    bench_json, cell = checkout(tmp_path, None, config=cfg)
    scratch = os.path.join(ROOT, ".bench_cache", "run", cell)
    assert not os.path.exists(scratch)
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark-json",
         bench_json, "--workload", cell, "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=60)
    assert out.returncode not in (0, 10), out.stderr
    assert out.stdout == ""
    assert "configuration 'case'" in out.stderr and "no result" in out.stderr
    for part in said:
        assert part in out.stderr, out.stderr
    assert not os.path.exists(scratch)


def test_a_sound_name_passes_the_same_door(tmp_path):
    """The fixture itself gets past `Plan` (and is then refused for the
    lack of a chip, like any cell): the refusals above are the names'."""
    bench_json, cell = checkout(tmp_path, FIXTURE)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark-json",
         bench_json, "--workload", cell, "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 2 and out.stdout == ""
    assert "configuration" not in out.stderr and "no result" in out.stderr
