"""The plain reference against the program where a CPU can hold both, and
the lower-precision control against the limits' rule.

The reference imports nothing of the program; these tests import both.
Tolerances: the program computes in bfloat16 (8 bits of mantissa) with
float32 accumulation and the reference in float32, so over two tiny
layers their logits differ by a few bfloat16 ULPs of values near 1:
under 0.1 at the worst position (measured: 0.06), where a wrong rotary
convention, a missing norm or a wrong weight recipe moves them by the
logits' own spread (about 1). With int4 weights the program's matmul
also dequantises into bfloat16 (scale and zero ride the bfloat16 tile),
which the configuration states: 0.3 there (measured: 0.17).
"""

import json
import os

import numpy as np
import pytest

from bench_paths import BENCH
from dtbench import reference


def tiny(**changes):
    with open(os.path.join(BENCH, "configs", "tiny-test.json")) as f:
        body = json.load(f)
    ref = body.pop("reference")
    cfg = {k: v for k, v in body.items() if not isinstance(v, (dict, list))}
    return {**cfg, **ref, **changes}


def program_logits(cfg, samples, weight_dtype):
    """The program's unified `forward` over prompt + served, one sequence
    at a time, on its own seeded weights (quantised its own way)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.quantize import quantize_params_int4
    from dynamo_tpu.models.transformer import (forward, init_params,
                                                make_kv_cache)

    mc = ModelConfig(
        name="ref-test", vocab_size=cfg["vocab_size"],
        hidden=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_q_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mlp_hidden=cfg["intermediate_size"], rope_theta=cfg["rope_theta"],
        rms_eps=cfg["rms_norm_eps"], qk_norm=cfg["qk_norm"],
        tie_embeddings=cfg["tie_word_embeddings"])
    params = init_params(jax.random.PRNGKey(cfg["weight_seed"]), mc)
    if weight_dtype == "int4":
        params = quantize_params_int4(params, mc)
    out = []
    for s in samples:
        ids = list(s["prompt"]) + list(s["served"])
        t, page = len(ids), 4
        pages = -(-t // page)
        kv = make_kv_cache(mc, pages + 1, page)
        table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None, :]
        _, logits = forward(
            params, mc, jnp.asarray([ids], jnp.int32),
            jnp.arange(t, dtype=jnp.int32)[None, :], kv, table,
            jnp.asarray([t], jnp.int32))
        n_p, n_s = len(s["prompt"]), len(s["served"])
        out.append(np.asarray(logits[0, n_p - 1:n_p + n_s - 1],
                              np.float32))
    return out


def samples_for(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return [{"prompt": rng.integers(0, cfg["vocab_size"], n_p).tolist(),
             "served": rng.integers(0, cfg["vocab_size"], n_s).tolist()}
            for n_p, n_s in ((9, 7), (30, 12), (50, 14))]


@pytest.mark.parametrize("changes,weight_dtype,tolerance", [
    ({}, "model", 0.1),
    ({"qk_norm": True, "tie_word_embeddings": False}, "model", 0.1),
    ({"weights": "int4", "tie_word_embeddings": False}, "int4", 0.3),
    ({"weights": "int4", "qk_norm": True}, "int4", 0.3),
], ids=["tiny-test", "qk-norm-untied", "int4-untied", "int4-qk-norm-tied"])
def test_reference_agrees_with_the_programs_forward(changes, weight_dtype,
                                                    tolerance):
    cfg = tiny(**changes)
    samples = samples_for(cfg)
    ref = reference.logits_for(samples, cfg, pad_to=64)
    prog = program_logits(cfg, samples, weight_dtype)
    for r, p in zip(ref, prog):
        assert r.shape == p.shape
        assert np.abs(r - p).max() < tolerance
        assert r.std() > 0.5  # the logits are not degenerate
    # and as the benchmark compares: the program's greedy tokens lie
    # within a hair of the reference's best
    numbers = reference.compare(ref, [p.argmax(-1) for p in prog])
    assert numbers["gap_max"] < tolerance and numbers["positions"] == 33


def test_the_weights_recipe_is_the_programs_bit_for_bit():
    """`layer_weights` states the seeded weights; the program's init and
    int4 quantiser must give the same float32 matrices."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.transformer import init_params
    from dynamo_tpu.ops.q4_linear import dequantize_q4, quantize_weight_q4

    cfg = tiny(weights="int4", tie_word_embeddings=False)
    mc = ModelConfig(tie_embeddings=False)
    params = init_params(jax.random.PRNGKey(0), mc)
    keys = reference.model_keys(cfg)
    ours = reference.layer_weights(keys[2], cfg, {})
    theirs = params["layers"][1]
    for name, n_contract in (("wq", 1), ("wo", 2), ("w_down", 1)):
        leaf = quantize_weight_q4(theirs[name], n_contract)
        deq = dequantize_q4(leaf["q4"], leaf["qs4"], leaf["qz4"])
        np.testing.assert_array_equal(
            np.asarray(deq, np.float32).reshape(ours[name].shape),
            np.asarray(ours[name]))
    plain = reference.layer_weights(keys[1], tiny(), {})
    np.testing.assert_array_equal(
        np.asarray(plain["w_gate"]),
        np.asarray(params["layers"][0]["w_gate"].astype(jnp.float32)))
    np.testing.assert_array_equal(
        np.asarray(reference.embed_weights(keys, cfg)),
        np.asarray(params["embed"].astype(jnp.float32)))
    np.testing.assert_array_equal(
        np.asarray(reference.head_weights(keys, cfg, None, {})),
        np.asarray(dequantize_q4(**{
            k: v for k, v in zip(
                ("q4", "scale", "zero"),
                quantize_weight_q4(params["lm_head"], 1).values())}),
            np.float32))


def test_a_wrong_convention_is_far_outside_the_tolerance():
    """What the 0.1 is there to catch: interleaved rotary pairs in place
    of the half-split convention moves the logits by their own spread."""
    cfg = tiny()
    samples = samples_for(cfg)
    ref = reference.logits_for(samples, cfg, pad_to=64)
    prog = program_logits(dict(cfg, rope_theta=500.0), samples, "model")
    assert max(np.abs(r - p).max() for r, p in zip(ref, prog)) > 0.5


def controls_of_every_configuration():
    """(file, axis, what is lowered) for every control a configuration's
    file names: a later configuration's controls are tested by being there."""
    out = []
    for name in sorted(os.listdir(os.path.join(BENCH, "configs"))):
        with open(os.path.join(BENCH, "configs", name)) as f:
            controls = json.load(f)["check"]["controls"]
        out += [pytest.param(lower, id=f"{name[:-5]}.{axis}")
                for axis, lower in controls.items()]
    return out


@pytest.mark.parametrize("lower", controls_of_every_configuration())
def test_a_lowered_precision_fails_the_limits_rule(lower):
    """A control: the reference with ONE stated precision a step down,
    teacher-forced on the same tokens; the token it puts first lies below
    the reference's best by more than a sound run's does. On this toy the
    program's own rounding reads 0 (bfloat16 never flips the arg-max of
    these few positions), so any limit with room above it is failed by a
    control that reads over 0.02; the cells' limits are set on the chip,
    against the weaker axis (PERF.md)."""
    assert len(lower) == 1  # one axis at a time
    cfg = tiny()
    rng = np.random.default_rng(3)
    samples = [{"prompt": rng.integers(0, 512, 60).tolist(),
                "served": rng.integers(0, 512, 80).tolist()}
               for _ in range(3)]
    ref = reference.logits_for(samples, cfg, pad_to=192)
    low = reference.logits_for(samples, cfg, pad_to=192, lower=lower)
    control = reference.compare(ref, [lg.argmax(-1) for lg in low])
    sound = reference.compare(ref, [r.argmax(-1) for r in ref])
    assert sound["gap_max"] == 0.0 and sound["off_best_share"] == 0.0
    assert control["gap_max"] > 0.02
    assert control["off_best_share"] > 0.0


def test_the_reference_child_reads_every_control_by_name(tmp_path):
    """The job a run hands the child: `served` for every set, and for a
    set marked `control` one row per axis of the configuration's file."""
    with open(os.path.join(BENCH, "configs", "tiny-test.json")) as f:
        controls = json.load(f)["check"]["controls"]
    cfg = tiny()
    samples = samples_for(cfg, seed=5)
    job = {"config": cfg, "pad_to": 64, "controls": controls,
           "sets": [{"label": "a", "samples": samples, "control": True},
                    {"label": "b", "samples": samples}]}
    with open(tmp_path / "job.json", "w") as f:
        json.dump(job, f)
    assert reference.main(["reference.py", str(tmp_path / "job.json"),
                           str(tmp_path / "out.json")]) == 0
    with open(tmp_path / "out.json") as f:
        out = json.load(f)
    first, second = out["sets"]
    assert set(first["controls"]) == set(controls) and len(controls) == 3
    assert "controls" not in second
    assert first["served"] == second["served"]
    assert first["served"]["positions"] == 33


def test_gaps_and_compare_arithmetic():
    logits = np.array([[1.0, 3.0, 2.0], [0.5, 0.1, 0.4]], np.float32)
    np.testing.assert_allclose(
        reference.gaps(logits, np.array([1, 2])), [0.0, 0.1], atol=1e-6)
    numbers = reference.compare([logits], [np.array([2, 0])])
    assert numbers["gap_max"] == pytest.approx(1.0)
    assert numbers["gap_mean"] == pytest.approx(0.5)
    assert numbers["off_best_share"] == 0.5 and numbers["positions"] == 2
