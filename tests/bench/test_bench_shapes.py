"""The byte and operation counts against hand counts, both configurations."""

import json
import os

import pytest

from bench_paths import BENCH
from dtbench import shapes


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_7b_int4_int8():
    cfg = config("mistral-7b-w4kv8")
    p = shapes.matmul_params(cfg)
    # q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336
    assert p["per_layer"] == 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert p["layers"] == 32 * 218_103_808 == 6_979_321_856
    assert p["head"] == 4096 * 32768
    # half a byte a code, an f32 scale and an f32 zero per 256 rows
    per = 0.5 + 8 / 256
    assert shapes.weight_bytes_per_step(cfg) == pytest.approx(
        (6_979_321_856 + 134_217_728) * per)  # 3.78 GB
    # 32 layers x K and V x (8 heads x 128 int8 + one bf16 scale)
    assert shapes.kv_bytes_per_token(cfg) == 32 * 2 * (1024 + 2) == 65_664
    assert shapes.decode_step_bytes(cfg, 1000) == pytest.approx(
        shapes.weight_bytes_per_step(cfg) + 65_664_000)
    assert shapes.attention_step_bytes(cfg, 10) == 656_640


def test_a_bf16_configuration_by_hand():
    """The bf16 branch, on a public dense 4B model's sizes (Qwen3-4B's:
    tied 152k head), which PR 25 served on the chip and took out again
    (PERF.md, Findings)."""
    cfg = {"hidden_size": 2560, "intermediate_size": 9728,
           "num_hidden_layers": 36, "num_attention_heads": 32,
           "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 151936,
           "tie_word_embeddings": True, "reference": {"weights": "model"},
           "serve": {"kv_dtype": "model"}}
    p = shapes.matmul_params(cfg)
    # q 2560x4096, k and v 2560x1024, o 4096x2560, three 2560x9728
    assert p["per_layer"] == 2 * 2560 * 4096 + 2 * 2560 * 1024 + 3 * 2560 * 9728
    assert p["per_layer"] == 100_925_440
    assert p["head"] == 2560 * 151936
    assert shapes.weight_bytes_per_step(cfg) == 2.0 * (
        36 * 100_925_440 + 388_956_160)  # 8.04 GB, the tied table read once
    assert shapes.kv_bytes_per_token(cfg) == 36 * 2 * 8 * 128 * 2 == 147_456


def test_flops_per_token():
    cfg = config("mistral-7b-w4kv8")
    base = 2.0 * (6_979_321_856 + 134_217_728)
    assert shapes.flops_per_token(cfg, 0) == base
    # QK^T and PV: 2 x 2 x heads x head_dim per context token per layer
    assert shapes.flops_per_token(cfg, 1000) - base == 32 * 4 * 32 * 128 * 1000
