"""Bytes and operations the algorithm needs, from a configuration's shapes.

The least a step must move or compute, never what an implementation
happens to touch: a roofline share built on these cannot pass 100% unless
the time is wrong. `cfg` is a configuration file of `configs/` (the
source's keys plus `reference.weights` and `serve.kv_dtype`).

This module counts a dense decoder-only transformer (grouped-query
attention, one SwiGLU block a layer). A configuration of another
architecture (a latent cache, routed experts) names a module of its own
under the key `shapes` of its file: a path to a file under the
benchmark's `paths`, say `benchmarks/shapes/<name>.py`. `run.py` hands
the readers that module as `ctx["shapes"]`, and this one where the key is
absent. The interface is what the readers call (`INTERFACE` below), each
taking the whole configuration file:

    weight_bytes_per_step(cfg)             bytes of weights a decode step reads
    kv_bytes_per_token(cfg)                bytes a cached token holds, all layers
    decode_step_bytes(cfg, live_tokens)    the least one decode step reads
    attention_step_bytes(cfg, live_tokens) the least its attention kernels read
    flops_per_token(cfg, context)          multiply-adds x 2 for one token

It is loaded in `run.py`'s own process, which never imports JAX (a chip
belongs to one process): plain Python over the file's numbers.
"""

from __future__ import annotations

Q4_GROUP = 256
INTERFACE = ("weight_bytes_per_step", "kv_bytes_per_token",
             "decode_step_bytes", "attention_step_bytes", "flops_per_token")


def matmul_params(cfg: dict) -> dict:
    """Parameter counts of the matrices a token passes through."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    qh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    m, v = cfg["intermediate_size"], cfg["vocab_size"]
    layer = h * qh * hd + 2 * h * kh * hd + qh * hd * h + 3 * h * m
    return {"per_layer": layer, "layers": cfg["num_hidden_layers"] * layer,
            "head": h * v, "embed": v * h}


def weight_bytes_per_step(cfg: dict) -> float:
    """Bytes of weights one decode step must read, as stored: every
    projection and the output head once (the embedding is a gather of a
    few rows; a tied head reads the table as the head)."""
    p = matmul_params(cfg)
    stored = cfg["reference"]["weights"]
    if stored == "int4":
        # half a byte per code + one f32 scale and one f32 zero per group
        per_param = 0.5 + 8.0 / Q4_GROUP
        head = p["head"] * (2.0 if cfg["tie_word_embeddings"] else per_param)
        return p["layers"] * per_param + head
    return (p["layers"] + p["head"]) * 2.0  # bf16


def kv_bytes_per_token(cfg: dict) -> float:
    """Bytes one cached token holds across all layers, as stored."""
    kh, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    layers = cfg["num_hidden_layers"]
    if cfg["serve"]["kv_dtype"] == "int8":
        return layers * 2 * (kh * hd * 1.0 + 2.0)  # values + one bf16 scale
    return layers * 2 * kh * hd * 2.0


def decode_step_bytes(cfg: dict, live_tokens: float) -> float:
    """The least one decode step reads: the weights as stored, and the
    keys and values of the live contexts (not the whole pool)."""
    return weight_bytes_per_step(cfg) + live_tokens * kv_bytes_per_token(cfg)


def attention_step_bytes(cfg: dict, live_tokens: float) -> float:
    """The least the decode-attention kernels of one step read."""
    return live_tokens * kv_bytes_per_token(cfg)


def flops_per_token(cfg: dict, context: float) -> float:
    """Multiply-adds x 2 for one token at the given context length."""
    p = matmul_params(cfg)
    attn = (cfg["num_hidden_layers"] * 4 * cfg["num_attention_heads"]
            * cfg["head_dim"] * context)
    return 2.0 * (p["layers"] + p["head"]) + attn
