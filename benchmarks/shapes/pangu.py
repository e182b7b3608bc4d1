"""Bytes and operations the pangu_ultra_moe architecture needs, from a
configuration's shapes: every block a latent-attention mixer (a cached
token is ONE row of `kv_lora_rank` + `qk_rope_head_dim` values for all
heads), then a dense SwiGLU (`first_k_dense_replace` leading blocks) or
`n_routed_experts_published` SwiGLU experts, `num_experts_per_tok` a
token, of which `experts_held` are here, plus a shared expert; an untied
head. The least a step must move or compute, never what an
implementation happens to touch. Plain Python, no JAX (`run.py` loads it
in its own process); `dtbench/shapes.py` states the interface its first
five functions keep.

What a decode step reads: every attention, dense, shared-expert, router
and head matrix once, and the held experts TOUCHED: with `rows` tokens
each choosing k of the published experts, a held expert is missed with
probability (1 - k / published)^rows under uniform routing
(`touched_experts`: 128 rows x 8 of 256 touch 15.72 of 16), and one row
of every live token a latent layer.

**Never over.** A cached row is counted at its 576 values (1,152 B);
the program stores it padded to 640 lanes and streams 1,280 B. The
interface hands `decode_step_bytes` live tokens and no rows (PERF.md
section 7 (g)), so the experts touched are those of the fewest rows the
live tokens can be (each at the longest context the mix allows).
`paged_attn_roofline_pct` divides by the HBM side alone, which is the
smaller of the kernel's two sides by 1%: `latent_layer_flops` below the
interface gives the other, and the reader of the kernel's own share
takes the larger.
"""

from __future__ import annotations

MAX_CONTEXT = 6144  # the longest context the cell's mix allows a row
BF16 = 2.0


def sizes(cfg: dict) -> dict:
    lo, hi = cfg["experts_held"]
    layers = cfg["served_layers"]
    dense = min(layers, cfg["first_k_dense_replace"])
    return {"h": cfg["hidden_size"], "layers": layers, "n_dense": dense,
            "n_expert": layers - dense, "heads": cfg["num_attention_heads"],
            "rank": cfg["kv_lora_rank"], "rope": cfg["qk_rope_head_dim"],
            "nope": cfg["qk_nope_head_dim"], "v": cfg["v_head_dim"],
            "published": cfg["n_routed_experts_published"],
            "held": hi - lo, "k": cfg["num_experts_per_tok"]}


def matmul_params(cfg: dict) -> dict:
    """Parameter counts of the matrices of one mixer of each kind, the
    head and the embedding."""
    z = sizes(cfg)
    h, heads, rank = z["h"], z["heads"], z["rank"]
    q_rank = cfg["q_lora_rank"]
    attention = (h * q_rank + q_rank * heads * (z["nope"] + z["rope"])
                 + h * (rank + z["rope"])
                 + rank * heads * (z["nope"] + z["v"])
                 + heads * z["v"] * h)
    expert = 3 * h * cfg["moe_intermediate_size"]
    return {"attention": attention,
            "dense": 3 * h * cfg["intermediate_size"],
            "expert": expert, "shared": cfg["n_shared_experts"] * expert,
            "router": h * z["published"],
            "head": h * cfg["vocab_size"], "embed": cfg["vocab_size"] * h}


def total_params(cfg: dict) -> float:
    z, p = sizes(cfg), matmul_params(cfg)
    return (z["layers"] * p["attention"] + z["n_dense"] * p["dense"]
            + z["n_expert"] * (z["held"] * p["expert"] + p["shared"]
                               + p["router"])
            + p["head"] + p["embed"])


def touched_experts(cfg: dict, rows: float) -> float:
    """Held experts at least one of `rows` tokens is routed to, under
    uniform routing over the published experts: what a trained, balanced
    router does, and the most a batch can touch."""
    z = sizes(cfg)
    return z["held"] * (1.0 - (1.0 - z["k"] / z["published"]) ** rows)


def weight_bytes_per_step(cfg: dict, rows: float = 1.0) -> float:
    """Bytes of weights one decode step of `rows` tokens must read: every
    attention, dense, shared-expert, router and head matrix once (the
    embedding is a gather of a few rows) and the held experts touched."""
    z, p = sizes(cfg), matmul_params(cfg)
    dense = (z["layers"] * p["attention"] + z["n_dense"] * p["dense"]
             + z["n_expert"] * (p["shared"] + p["router"]) + p["head"])
    return BF16 * (dense + z["n_expert"] * touched_experts(cfg, rows)
                   * p["expert"])


def kv_bytes_per_token_layer(cfg: dict) -> float:
    """Bytes one cached token holds in ONE latent layer: a row of
    `kv_lora_rank` latent + `qk_rope_head_dim` rope-key values."""
    z = sizes(cfg)
    return (z["rank"] + z["rope"]) * BF16


def kv_bytes_per_token(cfg: dict) -> float:
    """Bytes one cached token holds across all layers, unpadded."""
    return sizes(cfg)["layers"] * kv_bytes_per_token_layer(cfg)


def attention_step_bytes(cfg: dict, live_tokens: float) -> float:
    """The least the decode-attention kernels of one step read."""
    return live_tokens * kv_bytes_per_token(cfg)


def decode_step_bytes(cfg: dict, live_tokens: float,
                      rows: float | None = None) -> float:
    """The least one decode step reads: weights (experts touched) and a
    row of every live token a layer. Without `rows`: the fewest rows
    `live_tokens` can be."""
    if rows is None:
        rows = live_tokens / MAX_CONTEXT
    return (weight_bytes_per_step(cfg, rows)
            + attention_step_bytes(cfg, live_tokens))


def flops_per_token(cfg: dict, context: float) -> float:
    """Multiply-adds x 2 for one token at the given context length: the
    matrices it passes through (k of the published experts, of which
    held / published are computed here) and attention over its context
    in the form that does not absorb (scores nope + rope lanes wide,
    values v wide, a head)."""
    z, p = sizes(cfg), matmul_params(cfg)
    experts = z["k"] * z["held"] / z["published"] * p["expert"]
    matrices = (z["layers"] * p["attention"] + z["n_dense"] * p["dense"]
                + z["n_expert"] * (experts + p["shared"] + p["router"])
                + p["head"])
    attention = (z["layers"] * 2 * z["heads"]
                 * (z["nope"] + z["rope"] + z["v"]) * context)
    return 2.0 * matrices + attention


# -- what the readers of this architecture's kernel call ----------------------


def latent_layer_bytes(cfg: dict, contexts) -> float:
    """What ONE latent layer's decode kernel reads for rows of these
    context lengths: every cached token's row once, for all heads."""
    return sum(contexts) * kv_bytes_per_token_layer(cfg)


def latent_layer_flops(cfg: dict, contexts) -> float:
    """What it computes in the absorbed form: a head scores a cached
    token over rank + rope lanes and sums rank lanes of values."""
    z = sizes(cfg)
    return (sum(contexts) * z["heads"]
            * (z["rank"] + z["rope"] + z["rank"]) * 2.0)


def expert_gmm_floor(cfg: dict, decode_calls: float, decode_touched: float,
                     prefill_calls: float, prefill_touched: float,
                     slots_per_call: float) -> dict:
    """The grouped matmuls of `decode_calls + prefill_calls` calls of an
    expert layer (a call is two grouped matmuls: the fused [gate | up]
    and the down). Bytes: the three matrices of the held experts a call
    TOUCHES (the program's counter), read once a call. FLOPs: the
    token-slots a call routes to a held expert x 2 x an expert's
    parameters."""
    p = matmul_params(cfg)
    touched = (decode_calls * decode_touched
               + prefill_calls * prefill_touched)
    return {"bytes": touched * BF16 * p["expert"],
            "flops": ((decode_calls + prefill_calls) * slots_per_call
                      * 2.0 * p["expert"])}
