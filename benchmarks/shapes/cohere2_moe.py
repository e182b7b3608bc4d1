"""Bytes and operations the cohere2_moe architecture needs, from a
configuration's shapes: every block an attention layer (`layer_types`:
sliding_attention over the last `sliding_window` positions, or
full_attention) beside `num_experts_published` SwiGLU experts of three
matrices each, `num_experts_per_tok` of them a token, of which THIS chip
holds `experts_held` = [lo, hi), and `num_shared_experts` shared experts
of the same width that every token passes; one norm a block; a tied head
over the `vocab_size` rows held. The least a step must move or compute,
never what an implementation happens to touch. Plain Python, no JAX
(`run.py` loads it in its own process); `dtbench/shapes.py` states the
interface its first five functions keep.

What a decode step reads: every attention, router and shared-expert
matrix and the tied matrix once, and the held experts TOUCHED: with
`rows` tokens each choosing k of E published experts, a held expert is
missed with probability (1 - k/E)^rows under uniform routing
(`touched_experts`: 32 rows x 8 of 128 touch 13.97 of the 16 held), and
the keys and values its attention layers can SEE: a full layer every
live token, a sliding layer the last `sliding_window` of each row.

**KV bytes are window-aware and never over.** `attention_step_bytes` and
`decode_step_bytes` are handed `live_tokens` and no rows (the interface
is the dense architecture's: PERF.md section 7 (g)), so for the sliding
layers they count the FEWEST tokens those live tokens can need: as few
rows as can hold them, each at the longest context the cell's mix allows
(12,272) and seeing one window of it, i.e. live_tokens x 4096 / 12272. At
this cell's traffic (rows of mean context near 6,900, of which a sliding
layer sees min(context, 4096)) that under-reads the sliding layers'
bytes by about two fifths, so `paged_attn_roofline_pct` and
`decode_hbm_roofline_pct` under-read here, never over. The reader of the
window kernel's own share counts rows and contexts itself, from the
client's timelines, and calls `window_layer_kv_bytes` below the
interface.
"""

from __future__ import annotations

MAX_CONTEXT = 12272  # the longest context the cell's mix allows a row
BF16 = 2.0


def sizes(cfg: dict) -> dict:
    kinds = cfg["layer_types"]  # as cut: the blocks served
    lo, hi = cfg.get("experts_held") or (0, cfg["num_experts"])
    return {"h": cfg["hidden_size"], "layers": len(kinds),
            "n_window": kinds.count("sliding_attention"),
            "n_full": kinds.count("full_attention"),
            "window": cfg["sliding_window"],
            # the router's outputs, and the experts this chip computes
            # (what `expert_touched_pct` divides by)
            "experts": cfg.get("num_experts_published", cfg["num_experts"]),
            "held": hi - lo, "k": cfg["num_experts_per_tok"],
            "shared": cfg["num_shared_experts"]}


def matmul_params(cfg: dict) -> dict:
    """Parameter counts of the matrices of one block, and of the tied
    matrix (embedding and head are ONE array)."""
    z = sizes(cfg)
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    qh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    expert = 3 * h * cfg["intermediate_size"]
    return {"attention": h * qh * hd + 2 * h * kh * hd + qh * hd * h,
            "expert": expert, "shared": z["shared"] * expert,
            "router": h * z["experts"], "norm": h,
            "tied": cfg["vocab_size"] * h}


def block_params(cfg: dict, experts: float | None = None) -> float:
    """One block as held here: attention, its one norm, the router, the
    shared experts and `experts` routed ones (default: the held)."""
    z, p = sizes(cfg), matmul_params(cfg)
    held = z["held"] if experts is None else experts
    return (p["attention"] + p["norm"] + p["router"] + p["shared"]
            + held * p["expert"])


def total_params(cfg: dict) -> float:
    """What this chip holds: the served blocks, the held rows of the
    tied matrix and the final norm."""
    z, p = sizes(cfg), matmul_params(cfg)
    return z["layers"] * block_params(cfg) + p["tied"] + p["norm"]


def touched_experts(cfg: dict, rows: float) -> float:
    """Held experts at least one of `rows` tokens is routed to, under
    uniform routing over the published experts: what a trained, balanced
    router does, and the most a batch can touch."""
    z = sizes(cfg)
    return z["held"] * (1.0 - (1.0 - z["k"] / z["experts"]) ** rows)


def weight_bytes_per_step(cfg: dict, rows: float = 1.0) -> float:
    """Bytes of weights one decode step of `rows` tokens must read: every
    attention, router and shared-expert matrix and the tied matrix (as
    the head) once, and the held experts touched, three matrices each."""
    z, p = sizes(cfg), matmul_params(cfg)
    dense = (z["layers"] * (p["attention"] + p["router"] + p["shared"])
             + p["tied"])
    return BF16 * (dense + z["layers"] * touched_experts(cfg, rows)
                   * p["expert"])


def kv_bytes_per_token_layer(cfg: dict) -> float:
    """Bytes one cached token holds in ONE attention layer (K and V)."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BF16


def kv_bytes_per_token(cfg: dict) -> float:
    """Bytes one cached token holds while every layer still sees it: a
    page of each group (what the pools are sized by; a decode step reads
    less of an old token: `attention_step_bytes`)."""
    return sizes(cfg)["layers"] * kv_bytes_per_token_layer(cfg)


def window_layer_kv_bytes(cfg: dict, contexts) -> float:
    """What ONE sliding layer's decode attention reads for rows of these
    context lengths: the last `sliding_window` positions of each."""
    window = sizes(cfg)["window"]
    return (sum(min(c, window) for c in contexts)
            * kv_bytes_per_token_layer(cfg))


def attention_step_bytes(cfg: dict, live_tokens: float) -> float:
    """The least the decode-attention kernels of one step read: the full
    layers every live token, the sliding layers the fewest those tokens
    can need (the head of this file says by how much that under-reads)."""
    z = sizes(cfg)
    seen = z["n_full"] + z["n_window"] * min(1.0, z["window"] / MAX_CONTEXT)
    return live_tokens * seen * kv_bytes_per_token_layer(cfg)


def decode_step_bytes(cfg: dict, live_tokens: float,
                      rows: float | None = None) -> float:
    """The least one decode step reads: weights (held experts touched)
    and the keys and values its layers can see. Without `rows`: the
    fewest rows `live_tokens` can be."""
    if rows is None:
        rows = live_tokens / MAX_CONTEXT
    return (weight_bytes_per_step(cfg, rows)
            + attention_step_bytes(cfg, live_tokens))


def flops_per_token(cfg: dict, context: float) -> float:
    """Multiply-adds x 2 for one token at the given context length: the
    matrices it passes through (the shared experts whole, and of its k
    routed experts the k x held / published this chip holds on average)
    and the attention over what each layer sees of its context."""
    z, p = sizes(cfg), matmul_params(cfg)
    routed = z["k"] * z["held"] / z["experts"]
    matrices = (z["layers"] * (p["attention"] + p["router"] + p["shared"]
                               + routed * p["expert"]) + p["tied"])
    seen = z["n_full"] * context + z["n_window"] * min(context, z["window"])
    return (2.0 * matrices
            + 4 * cfg["num_attention_heads"] * cfg["head_dim"] * seen)


# -- what the readers of this architecture's kernels call --------------------


def expert_gmm_floor(cfg: dict, decode_calls: float, decode_touched: float,
                     prefill_calls: float, prefill_touched: float,
                     slots_per_call: float) -> dict:
    """The grouped matmuls of `decode_calls + prefill_calls` calls of an
    expert layer (every decode step and every prefill launch calls each
    expert layer once; a call is two grouped matmuls: the fused
    [gate | up] and the down; the shared experts are plain matmuls and
    not counted). Bytes: the three matrices of the held experts a call
    TOUCHES (the program's counter), read once a call. FLOPs: the
    token-slots a call routes to a held expert (the counter's mean over
    calls) x 2 x an expert's parameters."""
    p = matmul_params(cfg)
    touched = (decode_calls * decode_touched
               + prefill_calls * prefill_touched)
    return {"bytes": touched * BF16 * p["expert"],
            "flops": ((decode_calls + prefill_calls) * slots_per_call
                      * 2.0 * p["expert"])}
