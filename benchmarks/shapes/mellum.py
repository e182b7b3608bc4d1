"""Bytes and operations the mellum architecture needs, from a
configuration's shapes: every block an attention layer (`layer_types`:
sliding_attention over the last `sliding_window` positions, or
full_attention) and `num_experts` SwiGLU experts of three matrices each,
`num_experts_per_tok` of them a token, no shared expert; an untied head.
The least a step must move or compute, never what an implementation
happens to touch. Plain Python, no JAX (`run.py` loads it in its own
process); `dtbench/shapes.py` states the interface its first five
functions keep.

What a decode step reads: every attention, router and head matrix once,
and the experts TOUCHED: with `rows` tokens each choosing k of E
experts, an expert is missed with probability (1 - k/E)^rows under
uniform routing (`touched_experts`: 58 rows x 8 of 64 touch 63.97), and
the keys and values its attention layers can SEE: a full layer every
live token, a sliding layer the last `sliding_window` of each row.

**KV bytes are window-aware and never over.** `attention_step_bytes` and
`decode_step_bytes` are handed `live_tokens` and no rows (the interface
is the dense architecture's: PERF.md section 7 (g)), so for the sliding
layers they count the FEWEST tokens those live tokens can need: as few
rows as can hold them, each at the longest context the cell's mix allows
(8,176) and seeing one window of it, i.e. live_tokens x 1024 / 8176. At
this cell's traffic (58 decoding rows of mean context 2,850, of which a
sliding layer sees min(context, 1024), about 980 a row) that under-reads
the sliding layers' bytes 2.8-fold and all attention bytes by a third
(0.93 GB counted against 1.38 GB row-aware), so `paged_attn_roofline_pct`
and `decode_hbm_roofline_pct` under-read here, never over. A count of
every live token on all layers would read 2.03 GB and the window kernel
past 100%. The reader of the window kernel's own share counts rows and
contexts itself, from the client's timelines, and calls
`window_layer_kv_bytes` below the interface.
"""

from __future__ import annotations

MAX_CONTEXT = 8176  # the longest context the cell's mix allows a row
BF16 = 2.0


def sizes(cfg: dict) -> dict:
    kinds = cfg["layer_types"]  # as cut: the blocks served
    return {"h": cfg["hidden_size"], "layers": len(kinds),
            "n_window": kinds.count("sliding_attention"),
            "n_full": kinds.count("full_attention"),
            "window": cfg["sliding_window"],
            "experts": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
            # every expert is held here (what `expert_touched_pct` divides by)
            "held": cfg["num_experts"]}


def matmul_params(cfg: dict) -> dict:
    """Parameter counts of the matrices of one block, the head and the
    embedding."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    qh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"attention": h * qh * hd + 2 * h * kh * hd + qh * hd * h,
            "expert": 3 * h * cfg["moe_intermediate_size"],
            "router": h * cfg["num_experts"],
            "head": h * cfg["vocab_size"], "embed": cfg["vocab_size"] * h}


def total_params(cfg: dict) -> float:
    z, p = sizes(cfg), matmul_params(cfg)
    return (z["layers"] * (p["attention"] + p["router"]
                           + z["experts"] * p["expert"])
            + p["head"] + p["embed"])


def touched_experts(cfg: dict, rows: float) -> float:
    """Experts at least one of `rows` tokens is routed to, under uniform
    routing: what a trained, balanced router does, and the most a batch
    can touch."""
    z = sizes(cfg)
    return z["experts"] * (1.0 - (1.0 - z["k"] / z["experts"]) ** rows)


def weight_bytes_per_step(cfg: dict, rows: float = 1.0) -> float:
    """Bytes of weights one decode step of `rows` tokens must read: every
    attention, router and head matrix once (the embedding is a gather of
    a few rows) and the experts touched, three matrices each."""
    z, p = sizes(cfg), matmul_params(cfg)
    dense = z["layers"] * (p["attention"] + p["router"]) + p["head"]
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
    """The least one decode step reads: weights (experts touched) and the
    keys and values its layers can see. Without `rows`: the fewest rows
    `live_tokens` can be."""
    if rows is None:
        rows = live_tokens / MAX_CONTEXT
    return (weight_bytes_per_step(cfg, rows)
            + attention_step_bytes(cfg, live_tokens))


def flops_per_token(cfg: dict, context: float) -> float:
    """Multiply-adds x 2 for one token at the given context length: the
    matrices it passes through (k experts a block) and the attention
    over what each layer sees of its context."""
    z, p = sizes(cfg), matmul_params(cfg)
    matrices = (z["layers"] * (p["attention"] + p["router"]
                               + z["k"] * p["expert"]) + p["head"])
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
    [gate | up] and the down). Bytes: the three matrices of the experts a
    call TOUCHES (the program's counter), read once a call. FLOPs: the
    token-slots a call routes to an expert (the counter's mean over
    calls) x 2 x an expert's parameters."""
    p = matmul_params(cfg)
    touched = (decode_calls * decode_touched
               + prefill_calls * prefill_touched)
    return {"bytes": touched * BF16 * p["expert"],
            "flops": ((decode_calls + prefill_calls) * slots_per_call
                      * 2.0 * p["expert"])}
