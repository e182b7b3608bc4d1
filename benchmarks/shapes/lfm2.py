"""Bytes and operations the lfm2_moe architecture needs, from a
configuration's shapes: every block a token mixer (`layer_types`: conv,
the gated short convolution, or full_attention at head_dim hidden_size /
num_attention_heads = 64 with normed q and k) AND a feed-forward (a dense
SwiGLU `intermediate_size` wide behind the first `num_dense_layers`
blocks, `num_experts` SwiGLU experts `moe_intermediate_size` wide,
`num_experts_per_tok` a token, no shared expert, behind the rest), a
vocabulary whose embedding is also the head. The least a step must move or
compute, never what an implementation happens to touch. Plain Python, no
JAX (`run.py` loads it in its own process); `dtbench/shapes.py` states the
interface its first five functions keep.

What a decode step reads: every conv, attention, dense and router matrix
once, the TIED matrix once (as the head: the embedding going in is a
gather of a few rows of the same array, never counted again), and the
experts TOUCHED: with `rows` tokens each choosing k of E experts, an
expert is missed with probability (1 - k/E)^rows under uniform routing
(`touched_experts`: 256 rows x 4 of 32 touch all 32 to fifteen digits; 8
rows 21). It reads AND writes each live row's conv carries (K-1 = 2 rows
of `hidden_size` values a conv mixer: 8 KB; there is NO other recurrent
state), and reads the live contexts' keys and values in the attention
layers only, as they are stored: [kv heads, 64] a token, two heads a
128-lane row of the pool, nothing padded.

`decode_step_bytes(cfg, live_tokens)` is handed tokens and no rows (the
interface is the dense architecture's: PERF.md section 7 (g)), so it
counts carries and touched experts for the FEWEST rows those tokens can
be (contexts of the mix's `max_total_tokens` = 3,056 each). Here that
costs little: the carries are 0.4% of a step, and a third of the rows
already touch every expert (86 rows of 256 at mean context 1,030 touch
31.9997 of 32), so `decode_hbm_roofline_pct` under-reads by less than a
hundredth of itself. The readers of the grouped matmul count the live
rows themselves, from the program's counters, and call the functions
below the interface.
"""

from __future__ import annotations

MAX_CONTEXT = 3056  # the longest context the cell's mix allows a row
BF16 = 2.0


def sizes(cfg: dict) -> dict:
    kinds = cfg["layer_types"]  # as cut: the blocks served
    dense = min(cfg["num_dense_layers"], len(kinds))
    return {
        "h": cfg["hidden_size"], "layers": len(kinds),
        "n_c": kinds.count("conv"), "n_a": kinds.count("full_attention"),
        "n_d": dense, "n_e": len(kinds) - dense,
        "kw": cfg["conv_L_cache"],
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "held": cfg["num_experts"],
        "published": cfg.get("num_experts_published", cfg["num_experts"]),
        "k": cfg["num_experts_per_tok"],
    }


def matmul_params(cfg: dict) -> dict:
    """Parameter counts of the matrices, by mixer kind."""
    z = sizes(cfg)
    h, hd = z["h"], z["head_dim"]
    qh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "conv": h * 3 * h + h * h,  # W_in [h, 3h] and W_out [h, h]
        "attention": h * qh * hd + 2 * h * kh * hd + qh * hd * h,
        "dense": 3 * h * cfg["intermediate_size"],
        "expert": 3 * h * cfg["moe_intermediate_size"],
        "router": h * z["published"],
        # ONE matrix: the embedding going in, the head coming out
        "head": h * cfg["vocab_size"]}


def conv_small_params(cfg: dict) -> int:
    """What a conv mixer holds beside its two projections: K taps a
    channel (no bias)."""
    z = sizes(cfg)
    return z["kw"] * z["h"]


def attention_small_params(cfg: dict) -> int:
    """The q and k gains, head_dim each."""
    return 2 * sizes(cfg)["head_dim"]


def expert_layer_params(cfg: dict) -> int:
    """One expert layer as held: its experts, the router, the selection
    bias (`use_expert_bias`), the norm gain in front."""
    z, p = sizes(cfg), matmul_params(cfg)
    bias = z["published"] if cfg["use_expert_bias"] else 0
    return z["held"] * p["expert"] + p["router"] + bias + z["h"]


def total_params(cfg: dict) -> int:
    """Every parameter held here: the mixers' matrices, their small
    vectors, a norm gain of `h` in front of every mixer and the final
    one, the tied matrix once."""
    z, p = sizes(cfg), matmul_params(cfg)
    h = z["h"]
    return (z["n_c"] * (p["conv"] + conv_small_params(cfg) + h)
            + z["n_a"] * (p["attention"] + attention_small_params(cfg) + h)
            + z["n_d"] * (p["dense"] + h)
            + z["n_e"] * expert_layer_params(cfg)
            + p["head"] + h)


def active_params(cfg: dict) -> int:
    """Parameters one token passes through: `total_params` with k
    experts a layer for the held ones (the embedding's one row is
    counted as the head's matrix)."""
    z, p = sizes(cfg), matmul_params(cfg)
    return total_params(cfg) - z["n_e"] * (z["held"] - z["k"]) * p["expert"]


def touched_experts(cfg: dict, rows: float) -> float:
    """Held experts at least one of `rows` tokens is routed to, under
    uniform routing over the published experts: what a trained, balanced
    router does, and the most a batch can touch."""
    z = sizes(cfg)
    return z["held"] * (1.0 - (1.0 - z["k"] / z["published"]) ** rows)


def weight_bytes_per_step(cfg: dict, rows: float = 1.0) -> float:
    """Bytes of weights one decode step of `rows` tokens must read: every
    conv, attention, dense and router matrix once, the tied matrix once,
    and the experts touched."""
    z, p = sizes(cfg), matmul_params(cfg)
    dense = (z["n_c"] * p["conv"] + z["n_a"] * p["attention"]
             + z["n_d"] * p["dense"] + z["n_e"] * p["router"] + p["head"])
    return BF16 * (dense + z["n_e"] * touched_experts(cfg, rows)
                   * p["expert"])


def kv_bytes_per_token(cfg: dict) -> float:
    """Bytes one cached token holds: the attention layers only, K and V
    of every kv head 64 wide, as the pool stores them (no lane padded)."""
    z = sizes(cfg)
    return (z["n_a"] * 2 * cfg["num_key_value_heads"] * z["head_dim"]
            * BF16)


def conv_state_bytes_per_row(cfg: dict) -> float:
    """The conv carries one row holds, all conv mixers: K-1 rows of
    `hidden_size` values in the model dtype. The only recurrent state."""
    z = sizes(cfg)
    return z["n_c"] * (z["kw"] - 1) * z["h"] * BF16


def state_bytes_per_row(cfg: dict) -> float:
    """Recurrent state a live row's decode step reads, and writes again."""
    return conv_state_bytes_per_row(cfg)


def decode_step_bytes(cfg: dict, live_tokens: float,
                      rows: float | None = None) -> float:
    """The least one decode step moves: weights (experts touched, the
    tied matrix once), the live rows' carries read and written, the live
    contexts' keys and values. Without `rows`: the fewest rows
    `live_tokens` can be."""
    if rows is None:
        rows = live_tokens / MAX_CONTEXT
    return (weight_bytes_per_step(cfg, rows)
            + rows * 2.0 * state_bytes_per_row(cfg)
            + live_tokens * kv_bytes_per_token(cfg))


def attention_step_bytes(cfg: dict, live_tokens: float) -> float:
    """The least the decode-attention kernels of one step read."""
    return live_tokens * kv_bytes_per_token(cfg)


def flops_per_token(cfg: dict, context: float) -> float:
    """Multiply-adds x 2 for one token at the given context length: the
    matrices it passes through (k experts a layer, of which
    held/published are computed here), the attention over its context,
    and the conv's K taps and two gates a channel."""
    z, p = sizes(cfg), matmul_params(cfg)
    experts = z["k"] * z["held"] / z["published"] * p["expert"]
    matrices = (z["n_c"] * p["conv"] + z["n_a"] * p["attention"]
                + z["n_d"] * p["dense"]
                + z["n_e"] * (experts + p["router"]) + p["head"])
    attention = (z["n_a"] * 4 * cfg["num_attention_heads"]
                 * z["head_dim"] * context)
    conv = z["n_c"] * (2 * z["kw"] + 2) * z["h"]
    return 2.0 * matrices + attention + conv


# -- what the readers of the architecture's kernels call ----------------------


def expert_gmm_floor(cfg: dict, decode_calls: float, decode_touched: float,
                     prefill_calls: float, prefill_touched: float,
                     slots_per_call: float) -> dict:
    """The grouped matmuls of `decode_calls + prefill_calls` calls of an
    expert layer (every decode step and every prefill launch calls each
    expert layer once). Bytes: the weights of the held experts a call
    TOUCHES (the program's counter: experts with at least one token),
    read once a call. FLOPs: the token-slots a call routes to a held
    expert (the counter's mean over calls) x 2 x an expert's
    parameters."""
    p = matmul_params(cfg)
    touched = (decode_calls * decode_touched
               + prefill_calls * prefill_touched)
    return {"bytes": touched * BF16 * p["expert"],
            "flops": ((decode_calls + prefill_calls) * slots_per_call
                      * 2.0 * p["expert"])}

