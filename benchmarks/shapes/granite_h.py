"""Bytes and operations the granitemoehybrid architecture needs, from a
configuration's shapes: every block a token mixer (`layer_types`: mamba,
a Mamba-2 mixer, or attention) AND an expert layer (`num_local_experts`
SwiGLU experts of three matrices each HELD here of the published count,
`num_experts_per_tok` of the published a token, a shared expert of the
same form), a sliced vocabulary whose embedding is also the head. The
least a step must move or compute, never what an implementation happens
to touch. Plain Python, no JAX (`run.py` loads it in its own process);
`dtbench/shapes.py` states the interface its first five functions keep.

What a decode step reads: every Mamba, attention, shared-expert and
router matrix once, the TIED matrix once (as the head: the embedding
going in is a gather of a few rows of the same array, never counted
again), and the experts TOUCHED: with `rows` tokens each choosing k of E
published experts, an expert is missed with probability (1 - k/E)^rows
under uniform routing (`touched_experts`: 48 rows x 10 of 72 touch 35.97
of 36). It reads AND writes each live row's recurrent state, and reads
the live contexts' keys and values in the attention layers only.

`decode_step_bytes(cfg, live_tokens)` is handed tokens and no rows (the
interface is the dense architecture's: PERF.md section 7 (g)), so it
counts state and touched experts for the FEWEST rows those tokens can be
(contexts of the mix's `max_total_tokens` = 8,176 each):
`decode_hbm_roofline_pct` then under-reads in this cell (a row's state,
76 MB read and written, is a fifth of what a step moves; at 40 rows of
mean context 3,700 the fewest rows are 18), never over. The readers of
the state kernel and of the grouped matmul count the live rows
themselves, from the client's timelines and the program's counters, and
call the functions below the interface.
"""

from __future__ import annotations

MAX_CONTEXT = 8176  # the longest context the cell's mix allows a row
BF16, F32 = 2.0, 4.0


def sizes(cfg: dict) -> dict:
    kinds = cfg["layer_types"]  # as cut: the blocks served
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    inner = heads * p
    return {
        "h": cfg["hidden_size"], "layers": len(kinds),
        "n_m": kinds.count("mamba"), "n_a": kinds.count("attention"),
        "n_e": len(kinds),  # an expert layer behind every token mixer
        "heads": heads, "p": p, "g": g, "n": n, "inner": inner,
        "conv": inner + 2 * g * n, "kw": cfg["mamba_d_conv"],
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "held": cfg["num_local_experts"],
        "published": cfg.get("num_local_experts_published",
                             cfg["num_local_experts"]),
        "k": cfg["num_experts_per_tok"],
    }


def matmul_params(cfg: dict) -> dict:
    """Parameter counts of the matrices, by mixer kind."""
    z = sizes(cfg)
    h, hd = z["h"], z["head_dim"]
    qh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "mamba": h * (z["inner"] + z["conv"] + z["heads"]) + z["inner"] * h,
        "attention": h * qh * hd + 2 * h * kh * hd + qh * hd * h,
        "expert": 3 * h * cfg["intermediate_size"],
        "shared": 3 * h * cfg["shared_intermediate_size"],
        "router": h * z["published"],
        # ONE matrix: the embedding going in, the head coming out
        "head": h * cfg["vocab_size"]}


def mamba_small_params(cfg: dict) -> int:
    """What a Mamba-2 mixer holds beside its two projections: the conv's
    taps and bias, dt_bias, A and D a head, the gated norm's gain."""
    z = sizes(cfg)
    return z["kw"] * z["conv"] + z["conv"] + 3 * z["heads"] + z["inner"]


def total_params(cfg: dict) -> int:
    """Every parameter held here: the mixers' matrices, their small
    vectors, a norm gain of `h` in front of every mixer and the final
    one, the tied matrix once."""
    z, p = sizes(cfg), matmul_params(cfg)
    h = z["h"]
    return (z["n_m"] * (p["mamba"] + mamba_small_params(cfg) + h)
            + z["n_a"] * (p["attention"] + h)
            + z["n_e"] * (z["held"] * p["expert"] + p["shared"]
                          + p["router"] + h)
            + p["head"] + h)


def touched_experts(cfg: dict, rows: float) -> float:
    """Held experts at least one of `rows` tokens is routed to, under
    uniform routing over the published experts: what a trained, balanced
    router does, and the most a batch can touch."""
    z = sizes(cfg)
    return z["held"] * (1.0 - (1.0 - z["k"] / z["published"]) ** rows)


def weight_bytes_per_step(cfg: dict, rows: float = 1.0) -> float:
    """Bytes of weights one decode step of `rows` tokens must read: every
    Mamba, attention, shared-expert and router matrix once, the tied
    matrix once, and the experts touched."""
    z, p = sizes(cfg), matmul_params(cfg)
    dense = (z["n_m"] * p["mamba"] + z["n_a"] * p["attention"]
             + z["n_e"] * (p["shared"] + p["router"]) + p["head"])
    return BF16 * (dense + z["n_e"] * touched_experts(cfg, rows)
                   * p["expert"])


def kv_bytes_per_token(cfg: dict) -> float:
    """Bytes one cached token holds: the attention layers only."""
    z = sizes(cfg)
    return (z["n_a"] * 2 * cfg["num_key_value_heads"] * z["head_dim"]
            * BF16)


def ssm_state_bytes_per_row(cfg: dict) -> float:
    """Float32 SSM state one row holds, all Mamba mixers."""
    z = sizes(cfg)
    return z["n_m"] * z["heads"] * z["p"] * z["n"] * F32


def conv_state_bytes_per_row(cfg: dict) -> float:
    z = sizes(cfg)
    return z["n_m"] * (z["kw"] - 1) * z["conv"] * BF16


def state_bytes_per_row(cfg: dict) -> float:
    """Recurrent state a live row's decode step reads, and writes again."""
    return ssm_state_bytes_per_row(cfg) + conv_state_bytes_per_row(cfg)


def decode_step_bytes(cfg: dict, live_tokens: float,
                      rows: float | None = None) -> float:
    """The least one decode step moves: weights (experts touched, the
    tied matrix once), the live rows' state read and written, the live
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
    matrices it passes through (k of the published experts, of which
    held/published are computed here), the attention over its context,
    and the state update and read-out of the Mamba mixers."""
    z, p = sizes(cfg), matmul_params(cfg)
    experts = z["k"] * z["held"] / z["published"] * p["expert"]
    matrices = (z["n_m"] * p["mamba"] + z["n_a"] * p["attention"]
                + z["n_e"] * (experts + p["shared"] + p["router"])
                + p["head"])
    attention = (z["n_a"] * 4 * cfg["num_attention_heads"]
                 * z["head_dim"] * context)
    state = z["n_m"] * 6 * z["heads"] * z["p"] * z["n"]
    return 2.0 * matrices + attention + state


# -- what the readers of the architecture's kernels call ----------------------


def ssm_decode_kernel_bytes(cfg: dict, rows: float) -> float:
    """The decode state-update kernels of one step: each live row's SSM
    state read once and written once (the conv carry is outside them)."""
    return rows * 2.0 * ssm_state_bytes_per_row(cfg)


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
