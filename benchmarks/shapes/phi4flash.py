"""Bytes and operations the phi4flash architecture needs
(Phi-4-mini-flash-reasoning, SambaY), from a configuration's shapes: n =
`num_hidden_layers` blocks, each a token mixer and a SwiGLU
`intermediate_size` wide; under n/2 + 2 the token mixers alternate
Mamba-1 (`mamba_expand` x hidden channels, `mamba_d_state` columns, dt
through `mamba_dt_rank`) and differential attention (`sliding_window`
keys; block n/2 + 1 everything: the ONE layer of the full page group),
from n/2 + 2 on gated memory units and cross-attention onto block n/2 +
1's pages; LayerNorms with weight and bias; a tied head. The least a step
must move or compute, never what an implementation happens to touch.
Plain Python, no JAX (`run.py` loads it in its own process);
`dtbench/shapes.py` states the interface its first five functions keep.

What a decode step reads: every matrix once (the tied matrix as the
head), the full group's pages of every live token EIGHT times (the layer
that wrote them and the seven that own none: `full_readers`), the window
group's eight layers' last `sliding_window` tokens a row, and each live
row's recurrent state in and out.

**Window and state bytes are never over.** `attention_step_bytes` and
`decode_step_bytes` are handed `live_tokens` and no rows (the interface
is the dense architecture's: PERF.md section 7 (g)), so for the window
layers and the state they count the FEWEST rows those live tokens can be:
rows at the longest context the cell's mix allows (8,960), each seeing
one window of it. At this cell's traffic (rows of mean context near
3,200) that under-reads the window layers' bytes by about two thirds and
the state's by as much, together some 1.2 GB of a 17 GB step: so
`paged_attn_roofline_pct` and `decode_hbm_roofline_pct` under-read here
by a few per cent, never over.
"""

from __future__ import annotations

MAX_CONTEXT = 8960  # the longest context the cell's mix allows a row
BF16, F32 = 2.0, 4.0


def sizes(cfg: dict) -> dict:
    n, h = cfg["num_hidden_layers"], cfg["hidden_size"]
    per = cfg["mb_per_layer"]
    front = n // 2 + 2  # blocks that see every position of a prompt
    mamba = len([l for l in range(front) if l % per == 0])
    return {"h": h, "n": n, "front": front, "tail": n - front,
            "n_mamba": mamba, "n_window": n // 2 - len(
                [l for l in range(n // 2) if l % per == 0]),
            "n_full": 1, "n_gmu": len(
                [l for l in range(front, n) if l % per == 0]),
            "n_cross": len([l for l in range(front, n) if l % per]),
            "d": cfg["mamba_expand"] * h, "state": cfg["mamba_d_state"],
            "rank": cfg["mamba_dt_rank"], "taps": cfg["mamba_d_conv"],
            "qh": cfg["num_attention_heads"],
            "kh": cfg["num_key_value_heads"],
            "hd": h // cfg["num_attention_heads"],
            "window": cfg["sliding_window"]}


def mixer_params(cfg: dict) -> dict:
    """Parameters of one mixer of each kind, its LayerNorm (weight and
    bias) apart, and of the tied matrix (embedding and head are ONE
    array)."""
    z = sizes(cfg)
    h, d, n, rank = z["h"], z["d"], z["state"], z["rank"]
    q, kv = z["qh"] * z["hd"], z["kh"] * z["hd"]
    diff = 4 * z["hd"] + 2 * z["hd"]  # four lambda vectors, the sub-norm
    return {
        "mlp": 3 * h * cfg["intermediate_size"],
        # W_in, the conv's taps and bias, W_x, W_dt and its bias, A, D, W_out
        "mamba": (h * 2 * d + (z["taps"] + 1) * d + d * (rank + 2 * n)
                  + rank * d + d + n * d + d + d * h),
        # of which float32 as stored: dt's bias, A, D
        "mamba_f32": d + n * d + d,
        "attention": h * (q + 2 * kv) + (q + 2 * kv) + q * h + h + diff,
        "gmu": 2 * h * d,
        "cross": h * q + q + q * h + h + diff,
        "norm": 2 * h, "tied": cfg["vocab_size"] * h}


def total_params(cfg: dict) -> float:
    """The whole model, which one chip holds."""
    z, p = sizes(cfg), mixer_params(cfg)
    return (z["n"] * (p["mlp"] + 2 * p["norm"])
            + z["n_mamba"] * p["mamba"]
            + (z["n_window"] + z["n_full"]) * p["attention"]
            + z["n_gmu"] * p["gmu"] + z["n_cross"] * p["cross"]
            + p["tied"] + p["norm"])


def weight_bytes_per_step(cfg: dict) -> float:
    """Bytes of weights one decode step must read: every matrix once, the
    tied matrix as the head; bf16 but for a Mamba mixer's dt bias, A and
    D."""
    z, p = sizes(cfg), mixer_params(cfg)
    return (BF16 * total_params(cfg)
            + (F32 - BF16) * z["n_mamba"] * p["mamba_f32"])


def kv_bytes_per_token_layer(cfg: dict) -> float:
    """Bytes one cached token holds in ONE cache layer (K and V)."""
    z = sizes(cfg)
    return 2 * z["kh"] * z["hd"] * BF16


def full_readers(cfg: dict) -> int:
    """Layers that read the full page group's pages a decode step: the
    one that wrote them and the cross-attention layers."""
    z = sizes(cfg)
    return z["n_full"] + z["n_cross"]


def kv_bytes_per_token(cfg: dict) -> float:
    """Bytes one cached token holds while every layer still sees it: a
    page of each group's cache layers (what the pools are sized by: ONE
    full layer and the window layers; a decode step reads the full
    layer's `full_readers` times over: `attention_step_bytes`)."""
    z = sizes(cfg)
    return (z["n_full"] + z["n_window"]) * kv_bytes_per_token_layer(cfg)


def state_slot_bytes(cfg: dict) -> float:
    """Bytes of recurrent state one slot holds: a Mamba layer's float32
    [state, channels] and the conv's bf16 carry of taps - 1 positions."""
    z = sizes(cfg)
    return z["n_mamba"] * (F32 * z["state"] * z["d"]
                           + BF16 * (z["taps"] - 1) * z["d"])


def window_layer_kv_bytes(cfg: dict, contexts) -> float:
    """What ONE window layer's decode attention reads for rows of these
    context lengths: the last `sliding_window` positions of each."""
    window = sizes(cfg)["window"]
    return (sum(min(c, window) for c in contexts)
            * kv_bytes_per_token_layer(cfg))


def attention_step_bytes(cfg: dict, live_tokens: float) -> float:
    """The least the decode-attention kernels of one step read: the full
    group's live tokens once a reader (eight), the window layers the
    fewest those tokens can need (the head of this file says by how much
    that under-reads)."""
    z = sizes(cfg)
    seen = (full_readers(cfg)
            + z["n_window"] * min(1.0, z["window"] / MAX_CONTEXT))
    return live_tokens * seen * kv_bytes_per_token_layer(cfg)


def decode_step_bytes(cfg: dict, live_tokens: float,
                      rows: float | None = None) -> float:
    """The least one decode step reads: the weights, the keys and values
    its layers see, and each live row's state in and out. Without
    `rows`: the fewest rows `live_tokens` can be."""
    if rows is None:
        rows = live_tokens / MAX_CONTEXT
    return (weight_bytes_per_step(cfg)
            + attention_step_bytes(cfg, live_tokens)
            + 2.0 * rows * state_slot_bytes(cfg))


def attention_flops_per_key(cfg: dict) -> float:
    """Multiply-adds x 2 a query pair spends on one key pair, both
    softmaxes: two scores head_dim wide and two sums over values 2 x
    head_dim wide."""
    hd = sizes(cfg)["hd"]
    return 2 * (2 * hd + 2 * 2 * hd)


def scan_flops_per_token(cfg: dict) -> float:
    """Operations of ONE Mamba layer's recurrence for one position: for
    every (column, channel) dt A, its exp, decay x s, dt u B, the sum,
    s C and the sum over columns: seven."""
    z = sizes(cfg)
    return 7.0 * z["state"] * z["d"]


def _front_params(cfg: dict) -> float:
    """Matrices of the blocks that see every position of a prompt."""
    z, p = sizes(cfg), mixer_params(cfg)
    return (z["front"] * p["mlp"] + z["n_mamba"] * p["mamba"]
            + (z["n_window"] + z["n_full"]) * p["attention"])


def _tail_params(cfg: dict) -> float:
    """Matrices of the blocks that run on ONE position a row of a
    prefill launch, and the tied head behind them."""
    z, p = sizes(cfg), mixer_params(cfg)
    return (z["tail"] * p["mlp"] + z["n_gmu"] * p["gmu"]
            + z["n_cross"] * p["cross"] + p["tied"])


def flops_per_token(cfg: dict, context: float) -> float:
    """Multiply-adds x 2 for one DECODED token at the given context:
    every matrix, the recurrence of each Mamba layer, and attention over
    what each layer sees (a query pair a key pair: `qh` / 2 pairs)."""
    z = sizes(cfg)
    seen = (full_readers(cfg) * context
            + z["n_window"] * min(context, z["window"]))
    return (2.0 * (_front_params(cfg) + _tail_params(cfg))
            + z["n_mamba"] * scan_flops_per_token(cfg)
            + z["qh"] / 2 * attention_flops_per_key(cfg) * seen)


def prefill_launch_flops(cfg: dict, positions: float, rows: float,
                         mean_keys: float) -> float:
    """A prefill launch of `positions` valid positions in `rows` rows:
    the front blocks (n/2 + 2 of n) over every position, each position's
    attention over `mean_keys` keys in the full layer and
    min(mean_keys, window) in a window layer; the tail blocks and the
    head on ONE position a row, that position's cross-attention over the
    row's whole context (taken as 2 x `mean_keys`: a chunk's mean query
    sees half of what its last one does)."""
    z = sizes(cfg)
    pair = z["qh"] / 2 * attention_flops_per_key(cfg)
    front = positions * (
        2.0 * _front_params(cfg) + z["n_mamba"] * scan_flops_per_token(cfg)
        + pair * (z["n_full"] * mean_keys
                  + z["n_window"] * min(mean_keys, z["window"])))
    tail = rows * (2.0 * _tail_params(cfg)
                   + pair * z["n_cross"] * 2.0 * mean_keys)
    return front + tail


# -- what the readers of this architecture's own parts call -------------------


def selective_update_floor(cfg: dict, steps: float, rows: float) -> dict:
    """The one-token state update of `steps` decode steps of `rows` live
    rows, all Mamba layers: every live row's float32 state read and
    written once a layer a step, and the recurrence's operations."""
    z = sizes(cfg)
    calls = steps * rows * z["n_mamba"]
    return {"bytes": calls * 2.0 * F32 * z["state"] * z["d"],
            "flops": calls * scan_flops_per_token(cfg)}


def selective_scan_floor(cfg: dict, positions: float, rows: float) -> dict:
    """The prefill scan over `positions` valid positions in `rows` rows,
    all Mamba layers: a position's u (bf16), dt (float32, as the
    recurrence reads it), B and C read, its y (float32) written, a row's
    state read and written once; the recurrence's operations."""
    z = sizes(cfg)
    per_position = ((BF16 + F32 + F32) * z["d"] + 2 * BF16 * z["state"])
    return {"bytes": z["n_mamba"] * (
                positions * per_position
                + rows * 2.0 * F32 * z["state"] * z["d"]),
            "flops": z["n_mamba"] * positions * scan_flops_per_token(cfg)}
