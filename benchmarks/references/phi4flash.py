"""The plain reference of the phi4flash architecture (microsoft
Phi-4-mini-flash-reasoning; SambaY, arXiv:2507.06607, with differential
attention, arXiv:2410.05258): `jax.numpy`, float32, `highest` matmul
precision; no kernels, no cache, no pages, no batching. It imports
nothing of the program and takes nothing the program made.
`dtbench/reference.py` loads it by the path a configuration's file gives
(`reference.module`) and asks for `logits_for(samples, cfg, pad_to,
lower=None)`.

The equations (h = `hidden_size`, n = `num_hidden_layers` blocks, eps =
`layer_norm_eps`; D = `mamba_expand` x h channels, N = `mamba_d_state`, R
= `mamba_dt_rank`, K = `mamba_d_conv`; hd = h / `num_attention_heads`).
Every block, and EVERY position through all n of them:

    x <- x + Mixer_l(LN(x));  x <- x + fc2(silu(g) * u), [g | u] = fc1 LN(x)
    LN(x) = (x - mean) / sqrt(var + eps) * w + b    (weight AND bias)

with `intermediate_size` wide MLPs without bias, no positional term
anywhere, and logits = LN_f(x) Emb^T (tied head). Which mixer block l has
(`block_kinds`): under n/2 + 2, Mamba-1 where l % `mb_per_layer` == 0 and
differential attention else, over the last `sliding_window` positions
under n/2 and over everything at n/2 + 1; from n/2 + 2 on, a gated memory
unit where l % `mb_per_layer` == 0 and cross-attention else.

  mamba   [u | z] = a W_in; u = silu(conv_K(u) + b_c), causal, a channel
          at a time (tap K-1 on the current position); [dt_r | B | C] =
          u W_x; dt = softplus(dt_r W_dt + b_dt); A = -exp(A_log), one
          decay a channel AND a state column; in time order, as a
          `lax.scan`:  s_t = exp(dt_t (x) A) * s_{t-1} + (dt_t u_t) (x) B_t;
          y_t = s_t C_t + D u_t;  out = (y * silu(z)) W_out.
          Block n/2's y, BEFORE the gate, is the memory m.
  attn    [q | k | v] = a W_qkv + b; query pair p = (q_2p, q_2p+1) reads
          KV pair g = p // (query pairs a KV pair);
          a1 = softmax(q_2p k_2g^T / sqrt(hd)) [v_2g | v_2g+1],
          a2 = softmax(q_2p+1 k_2g+1^T / sqrt(hd)) [v_2g | v_2g+1]: TWO
          softmaxes and a subtraction, computed as written;
          lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
          lambda_init = 0.8 - 0.6 exp(-0.3 l);
          o_p = (1 - lambda_init) RMSNorm_2hd(a1 - lambda a2; w_s, eps);
          out = concat_p(o_p) W_o + b_o. Position i sees j <= i, a
          window layer those with i - j < `sliding_window`.
  gmu     out = (m_t * silu(a W_1)) W_2, m_t the memory AT THE SAME
          POSITION.
  cross   q = a W_q + b, no keys or values of its own: k, v are block
          n/2 + 1's of the same forward; differential attention as above
          with this block's own lambda and sub-norm.

Scores are computed for Q_BLOCK query positions at a time (exact: a
softmax row needs its own keys only), a window layer's over the window +
Q_BLOCK keys that block can see, and the MLP for ROW_BLOCK positions at a
time, so that 8,960 positions fit at the published widths.

The weights are a recipe of this file's own, not read from the server
(tests/test_phi4flash_model.py holds `models/hybrid.py` to it). The
program runs a block as two mixers, so keys split from
`PRNGKey(weight_seed)` as (embedding, mixer 0..2n-1, unused): block l's
token mixer is mixer 2l, its MLP mixer 2l + 1. A mixer's key k splits 15
ways (ks); what this family adds draws from `fold_in(k, 100 + j)`
("extra j"). Matrices are `normal / sqrt(fan_in)` rounded to the model
dtype (bfloat16 by `lax.reduce_precision`); norm weights are ones.

  * a matrix that writes into the residual stream (W_out, W_o, W_2, fc2)
    has its mean over its input axes taken off, then adds STREAM_MEAN x
    its first output lane's column to every column (a mean over the
    lanes for the LayerNorm to take off), then x the mixer's gain as a
    float32 scalar (`branch_gains`): the head is tied and the embedding
    1/sqrt(h) wide, so the stream has to outgrow it, and every mixer of
    this stack is gated or bilinear, so a branch as wide as the stream
    it joins grows a relative error 1.6 times a block (bf16 matmul
    inputs anywhere in the first 18 blocks then share no token with
    float32 at the published sizes). So the growth is ONE step: mixer 0
    writes FIRST_JUMP = 60 times the embedding's spread, and every
    later mixer BRANCH_SHARE = 0.25 of the stream it joins, whatever
    its kind: its gain is over the spread a unit-gain mixer of that
    kind writes at the published widths (KIND_SPREAD);
  * wq and wk x SHARP_QK_GAIN: two softmaxes that weigh hundreds of keys
    alike read the same mean of the values, a1 - lambda a2 is (1 -
    lambda) times it, and the sub-norm takes lambda out again; at scores
    of spread 2.25 they differ, and lambda moves the logits;
  * biases BIAS_SPREAD x normal (a norm's: extra 0; bq, bk, bv, bo: extra
    1..4, bo x the branch's gain; the final norm's from fold_in(embedding
    key, 1)); the four lambda vectors LAMBDA_SPREAD x normal, float32
    (extra 5..8); the sub-norm's weight 1 + SUBLN_SPREAD x normal (extra
    9): zeros and ones would let a program that drops them pass;
  * attention: wq, wk, wv, wo = ks 0..3; cross-attention the same without
    wk, wv; MLP: gate, up, down = ks 0..2; gmu: W_1 = ks 0, W_2 = ks 6;
  * mamba: W_in [h, 2D] = ks 0, taps [K, D] = ks 1, conv bias 0.1 x
    normal = ks 2, dt bias the inverse softplus of a log-uniform draw
    over [0.001, 0.1] (floor 1e-4) = ks 3, A uniform in [-16, -1] for
    every (column, channel), drawn [N, D] = ks 4, D = 1, W_out = ks 6, W_x
    [D, R + 2N] = extra 10, W_dt [R, D] = extra 11; the last four float32.

A control (`lower`, one entry of the file's `check.controls` or
`further_controls`) is this forward with ONE thing changed: {"act":
"fp8"} rounds every matmul input to e4m3 (the stated bf16 a step down);
{"state": "bf16"} rounds the scan's state to bfloat16 after every step;
and four that hold the architecture: {"lambda": "zero"} (a1 alone),
{"memory": "early"} (m taken from block n/2 - 2), {"cross": "window"}
(cross-attention sees the last `sliding_window` positions only),
{"window": "double"} (the window layers see 2 x `sliding_window`). Never
part of a benchmark run.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256
ROW_BLOCK = 2048
FIRST_JUMP, BRANCH_SHARE = 60.0, 0.25
KIND_SPREAD = {"mamba": 0.42, "mlp": 0.72, "window": 0.4, "full": 0.34,
               "gmu": 0.41, "cross": 0.34}
SHARP_QK_GAIN = 1.5
STREAM_MEAN = 0.5
BIAS_SPREAD = 0.25
SUBLN_SPREAD = 0.25
LAMBDA_SPREAD = 0.1
DT_MIN, DT_MAX, DT_FLOOR = 0.001, 0.1, 1e-4


# -- weights (a recipe, not a copy) -----------------------------------------


def _rounded(w, dtype):
    """`w` (float32) rounded to the model dtype and STORED in it: the
    forward keeps every mixer's weights across the samples (7.7 GB as
    bfloat16 where float32 would not fit beside them), and a float32
    activation times a bfloat16 weight is a float32 product of the same
    values."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.float32, jnp.bfloat16):
        raise ValueError(f"model dtype {dtype}")
    return w.astype(dtype)


def _dense(key, shape, fan_in: int, dtype, centre=None, gain=None):
    """`centre`: the input axes of a matrix that writes into the residual
    stream: their mean is taken off, then the lane mean goes in, then the
    gain (a float32 scalar, traced: one compiled program draws every
    mixer of a kind), then the rounding."""
    w = jax.random.normal(key, shape, dtype=jnp.float32) / math.sqrt(fan_in)
    if centre is not None:
        w = w - jnp.mean(w, axis=centre, keepdims=True)
        w = w + STREAM_MEAN * w[..., :1]
    if gain is not None:
        w = w * gain
    return _rounded(w, dtype)


def _bias(key, shape, dtype, gain=1.0):
    return _rounded(BIAS_SPREAD * gain
                    * jax.random.normal(key, shape, dtype=jnp.float32), dtype)


def sizes(cfg: dict) -> dict:
    h, qh = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"h": h, "n": cfg["num_hidden_layers"], "qh": qh,
            "kh": cfg["num_key_value_heads"], "hd": h // qh,
            "m": cfg["intermediate_size"], "d": cfg["mamba_expand"] * h,
            "state": cfg["mamba_d_state"], "rank": cfg["mamba_dt_rank"],
            "taps": cfg["mamba_d_conv"], "window": cfg["sliding_window"],
            "eps": cfg["layer_norm_eps"], "dtype": cfg["dtype"]}


def block_kinds(cfg: dict) -> list[str]:
    """mamba | window | full | gmu | cross for each block."""
    n, per = cfg["num_hidden_layers"], cfg["mb_per_layer"]
    kinds = []
    for l in range(n):
        token = l % per == 0
        if l < n // 2 + 2:
            kinds.append("mamba" if token else
                         "window" if l < n // 2 else "full")
        else:
            kinds.append("gmu" if token else "cross")
    if (kinds[n // 2], kinds[n // 2 + 1]) != ("mamba", "full"):
        raise ValueError(f"{n} blocks, mb_per_layer {per}: block n/2 is "
                         "not the memory's and n/2 + 1 not the shared KV's")
    return kinds


def branch_gains(cfg: dict) -> list:
    """What each mixer's residual writers are multiplied by (mixer 2l is
    block l's token mixer, 2l + 1 its MLP), as the float32 scalars the
    weight programs take. The embedding enters 1/sqrt(h) wide; mixer 0
    writes FIRST_JUMP times that, and every later mixer BRANCH_SHARE of
    the stream it joins, each over the spread a unit-gain mixer of its
    kind writes (KIND_SPREAD)."""
    kinds = [k for kind in block_kinds(cfg) for k in (kind, "mlp")]
    stream, gains = 1.0 / math.sqrt(cfg["hidden_size"]), []
    for m, kind in enumerate(kinds):
        branch = stream * (FIRST_JUMP if m == 0 else BRANCH_SHARE)
        gains.append(jnp.float32(branch / KIND_SPREAD[kind]))
        stream = math.hypot(stream, branch)
    return gains


def _extra(key, j: int):
    return jax.random.fold_in(key, 100 + j)


def norm_weights(key, cfg: dict) -> dict:
    """A mixer's LayerNorm: ones and a drawn bias."""
    return {"norm_b": _bias(_extra(key, 0), (cfg["hidden_size"],),
                            cfg["dtype"])}


def attention_weights(key, cfg: dict, gain, cross: bool) -> dict:
    z = sizes(cfg)
    h, hd, qh, kh, dtype = z["h"], z["hd"], z["qh"], z["kh"], z["dtype"]
    ks = jax.random.split(key, 15)
    w = {"wq": _dense(ks[0], (h, qh, hd), h, dtype, gain=SHARP_QK_GAIN),
         "wo": _dense(ks[3], (qh, hd, h), qh * hd, dtype, (0, 1), gain),
         "bq": _bias(_extra(key, 1), (qh, hd), dtype),
         "bo": _bias(_extra(key, 4), (h,), dtype, gain),
         "subln": _rounded(1.0 + SUBLN_SPREAD * jax.random.normal(
             _extra(key, 9), (2 * hd,), dtype=jnp.float32), dtype),
         **norm_weights(key, cfg)}
    for j, name in enumerate(("lq1", "lk1", "lq2", "lk2")):
        w[name] = LAMBDA_SPREAD * jax.random.normal(
            _extra(key, 5 + j), (hd,), dtype=jnp.float32)
    if not cross:
        w.update({
            "wk": _dense(ks[1], (h, kh, hd), h, dtype, gain=SHARP_QK_GAIN),
            "wv": _dense(ks[2], (h, kh, hd), h, dtype),
            "bk": _bias(_extra(key, 2), (kh, hd), dtype),
            "bv": _bias(_extra(key, 3), (kh, hd), dtype)})
    return w


def mamba_weights(key, cfg: dict, gain) -> dict:
    z = sizes(cfg)
    h, d, n, rank, dtype = z["h"], z["d"], z["state"], z["rank"], z["dtype"]
    ks = jax.random.split(key, 15)
    u = jax.random.uniform(ks[3], (d,), dtype=jnp.float32)
    dt = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN))
                 + math.log(DT_MIN))
    dt = jnp.maximum(dt, DT_FLOOR)
    return {"in_proj": _dense(ks[0], (h, 2 * d), h, dtype),
            "conv_w": _dense(ks[1], (z["taps"], d), z["taps"], dtype),
            "conv_b": _rounded(0.1 * jax.random.normal(
                ks[2], (d,), dtype=jnp.float32), dtype),
            "x_proj": _dense(_extra(key, 10), (d, rank + 2 * n), d, dtype),
            "dt_proj": _dense(_extra(key, 11), (rank, d), rank, dtype),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a": -jax.random.uniform(ks[4], (n, d), dtype=jnp.float32,
                                     minval=1.0, maxval=16.0),
            "out_proj": _dense(ks[6], (d, h), d, dtype, 0, gain),
            **norm_weights(key, cfg)}


def gmu_weights(key, cfg: dict, gain) -> dict:
    z = sizes(cfg)
    ks = jax.random.split(key, 15)
    return {"g_in": _dense(ks[0], (z["h"], z["d"]), z["h"], z["dtype"]),
            "g_out": _dense(ks[6], (z["d"], z["h"]), z["d"], z["dtype"], 0,
                            gain),
            **norm_weights(key, cfg)}


def mlp_weights(key, cfg: dict, gain) -> dict:
    z = sizes(cfg)
    h, m, dtype = z["h"], z["m"], z["dtype"]
    ks = jax.random.split(key, 15)
    return {"gate": _dense(ks[0], (h, m), h, dtype),
            "up": _dense(ks[1], (h, m), h, dtype),
            "down": _dense(ks[2], (m, h), m, dtype, 0, gain),
            **norm_weights(key, cfg)}


def embedding(key, cfg: dict):
    """[vocab_size, h], 1/sqrt(h) wide: the head too."""
    h = cfg["hidden_size"]
    w = (jax.random.normal(key, (cfg["vocab_size"], h), dtype=jnp.float32)
         * (1.0 / math.sqrt(h)))
    return _rounded(w, cfg["dtype"])


def final_norm_bias(key, cfg: dict):
    return _bias(jax.random.fold_in(key, 1), (cfg["hidden_size"],),
                 cfg["dtype"])


def model_keys(cfg: dict):
    """(embedding, mixer 0..2n-1, unused) for the n blocks."""
    return jax.random.split(jax.random.PRNGKey(int(cfg["weight_seed"])),
                            2 * cfg["num_hidden_layers"] + 2)


# -- the forward pass ---------------------------------------------------------


def _choice(lower: dict, key: str, value: str) -> bool:
    """Whether the control moves `key` to `value` (any other value of a
    key this file knows is a mistake in the configuration's file)."""
    got = lower.get(key)
    if got is not None and got != value:
        raise ValueError(f"control {key}: {got!r}")
    return got == value


def _round_act(x, lower: dict):
    """Control only: round a matmul input to e4m3."""
    return (x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            if _choice(lower, "act", "fp8") else x)


def norm(x, bias, eps: float):
    """LayerNorm with unit weight (the seeded weights are ones) and a
    bias."""
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) + bias


def mamba_mixer(a, w: dict, cfg: dict, lower: dict):
    """a [T, hidden], already normed -> (out [T, hidden], y [T, D]: the
    scan's output before the gate)."""
    z = sizes(cfg)
    d, n, rank, taps = z["d"], z["state"], z["rank"], z["taps"]
    t = a.shape[0]
    uz = _round_act(a, lower) @ w["in_proj"]
    u, gate = uz[:, :d], uz[:, d:]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(padded[i:i + t] * w["conv_w"][i]
                        for i in range(taps)) + w["conv_b"])
    dbc = _round_act(u, lower) @ w["x_proj"]
    dt = jax.nn.softplus(_round_act(dbc[:, :rank], lower) @ w["dt_proj"]
                         + w["dt_bias"])
    b, c = dbc[:, rank:rank + n], dbc[:, rank + n:]
    bf16_state = _choice(lower, "state", "bf16")

    def step(s, xs):
        dt_t, u_t, b_t, c_t = xs  # [D], [D], [N], [N]
        s = (jnp.exp(dt_t[None, :] * w["a"]) * s
             + (dt_t * u_t)[None, :] * b_t[:, None])
        if bf16_state:
            s = jax.lax.reduce_precision(s, exponent_bits=8,
                                         mantissa_bits=7)
        return s, jnp.sum(s * c_t[:, None], axis=0)

    # (unrolled eight positions a turn: the same recurrence in the same
    # order, fewer turns of the loop)
    _, y = jax.lax.scan(step, jnp.zeros((n, d), jnp.float32),
                        (dt, u, b, c), unroll=8)
    y = y + u  # D = 1
    return _round_act(y * jax.nn.silu(gate), lower) @ w["out_proj"], y


def _softmax_values(q, k, v, window: int, hd: int, lower: dict):
    """softmax(q k^T / sqrt(hd) + mask) v, one softmax of a pair. q [T,
    pairs, hd]; k [T, kv pairs, hd]; v [T, kv pairs, 2 hd] -> [T, pairs,
    2 hd]. Position i sees j <= i, with `window` those with i - j <
    window; a block of Q_BLOCK queries at a time."""
    t, pairs, _ = q.shape
    kv = k.shape[1]
    n = Q_BLOCK if t > Q_BLOCK else t
    back = min(-(-(window - 1) // n) * n, t - n) if window else 0
    keys = back + n if window else t
    k_pad = jnp.pad(k, ((back, 0), (0, 0), (0, 0)))
    v_pad = jnp.pad(v, ((back, 0), (0, 0), (0, 0)))

    def block(args):
        qb, start = args
        q_pos = start + jnp.arange(n)
        at = start if window else 0
        kb = jax.lax.dynamic_slice_in_dim(k_pad, at, keys)
        vb = jax.lax.dynamic_slice_in_dim(v_pad, at, keys)
        kv_pos = at - back + jnp.arange(keys)
        qg = _round_act(qb, lower).reshape(n, kv, pairs // kv, hd)
        scores = jnp.einsum("tkgd,skd->kgts", qg, kb) / math.sqrt(hd)
        seen = (kv_pos[None, :] <= q_pos[:, None]) & (kv_pos[None, :] >= 0)
        if window:
            seen = seen & (kv_pos[None, :] > q_pos[:, None] - window)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("kgts,skd->tkgd", _round_act(probs, lower), vb)

    out = jax.lax.map(block, (q.reshape(t // n, n, pairs, hd),
                              jnp.arange(t // n) * n))
    return out.reshape(t, pairs, v.shape[-1])


def keys_values(a, w: dict, cfg: dict, lower: dict):
    """The keys and values an attention block writes for the sequence:
    k [T, kv heads, hd] (rounded as a matmul input where a control says
    so), v the same."""
    x = _round_act(a, lower)
    k = jnp.einsum("th,hkd->tkd", x, w["wk"]) + w["bk"]
    v = jnp.einsum("th,hkd->tkd", x, w["wv"]) + w["bv"]
    return _round_act(k, lower), v


def lambda_init(block: int):
    """0.8 - 0.6 exp(-0.3 l) for block index l, as the float32 scalar
    the attention program takes."""
    return jnp.float32(0.8 - 0.6 * math.exp(-0.3 * block))


def diff_attention(a, w: dict, k, v, init, window: int, cfg: dict,
                   lower: dict):
    """a [T, hidden], already normed; k, v [T, kv heads, hd]: this
    block's own, or for cross-attention the shared block's; `init`: the
    block's `lambda_init`."""
    z = sizes(cfg)
    t, qh, kh, hd = a.shape[0], z["qh"], z["kh"], z["hd"]
    q = jnp.einsum("th,hqd->tqd", _round_act(a, lower), w["wq"]) + w["bq"]
    q = q.reshape(t, qh // 2, 2, hd)
    k = k.reshape(t, kh // 2, 2, hd)
    values = v.reshape(t, kh // 2, 2 * hd)  # [v_2g | v_2g+1]
    a1 = _softmax_values(q[:, :, 0], k[:, :, 0], values, window, hd, lower)
    a2 = _softmax_values(q[:, :, 1], k[:, :, 1], values, window, hd, lower)
    lam = (jnp.exp(jnp.sum(w["lq1"] * w["lk1"]))
           - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + init)
    if _choice(lower, "lambda", "zero"):
        lam = 0.0
    diff = a1 - lam * a2
    diff = diff * jax.lax.rsqrt(
        jnp.mean(diff * diff, axis=-1, keepdims=True) + z["eps"])
    out = (diff * w["subln"] * (1.0 - init)).reshape(t, qh, hd)
    return jnp.einsum("tqd,qdh->th", _round_act(out, lower),
                      w["wo"]) + w["bo"]


def gmu_mixer(a, memory, w: dict, lower: dict):
    gate = jax.nn.silu(_round_act(a, lower) @ w["g_in"])
    return _round_act(memory * gate, lower) @ w["g_out"]


def mlp_mixer(a, w: dict, lower: dict):
    t = a.shape[0]
    n = ROW_BLOCK if t % ROW_BLOCK == 0 else t

    def one(ab):
        x = _round_act(ab, lower)
        return _round_act(jax.nn.silu(x @ w["gate"]) * (x @ w["up"]),
                          lower) @ w["down"]

    return jax.lax.map(one, a.reshape(t // n, n, -1)).reshape(t, -1)


class Forward:
    """The forward pass of one configuration, its programs compiled once
    a kind of block, its weights drawn once (`weights`: mixer by mixer at
    first use, kept in the model dtype for the samples that follow)."""

    def __init__(self, cfg: dict, lower: dict | None = None) -> None:
        self.cfg, self.lower = cfg, lower or {}
        if not cfg["tie_word_embeddings"] or cfg.get("mlp_bias"):
            raise ValueError("this reference's head is the embedding and "
                             "its MLPs have no bias")
        self.kinds = block_kinds(cfg)
        self.gains = branch_gains(cfg)
        self.drawn: dict = {}
        self.keys = model_keys(cfg)
        eps, lower, n = cfg["layer_norm_eps"], self.lower, len(self.kinds)
        window = cfg["sliding_window"]
        self.window = 2 * window if _choice(lower, "window", "double") \
            else window
        self.cross_window = window if _choice(lower, "cross", "window") else 0
        self.memory_block = (n // 2 - 2 if _choice(lower, "memory", "early")
                             else n // 2)
        self.embed = jax.jit(lambda: embedding(self.keys[0], cfg))()
        self.final_bias = final_norm_bias(self.keys[0], cfg)
        self.make = {  # (key, gain) -> weights: a program a kind
            "mamba": jax.jit(lambda key, gain: mamba_weights(
                key, cfg, gain)),
            "attention": jax.jit(lambda key, gain: attention_weights(
                key, cfg, gain, False)),
            "cross": jax.jit(lambda key, gain: attention_weights(
                key, cfg, gain, True)),
            "gmu": jax.jit(lambda key, gain: gmu_weights(key, cfg, gain)),
            "mlp": jax.jit(lambda key, gain: mlp_weights(key, cfg, gain))}
        self.norm = jax.jit(lambda x, bias: norm(x, bias, eps))
        self.mamba = jax.jit(lambda a, w: mamba_mixer(a, w, cfg, lower))
        self.keys_values = jax.jit(
            lambda a, w: keys_values(a, w, cfg, lower))
        self.attend = jax.jit(
            lambda a, w, k, v, init, window: diff_attention(
                a, w, k, v, init, window, cfg, lower),
            static_argnums=5)
        self.gmu = jax.jit(lambda a, m, w: gmu_mixer(a, m, w, lower))
        self.mlp = jax.jit(lambda a, w: mlp_mixer(a, w, lower))
        self.final = jax.jit(lambda x, rows, embed, bias: _round_act(
            norm(x[rows], bias, eps), lower) @ embed.T)

    def weights(self, kind: str, mixer: int) -> dict:
        """Mixer `mixer`'s weights, drawn at first use."""
        if mixer not in self.drawn:
            self.drawn[mixer] = self.make[kind](self.keys[mixer + 1],
                                                self.gains[mixer])
        return self.drawn[mixer]

    def hidden(self, ids) -> jax.Array:
        """[T, hidden] after the last block, for one sequence of ids."""
        x = self.embed[jnp.asarray(ids)]
        memory = shared = None
        for l, kind in enumerate(self.kinds):
            if kind == "mamba":
                w = self.weights("mamba", 2 * l)
                out, y = self.mamba(self.norm(x, w["norm_b"]), w)
                if l == self.memory_block:
                    memory = y
            elif kind in ("window", "full"):
                w = self.weights("attention", 2 * l)
                a = self.norm(x, w["norm_b"])
                k, v = self.keys_values(a, w)
                if kind == "full":
                    shared = (k, v)
                out = self.attend(a, w, k, v, lambda_init(l),
                                  self.window if kind == "window" else 0)
            elif kind == "cross":
                w = self.weights("cross", 2 * l)
                out = self.attend(self.norm(x, w["norm_b"]), w, *shared,
                                  lambda_init(l), self.cross_window)
            else:
                w = self.weights("gmu", 2 * l)
                out = self.gmu(self.norm(x, w["norm_b"]), memory, w)
            x = x + out
            w = self.weights("mlp", 2 * l + 1)
            x = x + self.mlp(self.norm(x, w["norm_b"]), w)
        return x

    def logits(self, x, rows) -> jax.Array:
        """Logits [len(rows), vocab] of the final hidden states' `rows`."""
        return self.final(x, jnp.asarray(rows), self.embed, self.final_bias)


def logits_for(samples: list[dict], cfg: dict, pad_to: int,
               lower: dict | None = None) -> list[np.ndarray]:
    """For each sample (prompt ids, served ids): the logits, [n_served,
    vocab], at the positions that predicted each served token, from one
    full forward over prompt + served tokens padded to `pad_to` (causal:
    the padding behind a sequence changes nothing before it). One sample
    at a time, and the head over the served rows only (a fixed count,
    one compiled shape), so that pad_to x vocab never exists."""
    out = []
    if pad_to > Q_BLOCK:
        pad_to = -(-pad_to // ROW_BLOCK) * ROW_BLOCK
    with jax.default_matmul_precision("highest"):
        forward = Forward(cfg, lower)
        n_rows = max(len(s["served"]) for s in samples)
        for s in samples:
            seq = list(s["prompt"]) + list(s["served"])
            ids = np.zeros(pad_to, np.int32)
            ids[:len(seq)] = seq
            n_p, n_s = len(s["prompt"]), len(s["served"])
            rows = np.full(n_rows, n_p + n_s - 2, np.int32)
            rows[:n_s] = np.arange(n_p - 1, n_p + n_s - 1)
            out.append(np.asarray(
                forward.logits(forward.hidden(ids), rows))[:n_s])
    return out
