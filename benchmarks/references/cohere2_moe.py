"""The plain reference of the cohere2_moe architecture (CohereLabs
command-a-plus-05-2026, the language model alone): `jax.numpy`, float32,
`highest` matmul precision; no kernels, no cache, no pages, no batching.
It imports nothing of the program and takes nothing the program made.
`dtbench/reference.py` loads it by the path a configuration's file gives
(`reference.module`) and asks for `logits_for(samples, cfg, pad_to,
lower=None)`.

The equations (h = `hidden_size`, eps = `layer_norm_eps`). Block `l` of
`layer_types` (as the file cuts it: its length is the depth served),
every one a PARALLEL block (`use_parallel_block`): attention and experts
read ONE normed input and join the stream in ONE add.

    u = LN_l(x) = (x - mean(x)) / sqrt(var(x) + eps) * g_l
                    float32, a weight and no bias; mean and var over h
    q = u Wq [num_attention_heads x head_dim], k = u Wk, v = u Wv
                    (num_key_value_heads KV heads; no bias, no q/k norm)
    sliding_attention: q, k <- RoPE(`rope_theta`, every lane: `rotary_pct`
        1) on lane PAIRS (2i, 2i+1), the angle of pair i position x
        theta^(-2i/d) (`position_embedding_type` rope_gptj); position i
        sees j with 0 <= i - j < `sliding_window`
    full_attention: NO positional term; position i sees every j <= i
    a = softmax(q k^T / sqrt(head_dim) + mask) v Wo
    s = sigmoid_float32(u Wr) over all published experts
        (`expert_selection_fn`); T = the `num_experts_per_tok` largest of
        s (no selection bias, no groups); w_e = s_e / sum_T s
        (`norm_topk_prob`), no scaling factor
    m = sum_{e in T} w_e E_e(u)  +  (1 / S) sum_{j < S} S_j(u)
        E, S: W_down (silu(W_gate u) * W_up u), `intermediate_size` wide;
        S = `num_shared_experts`, their outputs AVERAGED
        (`shared_expert_combination_strategy` average) and that mean
        ADDED to the routed sum
    x <- x + a + m
after the last block: logits = LN_f(x) Emb^T * `logit_scale` (tied head).

Departures, each because the configuration's file says so: only the
leading `len(layer_types)` blocks exist (one pipeline stage), given the
final norm and the tied head so that it serves tokens; where the file
gives `experts_held` = [lo, hi) only those routed experts are computed (a
token routed to an absent one gets nothing from it; the router keeps its
`num_experts_published` outputs and its top-k; the shared experts are
whole); `vocab_size` is the number of leading vocabulary rows held; no
vision tower. Scores are computed for Q_BLOCK query positions at a time
(exact: a softmax row needs its own keys only), a sliding layer's over
the `sliding_window` + Q_BLOCK keys that block can see (exact again: the
keys left out are masked), and the experts for EXPERT_BLOCK positions at
a time, so that a 12,288-position sample fits at the published widths.

The weights are a recipe of this file's own, not read from the server
(tests/test_cohere2_model.py and tests/bench/test_bench_command_a_plus.py
hold `models/hybrid.py` to it). The program runs a block as two mixers,
so keys split from `PRNGKey(weight_seed)` as (embedding, mixer 0..2L-1,
unused): block l's attention is mixer 2l, its experts mixer 2l+1; a
mixer's key split 15 ways. Matrices are `normal(key) / sqrt(fan_in)`
rounded to the model dtype (bfloat16 by `lax.reduce_precision`, which no
compiler folds away); norm gains are ones. What this family needs beside
that, and why:

  * a matrix that writes into the residual stream (wo, every W_down)
    first has its mean over its INPUT axes taken off each column, so
    that silu's positive average gives the tokens no common direction
    for the router to follow (PERF.md, PR 30);
  * it then adds STREAM_MEAN = 0.5 times its FIRST output lane's column
    to every column: each branch writes a mean over the lanes (half
    lane 0's value, different for every token, as wide as half the
    branch's spread). Every reader of the stream is a LayerNorm, which
    takes it off again; a model that norms by the root mean square
    alone reads something else. Seeded matrices have lane means of
    spread 1/sqrt(h), and the two norms could not be told apart; a
    trained stream has a mean, which is what the subtraction is for;
  * the head is tied and the embedding drawn 1/sqrt(h) wide (logits of
    spread 1), so whatever of Emb[token] is left in the last hidden
    state scores the token itself: the stream has to outgrow it. A
    residual writer of mixer m (its PUBLISHED index, two a block) is
    multiplied by BRANCH_GROWTH^m = 1.23^m after the lane mean: unit
    gain in block 0 (whose branches are already 20 times the
    embedding's 1/64), 4.3 at mixer 7; the embedding ends under a
    hundredth of the stream;
  * wq and wk are drawn SHARP_QK_GAIN = 1.5 times wider: scores of
    spread 2.25. At spread 1 a softmax over a 4,096-key window weighs
    some 1,500 keys alike, the attention branch is a hundredth of its
    block's variance, and rope, no rope, a window or none all read
    inside bf16's rounding; at 2.25 it weighs some 26, as a trained
    model's attention does, and the branch is as wide as the experts'.

Attention: wq, wk (both x 1.5), wv, wo keys 0..3. Experts: W_r key 7
(normal / sqrt(h): logits of spread 1 on the normed input, sigmoid scores
0.27..0.73 at one spread; a 32-row step touches about 14 of this chip's
16 held experts: each is missed with probability (1 - 8/128)^32 = 0.127),
expert e's W_gate from fold_in(key 9, e), W_up from fold_in(key 11, e),
W_down from fold_in(key 10, e) with e the PUBLISHED index, so a chip's
share holds the experts the whole model would. The S shared experts are
drawn as ONE SwiGLU S x `intermediate_size` wide: W_gate key 12, W_up key
14, [h, S m] each, W_down key 13, [S m, h] with fan_in S m; shared expert
j is columns (rows) j m .. (j+1) m of them.

A control (`lower`, one entry of the file's `check.controls` or
`further_controls`) is this forward with ONE thing changed:
{"act": "fp8"} rounds every matmul input to e4m3 (the stated bf16 a step
down); and five that hold the mathematics, each one thing of this family
moved: {"block": "sequential"} (the experts read LN(x + a) with the same
weight and are added behind the attention: the usual pre-norm block
without its second gain), {"norm": "rms"} (no mean taken off, the final
norm too), {"rope": "all"} (the full layers rope as the sliding ones do),
{"window": "full"} (the sliding layers attend past their window),
{"shared": "sum"} (the shared experts' outputs summed, not averaged).
Never part of a benchmark run.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256
EXPERT_BLOCK = 2048
BRANCH_GROWTH = 1.23
SHARP_QK_GAIN = 1.5
STREAM_MEAN = 0.5


# -- weights (a recipe, not a copy) -----------------------------------------


def _rounded(w, dtype):
    """`w` (float32) at the precision of the model dtype, kept float32."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float32:
        return w
    if dtype != jnp.bfloat16:
        raise ValueError(f"model dtype {dtype}")
    return jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)


def _dense(key, shape, fan_in: int, dtype, centre=None, gain: float = 1.0):
    """`centre`: the input axes of a matrix that writes into the residual
    stream: their mean is taken off, then the lane mean goes in, then the
    gain, then the rounding."""
    w = jax.random.normal(key, shape, dtype=jnp.float32) / math.sqrt(fan_in)
    if centre is not None:
        w = w - jnp.mean(w, axis=centre, keepdims=True)
        w = w + STREAM_MEAN * w[..., :1]
    if gain != 1.0:
        w = w * gain
    return _rounded(w, dtype)


def sizes(cfg: dict) -> dict:
    lo, hi = cfg.get("experts_held") or (0, cfg["num_experts"])
    return {"h": cfg["hidden_size"], "hd": cfg["head_dim"],
            "qh": cfg["num_attention_heads"],
            "kh": cfg["num_key_value_heads"],
            "m": cfg["intermediate_size"], "lo": lo, "hi": hi,
            "router": cfg.get("num_experts_published", cfg["num_experts"]),
            "k": cfg["num_experts_per_tok"],
            "shared": cfg["num_shared_experts"],
            "eps": cfg["layer_norm_eps"], "dtype": cfg["dtype"]}


def branch_gain(mixer: int) -> float:
    return BRANCH_GROWTH ** mixer


def attention_weights(key, cfg: dict, mixer: int) -> dict:
    z = sizes(cfg)
    h, hd, qh, kh, dtype = z["h"], z["hd"], z["qh"], z["kh"], z["dtype"]
    ks = jax.random.split(key, 15)
    return {"wq": _dense(ks[0], (h, qh, hd), h, dtype, gain=SHARP_QK_GAIN),
            "wk": _dense(ks[1], (h, kh, hd), h, dtype, gain=SHARP_QK_GAIN),
            "wv": _dense(ks[2], (h, kh, hd), h, dtype),
            "wo": _dense(ks[3], (qh, hd, h), qh * hd, dtype, (0, 1),
                         branch_gain(mixer))}


def expert_weights(key, cfg: dict, mixer: int) -> dict:
    z = sizes(cfg)
    h, m, dtype, gain = z["h"], z["m"], z["dtype"], branch_gain(mixer)
    sm = z["shared"] * m
    ks = jax.random.split(key, 15)
    ids = jnp.arange(z["lo"], z["hi"])  # PUBLISHED indices

    def each(key, shape, fan_in, centre=None, gain=1.0):
        return jax.vmap(lambda e: _dense(jax.random.fold_in(key, e), shape,
                                         fan_in, dtype, centre, gain))(ids)

    return {"router": _dense(ks[7], (h, z["router"]), h, dtype),
            "gate": each(ks[9], (h, m), h), "up": each(ks[11], (h, m), h),
            "down": each(ks[10], (m, h), m, 0, gain),
            "s_gate": _dense(ks[12], (h, sm), h, dtype),
            "s_up": _dense(ks[14], (h, sm), h, dtype),
            "s_down": _dense(ks[13], (sm, h), sm, dtype, 0, gain)}


def embedding(key, cfg: dict):
    """[vocab_size, h], 1/sqrt(h) wide: the head too. The leading rows of
    the whole vocabulary's draw (a draw's rows do not depend on how many
    follow them)."""
    h = cfg["hidden_size"]
    w = (jax.random.normal(key, (cfg["vocab_size"], h), dtype=jnp.float32)
         * (1.0 / math.sqrt(h)))
    return _rounded(w, cfg["dtype"])


def model_keys(cfg: dict):
    """(embedding, mixer 0..2L-1, unused) for the L blocks served."""
    return jax.random.split(jax.random.PRNGKey(int(cfg["weight_seed"])),
                            2 * len(cfg["layer_types"]) + 2)


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


def norm(x, eps: float, lower: dict):
    """LayerNorm with unit gain (the seeded gains are ones) and no bias."""
    if not _choice(lower, "norm", "rms"):
        x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope_pairs(x, theta: float):
    """x [T, heads, d], positions 0..T-1: lane 2i turns with lane 2i+1 by
    position x theta^(-2i/d)."""
    t, _, d = x.shape
    inv_freq = theta ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d)
    angles = (jnp.arange(t, dtype=jnp.float32)[:, None]
              * inv_freq.astype(np.float32))
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention_mixer(u, w: dict, layer_type: str, cfg: dict, lower: dict):
    """u [T, hidden], already normed; T a multiple of Q_BLOCK or under it."""
    t = u.shape[0]
    z = sizes(cfg)
    qh, kh, hd = z["qh"], z["kh"], z["hd"]
    sliding = layer_type == "sliding_attention"
    if not sliding and layer_type != "full_attention":
        raise ValueError(f"layer type {layer_type!r}")
    a = _round_act(u, lower)
    q = jnp.einsum("th,hqd->tqd", a, w["wq"])
    k = jnp.einsum("th,hkd->tkd", a, w["wk"])
    v = jnp.einsum("th,hkd->tkd", a, w["wv"])
    if sliding or _choice(lower, "rope", "all"):
        theta = float(cfg["rope_theta"])
        q, k = rope_pairs(q, theta), rope_pairs(k, theta)
    k = _round_act(k, lower)
    window = (cfg["sliding_window"]
              if sliding and not _choice(lower, "window", "full") else 0)
    n = Q_BLOCK if t > Q_BLOCK else t
    # the keys a block of queries can see: all of them, or the window's
    # reach before the block's first query and the block itself (`back`
    # zero rows in front stand for positions before 0, which the causal
    # mask hides as it hides every key behind a query)
    back = min(-(-(window - 1) // n) * n, t - n) if window else 0
    keys = back + n if window else t
    k_pad = jnp.pad(k, ((back, 0), (0, 0), (0, 0)))
    v_pad = jnp.pad(v, ((back, 0), (0, 0), (0, 0)))

    def block(args):
        qb, start = args  # [n, heads, d], the block's first position
        q_pos = start + jnp.arange(n)
        at = start if window else 0  # where the block's keys start in k_pad
        kb = jax.lax.dynamic_slice_in_dim(k_pad, at, keys)
        vb = jax.lax.dynamic_slice_in_dim(v_pad, at, keys)
        kv_pos = at - back + jnp.arange(keys)
        qg = _round_act(qb, lower).reshape(n, kh, qh // kh, hd)
        scores = jnp.einsum("tkgd,skd->kgts", qg, kb) / math.sqrt(hd)
        seen = (kv_pos[None, :] <= q_pos[:, None]) & (kv_pos[None, :] >= 0)
        if window:
            seen = seen & (kv_pos[None, :] > q_pos[:, None] - window)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("kgts,skd->tkgd", _round_act(probs, lower), vb)

    attn = jax.lax.map(block, (q.reshape(t // n, n, qh, hd),
                               jnp.arange(t // n) * n))
    return jnp.einsum("tqd,qdh->th",
                      _round_act(attn.reshape(t, qh, hd), lower), w["wo"])


def _swiglu(x, gate, up):
    return jax.nn.silu(x @ gate) * (x @ up)


def moe_mixer(u, w: dict, cfg: dict, lower: dict):
    """u [T, hidden], already normed; a block of positions at a time."""
    z = sizes(cfg)
    t = u.shape[0]
    n = EXPERT_BLOCK if t % EXPERT_BLOCK == 0 else t
    share = 1.0 if _choice(lower, "shared", "sum") else 1.0 / z["shared"]

    def one(ub):
        scores = jax.nn.sigmoid(ub @ w["router"])  # float32, all published
        top, chosen = jax.lax.top_k(scores, z["k"])
        if cfg["norm_topk_prob"]:
            top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
        # [n, published]: the weight each token gives each expert
        per_expert = jnp.zeros_like(scores).at[
            jnp.arange(n)[:, None], chosen].set(top)
        xin = _round_act(ub, lower)

        def one_expert(out, inputs):
            gate, up, down, weight = inputs
            return out + weight[:, None] * (
                _round_act(_swiglu(xin, gate, up), lower) @ down), None

        held = per_expert[:, z["lo"]:z["hi"]].T  # absent ones add nothing
        routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(ub),
                                 (w["gate"], w["up"], w["down"], held))
        m = z["m"]
        shared = sum(
            _round_act(_swiglu(xin, w["s_gate"][:, j * m:(j + 1) * m],
                               w["s_up"][:, j * m:(j + 1) * m]), lower)
            @ w["s_down"][j * m:(j + 1) * m]
            for j in range(z["shared"]))
        return routed + share * shared

    return jax.lax.map(one, u.reshape(t // n, n, -1)).reshape(t, -1)


class Forward:
    """The forward pass of one configuration, its programs compiled once:
    mixer by mixer, so that one mixer's float32 weights are all that is
    live beside one sequence's activations."""

    def __init__(self, cfg: dict, lower: dict | None = None) -> None:
        self.cfg, self.lower = cfg, lower or {}
        if not (cfg["tie_word_embeddings"] and cfg["use_parallel_block"]):
            raise ValueError("this reference's head is the embedding and "
                             "its block parallel")
        if cfg["shared_expert_combination_strategy"] != "average":
            raise ValueError("shared_expert_combination_strategy")
        self.keys = model_keys(cfg)
        eps, lower = cfg["layer_norm_eps"], self.lower
        self.sequential = _choice(lower, "block", "sequential")
        self.embed = jax.jit(lambda: embedding(self.keys[0], cfg))()
        self.make_attention = jax.jit(
            lambda key, mixer: attention_weights(key, cfg, mixer),
            static_argnums=1)
        self.make_experts = jax.jit(
            lambda key, mixer: expert_weights(key, cfg, mixer),
            static_argnums=1)
        self.norm = jax.jit(lambda x: norm(x, eps, lower))
        self.attend = {
            kind: jax.jit(lambda u, w, kind=kind: attention_mixer(
                u, w, kind, cfg, lower))
            for kind in set(cfg["layer_types"])}
        self.experts = jax.jit(lambda u, w: moe_mixer(u, w, cfg, lower))
        self.final = jax.jit(lambda x, rows, embed: (_round_act(
            norm(x[rows], eps, lower), lower) @ embed.T)
            * float(cfg["logit_scale"]))

    def hidden(self, ids) -> jax.Array:
        """[T, hidden] after the last block, for one sequence of ids."""
        x = self.embed[jnp.asarray(ids)]
        for i, kind in enumerate(self.cfg["layer_types"]):
            u = self.norm(x)
            a = self.attend[kind](
                u, self.make_attention(self.keys[2 * i + 1], 2 * i))
            if self.sequential:  # control: the experts behind the attention
                x = x + a
                u, a = self.norm(x), 0.0
            m = self.experts(
                u, self.make_experts(self.keys[2 * i + 2], 2 * i + 1))
            x = x + a + m
        return x

    def logits(self, x, rows) -> jax.Array:
        """Logits [len(rows), vocab] of the final hidden states' `rows`."""
        return self.final(x, jnp.asarray(rows), self.embed)


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
        pad_to = -(-pad_to // EXPERT_BLOCK) * EXPERT_BLOCK
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
