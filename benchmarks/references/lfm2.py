"""The plain reference of the lfm2_moe architecture (LiquidAI
LFM2-8B-A1B): `jax.numpy`, float32, `highest` matmul precision; no
kernels, no cache, no pages, no batching. It imports nothing of the
program and takes nothing the program made. `dtbench/reference.py` loads
it by the path a configuration's file gives (`reference.module`) and asks
for `logits_for(samples, cfg, pad_to, lower=None)`.

The equations (h = `hidden_size`, eps = `norm_eps`). Block `l` of
`layer_types` (as the file cuts it: its length is the depth served):

    x_0 = Emb[token]                                       (no multiplier)
    x <- x + Op_l(RMSNorm(x; operator_norm_l))
    x <- x + FFN_l(RMSNorm(x; ffn_norm_l))
    logits = RMSNorm(x; embedding_norm) Emb^T              (tied head)

  conv  the gated short convolution. [B | C | u] = a W_in, W_in
     [h, 3h], no bias, three thirds of h in that order. v_t = B_t * u_t
     (elementwise). c_t = w_0 v_{t-2} + w_1 v_{t-1} + w_2 v_t: depthwise,
     causal, K = `conv_L_cache` = 3 taps, no bias (`conv_bias` false), NO
     activation; v before position 0 is zero. out = (C_t * c_t) W_out,
     W_out [h, h].
  full_attention  q = a W_q -> [num_attention_heads, hd], k = a W_k, v =
     a W_v -> [num_key_value_heads, hd], hd = h / num_attention_heads, no
     biases. q <- RMSNorm_hd(q; q_layernorm), k <- RMSNorm_hd(k;
     k_layernorm): per head, eps `norm_eps`, BEFORE rope. Rope
     `rope_theta` over all hd lanes, rotate-half. Causal softmax(q.k /
     sqrt(hd)), num_attention_heads / num_key_value_heads queries a kv
     head. out = attn W_o.
  FFN of block l < `num_dense_layers`: W_2 (silu(W_1 a) * W_3 a),
     `intermediate_size` wide.
  FFN of the others: s = sigmoid(a W_r) in float32 over all
     `num_experts`; the `num_experts_per_tok` largest of s + b (b =
     expert_bias, `use_expert_bias`); w_e = s_e / (sum of the chosen s +
     1e-6) (`norm_topk_prob`) x `routed_scaling_factor`; out = sum_e w_e
     W_2,e (silu(W_1,e a) * W_3,e a), `moe_intermediate_size` wide; no
     shared expert.

Departures, each because the configuration's file says so: only the
leading `len(layer_types)` blocks exist (the first pipeline stage), given
the final norm and the tied head so that it serves tokens; where the file
gives `experts_held` = [lo, hi) only those experts are computed (a token
routed to an absent one gets nothing from it; the benchmark's file holds
all 32 and gives no such key). Attention scores are computed for 512
query positions at a time and the experts for 2,048 positions at a time
(both exact), so that a 3,072-position sample fits at the published
widths.

The weights are a recipe of this file's own, not read from the server
(tests/test_lfm2_model.py and tests/bench/test_bench_lfm2.py hold
`models/hybrid.py` to it). The program runs a block as two mixers, so
keys split from `PRNGKey(weight_seed)` as (embedding, mixer 0..2L-1,
head): block l's token mixer is mixer 2l, its feed-forward mixer 2l+1;
a mixer's key split 15 ways. Matrices are `normal(key) / sqrt(fan_in)`
rounded to the model dtype; a matrix that writes into the residual
stream (W_out, W_o, every W_2) first has its mean over its INPUT axes
taken off each column (PERF.md, PR 30). What a TIED head needs, as
granite_h.py states it for its family, and what THIS family needs beside
it:

  * the embedding, which is also the head, is drawn 1 / sqrt(h) wide, so
    that RMSNorm(x) Emb^T has the spread 1 every other recipe's logits
    have; x_0's entries then have spread s0 = 1 / sqrt(h) (0.0221);
  * a matrix that writes into the residual stream is, after centring,
    multiplied by the branch gain of its mixer m (the PUBLISHED index of
    the mixer, two a block). The head is tied, so whatever of Emb[token]
    is left in the last hidden state scores the token itself (at cosine
    c the self-logit is c sqrt(h) times the logits' spread): the stream
    has to outgrow the embedding. granite's recipe grows it 1.23 times a
    mixer; here that fails, and the reason is the family's own mixer: a
    gated short convolution is CUBIC in its input (B * u, gated by C), so
    a branch as wide as the stream it joins multiplies a relative error
    by 2.2, nine times over, and float32 and bf16 share no token
    (gap_mean 3.3 on the chip at the published sizes, where a token
    drawn at random reads 4.2; K and V rounded to int8 alone read 0.73:
    PERF.md, PR 44). A branch much wider than the stream costs a factor
    3 once. So the growth is ONE step: mixer 0 writes FIRST_JUMP = 60
    times s0 (the embedding is 1/60 of the stream from there on), and
    every later mixer BRANCH_SHARE = 0.25 of the stream it joins,
    whatever its kind: the gain is the branch's spread over the spread a
    unit-gain mixer of its kind writes (KIND_SPREAD: conv 1.0, dense 0.6,
    experts 0.3, attention 0.125, measured at the published widths on
    the chip: attention averages its values, an expert layer four
    experts). The stream ends 1.9 times what mixer 0 left;
  * wq and wk are drawn NORMED_QK_GAIN = 2 times wider, which the q and k
    norms take out again (a trained model's projections have no unit
    scale: that is what its norms are for). Without the norms the scores
    are four times sharper, so a program that forgets them is told from
    one that has them.

conv: W_in key 0 (columns B, C, u in that order), taps [K, h] key 1
(fan_in K; tap K-1 multiplies the current position), W_out key 6.
full_attention: wq, wk (both x 2), wv, wo keys 0..3; the norms' gains
are ones.
dense: W_1 (gate) key 0, W_3 (up) key 1, W_2 (down) key 2. Experts: W_r
key 7 (normal / sqrt(h): logits of spread 1, scores of spread 0.2 round
0.5), expert_bias 0.02 x normal key 8 in float32, expert e's W_1 from
fold_in(key 9, e), W_3 from fold_in(key 11, e), W_2 from fold_in(key 10,
e) with e the PUBLISHED index. Norm gains are ones. At that width the
bias changes the set of experts chosen for 36% of tokens (32 experts
top-4 at hidden 2048, 4,096 normed random tokens, on the CPU; 0.05 wide
it would be 68%, 0.1 wide 92%; tests/test_lfm2_model.py measures it
again at the tiny size, 8 experts top-2: 7%), so a program that forgets
it is a different model, not a rounding.

A control (`lower`, one entry of the file's `check.controls` or
`further_controls`) is this forward with ONE thing changed:
{"act": "fp8"} rounds every matmul input to e4m3 (the stated bf16 a step
down); {"kv_bits": 8} rounds K and V per token to int8; and five that
hold the mathematics, each one thing of this family moved:
{"conv_gate": "off"} (out = c W_out: the C_t * dropped),
{"conv_taps": 2} (K = 2: the oldest tap dropped), {"qk_norm": "off"},
{"router_bias": "off"} (the top-k of s alone), {"router": "softmax"}
(s = softmax over all experts where the sigmoid is). Never part of a
benchmark run.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
EXPERT_BLOCK = 2048
FIRST_JUMP, BRANCH_SHARE = 60.0, 0.25
KIND_SPREAD = {"conv": 1.0, "dense": 0.6, "experts": 0.3,
               "full_attention": 0.125}
NORMED_QK_GAIN = 2.0
RENORM_EPS = 1e-6


# -- weights (a recipe, not a copy) -----------------------------------------


def _dense(key, shape, fan_in: int, dtype, centre=None, gain: float = 1.0):
    """`centre`: the input axes of a matrix that writes into the residual
    stream, whose mean is taken off before the gain and the rounding."""
    w = jax.random.normal(key, shape, dtype=jnp.float32) / math.sqrt(fan_in)
    if centre is not None:
        w = w - jnp.mean(w, axis=centre, keepdims=True)
    if gain != 1.0:
        w = w * gain
    return _stored(w, dtype)


def _stored(w, dtype):
    """float32 values rounded to the model dtype. bfloat16 by
    `reduce_precision` (round to nearest even, as `astype` does): of a
    pair of `astype`s the TPU compiler keeps the excess precision, and
    the reference's weights were then NOT the program's (on the chip
    `embed_max_diff` read 0.000244 = 2**-12, half a bfloat16 step at the
    embedding's largest entries, where rounded weights read 0: my chip
    run, PR 44, `chiprun_out/pr44/layers.json`, PERF.md section 2)."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        return jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)
    return w.astype(dtype).astype(jnp.float32)


def sizes(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    lo, hi = cfg.get("experts_held") or (0, cfg["num_experts"])
    return {"h": h, "hd": h // cfg["num_attention_heads"],
            "kw": cfg["conv_L_cache"], "lo": lo, "hi": hi,
            "router": cfg.get("num_experts_published", cfg["num_experts"]),
            "eps": cfg["norm_eps"]}


def branch_gain(cfg: dict, mixer: int) -> float:
    """Mixer 0 writes FIRST_JUMP times the embedding's spread, every
    later mixer BRANCH_SHARE of the stream it joins; over the spread a
    unit-gain mixer of its kind writes."""
    kinds = mixers(cfg)
    stream = 1.0 / math.sqrt(cfg["hidden_size"])
    for m in range(mixer + 1):
        branch = stream * (FIRST_JUMP if m == 0 else BRANCH_SHARE)
        if m < mixer:
            stream = math.hypot(stream, branch)
    return branch / KIND_SPREAD[kinds[mixer]]


def embedding(key, cfg: dict):
    """[rows, h]: read going in and, transposed, coming out."""
    h = cfg["hidden_size"]
    w = jax.random.normal(key, (cfg["vocab_size"], h),
                          dtype=jnp.float32) / math.sqrt(h)
    return _stored(w, jnp.dtype(cfg["dtype"]))


def conv_weights(key, cfg: dict, mixer: int) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    z = sizes(cfg)
    h, kw = z["h"], z["kw"]
    ks = jax.random.split(key, 15)
    return {"in_proj": _dense(ks[0], (h, 3 * h), h, dtype),
            "conv_w": _dense(ks[1], (kw, h), kw, dtype),
            "out_proj": _dense(ks[6], (h, h), h, dtype, 0,
                               branch_gain(cfg, mixer))}


def attention_weights(key, cfg: dict, mixer: int) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    z = sizes(cfg)
    h, hd = z["h"], z["hd"]
    qh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ks = jax.random.split(key, 15)
    return {"wq": _dense(ks[0], (h, qh, hd), h, dtype, gain=NORMED_QK_GAIN),
            "wk": _dense(ks[1], (h, kh, hd), h, dtype, gain=NORMED_QK_GAIN),
            "wv": _dense(ks[2], (h, kh, hd), h, dtype),
            "wo": _dense(ks[3], (qh, hd, h), qh * hd, dtype, (0, 1),
                         branch_gain(cfg, mixer))}


def dense_weights(key, cfg: dict, mixer: int) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    ks = jax.random.split(key, 15)
    return {"gate": _dense(ks[0], (h, m), h, dtype),
            "up": _dense(ks[1], (h, m), h, dtype),
            "down": _dense(ks[2], (m, h), m, dtype, 0,
                           branch_gain(cfg, mixer))}


def expert_weights(key, cfg: dict, mixer: int) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    z = sizes(cfg)
    h, m = z["h"], cfg["moe_intermediate_size"]
    ks = jax.random.split(key, 15)
    ids = jnp.arange(z["lo"], z["hi"])

    def each(key, shape, fan_in, centre=None, gain=1.0):
        return jax.vmap(lambda e: _dense(jax.random.fold_in(key, e), shape,
                                         fan_in, dtype, centre, gain))(ids)

    return {"router": _dense(ks[7], (h, z["router"]), h, dtype),
            "bias": 0.02 * jax.random.normal(ks[8], (z["router"],),
                                             jnp.float32),
            "gate": each(ks[9], (h, m), h), "up": each(ks[11], (h, m), h),
            "down": each(ks[10], (m, h), m, 0, branch_gain(cfg, mixer))}


def model_keys(cfg: dict):
    """(embedding, mixer 0..2L-1, head) for the L blocks served; the
    head's key is drawn and unused (the head is the embedding)."""
    return jax.random.split(jax.random.PRNGKey(int(cfg["weight_seed"])),
                            2 * len(cfg["layer_types"]) + 2)


# -- the forward pass ---------------------------------------------------------


def rms_norm(x, eps: float):
    """RMSNorm with unit gain (the seeded gains are ones)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _round_act(x, lower: dict):
    """Control only: round a matmul input to e4m3."""
    kind = lower.get("act")
    if kind is None:
        return x
    if kind != "fp8":
        raise ValueError(f"control act: {kind!r}")
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _round_kv(x, bits):
    """Control only: one symmetric scale per token, shared by heads."""
    if not bits:
        return x
    if bits != 8:
        raise ValueError(f"control kv_bits: {bits!r}")
    top = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(
        jnp.max(jnp.abs(x), axis=(-2, -1), keepdims=True) / top, 1e-12)
    return jnp.clip(jnp.round(x / scale), -top, top) * scale


def _choice(lower: dict, key: str, moved) -> bool:
    """Whether the control moves `key` (to `moved`, its one other value)."""
    value = lower.get(key)
    if value not in (None, moved):
        raise ValueError(f"control {key}: {value!r}")
    return value == moved


def conv_mixer(x, w: dict, cfg: dict, lower: dict):
    """x: [T, hidden], already normed."""
    z = sizes(cfg)
    h, kw, t = z["h"], z["kw"], x.shape[0]
    proj = _round_act(x, lower) @ w["in_proj"]
    b, c, u = proj[:, :h], proj[:, h:2 * h], proj[:, 2 * h:]
    padded = jnp.concatenate([jnp.zeros((kw - 1, h)), b * u])
    first = 1 if _choice(lower, "conv_taps", 2) else 0  # K = 2: newest two
    conv = sum(padded[k:k + t] * w["conv_w"][k] for k in range(first, kw))
    gated = conv if _choice(lower, "conv_gate", "off") else c * conv
    return _round_act(gated, lower) @ w["out_proj"]


def rope(x, theta: float):
    """Rotate-half rotary embedding over all lanes. x: [T, heads, hd]."""
    t, _, hd = x.shape
    half = hd // 2
    inv_freq = jnp.exp(-math.log(theta)
                       * jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention_mixer(x, w: dict, cfg: dict, lower: dict):
    """x: [T, hidden], already normed; T a multiple of Q_BLOCK or under
    it. Scores a block of queries at a time against all keys."""
    t = x.shape[0]
    qh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    z = sizes(cfg)
    hd = z["hd"]
    h = _round_act(x, lower)
    q = jnp.einsum("th,hqd->tqd", h, w["wq"])
    k = jnp.einsum("th,hkd->tkd", h, w["wk"])
    v = jnp.einsum("th,hkd->tkd", h, w["wv"])
    if not _choice(lower, "qk_norm", "off"):  # per head, before rope
        q, k = rms_norm(q, z["eps"]), rms_norm(k, z["eps"])
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = _round_kv(k, lower.get("kv_bits"))
    v = _round_kv(v, lower.get("kv_bits"))
    block = Q_BLOCK if t % Q_BLOCK == 0 else t
    qg = _round_act(q, lower).reshape(t // block, block, kh, qh // kh, hd)
    starts = jnp.arange(t // block) * block

    def one(inputs):
        qb, start = inputs
        scores = jnp.einsum("tkgd,skd->kgts", qb, k) / math.sqrt(hd)
        causal = ((start + jnp.arange(block))[:, None]
                  >= jnp.arange(t)[None, :])
        probs = jax.nn.softmax(
            jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgts,skd->tkgd", _round_act(probs, lower), v)

    attn = jax.lax.map(one, (qg, starts)).reshape(t, qh, hd)
    return jnp.einsum("tqd,qdh->th", _round_act(attn, lower), w["wo"])


def _swiglu(x, gate, up):
    return jax.nn.silu(x @ gate) * (x @ up)


def dense_mixer(x, w: dict, cfg: dict, lower: dict):
    xin = _round_act(x, lower)
    return _round_act(_swiglu(xin, w["gate"], w["up"]), lower) @ w["down"]


def routing(x, w: dict, cfg: dict, lower: dict):
    """x [T, hidden] -> [T, published] float32: the weight each token
    gives each expert (zero for the ones it did not choose)."""
    logits = x @ w["router"]  # float32, all published experts
    scores = (jax.nn.softmax(logits, axis=-1)
              if _choice(lower, "router", "softmax")
              else jax.nn.sigmoid(logits))
    choose = scores
    if cfg["use_expert_bias"] and not _choice(lower, "router_bias", "off"):
        choose = scores + w["bias"]
    _, chosen = jax.lax.top_k(choose, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + RENORM_EPS)
    top = top * cfg["routed_scaling_factor"]
    return jnp.zeros_like(logits).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(top)


def moe_mixer(x, w: dict, cfg: dict, lower: dict):
    """x: [T, hidden], already normed; a block of positions at a time."""
    z = sizes(cfg)
    t = x.shape[0]
    block = EXPERT_BLOCK if t % EXPERT_BLOCK == 0 else t

    def one(xb):
        per_expert = routing(xb, w, cfg, lower)
        xin = _round_act(xb, lower)

        def one_expert(out, inputs):
            gate, up, down, weight = inputs
            return out + weight[:, None] * (
                _round_act(_swiglu(xin, gate, up), lower) @ down), None

        held = per_expert[:, z["lo"]:z["hi"]].T  # absent ones add nothing
        out, _ = jax.lax.scan(one_expert, jnp.zeros_like(xb),
                              (w["gate"], w["up"], w["down"], held))
        return out

    return jax.lax.map(one, x.reshape(t // block, block, -1)).reshape(
        t, -1)


KINDS = {"conv": (conv_weights, conv_mixer),
         "full_attention": (attention_weights, attention_mixer),
         "dense": (dense_weights, dense_mixer),
         "experts": (expert_weights, moe_mixer)}


def mixer_forward(x, w: dict, kind: str, cfg: dict, lower: dict):
    """One mixer over one sequence. x: [T, hidden] float32."""
    return x + KINDS[kind][1](rms_norm(x, cfg["norm_eps"]), w, cfg, lower)


def mixers(cfg: dict) -> list[str]:
    """The served mixers in order: a block is its token mixer, then its
    feed-forward (dense for the first `num_dense_layers` blocks)."""
    return [kind for i, block in enumerate(cfg["layer_types"])
            for kind in (block, "dense" if i < cfg["num_dense_layers"]
                         else "experts")]


class Forward:
    """The forward pass of one configuration, its programs compiled once:
    mixer by mixer, so that one mixer's float32 weights are all that is
    live beside one sequence's activations."""

    def __init__(self, cfg: dict, lower: dict | None = None) -> None:
        self.cfg, self.lower = cfg, lower or {}
        if not cfg["tie_word_embeddings"]:
            raise ValueError("this reference's head is the embedding")
        self.keys = model_keys(cfg)
        self.embed = jax.jit(lambda: embedding(self.keys[0], cfg))()
        self.steps = {
            kind: (jax.jit(lambda key, mixer, kind=kind: KINDS[kind][0](
                       key, cfg, mixer), static_argnums=1),
                   jax.jit(lambda x, w, kind=kind: mixer_forward(
                       x, w, kind, cfg, self.lower)))
            for kind in set(mixers(cfg))}
        self.final = jax.jit(lambda x, rows, embed: _round_act(
            rms_norm(x[rows], cfg["norm_eps"]), self.lower) @ embed.T)

    def hidden(self, ids) -> jax.Array:
        """[T, hidden] after the last block, for one sequence of ids."""
        x = self.embed[jnp.asarray(ids)]
        for i, kind in enumerate(mixers(self.cfg)):
            make, step = self.steps[kind]
            x = step(x, make(self.keys[i + 1], i))
        return x

    def logits(self, x, rows) -> jax.Array:
        """Logits [len(rows), vocab] of the final hidden states' `rows`."""
        return self.final(x, jnp.asarray(rows), self.embed)


def logits_for(samples: list[dict], cfg: dict, pad_to: int,
               lower: dict | None = None) -> list[np.ndarray]:
    """For each sample (prompt ids, served ids): the logits, [n_served,
    vocab], at the positions that predicted each served token, from one
    full forward over prompt + served tokens padded to `pad_to`. One
    sample at a time, and the head over the served rows only (a fixed
    count, one compiled shape), so that pad_to x vocab never exists."""
    out = []
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
