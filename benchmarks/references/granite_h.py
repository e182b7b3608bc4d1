"""The plain reference of the granitemoehybrid architecture (IBM
granite-4.0-h-small): `jax.numpy`, float32, `highest` matmul precision,
the Mamba-2 layers scanned position by position; no kernels, no cache, no
pages, no chunked scan, no batching. It imports nothing of the program
and takes nothing the program made. `dtbench/reference.py` loads it by
the path a configuration's file gives (`reference.module`) and asks for
`logits_for(samples, cfg, pad_to, lower=None)`.

The equations (h = `hidden_size`, eps = `rms_norm_eps`). Block `l` of
`layer_types` (as the file cuts it: its length is the depth served):

    x_0 = embedding_multiplier * Emb[token]
    x <- x + residual_multiplier * Mix_l(RMSNorm(x))
    u = RMSNorm(x);  x <- x + residual_multiplier * (Experts_l(u) + Shared_l(u))
    logits = RMSNorm(x) Emb^T / logits_scaling        (tie_word_embeddings)

  mamba      Mamba-2. H = mamba_n_heads heads of P = mamba_d_head (inner
     H P), G = mamba_n_groups groups of B and C, N = mamba_d_state, conv
     width H P + 2 G N. [z | xBC | dt] = a W_in (no bias). xBC <-
     silu(conv1d_causal_depthwise_k(xBC) + b). xBC -> xs [T, H, P],
     B [T, G, N], C [T, G, N]; head j reads group j // (H / G).
     dt <- softplus(dt + dt_bias); A = -exp(A_log).
     S_t = exp(dt_t A) S_{t-1} + dt_t (xs_t outer B_t); y_t = S_t C_t + D xs_t.
     y <- GroupRMSNorm(y * silu(z); G groups) (gate first, then norm: one
     group of all H P lanes here); out = y W_out.
  attention  num_attention_heads query heads over num_key_value_heads KV
     heads of hidden_size / num_attention_heads, no bias, causal softmax
     of attention_multiplier x q.k (NOT 1/sqrt(head_dim)), no rotary or
     other positional term (`position_embedding_type: nope`).
  Experts    r = u W_r in float32 over ALL published experts; the
     num_experts_per_tok largest; weights = softmax over those chosen
     logits; expert e: W_down,e (silu(W_gate,e u) * W_up,e u),
     intermediate_size wide. Shared: the same form,
     shared_intermediate_size wide, added unweighted.

Departures, each because the configuration's file says so: only the
leading `len(layer_types)` blocks exist (one pipeline stage), given the
final norm and the tied head; only the experts `experts_held` = [lo, hi)
of the published count are computed (a token routed to an absent expert
gets nothing from it: the chip's share of an expert-parallel pair; the
shared expert is whole); only the leading `vocab_size` rows of the
embedding exist, going in and coming out. Attention scores are computed
for 512 query positions at a time and the experts for 2,048 positions at
a time (both exact), so that an 8,192-position sample fits.
`mamba_chunk_size` is a kernel's tiling in the source and means nothing
here.

The weights are a recipe of this file's own, not read from the server
(tests/bench/test_bench_granite_h.py holds `models/hybrid.py` to it).
The program runs a block as two mixers, so keys split from
`PRNGKey(weight_seed)` as (embedding, mixer 0..2L-1, head): block l's
token mixer is mixer 2l, its experts mixer 2l+1; a mixer's key split 15
ways. Matrices are `normal(key) / sqrt(fan_in)` rounded to the model
dtype; a matrix that writes into the residual stream (W_out, wo, every
W_down) first has its mean over its INPUT axes taken off each column
(PERF.md, PR 30). Three things are this family's own:

  * the embedding, which is also the head, is drawn logits_scaling /
    sqrt(h) wide (0.25 here), so that RMSNorm(x) Emb^T / logits_scaling
    has the spread 1 every other recipe's logits have;
  * wq and wk are drawn (attention_multiplier sqrt(head_dim))^-1/2 times
    wider (3.36 here), so that attention_multiplier x q.k has the spread
    1 that q.k / sqrt(head_dim) has at unit gains;
  * a matrix that writes into the residual stream is, after centring,
    multiplied by the branch gain of its mixer m (the PUBLISHED index of
    the mixer, two a block): s0 / residual_multiplier x 1.23^m with s0 =
    embedding_multiplier x logits_scaling / sqrt(h) the spread of x_0's
    entries (3.0 here). Why: the head is tied, so whatever of Emb[token]
    is left in the last hidden state scores the token itself; at cosine
    c between the two the self-logit is c sqrt(h) times the logits'
    spread, and with equal branches a random model answers every token
    with itself. A stream that grows with depth, as trained ones do,
    leaves the embedding under 1/60 of the last state after 20 mixers,
    and the first blocks see embedding and branches side by side.

mamba: W_in key 0, conv taps [K, C] key 1 (fan_in K), conv bias 0.1 x
normal key 2 (rounded likewise), dt_bias = inverse softplus of
exp(U(0,1) (ln dt_max - ln dt_min) + ln dt_min) floored at
time_step_floor, key 3, A_log = ln U(1, 16) key 4, D = 1, W_out key 6.
attention: wq, wk, wv, wo keys 0..3. Experts: W_r key 7 (normal /
sqrt(h): logits of spread 1), expert e's W_gate from fold_in(key 9, e),
W_up from fold_in(key 11, e), W_down from fold_in(key 10, e) with e the
PUBLISHED index, shared W_gate key 12, W_up key 14, W_down key 13. Norm
gains are ones.

A control (`lower`, one entry of the file's `check.controls` or
`further_controls`) is this forward with ONE thing changed:
{"act": "fp8"} rounds every matmul input to e4m3 (the stated bf16 a step
down); {"ssm_state": "bfloat16"} stores the SSM state in bf16 after
every position; {"kv_bits": 8} rounds K and V per token to int8; and
four that hold the mathematics, each a multiplier or the router moved to
what another family does: {"residual": "one"} (residual_multiplier 1),
{"attn_scale": "rsqrt"} (1/sqrt(head_dim)), {"embed": "unscaled"}
(embedding_multiplier 1), {"router": "sigmoid"} (sigmoid scores of the
chosen, renormalised). Never part of a benchmark run.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
EXPERT_BLOCK = 2048
BRANCH_GROWTH = 1.23


# -- weights (a recipe, not a copy) -----------------------------------------


def _dense(key, shape, fan_in: int, dtype, centre=None, gain: float = 1.0):
    """`centre`: the input axes of a matrix that writes into the residual
    stream, whose mean is taken off before the gain and the rounding."""
    w = jax.random.normal(key, shape, dtype=jnp.float32) / math.sqrt(fan_in)
    if centre is not None:
        w = w - jnp.mean(w, axis=centre, keepdims=True)
    if gain != 1.0:
        w = w * gain
    return w.astype(dtype).astype(jnp.float32)


def sizes(cfg: dict) -> dict:
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    lo, hi = cfg.get("experts_held") or (0, cfg["num_local_experts"])
    h = cfg["hidden_size"]
    return {"h": h, "heads": heads, "p": p, "g": g, "n": n,
            "inner": heads * p, "conv": heads * p + 2 * g * n,
            "kw": cfg["mamba_d_conv"], "lo": lo, "hi": hi,
            "router": cfg.get("num_local_experts_published",
                              cfg["num_local_experts"]),
            "hd": h // cfg["num_attention_heads"],
            "eps": cfg["rms_norm_eps"]}


def branch_gain(cfg: dict, mixer: int) -> float:
    s0 = (cfg["embedding_multiplier"] * cfg["logits_scaling"]
          / math.sqrt(cfg["hidden_size"]))
    return s0 / cfg["residual_multiplier"] * BRANCH_GROWTH ** mixer


def score_gain(cfg: dict) -> float:
    return (cfg["attention_multiplier"]
            * math.sqrt(sizes(cfg)["hd"])) ** -0.5


def embedding(key, cfg: dict):
    """[rows, h]: read x embedding_multiplier going in and, transposed,
    / logits_scaling coming out."""
    h = cfg["hidden_size"]
    w = jax.random.normal(key, (cfg["vocab_size"], h), dtype=jnp.float32) * (
        cfg["logits_scaling"] / math.sqrt(h))
    return w.astype(jnp.dtype(cfg["dtype"])).astype(jnp.float32)


def mamba_weights(key, cfg: dict, mixer: int) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    z = sizes(cfg)
    h = z["h"]
    ks = jax.random.split(key, 15)
    u = jax.random.uniform(ks[3], (z["heads"],), jnp.float32)
    dt = jnp.exp(u * (math.log(cfg["time_step_max"])
                      - math.log(cfg["time_step_min"]))
                 + math.log(cfg["time_step_min"]))
    dt = jnp.maximum(dt, cfg["time_step_floor"])
    return {
        "in_proj": _dense(ks[0], (h, z["inner"] + z["conv"] + z["heads"]),
                          h, dtype),
        "conv_w": _dense(ks[1], (z["kw"], z["conv"]), z["kw"], dtype),
        "conv_b": (0.1 * jax.random.normal(ks[2], (z["conv"],), jnp.float32)
                   ).astype(dtype).astype(jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "a": -jax.random.uniform(ks[4], (z["heads"],), jnp.float32,
                                 1.0, 16.0),
        "out_proj": _dense(ks[6], (z["inner"], h), z["inner"], dtype, 0,
                           branch_gain(cfg, mixer)),
    }


def attention_weights(key, cfg: dict, mixer: int) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    z = sizes(cfg)
    h, hd = z["h"], z["hd"]
    qh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ks = jax.random.split(key, 15)
    qk = score_gain(cfg)
    return {"wq": _dense(ks[0], (h, qh, hd), h, dtype, gain=qk),
            "wk": _dense(ks[1], (h, kh, hd), h, dtype, gain=qk),
            "wv": _dense(ks[2], (h, kh, hd), h, dtype),
            "wo": _dense(ks[3], (qh, hd, h), qh * hd, dtype, (0, 1),
                         branch_gain(cfg, mixer))}


def expert_weights(key, cfg: dict, mixer: int) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    z = sizes(cfg)
    h, m = z["h"], cfg["intermediate_size"]
    sm = cfg["shared_intermediate_size"]
    ks = jax.random.split(key, 15)
    ids = jnp.arange(z["lo"], z["hi"])
    gain = branch_gain(cfg, mixer)

    def each(key, shape, fan_in, centre=None, gain=1.0):
        return jax.vmap(lambda e: _dense(jax.random.fold_in(key, e), shape,
                                         fan_in, dtype, centre, gain))(ids)

    return {"router": _dense(ks[7], (h, z["router"]), h, dtype),
            "gate": each(ks[9], (h, m), h), "up": each(ks[11], (h, m), h),
            "down": each(ks[10], (m, h), m, 0, gain),
            "s_gate": _dense(ks[12], (h, sm), h, dtype),
            "s_up": _dense(ks[14], (h, sm), h, dtype),
            "s_down": _dense(ks[13], (sm, h), sm, dtype, 0, gain)}


def model_keys(cfg: dict):
    """(embedding, mixer 0..2L-1, head) for the L blocks served; the
    head's key is drawn and unused (the head is the embedding)."""
    return jax.random.split(jax.random.PRNGKey(int(cfg["weight_seed"])),
                            2 * len(cfg["layer_types"]) + 2)


# -- the forward pass ---------------------------------------------------------


def rms_norm(x, eps: float):
    """RMSNorm with unit gain (the seeded gains are ones)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _to_bf16(x):
    """float32 values rounded to bf16's 8 bits of mantissa (not a pair of
    `astype`s, whose excess precision the TPU compiler may keep: PERF.md,
    PR 30)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


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


def _choice(lower: dict, key: str, moved: str) -> bool:
    """Whether the control moves `key` (to `moved`, its one other value)."""
    value = lower.get(key)
    if value not in (None, moved):
        raise ValueError(f"control {key}: {value!r}")
    return value == moved


def mamba_mixer(x, w: dict, cfg: dict, lower: dict):
    """x: [T, hidden], already normed. Sequential over positions."""
    z = sizes(cfg)
    t = x.shape[0]
    heads, p, g, n = z["heads"], z["p"], z["g"], z["n"]
    proj = _round_act(x, lower) @ w["in_proj"]
    gate, xbc, dt = (proj[:, :z["inner"]],
                     proj[:, z["inner"]:z["inner"] + z["conv"]],
                     proj[:, z["inner"] + z["conv"]:])
    padded = jnp.concatenate([jnp.zeros((z["kw"] - 1, z["conv"])), xbc])
    conv = sum(padded[k:k + t] * w["conv_w"][k] for k in range(z["kw"]))
    xbc = jax.nn.silu(conv + w["conv_b"])
    xs = xbc[:, :z["inner"]].reshape(t, heads, p)
    b = xbc[:, z["inner"]:z["inner"] + g * n].reshape(t, g, n)
    c = xbc[:, z["inner"] + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # [T, H]
    state = lower.get("ssm_state", "float32")
    if state not in ("float32", "bfloat16"):
        raise ValueError(f"control ssm_state: {state!r}")
    per = heads // g  # head j reads group j // (H / G)

    def step(s, inputs):
        x_t, b_t, c_t, dt_t = inputs
        b_t = jnp.repeat(b_t, per, axis=0)
        c_t = jnp.repeat(c_t, per, axis=0)
        s = (jnp.exp(dt_t * w["a"])[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if state == "bfloat16":
            s = _to_bf16(s)  # as stored
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n)), (xs, b, c, dt))
    y = (y + xs).reshape(t, z["inner"])  # D = 1
    y = (y * jax.nn.silu(gate)).reshape(t, g, z["inner"] // g)
    y = rms_norm(y, z["eps"]).reshape(t, z["inner"])
    return _round_act(y, lower) @ w["out_proj"]


def attention_mixer(x, w: dict, cfg: dict, lower: dict):
    """x: [T, hidden], already normed; T a multiple of Q_BLOCK or under
    it. Scores a block of queries at a time against all keys."""
    t = x.shape[0]
    qh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = sizes(cfg)["hd"]
    scale = (1.0 / math.sqrt(hd) if _choice(lower, "attn_scale", "rsqrt")
             else cfg["attention_multiplier"])
    h = _round_act(x, lower)
    q = jnp.einsum("th,hqd->tqd", h, w["wq"])
    k = _round_kv(jnp.einsum("th,hkd->tkd", h, w["wk"]),
                  lower.get("kv_bits"))
    v = _round_kv(jnp.einsum("th,hkd->tkd", h, w["wv"]),
                  lower.get("kv_bits"))
    block = Q_BLOCK if t % Q_BLOCK == 0 else t
    qg = _round_act(q, lower).reshape(t // block, block, kh, qh // kh, hd)
    starts = jnp.arange(t // block) * block

    def one(inputs):
        qb, start = inputs
        scores = jnp.einsum("tkgd,skd->kgts", qb, k) * scale
        causal = ((start + jnp.arange(block))[:, None]
                  >= jnp.arange(t)[None, :])
        probs = jax.nn.softmax(
            jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgts,skd->tkgd", _round_act(probs, lower), v)

    attn = jax.lax.map(one, (qg, starts)).reshape(t, qh, hd)
    return jnp.einsum("tqd,qdh->th", _round_act(attn, lower), w["wo"])


def _swiglu(x, gate, up):
    return jax.nn.silu(x @ gate) * (x @ up)


def moe_mixer(x, w: dict, cfg: dict, lower: dict):
    """x: [T, hidden], already normed; a block of positions at a time."""
    z = sizes(cfg)
    k = cfg["num_experts_per_tok"]
    sigmoid = _choice(lower, "router", "sigmoid")
    t = x.shape[0]
    block = EXPERT_BLOCK if t % EXPERT_BLOCK == 0 else t

    def one(xb):
        logits = xb @ w["router"]  # float32, all published experts
        top, chosen = jax.lax.top_k(logits, k)
        if sigmoid:
            top = jax.nn.sigmoid(top)
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        else:
            top = jax.nn.softmax(top, axis=-1)
        # [T, published]: the weight each token gives each expert
        per_expert = jnp.zeros_like(logits).at[
            jnp.arange(block)[:, None], chosen].set(top)
        xin = _round_act(xb, lower)

        def one_expert(out, inputs):
            gate, up, down, weight = inputs
            return out + weight[:, None] * (
                _round_act(_swiglu(xin, gate, up), lower) @ down), None

        held = per_expert[:, z["lo"]:z["hi"]].T  # absent ones add nothing
        out, _ = jax.lax.scan(one_expert, jnp.zeros_like(xb),
                              (w["gate"], w["up"], w["down"], held))
        return out + _round_act(_swiglu(xin, w["s_gate"], w["s_up"]),
                                lower) @ w["s_down"]

    return jax.lax.map(one, x.reshape(t // block, block, -1)).reshape(
        t, -1)


KINDS = {"mamba": (mamba_weights, mamba_mixer),
         "attention": (attention_weights, attention_mixer),
         "experts": (expert_weights, moe_mixer)}


def mixer_forward(x, w: dict, kind: str, cfg: dict, lower: dict):
    """One mixer over one sequence. x: [T, hidden] float32."""
    scale = (1.0 if _choice(lower, "residual", "one")
             else cfg["residual_multiplier"])
    return x + scale * KINDS[kind][1](rms_norm(x, cfg["rms_norm_eps"]), w,
                                      cfg, lower)


def mixers(cfg: dict) -> list[str]:
    """The served mixers in order: a block is its token mixer, then its
    experts."""
    return [kind for block in cfg["layer_types"]
            for kind in (block, "experts")]


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
        self.embed_scale = (1.0 if _choice(self.lower, "embed", "unscaled")
                            else float(cfg["embedding_multiplier"]))
        self.final = jax.jit(lambda x, rows, embed: (_round_act(
            rms_norm(x[rows], cfg["rms_norm_eps"]), self.lower) @ embed.T)
            / cfg["logits_scaling"])

    def hidden(self, ids) -> jax.Array:
        """[T, hidden] after the last block, for one sequence of ids."""
        x = self.embed_scale * self.embed[jnp.asarray(ids)]
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
