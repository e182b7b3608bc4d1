"""The plain reference of the pangu_ultra_moe architecture
(openPangu-Ultra-MoE-718B): `jax.numpy`, float32, `highest` matmul
precision; no kernels, no cache, no pages, no absorption, no batching. It
imports nothing of the program and takes nothing the program made.
`dtbench/reference.py` loads it by the path a configuration's file gives
(`reference.module`) and asks for `logits_for(samples, cfg, pad_to,
lower=None)`.

The equations (h = `hidden_size`, eps = `rms_norm_eps`, heads =
`num_attention_heads`, nope / rope / v = `qk_nope_head_dim` /
`qk_rope_head_dim` / `v_head_dim`). One block, x [T, h]:

    a   = RMSNorm(x)
    c_q = RMSNorm(a W_dq)                                [q_lora_rank]
    q   = c_q W_uq -> heads x (q_nope | q_rope);   q_rope <- RoPE
    [c_kv | k_r] = a [W_dkv | W_kr]                      [kv_lora_rank | rope]
    c_kv <- RMSNorm(c_kv);   k_r <- RoPE(k_r)      ONE rope key a token
    k_h = [c_kv W_uk,h | k_r],   v_h = c_kv W_uv,h
    o_h = softmax(q_h k_h^T / sqrt(nope + rope) + causal) v_h
    x  <- x + RMSNorm(concat_h(o_h) W_o)           the BRANCH is normed
    b   = RMSNorm(x)
    dense block:   m = W_down (silu(W_gate b) * W_up b)      `intermediate_size`
    expert block:  s = sigmoid(b W_r)  [n_routed_experts_published]
                   top `num_experts_per_tok` of s (raw scores: no
                   selection bias, no groups)
                   w = s_top / (sum(s_top) + 1e-20) x routed_scaling_factor
                   m = sum_e w_e W_down,e (silu(W_gate,e b) * W_up,e b)
                       + Shared(b)                 `moe_intermediate_size`
    x  <- x + RMSNorm(m)
after the last block: RMSNorm, the untied head. RoPE rotates halves
(lane i with lane i + rope/2) with inv_freq_i = theta^(-2i/rope): no
YaRN, no mscale (the source has no `rope_scaling`).

Departures from the published description, each because the
configuration's file says so: `first_k_dense_replace` dense blocks lead
(1 of the published 3) and `served_layers` - that many expert blocks
follow (4 of 58); the router scores all `n_routed_experts_published`
experts and only those in `experts_held` exist here (a token routed to
an absent expert gets nothing from it: the other fifteen chips of the
deployment hold them), the shared expert whole; the vocabulary is the
leading `vocab_size` rows; the next-token prediction module is not held
(`num_nextn_predict_layers` 0). Scores are computed for Q_BLOCK query
positions at a time (exact: a softmax row needs its own keys only), so
that a 6,144-position sample fits.

The weights are a recipe of this file's own, not read from the server
(tests/bench/test_bench_pangu.py holds `models/hybrid.py` to it). The
program runs a block as two mixers, so keys split from
`PRNGKey(weight_seed)` as (embedding, mixer 0..2L-1, head): block l's
attention is mixer 2l, its dense SwiGLU or experts mixer 2l+1; a mixer's
key split 15 ways. Matrices are `normal(key) / sqrt(fan_in)` rounded to
the model dtype; a matrix that writes into the residual stream (W_o,
every W_down) first has its mean over its INPUT axes taken off each
column (the hybrid references' own: silu's positive average gives the
tokens no common direction for the router to follow; PERF.md, PR 30).
Latent attention: W_dq, W_uq, W_dkv, W_o, W_kr, W_uk, W_uv keys 0..6,
fan_in the rank for the three up-projections. Dense: gate, up, down keys
0..2. Experts: W_r key 7 (normal / sqrt(h): logits of spread 1), expert
e's W_gate from fold_in(key 9, e), W_up from fold_in(key 11, e), W_down
from fold_in(key 10, e) with e the PUBLISHED index; the shared expert's
gate key 12, up key 14, down key 13. Every norm gain is one. Nothing is
added for the sandwich norms: a branch is normed before it is added, so
no matrix's scale reaches the residual stream and the head sees unit-RMS
rows as in the other recipes.

A control (`lower`, one entry of the file's `check.controls` or
`further_controls`) is this forward with ONE thing changed:
{"act": "fp8"} rounds every matmul input to e4m3 (the stated bf16 a step
down); {"latent": "int8"} rounds a token's cached row [c_kv | k_r] to
int8 with one scale a token; and three that hold the mathematics:
{"post_norm": "none"} leaves both post-branch norms out, {"scale":
"nope"} divides the scores by sqrt(nope) alone, {"router": "softmax"}
scores the experts with a softmax. A program that leaves one of them out
has to read outside the limit. Never part of a benchmark run.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256


# -- weights (a recipe, not a copy) -----------------------------------------


def _dense(key, shape, fan_in: int, dtype, centre=None):
    """`centre`: the input axes of a matrix that writes into the residual
    stream, whose mean is taken off before rounding (see above)."""
    w = jax.random.normal(key, shape, dtype=jnp.float32) / math.sqrt(fan_in)
    if centre is not None:
        w = w - jnp.mean(w, axis=centre, keepdims=True)
    return w.astype(dtype).astype(jnp.float32)


def attention_weights(key, cfg: dict) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q_rank, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope_d, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    ks = jax.random.split(key, 15)
    return {"w_dq": _dense(ks[0], (h, q_rank), h, dtype),
            "w_uq": _dense(ks[1], (q_rank, heads, nope + rope_d), q_rank,
                           dtype),
            "w_dkv": _dense(ks[2], (h, rank), h, dtype),
            "wo": _dense(ks[3], (heads, vd, h), heads * vd, dtype, (0, 1)),
            "w_kr": _dense(ks[4], (h, rope_d), h, dtype),
            "w_uk": _dense(ks[5], (rank, heads, nope), rank, dtype),
            "w_uv": _dense(ks[6], (rank, heads, vd), rank, dtype)}


def dense_weights(key, cfg: dict) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    ks = jax.random.split(key, 15)
    return {"gate": _dense(ks[0], (h, m), h, dtype),
            "up": _dense(ks[1], (h, m), h, dtype),
            "down": _dense(ks[2], (m, h), m, dtype, 0)}


def held_experts(cfg: dict) -> tuple[int, int]:
    lo, hi = cfg.get("experts_held") or (0, cfg["n_routed_experts_published"])
    return int(lo), int(hi)


def expert_weights(key, cfg: dict) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    h, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    sm = cfg["n_shared_experts"] * m
    ks = jax.random.split(key, 15)
    ids = jnp.arange(*held_experts(cfg))

    def each(key, shape, fan_in, centre=None):
        return jax.vmap(lambda e: _dense(jax.random.fold_in(key, e), shape,
                                         fan_in, dtype, centre))(ids)

    return {"router": _dense(ks[7], (h, cfg["n_routed_experts_published"]),
                             h, dtype),
            "gate": each(ks[9], (h, m), h), "up": each(ks[11], (h, m), h),
            "down": each(ks[10], (m, h), m, 0),
            "s_gate": _dense(ks[12], (h, sm), h, dtype),
            "s_up": _dense(ks[14], (h, sm), h, dtype),
            "s_down": _dense(ks[13], (sm, h), sm, dtype, 0)}


def model_keys(cfg: dict):
    """(embedding, mixer 0..2L-1, head) for the L blocks served."""
    return jax.random.split(jax.random.PRNGKey(int(cfg["weight_seed"])),
                            2 * int(cfg["served_layers"]) + 2)


# -- the forward pass ---------------------------------------------------------


def rms_norm(x, eps: float):
    """RMSNorm with unit gain (the seeded gains are ones)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, theta: float):
    """x [T, ..., d], positions 0..T-1; halves rotated."""
    d = x.shape[-1]
    inv_freq = jnp.asarray(
        float(theta) ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d),
        jnp.float32)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    angles = angles.reshape(x.shape[0], *([1] * (x.ndim - 2)), d // 2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _round_act(x, lower: dict):
    """Control only: round a matmul input to e4m3."""
    kind = lower.get("act")
    if kind == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if kind is not None:
        raise ValueError(f"control act: {kind!r}")
    return x


def _round_latent(c_kv, k_r, lower: dict):
    """Control only: a token's cached row [c_kv | k_r] to int8, one
    absmax scale a token."""
    kind = lower.get("latent")
    if kind is None:
        return c_kv, k_r
    if kind != "int8":
        raise ValueError(f"control latent: {kind!r}")
    row = jnp.concatenate([c_kv, k_r], axis=-1)
    scale = jnp.maximum(jnp.max(jnp.abs(row), axis=-1, keepdims=True),
                        1e-12) / 127.0
    row = jnp.clip(jnp.round(row / scale), -127, 127) * scale
    return row[..., :c_kv.shape[-1]], row[..., c_kv.shape[-1]:]


def attention_mixer(x, w: dict, cfg: dict, lower: dict):
    """x [T, hidden], already normed; T a multiple of Q_BLOCK or under it.
    Nothing is absorbed: every head's keys and values are built."""
    t = x.shape[0]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    nope, rope_d = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    a = _round_act(x, lower)
    c_q = _round_act(rms_norm(a @ w["w_dq"], eps), lower)
    q = jnp.einsum("tr,rqd->tqd", c_q, w["w_uq"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
    c_kv = rms_norm(a @ w["w_dkv"], eps)
    k_r = rope(a @ w["w_kr"], theta)
    c_kv, k_r = _round_latent(c_kv, k_r, lower)
    c_in = _round_act(c_kv, lower)
    heads = q.shape[1]
    k = jnp.concatenate(
        [jnp.einsum("sr,rhn->shn", c_in, w["w_uk"]),
         jnp.broadcast_to(k_r[:, None, :], (t, heads, rope_d))], axis=-1)
    k = _round_act(k, lower)
    v = jnp.einsum("sr,rhv->shv", c_in, w["w_uv"])
    width = nope if lower.get("scale") == "nope" else nope + rope_d
    if lower.get("scale") not in (None, "nope"):
        raise ValueError(f"control scale: {lower.get('scale')!r}")
    kv_pos = jnp.arange(t)[None, :]

    def block(args):
        qb, q_pos = args  # [n, heads, d], [n]
        scores = jnp.einsum("thd,shd->hts", _round_act(qb, lower),
                            k) / math.sqrt(width)
        seen = kv_pos <= q_pos[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("hts,shv->thv", _round_act(probs, lower), v)

    n = Q_BLOCK if t > Q_BLOCK else t
    attn = jax.lax.map(block, (q.reshape(t // n, n, heads, -1),
                               jnp.arange(t).reshape(t // n, n)))
    return jnp.einsum("thv,hvd->td",
                      _round_act(attn.reshape(t, heads, -1), lower), w["wo"])


def dense_mixer(x, w: dict, lower: dict):
    xin = _round_act(x, lower)
    mid = jax.nn.silu(xin @ w["gate"]) * (xin @ w["up"])
    return _round_act(mid, lower) @ w["down"]


def routing(x, router, cfg: dict, lower: dict):
    """[T, published experts]: the weight each token gives each expert
    (0 = not chosen), from float32 scores over ALL published experts."""
    logits = x @ router
    kind = lower.get("router")
    if kind is None:
        scores = jax.nn.sigmoid(logits)
    elif kind == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"control router: {kind!r}")
    top, chosen = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(top)


def moe_mixer(x, w: dict, cfg: dict, lower: dict):
    lo, hi = held_experts(cfg)
    per_expert = routing(x, w["router"], cfg, lower)[:, lo:hi]
    xin = _round_act(x, lower)

    def one_expert(out, inputs):
        gate, up, down, weight = inputs
        mid = jax.nn.silu(xin @ gate) * (xin @ up)
        return out + weight[:, None] * (_round_act(mid, lower) @ down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                          (w["gate"], w["up"], w["down"], per_expert.T))
    shared = jax.nn.silu(xin @ w["s_gate"]) * (xin @ w["s_up"])
    return out + _round_act(shared, lower) @ w["s_down"]


class Forward:
    """The forward pass of one configuration, its programs compiled once:
    mixer by mixer, so that one mixer's float32 weights are all that is
    live beside one sequence's activations."""

    def __init__(self, cfg: dict, lower: dict | None = None) -> None:
        self.cfg, self.lower = cfg, lower or {}
        self.keys = model_keys(cfg)
        h, dtype = cfg["hidden_size"], jnp.dtype(cfg["dtype"])
        eps = cfg["rms_norm_eps"]
        kind = self.lower.get("post_norm")
        if kind not in (None, "none"):
            raise ValueError(f"control post_norm: {kind!r}")

        def branch(x, out):
            return x + (out if kind == "none" else rms_norm(out, eps))

        self.embed = jax.jit(lambda: _dense(
            self.keys[0], (cfg["vocab_size"], h), h, dtype))()
        self.head = jax.jit(lambda: _dense(
            self.keys[-1], (h, cfg["vocab_size"]), h, dtype))()
        self.make = {
            "attention": jax.jit(lambda key: attention_weights(key, cfg)),
            "dense": jax.jit(lambda key: dense_weights(key, cfg)),
            "experts": jax.jit(lambda key: expert_weights(key, cfg))}
        self.mix = {
            "attention": jax.jit(lambda x, w: branch(x, attention_mixer(
                rms_norm(x, eps), w, cfg, self.lower))),
            "dense": jax.jit(lambda x, w: branch(x, dense_mixer(
                rms_norm(x, eps), w, self.lower))),
            "experts": jax.jit(lambda x, w: branch(x, moe_mixer(
                rms_norm(x, eps), w, cfg, self.lower)))}
        self.final = jax.jit(lambda x, rows, head: _round_act(
            rms_norm(x[rows], eps), self.lower) @ head)

    def hidden(self, ids) -> jax.Array:
        """[T, hidden] after the last block, for one sequence of ids."""
        x = self.embed[jnp.asarray(ids)]
        for i in range(int(self.cfg["served_layers"])):
            second = ("dense" if i < self.cfg["first_k_dense_replace"]
                      else "experts")
            for j, kind in enumerate(("attention", second)):
                x = self.mix[kind](
                    x, self.make[kind](self.keys[2 * i + j + 1]))
        return x

    def logits(self, x, rows) -> jax.Array:
        """Logits [len(rows), vocab] of the final hidden states' `rows`."""
        return self.final(x, jnp.asarray(rows), self.head)


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
        pad_to = -(-pad_to // Q_BLOCK) * Q_BLOCK
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
