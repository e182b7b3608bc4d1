"""The plain reference of the mellum architecture (JetBrains Mellum2-12B-
A2.5B-Instruct): `jax.numpy`, float32, `highest` matmul precision; no
kernels, no cache, no pages, no batching. It imports nothing of the
program and takes nothing the program made. `dtbench/reference.py` loads
it by the path a configuration's file gives (`reference.module`) and asks
for `logits_for(samples, cfg, pad_to, lower=None)`.

The equations (h = `hidden_size`, eps = `rms_norm_eps`). Block `l` of
`layer_types` (as the file cuts it: its length is the depth served),
every one the same but for the attention kind:

    a = RMSNorm(x);  q = a Wq [heads x head_dim], k = a Wk, v = a Wv
                     (num_key_value_heads KV heads, no bias, no q/k norm)
    sliding_attention: q, k <- RoPE(rope_theta, default table);
        scores masked to  q_pos - sliding_window < kv_pos <= q_pos
    full_attention:    q, k <- RoPE(rope_theta, YaRN); causal mask only
    x <- x + softmax(q k^T / sqrt(head_dim) + mask) v Wo
    a = RMSNorm(x);  p = softmax_float32(a Wr) over num_experts;
        top num_experts_per_tok of p, weights = p_top / sum(p_top)
        (norm_topk_prob)
    x <- x + sum_e weight_e Wdown_e (silu(Wgate_e a) * Wup_e a)
after the last block: RMSNorm, the untied head.

RoPE rotates halves (lane i with lane i + head_dim/2: the transformers
library's `rotate_half`). The default table is inv_freq_i =
theta^(-2i/d). YaRN is that library's `_compute_yarn_parameters` with
its default `truncate`: with r(n) = d ln(original_max / (2 pi n)) /
(2 ln theta), low = floor(r(beta_fast)) and high = ceil(r(beta_slow))
clipped to [0, d-1], ramp_i = clip((i - low) / (high - low), 0, 1),
inv_freq_i = (1 - ramp_i) theta^(-2i/d) + ramp_i theta^(-2i/d) / factor;
cos and sin are multiplied by `attention_factor`, which the source
states (1.2772588722239782 = 0.1 ln 16 + 1).

Departures, each because the configuration's file says so: only the
leading `len(layer_types)` blocks exist (one pipeline stage), given the
embedding and the head; no shared expert, no per-head q/k norm and no
prediction module (the source's config has no key for any of them).
Scores are computed for 512 query positions at a time (exact: a softmax
row needs its own keys only), so that an 8,192-position sample fits.

The weights are a recipe of this file's own, not read from the server
(tests/bench/test_bench_mellum.py holds `models/hybrid.py` to it). The
program runs a block as two mixers, so keys split from
`PRNGKey(weight_seed)` as (embedding, mixer 0..2L-1, head): block l's
attention is mixer 2l, its experts mixer 2l+1; a mixer's key split 15
ways. Matrices are `normal(key) / sqrt(fan_in)` rounded to the model
dtype; a matrix that writes into the residual stream (wo, every W_down)
first has its mean over its INPUT axes taken off each column, so that
silu's positive average gives the tokens no common direction for the
router to follow (PERF.md, PR 30). Attention: wq, wk, wv, wo keys 0..3.
Experts: W_r key 7 (normal / sqrt(h): on unit-RMS inputs its logits have
spread 1, so a token's top-8 of 64 take about half the mass and the load
is near-uniform), expert e's W_gate from fold_in(key 9, e), W_up from
fold_in(key 11, e), W_down from fold_in(key 10, e). Norm gains are ones.

A control (`lower`, one entry of the file's `check.controls` or
`further_controls`) is this forward with ONE thing changed:
{"act": "fp8"} rounds every matmul input to e4m3 (the stated bf16 a step
down); {"window": "full"} lets the sliding layers attend past their
window; {"rope": "one-table"} gives the full layers the default table.
The last two are this architecture's own: a program that leaves the mask
or YaRN out has to read outside the limit. Never part of a benchmark run.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


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
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    qh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ks = jax.random.split(key, 15)
    return {"wq": _dense(ks[0], (h, qh, hd), h, dtype),
            "wk": _dense(ks[1], (h, kh, hd), h, dtype),
            "wv": _dense(ks[2], (h, kh, hd), h, dtype),
            "wo": _dense(ks[3], (qh, hd, h), qh * hd, dtype, (0, 1))}


def expert_weights(key, cfg: dict) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    h, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ks = jax.random.split(key, 15)
    ids = jnp.arange(cfg["num_experts"])

    def each(key, shape, fan_in, centre=None):
        return jax.vmap(lambda e: _dense(jax.random.fold_in(key, e), shape,
                                         fan_in, dtype, centre))(ids)

    return {"router": _dense(ks[7], (h, cfg["num_experts"]), h, dtype),
            "gate": each(ks[9], (h, m), h), "up": each(ks[11], (h, m), h),
            "down": each(ks[10], (m, h), m, 0)}


def model_keys(cfg: dict):
    """(embedding, mixer 0..2L-1, head) for the L blocks served."""
    return jax.random.split(jax.random.PRNGKey(int(cfg["weight_seed"])),
                            2 * len(cfg["layer_types"]) + 2)


# -- rope ---------------------------------------------------------------------


def rope_tables(cfg: dict, layer_type: str, lower: dict):
    """(inv_freq [head_dim / 2], cos/sin factor) of a layer type."""
    rope = cfg["rope_parameters"][layer_type]
    if lower.get("rope") == "one-table":
        rope = cfg["rope_parameters"]["sliding_attention"]
    d, theta = cfg["head_dim"], float(rope["rope_theta"])
    i = np.arange(d // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / d)
    if rope["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")

    def rotations_at(n):
        return (d * math.log(rope["original_max_position_embeddings"]
                             / (n * 2 * math.pi)) / (2 * math.log(theta)))

    low = max(math.floor(rotations_at(rope["beta_fast"])), 0)
    high = min(math.ceil(rotations_at(rope["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    inv_freq = (1.0 - ramp) * plain + ramp * plain / rope["factor"]
    return inv_freq.astype(np.float32), float(rope["attention_factor"])


def rope(x, tables):
    """x [T, heads, head_dim], positions 0..T-1; halves rotated."""
    inv_freq, factor = tables
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos = (jnp.cos(angles) * factor)[:, None, :]
    sin = (jnp.sin(angles) * factor)[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- the forward pass ---------------------------------------------------------


def rms_norm(x, eps: float):
    """RMSNorm with unit gain (the seeded gains are ones)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _round_act(x, lower: dict):
    """Control only: round a matmul input to e4m3."""
    kind = lower.get("act")
    if kind == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if kind is not None:
        raise ValueError(f"control act: {kind!r}")
    return x


def attention_mixer(x, w: dict, layer_type: str, cfg: dict, lower: dict):
    """x [T, hidden], already normed; T a multiple of Q_BLOCK or under it."""
    t = x.shape[0]
    qh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    tables = rope_tables(cfg, layer_type, lower)
    a = _round_act(x, lower)
    q = rope(jnp.einsum("th,hqd->tqd", a, w["wq"]), tables)
    k = _round_act(rope(jnp.einsum("th,hkd->tkd", a, w["wk"]), tables), lower)
    v = jnp.einsum("th,hkd->tkd", a, w["wv"])
    window = (cfg["sliding_window"]
              if layer_type == "sliding_attention"
              and lower.get("window") != "full" else 0)
    kv_pos = jnp.arange(t)[None, :]

    def block(args):
        qb, q_pos = args  # [n, heads, d], [n]
        qg = _round_act(qb, lower).reshape(qb.shape[0], kh, qh // kh, -1)
        scores = jnp.einsum("tkgd,skd->kgts", qg, k) / math.sqrt(
            qb.shape[-1])
        seen = kv_pos <= q_pos[:, None]
        if window:
            seen = seen & (kv_pos > q_pos[:, None] - window)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("kgts,skd->tkgd", _round_act(probs, lower), v)

    n = Q_BLOCK if t > Q_BLOCK else t
    attn = jax.lax.map(block, (q.reshape(t // n, n, qh, -1),
                               jnp.arange(t).reshape(t // n, n)))
    return jnp.einsum("tqd,qdh->th",
                      _round_act(attn.reshape(t, qh, -1), lower), w["wo"])


def moe_mixer(x, w: dict, cfg: dict, lower: dict):
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ w["router"], axis=-1)  # float32, all experts
    top, chosen = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    # [T, experts]: the weight each token gives each expert (0 = not chosen)
    per_expert = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(top)
    xin = _round_act(x, lower)

    def one_expert(out, inputs):
        gate, up, down, weight = inputs
        mid = jax.nn.silu(xin @ gate) * (xin @ up)
        return out + weight[:, None] * (_round_act(mid, lower) @ down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                          (w["gate"], w["up"], w["down"], per_expert.T))
    return out


class Forward:
    """The forward pass of one configuration, its programs compiled once:
    mixer by mixer, so that one mixer's float32 weights are all that is
    live beside one sequence's activations."""

    def __init__(self, cfg: dict, lower: dict | None = None) -> None:
        self.cfg, self.lower = cfg, lower or {}
        self.keys = model_keys(cfg)
        h, dtype = cfg["hidden_size"], jnp.dtype(cfg["dtype"])
        eps = cfg["rms_norm_eps"]
        self.embed = jax.jit(lambda: _dense(
            self.keys[0], (cfg["vocab_size"], h), h, dtype))()
        self.head = jax.jit(lambda: _dense(
            self.keys[-1], (h, cfg["vocab_size"]), h, dtype))()
        self.make_attention = jax.jit(
            lambda key: attention_weights(key, cfg))
        self.make_experts = jax.jit(lambda key: expert_weights(key, cfg))
        self.attend = {
            kind: jax.jit(lambda x, w, kind=kind: x + attention_mixer(
                rms_norm(x, eps), w, kind, cfg, self.lower))
            for kind in set(cfg["layer_types"])}
        self.experts = jax.jit(lambda x, w: x + moe_mixer(
            rms_norm(x, eps), w, cfg, self.lower))
        self.final = jax.jit(lambda x, rows, head: _round_act(
            rms_norm(x[rows], eps), self.lower) @ head)

    def hidden(self, ids) -> jax.Array:
        """[T, hidden] after the last block, for one sequence of ids."""
        x = self.embed[jnp.asarray(ids)]
        for i, kind in enumerate(self.cfg["layer_types"]):
            x = self.attend[kind](x, self.make_attention(self.keys[2 * i + 1]))
            x = self.experts(x, self.make_experts(self.keys[2 * i + 2]))
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
