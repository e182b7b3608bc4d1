"""The plain reference of the nemotron_h architecture (NVIDIA-Nemotron-3-
Nano-30B-A3B): one mixer a layer, `jax.numpy`, float32, `highest` matmul
precision, a sequential scan over positions for the Mamba-2 layers; no
kernels, no cache, no chunking, no batching. It imports nothing of the
program and takes nothing the program made. `dtbench/reference.py` loads
it by the path a configuration's file gives (`reference.module`) and asks
for `logits_for(samples, cfg, pad_to, lower=None)`.

The equations (`h` hidden size, eps = `layer_norm_epsilon`). Block `l`,
its kind the `l`-th character of `hybrid_override_pattern`:
`x <- x + Mixer_l(RMSNorm(x))`; after the last block RMSNorm and the
untied head.

  M  Mamba-2. d_inner = mamba_num_heads x mamba_head_dim (H x P; not
     expand x h), G = n_groups, N = ssm_state_size, conv width
     d_inner + 2 G N. [z | xBC | dt] = x W_in (no bias). xBC <-
     silu(conv1d_causal_depthwise_k(xBC) + b). xBC -> xs [T, H, P],
     B [T, G, N], C [T, G, N]; head j uses group j // (H / G).
     dt <- softplus(dt + dt_bias); A = -exp(A_log).
     S_t = exp(dt_t A) S_{t-1} + dt_t (xs_t outer B_t); y_t = S_t C_t + D xs_t.
     y <- GroupRMSNorm(y * silu(z); G groups) (gate first, then norm);
     out = y W_out.
  *  attention: num_attention_heads query heads over num_key_value_heads
     KV heads of head_dim, no bias, causal softmax at 1/sqrt(head_dim),
     NO rotary or other positional term (the family's published
     implementation applies none; `rope_theta` is unused).
  E  routed experts: logits = x W_r in float32 over ALL published
     experts, scores = sigmoid; selection = top-k of scores +
     e_score_correction_bias (n_group 1: no group limit); weights = the
     unbiased scores of the chosen, over their sum, x routed_scaling_factor.
     Expert e: W_down,e relu(W_up,e x)^2, no gate matrix. One shared
     expert of the same form, always added.

Departures, each because the configuration's file says so: only the
experts `experts_held` = [lo, hi) of the published count are computed (a
token routed to an absent expert gets nothing from it: the chip's share
of an expert-parallel pair), only the leading `vocab_size` rows of the
vocabulary exist, and only the layers `hybrid_override_pattern` names as
the file cuts it (its length is the depth served).

The weights are a recipe of this file's own, not read from the server
(the program's tests hold `models/hybrid.py` to it): keys split from
`PRNGKey(weight_seed)` as (embedding, layer 0..L-1, head); a layer's key
split 15 ways. Matrices are `normal(key) / sqrt(fan_in)` rounded to the
model dtype; a matrix that writes into the residual stream (W_out, wo,
every W_down) first has its mean over its INPUT axes taken off each
column: relu2, silu and a softmax's average are positive on average, and
an uncentred down-projection turns that average into one direction that
every token's hidden state shares and that the router then follows (at
these widths 13% of the router input's energy, the busiest held expert
at 6-11 times the mean, a 110-row step touching 35-45 of 64 held: the
float32 forward of this file on the CPU, PERF.md, PR 30). M: W_in key 0, conv taps [K, C] key 1 (fan_in K), conv bias
0.1 x normal key 2 (rounded likewise), dt_bias = inverse softplus of
exp(U(0,1) (ln dt_max - ln dt_min) + ln dt_min) floored at
time_step_floor, key 3, A_log = ln U(1, 16) key 4, D = 1, W_out key 6.
*: wq, wk, wv, wo keys 0..3. E: W_r key 7, e_score_correction_bias = 0.02
x normal key 8 (float32: a trained model's bias balances the load, so
the draw is small beside the scores' spread of 0.2; routing stays a
little uneven, the busiest held expert at about twice the mean, and a
110-row step touches 62-63 of the 64 held), expert e's W_up from fold_in(key 9, e) and
W_down from fold_in(key 10, e) with e the PUBLISHED index, shared W_up
key 12, W_down key 13. Norm gains are ones.

A control (`lower`, one entry of the file's `check.controls`) is this
forward with ONE stated precision a step down: {"ssm_state": "bfloat16"}
stores the SSM state in bf16 after every position; {"act": "fp8"} rounds
every matmul input to e4m3; {"kv_bits": 8} rounds K and V per token to
int8. Never part of a benchmark run.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


# -- weights (a recipe, not a copy) -----------------------------------------


def _dense(key, shape, fan_in: int, dtype, centre=None):
    """`centre`: the input axes of a matrix that writes into the residual
    stream, whose mean is taken off before rounding (see above)."""
    w = jax.random.normal(key, shape, dtype=jnp.float32) / math.sqrt(fan_in)
    if centre is not None:
        w = w - jnp.mean(w, axis=centre, keepdims=True)
    return w.astype(dtype).astype(jnp.float32)


def sizes(cfg: dict) -> dict:
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    lo, hi = cfg.get("experts_held") or (0, cfg["n_routed_experts"])
    return {"h": cfg["hidden_size"], "heads": heads, "p": p, "g": g, "n": n,
            "inner": heads * p, "conv": heads * p + 2 * g * n,
            "kw": cfg["conv_kernel"], "lo": lo, "hi": hi,
            "router": cfg.get("n_routed_experts_published",
                              cfg["n_routed_experts"]),
            "eps": cfg["layer_norm_epsilon"]}


def layer_weights(key, kind: str, cfg: dict) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    z = sizes(cfg)
    h = z["h"]
    ks = jax.random.split(key, 15)
    if kind == "M":
        u = jax.random.uniform(ks[3], (z["heads"],), jnp.float32)
        dt = jnp.exp(u * (math.log(cfg["time_step_max"])
                          - math.log(cfg["time_step_min"]))
                     + math.log(cfg["time_step_min"]))
        dt = jnp.maximum(dt, cfg["time_step_floor"])
        return {
            "in_proj": _dense(ks[0], (h, z["inner"] + z["conv"] + z["heads"]),
                              h, dtype),
            "conv_w": _dense(ks[1], (z["kw"], z["conv"]), z["kw"], dtype),
            "conv_b": (0.1 * jax.random.normal(ks[2], (z["conv"],),
                                               jnp.float32)
                       ).astype(dtype).astype(jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a": -jax.random.uniform(ks[4], (z["heads"],), jnp.float32,
                                     1.0, 16.0),
            "out_proj": _dense(ks[6], (z["inner"], h), z["inner"], dtype, 0),
        }
    if kind == "*":
        qh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        hd = cfg["head_dim"]
        return {"wq": _dense(ks[0], (h, qh, hd), h, dtype),
                "wk": _dense(ks[1], (h, kh, hd), h, dtype),
                "wv": _dense(ks[2], (h, kh, hd), h, dtype),
                "wo": _dense(ks[3], (qh, hd, h), qh * hd, dtype, (0, 1))}
    m = cfg["moe_intermediate_size"]
    sm = cfg["moe_shared_expert_intermediate_size"]
    ids = jnp.arange(z["lo"], z["hi"])
    return {
        "router": _dense(ks[7], (h, z["router"]), h, dtype),
        "e_bias": 0.02 * jax.random.normal(ks[8], (z["router"],),
                                          jnp.float32),
        "e_up": jax.vmap(lambda e: _dense(jax.random.fold_in(ks[9], e),
                                          (h, m), h, dtype))(ids),
        "e_down": jax.vmap(lambda e: _dense(jax.random.fold_in(ks[10], e),
                                            (m, h), m, dtype, 0))(ids),
        "s_up": _dense(ks[12], (h, sm), h, dtype),
        "s_down": _dense(ks[13], (sm, h), sm, dtype, 0),
    }


def depth(cfg: dict) -> int:
    """Layers served: one for each character of the pattern AS CUT (the
    file keeps `num_hidden_layers` at the published count)."""
    return len(cfg["hybrid_override_pattern"])


def model_keys(cfg: dict):
    return jax.random.split(jax.random.PRNGKey(int(cfg["weight_seed"])),
                            depth(cfg) + 2)


# -- the forward pass ---------------------------------------------------------


def rms_norm(x, eps: float):
    """RMSNorm with unit gain (the seeded gains are ones)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _to_bf16(x):
    """float32 values rounded to bf16's 8 bits of mantissa. Not
    `astype(bfloat16).astype(float32)`: the TPU compiler may keep the
    excess precision of such a pair and round nothing (my chip run, PR
    30: the state-bf16 control read the reference's own arg-max at
    every position)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _round_act(x, lower: dict):
    """Control only: round a matmul input to e4m3 (or to bf16: see the
    top of the file)."""
    kind = lower.get("act")
    if kind == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return _to_bf16(x) if kind == "bf16" else x


def _round_kv(x, bits):
    """Control only: one symmetric scale per token, shared by heads."""
    if not bits:
        return x
    top = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(
        jnp.max(jnp.abs(x), axis=(-2, -1), keepdims=True) / top, 1e-12)
    return jnp.clip(jnp.round(x / scale), -top, top) * scale


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
    b = jnp.repeat(b, heads // g, axis=1)  # head j reads group j // (H/G)
    c = jnp.repeat(c, heads // g, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # [T, H]
    state = lower.get("ssm_state", "float32")
    if state not in ("float32", "bfloat16"):
        raise ValueError(f"control ssm_state: {state!r}")

    def step(s, inputs):
        x_t, b_t, c_t, dt_t = inputs
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
    t = x.shape[0]
    qh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h = _round_act(x, lower)
    q = jnp.einsum("th,hqd->tqd", h, w["wq"])
    k = _round_kv(jnp.einsum("th,hkd->tkd", h, w["wk"]),
                  lower.get("kv_bits"))
    v = _round_kv(jnp.einsum("th,hkd->tkd", h, w["wv"]),
                  lower.get("kv_bits"))
    qg = _round_act(q, lower).reshape(t, kh, qh // kh, -1)
    scores = jnp.einsum("tkgd,skd->kgts", qg, k) / math.sqrt(q.shape[-1])
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf),
                           axis=-1)
    attn = jnp.einsum("kgts,skd->tkgd", _round_act(probs, lower), v)
    return jnp.einsum("tqd,qdh->th",
                      _round_act(attn.reshape(t, qh, -1), lower), w["wo"])


def _relu2(u):
    return jnp.square(jax.nn.relu(u))


def moe_mixer(x, w: dict, cfg: dict, lower: dict):
    z = sizes(cfg)
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ w["router"])  # float32, all published
    _, chosen = jax.lax.top_k(scores + w["e_bias"], k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]
    # [T, published]: the weight each token gives each expert (0 = not chosen)
    per_expert = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(top)
    xin = _round_act(x, lower)

    def one_expert(out, inputs):
        up, down, weight = inputs
        return out + weight[:, None] * (
            _round_act(_relu2(xin @ up), lower) @ down), None

    held = per_expert[:, z["lo"]:z["hi"]].T  # absent experts add nothing
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                          (w["e_up"], w["e_down"], held))
    return out + _round_act(_relu2(xin @ w["s_up"]), lower) @ w["s_down"]


MIXERS = {"M": mamba_mixer, "*": attention_mixer, "E": moe_mixer}


def layer_forward(x, w: dict, kind: str, cfg: dict, lower: dict):
    """One block over one sequence. x: [T, hidden] float32."""
    return x + MIXERS[kind](rms_norm(x, cfg["layer_norm_epsilon"]), w, cfg,
                            lower)


class Forward:
    """The forward pass of one configuration, its programs compiled once:
    layer by layer, so that one layer's float32 weights are all that is
    live beside one sequence's activations."""

    def __init__(self, cfg: dict, lower: dict | None = None) -> None:
        self.cfg, self.lower = cfg, lower or {}
        self.keys = model_keys(cfg)
        h, dtype = cfg["hidden_size"], jnp.dtype(cfg["dtype"])
        self.embed = jax.jit(lambda: _dense(
            self.keys[0], (cfg["vocab_size"], h), h, dtype))()
        self.head = jax.jit(lambda: _dense(
            self.keys[-1], (h, cfg["vocab_size"]), h, dtype))()
        self.steps = {
            kind: (jax.jit(lambda key, kind=kind: layer_weights(
                       key, kind, cfg)),
                   jax.jit(lambda x, w, kind=kind: layer_forward(
                       x, w, kind, cfg, self.lower)))
            for kind in set(cfg["hybrid_override_pattern"])}
        self.final = jax.jit(lambda x, rows, head: _round_act(
            rms_norm(x[rows], cfg["layer_norm_epsilon"]), self.lower) @ head)

    def hidden(self, ids) -> jax.Array:
        """[T, hidden] after the last block, for one sequence of ids."""
        x = self.embed[jnp.asarray(ids)]
        pattern = self.cfg["hybrid_override_pattern"]
        for i in range(depth(self.cfg)):
            make, step = self.steps[pattern[i]]
            x = step(x, make(self.keys[i + 1]))
        return x

    def logits(self, x, rows) -> jax.Array:
        """Logits [len(rows), vocab] of the final hidden states' `rows`."""
        return self.final(x, jnp.asarray(rows), self.head)


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
