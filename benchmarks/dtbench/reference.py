"""The reference child, and the dense architecture's plain reference.

Run as a child process once the server has exited (the chip is free only
then): `python reference.py <job.json> <out.json>`. `main` is the one
program for every configuration: the loop over sets and controls, `gaps`,
`compare` and the output file are shared, so `correct` means one thing.
What differs by architecture is the forward pass, and a configuration
brings its own by name.

**A named reference** (`reference.module` in the configuration's file: a
path to a file under the benchmark's `paths`, say
`benchmarks/references/<name>.py`) is loaded here and asked for

    logits_for(samples, cfg, pad_to, lower=None)
        -> list of float32 arrays [n_served, vocab], one per sample

`samples` are {"prompt": ids, "served": ids}; row i of a sample's array
holds the logits at the position that predicted served token i, from one
full forward over prompt + served tokens padded to `pad_to`. `cfg` is
the whole configuration file, nested keys too (`rope_scaling`, `serve`,
`check`), with the `reference` object's keys on top, as the dense
reference has them. `lower` is one entry of `check.controls`: ONE stated
precision a step down, named by keys the module itself defines. Such a
module

  * imports nothing of the program and takes nothing the program made;
  * computes in float32 under `jax.default_matmul_precision("highest")`,
    with no kernels, no cache and no batching, layer by layer so that
    one layer's weights are all that is live beside the activations;
  * states its own seeded-weights recipe at its top, as this file does
    below, and its tests hold the program to it.

Absent the key, the reference is the one in this file: **a dense
decoder-only transformer** in `jax.numpy`, float32, `highest` matmul
precision; no kernels, no cache, no batching. It reads the flat `config`
of the job (the file's scalars), not the whole file.

It imports nothing of the program and takes nothing the program made. The
equations are the published ones (pre-norm residual blocks, RMSNorm,
rotary embedding in the half-split convention, grouped-query causal
attention, SwiGLU; per-head RMSNorm on q and k and a tied output head
where the configuration says so). The weights are data, stated here as a
recipe of its own and not read from the server:

  * values: `normal(key) / sqrt(fan_in)` rounded to the model dtype, with
    keys split from `PRNGKey(weight_seed)` as (embed, layer 0..L-1, head)
    and each layer's key split 15 ways (wq, wk, wv, wo, gate, up, down =
    0..6); norm gains are ones. This is what the configuration's file
    means by "seeded random weights"; the tests hold the program to it.
  * `weights: int4`: asymmetric codes 0..15 per group of 256 contracted
    rows per output channel, `w ~ (u - z) * s` with `s = (max - min)/15`
    and `z = round(-min / s)` (docs/quantization.md states the layout);
    the reference multiplies by the dequantised float32 matrix.

A control is the same forward with ONE stated precision taken a step down
(`controls` in the job, each by name): K and V rounded per token to fewer
bits, or matmul inputs rounded to int8 or fp8, or weights to int8 per
output channel. One axis at a time, because a later PR would lower one:
the limits have to fail the weakest. Never part of a benchmark run.
"""

from __future__ import annotations

import importlib.util
import json
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

Q4_GROUP = 256


# -- weights (a recipe, not a copy) -----------------------------------------


def _dense(key, shape, fan_in: int, dtype):
    return (jax.random.normal(key, shape, dtype=jnp.float32)
            * (1.0 / math.sqrt(fan_in))).astype(dtype)


def _int4_groups(w, n_contract: int):
    """float32 matrix the int4 group quantiser stands for."""
    out_shape = w.shape
    k = int(np.prod(w.shape[:n_contract]))
    group = Q4_GROUP
    while group > 2 and k % group:
        group //= 2
    w2 = w.astype(jnp.float32).reshape(k // group, group, -1)
    lo = jnp.min(w2, axis=1)
    hi = jnp.max(w2, axis=1)
    scale = jnp.maximum((hi - lo) / 15.0, 1e-12)
    zero = jnp.round(-lo / scale)
    codes = jnp.clip(jnp.round(w2 / scale[:, None, :]) + zero[:, None, :],
                     0.0, 15.0)
    return ((codes - zero[:, None, :]) * scale[:, None, :]).reshape(out_shape)


def _int8_channels(w, n_contract: int):
    """Control only: symmetric int8 per output channel."""
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=tuple(range(n_contract)))
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    return jnp.clip(jnp.round(w32 / scale), -127, 127) * scale


def _stored(w, n_contract: int, cfg: dict, lower: dict):
    """The float32 matrix the stated storage format stands for."""
    if cfg["weights"] == "int4":
        return _int4_groups(w, n_contract)
    if lower.get("weights") == "int8":
        return _int8_channels(w, n_contract)
    return w.astype(jnp.float32)


def layer_weights(key, cfg: dict, lower: dict) -> dict:
    dtype = jnp.dtype(cfg["dtype"])
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    qh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    m = cfg["intermediate_size"]
    ks = jax.random.split(key, 15)
    return {
        "wq": _stored(_dense(ks[0], (h, qh, hd), h, dtype), 1, cfg, lower),
        "wk": _stored(_dense(ks[1], (h, kh, hd), h, dtype), 1, cfg, lower),
        "wv": _stored(_dense(ks[2], (h, kh, hd), h, dtype), 1, cfg, lower),
        "wo": _stored(_dense(ks[3], (qh, hd, h), qh * hd, dtype), 2, cfg,
                      lower),
        "w_gate": _stored(_dense(ks[4], (h, m), h, dtype), 1, cfg, lower),
        "w_up": _stored(_dense(ks[5], (h, m), h, dtype), 1, cfg, lower),
        "w_down": _stored(_dense(ks[6], (m, h), m, dtype), 1, cfg, lower),
    }


def model_keys(cfg: dict):
    return jax.random.split(jax.random.PRNGKey(int(cfg["weight_seed"])),
                            cfg["num_hidden_layers"] + 2)


def embed_weights(keys, cfg: dict):
    dtype = jnp.dtype(cfg["dtype"])
    h = cfg["hidden_size"]
    return _dense(keys[0], (cfg["vocab_size"], h), h,
                  dtype).astype(jnp.float32)


def head_weights(keys, cfg: dict, embed, lower: dict):
    if cfg["tie_word_embeddings"]:
        return embed.T
    dtype = jnp.dtype(cfg["dtype"])
    h = cfg["hidden_size"]
    return _stored(_dense(keys[-1], (h, cfg["vocab_size"]), h, dtype), 1,
                   cfg, lower)


# -- the forward pass ---------------------------------------------------------


def rms_norm(x, eps: float):
    """RMSNorm with unit gain (the seeded gains are ones)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, positions, theta: float):
    """x: [T, H, hd]; rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _round_act(x, how):
    """Control only: round a matmul input to a lower precision."""
    if how == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if how == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if how == "int8":  # per token, symmetric
        scale = jnp.maximum(
            jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0, 1e-12)
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x


def _round_kv(x, bits):
    """Control only: one symmetric scale per token, shared by heads."""
    if not bits:
        return x
    top = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(
        jnp.max(jnp.abs(x), axis=(-2, -1), keepdims=True) / top, 1e-12)
    return jnp.clip(jnp.round(x / scale), -top, top) * scale


def layer_forward(x, w: dict, cfg: dict, lower: dict):
    """One block over one sequence. x: [T, hidden] float32."""
    t = x.shape[0]
    qh, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, act = cfg["rms_norm_eps"], lower.get("act")
    positions = jnp.arange(t)
    h = _round_act(rms_norm(x, eps), act)
    q = jnp.einsum("th,hqd->tqd", h, w["wq"])
    k = jnp.einsum("th,hkd->tkd", h, w["wk"])
    v = jnp.einsum("th,hkd->tkd", h, w["wv"])
    if cfg["qk_norm"]:
        q, k = rms_norm(q, eps), rms_norm(k, eps)
    q = rope(q, positions, cfg["rope_theta"])
    k = rope(k, positions, cfg["rope_theta"])
    k, v = _round_kv(k, lower.get("kv_bits")), _round_kv(v, lower.get("kv_bits"))
    group = qh // kh
    qg = _round_act(q, act).reshape(t, kh, group, -1)
    scores = jnp.einsum("tkgd,skd->kgts", qg, k) / math.sqrt(q.shape[-1])
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("kgts,skd->tkgd", _round_act(probs, act), v)
    attn = attn.reshape(t, qh, -1)
    x = x + jnp.einsum("tqd,qdh->th", _round_act(attn, act), w["wo"])
    h = _round_act(rms_norm(x, eps), act)
    gate = jnp.einsum("th,hm->tm", h, w["w_gate"])
    up = jnp.einsum("th,hm->tm", h, w["w_up"])
    mlp = _round_act(jax.nn.silu(gate) * up, act)
    return x + jnp.einsum("tm,mh->th", mlp, w["w_down"])


def logits_for(samples: list[dict], cfg: dict, pad_to: int,
               lower: dict | None = None) -> list[np.ndarray]:
    """For each sample (prompt ids, served ids): the logits, [n_served,
    vocab], at the positions that predicted each served token, from one
    full forward over prompt + served tokens. Layer by layer, so that one
    layer's float32 weights are all that is live beside the activations."""
    lower = lower or {}
    keys = model_keys(cfg)
    with jax.default_matmul_precision("highest"):
        embed = jax.jit(lambda: embed_weights(keys, cfg))()
        xs = []
        for s in samples:
            ids = np.zeros(pad_to, np.int32)
            seq = list(s["prompt"]) + list(s["served"])
            ids[:len(seq)] = seq
            xs.append(embed[jnp.asarray(ids)])
        make = jax.jit(lambda key: layer_weights(key, cfg, lower))
        step = jax.jit(lambda x, w: layer_forward(x, w, cfg, lower))
        for i in range(cfg["num_hidden_layers"]):
            w = make(keys[i + 1])
            xs = [step(x, w) for x in xs]
        del w
        head = jax.jit(lambda e: head_weights(keys, cfg, e, lower))(embed)
        final = jax.jit(lambda x, rows, hd: jnp.einsum(
            "th,hv->tv",
            _round_act(rms_norm(x[rows], cfg["rms_norm_eps"]),
                       lower.get("act")), hd))
        out = []
        for s, x in zip(samples, xs):
            n_p, n_s = len(s["prompt"]), len(s["served"])
            # fixed row count (one compiled shape): pad with the last row
            rows = np.full(pad_to, n_p + n_s - 2, np.int32)
            rows[:n_s] = np.arange(n_p - 1, n_p + n_s - 1)
            out.append(np.asarray(final(x, jnp.asarray(rows), head))[:n_s])
    return out


# -- the comparison -----------------------------------------------------------


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """By how much each token's reference logit lies below the
    reference's best at that position (0 where it is the arg-max)."""
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]


def compare(ref_logits: list[np.ndarray], tokens: list[np.ndarray]) -> dict:
    """The numbers `correct` is decided on, over all sampled positions."""
    all_gaps = np.concatenate([gaps(r, np.asarray(t))
                               for r, t in zip(ref_logits, tokens)])
    spread = float(np.mean([r.std() for r in ref_logits]))
    return {
        "positions": int(all_gaps.size),
        "gap_max": float(all_gaps.max()),
        "gap_mean": float(all_gaps.mean()),
        "off_best_share": float((all_gaps > 0).mean()),
        "logit_std": spread,
    }


def named(path: str | None):
    """The `logits_for` a job's `module` names, or this file's."""
    if path is None:
        return logits_for
    spec = importlib.util.spec_from_file_location("named_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.logits_for


def main(argv: list[str]) -> int:
    job = json.load(open(argv[1]))
    t0 = time.monotonic()
    device = jax.devices()[0]
    cfg, pad_to = job["config"], int(job["pad_to"])
    module = job.get("module")
    forward = named(module)
    if module is not None:  # the whole file, nested keys too
        cfg = {**job["file"], **cfg}
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "module": module or "dtbench/reference.py (dense)",
              "sets": []}
    for entry in job["sets"]:
        samples = entry["samples"]
        ref = forward(samples, cfg, pad_to)
        served = [np.asarray(s["served"]) for s in samples]
        row = {"label": entry.get("label"), "served": compare(ref, served)}
        if entry.get("control"):
            row["controls"] = {}
            for name, lower in job["controls"].items():
                low = forward(samples, cfg, pad_to, lower)
                row["controls"][name] = compare(
                    ref, [lg.argmax(-1) for lg in low])
        result["sets"].append(row)
    result["seconds"] = time.monotonic() - t0
    with open(argv[2], "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
