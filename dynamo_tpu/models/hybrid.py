"""Hybrid decoder (nemotron_h): ONE mixer a layer, its kind read off
`ModelConfig.layer_pattern`.

    x <- x + Mixer_l(RMSNorm(x; w_l))          after the last: RMSNorm, head

  M  Mamba-2: [z | xBC | dt] = x W_in; xBC <- silu(conv1d_k4(xBC) + b);
     xBC -> x [H, P], B [G, N], C [G, N]; dt <- softplus(dt + dt_bias);
     S_t = exp(dt A) S_{t-1} + dt (x outer B); y = S C + D x;
     y <- GroupRMSNorm(y * silu(z)) (gate first, then norm); out = y W_out
  *  attention: grouped-query, causal softmax, NO positional term
  E  routed experts: sigmoid scores, top-k of scores + bias, weights = the
     unbiased scores renormalised x scale; expert = W_down relu(W_up x)^2;
     one shared expert of the same form, always added

Two kinds of cache side by side: KV pages for the `*` layers only (cache
layer j = the j-th `*` layer), and for each `M` layer a fixed-size state
per scheduler slot: `conv` [slots, K-1, conv_dim] (the K-1 inputs before
the next position; the model dtype) and `ssm` [slots, H, P, N] (float32).
Rules the scheduler and runner rely on:

  * a row that starts at position 0 starts from ZERO state, whatever the
    slot held: admission needs no separate reset;
  * padding of a prefill bucket and empty rows of a batched prefill give
    dt = 0 and stay out of the conv carry: they advance nothing;
  * a decode step touches only active rows (a slot between two prefill
    chunks keeps its state);
  * prefill computes logits for each row's LAST valid position only.

The expert layer is dropless (ops/grouped_matmul.py) and is told which of
the published experts it holds (`config.experts_held`); tokens routed to
an absent expert get nothing from it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import dropless_experts
from ..ops.ssm import (
    causal_conv,
    expand_groups,
    ssm_chunk_scan,
    ssm_state_update,
    ssm_state_update_xla,
)
from .config import ModelConfig
from .transformer import (
    _routing_weights,
    paged_attention_decode_xla,
    paged_attention_xla,
    rms_norm,
    write_kv_pages,
    write_kv_stack,
)


def hybrid_refusals(config: ModelConfig, weight_dtype: str = "model",
                    kv_dtype: str = "model", devices: int = 1) -> None:
    """What the step programs of a hybrid stack cannot run with yet,
    refused by flag and reason (engine/worker.py refuses them before a
    process starts, `ModelRunner` when it is built)."""
    what = f"{config.name} (layers {config.layer_pattern})"
    if weight_dtype != "model":
        raise ValueError(
            f"--weight-dtype {weight_dtype}: models/quantize.py packs dense "
            f"projections only; {what} has Mamba-2 and expert matrices it "
            "has no layout for")
    if kv_dtype != "model":
        raise ValueError(
            f"--kv-dtype {kv_dtype}: the int8 pool is not wired into the "
            f"hybrid decode path of {what}")
    if devices > 1:
        raise ValueError(
            f"--tp/--sp/--dp over {devices} devices: the per-slot state "
            f"and the experts of {what} are not sharded yet (no expert "
            "exchange, no sharded scan)")


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def hybrid_layer_axes(config: ModelConfig, layer_idx: int) -> dict:
    """Logical sharding axes of one layer (parallel.shardings). The Mamba
    and expert leaves are replicated: a sharded state cache and an expert
    exchange are not built (the worker refuses --tp/--sp for this family)."""
    kind = config.layer_kind(layer_idx)
    if kind == "M":
        return {"norm": ("embed",), "in_proj": ("embed", None),
                "conv_w": (None, None), "conv_b": (None,),
                "dt_bias": (None,), "a_log": (None,), "d_skip": (None,),
                "ssm_norm": (None,), "out_proj": (None, "embed")}
    if kind == "*":
        return {"norm": ("embed",),
                "wq": ("embed", "q_heads", "head_dim"),
                "wk": ("embed", "kv_heads", "head_dim"),
                "wv": ("embed", "kv_heads", "head_dim"),
                "wo": ("q_heads", "head_dim", "embed")}
    return {"norm": ("embed",), "router": ("embed", None),
            "e_bias": (None,), "e_up": (None, None, "embed"),
            "e_down": (None, None, "embed"),
            "s_up": ("embed", None), "s_down": (None, "embed")}


def init_hybrid_layer(k: jax.Array, config: ModelConfig,
                      layer_idx: int) -> dict:
    """Seeded weights of one layer. The recipe is restated, not imported,
    by benchmarks/references/nemotron_h.py; the tests hold the two equal.

    The layer's key splits 15 ways. Matrices are normal / sqrt(fan_in) in
    the model dtype; one that writes into the residual stream (out_proj,
    wo, every down-projection) has its mean over its input axes taken off
    first, so that the positive average of relu2 and silu outputs gives
    the tokens no common direction for the router to follow. Expert e's matrices come from fold_in(key, e) with e
    the PUBLISHED index, so a chip's share holds the same experts the
    whole model would. dt_bias is the inverse softplus of a log-uniform
    draw over [dt_min, dt_max]; A = -exp(a_log) with A uniform in
    [-16, -1]; D = 1; the router's selection bias is 0.02 x normal (small
    beside the scores' spread, as a trained bias that balances the load
    is), so routing is a little uneven. Those four stay float32."""
    dtype = jnp.dtype(config.dtype)
    h = config.hidden
    ks = jax.random.split(k, 15)

    def dense(key, shape, fan_in, centre=None):
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
        if centre is not None:
            w = w - jnp.mean(w, axis=centre, keepdims=True)
        return w.astype(dtype)

    kind = config.layer_kind(layer_idx)
    p = {"norm": jnp.ones((h,), dtype)}
    if kind == "M":
        nh, inner = config.mamba_heads, config.mamba_inner
        conv_dim, kw = config.mamba_conv_dim, config.conv_kernel
        u = jax.random.uniform(ks[3], (nh,), jnp.float32)
        dt = jnp.exp(u * (math.log(config.ssm_dt_max)
                          - math.log(config.ssm_dt_min))
                     + math.log(config.ssm_dt_min))
        dt = jnp.maximum(dt, config.ssm_dt_floor)
        p.update({
            "in_proj": dense(ks[0], (h, inner + conv_dim + nh), h),
            "conv_w": dense(ks[1], (kw, conv_dim), kw),
            "conv_b": (0.1 * jax.random.normal(
                ks[2], (conv_dim,), jnp.float32)).astype(dtype),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.log(jax.random.uniform(
                ks[4], (nh,), jnp.float32, 1.0, 16.0)),
            "d_skip": jnp.ones((nh,), jnp.float32),
            "ssm_norm": jnp.ones((inner,), dtype),
            "out_proj": dense(ks[6], (inner, h), inner, 0),
        })
    elif kind == "*":
        qh, kh, hd = config.n_q_heads, config.n_kv_heads, config.head_dim
        p.update({
            "wq": dense(ks[0], (h, qh, hd), h),
            "wk": dense(ks[1], (h, kh, hd), h),
            "wv": dense(ks[2], (h, kh, hd), h),
            "wo": dense(ks[3], (qh, hd, h), qh * hd, (0, 1)),
        })
    else:
        m = config.expert_mlp_hidden
        sm = config.shared_expert_hidden or config.n_shared_experts * m
        lo, hi = config.held_experts
        ids = jnp.arange(lo, hi)
        p.update({
            "router": dense(ks[7], (h, config.n_experts), h),
            "e_bias": 0.02 * jax.random.normal(
                ks[8], (config.n_experts,), jnp.float32),
            # stored [E, m, h]: see ops/grouped_matmul.expert_gmm
            "e_up": jax.vmap(lambda e: dense(
                jax.random.fold_in(ks[9], e), (h, m), h).T)(ids),
            "e_down": jax.vmap(lambda e: dense(
                jax.random.fold_in(ks[10], e), (m, h), m, 0))(ids),
            "s_up": dense(ks[12], (h, sm), h),
            "s_down": dense(ks[13], (sm, h), sm, 0),
        })
    return p


def make_state_cache(config: ModelConfig, slots: int) -> dict:
    """The per-slot recurrent state: one `conv` and one `ssm` array per
    Mamba layer (a list, so each layer's update aliases its own buffer)."""
    n = len(config.state_layers)
    return {
        "conv": [jnp.zeros((slots, config.conv_kernel - 1,
                            config.mamba_conv_dim), jnp.dtype(config.dtype))
                 for _ in range(n)],
        "ssm": [jnp.zeros((slots, config.mamba_heads, config.mamba_head_dim,
                           config.ssm_state),
                          jnp.dtype(config.ssm_state_dtype))
                for _ in range(n)],
    }


def state_slot_bytes(config: ModelConfig) -> int:
    """Bytes of recurrent state one slot holds, all Mamba layers."""
    conv = ((config.conv_kernel - 1) * config.mamba_conv_dim
            * jnp.dtype(config.dtype).itemsize)
    ssm = (config.mamba_heads * config.mamba_head_dim * config.ssm_state
           * jnp.dtype(config.ssm_state_dtype).itemsize)
    return len(config.state_layers) * (conv + ssm)


# ---------------------------------------------------------------------------
# mixers
# ---------------------------------------------------------------------------


def _gated_group_norm(y, z, weight, groups: int, eps: float):
    """GroupRMSNorm(y * silu(z)): gate first, then norm each group."""
    dtype = z.dtype
    g = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
    shape = g.shape
    g = g.reshape(*shape[:-1], groups, shape[-1] // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(shape).astype(dtype) * weight


def _split_in_proj(zxbcdt, config: ModelConfig):
    inner, conv_dim = config.mamba_inner, config.mamba_conv_dim
    return (zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv_dim],
            zxbcdt[..., inner + conv_dim:])


def _split_xbc(xbc, config: ModelConfig):
    inner = config.mamba_inner
    gn = config.ssm_groups * config.ssm_state
    lead = xbc.shape[:-1]
    xs = xbc[..., :inner].reshape(*lead, config.mamba_heads,
                                  config.mamba_head_dim)
    b = xbc[..., inner:inner + gn].reshape(*lead, config.ssm_groups,
                                           config.ssm_state)
    c = xbc[..., inner + gn:].reshape(*lead, config.ssm_groups,
                                      config.ssm_state)
    return xs, b, c


def mamba_prefill(x, lp, config: ModelConfig, conv, ssm, valid):
    """x [B, T, h] (normed); conv [B, K-1, C], ssm [B, H, P, N]: the rows'
    state going in. Returns (out [B, T, h], conv, ssm coming out)."""
    with jax.named_scope("mamba_mixer"):
        z, xbc, dt = _split_in_proj(
            jnp.einsum("bth,hm->btm", x, lp["in_proj"]), config)
        n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
        xbc, conv = causal_conv(conv, xbc, lp["conv_w"], lp["conv_b"],
                                n_valid)
        xs, b, c = _split_xbc(xbc, config)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
        dt = jnp.where(valid[:, :, None], dt, 0.0)
        a = -jnp.exp(lp["a_log"])
        ssm, y = ssm_chunk_scan(ssm, dt, a, xs, b, c, chunk=config.ssm_chunk)
        y = y + lp["d_skip"][None, None, :, None] * xs.astype(jnp.float32)
        y = _gated_group_norm(y.reshape(*z.shape), z, lp["ssm_norm"],
                              config.ssm_groups, config.rms_eps)
        return jnp.einsum("btm,mh->bth", y, lp["out_proj"]), conv, ssm


def mamba_decode(x, lp, config: ModelConfig, conv, ssm, active,
                 ssm_path: str):
    """One token a slot. x [S, h]; conv, ssm: the WHOLE cache of this
    layer (row i = slot i). Inactive rows keep their state."""
    with jax.named_scope("mamba_mixer"):
        z, xbc, dt = _split_in_proj(
            jnp.einsum("sh,hm->sm", x, lp["in_proj"]), config)
        window = jnp.concatenate([conv.astype(xbc.dtype), xbc[:, None]],
                                 axis=1)  # [S, K, C]
        xbc = jnp.einsum("skc,kc->sc", window.astype(jnp.float32),
                         lp["conv_w"].astype(jnp.float32))
        xbc = jax.nn.silu(xbc + lp["conv_b"].astype(jnp.float32)
                          ).astype(x.dtype)
        conv = jnp.where(active[:, None, None],
                         window[:, 1:].astype(conv.dtype), conv)
        xs, b, c = _split_xbc(xbc, config)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
        nh = config.mamba_heads
        args = (ssm, dt, -jnp.exp(lp["a_log"]), xs, expand_groups(b, nh),
                expand_groups(c, nh), active)
        if ssm_path == "xla":
            ssm, y = ssm_state_update_xla(*args)
        else:
            ssm, y = ssm_state_update(*args,
                                      interpret=ssm_path == "interpret")
        y = y + lp["d_skip"][None, :, None] * xs.astype(jnp.float32)
        y = _gated_group_norm(y.reshape(*z.shape), z, lp["ssm_norm"],
                              config.ssm_groups, config.rms_eps)
        return jnp.einsum("sm,mh->sh", y, lp["out_proj"]), conv, ssm


def _relu2(u):
    return jnp.square(jax.nn.relu(u))


def moe_stats_size(config: ModelConfig) -> int:
    """Length of a step's expert statistics (`moe_mixer`)."""
    lo, hi = config.held_experts
    return hi - lo + 3


def moe_mixer(x, lp, config: ModelConfig, valid, gmm_path: str):
    """x [B, T, h] -> (out, stats [E_held + 3]): the tokens each held
    expert computed, then slots dropped, held experts touched (at least
    one token: the weights this call had to read) and 1 for the call."""
    with jax.named_scope("moe_experts"):
        b, t, h = x.shape
        weights, topi = _routing_weights(x, lp, config)
        out, counts, dropped = dropless_experts(
            x.reshape(b * t, h), weights.reshape(b * t, -1),
            topi.reshape(b * t, -1), valid.reshape(b * t),
            lp["e_up"], lp["e_down"], config.held_experts, _relu2,
            path=gmm_path)
        shared = jnp.einsum(
            "btm,mh->bth",
            _relu2(jnp.einsum("bth,hm->btm", x, lp["s_up"])), lp["s_down"])
        stats = jnp.concatenate([
            counts, jnp.stack([dropped, jnp.sum(counts > 0), 1])
        ]).astype(jnp.int32)
        return out.reshape(b, t, h) + shared, stats


def _head(x, params, config: ModelConfig):
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    return jnp.einsum("...h,hv->...v", x, params["lm_head"]
                      ).astype(jnp.float32)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def forward_hybrid(params, config: ModelConfig, tokens, positions, kv_cache,
                   state, slots, block_tables, kv_lens, valid, last_idx,
                   attention_fn=None, gmm_path: str = "xla",
                   all_logits: bool = False):
    """A prefill chunk a row. tokens, positions, valid [B, T]; slots [B]:
    each row's state slot (>= the cache's size for an empty row: its
    write is dropped); last_idx [B]: the row's last valid position in
    this chunk. Returns (kv_cache, state, logits [B, vocab] at last_idx,
    moe stats [E_held + 3]); `all_logits` gives [B, T, vocab] (tests)."""
    attention = attention_fn or paged_attention_xla
    fresh = positions[:, 0] == 0  # a row at position 0 starts from zero
    x = params["embed"][tokens]
    conv_out, ssm_out = list(state["conv"]), list(state["ssm"])
    stats = jnp.zeros((moe_stats_size(config),), jnp.int32)
    kv_idx = state_idx = 0
    for layer_idx, lp in enumerate(params["layers"]):
        kind = config.layer_kind(layer_idx)
        h = rms_norm(x, lp["norm"], config.rms_eps)
        if kind == "M":
            conv_all, ssm_all = conv_out[state_idx], ssm_out[state_idx]
            conv = jnp.where(fresh[:, None, None], 0, conv_all[slots])
            ssm = jnp.where(fresh[:, None, None, None], 0, ssm_all[slots])
            out, conv, ssm = mamba_prefill(h, lp, config, conv, ssm, valid)
            conv_out[state_idx] = conv_all.at[slots].set(conv, mode="drop")
            ssm_out[state_idx] = ssm_all.at[slots].set(ssm, mode="drop")
            state_idx += 1
        elif kind == "*":
            q = jnp.einsum("bth,hqd->btqd", h, lp["wq"])
            k = jnp.einsum("bth,hkd->btkd", h, lp["wk"])
            v = jnp.einsum("bth,hkd->btkd", h, lp["wv"])
            kv_cache = write_kv_pages(kv_cache, kv_idx, k, v, block_tables,
                                      positions, valid)
            attn = attention(q, kv_cache, kv_idx, block_tables, positions,
                             kv_lens)
            out = jnp.einsum("btqd,qdh->bth", attn, lp["wo"])
            kv_idx += 1
        else:
            out, layer_stats = moe_mixer(h, lp, config, valid, gmm_path)
            stats = stats + layer_stats
        x = x + out
    state = {"conv": conv_out, "ssm": ssm_out}
    if not all_logits:
        x = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
    return kv_cache, state, _head(x, params, config), stats


def forward_hybrid_decode(params, config: ModelConfig, tokens, positions,
                          kv_cache, state, block_tables, kv_lens, active,
                          decode_attention_fn=None, ssm_path: str = "xla",
                          gmm_path: str = "xla"):
    """One token for every slot (row i = slot i), KV writes deferred to
    one scatter for all attention layers as in `forward_decode`. Returns
    (kv_cache, state, logits [S, 1, vocab], moe stats)."""
    attn_fn = decode_attention_fn or paged_attention_decode_xla
    attn_lens = jnp.where(active, kv_lens, 0)
    x = params["embed"][tokens]  # [S, h]
    conv_out, ssm_out = list(state["conv"]), list(state["ssm"])
    stats = jnp.zeros((moe_stats_size(config),), jnp.int32)
    ks, vs = [], []
    kv_idx = state_idx = 0
    for layer_idx, lp in enumerate(params["layers"]):
        kind = config.layer_kind(layer_idx)
        h = rms_norm(x, lp["norm"], config.rms_eps)
        if kind == "M":
            out, conv_out[state_idx], ssm_out[state_idx] = mamba_decode(
                h, lp, config, conv_out[state_idx], ssm_out[state_idx],
                active, ssm_path)
            state_idx += 1
        elif kind == "*":
            h1 = h[:, None, :]
            q = jnp.einsum("bth,hqd->btqd", h1, lp["wq"])
            k = jnp.einsum("bth,hkd->btkd", h1, lp["wk"])
            v = jnp.einsum("bth,hkd->btkd", h1, lp["wv"])
            attn = attn_fn(q, kv_cache, kv_idx, block_tables, attn_lens,
                           k, v)
            ks.append(k)
            vs.append(v)
            out = jnp.einsum("btqd,qdh->bth", attn, lp["wo"])[:, 0]
            kv_idx += 1
        else:
            out, layer_stats = moe_mixer(h[:, None, :], lp, config,
                                         active[:, None], gmm_path)
            out = out[:, 0]
            stats = stats + layer_stats
        x = x + out
    if ks:
        kv_cache = write_kv_stack(kv_cache, jnp.stack(ks), jnp.stack(vs),
                                  block_tables, positions[:, None],
                                  active[:, None])
    state = {"conv": conv_out, "ssm": ssm_out}
    return kv_cache, state, _head(x, params, config)[:, None, :], stats
