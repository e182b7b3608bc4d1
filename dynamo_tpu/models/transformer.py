"""Functional decoder transformer with paged KV.

Pure-functional JAX (params are a pytree; no Module state) so the whole
engine step jits and shards with pjit. Design points for TPU:

  * bf16 everywhere on the matmul path (MXU), fp32 for norms/softmax accum
  * paged KV cache: one array [layers, 2, pages, page_size, kv_heads, hd]
    donated through each step for in-place scatter updates
  * unified attention: queries (prefill chunk or single decode token) attend
    over the sequence's pages via its block table, so chunked prefill,
    prefix-cache hits, and decode share one code path
  * GQA with q-heads/kv-heads sharded over the tp mesh axis; all tensor
    contractions keep the tp axis inside einsums so XLA inserts ICI
    all-reduces only at block boundaries

The CUDA analog this replaces lives inside vLLM/TRT-LLM (the reference
delegates model code entirely; SURVEY section 2.5).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

# ---------------------------------------------------------------------------
# Parameter init + logical sharding axes
# ---------------------------------------------------------------------------


def param_axes(config: ModelConfig) -> dict:
    """Logical sharding axes per parameter (see parallel.shardings)."""
    if config.is_hybrid:
        from .hybrid import hybrid_layer_axes

        head = ({} if config.tie_embeddings
                else {"lm_head": ("embed", "vocab")})
        if config.norm_bias:
            head["final_norm_b"] = ("embed",)
        return {"embed": ("vocab", "embed"), "final_norm": ("embed",),
                **head,
                "layers": [hybrid_layer_axes(config, first, repeats)
                           for first, repeats, _ in config.layer_entries]}
    _refuse_multipliers(config)
    layer = {
        "attn_norm": ("embed",),
        "wq": ("embed", "q_heads", "head_dim"),
        "wo": ("q_heads", "head_dim", "embed"),
        "mlp_norm": ("embed",),
        "w_gate": ("embed", "mlp"),
        "w_up": ("embed", "mlp"),
        "w_down": ("mlp", "embed"),
    }
    if config.is_mla:
        # Latent path: the compressed c_kv is shared across heads (never
        # head-sharded); the up-projections carry the head axis for tp.
        layer["w_dkv"] = ("embed", None)
        layer["w_kr"] = ("embed", "head_dim")
        layer["kv_norm"] = (None,)
        layer["w_uk"] = (None, "q_heads", "head_dim")
        layer["w_uv"] = (None, "q_heads", "head_dim")
        if config.mla_q_lora_rank:
            # V3/R1-class query low-rank path replaces the direct wq
            del layer["wq"]
            layer["w_dq"] = ("embed", None)
            layer["q_a_norm"] = (None,)
            layer["w_uq"] = (None, "q_heads", "head_dim")
    else:
        layer["wk"] = ("embed", "kv_heads", "head_dim")
        layer["wv"] = ("embed", "kv_heads", "head_dim")
    if config.qk_norm:
        layer["q_norm"] = ("head_dim",)
        layer["k_norm"] = ("head_dim",)
    def layer_axes(i: int) -> dict:
        out = dict(layer)
        if config.is_gptoss:
            for name in ("w_gate", "w_up", "w_down"):
                out.pop(name, None)
            out.update({
                "bq": ("q_heads", "head_dim"),
                "bk": ("kv_heads", "head_dim"),
                "bv": ("kv_heads", "head_dim"),
                "bo": ("embed",),
                "sinks": ("q_heads",),
                "router": ("embed", "experts"),
                "router_bias": ("experts",),
                "e_gate_up": ("experts", "embed", "mlp"),
                "e_gate_up_bias": ("experts", "mlp"),
                "e_down": ("experts", "mlp", "embed"),
                "e_down_bias": ("experts", "embed"),
            })
            return out
        if config.layer_is_moe(i):
            out["router"] = ("embed", "experts")
            if config.moe_scoring == "sigmoid":
                out["e_bias"] = ("experts",)
            out["e_gate"] = ("experts", "embed", "mlp")
            out["e_up"] = ("experts", "embed", "mlp")
            out["e_down"] = ("experts", "mlp", "embed")
            if config.n_shared_experts:
                out["s_gate"] = ("embed", "mlp")
                out["s_up"] = ("embed", "mlp")
                out["s_down"] = ("mlp", "embed")
        return out

    axes = {
        "embed": ("vocab", "embed"),
        "final_norm": ("embed",),
        "layers": [layer_axes(i) for i in range(config.n_layers)],
    }
    if not config.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def _refuse_multipliers(config: ModelConfig) -> None:
    """The dense decoder applies none of granitemoehybrid's multipliers
    (models/hybrid.py does): a preset that sets one and has no
    `layer_pattern` would be served silently unscaled."""
    if config.multipliers:
        raise ValueError(
            f"{config.name} sets "
            + ", ".join(f"{k}={v}" for k, v in config.multipliers.items())
            + ", which the dense decoder (models/transformer.py) would "
            "ignore: only a `layer_pattern` stack (models/hybrid.py) "
            "applies them")


def _init_dense(k: jax.Array, shape, fan_in: int, dtype,
                gain: float = 1.0) -> jax.Array:
    return (jax.random.normal(k, shape, dtype=jnp.float32)
            * (gain / math.sqrt(fan_in))).astype(dtype)


def init_layer_params(k: jax.Array, config: ModelConfig,
                      layer_idx: int, out_gain=None) -> dict:
    """One layer of `init_params` (same values for the same key). Its
    own entry point so a 7B engine can initialise and quantise a layer
    at a time instead of compiling, and holding, the whole bf16 tree.
    `out_gain`: a hybrid layer's `branch_gain` as a traced scalar
    (models/hybrid.init_hybrid_layer)."""
    if config.is_hybrid:
        from .hybrid import init_hybrid_layer

        return init_hybrid_layer(k, config, layer_idx, out_gain)
    dtype = jnp.dtype(config.dtype)
    h, hd = config.hidden, config.head_dim
    qh, kh, m = config.n_q_heads, config.n_kv_heads, config.mlp_hidden

    def dense(k, shape, fan_in):
        return _init_dense(k, shape, fan_in, dtype)

    ks = jax.random.split(k, 15)
    if config.is_mla:
        dc = config.mla_kv_lora_rank
        nhd = config.mla_nope_head_dim
        rhd = config.mla_rope_head_dim
        vhd = config.mla_v_head_dim
        p = {
            "attn_norm": jnp.ones((h,), dtype),
            "w_dkv": dense(ks[1], (h, dc), h),
            "w_kr": dense(ks[2], (h, rhd), h),
            "kv_norm": jnp.ones((dc,), dtype),
            "w_uk": dense(ks[10], (dc, qh, nhd), dc),
            "w_uv": dense(ks[11], (dc, qh, vhd), dc),
            "wo": dense(ks[3], (qh, vhd, h), qh * vhd),
        }
        if config.mla_q_lora_rank:
            qr = config.mla_q_lora_rank
            p["w_dq"] = dense(ks[0], (h, qr), h)
            p["q_a_norm"] = jnp.ones((qr,), dtype)
            p["w_uq"] = dense(ks[12], (qr, qh, nhd + rhd), qr)
        else:
            p["wq"] = dense(ks[0], (h, qh, nhd + rhd), h)
    else:
        p = {
            "attn_norm": jnp.ones((h,), dtype),
            "wq": dense(ks[0], (h, qh, hd), h),
            "wk": dense(ks[1], (h, kh, hd), h),
            "wv": dense(ks[2], (h, kh, hd), h),
            "wo": dense(ks[3], (qh, hd, h), qh * hd),
        }
    p.update({
        "mlp_norm": jnp.ones((h,), dtype),
        "w_gate": dense(ks[4], (h, m), h),
        "w_up": dense(ks[5], (h, m), h),
        "w_down": dense(ks[6], (m, h), m),
    })
    if config.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    if config.is_gptoss:
        e, em = config.n_experts, config.expert_mlp_hidden or m
        for name in ("w_gate", "w_up", "w_down"):
            p.pop(name, None)  # experts replace the dense MLP
        p.update({
            "bq": dense(ks[7], (qh, hd), h) * 0.02,
            "bk": dense(ks[8], (kh, hd), h) * 0.02,
            "bv": dense(ks[9], (kh, hd), h) * 0.02,
            "bo": dense(ks[10], (h,), h) * 0.02,
            "sinks": dense(ks[11], (qh,), 1),
            "router": dense(ks[12], (h, e), h),
            "router_bias": jnp.zeros((e,), dtype),
            "e_gate_up": dense(ks[13], (e, h, 2 * em), h),
            "e_gate_up_bias": jnp.zeros((e, 2 * em), dtype),
            "e_down": dense(ks[14], (e, em, h), em),
            "e_down_bias": jnp.zeros((e, h), dtype),
        })
        return p
    if config.layer_is_moe(layer_idx):
        e, em = config.n_experts, config.expert_mlp_hidden or m
        p["router"] = dense(ks[7], (h, e), h)
        if config.moe_scoring == "sigmoid":
            p["e_bias"] = jnp.zeros((e,), jnp.float32)
        p["e_gate"] = dense(ks[8], (e, h, em), h)
        p["e_up"] = dense(ks[9], (e, h, em), h)
        p["e_down"] = dense(ks[7], (e, em, h), em)
        if config.n_shared_experts:
            sm = config.n_shared_experts * em
            p["s_gate"] = dense(ks[12], (h, sm), h)
            p["s_up"] = dense(ks[13], (h, sm), h)
            p["s_down"] = dense(ks[14], (sm, h), sm)
    return p


def init_top_params(k_embed: jax.Array, k_head: jax.Array,
                    config: ModelConfig) -> dict:
    """Everything in `init_params` outside the layer list."""
    dtype = jnp.dtype(config.dtype)
    h = config.hidden
    # The matrix the logits are read from is drawn `logits_scaling`
    # times wider, so that logits / logits_scaling have the spread 1
    # every recipe gives: the embedding itself where the head is tied.
    tied = config.tie_embeddings
    params = {
        "embed": _init_dense(k_embed, (config.vocab_size, h), h, dtype,
                             config.logits_scaling if tied else 1.0),
        "final_norm": jnp.ones((h,), dtype),
    }
    if not tied:
        params["lm_head"] = _init_dense(k_head, (h, config.vocab_size), h,
                                        dtype, config.logits_scaling)
    if config.norm_bias:  # a LayerNorm with a bias (models/hybrid.py)
        from .hybrid import BIAS_SPREAD

        params["final_norm_b"] = (BIAS_SPREAD * jax.random.normal(
            jax.random.fold_in(k_embed, 1), (h,), jnp.float32)).astype(dtype)
    return params


def init_params(key: jax.Array, config: ModelConfig) -> dict:
    keys = jax.random.split(key, config.n_layers + 2)
    params = init_top_params(keys[0], keys[-1], config)
    if config.is_hybrid:
        from .hybrid import init_hybrid_entry

        params["layers"] = [init_hybrid_entry(keys, config, entry)
                            for entry in config.layer_entries]
        return params
    params["layers"] = [init_layer_params(keys[i + 1], config, i)
                        for i in range(config.n_layers)]
    return params


def make_kv_cache(config: ModelConfig, num_pages: int, page_size: int,
                  dtype: Optional[str] = None,
                  group: str = "full") -> jax.Array:
    """[layers, kv_dims, pages, page_size, cache_heads, cache_head_dim].
    Standard attention: kv_dims=2 (K and V stacks), heads=n_kv_heads.
    MLA: kv_dims=1, heads=1, head_dim=latent_rank+rope_dim — the compressed
    latent cache. Page 0 is a reserved scratch page (block tables point
    unused slots at it). A hybrid stack caches its attention layers only,
    a page group each kind: `group` "full" holds `config.kv_layers`,
    "window" `config.window_kv_layers`, each with its own page ids."""
    layers = (config.window_kv_layers if group == "window"
              else config.kv_layers)
    return jnp.zeros(
        (len(layers), config.kv_cache_kv_dims, num_pages,
         page_size,
         config.kv_cache_heads, config.kv_cache_head_dim),
        dtype=jnp.dtype(dtype or config.dtype),
    )


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


# Lane width of the scale rows: matches the TPU vector lane count so the
# kernel's per-page scale DMA slices are tiling-aligned and the dequant is
# a pure elementwise multiply (no lane gathers/reshapes, which Mosaic
# rejects).
KV_SCALE_LANES = 128


def make_kv_cache_int8(config: ModelConfig, num_pages: int,
                       page_size: int) -> tuple[jax.Array, jax.Array]:
    """Quantized paged cache: (values int8 [L, 2, P, ps, kh, hd],
    scales bf16 [L, 2, P, ps, LANES]) with one absmax scale per TOKEN,
    shared across heads and lane-broadcast so the Pallas kernel dequant
    is elementwise. ~1.6x less KV HBM traffic and capacity vs bf16 — the
    decode bandwidth lever (BASELINE.md decode-wall analysis; the
    reference gets fp8 KV from its engines' quantized cache modes).
    Head-sharing costs little: qk-norm families normalize per head, so
    per-token absmax dominates. Standard-attention models only (MLA's
    latent is already ~10x smaller).

    Both arrays stay row-major on the device, in every step program: the
    decode kernels (ops/paged_attention.py) take the whole pool as an
    operand and Mosaic constrains an operand to row-major, so a program
    that gives the donated pool any other layout copies all of it out
    and back every step (1.34 GB of scales at 32 layers x 5120 pages:
    PERF.md, PR 37). Write scales with `_write_scale_rows` or one layer
    at a time (`write_kv_pages`), never by a scatter indexed on several
    of the array's dimensions; tests/test_tpu_compile.py holds it."""
    assert not config.is_mla, "int8 KV targets standard-attention models"
    values = jnp.zeros(
        (config.n_layers, 2, num_pages, page_size, config.n_kv_heads,
         config.head_dim), jnp.int8)
    scales = jnp.zeros(
        (config.n_layers, 2, num_pages, page_size, KV_SCALE_LANES),
        jnp.bfloat16)
    return values, scales


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[..., kh, hd] float -> (int8 [..., kh, hd], lane-broadcast scale
    bf16 [..., LANES]) — one symmetric absmax scale per TOKEN (shared
    across heads)."""
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=(-2, -1))
    scale = (absmax / 127.0).astype(jnp.bfloat16).astype(jnp.float32)
    q = jnp.round(x32 / jnp.maximum(scale, 1e-12)[..., None, None])
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    scale_rows = jnp.broadcast_to(
        scale[..., None].astype(jnp.bfloat16),
        scale.shape + (KV_SCALE_LANES,))
    return q, scale_rows


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    orig = x.dtype
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(orig) * weight


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [..., T, H, hd]; positions: [..., T]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, half]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def yarn_rope_tables(config: ModelConfig) -> tuple[jax.Array, float]:
    """YaRN-scaled inverse frequencies + cos/sin attention factor,
    matching HF `_compute_yarn_parameters` (gpt-oss: truncate=False,
    attention_factor = 0.1*ln(factor)+1). Returns (inv_freq [hd/2],
    attention_factor)."""
    dim = config.head_dim
    base = config.rope_theta
    factor = config.rope_yarn_factor
    orig_max = config.rope_yarn_orig_max

    def correction_dim(num_rot):
        return (dim * math.log(orig_max / (num_rot * 2 * math.pi))
                ) / (2 * math.log(base))

    low = correction_dim(config.rope_yarn_beta_fast)
    high = correction_dim(config.rope_yarn_beta_slow)
    if config.rope_yarn_truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    extrap = 1.0 / pos_freqs
    interp = 1.0 / (factor * pos_freqs)
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low),
        0, 1)
    extrap_factor = 1.0 - ramp
    inv_freq = interp * (1 - extrap_factor) + extrap * extrap_factor
    attention_factor = 0.1 * math.log(factor) + 1.0
    return inv_freq, attention_factor


def rope_gptoss(x: jax.Array, positions: jax.Array,
                config: ModelConfig) -> jax.Array:
    """Rotary embedding with YaRN scaling (same half-split rotate form
    as rope(); cos/sin scaled by the YaRN attention factor)."""
    inv_freq, att = yarn_rope_tables(config)
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = (jnp.cos(angles) * att)[..., None, :]
    sin = (jnp.sin(angles) * att)[..., None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def paged_attention_sinks_xla(
    q: jax.Array,  # [B, T, qh, hd]
    kv_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,
    positions: jax.Array,  # [B, T]
    kv_lens: jax.Array,
    sinks: jax.Array,  # [qh] learned sink logits
    window: int,  # 0 = full attention
) -> jax.Array:
    """gpt-oss attention: a per-head SINK logit joins the softmax (its
    probability is dropped — attention mass can 'park' on the sink,
    ref HF eager_attention_forward), with an optional sliding window
    (kv position > query position - window)."""
    values, _scales = _kv_parts(kv_cache)
    b, t, qh, hd = q.shape
    ps = values.shape[3]
    kh = values.shape[4]
    max_pages = block_tables.shape[1]
    ctx = max_pages * ps
    k = values[layer, 0][block_tables].reshape(b, ctx, kh, hd)
    v = values[layer, 1][block_tables].reshape(b, ctx, kh, hd)
    group = qh // kh
    qg = q.reshape(b, t, kh, group, hd)
    scores = jnp.einsum("btkgh,bskh->btkgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    kv_pos = jnp.arange(ctx)[None, :]
    mask = (kv_pos[:, None, :] <= positions[..., None]) & (
        kv_pos[:, None, :] < kv_lens[:, None, None])
    if window:
        mask = mask & (kv_pos[:, None, :]
                       > positions[..., None] - window)
    scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
    sink = sinks.astype(jnp.float32).reshape(kh, group)[None, None, :, :,
                                                        None]
    combined = jnp.concatenate(
        [scores, jnp.broadcast_to(sink, (b, t, kh, group, 1))], axis=-1)
    combined = combined - jnp.max(combined, axis=-1, keepdims=True)
    probs = jax.nn.softmax(combined, axis=-1)[..., :-1]  # drop the sink
    out = jnp.einsum("btkgs,bskh->btkgh", probs, v.astype(jnp.float32))
    return out.reshape(b, t, qh, hd).astype(q.dtype)


def _moe_gptoss(x: jax.Array, p: dict, config: ModelConfig) -> jax.Array:
    """gpt-oss MoE: biased router, softmax over the TOP-K logits, fused
    gate_up experts with the clipped gated swiglu
    (ref HF GptOssExperts/GptOssTopKRouter). Dense-over-experts compute
    (every expert for every token, masked) — matches HF's inference
    path; capacity dispatch over the ep axis is the optimization path
    shared with _moe once sharded."""
    logits = jnp.einsum("bth,he->bte", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32)) \
        + p["router_bias"].astype(jnp.float32)
    topv, topi = jax.lax.top_k(logits, config.n_experts_active)
    topw = jax.nn.softmax(topv, axis=-1)
    b, t, _ = x.shape
    mask = jnp.zeros((b, t, config.n_experts), jnp.float32).at[
        jnp.arange(b)[:, None, None], jnp.arange(t)[None, :, None], topi
    ].set(topw)
    gate_up = jnp.einsum("bth,ehm->betm", x, p["e_gate_up"]) \
        + p["e_gate_up_bias"][None, :, None, :].astype(x.dtype)
    gate = gate_up[..., ::2]
    up = gate_up[..., 1::2]
    limit = config.swiglu_limit
    gate = jnp.clip(gate.astype(jnp.float32), max=limit)
    up = jnp.clip(up.astype(jnp.float32), min=-limit, max=limit)
    glu = gate * jax.nn.sigmoid(gate * config.swiglu_alpha)
    act = ((up + 1.0) * glu).astype(x.dtype)
    expert_out = jnp.einsum("betm,emh->beth", act, p["e_down"]) \
        + p["e_down_bias"][None, :, None, :].astype(x.dtype)
    return jnp.einsum("beth,bte->bth", expert_out.astype(jnp.float32),
                      mask).astype(x.dtype)


def _gptoss_attention_block(
    h: jax.Array,  # [B, T, H] (attn-normed)
    lp: dict,
    config: ModelConfig,
    kv_cache: jax.Array,
    layer_idx: int,
    block_tables: jax.Array,
    positions: jax.Array,
    kv_lens: jax.Array,
    valid: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """qkv with biases, YaRN rope, sink attention with the per-layer
    sliding window; returns (kv_cache, attn [B, T, qh, hd])."""
    q = jnp.einsum("bth,hqd->btqd", h, lp["wq"]) + lp["bq"]
    k = jnp.einsum("bth,hkd->btkd", h, lp["wk"]) + lp["bk"]
    v = jnp.einsum("bth,hkd->btkd", h, lp["wv"]) + lp["bv"]
    q = rope_gptoss(q, positions, config)
    k = rope_gptoss(k, positions, config)
    kv_cache = write_kv_pages(kv_cache, layer_idx, k, v, block_tables,
                              positions, valid)
    attn = paged_attention_sinks_xla(
        q, kv_cache, layer_idx, block_tables, positions, kv_lens,
        lp["sinks"], config.layer_sliding_window(layer_idx))
    return kv_cache, attn


def _mm(spec: str, x: jax.Array, w, rows=None) -> jax.Array:
    """Dense projection that transparently supports weight-only
    quantized leaves (models/quantize.py): int8 {"q8","qs"} routes
    through the Pallas W8A16 kernel (ops/q8_linear.py), packed int4
    {"q4","qs4","qz4"} through the W4A16 kernel (ops/q4_linear.py) —
    either way the bf16 weight never materializes in HBM. `rows` (a
    prefill launch's validity mask as ops.q4_linear.live_row_blocks
    reduced it) lets the int4 kernel skip row blocks of padding; bf16
    and int8 leaves ignore it."""
    if isinstance(w, dict):
        if "q4" in w:
            from ..ops.q4_linear import q4_einsum

            return q4_einsum(spec, x, w["q4"], w["qs4"], w["qz4"],
                             rows=rows)
        from ..ops.q8_linear import q8_einsum

        return q8_einsum(spec, x, w["q8"], w["qs"])
    return jnp.einsum(spec, x, w)


def _swiglu(x: jax.Array, p: dict, lora_layer: Optional[dict] = None,
            lora_idx: Optional[jax.Array] = None, rows=None) -> jax.Array:
    gate = _mm("bth,hm->btm", x, p["w_gate"], rows)
    up = _mm("bth,hm->btm", x, p["w_up"], rows)
    if lora_layer is not None:
        gate = gate + _lora_delta(x, lora_layer["w_gate"], lora_idx)
        up = up + _lora_delta(x, lora_layer["w_up"], lora_idx)
    act = jax.nn.silu(gate) * up
    down = _mm("btm,mh->bth", act, p["w_down"], rows)
    if lora_layer is not None:
        down = down + _lora_delta(act, lora_layer["w_down"], lora_idx)
    return down


def _routing_weights(x: jax.Array, p: dict, config: ModelConfig):
    """Top-k routing weights, DeepSeek/Mixtral-general: softmax over ALL
    experts (fp32), take the top-k scores, optionally renormalize
    (norm_topk — Mixtral/Qwen3MoE semantics; equals softmax over the
    top-k logits), scaled by moe_routed_scale (DeepSeek). Returns
    (weights [b,t,k] f32, topi [b,t,k])."""
    logits = jnp.einsum("bth,he->bte", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if config.moe_scoring == "sigmoid":
        # DeepSeek-V3/R1: sigmoid scores; SELECTION adds the learned
        # correction bias and applies node-limited group routing (top-2
        # sums per group pick topk_group groups); WEIGHTS are the
        # unbiased scores at the selected experts.
        b, t, e = logits.shape
        scores = jax.nn.sigmoid(logits)
        choice = scores  # no selection bias: the raw scores choose
        if "e_bias" in p:
            choice = scores + p["e_bias"].astype(jnp.float32)
        g = config.moe_n_group
        if g > 1:
            grouped = choice.reshape(b, t, g, e // g)
            group_scores = jnp.sum(
                jax.lax.top_k(grouped, 2)[0], axis=-1)  # [b, t, g]
            _, gidx = jax.lax.top_k(group_scores, config.moe_topk_group)
            gmask = jnp.zeros((b, t, g), jnp.float32).at[
                jnp.arange(b)[:, None, None],
                jnp.arange(t)[None, :, None], gidx].set(1.0)
            choice = jnp.where(
                jnp.repeat(gmask, e // g, axis=-1) > 0, choice, 0.0)
        _, topi = jax.lax.top_k(choice, config.n_experts_active)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(scores, config.n_experts_active)
    if config.moe_norm_topk:
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True)
                       + config.moe_renorm_eps)
    return topv * config.moe_routed_scale, topi


def _shared_expert(x: jax.Array, p: dict) -> jax.Array:
    """Always-active shared-expert SwiGLU (DeepSeek n_shared_experts)."""
    gate = jnp.einsum("bth,hm->btm", x, p["s_gate"])
    up = jnp.einsum("bth,hm->btm", x, p["s_up"])
    return jnp.einsum("btm,mh->bth", jax.nn.silu(gate) * up, p["s_down"])


def _moe_dense(x: jax.Array, p: dict, config: ModelConfig) -> jax.Array:
    """Oracle MoE: every expert computed for every token, weighted by the
    router's top-k mask. O(e) FLOPs per token — used only as the test
    reference for the dispatched path below."""
    b, t, _ = x.shape
    weights, topi = _routing_weights(x, p, config)
    mask = jnp.zeros((b, t, config.n_experts), jnp.float32).at[
        jnp.arange(b)[:, None, None],
        jnp.arange(t)[None, :, None],
        topi,
    ].set(weights)  # [b, t, e]
    gate = jnp.einsum("bth,ehm->betm", x, p["e_gate"])
    up = jnp.einsum("bth,ehm->betm", x, p["e_up"])
    expert_out = jnp.einsum("betm,emh->beth", jax.nn.silu(gate) * up,
                            p["e_down"])
    out = jnp.einsum("beth,bte->bth", expert_out, mask.astype(x.dtype))
    if "s_gate" in p:
        out = out + _shared_expert(x, p)
    return out


def _moe(x: jax.Array, p: dict, config: ModelConfig) -> jax.Array:
    """Expert-parallel MoE with static-shape capacity dispatch.

    The classic einsum dispatch/combine formulation (Mesh-TF/Switch style —
    compiler-friendly: no dynamic shapes, no sorting): each token picks its
    top-k experts, gets a slot in a fixed-capacity per-expert buffer via a
    cumulative-sum position, and overflow tokens are dropped for that
    expert. Expert-dim tensors shard over the `ep` mesh axis (experts axis
    of e_gate/e_up/e_down — parallel/shardings.LOGICAL_RULES), so the
    dispatch/combine einsums lower to all-to-alls over ICI. This replaces
    the reference's delegation to SGLang WideEP/DeepEP (SURVEY §2.5) with
    an XLA-native design.
    """
    b, t, h = x.shape
    e = config.n_experts
    k = config.n_experts_active
    # capacity: slots per expert for this chunk (static: t is a traced shape)
    cap = max(k, int(math.ceil(config.moe_capacity_factor * t * k / e)))

    weights, topi = _routing_weights(x, p, config)  # [b, t, k]

    sel = jax.nn.one_hot(topi, e, dtype=jnp.float32)  # [b, t, k, e]
    # Priority order: all tokens' 1st choice first, then 2nd choices, ...
    # (flatten as [k*t] so lower-k picks win capacity slots).
    sel_flat = sel.transpose(0, 2, 1, 3).reshape(b, k * t, e)
    pos = jnp.cumsum(sel_flat, axis=1) - sel_flat  # exclusive: slot index
    keep = sel_flat * (pos < cap)
    slot = jax.nn.one_hot(pos, cap, dtype=jnp.float32)  # [b, k*t, e, cap]
    dispatch_f = keep[..., None] * slot  # [b, k*t, e, cap]
    dispatch = (
        dispatch_f.reshape(b, k, t, e, cap).transpose(0, 2, 1, 3, 4)
    )  # [b, t, k, e, cap]
    combine = jnp.einsum("btkec,btk->btec", dispatch, weights)
    dispatch_btec = dispatch.sum(axis=2).astype(x.dtype)  # [b, t, e, cap]

    xe = jnp.einsum("btec,bth->ebch", dispatch_btec, x)  # [e, b, cap, h]
    gate = jnp.einsum("ebch,ehm->ebcm", xe, p["e_gate"])
    up = jnp.einsum("ebch,ehm->ebcm", xe, p["e_up"])
    out_e = jnp.einsum("ebcm,emh->ebch", jax.nn.silu(gate) * up, p["e_down"])
    out = jnp.einsum("btec,ebch->bth", combine.astype(x.dtype), out_e)
    if "s_gate" in p:
        out = out + _shared_expert(x, p)
    return out


# ---------------------------------------------------------------------------
# Multi-LoRA (batched adapter slots, static shapes)
# ---------------------------------------------------------------------------

# Projections a LoRA adapter may target (dense path; expert weights and the
# MLA latent projections are out of scope, matching common adapter training).
LORA_TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def lora_target_dims(config: ModelConfig) -> dict[str, tuple[int, int]]:
    """(din, dout) per supported adapter target for this model family.
    Targets absent here are UNSUPPORTED for the family and are rejected at
    load time (never silently dropped): MLA has no dense wk/wv (K/V come
    from the shared latent path), and MoE layers have no dense MLP."""
    h, hd = config.hidden, config.head_dim
    qh, kh, m = config.n_q_heads, config.n_kv_heads, config.mlp_hidden
    if config.is_mla:
        dims = {
            "wo": (qh * config.mla_v_head_dim, h),
        }
        if not config.mla_q_lora_rank:
            # q-lora models (V3/R1) have no dense wq to adapt
            dims["wq"] = (h, qh * (config.mla_nope_head_dim
                                   + config.mla_rope_head_dim))
    else:
        dims = {
            "wq": (h, qh * hd),
            "wk": (h, kh * hd),
            "wv": (h, kh * hd),
            "wo": (qh * hd, h),
        }
    if not config.n_experts:
        dims.update({"w_gate": (h, m), "w_up": (h, m), "w_down": (m, h)})
    return dims


def init_lora_pack(config: ModelConfig, max_loras: int, rank: int) -> dict:
    """Zero-initialized stacked adapter slots: for every layer and target,
    a: [S, in, r] and b: [S, r, out] with S = max_loras + 1. Slot 0 is
    permanently zero (the base model), so requests without an adapter run
    through the same compiled step with lora_idx=0 — one XLA program for
    any adapter mix in the batch (the TPU answer to the reference's
    delegation of multi-LoRA batching to vLLM/Punica, ref: lib/llm/src/
    lora.rs + vllm handlers LoRA endpoints).

    alpha/rank scaling is baked into `b` at load time so the forward pass
    is just two small matmuls per target."""
    dtype = jnp.dtype(config.dtype)
    s = max_loras + 1
    dims = lora_target_dims(config)
    layer = {
        t: {
            "a": jnp.zeros((s, din, rank), dtype),
            "b": jnp.zeros((s, rank, dout), dtype),
        }
        for t, (din, dout) in dims.items()
    }
    return {"layers": [jax.tree.map(lambda x: x, layer)
                       for _ in range(config.n_layers)]}


def _lora_delta(x: jax.Array, entry: dict, idx: jax.Array) -> jax.Array:
    """x: [B, T, din]; entry: {a: [S, din, r], b: [S, r, dout]};
    idx: [B] int32 slot per sequence. Returns [B, T, dout]."""
    a = entry["a"][idx]  # [B, din, r]
    b = entry["b"][idx]  # [B, r, dout]
    low = jnp.einsum("bti,bir->btr", x, a)
    return jnp.einsum("btr,bro->bto", low, b)


# ---------------------------------------------------------------------------
# Paged KV write + attention (XLA reference path; Pallas kernel in ops/)
# ---------------------------------------------------------------------------


def _kv_parts(kv_cache):
    """(values, scales) for either cache form: plain array (scales=None)
    or the int8 (values, scales) pair."""
    if isinstance(kv_cache, tuple):
        return kv_cache
    return kv_cache, None


def write_kv_pages(
    kv_cache,  # [L, 2, P, ps, kh, hd] or int8 (values, scales) pair
    layer: int,
    k: jax.Array,  # [B, T, kh, hd]
    v: jax.Array,
    block_tables: jax.Array,  # [B, max_pages] int32
    positions: jax.Array,  # [B, T] int32 (absolute positions)
    valid: jax.Array,  # [B, T] bool
):
    values, scales = _kv_parts(kv_cache)
    page_size = values.shape[3]
    b, t = positions.shape
    page_of = positions // page_size  # logical page index per token
    page_idx = jnp.take_along_axis(
        block_tables, page_of.astype(jnp.int32), axis=1
    )  # [B, T] physical page ids
    offset = positions % page_size
    # Invalid (padding) tokens write to the reserved scratch page 0.
    page_idx = jnp.where(valid, page_idx, 0)
    flat_pages = page_idx.reshape(-1)
    flat_off = offset.reshape(-1)
    if scales is not None:
        kq, ks = quantize_kv(k)  # ks: [B, T, LANES] lane-broadcast
        vq, vs = quantize_kv(v)
        values = values.at[layer, 0, flat_pages, flat_off].set(
            kq.reshape(b * t, *kq.shape[2:]), mode="drop")
        values = values.at[layer, 1, flat_pages, flat_off].set(
            vq.reshape(b * t, *vq.shape[2:]), mode="drop")
        scales = scales.at[layer, 0, flat_pages, flat_off].set(
            ks.reshape(b * t, ks.shape[-1]), mode="drop")
        scales = scales.at[layer, 1, flat_pages, flat_off].set(
            vs.reshape(b * t, vs.shape[-1]), mode="drop")
        return values, scales
    values = values.at[layer, 0, flat_pages, flat_off].set(
        k.reshape(b * t, *k.shape[2:]), mode="drop"
    )
    values = values.at[layer, 1, flat_pages, flat_off].set(
        v.reshape(b * t, *v.shape[2:]), mode="drop"
    )
    return values


def _scaled(scores, hd: int, sm_scale: Optional[float]):
    """Attention scores at 1/sqrt(hd), or at the scale a model states."""
    return scores / math.sqrt(hd) if sm_scale is None else scores * sm_scale


def paged_attention_xla(
    q: jax.Array,  # [B, T, qh, hd]
    kv_cache: jax.Array,  # [L, 2, P, ps, kh, hd]
    layer: int,
    block_tables: jax.Array,  # [B, max_pages]
    positions: jax.Array,  # [B, T] absolute query positions
    kv_lens: jax.Array,  # [B] total kv tokens visible (incl. this chunk)
    window: int = 0,
    kv_offset: Optional[jax.Array] = None,
    flat_gather: bool = False,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Reference paged attention: gather the sequence's pages, run masked
    SDPA. Correct everywhere (CPU tests, fallback, the kernels' oracle);
    on a TPU the Pallas kernels (ops/paged_attention.py) replace it for
    decode and, behind `attention_fn`, for prefill launches: its scores
    are a float32 [B, T, heads, table tokens] in HBM.

    `window` > 0 is the mask's lower edge: a query sees the keys with
    q_pos - window < kv_pos <= q_pos. Table and positions may be a page
    group's own (column 0 = the group's first held block, positions
    counted from that block's first token): the mask is the same in
    either frame. `kv_offset` [B] is the position of the table's first
    column where the caller hands over a slice of a row's table
    (`models/hybrid.prefill_attention`: only the pages a block of
    queries can see). `flat_gather` reads the pages out of the pool as
    one array of all its layers' pages: inside a loop or a branch
    `values[layer, 0]` is a copy of the layer's whole pool before the
    gather (1.07 GB a full layer of 32,768 pages: PERF.md, PR 36).
    `sm_scale`: the score scale of a model that states one (None:
    1/sqrt(hd))."""
    values, scales = _kv_parts(kv_cache)
    b, t, qh, hd = q.shape
    ps = values.shape[3]
    # a pool that packs kv heads into whole lane tiles ([.., kh / 2,
    # 128] at head_dim 64: `ModelConfig.kv_heads_per_lane_tile`) holds a
    # token's [kh, hd] row-major as it is
    kh = values.shape[4] * values.shape[5] // hd
    max_pages = block_tables.shape[1]
    ctx = max_pages * ps
    # Gather pages: [B, max_pages, ps, kh, hd] -> [B, ctx, kh, hd]
    if flat_gather:
        k_pages = values[layer, 0, block_tables]
        v_pages = values[layer, 1, block_tables]
    else:
        k_pages = values[layer, 0][block_tables]
        v_pages = values[layer, 1][block_tables]
    k = k_pages.reshape(b, ctx, kh, hd)
    v = v_pages.reshape(b, ctx, kh, hd)
    if scales is not None:
        # [B, mp, ps, LANES] -> per-token scalar (lane 0; rows are
        # broadcast), shared across heads
        k_s = scales[layer, 0][block_tables].reshape(
            b, ctx, -1)[..., 0].astype(jnp.float32)
        v_s = scales[layer, 1][block_tables].reshape(
            b, ctx, -1)[..., 0].astype(jnp.float32)
        k = k.astype(jnp.float32) * k_s[..., None, None]
        v = v.astype(jnp.float32) * v_s[..., None, None]
    group = qh // kh
    qg = q.reshape(b, t, kh, group, hd)
    scores = _scaled(jnp.einsum("btkgh,bskh->btkgs", qg.astype(jnp.float32),
                                k.astype(jnp.float32)), hd, sm_scale)
    kv_pos = jnp.arange(ctx)[None, :]  # [1, ctx]
    if kv_offset is not None:
        kv_pos = kv_pos + kv_offset[:, None]  # [B, ctx]
    # causal: kv position must be < kv_len and <= query position
    mask = (kv_pos[:, None, :] <= positions[..., None]) & (
        kv_pos[:, None, :] < kv_lens[:, None, None]
    )  # [B, T, ctx]
    if window:
        mask = mask & (kv_pos[:, None, :] > positions[..., None] - window)
    scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("btkgs,bskh->btkgh", probs, v.astype(jnp.float32))
    return out.reshape(b, t, qh, hd).astype(q.dtype)


def paged_attention_decode_xla(
    q: jax.Array,  # [B, 1, qh, hd]
    kv_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,  # [B, max_pages]
    kv_lens: jax.Array,  # [B] kv length INCLUDING the current token
    k_cur: jax.Array,  # [B, 1, kh, hd] current token's K (not yet cached)
    v_cur: jax.Array,
    window: int = 0,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Decode attention over cached history PLUS the in-register current
    token. The current K/V never round-trips through the paged pool inside
    the step, so the (TPU-slow) cache scatter is deferred and batched once
    per step for ALL layers (write_kv_stack) instead of 2x per layer —
    scatters dominate small-batch decode latency otherwise. `window` > 0:
    only the last `window` positions, the current one among them, are
    seen (table and lengths in the page group's own frame)."""
    values, scales = _kv_parts(kv_cache)
    b, _, qh, hd = q.shape
    ps = values.shape[3]
    kh = values.shape[4] * values.shape[5] // hd  # a packed pool's too
    max_pages = block_tables.shape[1]
    ctx = max_pages * ps
    k_pages = values[layer, 0][block_tables]
    v_pages = values[layer, 1][block_tables]
    k = k_pages.reshape(b, ctx, kh, hd)
    v = v_pages.reshape(b, ctx, kh, hd)
    if scales is not None:
        # [B, mp, ps, LANES] -> per-token scalar (lane 0; rows are
        # broadcast), shared across heads
        k_s = scales[layer, 0][block_tables].reshape(
            b, ctx, -1)[..., 0].astype(jnp.float32)
        v_s = scales[layer, 1][block_tables].reshape(
            b, ctx, -1)[..., 0].astype(jnp.float32)
        k = k.astype(jnp.float32) * k_s[..., None, None]
        v = v.astype(jnp.float32) * v_s[..., None, None]
    group = qh // kh
    qg = q.reshape(b, kh, group, hd)
    scores = _scaled(jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                                k.astype(jnp.float32)), hd, sm_scale)
    # History: positions 0 .. kv_len-2 (the current token is separate).
    kv_pos = jnp.arange(ctx)[None, :]
    mask = kv_pos < (kv_lens[:, None] - 1)
    if window:
        mask = mask & (kv_pos >= kv_lens[:, None] - window)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    cur = _scaled(jnp.einsum("bkgh,bkh->bkg",
                             qg.astype(jnp.float32),
                             k_cur[:, 0].astype(jnp.float32)), hd, sm_scale)
    full = jnp.concatenate([scores, cur[..., None]], axis=-1)
    probs = jax.nn.softmax(full, axis=-1)
    out = (
        jnp.einsum("bkgs,bskh->bkgh", probs[..., :-1],
                   v.astype(jnp.float32))
        + probs[..., -1][..., None]
        * v_cur[:, 0].astype(jnp.float32)[:, :, None, :]
    )
    return out.reshape(b, 1, qh, hd).astype(q.dtype)


def forward_decode(
    params: dict,
    config: ModelConfig,
    tokens: jax.Array,  # [B]
    positions: jax.Array,  # [B] position of the current token
    kv_cache: jax.Array,
    block_tables: jax.Array,
    kv_lens: jax.Array,  # [B] length INCLUDING the current token
    active: jax.Array,  # [B] bool
    lora: Optional[dict] = None,
    lora_idx: Optional[jax.Array] = None,
    decode_attention_fn=None,  # (q, kv, layer, tables, lens, k, v) -> attn
) -> tuple[jax.Array, jax.Array]:
    """Single-token decode with DEFERRED cache writes: every layer attends
    over (cache history + current-token K/V in registers); the paged pool
    is updated once at the end for all layers in two batched scatters.
    Standard-attention models only (MLA keeps the unified path — its
    latent cache is one stack already). `decode_attention_fn` overrides the
    XLA history attention (the Pallas flash-decode kernel on TPU: the XLA
    page gather lowers ~10x off the bandwidth roofline there)."""
    assert not config.is_mla
    b = tokens.shape[0]
    pos2 = positions[:, None]
    attn_fn = decode_attention_fn or paged_attention_decode_xla
    # A slot that is not active (finished, or waiting for its prefill)
    # keeps its last sequence's length and table; its row's result is
    # thrown away, so the attention call sees it as an empty history and
    # streams nothing for it.
    attn_lens = jnp.where(active, kv_lens, 0)
    x = params["embed"][tokens][:, None, :]  # [B, 1, H]
    ks, vs = [], []
    for layer_idx, lp in enumerate(params["layers"]):
        ll = lora["layers"][layer_idx] if lora is not None else {}
        h = rms_norm(x, lp["attn_norm"], config.rms_eps)
        q = _mm("bth,hqd->btqd", h, lp["wq"])
        k = _mm("bth,hkd->btkd", h, lp["wk"])
        v = _mm("bth,hkd->btkd", h, lp["wv"])
        if "wq" in ll:
            q = q + _lora_delta(h, ll["wq"], lora_idx).reshape(q.shape)
            k = k + _lora_delta(h, ll["wk"], lora_idx).reshape(k.shape)
            v = v + _lora_delta(h, ll["wv"], lora_idx).reshape(v.shape)
        if config.qk_norm:
            q = rms_norm(q, lp["q_norm"], config.rms_eps)
            k = rms_norm(k, lp["k_norm"], config.rms_eps)
        q = rope(q, pos2, config.rope_theta)
        k = rope(k, pos2, config.rope_theta)
        attn = attn_fn(
            q, kv_cache, layer_idx, block_tables, attn_lens, k, v)
        ks.append(k)
        vs.append(v)
        attn_out = _mm("btqd,qdh->bth", attn, lp["wo"])
        if "wo" in ll:
            attn_out = attn_out + _lora_delta(
                attn.reshape(b, 1, -1), ll["wo"], lora_idx)
        x = x + attn_out
        h = rms_norm(x, lp["mlp_norm"], config.rms_eps)
        if "router" in lp:  # per-layer: DeepSeek stacks mix dense + MoE
            x = x + _moe(h, lp, config)
        else:
            x = x + _swiglu(h, lp, ll if "w_gate" in ll else None, lora_idx)
    kv_cache = write_kv_stack(kv_cache, jnp.stack(ks), jnp.stack(vs),
                              block_tables, pos2, active[:, None])
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    logits = _mm("bth,hv->btv", x, head).astype(jnp.float32)
    return kv_cache, logits


def paged_attention_spec_xla(
    q: jax.Array,  # [B, T, qh, hd] — T chunk queries per sequence
    kv_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,  # [B, max_pages]
    kv_lens: jax.Array,  # [B] committed length INCLUDING chunk token 0
    k_cur: jax.Array,  # [B, T, kh, hd] chunk K (not yet cached)
    v_cur: jax.Array,
) -> jax.Array:
    """Speculative-verification attention, XLA reference path: every
    chunk query attends the cached history (positions < kv_len - 1)
    plus the in-register chunk tokens causally (token j <= query i).
    The T == 1 case degenerates to `paged_attention_decode_xla` — same
    concat-then-softmax shape, so masked positions contribute exact
    zeros and the two paths agree bitwise on the shared prefix."""
    values, scales = _kv_parts(kv_cache)
    b, t, qh, hd = q.shape
    ps = values.shape[3]
    kh = values.shape[4]
    max_pages = block_tables.shape[1]
    ctx = max_pages * ps
    k = values[layer, 0][block_tables].reshape(b, ctx, kh, hd)
    v = values[layer, 1][block_tables].reshape(b, ctx, kh, hd)
    if scales is not None:
        k_s = scales[layer, 0][block_tables].reshape(
            b, ctx, -1)[..., 0].astype(jnp.float32)
        v_s = scales[layer, 1][block_tables].reshape(
            b, ctx, -1)[..., 0].astype(jnp.float32)
        k = k.astype(jnp.float32) * k_s[..., None, None]
        v = v.astype(jnp.float32) * v_s[..., None, None]
    group = qh // kh
    qg = q.reshape(b, t, kh, group, hd)
    hist = jnp.einsum("btkgh,bskh->btkgs", qg.astype(jnp.float32),
                      k.astype(jnp.float32)) / math.sqrt(hd)
    # History: positions 0 .. kv_len-2; the chunk (token 0 at kv_len-1)
    # is in registers.
    kv_pos = jnp.arange(ctx)[None, :]
    hist_mask = kv_pos < (kv_lens[:, None] - 1)
    hist = jnp.where(hist_mask[:, None, None, None, :], hist, -1e30)
    cur = jnp.einsum("btkgh,bskh->btkgs", qg.astype(jnp.float32),
                     k_cur.astype(jnp.float32)) / math.sqrt(hd)
    causal = (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])  # [Tq, Tk]
    cur = jnp.where(causal[None, :, None, None, :], cur, -1e30)
    full = jnp.concatenate([hist, cur], axis=-1)
    probs = jax.nn.softmax(full, axis=-1)
    out = (
        jnp.einsum("btkgs,bskh->btkgh", probs[..., :ctx],
                   v.astype(jnp.float32))
        + jnp.einsum("btkgs,bskh->btkgh", probs[..., ctx:],
                     v_cur.astype(jnp.float32))
    )
    return out.reshape(b, t, qh, hd).astype(q.dtype)


def forward_spec(
    params: dict,
    config: ModelConfig,
    tokens: jax.Array,  # [B, T] chunk token 0 = last committed token
    positions: jax.Array,  # [B, T] absolute positions
    kv_cache: jax.Array,
    block_tables: jax.Array,
    kv_lens: jax.Array,  # [B] committed length INCLUDING chunk token 0
    active: jax.Array,  # [B] bool
    lora: Optional[dict] = None,
    lora_idx: Optional[jax.Array] = None,
    spec_attention_fn=None,  # (q, kv, layer, tables, lens, k, v) -> attn
) -> tuple[jax.Array, jax.Array]:
    """Speculative batched verification: `forward_decode` generalized to
    T tokens per slot — one weight-streaming pass scores all T candidate
    positions (decode is memory-bound, so the extra FLOPs are nearly
    free). Deferred cache writes exactly like decode: chunk K/V stay in
    registers through the layer loop and land in two batched scatters at
    the end; rejected positions leave stale KV past the committed length
    that the next step's chunk rewrites before it can ever be attended.
    Standard-attention models only (MLA/gpt-oss keep per-token paths)."""
    assert not config.is_mla
    b, t = tokens.shape
    attn_fn = spec_attention_fn or paged_attention_spec_xla
    attn_lens = jnp.where(active, kv_lens, 0)  # as in forward_decode
    x = params["embed"][tokens]  # [B, T, H]
    ks, vs = [], []
    for layer_idx, lp in enumerate(params["layers"]):
        ll = lora["layers"][layer_idx] if lora is not None else {}
        h = rms_norm(x, lp["attn_norm"], config.rms_eps)
        q = _mm("bth,hqd->btqd", h, lp["wq"])
        k = _mm("bth,hkd->btkd", h, lp["wk"])
        v = _mm("bth,hkd->btkd", h, lp["wv"])
        if "wq" in ll:
            q = q + _lora_delta(h, ll["wq"], lora_idx).reshape(q.shape)
            k = k + _lora_delta(h, ll["wk"], lora_idx).reshape(k.shape)
            v = v + _lora_delta(h, ll["wv"], lora_idx).reshape(v.shape)
        if config.qk_norm:
            q = rms_norm(q, lp["q_norm"], config.rms_eps)
            k = rms_norm(k, lp["k_norm"], config.rms_eps)
        q = rope(q, positions, config.rope_theta)
        k = rope(k, positions, config.rope_theta)
        attn = attn_fn(
            q, kv_cache, layer_idx, block_tables, attn_lens, k, v)
        ks.append(k)
        vs.append(v)
        attn_out = _mm("btqd,qdh->bth", attn, lp["wo"])
        if "wo" in ll:
            attn_out = attn_out + _lora_delta(
                attn.reshape(b, t, -1), ll["wo"], lora_idx)
        x = x + attn_out
        h = rms_norm(x, lp["mlp_norm"], config.rms_eps)
        if "router" in lp:
            x = x + _moe(h, lp, config)
        else:
            x = x + _swiglu(h, lp, ll if "w_gate" in ll else None, lora_idx)
    valid = jnp.broadcast_to(active[:, None], positions.shape)
    kv_cache = write_kv_stack(kv_cache, jnp.stack(ks), jnp.stack(vs),
                              block_tables, positions, valid)
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    logits = _mm("bth,hv->btv", x, head).astype(jnp.float32)
    return kv_cache, logits


def write_latent_pages(
    kv_cache: jax.Array,  # [L, 1, P, ps, 1, dc+rhd]
    layer: int,
    latent: jax.Array,  # [B, T, dc+rhd] c_kv ++ k_rope per token
    block_tables: jax.Array,
    positions: jax.Array,
    valid: jax.Array,
) -> jax.Array:
    """MLA cache write: one compressed latent row per token."""
    page_size = kv_cache.shape[3]
    b, t = positions.shape
    page_of = positions // page_size
    page_idx = jnp.take_along_axis(block_tables, page_of.astype(jnp.int32),
                                   axis=1)
    page_idx = jnp.where(valid, page_idx, 0)
    flat_pages = page_idx.reshape(-1)
    flat_off = (positions % page_size).reshape(-1)
    return kv_cache.at[layer, 0, flat_pages, flat_off, 0].set(
        latent.reshape(b * t, -1), mode="drop"
    )


def _mla_attention_block(
    x: jax.Array,  # [B, T, H] (already attn-normed)
    lp: dict,
    config: ModelConfig,
    kv_cache: jax.Array,
    layer_idx: int,
    block_tables: jax.Array,
    positions: jax.Array,
    kv_lens: jax.Array,
    valid: jax.Array,
    q_extra: Optional[jax.Array] = None,  # [B, T, qh*(nhd+rhd)] LoRA delta
) -> tuple[jax.Array, jax.Array]:
    """MLA with weight absorption (the efficient decode form): queries are
    projected into latent space (q_nope @ W_uk) so scores and context are
    computed directly against the compressed cache — no per-head K/V is
    ever materialized for past tokens. Per-token cache cost is
    latent_rank+rope_dim (e.g. 576 vs 2*kh*hd=6144 for DeepSeek-class) —
    the long-context memory win that motivates MLA.

    Returns (new_kv_cache, attn_out [B, T, qh, v_hd]).
    """
    b, t, _ = x.shape
    nhd, rhd = config.mla_nope_head_dim, config.mla_rope_head_dim
    dc = config.mla_kv_lora_rank
    scale = 1.0 / math.sqrt(config.mla_qk_head_dim)

    if "w_dq" in lp:
        # V3/R1-class query low-rank path: rms(x @ w_dq) @ w_uq
        q_lat = rms_norm(jnp.einsum("bth,hr->btr", x, lp["w_dq"]),
                         lp["q_a_norm"], config.rms_eps)
        q = jnp.einsum("btr,rqd->btqd", q_lat, lp["w_uq"])
    else:
        q = jnp.einsum("bth,hqd->btqd", x, lp["wq"])  # [B,T,qh,nhd+rhd]
    if q_extra is not None:
        q = q + q_extra.reshape(q.shape)
    q_nope, q_rope = q[..., :nhd], q[..., nhd:]
    q_rope = rope(q_rope, positions, config.rope_theta)

    c_kv = rms_norm(jnp.einsum("bth,hd->btd", x, lp["w_dkv"]),
                    lp["kv_norm"], config.rms_eps)  # [B,T,dc]
    k_rope = rope(jnp.einsum("bth,hr->btr", x, lp["w_kr"])[:, :, None, :],
                  positions, config.rope_theta)[:, :, 0, :]  # [B,T,rhd]

    latent = jnp.concatenate([c_kv, k_rope], axis=-1)
    kv_cache = write_latent_pages(kv_cache, layer_idx, latent, block_tables,
                                  positions, valid)

    # absorb W_uk: queries into latent space
    q_lat = jnp.einsum("btqn,dqn->btqd", q_nope, lp["w_uk"])  # [B,T,qh,dc]

    # gather latent pages: [B, ctx, dc+rhd]
    ps = kv_cache.shape[3]
    ctx = block_tables.shape[1] * ps
    pages = kv_cache[layer_idx, 0][block_tables][..., 0, :]
    lat_ctx = pages.reshape(b, ctx, dc + rhd)
    ckv_ctx, kr_ctx = lat_ctx[..., :dc], lat_ctx[..., dc:]

    scores = (
        jnp.einsum("btqd,bsd->btqs", q_lat.astype(jnp.float32),
                   ckv_ctx.astype(jnp.float32))
        + jnp.einsum("btqr,bsr->btqs", q_rope.astype(jnp.float32),
                     kr_ctx.astype(jnp.float32))
    ) * scale
    kv_pos = jnp.arange(ctx)[None, :]
    mask = (kv_pos[:, None, :] <= positions[..., None]) & (
        kv_pos[:, None, :] < kv_lens[:, None, None]
    )
    scores = jnp.where(mask[:, :, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx_lat = jnp.einsum("btqs,bsd->btqd", probs,
                         ckv_ctx.astype(jnp.float32))  # [B,T,qh,dc]
    attn = jnp.einsum("btqd,dqv->btqv", ctx_lat.astype(x.dtype), lp["w_uv"])
    return kv_cache, attn


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def forward_ring(
    params: dict,
    config: ModelConfig,
    tokens: jax.Array,  # [B, T] — T sharded over sp by the caller's jit
    positions: jax.Array,  # [B, T] global positions
    valid: jax.Array,  # [B, T]
    ring_attention_fn,  # (q, k, v, q_pos, k_pos, k_valid) -> attn out
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sequence-parallel long-context prefill: attention over the chunk
    itself via ring attention (ops/ring_attention.py) — no paged-cache read,
    no [T, T] materialization, sequence sharded over the sp mesh axis.

    Returns (logits [B, T, vocab], k_stack [L, B, T, kh, hd], v_stack) —
    the caller scatters the K/V stacks into the paged pool (write_kv_stack)
    so decode continues on the standard paged path. This is the long-context
    mechanism the reference lacks natively (SURVEY §5.7: it leans on KVBM
    tiering + chunked prefill; owning the model lets us shard the sequence).
    """
    assert not config.is_mla, (
        "ring prefill currently targets GQA models; MLA long prefill uses "
        "the chunked path (its latent cache is already ~10x smaller)")
    x = params["embed"][tokens]
    ks, vs = [], []
    for lp in params["layers"]:
        h = rms_norm(x, lp["attn_norm"], config.rms_eps)
        q = _mm("bth,hqd->btqd", h, lp["wq"])
        k = _mm("bth,hkd->btkd", h, lp["wk"])
        v = _mm("bth,hkd->btkd", h, lp["wv"])
        if config.qk_norm:
            q = rms_norm(q, lp["q_norm"], config.rms_eps)
            k = rms_norm(k, lp["k_norm"], config.rms_eps)
        q = rope(q, positions, config.rope_theta)
        k = rope(k, positions, config.rope_theta)
        attn = ring_attention_fn(q, k, v, positions, positions, valid)
        ks.append(k)
        vs.append(v)
        x = x + _mm("btqd,qdh->bth", attn, lp["wo"])
        h = rms_norm(x, lp["mlp_norm"], config.rms_eps)
        if "router" in lp:  # per-layer: DeepSeek stacks mix dense + MoE
            x = x + _moe(h, lp, config)
        else:
            x = x + _swiglu(h, lp)
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    logits = _mm("bth,hv->btv", x, head).astype(jnp.float32)
    return logits, jnp.stack(ks), jnp.stack(vs)


def stack_layer_params(layers: list[dict]) -> dict:
    """Stack a list of UNIFORM layer dicts into one pytree with a leading
    layer axis (pipeline stages scan over it; the stack shards over pp)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def _dense_layer_step(x: jax.Array, lp: dict, config: ModelConfig,
                      positions: jax.Array, mask: jax.Array,
                      axis_tp: Optional[str] = None):
    """One dense-GQA layer with in-chunk causal attention (prefill; no
    paged-cache read). Returns (x, (k, v)). Uniform across layers so
    pipeline stages can lax.scan over a stacked layer pytree.

    With `axis_tp` set (inside shard_map), lp's head/mlp dims are LOCAL
    shards: attention runs on local heads and the two residual
    projections psum over tp — the manual form of the tp sharding pjit
    inserts on the non-PP path."""
    b, t, _ = x.shape
    kh_local = lp["wk"].shape[1]
    group = config.n_q_heads // config.n_kv_heads
    h = rms_norm(x, lp["attn_norm"], config.rms_eps)
    q = _mm("bth,hqd->btqd", h, lp["wq"])
    k = _mm("bth,hkd->btkd", h, lp["wk"])
    v = _mm("bth,hkd->btkd", h, lp["wv"])
    if config.qk_norm:
        q = rms_norm(q, lp["q_norm"], config.rms_eps)
        k = rms_norm(k, lp["k_norm"], config.rms_eps)
    q = rope(q, positions, config.rope_theta)
    k = rope(k, positions, config.rope_theta)
    qg = q.reshape(b, t, kh_local, group, config.head_dim)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) \
        * (1.0 / math.sqrt(config.head_dim))
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    weights = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bkgts,bskd->btkgd", weights,
                      v.astype(jnp.float32)).astype(q.dtype)
    attn = attn.reshape(b, t, kh_local * group, config.head_dim)
    attn_out = _mm("btqd,qdh->bth", attn, lp["wo"])
    if axis_tp:
        attn_out = jax.lax.psum(attn_out, axis_tp)
    x = x + attn_out
    hmid = rms_norm(x, lp["mlp_norm"], config.rms_eps)
    gate = jnp.einsum("bth,hm->btm", hmid, lp["w_gate"])
    up = jnp.einsum("bth,hm->btm", hmid, lp["w_up"])
    down = jnp.einsum("btm,mh->bth", jax.nn.silu(gate) * up, lp["w_down"])
    if axis_tp:
        down = jax.lax.psum(down, axis_tp)
    x = x + down
    return x, (k, v)


def make_pp_prefill(config: ModelConfig, mesh, n_micro: int):
    """Pipeline-parallel prefill over the `pp` mesh axis (GPipe schedule,
    ops/pipeline.py): layers split into pp stages, activations hop stages
    via collective permute, each stage keeps ITS layers' K/V locally —
    exactly the shard a layer-partitioned paged pool wants. Dense-GQA
    models (uniform layers; MoE/MLA keep tp/ep/dp).

    Layer weights shard over BOTH pp (layer axis, via the stacked pytree)
    and tp (head/mlp axes) inside one shard_map — stage hops ppermute over
    pp while the two residual projections psum over tp, so tp collectives
    stay on the fast inner links.

    Returns fn(params, tokens [M, mb, T], positions [M, mb, T],
               valid [M, mb, T]) -> (logits [M, mb, T, V],
               ks [L, M, mb, T, kh, hd] pp-sharded on L, vs ...).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..ops.pipeline import gpipe_prefill_loop
    from ..parallel.mesh import AXIS_PP, AXIS_TP

    assert not config.is_mla and not config.n_experts, (
        "pp prefill targets dense-GQA models")
    pp = mesh.shape.get(AXIS_PP, 1)
    tp = mesh.shape.get(AXIS_TP, 1)
    assert config.n_layers % pp == 0, (
        f"n_layers={config.n_layers} must divide by pp={pp}")
    assert config.n_kv_heads % tp == 0, (
        f"n_kv_heads={config.n_kv_heads} must divide by tp={tp}")
    # Always thread the tp axis: the weight specs shard over tp even at
    # size 1, which types every layer output tp-varying; psum/pmean over a
    # size-1 axis compiles to a no-op.
    axis_tp = AXIS_TP

    # Per-leaf shard specs for the stacked layer pytree: pp on the leading
    # layer axis everywhere; tp on head/mlp axes.
    _SPECS = {
        "attn_norm": P(AXIS_PP), "mlp_norm": P(AXIS_PP),
        "q_norm": P(AXIS_PP), "k_norm": P(AXIS_PP),
        "wq": P(AXIS_PP, None, AXIS_TP),
        "wk": P(AXIS_PP, None, AXIS_TP),
        "wv": P(AXIS_PP, None, AXIS_TP),
        "wo": P(AXIS_PP, AXIS_TP),
        "w_gate": P(AXIS_PP, None, AXIS_TP),
        "w_up": P(AXIS_PP, None, AXIS_TP),
        "w_down": P(AXIS_PP, AXIS_TP),
    }
    # Stacking copies the whole layer stack once; the memo holds a strong
    # reference to the source list, so BOTH the per-layer copy and the
    # stacked copy stay resident (plan HBM for 2x layer weights when using
    # PP, or build params in stacked form at load for dedicated PP
    # deployments). Holding the source keeps its id from being recycled —
    # a weight SWAP (replacing the list object) safely misses the cache.
    # In-place mutation of the list's element arrays is NOT supported:
    # always replace params["layers"] wholesale on weight updates.
    _stack_cache: dict = {"src": None, "stacked": None}

    def run(params, tokens, positions, valid):
        m, mb, t = tokens.shape
        assert m == n_micro, (
            f"built for n_micro={n_micro} microbatches, got {m} — the "
            "pipeline bubble fraction depends on it")
        # Embedding outside the pipeline (replicated table).
        x = params["embed"][tokens]  # [M, mb, T, H]
        causal = jnp.tril(jnp.ones((t, t), bool))
        if _stack_cache["src"] is not params["layers"]:
            _stack_cache["src"] = params["layers"]
            _stack_cache["stacked"] = stack_layer_params(params["layers"])
        stacked = _stack_cache["stacked"]

        def stage(stage_params, act):
            # act: [mb, T, H+2] float32 — hidden state with positions and
            # valid appended so per-microbatch metadata rides the pipeline
            # (f32 between stages: bf16 cannot represent positions > 256
            # exactly).
            hstate = act[..., : config.hidden].astype(
                jnp.dtype(config.dtype))
            pos = act[..., config.hidden]
            val = act[..., config.hidden + 1] > 0.5
            mask = causal[None] & val[:, None, :]

            def body(carry, lp):
                out, kv = _dense_layer_step(carry, lp, config,
                                            pos.astype(jnp.int32), mask,
                                            axis_tp=axis_tp)
                return out, kv

            hstate, (ks, vs) = jax.lax.scan(body, hstate, stage_params)
            out = jnp.concatenate(
                [hstate.astype(jnp.float32), pos[..., None],
                 val[..., None].astype(jnp.float32)], axis=-1)
            return out, (ks, vs)

        # Pack per-microbatch positions/valid alongside the hidden state so
        # they travel with the activation through ppermute.
        acts = jnp.concatenate(
            [x.astype(jnp.float32),
             positions[..., None].astype(jnp.float32),
             valid[..., None].astype(jnp.float32)], axis=-1)

        l_local = config.n_layers // pp
        kh_local = config.n_kv_heads // tp
        kv_shape = (l_local, mb, t, kh_local, config.head_dim)
        kv_dtype = jnp.dtype(config.dtype)

        def shard_body(stacked_local, acts_all):
            outs, ks, vs = gpipe_prefill_loop(
                stage, stacked_local, acts_all,
                kv_shapes=(kv_shape, kv_shape), kv_dtype=kv_dtype,
                axis_name=AXIS_PP,
                extra_varying=(AXIS_TP,))
            # outs is tp-REPLICATED numerically but tp-varying in the type
            # system; pmean collapses it (exact: x*tp/tp with power-of-two
            # tp).
            outs = jax.lax.pmean(outs, AXIS_TP)
            return outs, ks, vs

        stacked_specs = jax.tree_util.tree_map_with_path(
            lambda path, _: _SPECS[str(getattr(path[-1], "key", ""))],
            stacked)
        outs, ks, vs = shard_map(
            shard_body, mesh=mesh,
            in_specs=(stacked_specs, P()),
            out_specs=(P(), P(AXIS_PP, None, None, None, AXIS_TP),
                       P(AXIS_PP, None, None, None, AXIS_TP)),
        )(stacked, acts)
        # Back to model dtype before norm+head so logits match the dense
        # forward bit-for-bit in rounding behavior.
        hidden = outs[..., : config.hidden].astype(jnp.dtype(config.dtype))
        hidden = rms_norm(hidden, params["final_norm"], config.rms_eps)
        head = (params["embed"].T if config.tie_embeddings
                else params["lm_head"])
        logits = jnp.einsum("mbth,hv->mbtv", hidden, head).astype(
            jnp.float32)
        # ks/vs: [L_local * pp, M, mb, T, kh, hd] -> reorder to [L, ...]
        ks = ks.reshape(config.n_layers, m, mb, t, config.n_kv_heads,
                        config.head_dim)
        vs = vs.reshape(config.n_layers, m, mb, t, config.n_kv_heads,
                        config.head_dim)
        return logits, ks, vs

    return run


def _write_scale_rows(
    scales: jax.Array,  # [L, 2, P, ps, LANES]
    ks: jax.Array,  # [L, B, T, LANES]
    vs: jax.Array,
    flat_pages: jax.Array,  # [B*T]
    flat_off: jax.Array,  # [B*T], in [0, ps)
) -> jax.Array:
    """`scales.at[:, kv, flat_pages, flat_off].set(.., mode="drop")` for
    K and V, written as ONE scatter of whole rows of the array's flat
    `[L*2*P*ps, LANES]` view (a bitcast of the row-major array). Indexed
    on four dimensions, XLA's TPU layout assignment gives the scatter,
    and with it the donated pool the decode loop carries, the layout
    {4,0,3,2,1}; the decode kernel's operand must be row-major, so every
    step copied the whole scale array out and back (PERF.md, PR 37). A
    page the indexed form dropped (past the pool after numpy's wrap of a
    negative index) goes to the row past the end, which `drop` drops:
    a plain flat index would alias it into the next layer's rows."""
    n_layers, _, n_pages, page_size, lanes = scales.shape
    n_rows = n_layers * 2 * n_pages * page_size
    pages = jnp.where(flat_pages < 0, flat_pages + n_pages, flat_pages)
    plane = jnp.arange(n_layers * 2, dtype=jnp.int32)[:, None]
    rows = jnp.where(
        (pages >= 0) & (pages < n_pages),
        (plane * n_pages + pages) * page_size + flat_off, n_rows)
    new = jnp.stack([ks, vs], axis=1)  # [L, 2, B, T, LANES]: plane-major
    flat = scales.reshape(n_rows, lanes).at[rows.reshape(-1)].set(
        new.reshape(-1, lanes), mode="drop")
    return flat.reshape(scales.shape)


def write_kv_stack(
    kv_cache,  # [L, 2, P, ps, kh, hd] or int8 (values, scales) pair
    k_stack: jax.Array,  # [L, B, T, kh, hd]
    v_stack: jax.Array,
    block_tables: jax.Array,  # [B, max_pages]
    positions: jax.Array,  # [B, T]
    valid: jax.Array,  # [B, T]
):
    """Scatter every layer's K/V chunk into the paged pool in one shot
    (deferred decode writeback + ring-prefill writeback). A row whose
    `valid` is false goes to scratch page 0; a position past the table
    or a page outside the pool is dropped. An int8 pool's scales go in
    as rows of the array's flat view, which keeps the array row-major
    for the decode kernel (`_write_scale_rows`)."""
    values, scales = _kv_parts(kv_cache)
    n_layers, b, t = k_stack.shape[:3]
    page_size = values.shape[3]
    page_of = positions // page_size
    page_idx = jnp.take_along_axis(block_tables, page_of.astype(jnp.int32), axis=1)
    page_idx = jnp.where(valid, page_idx, 0)  # padding -> scratch page 0
    flat_pages = page_idx.reshape(-1)
    flat_off = (positions % page_size).reshape(-1)
    if scales is not None:
        kq, ks = quantize_kv(k_stack)  # ks: [L, B, T, LANES]
        vq, vs = quantize_kv(v_stack)
        values = values.at[:, 0, flat_pages, flat_off].set(
            kq.reshape(n_layers, b * t, *kq.shape[3:]), mode="drop")
        values = values.at[:, 1, flat_pages, flat_off].set(
            vq.reshape(n_layers, b * t, *vq.shape[3:]), mode="drop")
        return values, _write_scale_rows(scales, ks, vs, flat_pages,
                                         flat_off)
    values = values.at[:, 0, flat_pages, flat_off].set(
        k_stack.reshape(n_layers, b * t, *k_stack.shape[3:]), mode="drop"
    )
    values = values.at[:, 1, flat_pages, flat_off].set(
        v_stack.reshape(n_layers, b * t, *v_stack.shape[3:]), mode="drop"
    )
    return values


def forward_embed(
    params: dict,
    config: ModelConfig,
    tokens: jax.Array,  # [B, T]
    valid: jax.Array,  # [B, T] bool
) -> jax.Array:
    """Trunk-only forward for embedding requests: in-chunk causal attention
    (no KV cache touched), masked mean pooling over valid positions, L2
    normalization. Returns [B, H] float32 (ref surface: /v1/embeddings,
    lib/llm/src/http/service/openai.rs embeddings route — the reference
    delegates the encoder to its engines; here we own it)."""
    assert not config.is_mla, "embedding path supports standard-attention models"
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))
    causal = jnp.tril(jnp.ones((t, t), bool))
    mask = causal[None, :, :] & valid[:, None, :]  # [B, Tq, Tk]
    group = config.n_q_heads // config.n_kv_heads
    x = params["embed"][tokens]
    for lp in params["layers"]:
        h = rms_norm(x, lp["attn_norm"], config.rms_eps)
        q = _mm("bth,hqd->btqd", h, lp["wq"])
        k = _mm("bth,hkd->btkd", h, lp["wk"])
        v = _mm("bth,hkd->btkd", h, lp["wv"])
        if config.qk_norm:
            q = rms_norm(q, lp["q_norm"], config.rms_eps)
            k = rms_norm(k, lp["k_norm"], config.rms_eps)
        q = rope(q, positions, config.rope_theta)
        k = rope(k, positions, config.rope_theta)
        qg = q.reshape(b, t, config.n_kv_heads, group, config.head_dim)
        scores = jnp.einsum("btkgd,bskd->bkgts", qg, k) \
            * (1.0 / math.sqrt(config.head_dim))
        scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
        weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        attn = jnp.einsum("bkgts,bskd->btkgd", weights.astype(q.dtype), v)
        attn = attn.reshape(b, t, config.n_q_heads, config.head_dim)
        x = x + _mm("btqd,qdh->bth", attn, lp["wo"])
        h = rms_norm(x, lp["mlp_norm"], config.rms_eps)
        if "router" in lp:  # per-layer: DeepSeek stacks mix dense + MoE
            x = x + _moe(h, lp, config)
        else:
            x = x + _swiglu(h, lp)
    x = rms_norm(x, params["final_norm"], config.rms_eps).astype(jnp.float32)
    w = valid.astype(jnp.float32)[:, :, None]
    pooled = (x * w).sum(axis=1) / jnp.maximum(w.sum(axis=1), 1.0)
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


def forward(
    params: dict,
    config: ModelConfig,
    tokens: jax.Array,  # [B, T]
    positions: jax.Array,  # [B, T]
    kv_cache: jax.Array,
    block_tables: jax.Array,  # [B, max_pages]
    kv_lens: jax.Array,  # [B] kv length AFTER this chunk
    valid: Optional[jax.Array] = None,  # [B, T]
    attention_fn=None,
    lora: Optional[dict] = None,  # init_lora_pack() pytree
    lora_idx: Optional[jax.Array] = None,  # [B] adapter slot per sequence
    extra_embeds: Optional[jax.Array] = None,  # [B, T, H] spliced inputs
    extra_mask: Optional[jax.Array] = None,  # [B, T] True = use extra
) -> tuple[jax.Array, jax.Array]:
    """Unified chunk forward (prefill T>1 or decode T=1).

    `extra_embeds`/`extra_mask` splice non-text inputs (image-token
    embeddings from the vision encoder) over the token embedding at
    masked positions — the multimodal injection point (ref: the reference
    delegates this to its engines' multimodal runners).

    Returns (new_kv_cache, logits [B, T, vocab]).
    """
    # Which row blocks of the launch hold a real position, reduced once
    # here for every int4 projection below (None: the launch takes no map).
    _refuse_multipliers(config)
    rows = None
    if valid is None:
        valid = jnp.ones(tokens.shape, dtype=bool)
    else:
        from ..ops.q4_linear import live_row_blocks

        rows = live_row_blocks(valid)
    attention = attention_fn or paged_attention_xla
    b, t = tokens.shape
    x = params["embed"][tokens]  # [B, T, H]
    if extra_embeds is not None:
        x = jnp.where(extra_mask[:, :, None],
                      extra_embeds.astype(x.dtype), x)
    for layer_idx, lp in enumerate(params["layers"]):
        ll = lora["layers"][layer_idx] if lora is not None else {}
        h = rms_norm(x, lp["attn_norm"], config.rms_eps)
        if config.is_gptoss:
            kv_cache, attn = _gptoss_attention_block(
                h, lp, config, kv_cache, layer_idx, block_tables,
                positions, kv_lens, valid)
        elif config.is_mla:
            kv_cache, attn = _mla_attention_block(
                h, lp, config, kv_cache, layer_idx, block_tables,
                positions, kv_lens, valid,
                q_extra=(_lora_delta(h, ll["wq"], lora_idx)
                         if "wq" in ll else None),
            )
        else:
            q = _mm("bth,hqd->btqd", h, lp["wq"], rows)
            k = _mm("bth,hkd->btkd", h, lp["wk"], rows)
            v = _mm("bth,hkd->btkd", h, lp["wv"], rows)
            if "wq" in ll:
                q = q + _lora_delta(h, ll["wq"], lora_idx).reshape(q.shape)
                k = k + _lora_delta(h, ll["wk"], lora_idx).reshape(k.shape)
                v = v + _lora_delta(h, ll["wv"], lora_idx).reshape(v.shape)
            if config.qk_norm:
                q = rms_norm(q, lp["q_norm"], config.rms_eps)
                k = rms_norm(k, lp["k_norm"], config.rms_eps)
            q = rope(q, positions, config.rope_theta)
            k = rope(k, positions, config.rope_theta)
            kv_cache = write_kv_pages(kv_cache, layer_idx, k, v,
                                      block_tables, positions, valid)
            attn = attention(q, kv_cache, layer_idx, block_tables,
                             positions, kv_lens)
        attn_out = _mm("btqd,qdh->bth", attn, lp["wo"], rows)
        if "bo" in lp:
            attn_out = attn_out + lp["bo"]
        if "wo" in ll:
            attn_out = attn_out + _lora_delta(
                attn.reshape(b, t, -1), ll["wo"], lora_idx)
        x = x + attn_out
        h = rms_norm(x, lp["mlp_norm"], config.rms_eps)
        if config.is_gptoss:
            x = x + _moe_gptoss(h, lp, config)
        elif "router" in lp:  # per-layer: DeepSeek stacks mix dense + MoE
            x = x + _moe(h, lp, config)
        else:
            x = x + _swiglu(h, lp, ll if "w_gate" in ll else None, lora_idx,
                            rows)
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    logits = _mm("bth,hv->btv", x, head, rows).astype(jnp.float32)
    return kv_cache, logits


# ---------------------------------------------------------------------------
# The stack behind ModelRunner's step programs
# ---------------------------------------------------------------------------


class DenseSteps:
    """The dense decoder as `ModelRunner`'s step programs call every
    stack (a `layer_pattern` one: models/hybrid.HybridSteps): one
    signature, `cache` = (pools, state) in and out, `tables` a tuple with
    an entry a page group, and what the sampler needs back beside the
    experts' statistics. Here `pools` is `(kv,)` (an int8 pool's (values,
    scales) inside its entry), `state` is None, `slots` goes unread and
    the statistics are None: no leaf more than the forwards take.

    `kernels`: what the runner's mesh and backend run in each attention
    slot (prefill, prefill_latent, decode, decode_latent, spec; None for
    the XLA form).
    A caller's own `attention_fn` takes every path instead."""

    stats_size = 0  # no dropless experts
    lora = True  # `lora_target_dims` names this stack's adapter targets

    def __init__(self, config: ModelConfig, kernels: dict,
                 attention_fn=None) -> None:
        self.config = config
        user = attention_fn is not None
        # gpt-oss: sink + sliding-window attention lives in the unified
        # forward (the Pallas kernels don't model sinks), which ignores
        # attention_fn.
        own = user or config.is_gptoss
        self.attention_fn = attention_fn if own else kernels["prefill"]
        self.decode_attention_fn = None if own else kernels["decode"]
        self.spec_attention_fn = (None if own or config.is_mla
                                  else kernels["spec"])
        # page groups whose prefill layers run through `attention_fn`
        self.attention_groups = (() if config.is_mla or config.is_gptoss
                                 else ("full",))
        # Deferred-write decode (2 batched scatters per step for all layers
        # instead of 2 per layer) measured ~12x faster than the unified
        # path with the Pallas flash-decode kernel on v5e — it is the
        # default. A USER-SUPPLIED attention_fn still wins (tests inject
        # reference kernels); MLA keeps the unified path (its latent cache
        # is a single stack, so the scatter count is already minimal).
        self.fast_decode = not (config.is_mla or config.is_gptoss or user)
        # `forward_spec` covers what `forward_decode` does: MLA's latent
        # cache and gpt-oss's sink attention keep per-token paths, and
        # verification targets drawn from other attention semantics than
        # the injected kernel's would silently diverge from it.
        self.spec = self._spec if self.fast_decode else None

    def make_state(self, slots: int) -> None:
        return None

    def kernel_paths(self) -> dict:
        return {}

    def prefill(self, params, cache, tokens, positions, tables, kv_lens,
                valid, last_idx, slots=None, lora=None, lora_idx=None,
                extra_embeds=None):
        """A chunk a row: (cache, logits [B, V] of each row's `last_idx`,
        None). `extra_embeds` is handed in by a multimodal engine only."""
        ((kv,), state), (table,) = cache, tables
        kv, logits = forward(
            params, self.config, tokens, positions, kv, table, kv_lens,
            valid=valid, attention_fn=self.attention_fn, lora=lora,
            lora_idx=lora_idx, extra_embeds=extra_embeds,
            extra_mask=(None if extra_embeds is None
                        else tokens == self.config.image_token_id))
        last = jnp.take_along_axis(
            logits, last_idx[:, None, None], axis=1)[:, 0, :]
        return ((kv,), state), last, None

    def decode(self, params, cache, tokens, positions, tables, kv_lens,
               active, lora=None, lora_idx=None):
        """One token a slot: (cache, logits [B, 1, V], None)."""
        ((kv,), state), (table,) = cache, tables
        if self.fast_decode:
            kv, logits = forward_decode(
                params, self.config, tokens, positions, kv, table,
                kv_lens, active, lora=lora, lora_idx=lora_idx,
                decode_attention_fn=self.decode_attention_fn)
        else:
            kv, logits = forward(
                params, self.config, tokens[:, None], positions[:, None],
                kv, table, kv_lens, valid=active[:, None],
                attention_fn=self.attention_fn, lora=lora,
                lora_idx=lora_idx)
        return ((kv,), state), logits, None

    def _spec(self, params, cache, tokens, positions, tables, kv_lens,
              active, lora=None, lora_idx=None):
        """t candidate positions a slot: (cache, logits [B, t, V])."""
        ((kv,), state), (table,) = cache, tables
        kv, logits = forward_spec(
            params, self.config, tokens, positions, kv, table, kv_lens,
            active, lora=lora, lora_idx=lora_idx,
            spec_attention_fn=self.spec_attention_fn)
        return ((kv,), state), logits
