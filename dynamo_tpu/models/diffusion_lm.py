"""Masked-diffusion language model (LLaDA-class) — TPU-native.

The reference serves diffusion LLMs through sglang's dLLM engine
(ref: components/src/dynamo/sglang/main.py init_llm_diffusion +
server_args.dllm_algorithm — LLaDA-style algorithms). The TPU-native
equivalent generates a whole response block by iterative parallel
denoising instead of autoregressive decoding:

  1. the response region starts as [MASK] * gen_len behind the prompt;
  2. each of S denoise steps runs ONE bidirectional transformer pass
     over the full sequence (no causal mask, no KV cache — every step
     re-reads everything, which is exactly the regime where the MXU is
     happiest: big [B*T, H] matmuls, static shapes);
  3. confidence-scheduled unmasking (LLaDA/MaskGIT low-confidence
     remasking): after each pass the cumulative top
     `round(gen_len * (s+1)/S)` most-confident predictions become
     fixed; the rest return to [MASK] for the next step.

The whole S-step loop is ONE jit (lax.scan) — a single dispatch per
request regardless of step count, so the per-token dispatch cost that
shaped the AR serving loop doesn't apply here.

Weights reuse the dense-family param pytree (init_params /
checkpoint loaders): a LLaDA checkpoint IS a dense transformer trained
with a mask objective; only the attention mask and sampling loop
differ.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .config import ModelConfig, get_config
from .transformer import rms_norm, rope


def bidirectional_forward(params: dict, config: ModelConfig,
                          tokens: jax.Array,
                          positions: jax.Array = None,
                          valid: jax.Array = None) -> jax.Array:
    """[B, T] -> logits [B, T, V]: the dense-family layer stack with
    FULL (bidirectional) attention — the mask-predictor network of a
    masked-diffusion LM. Cited sites: same projections as
    transformer.forward's dense branch; no cache, no causal mask.

    `positions`/`valid` support PADDED prefixes (semi-autoregressive
    block continuation pads prompt+committed to a bucket): invalid key
    positions are masked out of every score row, and positions carry
    the true RoPE indices so padding gaps don't shift the block."""
    b, t = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    x = params["embed"][tokens]
    for lp in params["layers"]:
        h = rms_norm(x, lp["attn_norm"], config.rms_eps)
        q = jnp.einsum("bth,hqd->btqd", h, lp["wq"])
        k = jnp.einsum("bth,hkd->btkd", h, lp["wk"])
        v = jnp.einsum("bth,hkd->btkd", h, lp["wv"])
        if config.qk_norm:
            q = rms_norm(q, lp["q_norm"], config.rms_eps)
            k = rms_norm(k, lp["k_norm"], config.rms_eps)
        q = rope(q, positions, config.rope_theta)
        k = rope(k, positions, config.rope_theta)
        groups = config.n_q_heads // config.n_kv_heads
        qg = q.reshape(b, t, config.n_kv_heads, groups, config.head_dim)
        scores = jnp.einsum("btkgh,bskh->btkgs",
                            qg.astype(jnp.float32),
                            k.astype(jnp.float32))
        scores = scores / jnp.sqrt(float(config.head_dim))
        if valid is not None:
            scores = jnp.where(valid[:, None, None, None, :],
                               scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)  # FULL attention
        attn = jnp.einsum("btkgs,bskh->btkgh", probs,
                          v.astype(jnp.float32))
        attn = attn.reshape(b, t, config.n_q_heads,
                            config.head_dim).astype(x.dtype)
        x = x + jnp.einsum("btqd,qdh->bth", attn, lp["wo"])
        h = rms_norm(x, lp["mlp_norm"], config.rms_eps)
        gate = jnp.einsum("bth,hm->btm", h, lp["w_gate"])
        up = jnp.einsum("bth,hm->btm", h, lp["w_up"])
        x = x + jnp.einsum("btm,mh->bth", jax.nn.silu(gate) * up,
                           lp["w_down"])
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    head = (params["embed"].T if config.tie_embeddings
            else params["lm_head"])
    return jnp.einsum("bth,hv->btv", x, head).astype(jnp.float32)


def diffusion_generate(
    params: dict,
    config: ModelConfig,
    prompt: jax.Array,  # [B, Tp] int32
    gen_len: int,
    steps: int,
    mask_id: jax.Array,  # scalar int32
    temperature: jax.Array,  # scalar f32; 0 = greedy
    seed: jax.Array,  # scalar uint32
) -> jax.Array:
    """-> [B, gen_len] denoised response tokens: the unpadded
    single-block case of diffusion_generate_block (all-valid prefix,
    contiguous positions)."""
    b, tp = prompt.shape
    return diffusion_generate_block(
        params, config, jnp.asarray(prompt, jnp.int32),
        jnp.ones((b, tp), bool), jnp.full((b,), tp, jnp.int32),
        gen_len, steps, mask_id, temperature, seed)


@partial(jax.jit, static_argnames=("config", "gen_len", "steps"))
def diffusion_generate_block(
    params: dict,
    config: ModelConfig,
    prefix: jax.Array,  # [B, Tp_pad] prompt + committed blocks, padded
    prefix_valid: jax.Array,  # [B, Tp_pad] bool
    prefix_len: jax.Array,  # [B] true prefix length (positions source)
    gen_len: int,
    steps: int,
    mask_id: jax.Array,
    temperature: jax.Array,
    seed: jax.Array,
) -> jax.Array:
    """Semi-autoregressive continuation (LLaDA's long-form mode): denoise
    ONE gen_len block conditioned on the padded prefix. The prefix pads
    to a bucket so jit specializations stay finite as committed blocks
    grow; padding is masked out of attention and RoPE positions skip it,
    so the result equals an unpadded run."""
    b, tp = prefix.shape
    gen0 = jnp.full((b, gen_len), mask_id, jnp.int32)
    x0 = jnp.concatenate([prefix.astype(jnp.int32), gen0], axis=1)
    prefix_pos = jnp.broadcast_to(jnp.arange(tp)[None, :], (b, tp))
    gen_pos = prefix_len[:, None] + jnp.arange(gen_len)[None, :]
    positions = jnp.concatenate([prefix_pos, gen_pos], axis=1)
    valid = jnp.concatenate(
        [prefix_valid, jnp.ones((b, gen_len), bool)], axis=1)
    base_key = jax.random.PRNGKey(seed)

    def step(carry, s):
        x, fixed = carry
        logits = bidirectional_forward(params, config, x,
                                       positions=positions, valid=valid)
        gen_logits = logits[:, tp:, :]
        # [MASK] is a sentinel, never a committable token: an argmax
        # that lands on it would freeze the mask into the output when
        # the position is kept, so the id is barred from prediction.
        vocab_ids = jnp.arange(gen_logits.shape[-1])
        gen_logits = jnp.where(vocab_ids[None, None, :] == mask_id,
                               -jnp.inf, gen_logits)
        key = jax.random.fold_in(base_key, s)
        gumbel = jax.random.gumbel(key, gen_logits.shape,
                                   dtype=jnp.float32)
        noisy = gen_logits + jnp.where(temperature > 0,
                                       gumbel * temperature, 0.0)
        pred = jnp.argmax(noisy, axis=-1).astype(jnp.int32)
        logp = jax.nn.log_softmax(gen_logits, axis=-1)
        conf = jnp.take_along_axis(logp, pred[..., None],
                                   axis=-1)[..., 0]
        conf = jnp.where(fixed, jnp.inf, conf)
        n_keep = jnp.round(gen_len * (s + 1).astype(jnp.float32)
                           / steps).astype(jnp.int32)
        order = jnp.argsort(-conf, axis=-1)
        rank = jnp.argsort(order, axis=-1)
        keep = rank < n_keep
        gen_tokens = jnp.where(fixed, x[:, tp:],
                               jnp.where(keep, pred, mask_id))
        return (jnp.concatenate([x[:, :tp], gen_tokens], axis=1),
                fixed | keep), None

    (x_final, _), _ = jax.lax.scan(
        step, (x0, jnp.zeros((b, gen_len), bool)), jnp.arange(steps))
    return x_final[:, tp:]


DLM_PRESETS = {
    # Test-scale masked-diffusion LM: the tiny dense config with the
    # last vocab id reserved as [MASK].
    "tiny-dlm-test": "tiny-test",
}


def get_dlm_config(preset: str) -> tuple[ModelConfig, int]:
    """(backbone config, mask_token_id)."""
    base = DLM_PRESETS.get(preset, preset)
    config = get_config(base)
    return config, config.vocab_size - 1
