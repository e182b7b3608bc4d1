"""Hybrid decoder: ONE mixer a layer, its kind read off
`ModelConfig.layer_pattern` (nemotron_h's Mamba-2 / attention / relu2
experts; mellum's window and full attention over SwiGLU experts, a
published block being two mixers; pangu_ultra_moe's latent attention
over a dense SwiGLU or routed experts with a shared one, every branch
normed again before it is added; granitemoehybrid's Mamba-2 or attention
mixer over SwiGLU experts with a shared one, a published block being
two mixers, with the family's four multipliers and a tied head;
lfm2_moe's gated short convolution or attention with normed q and k over
a dense SwiGLU or sigmoid-routed SwiGLU experts, a tied head;
cohere2_moe's PARALLEL block: window or full attention and sigmoid-routed
SwiGLU experts beside averaged shared ones, both off ONE LayerNorm and
added together, rope on lane pairs on the window layers and no
positional term on the full ones, a tied head).

    x <- x + Mixer_l(RMSNorm(x; w_l))          after the last: RMSNorm, head
    x <- x + RMSNorm(Mixer_l(RMSNorm(x; w_l)); post_l)    with `sandwich_norm`
    h = Norm(x; w_b); x <- x + sum of Mixer_l(h) over the block's mixers
                                                         with `parallel_block`

Norm is RMSNorm, or with `norm_kind` "layer" a LayerNorm (`layer_norm`:
the mean taken off first, float32, a weight and no bias), the final norm
too.

With granitemoehybrid's multipliers (each a `ModelConfig` field at its
default for every other family, where nothing of it is traced):

    x_0 = embedding_multiplier * Emb[token]
    x <- x + residual_multiplier * Mixer_l(RMSNorm(x; w_l))
    attention scores = attention_multiplier * q.k    (not 1/sqrt(head_dim))
    logits = RMSNorm(x) Head / logits_scaling

The head is `lm_head` [h, rows], or with `tie_embeddings` the embedding's
own [rows, h] array contracted over h (`_head`: no transposed copy).

  M  Mamba-2: [z | xBC | dt] = x W_in; xBC <- silu(conv1d_k4(xBC) + b);
     xBC -> x [H, P], B [G, N], C [G, N]; dt <- softplus(dt + dt_bias);
     S_t = exp(dt A) S_{t-1} + dt (x outer B); y = S C + D x;
     y <- GroupRMSNorm(y * silu(z)) (gate first, then norm); out = y W_out.
     One mixer a block (nemotron_h) or the first of two (granitemoehybrid:
     G = 1, the norm over all H x P lanes as one group)
  C  gated short convolution (lfm2_moe): [B | C | u] = x W_in, three
     thirds of `hidden`; v = B * u; c_t = sum_k w_k v_{t-(K-1)+k}, a
     depthwise causal conv of `conv_kernel` taps with no bias and NO
     activation (v before position 0 is zero); out = (C * c) W_out
  *  attention: grouped-query, causal softmax; with `qk_norm` q and k
     are RMS-normed per head (a learned gain of head_dim) BEFORE rope;
     rope where `use_rope` (YaRN where `rope_yarn_factor`), none for
     nemotron_h and granitemoehybrid, none on a kind `rope_kinds` leaves
     out (cohere2_moe's full layers); on lane pairs (2i, 2i+1) with
     `rope_interleaved`, else on (i, i + head_dim/2)
  W  the same over the last `sliding_window` positions, default rope:
     scores masked to q_pos - window < kv_pos <= q_pos
  E  routed experts: sigmoid scores, top-k of scores + bias (of the raw
     scores without `moe_selection_bias`), weights = the unbiased scores
     renormalised (over their sum + `moe_renorm_eps`) x scale (or a
     float32 softmax's top-k, renormalised);
     expert = W_down relu(W_up x)^2, or with `mlp_act` swiglu
     W_down (silu(W_gate x) * W_up x) from one fused [gate | up] matrix;
     a shared expert of the same form where the model has one (with
     `shared_expert_mean` its output x 1/`n_shared_experts`: the mean of
     that many experts stored as one matrix)
  D  a dense SwiGLU `mlp_hidden` wide, one fused [gate | up] matrix
  S  Mamba-1 (phi4flash): [u | z] = x W_in; u = silu(conv(u) + b); [dt_r |
     B | C] = u W_x; dt = softplus(dt_r W_dt + b_dt); s_t = exp(dt_t (x)
     A) * s_{t-1} + (dt_t u_t) (x) B_t with A [N, D], a decay a channel
     AND a column; y = s C + D u; out = (y * silu(z)) W_out. The LAST S
     before the first G hands its y, before the gate, to the G mixers
  G  a gated memory unit: (m * silu(x W_1)) W_2, m that y at the same
     position: no state, no cache
  X  cross-attention onto the keys and values the last `*` mixer before
     it wrote: a query projection alone, no pages of its own
     With `diff_attention` every attention kind is differential (the
     comment above `_diff_layout` has the form it runs in); with
     `layer_sections` a run of identical blocks is one `lax.scan`
  L  latent attention: c_q = RMSNorm(x W_dq), q = c_q W_uq -> heads x
     (nope | rope), q_rope roped; c_kv = RMSNorm(x W_dkv), k_r =
     RoPE(x W_kr), ONE rope key a token for all heads. A token's cache
     row is [c_kv | k_r | zeros to a lane tile]. Prefill does not
     absorb: k_h = [c_kv W_uk,h | k_r], v_h = c_kv W_uv,h are rebuilt
     from the cached rows a block of keys at a time (`latent_prefill`:
     in VMEM by the blocked kernel behind `ops/paged_attention.
     paged_attention_latent`, in HBM by `latent_prefill_attention`, the
     XLA form, the CPU path and the oracle).
     Decode absorbs: q~_h = q_nope,h W_uk,h^T, scores q~_h . c_kv +
     q_rope,h . k_r over the rows themselves, context sum_s p_s c_kv,s,
     then W_uv,h (`latent_decode`). Softmax scale 1/sqrt(nope + rope).

Kinds of cache side by side: KV pages for the attention layers only, a
page group each kind (`*`: cache layer j = the j-th `*` layer of the full
group, whose table a sequence fills from position 0; `W`: the same count
within the window group, whose table starts at the first block the
sequence still holds, positions and lengths counted from that block's
first token: engine/pages.py), and for each `M` and each `C` layer a
fixed-size state per scheduler slot: `conv` [slots, K-1, channels], the
K-1 conv inputs before the next position in the model dtype (an `M`
layer's xBC, conv_dim wide; a `C` layer's B * u, hidden wide) and, an
`M` layer only, `ssm` [slots, H, P, N] (float32): a `C` layer has no
other state.
Rules the scheduler and runner rely on:

  * a row that starts at position 0 starts from ZERO state, whatever the
    slot held: admission needs no separate reset;
  * padding of a prefill bucket and empty rows of a batched prefill give
    dt = 0 and stay out of the conv carry (of either kind): they advance
    nothing;
  * a decode step touches only active rows (a slot between two prefill
    chunks keeps its state);
  * prefill computes logits for each row's LAST valid position only.

The expert layer is dropless (ops/grouped_matmul.py) and is told which of
the published experts it holds (`config.experts_held`); tokens routed to
an absent expert get nothing from it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import dropless_experts
from ..ops.paged_attention import prefill_kernel_tiles
from ..ops.ssm import (
    LANES,
    causal_conv,
    expand_groups,
    scan_kernel_tiles,
    selective_scan,
    selective_state_update,
    ssm_chunk_scan,
    ssm_chunk_scan_kernel,
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
    write_latent_pages,
    yarn_rope_tables,
)

# A windowed model's prefill attention in XLA (`prefill_attention`: the
# CPU's path, the kernel's oracle at the model's shapes and the fallback
# for a page group whose geometry `prefill_kernel_tiles` refuses; on the
# chip both page groups run the blocked kernel behind `attention_fn`)
# scores at most PREFILL_Q_BLOCK query positions a row and
# PREFILL_SCORE_POSITIONS over all rows at a time: its full layers'
# table is as wide as the longest context served, and float32 scores
# [rows, T, heads, keys] of a whole 2048-token chunk over 8192 keys are
# 2.1 GB (PERF.md, fault 2). A full layer gathers the narrowest of
# FULL_TABLE_PAGES (and the whole table) that holds what a block of
# queries can see.
PREFILL_Q_BLOCK = 512
PREFILL_SCORE_POSITIONS = 2048
FULL_TABLE_PAGES = (64, 128, 256)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def hybrid_layer_axes(config: ModelConfig, layer_idx: int,
                      repeats: int = 1) -> dict:
    """Logical sharding axes of one layer (parallel.shardings). The Mamba
    and expert leaves are replicated: a sharded state cache and an expert
    exchange are not built (the worker refuses --tp/--sp for this family).
    `repeats` > 1: an entry of a rolled section
    (`ModelConfig.layer_entries`), every leaf stacked along a leading
    axis."""
    axes = _kind_axes(config, config.layer_kind(layer_idx))
    if not has_own_norm(config, layer_idx):
        del axes["norm"]
    elif config.norm_bias:
        axes["norm_b"] = ("embed",)
    if repeats > 1:
        axes = {name: (None, *ax) for name, ax in axes.items()}
    return axes


def has_own_norm(config: ModelConfig, layer_idx: int) -> bool:
    """Whether mixer `layer_idx` norms its own input: every mixer but
    the later ones of a parallel block, which read the first one's."""
    return (not config.parallel_block
            or layer_idx % config.mixers_per_layer == 0)


def _kind_axes(config: ModelConfig, kind: str) -> dict:
    if kind == "M":
        return {"norm": ("embed",), "in_proj": ("embed", None),
                "conv_w": (None, None), "conv_b": (None,),
                "dt_bias": (None,), "a_log": (None,), "d_skip": (None,),
                "ssm_norm": (None,), "out_proj": (None, "embed")}
    if kind == "S":  # replicated, as the state it carries
        return {"norm": ("embed",), "in_proj": ("embed", None),
                "conv_w": (None, None), "conv_b": (None,),
                "x_proj": (None, None), "dt_proj": (None, None),
                "dt_bias": (None,), "a_log": (None, None),
                "d_skip": (None,), "out_proj": (None, "embed")}
    if kind == "G":
        return {"norm": ("embed",), "g_in": ("embed", None),
                "g_out": (None, "embed")}
    if kind == "C":  # replicated, as the state it carries
        return {"norm": ("embed",), "in_proj": ("embed", None),
                "conv_w": (None, None), "out_proj": (None, "embed")}
    post = {"post_norm": ("embed",)} if config.sandwich_norm else {}
    if kind in "*WX":
        qk = ({"q_norm": ("head_dim",), "k_norm": ("head_dim",)}
              if config.qk_norm else {})
        axes = {"norm": ("embed",),
                "wq": ("embed", "q_heads", "head_dim"),
                "wk": ("embed", "kv_heads", "head_dim"),
                "wv": ("embed", "kv_heads", "head_dim"),
                "wo": ("q_heads", "head_dim", "embed"), **qk, **post}
        if config.attn_bias:
            axes.update({"bq": ("q_heads", "head_dim"),
                         "bk": ("kv_heads", "head_dim"),
                         "bv": ("kv_heads", "head_dim"), "bo": ("embed",)})
        if config.diff_attention:
            axes.update({name: (None,) for name in DIFF_PARAMS})
        if kind == "X":  # a query projection alone
            for name in ("wk", "wv", "bk", "bv"):
                axes.pop(name, None)
        return axes
    if kind == "L":  # replicated: the worker refuses --tp for this family
        return {"norm": ("embed",), "w_dq": ("embed", None),
                "q_norm": (None,), "w_uq": (None, None),
                "w_dkv": ("embed", None), "w_kr": ("embed", None),
                "kv_norm": (None,), "w_uk": (None, None, None),
                "w_uv": (None, None, None), "wo": (None, None, "embed"),
                **post}
    if kind == "D":
        return {"norm": ("embed",), "d_up": ("embed", None),
                "d_down": (None, "embed"), **post}
    axes = {"norm": ("embed",), "router": ("embed", None),
            "e_up": (None, None, "embed"), "e_down": (None, None, "embed"),
            **post}
    if _has_selection_bias(config):
        axes["e_bias"] = (None,)
    if _shared_width(config):
        axes.update({"s_up": ("embed", None), "s_down": (None, "embed")})
    return axes


def _has_selection_bias(config: ModelConfig) -> bool:
    return config.moe_scoring == "sigmoid" and config.moe_selection_bias


def _shared_width(config: ModelConfig) -> int:
    return (config.shared_expert_hidden
            or config.n_shared_experts * config.expert_mlp_hidden)


# Seeded recipe, tied head: a branch writes `BRANCH_GROWTH` times wider
# than the mixer before it (`branch_gain`).
BRANCH_GROWTH = 1.23
# The same for a stack with gated short convolutions, whose mixers are
# cubic in their input: the first branch is FIRST_JUMP times the
# embedding, every later one BRANCH_SHARE of the stream it joins, each
# kind's unit-gain spread taken out (`_conv_stack_gain`). And q and k
# that are normed per head are drawn NORMED_QK_GAIN times wider, which
# the norms take out again.
FIRST_JUMP, BRANCH_SHARE = 60.0, 0.25
KIND_SPREAD = {"C": 1.0, "D": 0.6, "E": 0.3, "*": 0.125}
# The same for a stack with Mamba-1 mixers (phi4flash: 64 mixers, every
# one gated or bilinear: u * silu(z), silu(g) * u, m * silu(.), a1 -
# lambda a2 under a norm): under the exponential recipe a feed-forward
# writes a branch as wide as the stream it joins, a relative error grows
# 1.6 times a block, and bf16 matmul inputs ANYWHERE in the first 18
# blocks leave no token in common with float32 at the published sizes
# (gap_mean 3.8 where a token at random reads 4.2: the chip and the
# reference with its inputs rounded agree on that; PERF.md, PR 52). The
# spreads are a unit-gain mixer's at the published widths (the float32
# reference over 256 positions: a Mamba-1 mixer 0.42, a SwiGLU 10,240
# wide 0.72, differential attention 0.34 to 0.45, a memory unit 0.41).
SELECTIVE_KIND_SPREAD = {"S": 0.42, "D": 0.72, "W": 0.4, "*": 0.34,
                         "G": 0.41, "X": 0.34}
NORMED_QK_GAIN = 2.0
# The same for a stack whose attention kinds differ by their positional
# term (`rope_kinds`) and whose norms take a mean off (`norm_kind`
# "layer"): wq and wk are drawn SHARP_QK_GAIN times wider (`score_gain`),
# and a matrix that writes into the residual stream adds STREAM_MEAN
# times its first output lane's column to every column
# (`init_hybrid_layer`), so that the stream carries a mean for the
# LayerNorm to take off.
SHARP_QK_GAIN = 1.5
STREAM_MEAN = 0.5
# The same for phi4flash's stack. Biases (`attn_bias`, `norm_bias`) are
# drawn BIAS_SPREAD wide beside projections and normed lanes of spread 1
# (an output bias beside its branch: x `branch_gain`); the differential
# sub-norm's gain 1 + SUBLN_SPREAD x normal; the four lambda vectors
# LAMBDA_SPREAD wide, so that exp(lq1 . lk1) - exp(lq2 . lk2) moves
# lambda by some 0.1 about `lambda_init`, as the published
# initialisation does. Zeros and ones (what a fresh model has) would let
# a program that drops a bias or the gain pass. With differential
# attention wq and wk take SHARP_QK_GAIN too: two softmaxes that weigh
# hundreds of keys alike read the same mean of the values, a1 - lambda
# a2 is (1 - lambda) times it, and the sub-norm takes lambda out again.
BIAS_SPREAD = 0.25
SUBLN_SPREAD = 0.25
LAMBDA_SPREAD = 0.1
DIFF_PARAMS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln")


def branch_gain(config: ModelConfig, layer_idx: int) -> float:
    """Seeded recipe: what a matrix that writes into the residual stream
    (out_proj, wo, every down-projection) of mixer `layer_idx` is
    multiplied by. 1 for an untied head. A TIED head reads the logits
    off the embedding, so whatever of Emb[token] is left in the last
    hidden state scores token itself: at cosine c between the two the
    self-logit is c sqrt(h) times the logits' spread, and a random
    model in which the embedding is a fifth of the stream (equal
    branches, twenty mixers) answers every token with itself. Trained
    models have streams that grow with depth; so has this one. The
    embedding enters with entries of spread s0 = embedding_multiplier x
    logits_scaling / sqrt(h) (`init_top_params` draws it logits_scaling /
    sqrt(h) wide); a Mamba branch of unit gain writes entries of spread
    about 1, so a gain of s0 / residual_multiplier adds what the stream
    holds, and BRANCH_GROWTH a mixer (1.51 a block) keeps it so: after
    20 mixers the embedding is under 1/60 of the state, the self-logit
    under the spread, while the first blocks see embedding and branches
    side by side, which is where the multipliers' ratio matters."""
    if not config.tie_embeddings:
        return 1.0
    s0 = (config.embedding_multiplier * config.logits_scaling
          / math.sqrt(config.hidden))
    if "C" in config.layer_pattern or "S" in config.layer_pattern:
        return _conv_stack_gain(config, layer_idx, s0)
    # never under 1: an embedding drawn narrower than a unit-gain branch
    # (no multipliers: s0 = 1 / sqrt(h)) is outweighed from the first
    # block on, and the growth alone takes its share under the spread
    return (max(s0 / config.residual_multiplier, 1.0)
            * BRANCH_GROWTH ** layer_idx)


def _conv_stack_gain(config: ModelConfig, layer_idx: int, s0: float):
    """`branch_gain` for a stack with gated short convolutions, and for
    one with Mamba-1 mixers (its own spreads: SELECTIVE_KIND_SPREAD). A
    conv mixer is CUBIC in its input (B * u, gated by C), so a branch as wide
    as the stream it joins multiplies a relative error by 2.2, and nine
    such mixers by a thousand: under the exponential recipe float32 and
    bf16 share no token at the published sizes (gap_mean 3.3 on the
    chip, where a token drawn at random reads 4.2; K and V rounded to
    int8 alone read 0.73: PERF.md, PR 44). A branch much wider than the
    stream costs a factor 3 once, so the growth a tied head needs is ONE
    step: mixer 0 writes FIRST_JUMP times the embedding's spread (the
    embedding is 1/60 of the stream from there on), and every later
    mixer BRANCH_SHARE of the stream it joins, whatever its kind: each
    kind's gain is over the spread a unit-gain mixer of that kind writes
    (KIND_SPREAD, measured at the published widths on the chip: an
    attention mixer averages its values, an expert layer four experts)."""
    stream = s0
    for m in range(layer_idx + 1):
        branch = stream * (FIRST_JUMP if m == 0 else BRANCH_SHARE)
        if m < layer_idx:
            stream = math.hypot(stream, branch)
    kind = config.layer_kind(layer_idx)
    spreads = (KIND_SPREAD if "C" in config.layer_pattern
               else SELECTIVE_KIND_SPREAD)
    if kind not in spreads:
        raise ValueError(
            f"{config.name} (layers {config.layer_pattern}): no seeded "
            f"recipe for a stack that mixes gated short convolutions "
            f"with layer kind {kind!r}; it has one for "
            f"{' '.join(spreads)}")
    return branch / spreads[kind] / config.residual_multiplier


def score_gain(config: ModelConfig) -> float:
    """Seeded recipe: what wq and wk are multiplied by. A model that
    states its own score scale: so that scores at `attention_multiplier`
    have the spread they have at 1/sqrt(head_dim) with unit gains. A
    `layer_pattern` stack that norms q and k per head: NORMED_QK_GAIN,
    which the norms take out again (a trained model's projections have
    no unit scale, which is what its norms are for; without them the
    seeded scores are four times sharper, and a program that forgets
    the norms is told from one that has them). A stack whose attention
    kinds differ by their positional term (`rope_kinds`): SHARP_QK_GAIN,
    scores of spread 2.25. At spread 1 a softmax over a 4,096-key
    window weighs some 1,500 keys alike: the attention branch is a
    hundredth of its block's variance, and rope, no rope, a window or
    none all read inside bf16's rounding. At 2.25 it weighs some 26, as
    a trained model's attention does, and the branch is as wide as the
    experts'."""
    if config.qk_norm and config.is_hybrid:
        return NORMED_QK_GAIN
    if config.rope_kinds or config.diff_attention:
        return SHARP_QK_GAIN
    if not config.attention_multiplier:
        return 1.0
    return (config.attention_multiplier
            * math.sqrt(config.head_dim)) ** -0.5


def attention_scale(config: ModelConfig) -> dict:
    """The keyword a model that states its own score scale hands every
    attention function (kernels and XLA forms alike); nothing for the
    others, whose programs trace 1/sqrt(head_dim) as they always did."""
    if config.attention_multiplier:
        return {"sm_scale": config.attention_multiplier}
    # differential attention is handed to the kernels two heads wide
    # (`_diff_inputs`): the scale is the single head's
    return ({"sm_scale": config.head_dim ** -0.5}
            if config.diff_attention else {})


def init_hybrid_layer(k: jax.Array, config: ModelConfig,
                      layer_idx: int, out_gain=None) -> dict:
    """Seeded weights of one layer. The recipe is restated, not imported,
    by benchmarks/references/nemotron_h.py; the tests hold the two equal.
    `out_gain`: `branch_gain(config, layer_idx)` handed in as a traced
    float32 scalar, so that one compiled program draws every layer of a
    kind (`ModelRunner._init_random_params`); None: computed here.

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
    is), so routing is a little uneven. Those four stay float32.

    A window layer's matrices are a full layer's. A SwiGLU expert
    (`mlp_act` swiglu) draws its gate from fold_in(ks[9], e) and its up
    from fold_in(ks[11], e), stored as one [2m, h] matrix, gate rows
    first; a softmax router has no selection bias and a model without a
    shared expert no s_up / s_down (no draw is made for either, and the
    other keys are unmoved).

    A model that states multipliers (granitemoehybrid; gains of 1 and
    the recipe above for every other): wq and wk are drawn
    (attention_multiplier sqrt(head_dim))^-1/2 times wider, so that
    scores keep the spread 1 they have at 1/sqrt(head_dim); and with a
    TIED head a matrix that writes into the residual stream is drawn
    `branch_gain` times wider, growing with the mixer's published index
    (`branch_gain` has the why: the embedding's share of the last
    hidden state has to be small, or every token predicts itself).

    A latent-attention layer draws W_dq, W_uq, W_dkv, W_o (centred),
    W_kr, W_uk, W_uv from keys 0..6, each normal / sqrt(its fan_in): the
    rank for W_uq, W_uk and W_uv, whose inputs are normed to unit RMS.
    A dense SwiGLU draws gate, up, down (centred) from keys 0..2 and
    stores [gate | up] as one matrix. A SwiGLU shared expert draws its
    gate from key 12, its up from key 14 (key 12 alone is relu2's up),
    stored [gate | up]. With `sandwich_norm` every mixer has a second
    gain, ones. Why nothing more is added for that block: the branch is
    normed before it is added, so no matrix's scale reaches the residual
    stream, each block adds two unit-RMS branches, and the final norm
    and a head of spread 1 give logits of spread 1 as in the other
    recipes.

    A gated short convolution draws W_in [h, 3h] (the thirds B, C, u in
    that order) from key 0, its taps [K, h] from key 1 (normal /
    sqrt(K), tap K-1 on the current position) and W_out (centred) from
    key 6, the keys a Mamba mixer's three like matrices have. With
    `qk_norm` an attention mixer has two more gains of head_dim, ones.

    A stack of LayerNorms (`norm_kind` "layer"; cohere2_moe): a matrix
    that writes into the residual stream, once centred, adds STREAM_MEAN
    times its first output lane's column to EVERY column, so that each
    branch writes a mean over the lanes (half its lane 0's value: as
    wide as half the branch's spread, different for every token). Every
    reader of the stream is a LayerNorm, which takes it off again: the
    model computes what it would without it, and one that norms by the
    root mean square alone does not. Seeded matrices have lane means of
    spread 1/sqrt(h) and would not tell the two norms apart; a trained
    stream has a mean, which is what the subtraction is for. The later
    mixers of a parallel block have no `norm` (`has_own_norm`).

    phi4flash's kinds and fields draw what they add from keys of their
    own, `fold_in(k, 100 + j)` (`extra`), so that no other draw moves:
    a norm's bias (`norm_bias`, extra 0) and the attention biases
    (`attn_bias`: bq, bk, bv, bo, extra 1..4) BIAS_SPREAD x normal, bo x
    `out_gain` beside the branch it joins; the differential parameters
    (extra 5..9): four lambda vectors LAMBDA_SPREAD x normal, float32,
    and the sub-norm's gain 1 + SUBLN_SPREAD x normal. A cross-attention
    mixer ("X") draws wq, wo and their biases as an attention mixer does
    and nothing else. A gated memory unit ("G") draws W_1 [h, D] from
    key 0 and W_2 [D, h] (centred) from key 6. A Mamba-1 mixer ("S")
    draws W_in [h, 2D] = [u | z] from key 0, its taps from key 1, the
    conv's bias from key 2, dt's bias from key 3 and A from key 4 as a
    Mamba-2 mixer does (A uniform in [-16, -1] for every (column,
    channel), stored [N, D] as the state is), W_out from key 6, and
    W_x [D, R + 2N] from extra 10, W_dt [R, D] from extra 11."""
    dtype = jnp.dtype(config.dtype)
    h = config.hidden
    ks = jax.random.split(k, 15)
    lane_mean = STREAM_MEAN if config.norm_kind == "layer" else 0.0

    def extra(j):
        return jax.random.fold_in(k, 100 + j)

    def bias(j, shape, gain=1.0):
        return (BIAS_SPREAD * gain * jax.random.normal(
            extra(j), shape, jnp.float32)).astype(dtype)

    def dense(key, shape, fan_in, centre=None, gain=1.0):
        w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
        if centre is not None:
            w = w - jnp.mean(w, axis=centre, keepdims=True)
            if lane_mean:
                w = w + lane_mean * w[..., :1]
        if not (isinstance(gain, float) and gain == 1.0):
            w = w * gain
        return w.astype(dtype)

    if out_gain is None:
        out_gain = branch_gain(config, layer_idx)
    kind = config.layer_kind(layer_idx)
    p = ({"norm": jnp.ones((h,), dtype)}
         if has_own_norm(config, layer_idx) else {})
    if config.sandwich_norm:
        p["post_norm"] = jnp.ones((h,), dtype)
    if config.norm_bias and "norm" in p:
        p["norm_b"] = bias(0, (h,))
    if kind == "S":
        inner, kw = config.mamba_inner, config.conv_kernel
        n, rank = config.ssm_state, config.mamba_dt_rank
        u = jax.random.uniform(ks[3], (inner,), jnp.float32)
        dt = jnp.exp(u * (math.log(config.ssm_dt_max)
                          - math.log(config.ssm_dt_min))
                     + math.log(config.ssm_dt_min))
        dt = jnp.maximum(dt, config.ssm_dt_floor)
        p.update({
            "in_proj": dense(ks[0], (h, 2 * inner), h),
            "conv_w": dense(ks[1], (kw, inner), kw),
            "conv_b": (0.1 * jax.random.normal(
                ks[2], (inner,), jnp.float32)).astype(dtype),
            "x_proj": dense(extra(10), (inner, rank + 2 * n), inner),
            "dt_proj": dense(extra(11), (rank, inner), rank),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.log(jax.random.uniform(
                ks[4], (n, inner), jnp.float32, 1.0, 16.0)),
            "d_skip": jnp.ones((inner,), jnp.float32),
            "out_proj": dense(ks[6], (inner, h), inner, 0, out_gain),
        })
    elif kind == "G":
        inner = config.mamba_inner
        p.update({
            "g_in": dense(ks[0], (h, inner), h),
            "g_out": dense(ks[6], (inner, h), inner, 0, out_gain),
        })
    elif kind == "L":
        qh, rank, q_rank = (config.n_q_heads, config.mla_kv_lora_rank,
                            config.mla_q_lora_rank)
        nope, rd, vd = (config.mla_nope_head_dim, config.mla_rope_head_dim,
                        config.mla_v_head_dim)
        p.update({
            "w_dq": dense(ks[0], (h, q_rank), h),
            "q_norm": jnp.ones((q_rank,), dtype),
            # drawn as [rank, heads, d], stored as the matmuls read them:
            # W_uq one [q_rank, heads x (nope + rope)] matrix, W_uk and
            # W_uv head-major, the batch dimension of the absorbed decode
            # step's products
            "w_uq": dense(ks[1], (q_rank, qh, nope + rd),
                          q_rank).reshape(q_rank, -1),
            "w_dkv": dense(ks[2], (h, rank), h),
            "kv_norm": jnp.ones((rank,), dtype),
            "wo": dense(ks[3], (qh, vd, h), qh * vd, (0, 1), out_gain),
            "w_kr": dense(ks[4], (h, rd), h),
            "w_uk": dense(ks[5], (rank, qh, nope),
                          rank).transpose(1, 2, 0),  # [heads, nope, rank]
            "w_uv": dense(ks[6], (rank, qh, vd),
                          rank).transpose(1, 0, 2),  # [heads, rank, v]
        })
    elif kind == "D":
        m = config.mlp_hidden
        p.update({
            "d_up": jnp.concatenate([dense(ks[0], (h, m), h),
                                     dense(ks[1], (h, m), h)], axis=1),
            "d_down": dense(ks[2], (m, h), m, 0, out_gain),
        })
    elif kind == "M":
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
            "out_proj": dense(ks[6], (inner, h), inner, 0, out_gain),
        })
    elif kind == "C":
        kw = config.conv_kernel
        p.update({
            "in_proj": dense(ks[0], (h, 3 * h), h),
            "conv_w": dense(ks[1], (kw, h), kw),
            "out_proj": dense(ks[6], (h, h), h, 0, out_gain),
        })
    elif kind in "*WX":
        qh, kh, hd = config.n_q_heads, config.n_kv_heads, config.head_dim
        qk_gain = score_gain(config)
        p.update({
            "wq": dense(ks[0], (h, qh, hd), h, gain=qk_gain),
            "wk": dense(ks[1], (h, kh, hd), h, gain=qk_gain),
            "wv": dense(ks[2], (h, kh, hd), h),
            "wo": dense(ks[3], (qh, hd, h), qh * hd, (0, 1), out_gain),
        })
        if config.qk_norm:
            p["q_norm"] = jnp.ones((hd,), dtype)
            p["k_norm"] = jnp.ones((hd,), dtype)
        if config.attn_bias:
            p.update({"bq": bias(1, (qh, hd)), "bk": bias(2, (kh, hd)),
                      "bv": bias(3, (kh, hd)),
                      "bo": bias(4, (h,), out_gain)})
        if config.diff_attention:
            for j, name in enumerate(DIFF_PARAMS[:4]):
                p[name] = LAMBDA_SPREAD * jax.random.normal(
                    extra(5 + j), (hd,), jnp.float32)
            p["subln"] = (1.0 + SUBLN_SPREAD * jax.random.normal(
                extra(9), (2 * hd,), jnp.float32)).astype(dtype)
        if kind == "X":  # reads the "*" layer's keys and values
            for name in ("wk", "wv", "bk", "bv"):
                p.pop(name, None)
    else:
        m = config.expert_mlp_hidden
        sm = _shared_width(config)
        lo, hi = config.held_experts
        ids = jnp.arange(lo, hi)

        def up_of(key):
            return jax.vmap(lambda e: dense(
                jax.random.fold_in(key, e), (h, m), h).T)(ids)

        p.update({
            "router": dense(ks[7], (h, config.n_experts), h),
            # stored [E, m, h]: see ops/grouped_matmul.expert_gmm
            "e_up": up_of(ks[9]),
            "e_down": jax.vmap(lambda e: dense(
                jax.random.fold_in(ks[10], e), (m, h), m, 0,
                out_gain))(ids),
        })
        if config.mlp_act == "swiglu":  # [E, gate | up, h]
            p["e_up"] = jnp.concatenate([p["e_up"], up_of(ks[11])], axis=1)
        if _has_selection_bias(config):
            p["e_bias"] = 0.02 * jax.random.normal(
                ks[8], (config.n_experts,), jnp.float32)
        if sm:
            p["s_up"] = dense(ks[12], (h, sm), h)
            p["s_down"] = dense(ks[13], (sm, h), sm, 0, out_gain)
            if config.mlp_act == "swiglu":  # [gate | up]
                p["s_up"] = jnp.concatenate(
                    [p["s_up"], dense(ks[14], (h, sm), h)], axis=1)
    return p


def init_hybrid_entry(keys, config: ModelConfig, entry, gains=None) -> dict:
    """Seeded weights of one entry of the `layers` list
    (`ModelConfig.layer_entries`): mixer `first`'s, or for a rolled
    section the mixers first, first + stride, .. drawn each from its own
    key and gain as `init_hybrid_layer` draws it, stacked along a
    leading axis. `keys`: the model's (`init_params`: mixer i has
    keys[i + 1]) and the gains computed here; or, with `gains` (float32
    [repeats]), the keys [repeats] and `branch_gain`s of the entry's OWN
    mixers in order, so that one compiled program draws every entry of
    a kind (`ModelRunner._init_random_params`)."""
    first, repeats, stride = entry
    if gains is None:
        at = [first + stride * r for r in range(repeats)]
        gains = jnp.asarray([branch_gain(config, i) for i in at],
                            jnp.float32)
        keys = keys[jnp.asarray(at) + 1]
    if repeats == 1:
        return init_hybrid_layer(keys[0], config, first, gains[0])
    return jax.vmap(lambda k, gain: init_hybrid_layer(
        k, config, first, gain))(keys, gains)


def conv_channels(config: ModelConfig, layer_idx: int) -> int:
    """Width of a state layer's conv carry: Mamba-2's xBC, or the gated
    short convolution's B * u."""
    return {"M": config.mamba_conv_dim, "S": config.mamba_inner}.get(
        config.layer_kind(layer_idx), config.hidden)


def _ssm_state_shape(config: ModelConfig, layer_idx: int) -> tuple:
    """A slot's SSM state of Mamba layer `layer_idx`: Mamba-2's [heads,
    head_dim, state], Mamba-1's [state, channels] (channels along the
    lanes: ops/ssm.py)."""
    if config.layer_kind(layer_idx) == "S":
        return (config.ssm_state, config.mamba_inner)
    return (config.mamba_heads, config.mamba_head_dim, config.ssm_state)


def make_state_cache(config: ModelConfig, slots: int) -> dict:
    """The per-slot recurrent state: one `conv` array per state layer
    ("M", "S" or "C", in order) and one `ssm` array per Mamba layer ("M"
    or "S": `_ssm_state_shape`; lists, so each layer's update aliases
    its own buffer). A stack of "C" layers alone has an empty `ssm`
    list. An entry of a rolled section (`ModelConfig.layer_entries`)
    holds its layers' arrays stacked along a leading axis."""
    def stacked(repeats):  # a rolled section's layers along an axis
        return (repeats,) if repeats > 1 else ()

    entries = config.layer_entries
    return {
        "conv": [jnp.zeros((*stacked(repeats), slots,
                            config.conv_kernel - 1,
                            conv_channels(config, i)),
                           jnp.dtype(config.dtype))
                 for i, repeats, _ in entries
                 if config.layer_kind(i) in "MCS"],
        "ssm": [jnp.zeros((*stacked(repeats), slots,
                           *_ssm_state_shape(config, i)),
                          jnp.dtype(config.ssm_state_dtype))
                for i, repeats, _ in entries
                if config.layer_kind(i) in "MS"],
    }


def state_slot_bytes(config: ModelConfig) -> int:
    """Bytes of recurrent state one slot holds, all state layers: each
    its conv carry, a Mamba layer its SSM state beside it."""
    conv = ((config.conv_kernel - 1)
            * sum(conv_channels(config, i) for i in config.state_layers)
            * jnp.dtype(config.dtype).itemsize)
    ssm = (config.mamba_inner * config.ssm_state
           * jnp.dtype(config.ssm_state_dtype).itemsize)
    return conv + len(config.ssm_layers) * ssm


# ---------------------------------------------------------------------------
# mixers
# ---------------------------------------------------------------------------


def _gated_group_norm(y, z, weight, groups: int, eps: float,
                      by_slices: bool = False):
    """GroupRMSNorm(y * silu(z)): gate first, then norm each group.
    `by_slices` (a prefill launch): each group as a slice of lanes. Over
    [rows, T, inner] XLA turns the [.., groups, width] reshape into a
    float32 broadcast of the scales and a relaid copy of the gated input,
    67 MB each at 8 x 512 x 4096; over a decode step's [slots, inner] the
    reshape is one fusion and the slices would be two a group."""
    dtype = z.dtype
    g = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
    shape = g.shape
    width = shape[-1] // groups

    def normed(part):
        return part * jax.lax.rsqrt(
            jnp.mean(part * part, axis=-1, keepdims=True) + eps)

    if by_slices:
        g = jnp.concatenate([normed(g[..., k * width:(k + 1) * width])
                             for k in range(groups)], axis=-1)
    else:
        g = normed(g.reshape(*shape[:-1], groups, width)).reshape(shape)
    return g.astype(dtype) * weight


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


def scan_head_block(config: ModelConfig, t: int, ssm_path: str):
    """Heads a grid step where the Mamba mixers of a launch of `t`
    positions a row run the chunked-scan kernel, None where they run the
    XLA form: the slot says `xla`, or `scan_kernel_tiles` refuses the
    shapes. `mamba_prefill`'s rule, and what `ModelRunner` counts by. A
    stack whose Mamba layers are Mamba-1 has no scan kernel: its
    `selective_scan` is the XLA form."""
    if ssm_path == "xla" or "M" not in config.layer_pattern:
        return None
    return scan_kernel_tiles(t, config.mamba_heads, config.mamba_head_dim,
                             config.ssm_groups, config.ssm_state,
                             config.ssm_chunk)


def mamba_prefill(x, lp, config: ModelConfig, conv, ssm, valid,
                  ssm_path: str = "xla"):
    """x [B, T, h] (normed); conv [B, K-1, C], ssm [B, H, P, N]: the rows'
    state going in. Returns (out [B, T, h], conv, ssm coming out).
    `ssm_path` (ops.kernel_path("DYNT_SSM")): the scan is the Pallas
    kernel where `scan_head_block` gives it a head block; `x` and `y`
    then stay [B, T, H*P], as the projection and the conv wrote them."""
    with jax.named_scope("mamba_mixer"):
        w, split = lp["in_proj"], config.mamba_inner + config.mamba_conv_dim
        if w.shape[1] % LANES == 0:
            z, xbc, dt = _split_in_proj(
                jnp.einsum("bth,hm->btm", x, w), config)
        else:
            # [z | xbc] are whole lane tiles and the heads' dt behind
            # them are not: projected as one, XLA lays the product out
            # with POSITIONS minor and relays z and xbc for every reader
            z, xbc, _ = _split_in_proj(
                jnp.einsum("bth,hm->btm", x, w[:, :split]), config)
            dt = jnp.einsum("bth,hm->btm", x, w[:, split:])
        n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
        xbc_dtype = xbc.dtype
        xbc, conv = causal_conv(conv, xbc, lp["conv_w"], n_valid)
        xbc = jax.nn.silu(xbc + lp["conv_b"].astype(jnp.float32)
                          ).astype(xbc_dtype)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
        dt = jnp.where(valid[:, :, None], dt, 0.0)
        a = -jnp.exp(lp["a_log"])
        xs = xbc[..., :config.mamba_inner]
        head_block = scan_head_block(config, x.shape[1], ssm_path)
        if head_block is None:
            ssm, y = ssm_chunk_scan(ssm, dt, a, *_split_xbc(xbc, config),
                                    chunk=config.ssm_chunk)
            y = y.reshape(*z.shape)
        else:
            ssm, y = ssm_chunk_scan_kernel(
                ssm, dt, a, xbc, chunk=config.ssm_chunk,
                heads_per_block=head_block,
                interpret=ssm_path == "interpret")
        y = y + (jnp.repeat(lp["d_skip"], config.mamba_head_dim)
                 * xs.astype(jnp.float32))
        y = _gated_group_norm(y, z, lp["ssm_norm"], config.ssm_groups,
                              config.rms_eps, by_slices=True)
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


def _selective_inputs(u, lp, config: ModelConfig):
    """u [..., D] (after the conv and its silu) -> (dt [..., D] float32
    after softplus, B [..., N], C [..., N])."""
    rank, n = config.mamba_dt_rank, config.ssm_state
    dbc = jnp.einsum("...d,dr->...r", u, lp["x_proj"])
    dt = jnp.einsum("...r,rd->...d", dbc[..., :rank], lp["dt_proj"])
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    return dt, dbc[..., rank:rank + n], dbc[..., rank + n:]


def _selective_out(y, u, z, lp):
    """y [..., D] float32 (the scan's read-out) -> (the mixer's output,
    the memory m: y + D u BEFORE the gate, in the model dtype)."""
    y = y + lp["d_skip"] * u.astype(jnp.float32)
    gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype)
    return (jnp.einsum("...m,mh->...h", gated, lp["out_proj"]),
            y.astype(u.dtype))


def mamba1_prefill(x, lp, config: ModelConfig, conv, ssm, valid):
    """A Mamba-1 mixer over a prefill chunk a row. x [B, T, h] (normed);
    conv [B, K-1, D], ssm [B, N, D]: the rows' state going in. Returns
    (out [B, T, h], conv, ssm coming out, m [B, T, D]: the scan's output
    before the gate, what a gated memory unit reads)."""
    with jax.named_scope("mamba1_mixer"):
        inner = config.mamba_inner
        uz = jnp.einsum("bth,hm->btm", x, lp["in_proj"])
        u, z = uz[..., :inner], uz[..., inner:]
        n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
        u_dtype = u.dtype
        u, conv = causal_conv(conv, u, lp["conv_w"], n_valid)
        u = jax.nn.silu(u + lp["conv_b"].astype(jnp.float32)
                        ).astype(u_dtype)
        dt, b, c = _selective_inputs(u, lp, config)
        dt = jnp.where(valid[:, :, None], dt, 0.0)
        ssm, y = selective_scan(ssm, dt, -jnp.exp(lp["a_log"]), u, b, c)
        out, memory = _selective_out(y, u, z, lp)
        return out, conv, ssm, memory


def mamba1_decode(x, lp, config: ModelConfig, conv, ssm, active):
    """One token a slot. x [S, h]; conv, ssm: the WHOLE cache of this
    layer (row i = slot i). Inactive rows keep their state. Returns
    (out [S, h], conv, ssm, m [S, D])."""
    with jax.named_scope("mamba1_mixer"):
        inner = config.mamba_inner
        uz = jnp.einsum("sh,hm->sm", x, lp["in_proj"])
        u, z = uz[..., :inner], uz[..., inner:]
        window = jnp.concatenate([conv.astype(u.dtype), u[:, None]],
                                 axis=1)  # [S, K, D]
        u = jnp.einsum("skc,kc->sc", window.astype(jnp.float32),
                       lp["conv_w"].astype(jnp.float32))
        u = jax.nn.silu(u + lp["conv_b"].astype(jnp.float32)
                        ).astype(x.dtype)
        conv = jnp.where(active[:, None, None],
                         window[:, 1:].astype(conv.dtype), conv)
        dt, b, c = _selective_inputs(u, lp, config)
        ssm, y = selective_state_update(ssm, dt, -jnp.exp(lp["a_log"]), u,
                                        b, c, active)
        out, memory = _selective_out(y, u, z, lp)
        return out, conv, ssm, memory


def memory_gate_mixer(x, memory, lp):
    """A gated memory unit: x [..., h] (normed), memory [..., D] (the
    memory layer's pre-gate scan output AT THE SAME POSITION) ->
    (m * silu(x W_1)) W_2. No state, no cache, elementwise in time."""
    with jax.named_scope("memory_gate"):
        gate = jax.nn.silu(jnp.einsum("...h,hm->...m", x, lp["g_in"]))
        return jnp.einsum("...m,mh->...h", memory * gate, lp["g_out"])


def _split_bcu(bcu, config: ModelConfig):
    h = config.hidden
    return bcu[..., :h], bcu[..., h:2 * h], bcu[..., 2 * h:]


def short_conv_prefill(x, lp, config: ModelConfig, conv, valid):
    """The gated short convolution over a prefill chunk a row. x [B, T, h]
    (normed); conv [B, K-1, h]: the rows' carry going in (the K-1 values
    of B * u before the chunk). Returns (out [B, T, h], carry coming
    out); padding stays out of the carry (`causal_conv`)."""
    with jax.named_scope("conv_mixer"):
        b, c, u = _split_bcu(
            jnp.einsum("bth,hm->btm", x, lp["in_proj"]), config)
        n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
        y, conv = causal_conv(conv, b * u, lp["conv_w"], n_valid)
        y = (c.astype(jnp.float32) * y).astype(x.dtype)
        return jnp.einsum("btm,mh->bth", y, lp["out_proj"]), conv


def short_conv_decode(x, lp, config: ModelConfig, conv, active):
    """One token a slot. x [S, h]; conv [S, K-1, h]: the WHOLE carry of
    this layer (row i = slot i). Inactive rows keep theirs."""
    with jax.named_scope("conv_mixer"):
        b, c, u = _split_bcu(
            jnp.einsum("sh,hm->sm", x, lp["in_proj"]), config)
        window = jnp.concatenate([conv.astype(x.dtype), (b * u)[:, None]],
                                 axis=1)  # [S, K, h]
        y = jnp.einsum("skc,kc->sc", window.astype(jnp.float32),
                       lp["conv_w"].astype(jnp.float32))
        conv = jnp.where(active[:, None, None],
                         window[:, 1:].astype(conv.dtype), conv)
        y = (c.astype(jnp.float32) * y).astype(x.dtype)
        return jnp.einsum("sm,mh->sh", y, lp["out_proj"]), conv


def _relu2(u):
    return jnp.square(jax.nn.relu(u))


def _swiglu(u):
    """`u` = x [W_gate | W_up] from one fused matrix: silu(gate) * up."""
    m = u.shape[-1] // 2
    return jax.nn.silu(u[..., :m]) * u[..., m:]


def rope_tables(config: ModelConfig, kind: str):
    """(inverse frequencies [hd/2] float32, cos/sin factor) of a layer
    kind, or None where attention has no positional term: the default
    table on a window layer, YaRN on a full one where the model states a
    factor (HF `rope_parameters` keyed by layer type), none on a kind
    that `rope_kinds` leaves out."""
    if not config.use_rope or (config.rope_kinds
                               and kind not in config.rope_kinds):
        return None
    if kind == "*" and config.rope_yarn_factor:
        return yarn_rope_tables(config)
    # a latent layer ropes its `mla_rope_head_dim` lanes alone
    half = (config.mla_rope_head_dim if kind == "L"
            else config.head_dim) // 2
    return (jnp.exp(-math.log(config.rope_theta)
                    * jnp.arange(0, half, dtype=jnp.float32) / half), 1.0)


def apply_rope(x, positions, tables, interleaved: bool = False):
    """Rotate-half rope: lane i turns with lane i + hd/2. x [..., T, H,
    hd]; positions [..., T], ABSOLUTE (never a page group's own frame).
    `interleaved` (GPT-J's convention, `ModelConfig.rope_interleaved`):
    lane 2i turns with lane 2i + 1 by the same angle i. Computed on whole
    lanes: the partner of every lane is fetched by two rolls and a
    select on the lane's parity, so that no [.., hd/2, 2] array exists
    (two lanes minor would be padded to a tile of 128)."""
    if tables is None:
        return x
    inv_freq, factor = tables
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = (jnp.cos(angles) * factor)[..., None, :]
    sin = (jnp.sin(angles) * factor)[..., None, :]
    if interleaved:
        cos, sin = jnp.repeat(cos, 2, axis=-1), jnp.repeat(sin, 2, axis=-1)
        even = jnp.arange(x.shape[-1]) % 2 == 0
        # out[2i] = x[2i] cos - x[2i+1] sin; out[2i+1] = x[2i+1] cos +
        # x[2i] sin
        partner = jnp.where(even, -jnp.roll(x, -1, axis=-1),
                            jnp.roll(x, 1, axis=-1))
        return (x * cos + partner * sin).astype(x.dtype)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _qkv(h, lp, config: ModelConfig, kind: str, positions):
    """h [B, T, hidden] -> q [B, T, qh, hd], k, v [B, T, kh, hd], roped."""
    tables = rope_tables(config, kind)
    q = jnp.einsum("bth,hqd->btqd", h, lp["wq"])
    k = jnp.einsum("bth,hkd->btkd", h, lp["wk"])
    v = jnp.einsum("bth,hkd->btkd", h, lp["wv"])
    if config.attn_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    if config.qk_norm:  # per head, before rope
        q = rms_norm(q, lp["q_norm"], config.rms_eps)
        k = rms_norm(k, lp["k_norm"], config.rms_eps)
    pairs = config.rope_interleaved
    q, k = (apply_rope(q, positions, tables, pairs),
            apply_rope(k, positions, tables, pairs))
    if config.diff_attention:
        return (_wide_query(q, config), _kv_rows(k, config),
                _kv_rows(v, config))
    return q, k, v


def _cross_query(h, lp, config: ModelConfig):
    """h [B, T, hidden] -> the query of a cross-attention ("X") mixer,
    which has no keys or values of its own, as `_qkv` gives a "*"
    mixer's."""
    q = jnp.einsum("bth,hqd->btqd", h, lp["wq"])
    if config.attn_bias:
        q = q + lp["bq"]
    return _wide_query(q, config) if config.diff_attention else q


# Differential attention (arXiv:2410.05258, as phi4flash states it).
# Heads are paired: query pair p = (q_2p, q_2p+1) reads KV pair g = p //
# (query pairs a KV pair) = (k_2g, k_2g+1; v_2g, v_2g+1):
#
#     a1 = softmax(q_2p   k_2g^T   / sqrt(hd)) [v_2g | v_2g+1]
#     a2 = softmax(q_2p+1 k_2g+1^T / sqrt(hd)) [v_2g | v_2g+1]
#     o_p = (1 - lambda_init) RMSNorm(a1 - lambda a2; subln)   [2 hd]
#
# A KV pair side by side, (k_2g | k_2g+1), is 2 hd lanes of a token's row
# of the pool, and so is (v_2g | v_2g+1); a row holds `pairs a row` of
# them (`ModelConfig.diff_rows`: 10 pairs lie in 2 rows of 640 lanes, the
# same bytes as [kv heads, 64] row-major). To the kernels a row is ONE kv
# head as wide as the row. Query head j is handed over as wide as the row
# with its values in the lanes ITS kv head (2g for even j, 2g + 1 for odd)
# has there and zeros in the others: q . row = q_j . k exactly (every
# other head's lanes meet zeros), and P V comes back as wide as the row,
# of which the pair's 2 hd lanes are a1 (even j) or a2 (odd j). So both
# softmaxes are plain grouped-query attention at the row's width and
# scale 1/sqrt(hd) (`attention_scale`), which the accepted kernels and the
# XLA forms run as they stand; a row's query heads are padded with zero
# heads to a power of two (the prefill kernel's tiles), and the
# subtraction, the sub-norm and the scale are the epilogue
# `_attention_out` adds before W_o.


def _diff_layout(config: ModelConfig):
    """(rows, pairs a row, query heads a pair, query heads a row, the
    same padded to a power of two)."""
    rows = config.diff_rows
    pairs = config.n_kv_heads // 2 // rows
    per_pair = config.n_q_heads // (rows * pairs)
    per_row = pairs * per_pair
    return rows, pairs, per_pair, per_row, config.attn_geometry[0] // rows


def _wide_query(q, config: ModelConfig):
    """q [..., heads, hd] -> [..., rows x padded heads a row, row lanes]:
    head j's values in the lanes of its kv head within its row (kv head
    2g + j % 2 of pair g = j // (query heads a pair)), zeros elsewhere."""
    rows, pairs, per_pair, per_row, padded = _diff_layout(config)
    hd = q.shape[-1]
    local = jnp.arange(per_row)
    block = 2 * (local // per_pair) + local % 2  # kv head within the row
    own = (block[:, None] == jnp.arange(2 * pairs)[None, :]).astype(q.dtype)
    wide = (q.reshape(*q.shape[:-2], rows, per_row, 1, hd)
            * own[:, :, None]).reshape(*q.shape[:-2], rows, per_row,
                                       2 * pairs * hd)
    wide = jnp.pad(wide, [(0, 0)] * (wide.ndim - 2)
                   + [(0, padded - per_row), (0, 0)])
    return wide.reshape(*q.shape[:-2], rows * padded, 2 * pairs * hd)


def _kv_rows(k, config: ModelConfig):
    """k or v [..., kv heads, hd] -> [..., rows, row lanes]: a row's kv
    heads side by side, the same values row-major."""
    rows = config.diff_rows
    return k.reshape(*k.shape[:-2], rows, k.shape[-2] // rows * k.shape[-1])


def _pair_values(attn, config: ModelConfig):
    """attn [..., rows x padded heads a row, row lanes] as the attention
    function gave it -> [..., heads, 2 hd]: each real head's own pair's
    lanes (a1 at an even head, a2 at an odd one)."""
    rows, pairs, per_pair, per_row, padded = _diff_layout(config)
    lead = attn.shape[:-2]
    attn = attn.reshape(*lead, rows, padded, pairs, -1)[..., :per_row, :, :]
    own = (jnp.arange(per_row)[:, None] // per_pair
           == jnp.arange(pairs)[None, :]).astype(attn.dtype)
    picked = jnp.sum(attn * own[:, :, None], axis=-2)  # one term is not 0
    return picked.reshape(*lead, rows * per_row, picked.shape[-1])


def diff_lambda(lp, config: ModelConfig, layer_idx: int):
    """(lambda, lambda_init) of attention mixer `layer_idx`: lambda_init
    = 0.8 - 0.6 exp(-0.3 l) for its BLOCK's index l; lambda float32."""
    block = layer_idx // config.mixers_per_layer
    # a rolled section's repeat is traced, and so then is its block
    init = 0.8 - 0.6 * (math.exp(-0.3 * block) if isinstance(block, int)
                        else jnp.exp(-0.3 * block.astype(jnp.float32)))
    return (jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
            - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"]))
            + init), init


def _attention_out(attn, lp, config: ModelConfig, layer_idx: int):
    """attn [..., query heads, width] as the attention function gave it
    -> the mixer's output [..., hidden]: with `diff_attention` the
    epilogue first (heads 2p and 2p + 1 hold a1 and a2 of pair p), then
    W_o and with `attn_bias` its bias."""
    if config.diff_attention:
        lam, init = diff_lambda(lp, config, layer_idx)
        attn = _pair_values(attn, config)
        pair = attn.reshape(*attn.shape[:-2], attn.shape[-2] // 2, 2,
                            attn.shape[-1]).astype(jnp.float32)
        diff = pair[..., 0, :] - lam * pair[..., 1, :]
        diff = diff * jax.lax.rsqrt(
            jnp.mean(diff * diff, axis=-1, keepdims=True) + config.rms_eps)
        # (a rolled section's traced `init` rounds as a Python float's
        # product with the model dtype does)
        diff = (diff.astype(attn.dtype) * lp["subln"]) * jnp.asarray(
            1.0 - init, attn.dtype)
        # [.., pairs, 2 hd] row-major is [.., heads, hd]: pair p's first
        # half is head 2p's lanes of W_o
        attn = diff.reshape(*diff.shape[:-2], *lp["wo"].shape[:2])
    out = jnp.einsum("...qd,qdh->...h", attn, lp["wo"])
    return out + lp["bo"] if config.attn_bias else out


ATTENTION_SCOPES = {"*": "attn_full", "W": "attn_window", "L": "attn_latent",
                    "X": "attn_cross"}

# Keys a step of a latent layer's prefill attention in XLA: their K and
# V are rebuilt from the cached rows and scored against every query of
# the launch (at most the 2,048 positions `prefill_launch_fits` allows),
# so the float32 scores [rows, heads, T, keys] are 134 MB at 128 heads.
LATENT_KEY_BLOCK = 128


def _latent_sizes(config: ModelConfig):
    """(rank, rope lanes, softmax scale) of a latent layer."""
    return (config.mla_kv_lora_rank, config.mla_rope_head_dim,
            1.0 / math.sqrt(config.mla_qk_head_dim))


def _pad_lanes(x, width: int):
    """x [..., n] -> [..., width], zeros behind."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _latent_projections(h, lp, config: ModelConfig, positions):
    """h [B, T, hidden] (normed) -> q_nope [B, T, heads, nope], q_rope
    [B, T, heads, rope] (roped) and the tokens' cache rows [B, T, width]:
    [RMSNorm(c_kv) | RoPE(k_r) | zeros]."""
    nope = config.mla_nope_head_dim
    tables = rope_tables(config, "L")
    c_q = rms_norm(jnp.einsum("bth,hr->btr", h, lp["w_dq"]), lp["q_norm"],
                   config.rms_eps)
    q = jnp.einsum("btr,rm->btm", c_q, lp["w_uq"])
    q = q.reshape(*q.shape[:2], config.n_q_heads, -1)
    q_rope = apply_rope(q[..., nope:], positions, tables)
    c_kv = rms_norm(jnp.einsum("bth,hr->btr", h, lp["w_dkv"]),
                    lp["kv_norm"], config.rms_eps)
    k_r = apply_rope(jnp.einsum("bth,hr->btr", h, lp["w_kr"])[:, :, None],
                     positions, tables)[:, :, 0]
    row = _pad_lanes(jnp.concatenate([c_kv, k_r], axis=-1),
                     config.kv_cache_head_dim)
    return q[..., :nope], q_rope, row


def latent_prefill_attention(q_nope, q_rope, kv_cache, layer, block_tables,
                             positions, kv_lens, w_uk, w_uv,
                             config: ModelConfig):
    """The XLA form of a latent layer's prefill attention: the CPU
    path, the oracle of `ops/paged_attention.paged_attention_latent`
    (same signature) and what a geometry its kernel refuses takes.
    Causal attention of a launch's queries over the rows' cached
    latents WITHOUT absorbing: a block of LATENT_KEY_BLOCK keys at a
    time (a loop as long as the longest row's context needs), the
    block's rows are gathered straight from the pool, expanded to
    k_h = [c_kv W_uk,h | k_r] and v_h = c_kv W_uv,h, scored 128 + 64
    lanes wide and folded into a running softmax. No array over all
    keys exists. Returns [B, T, heads, v] in q's dtype."""
    b, t, nh, _ = q_nope.shape
    rank, rd, scale = _latent_sizes(config)
    ps = kv_cache.shape[3]
    width = block_tables.shape[1]
    pages = min(width, max(1, LATENT_KEY_BLOCK // ps))
    if width % pages:  # the scratch page, masked by the lengths
        block_tables = jnp.pad(block_tables,
                               ((0, 0), (0, pages - width % pages)))
    keys = pages * ps
    seen = jnp.minimum(kv_lens, jnp.max(positions, axis=1) + 1)
    n_blocks = (jnp.max(seen) + keys - 1) // keys

    def body(i, carry):
        m, l, acc = carry
        tables = jax.lax.dynamic_slice_in_dim(block_tables, i * pages,
                                              pages, axis=1)
        rows = kv_cache[layer, 0, tables].reshape(b, keys, -1)
        c_kv, k_r = rows[..., :rank], rows[..., rank:rank + rd]
        k = jnp.einsum("bsr,hnr->bshn", c_kv, w_uk)
        v = jnp.einsum("bsr,hrv->bshv", c_kv, w_uv)
        s = (jnp.einsum("bthn,bshn->bhts", q_nope, k,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bthr,bsr->bhts", q_rope, k_r,
                          preferred_element_type=jnp.float32)) * scale
        kv_pos = i * keys + jnp.arange(keys)
        mask = ((kv_pos[None, None, :] <= positions[:, :, None])
                & (kv_pos[None, None, :] < kv_lens[:, None, None]))
        # block 0 holds key 0, which every query sees: m is finite from
        # the first step on, and a block wholly masked adds exp(-1e30 - m)
        s = jnp.where(mask[:, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhts,bshv->bhtv", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    vd = w_uv.shape[-1]
    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((b, nh, t), -1e30, jnp.float32),
         jnp.zeros((b, nh, t), jnp.float32),
         jnp.zeros((b, nh, t, vd), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 1, 2).astype(q_nope.dtype)


def latent_prefill(h, lp, config: ModelConfig, kv_cache, layer,
                   block_tables, positions, kv_lens, valid,
                   attention_fn=None):
    """A latent layer over a prefill chunk a row: the chunk's rows go
    into the pool first, then attention reads the pool, through
    `attention_fn` (`ops/paged_attention.paged_attention_latent`: the
    blocked kernel) or, without one, through
    `latent_prefill_attention`, whose signature it has. Returns
    (kv_cache, out [B, T, hidden])."""
    with jax.named_scope(ATTENTION_SCOPES["L"]):
        q_nope, q_rope, row = _latent_projections(h, lp, config, positions)
        kv_cache = write_latent_pages(kv_cache, layer, row, block_tables,
                                      positions, valid)
        attn = (attention_fn or latent_prefill_attention)(
            q_nope, q_rope, kv_cache, layer, block_tables, positions,
            kv_lens, lp["w_uk"], lp["w_uv"], config)
        return kv_cache, jnp.einsum("btqv,qvh->bth", attn, lp["wo"])


def paged_attention_decode_latent_xla(q, kv_cache, layer, block_tables,
                                      kv_lens, row_cur, *, rank: int,
                                      sm_scale: float):
    """The oracle of `ops/paged_attention.paged_attention_decode_latent`
    (same signature): gathers the whole table. q [B, heads, width],
    row_cur [B, width] -> context [B, heads, rank] float32."""
    b = q.shape[0]
    rows = kv_cache[layer, 0][block_tables]
    rows = rows.reshape(b, -1, rows.shape[-1]).astype(jnp.float32)
    q32, cur = q.astype(jnp.float32), row_cur.astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q32, rows) * sm_scale
    hist = jnp.arange(rows.shape[1])[None, :] < (kv_lens[:, None] - 1)
    s = jnp.where(hist[:, None, :], s, -1e30)
    s_cur = jnp.einsum("bhw,bw->bh", q32, cur) * sm_scale
    probs = jax.nn.softmax(
        jnp.concatenate([s, s_cur[..., None]], axis=-1), axis=-1)
    return (jnp.einsum("bhs,bsr->bhr", probs[..., :-1], rows[..., :rank])
            + probs[..., -1:] * cur[:, None, :rank])


def latent_decode(h, lp, config: ModelConfig, kv_cache, layer, block_tables,
                  positions, attn_lens, attn_fn):
    """One token a slot in the absorbed form: h [S, hidden] (normed) ->
    (out [S, hidden], the tokens' cache rows [S, width], written by the
    caller in one scatter for all layers)."""
    with jax.named_scope(ATTENTION_SCOPES["L"]):
        rank, _, scale = _latent_sizes(config)
        q_nope, q_rope, row = _latent_projections(
            h[:, None], lp, config, positions[:, None])
        q_abs = jnp.einsum("bhn,hnr->bhr", q_nope[:, 0], lp["w_uk"])
        q = _pad_lanes(jnp.concatenate([q_abs, q_rope[:, 0]], axis=-1),
                       row.shape[-1])
        ctx = attn_fn(q, kv_cache, layer, block_tables, attn_lens, row[:, 0],
                      rank=rank, sm_scale=scale)
        out = jnp.einsum("bhr,hrv->bhv", ctx.astype(h.dtype), lp["w_uv"])
        return jnp.einsum("bhv,hvd->bd", out, lp["wo"]), row[:, 0]


def write_latent_stack(kv_cache, rows, block_tables, positions, active):
    """`write_kv_stack` for a single-stack pool: rows [L, S, width], one
    token a slot, every latent layer in ONE scatter of whole rows of the
    pool's flat [L * P * ps, width] view (a bitcast of the row-major
    array). Indexed on (layer, page, offset) the scatter, and with it
    the donated pool the decode loop carries, takes a layout with the
    layer dimension next to the lanes, and every step copies the pool
    out of and back into the row-major one the kernel reads (3.75 GB at
    the published sizes: PERF.md, PR 38; `_write_scale_rows` has the
    same story). An idle slot writes the scratch page; a page outside
    the pool goes to the row past the end, which `drop` drops."""
    n_layers, _, n_pages, page_size = kv_cache.shape[:4]
    width = kv_cache.shape[-1]
    n_rows = n_layers * n_pages * page_size
    page = jnp.take_along_axis(
        block_tables, (positions // page_size)[:, None].astype(jnp.int32),
        axis=1)[:, 0]
    page = jnp.where(active, page, 0)
    row = page * page_size + positions % page_size  # [S], within a layer
    layer = jnp.arange(n_layers, dtype=jnp.int32)[:, None]
    at = jnp.where((page >= 0) & (page < n_pages),
                   layer * (n_pages * page_size) + row, n_rows)
    flat = kv_cache.reshape(n_rows, width).at[at.reshape(-1)].set(
        rows.reshape(-1, width), mode="drop")
    return flat.reshape(kv_cache.shape)


def dense_mixer(x, lp):
    """x [..., h] -> W_down (silu(W_gate x) * W_up x)."""
    with jax.named_scope("mlp_dense"):
        return jnp.einsum(
            "...m,mh->...h",
            _swiglu(jnp.einsum("...h,hm->...m", x, lp["d_up"])),
            lp["d_down"])


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
        act = _swiglu if config.mlp_act == "swiglu" else _relu2
        weights, topi = _routing_weights(x, lp, config)
        out, counts, dropped = dropless_experts(
            x.reshape(b * t, h), weights.reshape(b * t, -1),
            topi.reshape(b * t, -1), valid.reshape(b * t),
            lp["e_up"], lp["e_down"], config.held_experts, act,
            path=gmm_path)
        shared = None
        if "s_up" in lp:
            with jax.named_scope("moe_shared"):
                shared = jnp.einsum(
                    "btm,mh->bth",
                    act(jnp.einsum("bth,hm->btm", x, lp["s_up"])),
                    lp["s_down"])
                if config.shared_expert_mean:
                    shared = shared / config.n_shared_experts
        stats = jnp.concatenate([
            counts, jnp.stack([dropped, jnp.sum(counts > 0), 1])
        ]).astype(jnp.int32)
        out = out.reshape(b, t, h)
        return (out if shared is None else out + shared), stats


def layer_norm(x, weight, eps: float, bias=None):
    """LayerNorm in float32: the mean over the lanes taken off, then the
    root mean square of what is left, a weight and, where the model has
    one (`norm_bias`), a bias."""
    orig = x.dtype
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    out = (x32 * scale).astype(orig) * weight
    return out if bias is None else out + bias


def stream_norm(x, weight, config: ModelConfig, scope="block_norm",
                bias=None):
    """The norm a mixer (a parallel block's mixers: ONE) or the head
    reads the residual stream through."""
    if config.norm_kind == "layer":
        with jax.named_scope(scope):
            if bias is None:
                return layer_norm(x, weight, config.rms_eps)
            return layer_norm(x, weight, config.rms_eps, bias)
    return rms_norm(x, weight, config.rms_eps)


def _head(x, params, config: ModelConfig):
    x = stream_norm(x, params["final_norm"], config, "final_norm",
                    params.get("final_norm_b"))
    if config.tie_embeddings:
        # the embedding's own [rows, h] array, contracted over h: a
        # transposed copy of it (0.41 GB at 50,176 x 4096) is in no step
        with jax.named_scope("tied_head"):
            logits = jnp.einsum("...h,vh->...v", x, params["embed"]
                                ).astype(jnp.float32)
    else:
        logits = jnp.einsum("...h,hv->...v", x, params["lm_head"]
                            ).astype(jnp.float32)
    if config.logits_scaling != 1.0:
        logits = logits / config.logits_scaling
    return logits


def _embed(params, config: ModelConfig, tokens):
    x = params["embed"][tokens]
    if config.embedding_multiplier != 1.0:
        x = x * config.embedding_multiplier
    return x


def _branch(out, config: ModelConfig):
    """A mixer's output as it joins the residual stream."""
    return (out if config.residual_multiplier == 1.0
            else out * config.residual_multiplier)


def _join(x, pending, out, config: ModelConfig, layer_idx: int):
    """(stream, branches waiting) once mixer `layer_idx` has written
    `out`: added at once, or in a parallel block kept until the block's
    last mixer and added together, so that every mixer of the block read
    the same stream: x <- x + (a + m)."""
    if not config.parallel_block:
        return x + out, None
    if pending is not None:
        out = pending + out
    if (layer_idx + 1) % config.mixers_per_layer:
        return x, out
    return x + out, None


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _window_frame(window, positions, kv_lens):
    """A window group's (cache, table, positions, lengths) in its own
    frame: `window` = (cache, tables [B, pages], base [B]), base the
    position of the first token of each row's first held block."""
    cache, tables, base = window
    return (cache, tables,
            positions - base.reshape((-1,) + (1,) * (positions.ndim - 1)),
            kv_lens - base)


def prefill_attention(q, kv_cache, layer, block_tables, positions, kv_lens,
                      window: int = 0, sm_scale=None):
    """`paged_attention_xla` for a model with window layers where no
    kernel runs (`_group_attention`: the CPU, a geometry the kernel
    refuses), a block of query positions at a time (`lax.map`), each
    over the pages it can see and no others: a window layer the window
    + block positions that end at the block's last query (a slice of
    its table, by row), a full layer the narrowest table prefix that
    holds every row's keys up to the block's last query (one of a few
    static widths, chosen at run time: the program keeps one shape).
    The same numbers as scoring the whole table: what is left out is
    masked there."""
    b, t, qh, hd = q.shape
    ps = kv_cache.shape[3]
    width = block_tables.shape[1]
    block = max(1, min(PREFILL_Q_BLOCK, PREFILL_SCORE_POSITIONS // b))
    if t % block:
        block = t
    # a model that states its score scale; nothing for the others
    scaled = {} if sm_scale is None else {"sm_scale": sm_scale}
    if window:
        pages = min(width, (window + block) // ps + 1)
    else:
        widths = [w for w in FULL_TABLE_PAGES if w < width] + [width]

    def one(part):
        qb, pb = part  # [B, block, qh, hd], [B, block]
        if window:
            # padding positions are 0: a row's first query of the block
            first = jnp.maximum(pb[:, 0] - (window - 1), 0) // ps
            cols = first[:, None] + jnp.arange(pages)[None, :]
            tables = jnp.take_along_axis(
                block_tables, jnp.minimum(cols, width - 1), axis=1)
            return paged_attention_xla(qb, kv_cache, layer, tables, pb,
                                       kv_lens, window=window,
                                       kv_offset=first * ps,
                                       flat_gather=True, **scaled)
        need = jnp.max(jnp.minimum(kv_lens, jnp.max(pb, axis=1) + 1))
        return jax.lax.switch(
            sum((need > w * ps).astype(jnp.int32) for w in widths[:-1]),
            [functools.partial(_prefix_attention, w, layer, **scaled)
             for w in widths],
            qb, kv_cache, block_tables, pb, kv_lens)

    if block == t:
        return one((q, positions))
    n = t // block
    out = jax.lax.map(
        one, (jnp.moveaxis(q.reshape(b, n, block, qh, hd), 1, 0),
              jnp.moveaxis(positions.reshape(b, n, block), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, qh, hd)


def _prefix_attention(pages, layer, q, kv_cache, block_tables, positions,
                      kv_lens, **scaled):
    return paged_attention_xla(q, kv_cache, layer, block_tables[:, :pages],
                               positions, kv_lens, flat_gather=True,
                               **scaled)


def _as_stored(x, cache):
    """k or v [..., kv heads, head_dim] as the pool holds a token: [...,
    cache heads, cache lanes], the same values row-major (a pool that
    packs kv heads into lane tiles: `ModelConfig.kv_heads_per_lane_tile`;
    every other pool's shape is x's own and nothing is traced)."""
    return x.reshape(*x.shape[:-2], *cache.shape[4:])


def _group_attention(attention_fn, q_shape, cache, tables):
    """Prefill attention of one page group of a model with window
    layers, or whose pool packs kv heads into lane tiles: `attention_fn`
    (`ops.paged_attention.paged_attention`: the blocked kernel, with a
    window's lower edge where the layer states one) where one is given
    and `prefill_kernel_tiles` admits the group's shapes, the blocked
    XLA form otherwise. Static: the shapes of a program decide."""
    _, t, qh, hd = q_shape
    if attention_fn is not None and prefill_kernel_tiles(
            t, qh, cache.shape[4] * cache.shape[5] // hd, hd,
            cache.shape[3], tables.shape[1], cache.dtype) is not None:
        return attention_fn
    return prefill_attention


def _group_index(config: ModelConfig, kinds: str, first: int, stride: int,
                 r):
    """The cache layer, within its page group, of mixer first + r x
    stride: the layers of `kinds` before it. `r` is a rolled section's
    repeat (traced), or 0 for a mixer of its own."""
    def count(part):
        return sum(part.count(kind) for kind in kinds)

    base = count(config.layer_pattern[:first])
    return base + r * count(config.layer_pattern[first:first + stride]) \
        if stride else base


def _state_counts(kinds: str) -> tuple[int, int]:
    """(conv arrays, ssm arrays) the mixers `kinds` keep."""
    return (sum(kinds.count(k) for k in "MCS"),
            sum(kinds.count(k) for k in "MS"))


def forward_hybrid(params, config: ModelConfig, tokens, positions, kv_cache,
                   state, slots, block_tables, kv_lens, valid, last_idx,
                   attention_fn=None, gmm_path: str = "xla",
                   all_logits: bool = False, window=None,
                   ssm_path: str = "xla", decode_attention_fn=None):
    """A prefill chunk a row. tokens, positions, valid [B, T]; slots [B]:
    each row's state slot (>= the cache's size for an empty row: its
    write is dropped); last_idx [B]: the row's last valid position in
    this chunk. Returns (kv_cache, state, logits [B, vocab] at last_idx,
    moe stats [E_held + 3]); `all_logits` gives [B, T, vocab] (tests).

    A model with window layers is handed `window` = (the window group's
    cache, its tables [B, pages], base [B]) and gives back `kv_cache` as
    (full group, window group); its window layers read their short
    table (window + chunk keys) in that group's frame, its full layers
    the sequence's, each group through `_group_attention`. `ssm_path`:
    the Mamba mixers' scan (`mamba_prefill`); no other mixer reads it.

    A stack with a tail that caches nothing and carries nothing in time
    (`ModelConfig.cross_decoder_start`: gated memory units and
    cross-attention) runs the tail on each row's `last_idx` ALONE, every
    row of every launch (one program a launch shape, whether or not a
    row's chunk is its last): the stream, the memory and the shared
    layer's key and value are cut to that position, and a cross-attention
    mixer reads the pages through `decode_attention_fn`, one query a row
    over the history plus the position's own key and value in registers,
    as a decode step does. The logits at `last_idx` are the same;
    `all_logits` runs the tail on every position instead.

    The mixers are walked by the entries of `params["layers"]`
    (`ModelConfig.layer_entries`): one at a time, or a rolled section's
    period as the body of ONE `lax.scan` over its repeats, which carries
    the stream, the pools and the section's stacked state."""
    attention = win_attention = attention_fn or paged_attention_xla
    qh, _, hd = config.attn_geometry
    q_shape = (*tokens.shape, qh, hd)
    win_cache = None
    if window is not None:
        win_cache, win_tables, win_pos, win_lens = _window_frame(
            window, positions, kv_lens)
        attention = _group_attention(attention_fn, q_shape, kv_cache,
                                     block_tables)
        win_attention = _group_attention(attention_fn, q_shape, win_cache,
                                         win_tables)
    elif config.kv_heads_per_lane_tile > 1:
        # head_dim 64: no prefill kernel takes the geometry, and the
        # blocked XLA form scores the table prefix the rows fill, not the
        # longest context served (float32 [rows, T, heads, keys])
        attention = _group_attention(attention_fn, q_shape, kv_cache,
                                     block_tables)
    decode_attention = decode_attention_fn or paged_attention_decode_xla
    fresh = positions[:, 0] == 0  # a row at position 0 starts from zero
    scaled = attention_scale(config)
    x = _embed(params, config, tokens)
    conv_out, ssm_out = list(state["conv"]), list(state["ssm"])
    stats = jnp.zeros((moe_stats_size(config),), jnp.int32)
    tail = config.n_layers if all_logits else config.cross_decoder_start
    # what later mixers read of an earlier one, values of this step: the
    # memory layer's scan output and the shared layer's keys and values
    memory = shared = None

    def at_last(a):  # [B, T, ...] -> [B, 1, ...]: each row's `last_idx`
        return jnp.take_along_axis(
            a, last_idx[(slice(None),) + (None,) * (a.ndim - 1)], axis=1)

    def mixer(first, layer_idx, lp, h, carry, conv_all, ssm_all, kv_idx,
              win_idx):
        """Mixer `layer_idx` (= `first`, or a later repeat of it in a
        rolled section: then traced, as `kv_idx` and `win_idx` are) on
        `carry` = (x, pending, kv_cache, win_cache, moe stats) and its
        own state arrays. Returns (h, carry, conv_all, ssm_all, kept)."""
        x, pending, kv_cache, win_cache, stats = carry
        kind = config.layer_kind(first)
        kept = None
        if has_own_norm(config, first):  # else: its block's first
            h = stream_norm(x, lp["norm"], config, bias=lp.get("norm_b"))
        if kind == "M":
            conv = jnp.where(fresh[:, None, None], 0, conv_all[slots])
            ssm = jnp.where(fresh[:, None, None, None], 0, ssm_all[slots])
            out, conv, ssm = mamba_prefill(h, lp, config, conv, ssm, valid,
                                           ssm_path)
            conv_all = conv_all.at[slots].set(conv, mode="drop")
            ssm_all = ssm_all.at[slots].set(ssm, mode="drop")
        elif kind == "S":
            conv = jnp.where(fresh[:, None, None], 0, conv_all[slots])
            ssm = jnp.where(fresh[:, None, None], 0, ssm_all[slots])
            out, conv, ssm, kept = mamba1_prefill(h, lp, config, conv, ssm,
                                                  valid)
            conv_all = conv_all.at[slots].set(conv, mode="drop")
            ssm_all = ssm_all.at[slots].set(ssm, mode="drop")
        elif kind == "C":
            conv = jnp.where(fresh[:, None, None], 0, conv_all[slots])
            out, conv = short_conv_prefill(h, lp, config, conv, valid)
            conv_all = conv_all.at[slots].set(conv, mode="drop")
        elif kind == "*":
            with jax.named_scope(ATTENTION_SCOPES[kind]):
                q, k, v = _qkv(h, lp, config, kind, positions)
                kv_cache = write_kv_pages(
                    kv_cache, kv_idx, _as_stored(k, kv_cache),
                    _as_stored(v, kv_cache), block_tables, positions, valid)
                attn = attention(q, kv_cache, kv_idx, block_tables,
                                 positions, kv_lens, **scaled)
                out = _attention_out(attn, lp, config, layer_idx)
            kept = (k, v)
        elif kind == "W":
            with jax.named_scope(ATTENTION_SCOPES[kind]):
                q, k, v = _qkv(h, lp, config, kind, positions)
                win_cache = write_kv_pages(win_cache, win_idx, k, v,
                                           win_tables, win_pos, valid)
                attn = win_attention(q, win_cache, win_idx, win_tables,
                                     win_pos, win_lens,
                                     window=config.sliding_window, **scaled)
                out = _attention_out(attn, lp, config, layer_idx)
        elif kind == "X":
            with jax.named_scope(ATTENTION_SCOPES[kind]):
                q = _cross_query(h, lp, config)
                at = config.shared_kv_layer(first)
                if first >= tail:
                    attn = decode_attention(q, kv_cache, at, block_tables,
                                            kv_lens, *shared, **scaled)
                else:  # `all_logits`: every position reads the pages
                    attn = attention(q, kv_cache, at, block_tables,
                                     positions, kv_lens, **scaled)
                out = _attention_out(attn, lp, config, layer_idx)
        elif kind == "G":
            out = memory_gate_mixer(h, memory, lp)
        elif kind == "L":
            kv_cache, out = latent_prefill(h, lp, config, kv_cache, kv_idx,
                                           block_tables, positions, kv_lens,
                                           valid, attention_fn)
        elif kind == "D":
            out = dense_mixer(h, lp)
        else:
            out, layer_stats = moe_mixer(h, lp, config, valid, gmm_path)
            stats = stats + layer_stats
        if config.sandwich_norm:
            out = rms_norm(out, lp["post_norm"], config.rms_eps)
        x, pending = _join(x, pending, _branch(out, config), config, first)
        return (h, (x, pending, kv_cache, win_cache, stats), conv_all,
                ssm_all, kept)

    carry = (x, None, kv_cache, win_cache, stats)
    entries, layers = config.layer_entries, params["layers"]
    h = None
    e = conv_at = ssm_at = 0
    while e < len(entries):
        first, repeats, stride = entries[e]
        if first == tail:
            carry = (at_last(carry[0]), *carry[1:])
            memory = at_last(memory)
            shared = tuple(at_last(a) for a in shared)
        if repeats == 1:
            kind = config.layer_kind(first)
            n_conv, n_ssm = _state_counts(kind)
            h, carry, conv_all, ssm_all, kept = mixer(
                first, first, layers[e], h, carry,
                conv_out[conv_at] if n_conv else None,
                ssm_out[ssm_at] if n_ssm else None,
                _group_index(config, "*L", first, 0, 0),
                _group_index(config, "W", first, 0, 0))
            if n_conv:
                conv_out[conv_at] = conv_all
            if n_ssm:
                ssm_out[ssm_at] = ssm_all
            if first == config.memory_layer:
                memory = kept
            elif kind == "*":
                shared = kept
            e, conv_at, ssm_at = e + 1, conv_at + n_conv, ssm_at + n_ssm
            continue
        # a rolled section: its period is the body of one scan
        period = config.layer_pattern[first:first + stride]
        n_conv, n_ssm = _state_counts(period)

        def body(walk, xs, first=first, stride=stride, period=period):
            (x, kv_cache, win_cache), convs, ssms = walk
            r, lps = xs
            convs, ssms = list(convs), list(ssms)
            # no expert layer in a rolled section: no statistics
            inner, ci, si = (x, None, kv_cache, win_cache, None), 0, 0
            for j, kind in enumerate(period):
                has_conv, has_ssm = _state_counts(kind)
                _, inner, conv_all, ssm_all, _ = mixer(
                    first + j, first + j + r * stride, lps[j], None, inner,
                    convs[ci][r] if has_conv else None,
                    ssms[si][r] if has_ssm else None,
                    _group_index(config, "*L", first + j, stride, r),
                    _group_index(config, "W", first + j, stride, r))
                if has_conv:
                    convs[ci] = convs[ci].at[r].set(conv_all)
                if has_ssm:
                    ssms[si] = ssms[si].at[r].set(ssm_all)
                ci, si = ci + has_conv, si + has_ssm
            x, _, kv_cache, win_cache, _ = inner
            return ((x, kv_cache, win_cache), tuple(convs), tuple(ssms)), None

        (walked, convs, ssms), _ = jax.lax.scan(
            body,
            (carry[0:1] + carry[2:4],
             tuple(conv_out[conv_at:conv_at + n_conv]),
             tuple(ssm_out[ssm_at:ssm_at + n_ssm])),
            (jnp.arange(repeats), tuple(layers[e:e + stride])))
        carry = (walked[0], None, *walked[1:], carry[4])
        conv_out[conv_at:conv_at + n_conv] = convs
        ssm_out[ssm_at:ssm_at + n_ssm] = ssms
        e, conv_at, ssm_at = e + stride, conv_at + n_conv, ssm_at + n_ssm
    x, _, kv_cache, win_cache, stats = carry
    state = {"conv": conv_out, "ssm": ssm_out}
    if not all_logits:
        x = (x if tail < config.n_layers else at_last(x))[:, 0]
    if window is not None:
        kv_cache = (kv_cache, win_cache)
    return kv_cache, state, _head(x, params, config), stats


def forward_hybrid_decode(params, config: ModelConfig, tokens, positions,
                          kv_cache, state, block_tables, kv_lens, active,
                          decode_attention_fn=None, ssm_path: str = "xla",
                          gmm_path: str = "xla", window=None):
    """One token for every slot (row i = slot i), KV writes deferred to
    one scatter a page group for all its attention layers as in
    `forward_decode`. Returns (kv_cache, state, logits [S, 1, vocab], moe
    stats); `window` as in `forward_hybrid`. A cross-attention mixer
    reads the shared layer's pages and this step's key and value of that
    layer, and adds nothing to the scatter. The mixers are walked by
    entries as in `forward_hybrid`; a rolled section's scan carries the
    stream, the section's stacked state and, where it has window layers,
    their pool, which each of them writes its own token into."""
    attn_fn = decode_attention_fn or (
        paged_attention_decode_latent_xla if config.has_latent_layers
        else paged_attention_decode_xla)
    attn_lens = jnp.where(active, kv_lens, 0)
    if window is not None:
        win_cache, win_tables, win_pos, win_lens = _window_frame(
            window, positions, kv_lens)
        win_attn_lens = jnp.where(active, win_lens, 0)
    scaled = attention_scale(config)
    x = _embed(params, config, tokens)  # [S, h]
    conv_out, ssm_out = list(state["conv"]), list(state["ssm"])
    stats = jnp.zeros((moe_stats_size(config),), jnp.int32)
    ks, vs, win_ks, win_vs = [], [], [], []
    memory = None

    def mixer(first, layer_idx, lp, h, carry, conv_all, ssm_all, kv_idx,
              win_idx, win_pool=None):
        """As `forward_hybrid`'s, on `carry` = (x, pending, moe stats);
        `win_pool`: the window group's pool as a rolled section carries
        it (None: the step's own, which the deferred scatter writes).
        Returns (h, carry, conv_all, ssm_all, kept): `kept` the keys and
        values (or latent row) an attention mixer has for the scatter,
        or the memory layer's scan output."""
        x, pending, stats = carry
        kind = config.layer_kind(first)
        kept = None
        if has_own_norm(config, first):  # else: its block's first
            h = stream_norm(x, lp["norm"], config, bias=lp.get("norm_b"))
        if kind == "M":
            out, conv_all, ssm_all = mamba_decode(
                h, lp, config, conv_all, ssm_all, active, ssm_path)
        elif kind == "S":
            out, conv_all, ssm_all, kept = mamba1_decode(
                h, lp, config, conv_all, ssm_all, active)
        elif kind == "C":
            out, conv_all = short_conv_decode(h, lp, config, conv_all,
                                              active)
        elif kind == "*":
            with jax.named_scope(ATTENTION_SCOPES[kind]):
                q, k, v = _qkv(h[:, None, :], lp, config, kind,
                               positions[:, None])
                attn = attn_fn(q, kv_cache, kv_idx, block_tables, attn_lens,
                               k, v, **scaled)
                out = _attention_out(attn, lp, config, layer_idx)[:, 0]
            kept = (k, v)
        elif kind == "W":
            with jax.named_scope(ATTENTION_SCOPES[kind]):
                q, k, v = _qkv(h[:, None, :], lp, config, kind,
                               positions[:, None])
                attn = attn_fn(q, win_cache if win_pool is None else win_pool,
                               win_idx, win_tables, win_attn_lens, k, v,
                               window=config.sliding_window, **scaled)
                out = _attention_out(attn, lp, config, layer_idx)[:, 0]
            kept = (k, v)
        elif kind == "X":
            with jax.named_scope(ATTENTION_SCOPES[kind]):
                at = config.shared_kv_layer(first)
                attn = attn_fn(_cross_query(h[:, None, :], lp, config),
                               kv_cache, at, block_tables, attn_lens,
                               ks[at], vs[at], **scaled)
                out = _attention_out(attn, lp, config, layer_idx)[:, 0]
        elif kind == "G":
            out = memory_gate_mixer(h, memory, lp)
        elif kind == "L":
            out, kept = latent_decode(h, lp, config, kv_cache, kv_idx,
                                      block_tables, positions, attn_lens,
                                      attn_fn)
        elif kind == "D":
            out = dense_mixer(h, lp)
        else:
            out, layer_stats = moe_mixer(h[:, None, :], lp, config,
                                         active[:, None], gmm_path)
            out = out[:, 0]
            stats = stats + layer_stats
        if config.sandwich_norm:
            out = rms_norm(out, lp["post_norm"], config.rms_eps)
        x, pending = _join(x, pending, _branch(out, config), config, first)
        return h, (x, pending, stats), conv_all, ssm_all, kept

    carry = (x, None, stats)
    entries, layers = config.layer_entries, params["layers"]
    h = None
    e = conv_at = ssm_at = 0
    while e < len(entries):
        first, repeats, stride = entries[e]
        if repeats == 1:
            kind = config.layer_kind(first)
            n_conv, n_ssm = _state_counts(kind)
            h, carry, conv_all, ssm_all, kept = mixer(
                first, first, layers[e], h, carry,
                conv_out[conv_at] if n_conv else None,
                ssm_out[ssm_at] if n_ssm else None,
                _group_index(config, "*L", first, 0, 0),
                _group_index(config, "W", first, 0, 0))
            if n_conv:
                conv_out[conv_at] = conv_all
            if n_ssm:
                ssm_out[ssm_at] = ssm_all
            if first == config.memory_layer:
                memory = kept
            elif kind == "*":
                ks.append(kept[0])
                vs.append(kept[1])
            elif kind == "L":
                ks.append(kept)
            elif kind == "W":
                win_ks.append(kept[0])
                win_vs.append(kept[1])
            e, conv_at, ssm_at = e + 1, conv_at + n_conv, ssm_at + n_ssm
            continue
        period = config.layer_pattern[first:first + stride]
        n_conv, n_ssm = _state_counts(period)

        def body(walk, xs, first=first, stride=stride, period=period):
            x, pool, convs, ssms = walk
            r, lps = xs
            convs, ssms = list(convs), list(ssms)
            inner, ci, si = (x, None, None), 0, 0
            for j, kind in enumerate(period):
                has_conv, has_ssm = _state_counts(kind)
                win_idx = _group_index(config, "W", first + j, stride, r)
                _, inner, conv_all, ssm_all, kept = mixer(
                    first + j, first + j + r * stride, lps[j], None, inner,
                    convs[ci][r] if has_conv else None,
                    ssms[si][r] if has_ssm else None, None, win_idx, pool)
                if has_conv:
                    convs[ci] = convs[ci].at[r].set(conv_all)
                if has_ssm:
                    ssms[si] = ssms[si].at[r].set(ssm_all)
                ci, si = ci + has_conv, si + has_ssm
                if kind == "W":
                    # a rolled section's window layers write their own
                    # token here, a layer a scatter on the carried pool:
                    # ONE scatter over a stack of layers makes XLA lay
                    # the pool out with the layers next to the lanes
                    # ([8 layers, .., 2 rows, 640]: whole (8, 128)
                    # tiles), and every step copies 2.1 GB in and out of
                    # the layout the kernels read (the compiler, PR 52)
                    pool = write_kv_pages(pool, win_idx, *kept, win_tables,
                                          win_pos[:, None], active[:, None])
            return (inner[0], pool, tuple(convs), tuple(ssms)), None

        (x, pool, convs, ssms), _ = jax.lax.scan(
            body,
            (carry[0], win_cache if "W" in period else None,
             tuple(conv_out[conv_at:conv_at + n_conv]),
             tuple(ssm_out[ssm_at:ssm_at + n_ssm])),
            (jnp.arange(repeats), tuple(layers[e:e + stride])))
        if "W" in period:
            win_cache = pool
        carry = (x, None, carry[2])
        conv_out[conv_at:conv_at + n_conv] = convs
        ssm_out[ssm_at:ssm_at + n_ssm] = ssms
        e, conv_at, ssm_at = e + stride, conv_at + n_conv, ssm_at + n_ssm
    x, _, stats = carry
    if config.has_latent_layers:
        kv_cache = write_latent_stack(kv_cache, jnp.stack(ks), block_tables,
                                      positions, active)
    elif ks:
        kv_cache = write_kv_stack(
            kv_cache, _as_stored(jnp.stack(ks), kv_cache),
            _as_stored(jnp.stack(vs), kv_cache), block_tables,
            positions[:, None], active[:, None])
    if window is not None:
        if win_ks:  # of mixers of their own: one scatter for them all
            win_cache = write_kv_stack(win_cache, jnp.stack(win_ks),
                                       jnp.stack(win_vs), win_tables,
                                       win_pos[:, None], active[:, None])
        kv_cache = (kv_cache, win_cache)
    state = {"conv": conv_out, "ssm": ssm_out}
    return kv_cache, state, _head(x, params, config)[:, None, :], stats


# ---------------------------------------------------------------------------
# The stack behind ModelRunner's step programs
# ---------------------------------------------------------------------------


class HybridSteps:
    """A `layer_pattern` stack as `ModelRunner`'s step programs call
    every stack (the one signature: models/transformer.DenseSteps).
    `cache` = (pools, state): `pools` is `(full,)`, or `(full, window)`
    for a stack with window layers, whose `tables` are then (full
    tables, window tables, window base [B]); `state` is
    `make_state_cache`'s. Every step gives its experts' statistics back
    (`moe_stats_size` counters). Nothing here scores several positions a
    step (`cache_plan`: `score_positions`), and no adapter has targets
    on these layers."""

    spec = spec_attention_fn = None
    lora = False

    def __init__(self, config: ModelConfig, kernels: dict,
                 attention_fn=None) -> None:
        from ..ops import kernel_path

        self.config = config
        user = attention_fn is not None
        # a single-stack latent pool has kernels of its own
        latent = "_latent" if config.has_latent_layers else ""
        self.attention_fn = (attention_fn if user
                             else kernels["prefill" + latent])
        self.decode_attention_fn = (None if user
                                    else kernels["decode" + latent])
        # page groups whose prefill layers run through `attention_fn`
        self.attention_groups = tuple(
            group for group, layers in (("full", config.kv_layers),
                                        ("window", config.window_kv_layers))
            if layers)
        self.ssm_path = kernel_path("DYNT_SSM")
        self.gmm_path = kernel_path("DYNT_MOE_GMM")
        self.stats_size = moe_stats_size(config)

    def make_state(self, slots: int) -> dict:
        return make_state_cache(self.config, slots)

    def kernel_paths(self) -> dict:
        """This stack's own slots of `ModelRunner.kernel_paths`: the
        decode state update and, where it has Mamba layers, the prefill
        scan (both DYNT_SSM; a launch whose shapes the scan kernel
        refuses still takes the XLA form:
        dynamo_ssm_scan_launches_total) and the experts' grouped matmul
        (DYNT_MOE_GMM)."""
        paths = {"ssm_update": self.ssm_path}
        if self.config.ssm_layers:
            paths["ssm_scan"] = self.ssm_path
        return {**paths, "expert_gmm": self.gmm_path}

    @staticmethod
    def _unpack(pools, tables):
        """(full pool, full tables, `forward_hybrid*`'s `window=`) of the
        runner's tuples."""
        if len(pools) == 1:
            (kv,), (table,) = pools, tables
            return kv, table, None
        (kv, win), (table, win_tables, win_base) = pools, tables
        return kv, table, (win, win_tables, win_base)

    def prefill(self, params, cache, tokens, positions, tables, kv_lens,
                valid, last_idx, slots, lora=None, lora_idx=None,
                extra_embeds=None):
        """A chunk a row: (cache, logits [B, V] of each row's `last_idx`
        only, so that no [rows x T, vocab] float32 exists on this path,
        moe stats)."""
        pools, state = cache
        kv, table, window = self._unpack(pools, tables)
        kv, state, last, stats = forward_hybrid(
            params, self.config, tokens, positions, kv, state, slots, table,
            kv_lens, valid, last_idx, attention_fn=self.attention_fn,
            gmm_path=self.gmm_path, window=window, ssm_path=self.ssm_path,
            decode_attention_fn=self.decode_attention_fn)
        return (kv if window is not None else (kv,), state), last, stats

    def decode(self, params, cache, tokens, positions, tables, kv_lens,
               active, lora=None, lora_idx=None):
        """One token a slot: (cache, logits [B, 1, V], moe stats)."""
        pools, state = cache
        kv, table, window = self._unpack(pools, tables)
        kv, state, logits, stats = forward_hybrid_decode(
            params, self.config, tokens, positions, kv, state, table,
            kv_lens, active, decode_attention_fn=self.decode_attention_fn,
            ssm_path=self.ssm_path, gmm_path=self.gmm_path, window=window)
        return (kv if window is not None else (kv,), state), logits, stats
