"""Model family configs.

The reference orchestrates external engines and never owns model code; the
TPU build owns the engine, so model families live here. Flagship families
mirror BASELINE.json configs: Qwen3-class (RMSNorm + SwiGLU + GQA + QK-norm),
Llama-3-class (same minus QK-norm), plus a tiny test model for CI on the
8-device virtual CPU mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny-test"
    vocab_size: int = 512
    hidden: int = 64
    n_layers: int = 2
    n_q_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    mlp_hidden: int = 128
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    qk_norm: bool = False  # Qwen3-style per-head RMSNorm on q/k
    tie_embeddings: bool = True
    max_context: int = 8192
    dtype: str = "bfloat16"
    # MoE (0 experts = dense)
    n_experts: int = 0
    n_experts_active: int = 0
    expert_mlp_hidden: int = 0
    # Static per-expert buffer headroom for capacity dispatch (tokens per
    # expert = ceil(cf * t * k / e)); overflow tokens drop that expert.
    moe_capacity_factor: float = 1.25
    # DeepSeek-style MoE shape: the first K layers use a dense MLP instead
    # of experts, always-active shared experts add a dense SwiGLU of width
    # n_shared_experts * expert_mlp_hidden, and routing weights are the
    # raw softmax-over-all-experts scores (norm_topk=False) times a scale.
    first_k_dense: int = 0
    n_shared_experts: int = 0
    moe_norm_topk: bool = True
    moe_routed_scale: float = 1.0
    # DeepSeek-V3/R1 routing: sigmoid scores + a learned per-expert
    # selection bias (e_score_correction_bias; selection only — weights
    # use the unbiased scores) and node-limited group routing.
    moe_scoring: str = "softmax"  # softmax | sigmoid
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # Multimodal: placeholder token id for spliced image embeddings
    # (-1 = text-only) and the rows one image expands to (must match the
    # paired vision encoder's n_image_tokens)
    image_token_id: int = -1
    n_image_tokens: int = 0
    # MLA (DeepSeek-class latent attention); 0 = standard GQA/MHA
    mla_kv_lora_rank: int = 0
    mla_q_lora_rank: int = 0
    mla_rope_head_dim: int = 0
    mla_nope_head_dim: int = 0
    mla_v_head_dim: int = 0
    # gpt-oss family (ref workload: recipes/ gpt-oss entries; parsers
    # lib/parsers/src/tool_calling/harmony/). attn_sinks is the family
    # marker: learned per-head sink logits join the softmax denominator;
    # even-indexed layers use a sliding window (HF layer_types pattern);
    # projections carry biases; experts use the clipped gated-swiglu
    # (clamp + sigmoid(alpha*x)) with fused gate_up weights; rope is YaRN.
    attn_sinks: bool = False
    # The window of a layer that has one (0 = none): the "W" layers of a
    # `layer_pattern`, the even layers of a gpt-oss stack (attn_sinks)
    sliding_window: int = 0
    attn_bias: bool = False
    swiglu_limit: float = 0.0  # 0 = plain silu*up
    swiglu_alpha: float = 1.702
    rope_yarn_factor: float = 0.0  # 0 = no yarn scaling
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_orig_max: int = 4096
    # HF `_compute_yarn_parameters`: round the correction range to whole
    # dimensions (its default; gpt-oss states truncate=False)
    rope_yarn_truncate: bool = False
    # Hybrid stacks: a layer is ONE mixer, its kind read off
    # `layer_pattern`, seven kinds ("M" Mamba-2, "C" a gated short
    # convolution `conv_kernel` taps wide over `hidden` channels, "E"
    # routed experts, "D" a dense SwiGLU `mlp_hidden` wide, "*" full
    # attention (q and k normed per head where `qk_norm`), "W" attention
    # over the last `sliding_window` positions, "L" latent attention
    # (the `mla_*` sizes: one row of `mla_kv_lora_rank` latent +
    # `mla_rope_head_dim` rope-key values a cached token, shared by all
    # heads); "" = every layer is attention + MLP). A pre-norm block of
    # a published model is `mixers_per_layer` of them (attention, then
    # experts: 2). Rope is a kind's: the default table on "W" and "L",
    # YaRN on "*" where `rope_yarn_factor` is set, none on a kind that
    # `rope_kinds` leaves out. Only attention layers that WRITE keys
    # and values have KV pages, and each such kind has a page group of
    # its own (`kv_layers` the full group, "*" or "L"; `window_kv_layers`
    # the window group: a cache layer index counts within its group; an
    # "X" layer reads a "*" layer's pages and has none); "M", "S" and
    # "C" layers keep a fixed-size state per scheduler slot, "C" the
    # conv's carry alone (models/hybrid.py). "S", "G" and "X" are
    # phi4flash's, further down.
    layer_pattern: str = ""
    mixers_per_layer: int = 1
    # `layer_pattern` stacks: a mixer's OUTPUT is normed too before it
    # joins the residual stream (x <- x + RMSNorm(Mixer(RMSNorm(x))))
    sandwich_norm: bool = False
    # sigmoid routers: the learned per-expert selection bias (False: the
    # top-k is taken on the raw scores)
    moe_selection_bias: bool = True
    # what the chosen scores' sum is given before they are divided by it
    # (`moe_norm_topk`): the family's own (lfm2_moe states 1e-6)
    moe_renorm_eps: float = 1e-20
    use_rope: bool = True  # nemotron_h attention has no positional term
    mlp_act: str = "swiglu"  # swiglu | relu2 (non-gated: down(relu(up x)^2))
    shared_expert_hidden: int = 0  # 0 = n_shared_experts * expert width
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state: int = 0
    conv_kernel: int = 4
    ssm_chunk: int = 128
    ssm_dt_min: float = 0.001
    ssm_dt_max: float = 0.1
    ssm_dt_floor: float = 1e-4
    ssm_state_dtype: str = "float32"
    # granitemoehybrid's four multipliers (`layer_pattern` stacks only;
    # the dense decoder refuses a preset that sets one): the embedding
    # x `embedding_multiplier`, every mixer's branch x
    # `residual_multiplier` before it is added, attention scores
    # `attention_multiplier` x q.k (0 = 1/sqrt(head_dim)), logits /
    # `logits_scaling`
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # cohere2_moe's block (`layer_pattern` stacks only; each at its
    # default for every other family, where nothing of it is traced).
    # `parallel_block`: the `mixers_per_layer` mixers of a block all read
    # ONE normed input, the first mixer's `norm` (the others have none),
    # and join the residual stream together: x <- x + a + m.
    # `norm_kind` "layer": every norm of the stack takes the mean off
    # first (float32, a weight, no bias, eps `rms_eps`). `rope_kinds`:
    # the attention kinds that rope ("" = every kind, where `use_rope`);
    # a kind left out has no positional term. `rope_interleaved`: rope
    # pairs lanes (2i, 2i+1) (GPT-J's convention), not (i, i + hd/2).
    # `shared_expert_mean`: the shared experts' output is the MEAN of
    # the `n_shared_experts` experts' outputs, not their sum.
    parallel_block: bool = False
    norm_kind: str = "rms"  # rms | layer
    # phi4flash's (SambaY's) stack, each at its default for every other
    # family, where nothing of it is traced. Three more mixer kinds of a
    # `layer_pattern`: "S" Mamba-1 (a selective scan over `mamba_inner`
    # channels x `ssm_state` columns, a decay a channel AND a column; dt
    # through a bottleneck of `mamba_dt_rank`), "G" a gated memory unit
    # (out = (m * silu(h W_1)) W_2, m the pre-gate output of the LAST "S"
    # mixer before the first "G": `memory_layer`) and "X" cross-attention
    # onto the keys and values the last "*" layer before it wrote (a
    # query projection alone; it owns no pages: `shared_kv_layer`).
    # `diff_attention`: every attention kind is DIFFERENTIAL (heads in
    # pairs, two softmaxes, a1 - lambda a2, a sub-norm over 2 x head_dim
    # lanes; models/hybrid.py `_diff_epilogue`). `norm_bias`: a
    # LayerNorm (`norm_kind` "layer") has a bias beside its weight, and
    # with `attn_bias` a `layer_pattern` stack's attention projections
    # have biases too (bqkv, bo).
    mamba_dt_rank: int = 0
    diff_attention: bool = False
    norm_bias: bool = False
    # `layer_pattern` stacks: (mixers a period, repeats) of consecutive
    # sections that cover the stack. A section of several repeats is ONE
    # traced body run as a loop (`lax.scan`) over its repeats, its
    # weights and per-slot state stored stacked along a leading axis
    # (`layer_entries`): 64 unrolled mixers trace, lower, compile and
    # load as 64, and a cell has some twenty programs to build inside
    # its run's budget. () = every mixer unrolled, each with arrays of
    # its own (every other family).
    layer_sections: tuple[tuple[int, int], ...] = ()
    rope_kinds: str = ""
    rope_interleaved: bool = False
    shared_expert_mean: bool = False
    # The chip's share of an expert-parallel deployment: (lo, hi) of the
    # published experts held here. The router keeps its n_experts outputs
    # and its top-k; a token routed to an absent expert gets nothing from
    # it. None = all of them.
    experts_held: Optional[tuple[int, int]] = None

    @property
    def is_hybrid(self) -> bool:
        return bool(self.layer_pattern)

    @property
    def multipliers(self) -> dict:
        """The multipliers this preset moves off their defaults, by
        field name."""
        return {name: getattr(self, name)
                for name, default in (("embedding_multiplier", 1.0),
                                      ("residual_multiplier", 1.0),
                                      ("attention_multiplier", 0.0),
                                      ("logits_scaling", 1.0))
                if getattr(self, name) != default}

    def layer_kind(self, layer_idx: int) -> str:
        return self.layer_pattern[layer_idx] if self.layer_pattern else "*"

    @property
    def kv_layers(self) -> tuple[int, ...]:
        """Model layer index of each layer of the paged KV cache's FULL
        group (a sequence holds a page for every 16 positions)."""
        return tuple(i for i in range(self.n_layers)
                     if self.layer_kind(i) in "*L")

    @property
    def window_kv_layers(self) -> tuple[int, ...]:
        """The same for the WINDOW group: layers that can see only the
        last `sliding_window` positions, so that a page behind them goes
        back to its pool while the sequence lives (engine/pages.py)."""
        return tuple(i for i in range(self.n_layers)
                     if self.layer_kind(i) == "W")

    @property
    def has_latent_layers(self) -> bool:
        """Latent attention in a `layer_pattern` stack: the full group's
        pool is ONE stack of `kv_cache_head_dim`-wide rows
        (models/hybrid.py `latent_prefill`, `latent_decode`)."""
        return "L" in self.layer_pattern

    @property
    def state_layers(self) -> tuple[int, ...]:
        """Layers that keep a state per scheduler slot: a conv carry
        each, and the "M" ones (`ssm_layers`) an SSM state beside it."""
        return tuple(i for i in range(self.n_layers)
                     if self.layer_kind(i) in "MCS")

    @property
    def ssm_layers(self) -> tuple[int, ...]:
        """Layers with an SSM state beside the conv's carry: Mamba-2
        ("M": [heads, head_dim, state]) and Mamba-1 ("S": [state,
        channels])."""
        return tuple(i for i in range(self.n_layers)
                     if self.layer_kind(i) in "MS")

    @property
    def layer_entries(self) -> tuple[tuple[int, int, int], ...]:
        """(first mixer, repeats, stride) of each entry of a parameter
        tree's `layers` list: a mixer of its own (repeats 1), or where
        `layer_sections` rolls a section, position j of its period:
        mixers first, first + stride, .. share one body, their arrays
        stacked along a leading axis of `repeats`. A rolled section
        holds the kinds S, D, W, G and X only, whole blocks, and neither
        the memory layer nor a layer that writes the full page group
        (the forwards thread those as values of the step)."""
        if not self.layer_sections:
            return tuple((i, 1, 0) for i in range(self.n_layers))
        entries, start = [], 0
        for period, repeats in self.layer_sections:
            body = self.layer_pattern[start:start + period]
            end = start + period * repeats
            if (self.layer_pattern[start:end] != body * repeats
                    or period % self.mixers_per_layer):
                raise ValueError(
                    f"{self.name}: layer_sections {self.layer_sections} "
                    f"do not repeat whole blocks of {self.layer_pattern}")
            if repeats > 1 and (body.strip("SDWGX") or self.parallel_block
                                or start <= self.memory_layer < end):
                raise ValueError(
                    f"{self.name}: a rolled section of {body!r} (kinds "
                    "S D W G X, never the memory layer)")
            entries += [(start + j, repeats, period) for j in range(period)]
            start = end
        if start != self.n_layers:
            raise ValueError(f"{self.name}: layer_sections cover {start} "
                             f"of {self.n_layers} mixers")
        return tuple(entries)

    @property
    def shared_kv_readers(self) -> tuple[int, ...]:
        """The "X" layers: each reads the pages of the full group's
        layer `shared_kv_layer` names and writes none."""
        return tuple(i for i in range(self.n_layers)
                     if self.layer_kind(i) == "X")

    def shared_kv_layer(self, layer_idx: int) -> int:
        """The full group's cache layer an "X" mixer reads: that of the
        last "*" mixer before it."""
        return self.layer_pattern[:layer_idx].count("*") - 1

    @property
    def memory_layer(self) -> int:
        """The "S" mixer whose pre-gate output the "G" mixers read: the
        last one before the first "G" (-1: the stack has no "G")."""
        first = self.layer_pattern.find("G")
        return self.layer_pattern.rfind("S", 0, max(first, 0))

    @property
    def cross_decoder_start(self) -> int:
        """The first mixer of the stack's tail that caches nothing and
        carries nothing in time ("G", "X" and the feed-forwards between
        them), so that a prefill launch runs it on each row's LAST
        position alone; `n_layers` where the stack has no such tail."""
        first = min((i for i in (self.layer_pattern.find("G"),
                                 self.layer_pattern.find("X")) if i >= 0),
                    default=-1)
        if first < 0 or self.layer_pattern[first:].strip("GXDE"):
            return self.n_layers
        return first

    @property
    def diff_rows(self) -> int:
        """Rows of the pool a token's KV PAIRS of a differential-attention
        stack lie in: a row's kv heads are one "head" of the kernels,
        which read a page's heads out of 32-bit words two at a time and
        whose pool's head axis is tiled by the chip: 2, 4 or 8 rows, or
        a multiple of 16, are whole tiles, 10 are padded to 16 and
        Mosaic refuses a page's slice of 10 ("Slice shape along
        dimension 4 must be aligned to tiling (8), but is 10": the
        compiler, PR 52). The most of 8, 4, 2 that divide the pairs (10
        pairs: 2 rows of 5 pairs, 640 lanes), else 1."""
        pairs = self.n_kv_heads // 2
        return next(r for r in (8, 4, 2, 1) if pairs % r == 0)

    @property
    def attn_geometry(self) -> tuple[int, int, int]:
        """(query heads, kv heads, head_dim) as the attention kernels and
        the pool see a `layer_pattern` stack's layers: the model's own,
        or with `diff_attention` `diff_rows` kv "heads", each a row of
        whole KV pairs side by side, and a row's query heads padded to a
        power of two, each handed over with its values in the lanes its
        own kv head has in the row and zeros in the others
        (models/hybrid.py `_wide_query`)."""
        if not self.diff_attention:
            return self.n_q_heads, self.n_kv_heads, self.head_dim
        rows = self.diff_rows
        per_row = self.n_q_heads // rows
        padded = 1 << (per_row - 1).bit_length()
        return rows * padded, rows, self.n_kv_heads // rows * self.head_dim

    @property
    def held_experts(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def is_gptoss(self) -> bool:
        return self.attn_sinks

    def layer_sliding_window(self, layer_idx: int) -> int:
        """Per-layer window (0 = full attention): the layer's kind for a
        `layer_pattern` model; gpt-oss (attn_sinks) alternates
        sliding/full starting with sliding at layer 0 (HF layer_types)."""
        if self.layer_pattern:
            return (self.sliding_window
                    if self.layer_pattern[layer_idx] == "W" else 0)
        if not self.attn_sinks or not self.sliding_window:
            return 0
        return self.sliding_window if layer_idx % 2 == 0 else 0

    def layer_is_moe(self, layer_idx: int) -> bool:
        """DeepSeek-style mixed stacks: layers below first_k_dense keep a
        dense MLP; the rest route through experts."""
        if self.layer_pattern:
            return self.layer_pattern[layer_idx] == "E"
        return self.n_experts > 0 and layer_idx >= self.first_k_dense

    @property
    def q_dim(self) -> int:
        return self.n_q_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    # -- MLA (latent attention) cache geometry ----------------------------

    @property
    def is_mla(self) -> bool:
        return self.mla_kv_lora_rank > 0

    @property
    def mla_qk_head_dim(self) -> int:
        return self.mla_nope_head_dim + self.mla_rope_head_dim

    @property
    def kv_cache_kv_dims(self) -> int:
        """Size of the kv axis of the paged cache (2 = separate K and V
        stacks; 1 for MLA's single latent stack)."""
        return 1 if self.is_mla else 2

    @property
    def kv_heads_per_lane_tile(self) -> int:
        """kv heads that share one row of the pool (128 lanes, or with
        differential attention as many as a row's KV pairs fill:
        `diff_rows`): 1, or for a `layer_pattern` stack whose head_dim
        divides a lane tile (64: 2) and whose kv heads fill whole tiles,
        128 / head_dim. The TPU
        tiles a pool's last two axes as (8 or 16, 128): [.., 8, 64] is
        padded to 128 lanes, twice the memory, and Mosaic refuses a
        page's 64-lane slice ("Slice shape along dimension 5 must be
        aligned to tiling (128), but is 64": the compiler, PR 44). So a
        token's row is stored [kv heads / 2, 128], head 2j in lanes
        0..63 and head 2j + 1 in 64..127: the same bytes in the same
        order as [kv heads, 64] row-major, read back by a reshape."""
        if self.diff_attention:
            # whole KV pairs side by side, `diff_rows` rows a token
            return self.n_kv_heads // self.diff_rows
        if (not self.layer_pattern or self.is_mla or self.head_dim >= 128
                or 128 % self.head_dim):
            return 1
        per = 128 // self.head_dim
        return per if self.n_kv_heads % per == 0 else 1

    @property
    def kv_cache_heads(self) -> int:
        return (1 if self.is_mla
                else self.n_kv_heads // self.kv_heads_per_lane_tile)

    @property
    def kv_cache_head_dim(self) -> int:
        """Per-token per-'head' cache width: MLA caches the compressed
        latent + shared rope key instead of per-head K/V — the memory win
        that lets DeepSeek-class models hold long contexts."""
        if self.is_mla:
            width = self.mla_kv_lora_rank + self.mla_rope_head_dim
            # A `layer_pattern` stack's decode kernel streams whole rows:
            # padded to 128 lanes (576 -> 640), the pool keeps the
            # row-major layout Mosaic reads in place. Unpadded, the TPU
            # lays [.., pages, 16, 1, 576] out pages-innermost (whichever
            # minor dimension pads least) and a kernel operand would be
            # a copy of the pool a layer a step (PERF.md, PR 38).
            return -(-width // 128) * 128 if self.has_latent_layers \
                else width
        return self.head_dim * self.kv_heads_per_lane_tile


@dataclasses.dataclass(frozen=True)
class CachePlan:
    """What a configuration's cache is, and what it cannot do: the one
    place that says so. `ModelRunner` builds the cache from `groups` and
    `state`; the scheduler, the worker's flags
    (`engine.worker.recurrent_state_refusals`) and every feature that
    moves, reuses or re-scores pages ask a trait and never which family
    the model is. A trait is "" where the cache can, else the reason in
    words, naming the model."""

    # page groups by name, one pool and one block table each: "full" (a
    # page for every 16 positions of a sequence) and, for a stack with
    # window layers, "window" (the last `sliding_window` positions)
    groups: tuple[str, ...] = ("full",)
    # cache layers each group's pool holds: the layers that WRITE pages
    # of the group (`kv_layers`, `window_kv_layers`), and the layers
    # that read them. They differ where layers read pages they do not
    # own (an "X" layer reads the full group's layer that
    # `ModelConfig.shared_kv_layer` names): bytes a token and pool
    # sizes follow `group_layers`, a decode step's page reads
    # `group_readers`. () for a dense stack: every layer of the model
    # writes and reads its own.
    group_layers: tuple[int, ...] = ()
    group_readers: tuple[int, ...] = ()
    # whether a slot keeps state beside its pages: a conv carry or an
    # SSM state a state layer (`models.hybrid.make_state_cache`)
    state: bool = False
    # have a prefix of its pages found again and reused
    reuse_prefix: str = ""
    # have pages moved between workers or tiers: disaggregated transfer,
    # KVBM offload and onboard, parking a preempted sequence
    move_pages: str = ""
    # be scored at several positions a step and rolled back by length
    # (speculative verification)
    score_positions: str = ""
    # be sharded over devices
    shard: str = ""
    # hold an int8 pool; run over int8 or int4 weights
    int8_pool: str = ""
    quantized_weights: str = ""
    # whether a prefill launch's rows x bucket stay inside the token
    # budget (`ModelRunner.bounds_prefill_launches`): "always", "carried"
    # (only where a context runs past one launch, its state carried from
    # launch to launch), or "" for never
    launch_bound: str = ""


def cache_plan(config: ModelConfig) -> CachePlan:
    """The plan of `config`'s cache, a pure function of the
    configuration. A dense stack's is one page group and nothing it
    cannot do but hold an int8 pool where it caches latents (MLA). A
    `layer_pattern` stack's says what its kinds of layer bring: window
    layers a second page group, Mamba-2, Mamba-1 or short-conv layers
    per-slot state, latent layers a single-stack pool; a page group
    holds a cache layer for every layer that WRITES it, and a layer that
    reads another's pages ("X") adds a reader and no pages; where
    several would refuse
    the same thing, the first of those in that order gives the reason."""
    if not config.layer_pattern:
        return CachePlan(int8_pool=(
            f"int8 KV targets standard-attention models ({config.name}: "
            "MLA's latent cache is already compact)"
            if config.is_mla else ""))
    writers = (len(config.kv_layers), len(config.window_kv_layers))
    readers = (writers[0] + len(config.shared_kv_readers), writers[1])
    what = f"{config.name} (layers {config.layer_pattern})"
    windowed, latent = "W" in config.layer_pattern, config.has_latent_layers
    stateful = bool(config.state_layers)
    have = " and ".join(
        name for kind, name in (("M", "Mamba-2"), ("S", "Mamba-1"),
                                ("C", "short-conv"), ("G", "gated-memory"),
                                ("X", "shared-KV cross-attention"),
                                ("E", "expert"), ("L", "latent-attention"))
        if kind in config.layer_pattern)
    window_group = (f"{what} keeps two page groups, and the window group "
                    f"only a sequence's last {config.sliding_window} "
                    "positions (what lay behind is freed)")
    latent_pool = (f"{what} keeps a single-stack latent pool "
                   f"({config.kv_cache_head_dim} values a token)")
    return CachePlan(
        groups=("full", "window") if windowed else ("full",),
        group_layers=writers if windowed else writers[:1],
        group_readers=readers if windowed else readers[:1],
        state=stateful,
        reuse_prefix=(
            f"{window_group}: a prefix hit needs the full group's pages of "
            "the prefix AND the window group's last positions before it, "
            "which nothing keeps" if windowed else
            f"{what} keeps recurrent state per slot: a prefix hit would "
            "skip tokens the state has to see" if stateful else ""),
        move_pages=(
            f"{window_group}: the full group's pages alone resume from "
            "half a cache, and no tier keeps the other half" if windowed
            else
            f"{latent_pool}; page bundles are K and V per kv head "
            "(ops/block_copy.py), and no hand-over or tier of latent "
            "pages is built or tested" if latent else
            f"{what} keeps recurrent state per slot and no state snapshot "
            "travels with its pages: pages alone resume nothing"
            if stateful else ""),
        score_positions=(
            f"{window_group}, which has no multi-position decode path"
            if windowed else
            f"{latent_pool}, whose absorbed decode path scores one "
            "position a step" if latent else
            f"the recurrent state of {what} cannot be rolled back"
            if stateful else ""),
        shard=(f"the per-slot state and the experts of {what} are not "
               "sharded yet (no expert exchange, no sharded scan)"),
        int8_pool=("the int8 pool is not wired into the hybrid decode "
                   f"path of {what}"),
        quantized_weights=(
            "models/quantize.py packs the dense decoder's projections "
            f"only; {what} has {have} matrices it has no layout for"),
        launch_bound=("always" if windowed or latent
                      else "carried" if stateful else ""))


def _blocks(token_mixers: str, dense: int) -> str:
    """A `layer_pattern` of two mixers a block: each token mixer, then a
    dense feed-forward behind the first `dense` and experts behind the
    rest."""
    return "".join(mixer + ("D" if block < dense else "E")
                   for block, mixer in enumerate(token_mixers))


PRESETS: dict[str, ModelConfig] = {
    "tiny-test": ModelConfig(),
    "tiny-moe-test": ModelConfig(
        name="tiny-moe-test", n_experts=4, n_experts_active=2,
        expert_mlp_hidden=128,
    ),
    # Multimodal CI model: token 511 is the image placeholder; 16 rows per
    # image (= tiny-vit-test n_patches)
    "tiny-mm-test": ModelConfig(
        name="tiny-mm-test", image_token_id=511, n_image_tokens=16,
    ),
    # Qwen3-0.6B (ref workload: BASELINE.json config 1)
    "qwen3-0.6b": ModelConfig(
        name="qwen3-0.6b", vocab_size=151936, hidden=1024, n_layers=28,
        n_q_heads=16, n_kv_heads=8, head_dim=128, mlp_hidden=3072,
        rope_theta=1e6, qk_norm=True, tie_embeddings=True, max_context=32768,
    ),
    "qwen3-4b": ModelConfig(
        name="qwen3-4b", vocab_size=151936, hidden=2560, n_layers=36,
        n_q_heads=32, n_kv_heads=8, head_dim=128, mlp_hidden=9728,
        rope_theta=1e6, qk_norm=True, tie_embeddings=True, max_context=32768,
    ),
    # Llama-3-8B (ref workload: BASELINE.json config 2)
    "llama3-8b": ModelConfig(
        name="llama3-8b", vocab_size=128256, hidden=4096, n_layers=32,
        n_q_heads=32, n_kv_heads=8, head_dim=128, mlp_hidden=14336,
        rope_theta=5e5, tie_embeddings=False, max_context=8192,
    ),
    # Mistral-7B-v0.3 (ref serves it via the vLLM adapter; the 7-8B-class
    # config that actually FITS a 16GB single chip in bf16 — llama3-8b's
    # 128k vocab pushes it to 16.06GB, over the v5e HBM line)
    "mistral-7b": ModelConfig(
        name="mistral-7b", vocab_size=32768, hidden=4096, n_layers=32,
        n_q_heads=32, n_kv_heads=8, head_dim=128, mlp_hidden=14336,
        rope_theta=1e6, tie_embeddings=False, max_context=8192,
    ),
    # Llama-3-70B (ref workload: recipes/llama-3-70b, BASELINE config 3)
    "llama3-70b": ModelConfig(
        name="llama3-70b", vocab_size=128256, hidden=8192, n_layers=80,
        n_q_heads=64, n_kv_heads=8, head_dim=128, mlp_hidden=28672,
        rope_theta=5e5, tie_embeddings=False, max_context=8192,
    ),
    # MoE families (expert axis shards over ep; ref orchestrates these via
    # SGLang WideEP recipes — recipes/deepseek-r1, SURVEY §2.5)
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b", vocab_size=32000, hidden=4096, n_layers=32,
        n_q_heads=32, n_kv_heads=8, head_dim=128, mlp_hidden=14336,
        rope_theta=1e6, tie_embeddings=False, max_context=32768,
        n_experts=8, n_experts_active=2, expert_mlp_hidden=14336,
    ),
    "qwen3-30b-a3b": ModelConfig(
        name="qwen3-30b-a3b", vocab_size=151936, hidden=2048, n_layers=48,
        n_q_heads=32, n_kv_heads=4, head_dim=128, mlp_hidden=6144,
        rope_theta=1e6, qk_norm=True, tie_embeddings=False,
        max_context=32768, n_experts=128, n_experts_active=8,
        expert_mlp_hidden=768,
    ),
    # GPT-OSS-120B class (ref workload: BASELINE config 4, KVBM offload)
    "gpt-oss-120b": ModelConfig(
        name="gpt-oss-120b", vocab_size=201088, hidden=2880, n_layers=36,
        n_q_heads=64, n_kv_heads=8, head_dim=64, mlp_hidden=2880,
        rope_theta=1.5e5, tie_embeddings=False, max_context=131072,
        n_experts=128, n_experts_active=4, expert_mlp_hidden=2880,
        attn_sinks=True, sliding_window=128, attn_bias=True,
        swiglu_limit=7.0, rope_yarn_factor=32.0, rope_yarn_orig_max=4096,
    ),
    # gpt-oss-20b: same family, 24 layers / 32 experts
    "gpt-oss-20b": ModelConfig(
        name="gpt-oss-20b", vocab_size=201088, hidden=2880, n_layers=24,
        n_q_heads=64, n_kv_heads=8, head_dim=64, mlp_hidden=2880,
        rope_theta=1.5e5, tie_embeddings=False, max_context=131072,
        n_experts=32, n_experts_active=4, expert_mlp_hidden=2880,
        attn_sinks=True, sliding_window=128, attn_bias=True,
        swiglu_limit=7.0, rope_yarn_factor=32.0, rope_yarn_orig_max=4096,
    ),
    # tiny gpt-oss for CI (sinks, sliding, biases, clipped swiglu, yarn)
    "tiny-gptoss-test": ModelConfig(
        name="tiny-gptoss-test", vocab_size=512, hidden=64, n_layers=4,
        n_q_heads=4, n_kv_heads=2, head_dim=16, mlp_hidden=64,
        tie_embeddings=False, max_context=256,
        n_experts=4, n_experts_active=2, expert_mlp_hidden=64,
        attn_sinks=True, sliding_window=16, attn_bias=True,
        swiglu_limit=7.0, rope_yarn_factor=8.0, rope_yarn_orig_max=64,
    ),
    # DeepSeek-V2-Lite class: MLA latent attention + MoE (the reference's
    # headline DeepSeek-R1 recipes use the full-size sibling)
    "deepseek-v2-lite": ModelConfig(
        name="deepseek-v2-lite", vocab_size=102400, hidden=2048, n_layers=27,
        n_q_heads=16, n_kv_heads=16, head_dim=192, mlp_hidden=10944,
        rope_theta=1e4, tie_embeddings=False, max_context=32768,
        n_experts=64, n_experts_active=6, expert_mlp_hidden=1408,
        first_k_dense=1, n_shared_experts=2, moe_norm_topk=False,
        mla_kv_lora_rank=512, mla_rope_head_dim=64, mla_nope_head_dim=128,
        mla_v_head_dim=128,
    ),
    # DeepSeek-V3/R1 (671B): the reference's headline recipes
    # (recipes/deepseek-r1) — q-lora MLA, sigmoid+bias node-limited
    # routing, 3 dense layers then 256-expert MoE with 1 shared expert.
    "deepseek-v3": ModelConfig(
        name="deepseek-v3", vocab_size=129280, hidden=7168, n_layers=61,
        n_q_heads=128, n_kv_heads=128, head_dim=192, mlp_hidden=18432,
        rope_theta=1e4, tie_embeddings=False, max_context=163840,
        n_experts=256, n_experts_active=8, expert_mlp_hidden=2048,
        first_k_dense=3, n_shared_experts=1, moe_norm_topk=True,
        moe_routed_scale=2.5, moe_scoring="sigmoid", moe_n_group=8,
        moe_topk_group=4,
        mla_kv_lora_rank=512, mla_q_lora_rank=1536, mla_rope_head_dim=64,
        mla_nope_head_dim=128, mla_v_head_dim=128,
    ),
    # NVIDIA-Nemotron-3-Nano-30B-A3B (config.json, model_type nemotron_h)
    # at its published sizes: 23 Mamba-2, 23 routed-expert and 6 attention
    # layers, one mixer a layer. A worker serves a cut of it by flags
    # (--serve-layers, --experts-held, --vocab-rows: `cut_config`).
    "nemotron3-nano-30b-a3b": ModelConfig(
        name="nemotron3-nano-30b-a3b", vocab_size=131072, hidden=2688,
        n_layers=52,
        layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        n_q_heads=32, n_kv_heads=2, head_dim=128, mlp_hidden=1856,
        rms_eps=1e-5, use_rope=False, tie_embeddings=False,
        max_context=262144, mlp_act="relu2",
        n_experts=128, n_experts_active=6, expert_mlp_hidden=1856,
        n_shared_experts=1, shared_expert_hidden=3712,
        moe_norm_topk=True, moe_routed_scale=2.5, moe_scoring="sigmoid",
        mamba_heads=64, mamba_head_dim=64, ssm_groups=8, ssm_state=128,
    ),
    # JetBrains Mellum2-12B-A2.5B-Instruct (config.json, model_type
    # mellum): 28 pre-norm blocks, each an attention mixer then 64 SwiGLU
    # experts 896 wide (top-8 of a float32 softmax, renormalised, no
    # shared expert), so 56 mixers; three blocks of four attend over the
    # last 1024 positions with the default rope, the fourth over
    # everything with YaRN (factor 16 over 8192). No q/k norm, no
    # prediction module. `--serve-layers` counts blocks.
    "mellum2-12b-a2.5b": ModelConfig(
        name="mellum2-12b-a2.5b", vocab_size=98304, hidden=2304,
        n_layers=56, layer_pattern="WEWEWE*E" * 7, mixers_per_layer=2,
        n_q_heads=32, n_kv_heads=4, head_dim=128, mlp_hidden=7168,
        rope_theta=5e5, rms_eps=1e-6, tie_embeddings=False,
        max_context=131072, sliding_window=1024,
        rope_yarn_factor=16.0, rope_yarn_orig_max=8192,
        rope_yarn_truncate=True,
        n_experts=64, n_experts_active=8, expert_mlp_hidden=896,
        moe_norm_topk=True,
    ),
    # CPU sibling: two periods, window 32, 8 experts top-2
    "tiny-mellum-test": ModelConfig(
        name="tiny-mellum-test", vocab_size=512, hidden=64, n_layers=16,
        layer_pattern="WEWEWE*E" * 2, mixers_per_layer=2,
        n_q_heads=4, n_kv_heads=2, head_dim=16, mlp_hidden=128,
        rope_theta=5e5, rms_eps=1e-6, tie_embeddings=False,
        max_context=1024, sliding_window=32,
        rope_yarn_factor=16.0, rope_yarn_orig_max=64,
        rope_yarn_truncate=True,
        n_experts=8, n_experts_active=2, expert_mlp_hidden=48,
        moe_norm_topk=True,
    ),
    # IBM granite-4.0-h-small (config.json, model_type granitemoehybrid)
    # at its published sizes: 40 pre-norm blocks, each a token mixer then
    # 72 SwiGLU experts 768 wide (top-10, a softmax over the chosen
    # logits) with a shared expert 1536 wide, so 80 mixers; nine token
    # mixers in ten are Mamba-2 (128 heads of 64, ONE group of B and C,
    # state 128), the sixth of every ten grouped-query attention with no
    # positional term and scores 1/128 x q.k. The embedding is read x 12
    # going in and, tied, / 16 coming out; every branch joins the
    # residual stream x 0.22. `--serve-layers` counts blocks.
    "granite-4.0-h-small": ModelConfig(
        name="granite-4.0-h-small", vocab_size=100352, hidden=4096,
        n_layers=80, layer_pattern=("ME" * 5 + "*E" + "ME" * 4) * 4,
        mixers_per_layer=2,
        n_q_heads=32, n_kv_heads=8, head_dim=128, mlp_hidden=768,
        rms_eps=1e-5, use_rope=False, tie_embeddings=True,
        max_context=131072,
        n_experts=72, n_experts_active=10, expert_mlp_hidden=768,
        n_shared_experts=1, shared_expert_hidden=1536, moe_norm_topk=True,
        mamba_heads=128, mamba_head_dim=64, ssm_groups=1, ssm_state=128,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.0078125, logits_scaling=16.0,
    ),
    # CPU sibling: one period of the same pattern, 4 Mamba heads in one
    # group, 8 experts top-3, a shared expert, a tied head, all four
    # multipliers off 1
    "tiny-granite-test": ModelConfig(
        name="tiny-granite-test", vocab_size=512, hidden=64, n_layers=20,
        layer_pattern="ME" * 5 + "*E" + "ME" * 4, mixers_per_layer=2,
        n_q_heads=4, n_kv_heads=2, head_dim=16, mlp_hidden=48,
        rms_eps=1e-5, use_rope=False, tie_embeddings=True,
        max_context=1024,
        n_experts=8, n_experts_active=3, expert_mlp_hidden=48,
        n_shared_experts=1, shared_expert_hidden=96, moe_norm_topk=True,
        mamba_heads=4, mamba_head_dim=16, ssm_groups=1, ssm_state=32,
        ssm_chunk=16,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.03125, logits_scaling=16.0,
    ),
    # LiquidAI LFM2-8B-A1B (config.json, model_type lfm2_moe) at its
    # published sizes: 24 pre-norm blocks, each a token mixer then a
    # feed-forward, so 48 mixers; three token mixers in four are a gated
    # short convolution (three taps over 2048 channels, no bias, no
    # activation), the others (blocks 2, 6, 10, 14, 18, 21) grouped-query
    # attention at head_dim 64 with q and k normed per head before rope;
    # the first two feed-forwards are a dense SwiGLU 7168 wide, the other
    # 22 are 32 SwiGLU experts 1792 wide (top-4 of sigmoid scores + a
    # selection bias, renormalised over their sum + 1e-6, no shared
    # expert). Tied head. `--serve-layers` counts blocks and keeps both
    # dense ones (`cut_config`).
    "lfm2-8b-a1b": ModelConfig(
        name="lfm2-8b-a1b", vocab_size=65536, hidden=2048, n_layers=48,
        layer_pattern=_blocks("CC*C" * 5 + "C*CC", dense=2),
        mixers_per_layer=2,
        n_q_heads=32, n_kv_heads=8, head_dim=64, mlp_hidden=7168,
        rope_theta=1e6, rms_eps=1e-5, qk_norm=True, tie_embeddings=True,
        max_context=128000, conv_kernel=3,
        n_experts=32, n_experts_active=4, expert_mlp_hidden=1792,
        moe_norm_topk=True, moe_scoring="sigmoid", moe_renorm_eps=1e-6,
    ),
    # CPU sibling: two periods (both dense blocks, then six expert
    # blocks), head_dim 64 as published (two kv heads a lane tile of the
    # pool), 8 experts top-2
    "tiny-lfm2-test": ModelConfig(
        name="tiny-lfm2-test", vocab_size=512, hidden=256, n_layers=16,
        layer_pattern=_blocks("CC*C" * 2, dense=2), mixers_per_layer=2,
        n_q_heads=4, n_kv_heads=2, head_dim=64, mlp_hidden=256,
        rope_theta=1e6, rms_eps=1e-5, qk_norm=True, tie_embeddings=True,
        max_context=1024, conv_kernel=3,
        n_experts=8, n_experts_active=2, expert_mlp_hidden=64,
        moe_norm_topk=True, moe_scoring="sigmoid", moe_renorm_eps=1e-6,
    ),
    # CPU sibling: every layer kind at least twice, 8 experts top-2, a
    # shared expert, 4 Mamba heads in 2 groups
    "tiny-hybrid-test": ModelConfig(
        name="tiny-hybrid-test", vocab_size=512, hidden=64, n_layers=7,
        layer_pattern="MEM*EM*", n_q_heads=4, n_kv_heads=2, head_dim=16,
        mlp_hidden=48, rms_eps=1e-5, use_rope=False, tie_embeddings=False,
        max_context=256, mlp_act="relu2",
        n_experts=8, n_experts_active=2, expert_mlp_hidden=48,
        n_shared_experts=1, shared_expert_hidden=96,
        moe_norm_topk=True, moe_routed_scale=2.5, moe_scoring="sigmoid",
        mamba_heads=4, mamba_head_dim=16, ssm_groups=2, ssm_state=32,
        ssm_chunk=16,
    ),
    # openPangu-Ultra-MoE-718B (config.json, model_type pangu_ultra_moe)
    # at its published sizes: 61 sandwich-normed blocks as 122 mixers,
    # latent attention (q rank 1536, kv rank 512, 128 heads of 128 + 64
    # rope lanes, values 128) then a dense SwiGLU 18,432 wide three
    # times, then latent attention and 256 routed experts 2,048 wide
    # (top-8 of sigmoid scores, no selection bias, renormalised, x 2.5)
    # with one shared expert 58 times. No YaRN; the next-token
    # prediction module is not held. A worker serves a cut of it by
    # flags (`cut_config`: one leading dense block, then expert blocks).
    "openpangu-ultra-moe-718b": ModelConfig(
        name="openpangu-ultra-moe-718b", vocab_size=153600, hidden=7680,
        n_layers=122, layer_pattern="LD" * 3 + "LE" * 58,
        mixers_per_layer=2, sandwich_norm=True,
        n_q_heads=128, n_kv_heads=128, head_dim=192, mlp_hidden=18432,
        rope_theta=25.6e6, rms_eps=1e-5, tie_embeddings=False,
        max_context=131072,
        n_experts=256, n_experts_active=8, expert_mlp_hidden=2048,
        n_shared_experts=1, moe_norm_topk=True, moe_routed_scale=2.5,
        moe_scoring="sigmoid", moe_selection_bias=False,
        mla_kv_lora_rank=512, mla_q_lora_rank=1536, mla_rope_head_dim=64,
        mla_nope_head_dim=128, mla_v_head_dim=128,
    ),
    # CPU sibling, as the benchmark's cell cuts the model: one dense
    # block, then four expert blocks; 8 experts top-2, a shared expert
    "tiny-pangu-test": ModelConfig(
        name="tiny-pangu-test", vocab_size=512, hidden=64, n_layers=10,
        layer_pattern="LD" + "LE" * 4, mixers_per_layer=2,
        sandwich_norm=True,
        n_q_heads=4, n_kv_heads=4, head_dim=24, mlp_hidden=128,
        rope_theta=25.6e6, rms_eps=1e-5, tie_embeddings=False,
        max_context=1024,
        n_experts=8, n_experts_active=2, expert_mlp_hidden=48,
        n_shared_experts=1, moe_norm_topk=True, moe_routed_scale=2.5,
        moe_scoring="sigmoid", moe_selection_bias=False,
        mla_kv_lora_rank=32, mla_q_lora_rank=24, mla_rope_head_dim=8,
        mla_nope_head_dim=16, mla_v_head_dim=16,
    ),
    # CohereLabs command-a-plus-05-2026 (config.json, model_type
    # cohere2_moe) at its published sizes, text only: 32 PARALLEL blocks
    # (`use_parallel_block`: attention and experts both read ONE
    # LayerNorm of the stream and are added to it together), so 64
    # mixers, the second of a block without a norm of its own; three
    # blocks of four attend over the last 4096 positions with rope on
    # lane pairs (2i, 2i+1) (`rope_gptj`, theta 50,000), the fourth over
    # everything with NO positional term; 128 query heads over 8 KV
    # heads of 128; 128 SwiGLU experts 4096 wide (top-8 of float32
    # sigmoid scores, no selection bias, renormalised) beside four
    # shared experts whose outputs are averaged (one SwiGLU 16,384 wide
    # x 1/4). `first_k_dense_replace` is 0: no dense block, and
    # `prefix_dense_intermediate_size` (`mlp_hidden` here) is used by no
    # layer. Tied head, `logit_scale` 1. The vision tower is not held.
    # `--serve-layers` counts blocks.
    "command-a-plus-05-2026": ModelConfig(
        name="command-a-plus-05-2026", vocab_size=262144, hidden=4096,
        n_layers=64, layer_pattern="WEWEWE*E" * 8, mixers_per_layer=2,
        parallel_block=True, norm_kind="layer", rope_kinds="W",
        rope_interleaved=True,
        n_q_heads=128, n_kv_heads=8, head_dim=128, mlp_hidden=16384,
        rope_theta=5e4, rms_eps=1e-5, tie_embeddings=True,
        max_context=200000, sliding_window=4096,
        n_experts=128, n_experts_active=8, expert_mlp_hidden=4096,
        n_shared_experts=4, shared_expert_mean=True, moe_norm_topk=True,
        moe_scoring="sigmoid", moe_selection_bias=False,
    ),
    # CPU sibling: two periods, window 32, 8 experts top-2, two shared
    # experts averaged
    "tiny-cohere2-test": ModelConfig(
        name="tiny-cohere2-test", vocab_size=512, hidden=64, n_layers=16,
        layer_pattern="WEWEWE*E" * 2, mixers_per_layer=2,
        parallel_block=True, norm_kind="layer", rope_kinds="W",
        rope_interleaved=True,
        n_q_heads=4, n_kv_heads=2, head_dim=16, mlp_hidden=128,
        rope_theta=5e4, rms_eps=1e-5, tie_embeddings=True,
        max_context=1024, sliding_window=32,
        n_experts=8, n_experts_active=2, expert_mlp_hidden=48,
        n_shared_experts=2, shared_expert_mean=True, moe_norm_topk=True,
        moe_scoring="sigmoid", moe_selection_bias=False,
    ),
    # microsoft/Phi-4-mini-flash-reasoning (config.json, model_type
    # phi4flash; SambaY, arXiv:2507.06607) at its published sizes: 32
    # pre-norm blocks, each a token mixer then a SwiGLU 10,240 wide, so
    # 64 mixers, every norm a LayerNorm with weight AND bias, no
    # positional term anywhere, a tied head. Blocks 0, 2, .., 16 are
    # Mamba-1 (5,120 channels = `mamba_heads` x `mamba_head_dim`, which a
    # Mamba-1 mixer has not: the product alone is read; 16 state
    # columns, dt rank 160, conv 4 with a bias); blocks 1, 3, .., 15
    # differential attention over the last 512 positions (40 query
    # heads, 20 KV heads of 64, biases on the projections); block 17 the
    # same over everything, the ONE layer of the full page group; blocks
    # 18, 20, .., 30 gated memory units on block 16's pre-gate scan
    # output; blocks 19, 21, .., 31 cross-attention (a query projection
    # alone) onto block 17's pages.
    "phi4-mini-flash-reasoning": ModelConfig(
        name="phi4-mini-flash-reasoning", vocab_size=200064, hidden=2560,
        n_layers=64,
        layer_pattern="SDWD" * 8 + "SD*D" + "GDXD" * 7, mixers_per_layer=2,
        norm_kind="layer", norm_bias=True, attn_bias=True,
        diff_attention=True, use_rope=False,
        layer_sections=((4, 8), (4, 1), (4, 7)),
        n_q_heads=40, n_kv_heads=20, head_dim=64, mlp_hidden=10240,
        rms_eps=1e-5, tie_embeddings=True, max_context=262144,
        sliding_window=512,
        mamba_heads=40, mamba_head_dim=128, ssm_state=16, mamba_dt_rank=160,
        conv_kernel=4,
    ),
    # CPU sibling: the same rule at 12 blocks (Mamba-1 at 0, 2, 4 and,
    # the memory, 6; window layers 1, 3, 5; the full one 7; memory units
    # 8, 10; cross-attention 9, 11), head_dim 64 as published (a KV pair
    # fills a lane tile), window 32
    "tiny-phi4flash-test": ModelConfig(
        name="tiny-phi4flash-test", vocab_size=512, hidden=256,
        n_layers=24,
        layer_pattern="SDWD" * 3 + "SD*D" + "GDXD" * 2, mixers_per_layer=2,
        norm_kind="layer", norm_bias=True, attn_bias=True,
        diff_attention=True, use_rope=False,
        layer_sections=((4, 3), (4, 1), (4, 2)),
        n_q_heads=4, n_kv_heads=2, head_dim=64, mlp_hidden=512,
        rms_eps=1e-5, tie_embeddings=True, max_context=1024,
        sliding_window=32,
        mamba_heads=4, mamba_head_dim=128, ssm_state=16, mamba_dt_rank=16,
        conv_kernel=4,
    ),
    "tiny-mla-test": ModelConfig(
        name="tiny-mla-test", vocab_size=512, hidden=64, n_layers=2,
        n_q_heads=4, n_kv_heads=4, head_dim=24, mlp_hidden=128,
        mla_kv_lora_rank=32, mla_rope_head_dim=8, mla_nope_head_dim=16,
        mla_v_head_dim=16,
    ),
}


def cut_config(config: ModelConfig, layers: Optional[int] = None,
               experts: Optional[str] = None,
               vocab_rows: Optional[int] = None) -> ModelConfig:
    """The share of `config` one chip of a stated deployment serves: the
    leading `layers`, the experts `lo:hi` of the published count, the
    leading `vocab_rows` of the vocabulary (embedding, head, logits and
    sampling are over the slice). No width changes. Where every block
    has the same token mixer, leading dense blocks (a "D" mixer) count
    ONCE: the first, then the blocks behind the last of them, so that a
    cut of a few blocks holds the expert blocks the model is made of.
    Where token mixers differ by block the cut is the contiguous leading
    blocks, a pipeline stage: skipping a dense block would drop the
    mixer in front of it and shift the period."""
    changes: dict = {}
    if layers is not None:
        per = config.mixers_per_layer
        if not 0 < layers * per <= config.n_layers:
            raise ValueError(f"--serve-layers {layers}: {config.name} has "
                             f"{config.n_layers // per} layers")
        changes["n_layers"] = layers * per
        if config.layer_pattern:
            pattern = config.layer_pattern
            dense = (pattern.rindex("D") // per + 1) if "D" in pattern else 0
            # alike but for the feed-forward: pangu_ultra_moe's "LD" x 3
            # + "LE" x 58; a stack of several token mixers (lfm2_moe's
            # conv, conv, attention, conv) is cut as it lies
            alike = per == 2 and len(set(pattern[::per])) == 1
            if dense > 1 and alike:
                pattern = pattern[:per] + pattern[dense * per:]
                if layers * per > len(pattern):
                    raise ValueError(
                        f"--serve-layers {layers}: {config.name} has one "
                        f"dense block to serve and {len(pattern) // per - 1}"
                        " expert blocks behind it")
            changes["layer_pattern"] = pattern[:layers * per]
    if experts is not None:
        try:
            lo, hi = (int(part) for part in experts.split(":"))
        except ValueError:
            raise ValueError(f"--experts-held {experts!r} is not lo:hi")
        if not (config.is_hybrid and 0 <= lo < hi <= config.n_experts):
            raise ValueError(
                f"--experts-held {experts}: {config.name} has "
                f"{config.n_experts} routed experts"
                + ("" if config.is_hybrid else " and no dropless expert "
                   "layer that can be told which it holds"))
        changes["experts_held"] = (lo, hi)
    if vocab_rows is not None:
        if not 0 < vocab_rows <= config.vocab_size:
            raise ValueError(f"--vocab-rows {vocab_rows}: {config.name} "
                             f"has {config.vocab_size} rows")
        if (config.tie_embeddings and not config.is_hybrid
                and vocab_rows != config.vocab_size):
            # a `layer_pattern` stack's tied head contracts the held
            # rows of the embedding itself (models/hybrid._head)
            raise ValueError("--vocab-rows needs an untied output head")
        changes["vocab_size"] = vocab_rows
    return dataclasses.replace(config, **changes) if changes else config


def get_config(name: str) -> ModelConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown model preset '{name}' "
                       f"(have: {sorted(PRESETS)})")
    return PRESETS[name]
