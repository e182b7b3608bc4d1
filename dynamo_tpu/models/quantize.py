"""Weight-only int8 quantization of the dense matmul stack (W8A16).

Transforms a dense-family params pytree so that the seven per-layer
projection weights (wq/wk/wv/wo, w_gate/w_up/w_down) and the lm_head
become {"q8": int8, "qs": f32 per-output-channel scale} leaves; the
transformer's `_mm` helper routes those through the Pallas W8A16 kernel
(ops/q8_linear.py). Embeddings, norms, biases, routers, and MoE expert
stacks stay in the model dtype — decode bandwidth is dominated by the
dense projections, and tied-embedding heads must keep the embed table
usable for the gather.

Scope (v1): the dense llama/mistral/qwen family on tp=1 — exactly the
single-chip 7-8B configuration where decode is weight-streaming-bound
(BASELINE.md). MLA/gpt-oss/MoE and tp>1 raise with an actionable
message rather than silently running a slower path.

Ref: the reference reaches this lever through its engines' w8a16
checkpoint modes; BASELINE.md names int8 weights as the honest decode
lever and defers it to this round (VERDICT r4 item 9).
"""

from __future__ import annotations

from ..ops.q8_linear import QUANT_LEAVES, quantize_weight


def check_quantizable(config, tp: int = 1, n_devices: int = 1,
                      dtype: str = "int8") -> None:
    if (config.is_mla or config.is_gptoss or config.n_experts
            or config.is_hybrid):
        raise ValueError(
            f"weight_dtype='{dtype}' supports the dense "
            f"llama/mistral/qwen family in v1 ({config.name} is "
            "MLA/MoE/gpt-oss/hybrid: no layout for latent, expert or "
            "Mamba-2 matrices)")
    if tp != 1 or n_devices != 1:
        raise ValueError(
            f"weight_dtype='{dtype}' is single-device in v1 (the Pallas "
            "dequant kernels are not shard_map-wrapped yet); it targets "
            "the single-chip 7-8B HBM-bound configuration")


def quantize_params_int8(params: dict, config) -> dict:
    """Device-side transform (run under jit by the caller or eagerly):
    returns a NEW pytree with quantized projection leaves."""
    check_quantizable(config)
    out = dict(params)
    out["layers"] = [
        {name: (quantize_weight(leaf, QUANT_LEAVES[name])
                if name in QUANT_LEAVES else leaf)
         for name, leaf in layer.items()}
        for layer in params["layers"]
    ]
    if "lm_head" in params and not config.tie_embeddings:
        out["lm_head"] = quantize_weight(params["lm_head"],
                                         QUANT_LEAVES["lm_head"])
    return out


def quantize_param_axes(axes: dict, config) -> dict:
    """Mirror of quantize_params_int8 over the logical-axes tree, so
    param_shardings() produces a matching pytree: q8 keeps the weight's
    axes, qs keeps the output axes (scales shard exactly like the
    output channels they scale)."""
    def q(name, tup):
        if name not in QUANT_LEAVES:
            return tup
        n_contract = QUANT_LEAVES[name]
        return {"q8": tup, "qs": tuple(tup[n_contract:])}

    out = dict(axes)
    out["layers"] = [
        {name: q(name, tup) for name, tup in layer.items()}
        for layer in axes["layers"]
    ]
    if "lm_head" in axes and not config.tie_embeddings:
        out["lm_head"] = {"q8": axes["lm_head"],
                          "qs": tuple(axes["lm_head"][1:])}
    return out


# --- W4A16 (packed int4 + per-group scale/zero, ops/q4_linear.py) ----


def quantize_params_int4(params: dict, config) -> dict:
    """Device-side transform: packed-int4 projection leaves
    ({"q4","qs4","qz4"}). Same scope as int8 (dense family, tp=1)."""
    from ..ops.q4_linear import QUANT_LEAVES as Q4_LEAVES
    from ..ops.q4_linear import quantize_weight_q4

    check_quantizable(config, dtype="int4")
    out = dict(params)
    out["layers"] = [
        {name: (quantize_weight_q4(leaf, Q4_LEAVES[name])
                if name in Q4_LEAVES else leaf)
         for name, leaf in layer.items()}
        for layer in params["layers"]
    ]
    if "lm_head" in params and not config.tie_embeddings:
        out["lm_head"] = quantize_weight_q4(params["lm_head"],
                                            Q4_LEAVES["lm_head"])
    return out


def check_packed_int4(params: dict) -> None:
    """A tree that arrives already quantized (a checkpoint, the weight
    service, a peer's stream) is placed as it comes, so every packed
    leaf must be what ops.q4_linear packs: refused here, by leaf name,
    before anything reaches the device."""
    from jax.tree_util import keystr, tree_leaves_with_path

    from ..ops.q4_linear import require_packed

    for path, leaf in tree_leaves_with_path(params):
        if getattr(path[-1], "key", None) == "q4":
            require_packed(leaf, keystr(path[:-1]))


def quantize_param_axes_q4(axes: dict, config) -> dict:
    """Logical-axes mirror of quantize_params_int4. int4 is
    single-device in v1 (check_quantizable), so every quantized leaf is
    replicated: q4 keeps the weight's rank (flattened to 2 for wo whose
    pack blocks span heads), scales/zeros are rank-2 [K//128, N]."""
    from ..ops.q4_linear import QUANT_LEAVES as Q4_LEAVES

    def q(name, tup):
        if name not in Q4_LEAVES:
            return tup
        rank = 2 if name == "wo" else len(tup)
        return {"q4": (None,) * rank, "qs4": (None, None),
                "qz4": (None, None)}

    out = dict(axes)
    out["layers"] = [
        {name: q(name, tup) for name, tup in layer.items()}
        for layer in axes["layers"]
    ]
    if "lm_head" in axes and not config.tie_embeddings:
        out["lm_head"] = {"q4": (None, None), "qs4": (None, None),
                          "qz4": (None, None)}
    return out
