"""Model families (flagship: Qwen3/Llama-class decoders)."""

from .config import ModelConfig, PRESETS, get_config
from .transformer import (
    DenseSteps,
    forward,
    forward_embed,
    init_params,
    make_kv_cache,
    paged_attention_xla,
    param_axes,
    rms_norm,
    rope,
    write_kv_pages,
)



def make_steps(config: ModelConfig, kernels: dict, attention_fn=None):
    """The adapter between `ModelRunner`'s step programs and `config`'s
    stack: the one place that asks which kind of stack it is."""
    if config.layer_pattern:
        from .hybrid import HybridSteps

        return HybridSteps(config, kernels, attention_fn)
    return DenseSteps(config, kernels, attention_fn)


__all__ = [
    "ModelConfig",
    "PRESETS",
    "forward",
    "forward_embed",
    "get_config",
    "init_params",
    "make_kv_cache",
    "make_steps",
    "paged_attention_xla",
    "param_axes",
    "rms_norm",
    "rope",
    "write_kv_pages",
]
