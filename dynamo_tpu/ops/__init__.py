"""TPU kernels and fused ops (Pallas + XLA).

This package is the TPU-native equivalent of the reference's CUDA kernel
layer (ref: lib/llm/src/kernels/block_copy.cu, lib/kvbm-kernels/cuda/
tensor_kernels.cu) plus the paged-attention kernels the reference inherits
from its engines (vLLM/TRT-LLM). Everything here runs in two modes:

  * compiled (Mosaic) on real TPU chips
  * interpret mode on CPU, so the full kernel logic is unit-testable
    against the pure-XLA reference implementations with zero chips
"""

import jax

from .paged_attention import paged_attention, paged_decode_attention
from .block_copy import gather_kv_blocks, scatter_kv_blocks, swap_kv_blocks
from .layout import universal_to_layered, layered_to_universal


def kernel_path(option: str) -> str:
    """The implementation a kernel option (DYNT_ATTENTION, DYNT_Q4_MATMUL,
    DYNT_Q8_MATMUL) selects on this process's backend: "pallas" (compiled
    by Mosaic), "interpret" (the Pallas interpreter: an explicit `pallas`
    off the TPU, which is how tests reach the kernels) or "xla" (the
    reference). `auto` is Pallas on a TPU and the reference on the CPU
    the tests run on. Any other backend is an accelerator this tree has
    no kernels for: serving the reference there would look like a slow
    chip, so it is an error."""
    from ..runtime.config import env

    mode = env(option) or "auto"
    if mode not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown {option} {mode!r} (expected "
                         "auto|pallas|xla)")
    backend = jax.default_backend()
    if mode == "xla" or (mode == "auto" and backend == "cpu"):
        return "xla"
    if backend == "tpu":
        return "pallas"
    if mode == "pallas":
        return "interpret"
    raise RuntimeError(
        f"{option}=auto has no kernel path for backend {backend!r} "
        "(Pallas kernels target tpu; the XLA reference is the cpu test "
        f"path) — set {option}=xla or =pallas to choose one knowingly")


__all__ = [
    "kernel_path",
    "paged_attention",
    "paged_decode_attention",
    "gather_kv_blocks",
    "scatter_kv_blocks",
    "swap_kv_blocks",
    "universal_to_layered",
    "layered_to_universal",
]
