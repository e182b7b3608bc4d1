"""Weight-only int8 linear layers (W8A16) — the decode-bandwidth lever.

At 7B on one chip decode is weight-streaming-bound (BASELINE.md: the
step floor is weights/HBM-bandwidth, roofline fraction ~0.55 in bf16).
Storing the dense matmul stack as int8 + per-output-channel scales
halves the streamed bytes; the Pallas kernel below keeps the win honest
by dequantizing IN VMEM — tiles stream from HBM as int8, convert on the
VPU, and feed the MXU, so the bf16 weight never exists in HBM. (A plain
`x @ q.astype(bf16) * s` einsum would materialize the full bf16 weight
every step — strictly worse than bf16 weights.)

Math: per-output-channel scales factor out of the contraction, so
  x @ dequant(q, s) == (x @ q) * s
exactly (s has no contracted axis). The kernel computes the right-hand
side with an f32 accumulator.

The reference reaches the same lever through its engines' quantized
checkpoints (vLLM/TRT-LLM w8a16 paths); ref perf doc: BASELINE.md
"decode floor is weight streaming".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_path

# Leaf name -> number of LEADING contracted axes (the rest are output
# axes carrying the per-channel scale). Shared by the quantizer and the
# sharding-tree transform (models/quantize.py).
QUANT_LEAVES = {
    "wq": 1, "wk": 1, "wv": 1, "wo": 2,
    "w_gate": 1, "w_up": 1, "w_down": 1,
    "lm_head": 1,
}


def quantize_weight(w: jax.Array, n_contract: int) -> dict:
    """Symmetric per-output-channel int8: absmax over the `n_contract`
    leading (contracted) axes. Returns {"q8": int8 like w, "qs": f32
    scale of the output-axes shape}."""
    w32 = jnp.asarray(w, jnp.float32)
    axes = tuple(range(n_contract))
    absmax = jnp.max(jnp.abs(w32), axis=axes)
    scale = absmax / 127.0
    safe = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(w32 / safe), -127, 127).astype(jnp.int8)
    return {"q8": q, "qs": scale.astype(jnp.float32)}


def _q8_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # int8 tile -> bf16 in VMEM (VPU convert), MXU dot, f32 accumulate.
    acc_ref[:] += jax.lax.dot_general(
        x_ref[:], w_ref[:].astype(x_ref.dtype),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _emit():
        o_ref[:] = (acc_ref[:] * s_ref[:].astype(jnp.float32)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def q8_matmul(x: jax.Array, wq: jax.Array, scale: jax.Array,
              bm: int = 256, bn: int = 512, bk: int = 512,
              interpret: bool = False) -> jax.Array:
    """x [M, K] (bf16/f32) @ wq [K, N] int8, per-column scale [N] ->
    [M, N] in x.dtype. M is padded to the tile; K and N must divide the
    block sizes (the dense-family geometries all do — H/QD/M/V are
    multiples of 512)."""
    m, k = x.shape
    k2, n = wq.shape
    assert k == k2 and scale.shape == (n,), (x.shape, wq.shape,
                                             scale.shape)
    bm = min(bm, max(16, 1 << max(0, m - 1).bit_length()))
    mp = -(-m // bm) * bm
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))

    def _divisor(dim: int, pref: int, floor: int) -> int:
        # Largest power-of-two block <= pref that divides dim: the dense
        # geometries are mostly 512-multiples, but e.g. llama3's untied
        # 128,256 vocab is only a 256-multiple.
        b = min(pref, dim)
        while b > floor and dim % b:
            b //= 2
        return b

    bk = _divisor(k, bk, 1)
    bn = _divisor(n, bn, 1)
    if (n >= 128 and bn < 128) or (k >= 128 and bk < 128):
        raise ValueError(
            f"q8_matmul needs 128-lane-divisible geometry (K={k}, "
            f"N={n}); this weight cannot take the W8A16 kernel")
    s2 = scale.reshape(1, n)
    out = pl.pallas_call(
        _q8_matmul_kernel,
        grid=(mp // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, wq, s2)
    return out[:m]


def q8_matmul_ref(x: jax.Array, wq: jax.Array,
                  scale: jax.Array) -> jax.Array:
    """XLA reference (tests / non-TPU fallback): mathematically identical
    contraction-then-scale; XLA materializes the converted weight, so
    this is a correctness path, not the perf path."""
    acc = jax.lax.dot_general(
        x, wq.astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (acc * scale.astype(jnp.float32)).astype(x.dtype)


def q8_einsum(spec: str, x: jax.Array, q8: jax.Array,
              qs: jax.Array) -> jax.Array:
    """Quantized drop-in for the transformer's dense einsums: reshape to
    a 2-D [rows, K] x [K, N] matmul, run the kernel, reshape back. The
    supported specs are exactly the dense-family projection shapes."""
    if spec in ("bth,hm->btm", "btm,mh->bth", "bth,hv->btv"):
        b, t, k = x.shape
        out_shape = (b, t, q8.shape[1])
        x2 = x.reshape(b * t, k)
        w2, s2 = q8, qs
    elif spec == "bth,hqd->btqd":
        b, t, k = x.shape
        _, qh, hd = q8.shape
        out_shape = (b, t, qh, hd)
        x2 = x.reshape(b * t, k)
        w2 = q8.reshape(k, qh * hd)
        s2 = qs.reshape(qh * hd)
    elif spec == "bth,hkd->btkd":
        b, t, k = x.shape
        _, kh, hd = q8.shape
        out_shape = (b, t, kh, hd)
        x2 = x.reshape(b * t, k)
        w2 = q8.reshape(k, kh * hd)
        s2 = qs.reshape(kh * hd)
    elif spec == "btqd,qdh->bth":
        b, t, qh, hd = x.shape
        h = q8.shape[-1]
        out_shape = (b, t, h)
        x2 = x.reshape(b * t, qh * hd)
        w2 = q8.reshape(qh * hd, h)
        s2 = qs
    else:
        raise ValueError(f"q8_einsum does not support spec {spec!r}")
    path = kernel_path("DYNT_Q8_MATMUL")
    if path == "xla":
        out = q8_matmul_ref(x2, w2, s2)
    else:
        out = q8_matmul(x2, w2, s2,
                        interpret=path == "interpret")
    return out.reshape(out_shape)


def quantize_weight_np(w: np.ndarray, n_contract: int) -> dict:
    """Host-side variant (checkpoint loaders that stay in numpy)."""
    w32 = np.asarray(w, np.float32)
    axes = tuple(range(n_contract))
    absmax = np.max(np.abs(w32), axis=axes)
    scale = absmax / 127.0
    safe = np.maximum(scale, 1e-12)
    q = np.clip(np.round(w32 / safe), -127, 127).astype(np.int8)
    return {"q8": q, "qs": scale.astype(np.float32)}
