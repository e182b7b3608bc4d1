"""Weight-only int4 linear layers (W4A16): the dense projections stored
as packed 4-bit codes, dequantized in VMEM by a Pallas kernel.

The projection stack is stored as 4-bit codes, two to a byte, with one
float32 scale row and one float32 zero-point row per group of
contracted rows (PACK_BLOCK = 256, or DYNT_Q4_GROUP), so a 7B's
projections are ~3.6 GB in HBM where bf16 holds ~14.5. Packed bytes
stream from HBM, the nibbles are unpacked on the VPU and the MXU takes
bf16 tiles: no wider copy of a weight exists in HBM.

Math. A code u dequantizes as (u - z) * s, with s and z constant over a
group. Inside a group the scale has no contracted axis, so it factors
out of the partial dot, and the zero point folds into a rank-1
correction that never touches the weight tile:
  x @ dequant(u) == sum_g (x_g @ u_g - rowsum(x_g) * z_g) * s_g

Layout. A packed leaf is uint8 [K//2, N] (a q4 leaf of any other dtype
is refused: it comes from a checkpoint or a peer, not from this
module). Within each group, byte row r holds code row r in its low
nibble and code row r + group//2 in its high nibble. Unpacking a group
gives two half-group tiles, so the kernel makes two half-contraction
dots per group and one [bm, bn] pass for the scale and zero point, all
through one int32 widen (Mosaic has no u8 -> bf16 cast and no int8
vector shifts). Only this module knows the layout: quantize_weight_q4
packs, the kernel and dequantize_q4 unpack.

What a prefill launch pays (v5e, 7B shapes, bf16 x; PERF.md section 5).
The weight tile is unpacked again for every BLOCK_M = 256 rows of x,
and at M = 2048-4096 the kernel still runs the rows it is given at
135-183 TFLOP/s for the square, MLP and head projections (84-112 for
the narrow wk/wv), as fast as XLA's bf16 dot on the same shapes. What
it was given was the waste: a batched launch is pow2(rows) x
bucket(longest chunk) positions flattened to M rows, 52-68% of them
prompt tokens in the dense cell. So q4_matmul takes `live`, one int32
per row block (live_row_blocks, an `any` over the launch's validity
mask, handed over by scalar prefetch): a dead block skips the body,
stores a zero tile at its last k step and keeps the previous block's
weight tiles in VMEM; a dead (row, column) block costs 0.9-1.7 us
where a live one at K = 4096 costs 12-15 (nine live row blocks of
sixteen: 62-66% of the time of sixteen, 77% for wk/wv). BLOCK_M = 128
would skip a little more and measures 3-12% slower at every shape
(twice the unpacking). Only launches whose rows are MAP_MIN_ROW =
1024 positions or longer take the map: on the serving host a program
whose matmuls carry it spends 5.1-5.8 s in jaxpr -> MLIR lowering
where one without spends 1.7-2.1 (Mosaic's lowering of the prefetch
kernel inside a 32-layer module; alone, either kernel lowers in
0.06 s), and that is paid at every start, compile cache or not. Every
other caller (shorter buckets, no mask, every decode step) gets the
kernel without a map.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_path

# Preferred contracted rows per quantization group (the packed layout
# bakes the group in: module docstring). 256 is what the cells run; 128
# (DYNT_Q4_GROUP, the GPTQ/AWQ convention) is not measured on the chip.
# Small-geometry weights (tests' tiny models) fall back to the largest
# power-of-two divisor of K.
PACK_BLOCK = 256


def _group_for(k: int) -> int:
    from ..runtime.config import env

    g = int(env("DYNT_Q4_GROUP") or PACK_BLOCK)
    while g > 2 and k % g:
        g //= 2
    if k % g or g < 2:
        raise ValueError(
            f"int4 needs the contracted size to divide a power-of-two "
            f"group (got K={k}); this weight cannot take the W4A16 "
            "kernel")
    return g


# Leaf name -> number of LEADING contracted axes (same registry shape as
# q8_linear.QUANT_LEAVES; shared by the quantizer and model plumbing).
QUANT_LEAVES = {
    "wq": 1, "wk": 1, "wv": 1, "wo": 2,
    "w_gate": 1, "w_up": 1, "w_down": 1,
    "lm_head": 1,
}


def _pack_codes(u: jnp.ndarray, group: int) -> jnp.ndarray:
    """uint8 codes [K, N] in [0, 15] -> packed uint8 [K//2, N] (byte
    row r of each group holds code rows r and r + group//2)."""
    k, n = u.shape
    half = group // 2
    blk = u.reshape(k // group, group, n)
    lo, hi = blk[:, :half], blk[:, half:]
    return (lo | (hi << 4)).reshape(k // 2, n)


def _unpack_codes(packed: jnp.ndarray, group: int) -> jnp.ndarray:
    """Inverse of _pack_codes (reference path / tests)."""
    k2, n = packed.shape
    half = group // 2
    blk = packed.reshape(k2 // half, half, n)
    lo = blk & 0xF
    hi = blk >> 4
    return jnp.concatenate([lo, hi], axis=1).reshape(k2 * 2, n)


def require_packed(q4, where: str) -> None:
    """A q4 leaf comes from a checkpoint or a peer's weight stream as
    well as from quantize_weight_q4; its bytes mean what this module
    says only when they are uint8."""
    if q4.dtype != jnp.uint8:
        raise ValueError(
            f"{where}: a packed int4 leaf is uint8 (got {q4.dtype}); "
            "this tree was not packed by quantize_weight_q4")


def quantize_weight_q4(w: jax.Array, n_contract: int) -> dict:
    """Asymmetric per-group int4 over the contracted axes.

    Returns {"q4": packed uint8, "qs4": f32 [K//group, N], "qz4": f32
    [K//group, N]}. q4 keeps the weight's output axes when a single
    leading axis is contracted ([K//2, *out_axes]); multi-axis
    contractions (wo) flatten to 2-D [K//2, N] because pack groups span
    head boundaries.
    """
    out_axes = w.shape[n_contract:]
    k = int(np.prod(w.shape[:n_contract]))
    n = int(np.prod(out_axes)) if out_axes else 1
    group = _group_for(k)
    w2 = jnp.asarray(w, jnp.float32).reshape(k, n)
    grp = w2.reshape(k // group, group, n)
    lo = jnp.min(grp, axis=1)
    hi = jnp.max(grp, axis=1)
    scale = (hi - lo) / 15.0
    safe = jnp.maximum(scale, 1e-12)
    # The zero-point is stored as an f32 row, NOT packed, so it must not
    # be clipped to the code range: an all-positive (or all-negative)
    # group has -lo/s outside [0, 15], and clipping it would shift every
    # dequantized value by the clipped amount (a constant group would
    # reconstruct to 0 instead of its value). Only the CODES clip.
    zero = jnp.round(-lo / safe)
    codes = jnp.clip(
        jnp.round(grp / safe[:, None, :]) + zero[:, None, :], 0.0, 15.0
    ).reshape(k, n).astype(jnp.uint8)
    q4 = _pack_codes(codes, group)
    if n_contract == 1 and out_axes:
        q4 = q4.reshape((k // 2,) + out_axes)
    # Store the CLAMPED scale: the zero-point was computed against it,
    # and a constant group (raw scale 0) must dequantize as
    # (u - z)*safe = u*eps + lo, not (u - z)*0 = 0.
    return {"q4": q4, "qs4": safe.astype(jnp.float32),
            "qz4": zero.astype(jnp.float32)}


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))

# Rows of x one grid step multiplies, and so the unit in which a prefill
# launch's padding is skipped (module docstring: what a prefill launch
# pays).
BLOCK_M = 256
# The shortest row (positions) whose launch takes the map: a row of four
# blocks can end in one to three blocks of padding of its own, a shorter
# bucket only ever skips whole pad rows, and every program shape with
# the map is paid for at start-up (module docstring).
MAP_MIN_ROW = 4 * BLOCK_M


def _live_and(live, cond):
    return cond if live is None else live & cond


def _when_live(live):
    """pl.when(live); with no map, the body as it stands."""
    return (lambda body: body()) if live is None else pl.when(live)


def _q4_matmul_kernel(group, gk, x_ref, wp_ref, s_ref, z_ref, o_ref,
                      acc_ref, live=None):
    k = pl.program_id(2)
    half = group // 2

    @pl.when(_live_and(live, k == 0))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Per group: packed bytes -> two int32 nibble tiles -> ONE convert
    # each to the MXU dtype (the zero-point never touches the weight
    # tile: dot(x, u - z) == dot(x, u) - colsum(x) * z, so the asymmetric
    # offset folds into a [bm, 1] x [1, bn] outer product). The group
    # scale factors out of the block's contraction and lands on the
    # [bm, bn] partial product.
    @_when_live(live)
    def _accumulate():
        for g in range(gk):
            # Mosaic has no u8->bf16 cast: widen once to i32, mask/shift,
            # one convert per nibble tile.
            w32 = wp_ref[g * half:(g + 1) * half].astype(jnp.int32)
            u_lo = (w32 & 0xF).astype(x_ref.dtype)
            u_hi = (w32 >> 4).astype(x_ref.dtype)
            xg = x_ref[:, g * group:(g + 1) * group]
            part = jax.lax.dot_general(
                xg[:, :half], u_lo, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            part += jax.lax.dot_general(
                xg[:, half:], u_hi, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            xsum = jnp.sum(xg.astype(jnp.float32), axis=1, keepdims=True)
            z = z_ref[g].astype(jnp.float32)
            s = s_ref[g].astype(jnp.float32)
            acc_ref[:] += (part - xsum * z) * s

    @pl.when(_live_and(live, k == pl.num_programs(2) - 1))
    def _emit():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _live_rows_only(kernel):
    """`kernel` behind a scalar-prefetched `live` map: a row block with
    live[mi] == 0 runs none of the body (no unpack, no MXU pass) and
    stores zeros at its last k step, so its output is defined and no
    stale VMEM reaches the residual stream; a live block runs the body
    as it is."""

    def masked(live_ref, *refs):
        o_ref = refs[-2]
        live = live_ref[pl.program_id(0)] != 0
        kernel(*refs, live=live)

        @pl.when(jnp.logical_not(live)
                 & (pl.program_id(2) == pl.num_programs(2) - 1))
        def _zero():
            o_ref[:] = jnp.zeros_like(o_ref)

    return masked


def _k_block_groups(k: int, group: int) -> int:
    """Groups one k step contracts: the largest power of two, 32 at
    most, whose k-blocks divide K. Several groups a step make the DMA
    tiles large enough to amortize the grid and let Mosaic double-buffer
    the packed stream."""
    gk = 1
    while gk < 32 and k % (group * gk * 2) == 0:
        gk *= 2
    return gk


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def q4_matmul(x: jax.Array, q4: jax.Array, scale: jax.Array,
              zero: jax.Array, bm: int = BLOCK_M, bn: int = 1024,
              interpret: bool = False,
              live: jax.Array | None = None) -> jax.Array:
    """x [M, K] (bf16/f32) @ packed-int4 [K//2, N] with per-group
    scale/zero [K//group, N] -> [M, N] in x.dtype. The group is inferred
    from the scale shape, the k-block from _k_block_groups. `live`
    (int32 [ceil(M / bm)], live_row_blocks) says which row blocks hold a
    real position: the others do no work and come back exactly zero, the
    live ones are computed as without it, bit for bit. None is the
    kernel without a map."""
    require_packed(q4, "q4_matmul")
    m, k2 = x.shape[0], q4.shape[0]
    k = k2 * 2
    n = q4.shape[1]
    # Explicit raises (not asserts): geometry validation must survive
    # python -O, exactly like the lane-divisibility error below.
    if x.shape[1] != k:
        raise ValueError(
            f"q4_matmul: x columns must equal 2 * packed rows "
            f"(x {x.shape}, q4 {q4.shape})")
    if k % scale.shape[0]:
        raise ValueError(
            f"q4_matmul: scale rows must divide K (K={k}, "
            f"scale {scale.shape})")
    group = k // scale.shape[0]
    if scale.shape != (k // group, n):
        raise ValueError(
            f"q4_matmul: scale must be [K//group, N] "
            f"(got {scale.shape}, expected {(k // group, n)})")
    if zero.shape != scale.shape:
        raise ValueError(
            f"q4_matmul: zero must match scale shape "
            f"(zero {zero.shape}, scale {scale.shape})")
    bm = min(bm, max(16, 1 << max(0, m - 1).bit_length()))
    mp = -(-m // bm) * bm
    if live is not None and live.shape != (mp // bm,):
        raise ValueError(
            f"q4_matmul: live must name every row block "
            f"(live {live.shape}, {mp // bm} blocks of {bm} rows)")
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))
    b = min(bn, n)
    while b > 128 and n % b:
        b //= 2
    bn = b
    if n >= 128 and (bn % 128 or n % bn):
        raise ValueError(
            f"q4_matmul needs 128-lane-divisible geometry (N={n}); "
            "this weight cannot take the W4A16 kernel")
    gk = _k_block_groups(k, group)
    # Mosaic requires the sublane block dim to divide 8 or equal the
    # array dim: give the per-group rows a unit middle axis so each
    # scale/zero block spans full (singleton) sublane dimensions.
    s3 = scale.reshape(k // group, 1, n)
    z3 = zero.reshape(k // group, 1, n)
    grid = (mp // bm, n // bn, k // (group * gk))
    _, nn, nk = grid

    def held(fn):
        """With a `live` map every step of a dead row block asks for the
        tiles of the block's LAST step in place of its own, so the
        pipeline finds the weight, scale and zero tiles in VMEM already
        (the row block before it ended on them) and fetches one x tile a
        dead block, not one a step."""
        if live is None:
            return fn

        def index(mi, ni, ki, live_ref):
            dead = live_ref[mi] == 0
            return fn(mi, jnp.where(dead, nn - 1, ni),
                      jnp.where(dead, nk - 1, ki))

        return index

    per_group = lambda mi, ni, ki: (ki, 0, ni)  # noqa: E731
    in_specs = [
        pl.BlockSpec((bm, group * gk), held(lambda mi, ni, ki: (mi, ki))),
        pl.BlockSpec((group * gk // 2, bn),
                     held(lambda mi, ni, ki: (ki, ni))),
        pl.BlockSpec((gk, 1, bn), held(per_group)),
        pl.BlockSpec((gk, 1, bn), held(per_group)),
    ]
    out_specs = pl.BlockSpec((bm, bn), lambda mi, ni, ki, *_: (mi, ni))
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    out_shape = jax.ShapeDtypeStruct((mp, n), x.dtype)
    kernel = functools.partial(_q4_matmul_kernel, group, gk)
    if live is None:
        out = pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch,
            compiler_params=_COMPILER_PARAMS, interpret=interpret,
        )(x, q4, s3, z3)
    else:
        out = pl.pallas_call(
            _live_rows_only(kernel),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch),
            out_shape=out_shape, compiler_params=_COMPILER_PARAMS,
            interpret=interpret,
        )(live, x, q4, s3, z3)
    return out[:m]


def dequantize_q4(q4: jax.Array, scale: jax.Array,
                  zero: jax.Array) -> jax.Array:
    """Full-precision reconstruction [K, N] f32 (tests / ref path)."""
    require_packed(q4, "dequantize_q4")
    k2 = q4.shape[0]
    n = int(np.prod(q4.shape[1:]))
    group = (k2 * 2) // scale.shape[0]
    u = _unpack_codes(q4.reshape(k2, n), group).astype(jnp.float32)
    s = jnp.repeat(scale.reshape(-1, n), group, axis=0)
    z = jnp.repeat(zero.reshape(-1, n), group, axis=0)
    return (u - z) * s


def q4_matmul_ref(x: jax.Array, q4: jax.Array, scale: jax.Array,
                  zero: jax.Array,
                  live: jax.Array | None = None) -> jax.Array:
    """XLA reference: materializes the dequantized weight (correctness
    path, not the perf path). With `live`, the rows of a dead block come
    back zero as the kernel's do (nothing is saved here)."""
    w = dequantize_q4(q4, scale, zero)
    acc = jax.lax.dot_general(
        x, w.astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if live is not None:
        rows = jnp.repeat(live != 0, BLOCK_M)[:x.shape[0], None]
        acc = jnp.where(rows, acc, 0.0)
    return acc.astype(x.dtype)


def live_row_blocks(valid: jax.Array) -> jax.Array | None:
    """Which BLOCK_M-row blocks of a launch's flattened [B*T] positions
    hold a real position: int32 [ceil(B*T / BLOCK_M)], the `live` of
    q4_matmul, reduced from the [B, T] validity mask. None when rows
    are shorter than MAP_MIN_ROW (every decode step, the short prefill
    buckets): the call keeps the kernel without a map."""
    if valid.shape[-1] < MAP_MIN_ROW:
        return None
    m = valid.size
    flat = jnp.pad(valid.reshape(m), (0, -m % BLOCK_M))
    return flat.reshape(-1, BLOCK_M).any(axis=1).astype(jnp.int32)


def count_row_blocks(lengths, rows: int, bucket: int) -> tuple[int, int]:
    """(live, skipped) row blocks of a launch of `rows` x `bucket`
    positions whose row i holds `lengths[i]` real positions from its
    start (rows past the list are padding): live_row_blocks's answer
    from the host's own numbers, for the engine's counters. A launch
    without a map runs every block it has."""
    total = -(-rows * bucket // BLOCK_M)
    if bucket < MAP_MIN_ROW:
        return total, 0
    live = set()
    for i, n in enumerate(lengths):
        if n > 0:
            live.update(range(i * bucket // BLOCK_M,
                              (i * bucket + n - 1) // BLOCK_M + 1))
    return len(live), total - len(live)


def q4_einsum(spec: str, x: jax.Array, q4: jax.Array, qs4: jax.Array,
              qz4: jax.Array, rows: jax.Array | None = None) -> jax.Array:
    """Quantized drop-in for the transformer's dense einsums (mirror of
    q8_linear.q8_einsum over the packed-int4 leaves), for all five
    projection specs (the flat wo among them). `rows` is the launch's
    validity mask as live_row_blocks reduced it, once a forward: the
    matmul does no work for row blocks of padding and returns zeros
    there."""
    if spec in ("bth,hm->btm", "btm,mh->bth", "bth,hv->btv"):
        b, t, k = x.shape
        out_shape = (b, t, q4.shape[1])
        x2 = x.reshape(b * t, k)
        w2 = q4
    elif spec == "bth,hqd->btqd":
        b, t, k = x.shape
        _, qh, hd = q4.shape
        out_shape = (b, t, qh, hd)
        x2 = x.reshape(b * t, k)
        w2 = q4.reshape(k // 2, qh * hd)
    elif spec == "bth,hkd->btkd":
        b, t, k = x.shape
        _, kh, hd = q4.shape
        out_shape = (b, t, kh, hd)
        x2 = x.reshape(b * t, k)
        w2 = q4.reshape(k // 2, kh * hd)
    elif spec == "btqd,qdh->bth":
        b, t, qh, hd = x.shape
        h = q4.shape[-1]
        out_shape = (b, t, h)
        x2 = x.reshape(b * t, qh * hd)
        w2 = q4  # wo is stored flat [K//2, h] (pack blocks span heads)
    else:
        raise ValueError(f"q4_einsum does not support spec {spec!r}")
    path = kernel_path("DYNT_Q4_MATMUL")
    if path == "xla":
        out = q4_matmul_ref(x2, w2, qs4, qz4, live=rows)
    else:
        out = q4_matmul(x2, w2, qs4, qz4,
                        interpret=path == "interpret", live=rows)
    return out.reshape(out_shape)
