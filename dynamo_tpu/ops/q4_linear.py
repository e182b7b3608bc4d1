"""Weight-only int4 linear layers (W4A16) — the second halving of the
decode weight stream.

W8A16 (ops/q8_linear.py) halves the bytes decode streams from HBM every
step; W4A16 halves them again: the dense projection stack is stored as
packed 4-bit codes (two per byte) with per-group asymmetric scale/zero
rows (group = PACK_BLOCK contracted rows; DYNT_Q4_GROUP=128 gives the
finer GPTQ/AWQ-convention groups), so a 7B's projections drop from
~14.5 GB (bf16) to ~3.6 GB streamed per decode step. The Pallas kernel
dequantizes IN VMEM — packed bytes stream from HBM, nibbles unpack on
the VPU, and the MXU consumes bf16 tiles — so the bf16 (or even int8)
weight never exists in HBM.

Math: per-group asymmetric codes dequantize as (u - z) * s with s, z
constant over each contracted group. Within a group the scale has no
contracted axis, so it factors out of the partial dot, and the integer
zero-point folds into a rank-1 correction instead of touching the
weight tile:
  x @ dequant(u) == sum_g (x_g @ u_g - colsum(x_g) * z_g) * s_g

Two pack layouts coexist, selected by DYNT_Q4_VARIANT at quantize time
and dispatched by the packed dtype (uint8 = v1, int8 = v2 — the version
travels with the leaf, jit-static, no extra pytree field). `auto` is v1:
Mosaic (jax 0.9.0, v5e) refuses v2's int8 nibble shifts (`arith.shli`
on i8 vectors does not legalize), so v2 as designed has never run on a
chip; its kernel now widens to int32 first, which compiles but gives up
the one-convert unpack v2 was built for. Which layout is faster on the
chip is not measured (ROADMAP A5 decides and deletes the loser):

v1 (half-block, uint8): within each group, byte row r holds code row r
  in its LOW nibble and code row r + group//2 in its HIGH nibble.
  Unpacking a group yields two half-group tiles, so the kernel pays two
  half-contraction dots per group and a full [bm, bn] VPU pass per
  group for the scale/zero epilogue, all through an int32 widen.
  What a prefill launch pays (v5e, 7B shapes, bf16 x; PERF.md PR 31):
  the weight tile is unpacked again for every BLOCK_M = 256 rows of x,
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
  kernel without a map: the same program as before `live` existed.

v2 (VPU-swizzled global half-split, int8): byte row r of the WHOLE
  packed array holds code row r (low nibble) and code row r + K/2
  (high nibble), codes biased to signed (c = u - 8) so nibble
  sign-extension is two int8 shifts — the q8_linear dequant idiom (one
  narrow-int unpack, ONE convert per tile) instead of the v1 int32
  mask/shift/convert pipeline. Each nibble tile of a k-block then IS a
  contiguous run of whole groups in contracted order, so the k-step
  collapses to one full-width dot per nibble tile (the unpack fuses
  into the k-block contraction), the per-group scale rides the weight
  tile, and the zero-point correction becomes one small
  [bm, groups] x [groups, bn] MXU dot per tile instead of per-group
  [bm, bn] VPU passes. Scale/zero rows are byte-identical to v1 (the
  kernel subtracts the +8 bias inside the rank-1 term), which keeps
  v1<->v2 repacking a pure transform of the code bytes — bit-exact
  roundtrips by construction. v2 needs K % (2*group) == 0; smaller
  weights (tests' tiny models) fall back to v1.

The reference reaches this lever through its engines' 4-bit checkpoint
modes (vLLM/TRT-LLM AWQ/GPTQ w4a16 paths); BASELINE.md names weight
streaming as the decode floor at 7B. The variant x block-size ablation
harness lives in dynamo_tpu/perf/q4_ablation.py (scripts/q4_ablate.py,
bench.py's q4_ablation block).
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
# bakes the group in — see module docstring). 256 measured fastest on
# v5e (706 tok/s decode at 7B vs 615 at group 128 — BASELINE.md r5);
# DYNT_Q4_GROUP=128 selects the finer GPTQ/AWQ-convention groups when
# quality matters more than the last ~15% of decode. Small-geometry
# weights (tests' tiny models) fall back to the largest power-of-two
# divisor of K.
PACK_BLOCK = 256

# Pack-layout versions (see module docstring). The version is encoded in
# the packed dtype — uint8 = v1, int8 = v2 — so it is jit-static, rides
# every pytree/wire hop for free, and q4_einsum carries it through all
# five projection specs (including the flat wo) untouched.
PACK_V1 = 1
PACK_V2 = 2


def pack_version(q4) -> int:
    """Layout version of a packed-int4 leaf (dtype-encoded)."""
    return PACK_V2 if q4.dtype == jnp.int8 else PACK_V1


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


def resolve_pack_version(k: int, group: int | None = None,
                         strict: bool = True) -> int:
    """Pack layout for a weight with contracted size `k` under the
    DYNT_Q4_VARIANT policy: auto = v1 (the layout that has run on the
    chip — module docstring); v1/v2 force the layout. Forcing v2 on a K
    whose global half-split is not well-formed (K must divide 2*group)
    raises when `strict` (the quantizer must not mis-pack) and falls
    back to v1 otherwise (the load-time repack keeps such leaves as
    they are). An unknown mode ALWAYS raises — a typo'd knob must not
    silently pick a layout."""
    from ..runtime.config import env

    g = group or _group_for(k)
    mode = env("DYNT_Q4_VARIANT") or "auto"
    if mode not in ("auto", "v1", "v2"):
        raise ValueError(
            f"unknown DYNT_Q4_VARIANT {mode!r} (expected auto|v1|v2)")
    if mode != "v2":
        return PACK_V1
    if k % (2 * g):
        if strict:
            raise ValueError(
                f"DYNT_Q4_VARIANT=v2 needs K % (2*group) == 0 "
                f"(K={k}, group={g}); this weight only supports the "
                "v1 half-block layout")
        return PACK_V1
    return PACK_V2

# Leaf name -> number of LEADING contracted axes (same registry shape as
# q8_linear.QUANT_LEAVES; shared by the quantizer and model plumbing).
QUANT_LEAVES = {
    "wq": 1, "wk": 1, "wv": 1, "wo": 2,
    "w_gate": 1, "w_up": 1, "w_down": 1,
    "lm_head": 1,
}


def _pack_codes(u: jnp.ndarray, group: int) -> jnp.ndarray:
    """v1: uint8 codes [K, N] in [0, 15] -> packed uint8 [K//2, N] in
    the half-block layout (byte row r of each group holds code rows r
    and r + group//2)."""
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


def _pack_codes_v2(u: jnp.ndarray) -> jnp.ndarray:
    """v2: uint8 codes [K, N] in [0, 15] -> packed int8 [K//2, N] in the
    global half-split layout: byte row r holds code row r (low nibble)
    and code row r + K//2 (high nibble), both biased to signed
    two's-complement nibbles (c = u - 8, and (u - 8) & 0xF ==
    (u + 8) & 0xF mod 16)."""
    k, n = u.shape
    half = k // 2
    lo = (u[:half].astype(jnp.int32) + 8) & 0xF
    hi = (u[half:].astype(jnp.int32) + 8) & 0xF
    return jax.lax.bitcast_convert_type(
        (lo | (hi << 4)).astype(jnp.uint8), jnp.int8)


def _unpack_codes_v2(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of _pack_codes_v2 -> UNSIGNED codes [K, N] in [0, 15]
    (reference path / tests; u = nibble ^ 8 undoes the sign bias)."""
    b = jax.lax.bitcast_convert_type(packed, jnp.uint8)
    lo = (b & 0xF) ^ 8
    hi = (b >> 4) ^ 8
    return jnp.concatenate([lo, hi], axis=0)


def quantize_weight_q4(w: jax.Array, n_contract: int,
                       version: int | None = None) -> dict:
    """Asymmetric per-group int4 over the contracted axes.

    Returns {"q4": packed uint8 (v1) / int8 (v2), "qs4": f32
    [K//group, N], "qz4": f32 [K//group, N]}. The scale/zero rows are
    identical across layouts (only the code bytes differ), so v1<->v2
    repacking never touches them. q4 keeps the weight's output axes when
    a single leading axis is contracted ([K//2, *out_axes]); multi-axis
    contractions (wo) flatten to 2-D [K//2, N] because pack groups span
    head boundaries. `version` None follows DYNT_Q4_VARIANT
    (resolve_pack_version).
    """
    out_axes = w.shape[n_contract:]
    k = int(np.prod(w.shape[:n_contract]))
    n = int(np.prod(out_axes)) if out_axes else 1
    group = _group_for(k)
    if version is None:
        version = resolve_pack_version(k, group)
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
    if version == PACK_V2:
        if k % (2 * group):
            raise ValueError(
                f"pack layout v2 needs K % (2*group) == 0 (K={k}, "
                f"group={group})")
        q4 = _pack_codes_v2(codes)
    else:
        q4 = _pack_codes(codes, group)
    if n_contract == 1 and out_axes:
        q4 = q4.reshape((k // 2,) + out_axes)
    # Store the CLAMPED scale: the zero-point was computed against it,
    # and a constant group (raw scale 0) must dequantize as
    # (u - z)*safe = u*eps + lo, not (u - z)*0 = 0.
    return {"q4": q4, "qs4": safe.astype(jnp.float32),
            "qz4": zero.astype(jnp.float32)}


# -- host-side repack (checkpoint migration; pure numpy, no device) -----


def _np_unpack_v1(q2: np.ndarray, group: int) -> np.ndarray:
    k2, n = q2.shape
    half = group // 2
    blk = q2.reshape(k2 // half, half, n)
    return np.concatenate([blk & 0xF, blk >> 4], axis=1).reshape(
        k2 * 2, n).astype(np.uint8)


def _np_pack_v1(u: np.ndarray, group: int) -> np.ndarray:
    k, n = u.shape
    half = group // 2
    blk = u.reshape(k // group, group, n)
    return (blk[:, :half] | (blk[:, half:] << 4)).reshape(
        k // 2, n).astype(np.uint8)


def _np_unpack_v2(packed: np.ndarray) -> np.ndarray:
    b = packed.view(np.uint8)
    return np.concatenate([(b & 0xF) ^ 8, (b >> 4) ^ 8],
                          axis=0).astype(np.uint8)


def _np_pack_v2(u: np.ndarray) -> np.ndarray:
    k, n = u.shape
    half = k // 2
    lo = (u[:half].astype(np.int32) + 8) & 0xF
    hi = (u[half:].astype(np.int32) + 8) & 0xF
    return (lo | (hi << 4)).astype(np.uint8).view(np.int8)


def repack_q4_leaf(leaf: dict, version: int | None = None) -> dict:
    """Host-side layout migration of one quantized leaf. `version` None
    follows DYNT_Q4_VARIANT (auto keeps v1 where v2's half-split is not
    well-formed). Scale/zero rows pass through untouched and the code
    transform is a bijection on nibbles, so v1 -> v2 -> v1 roundtrips
    bit-exactly. Returns the SAME dict when no repack is needed (device
    leaves are never pulled to host for a no-op)."""
    q4 = leaf["q4"]
    cur = pack_version(q4)
    k2 = q4.shape[0]
    k = k2 * 2
    qs4 = leaf["qs4"]
    group = k // qs4.shape[0]
    if version is None:
        # non-strict: a forced variant this K can't take keeps the leaf
        # as-is; an unknown DYNT_Q4_VARIANT still raises.
        version = resolve_pack_version(k, group, strict=False)
    if version == cur:
        return leaf
    n = int(np.prod(q4.shape[1:]))
    q2 = np.asarray(q4).reshape(k2, n)
    if version == PACK_V2:
        if k % (2 * group):
            raise ValueError(
                f"cannot repack to v2: K % (2*group) != 0 (K={k}, "
                f"group={group})")
        out = _np_pack_v2(_np_unpack_v1(q2, group))
    else:
        out = _np_pack_v1(_np_unpack_v2(q2), group)
    return {"q4": out.reshape(q4.shape), "qs4": qs4, "qz4": leaf["qz4"]}


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


def _q4_matmul_kernel_v2(group, gh, x_lo_ref, x_hi_ref, wp_ref,
                         s_lo_ref, s_hi_ref, z_lo_ref, z_hi_ref, o_ref,
                         acc_ref, live=None):
    """v2: the packed tile's nibbles ARE contracted order (low nibbles =
    `gh` whole groups of the low K-half, high nibbles = the matching
    groups of the high K-half), so each k-step is two full-width dots.
    The signed nibbles sign-extend by shifts — on an int32 widen, since
    Mosaic has no int8 vector shifts (module docstring) — and the
    per-group scale rides the weight tile while the zero-point (incl.
    the -8 bias absorbed by the signed codes) folds into one small
    [bm, gh] x [gh, bn] dot per tile."""
    k = pl.program_id(2)
    kb2 = group * gh

    @pl.when(_live_and(live, k == 0))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @_when_live(live)
    def _accumulate():
        # [kb2, bn] int8, two signed nibbles per byte; the widen
        # sign-extends, so arithmetic shifts recover both.
        w32 = wp_ref[:].astype(jnp.int32)
        lo = jnp.right_shift(jnp.left_shift(w32, 28), 28)
        hi = jnp.right_shift(w32, 4)
        bn = o_ref.shape[1]
        for x_ref, s_ref, z_ref, codes in (
                (x_lo_ref, s_lo_ref, z_lo_ref, lo),
                (x_hi_ref, s_hi_ref, z_hi_ref, hi)):
            x = x_ref[:]
            s = s_ref[:].astype(jnp.float32)  # [gh, 1, bn]
            z = z_ref[:].astype(jnp.float32)
            # One convert per nibble tile; the scale broadcasts over each
            # group's sublanes and lands on the weight tile, so the dot
            # spans all `gh` groups at once.
            sw = jnp.broadcast_to(s, (gh, group, bn)).reshape(kb2, bn)
            u = codes.astype(x.dtype) * sw.astype(x.dtype)
            part = jax.lax.dot_general(
                x, u, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            # Rank-1 zero-point for all gh groups as ONE small MXU dot:
            # per-group colsums via a 0/1 block-diagonal mask, then
            # [bm, gh] x [gh, bn] against the (z - 8) * s rows (the
            # signed codes are u - 8, so the stored v1-convention zero
            # row shifts by the same bias here instead of at pack time —
            # repacks stay bit-exact).
            rows = jax.lax.broadcasted_iota(jnp.int32, (kb2, gh), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (kb2, gh), 1)
            gmask = (rows // group == cols).astype(x.dtype)
            xsum = jax.lax.dot_general(
                x, gmask, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            zs = ((z - 8.0) * s).reshape(gh, bn)
            acc_ref[:] += part - jax.lax.dot_general(
                xsum, zs, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

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


def _q4_call(kernel, grid, in_maps, in_blocks, out_block, out_shape,
             live, interpret):
    """The pallas_call both layouts share. `in_maps` take the grid step
    (mi, ni, ki). With a `live` map every step of a dead row block asks
    for the tiles of the block's LAST step in place of its own, so the
    pipeline finds the weight, scale and zero tiles in VMEM already
    (the row block before it ended on them) and fetches one x tile a
    dead block, not one a step."""
    _, nn, nk = grid

    def held(fn):
        if live is None:
            return fn

        def index(mi, ni, ki, live_ref):
            dead = live_ref[mi] == 0
            return fn(mi, jnp.where(dead, nn - 1, ni),
                      jnp.where(dead, nk - 1, ki))

        return index

    in_specs = [pl.BlockSpec(blk, held(fn))
                for blk, fn in zip(in_blocks, in_maps)]
    out_specs = pl.BlockSpec(out_block, lambda mi, ni, ki, *_: (mi, ni))
    scratch = [pltpu.VMEM(out_block, jnp.float32)]
    if live is None:
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch,
            compiler_params=_COMPILER_PARAMS, interpret=interpret)
    return functools.partial(pl.pallas_call(
        _live_rows_only(kernel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape, compiler_params=_COMPILER_PARAMS,
        interpret=interpret), live)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "gk", "interpret"))
def q4_matmul(x: jax.Array, q4: jax.Array, scale: jax.Array,
              zero: jax.Array, bm: int = BLOCK_M, bn: int = 1024,
              gk: int = 0, interpret: bool = False,
              live: jax.Array | None = None) -> jax.Array:
    """x [M, K] (bf16/f32) @ packed-int4 [K//2, N] with per-group
    scale/zero [K//group, N] -> [M, N] in x.dtype. The group (and the
    kernel's k-block) is inferred from the scale shape; the kernel
    variant is dispatched from the packed dtype (uint8 = v1 half-block,
    int8 = v2 swizzled — see module docstring). `gk` overrides the
    groups contracted per k-step (0 = auto; the ablation harness sweeps
    it). `live` (int32 [ceil(M / bm)], live_row_blocks) says which row
    blocks hold a real position: the others do no work and come back
    exactly zero, the live ones are computed as without it, bit for bit.
    None is the kernel without a map."""
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
    version = pack_version(q4)
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
    # Process several groups per k-block: bigger DMA tiles amortize the
    # grid and let Mosaic double-buffer the packed stream. A k-step
    # contracts group*gk codes for either variant (v2 splits them as
    # gk/2 whole groups per nibble tile, so it needs gk even).
    if gk:
        if k % (group * gk):
            raise ValueError(
                f"q4_matmul: gk={gk} does not divide the contraction "
                f"(K={k}, group={group})")
        if version == PACK_V2 and gk % 2:
            raise ValueError(
                f"q4_matmul: the v2 layout needs an even gk (got {gk})")
    else:
        # v2 holds its whole k-block unpacked (int32 nibbles, the scale
        # tile and the scaled bf16 tile) where v1 loops group by group:
        # past 4 groups per step that exceeds the v5e's scoped VMEM.
        limit = 4 if version == PACK_V2 else 32
        gk = 1
        while gk < limit and k % (group * gk * 2) == 0:
            gk *= 2
    # Mosaic requires the sublane block dim to divide 8 or equal the
    # array dim: give the per-group rows a unit middle axis so each
    # scale/zero block spans full (singleton) sublane dimensions.
    s3 = scale.reshape(k // group, 1, n)
    z3 = zero.reshape(k // group, 1, n)
    out_shape = jax.ShapeDtypeStruct((mp, n), x.dtype)
    if version == PACK_V2:
        gh = gk // 2
        kb2 = group * gh  # packed byte rows (= codes per nibble tile)
        nk = (k // 2) // kb2
        lo = lambda mi, ni, ki: (ki, 0, ni)  # noqa: E731
        hi = lambda mi, ni, ki: (ki + nk, 0, ni)  # noqa: E731
        out = _q4_call(
            functools.partial(_q4_matmul_kernel_v2, group, gh),
            (mp // bm, n // bn, nk),
            [lambda mi, ni, ki: (mi, ki),
             lambda mi, ni, ki: (mi, ki + nk),
             lambda mi, ni, ki: (ki, ni), lo, hi, lo, hi],
            [(bm, kb2), (bm, kb2), (kb2, bn)] + [(gh, 1, bn)] * 4,
            (bm, bn), out_shape, live, interpret,
        )(x, x, q4, s3, s3, z3, z3)
        return out[:m]
    per_group = lambda mi, ni, ki: (ki, 0, ni)  # noqa: E731
    out = _q4_call(
        functools.partial(_q4_matmul_kernel, group, gk),
        (mp // bm, n // bn, k // (group * gk)),
        [lambda mi, ni, ki: (mi, ki), lambda mi, ni, ki: (ki, ni),
         per_group, per_group],
        [(bm, group * gk), (group * gk // 2, bn), (gk, 1, bn),
         (gk, 1, bn)],
        (bm, bn), out_shape, live, interpret,
    )(x, q4, s3, z3)
    return out[:m]


def dequantize_q4(q4: jax.Array, scale: jax.Array,
                  zero: jax.Array) -> jax.Array:
    """Full-precision reconstruction [K, N] f32 (tests / ref path);
    dispatches the unpack on the layout version like the kernel."""
    k2 = q4.shape[0]
    n = int(np.prod(q4.shape[1:]))
    group = (k2 * 2) // scale.shape[0]
    q2 = q4.reshape(k2, n)
    if pack_version(q4) == PACK_V2:
        u = _unpack_codes_v2(q2).astype(jnp.float32)
    else:
        u = _unpack_codes(q2, group).astype(jnp.float32)
    s = jnp.repeat(scale.reshape(-1, n), group, axis=0)
    z = jnp.repeat(zero.reshape(-1, n), group, axis=0)
    return (u - z) * s


def q4_matmul_ref(x: jax.Array, q4: jax.Array, scale: jax.Array,
                  zero: jax.Array,
                  live: jax.Array | None = None) -> jax.Array:
    """XLA reference: materializes the dequantized weight (correctness
    path, not the perf path). Layout-agnostic via dequantize_q4. With
    `live`, the rows of a dead block come back zero as the kernel's do
    (nothing is saved here)."""
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
    q8_linear.q8_einsum over the packed-int4 leaves). The pack-layout
    version rides the q4 dtype through every reshape, so all five
    projection specs (including the flat wo) dispatch the right kernel
    variant without extra plumbing. `rows` is the launch's validity
    mask as live_row_blocks reduced it, once a forward: the matmul does
    no work for row blocks of padding and returns zeros there."""
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
