"""Dropless routed experts: sort the T x k assignments by expert, one
grouped matmul over the experts HELD here, combine.

The capacity path (`models/transformer._moe`) gives every expert a fixed
buffer and drops what overflows; at 2048 prefill tokens x 6 x 128 experts
its one-hot is not buildable. Here the buffer is the assignments
themselves, [T*k, h], sorted so that an expert's rows are contiguous; the
grouped matmul (`jax.experimental.pallas.ops.tpu.megablox.gmm`, which
ships with JAX; `jax.lax.ragged_dot` off the TPU) walks it tile by tile
and reads only the weights of experts that have rows. Nothing is dropped
by construction; `dropped` counts the held assignments whose row in the
sorted buffer the grouped matmul was not given, so that a counter can
say so: the rows come from the sort and the group sizes from a
histogram, and the two agree only while both are right.

A chip of an expert-parallel pair holds experts [lo, hi) of the published
count. The router's top-k is over all of them; an assignment to an absent
expert sorts behind the held ones, is never multiplied and adds nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# (tm, tk, tn) of the grouped matmul, chosen on the v5e for h=2688 and
# expert width 1856: a whole contraction and a wide slab of outputs a
# step, so that a step's DMA (2-3 MB of weights) dwarfs its fixed cost.
# A wider contraction narrows the slab (h=7680: tn 256, 3.9 MB).
GMM_ROW_TILE = 128


# A step's weight slab [k, tn] is double-buffered in VMEM beside the row
# tile; past this a contraction of 7680 with tn = 512 asks for 19 MB of
# the 16 MB a kernel may have (the compiler refuses it).
GMM_SLAB_BYTES = 4 << 20


def _tiling(k: int, n: int) -> tuple[int, int, int]:
    tn = next((c for c in (512, 384, 256, 128)
               if n >= c and (c == 128 or k * c * 2 <= GMM_SLAB_BYTES)), n)
    return (GMM_ROW_TILE, k, tn)


@functools.partial(jax.jit, static_argnames=("path", "transpose_rhs"))
def expert_gmm(lhs, rhs, group_sizes, *, path: str,
               transpose_rhs: bool = False):
    """lhs [M, K] rows sorted by group, rhs [E, K, N] (or [E, N, K] with
    `transpose_rhs`), group_sizes [E] -> [M, N] float32; rows past
    sum(group_sizes) are undefined.

    Why `transpose_rhs`: the TPU lays an array out with whichever of its
    two minor dimensions pads least to 128 lanes innermost, and the
    kernel wants rows as stored. An up-projection kept as [E, h, 1856]
    would be stored 2688-innermost and copied whole (0.64 GB a layer) in
    front of every launch; kept as [E, 1856, h] it is read in place."""
    if path == "xla":
        if transpose_rhs:
            rhs = jnp.swapaxes(rhs, 1, 2)
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return gmm(lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
               tiling=_tiling(lhs.shape[1], n), transpose_rhs=transpose_rhs,
               interpret=path == "interpret")


def dropped_slots(here, inverse, n_rows):
    """Held assignments (`here`, [T*k] bool) whose row in the sorted
    buffer (`inverse`, the row each assignment went to) lies past the
    `n_rows` the group sizes cover: computed by nothing, zeroed in the
    combine. 0 while the sort and the histogram agree and the buffer
    holds every assignment."""
    return jnp.sum(here & (inverse >= n_rows)).astype(jnp.int32)


def dropless_experts(x, weights, topi, valid, w_up, w_down, held, act,
                     *, path: str):
    """x [T, h]; weights, topi [T, k] (global expert ids); valid [T] bool
    (padding and empty rows route nowhere); w_up [E_held, m, h] (stored
    output-major: `expert_gmm`), w_down [E_held, m, h]; held = (lo, hi).
    Returns (out [T, h] in x's dtype, tokens per held expert [E_held]
    int32, dropped int32).

    The combine un-sorts the down-projection's float32 rows with ONE
    gather whose slots lie on the major axis, [k, T, h], and masks,
    weighs and sums them in one fusion over that axis: two passes over
    the rows, 2 x T*k*h*4 bytes read and one written (1.02 GB a layer at
    granite's [2048, 4096] top-10). Gathered token-major, [T, k, h], the
    TPU tiles (k, h) as (8, 128): k = 10 pads to 16 sublanes (4 and 6
    likewise) and XLA relays the whole array out to get there, and a
    mask over the sorted rows is a pass of its own: 2.77 GB a layer
    (PERF.md, PR 46). Rows past the groups are undefined (`expert_gmm`)
    and may hold NaN, so they are dropped by a select on the gathered
    row's index, never by a zero weight."""
    t, h = x.shape
    k = topi.shape[1]
    lo, hi = held
    n_held = hi - lo
    m_rows = -(-t * k // GMM_ROW_TILE) * GMM_ROW_TILE
    local = topi.reshape(-1) - lo
    here = ((local >= 0) & (local < n_held)
            & jnp.repeat(valid, k))
    key = jnp.where(here, local, n_held)  # absent / padding sort last
    order = jnp.argsort(key, stable=True)
    counts = jnp.zeros((n_held + 1,), jnp.int32).at[key].add(1)[:n_held]
    token_of = order // k
    rows = x[token_of]
    if m_rows > t * k:
        rows = jnp.pad(rows, ((0, m_rows - t * k), (0, 0)))
    with jax.named_scope("expert_gmm"):
        up = expert_gmm(rows, w_up, counts, path=path, transpose_rhs=True)
        mid = act(up).astype(x.dtype)
        down = expert_gmm(mid, w_down, counts, path=path)
    n_rows = jnp.sum(counts)
    inverse = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    row_of = inverse.reshape(t, k).T  # [k, T], every one under T*k
    weighed = down[row_of] * weights.T[:, :, None].astype(jnp.float32)
    out = jnp.sum(jnp.where((row_of < n_rows)[:, :, None], weighed, 0.0),
                  axis=0)
    return out.astype(x.dtype), counts, dropped_slots(here, inverse, n_rows)
