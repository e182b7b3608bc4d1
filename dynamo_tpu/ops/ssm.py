"""Mamba-2 (SSD) state ops: the decode state update and the chunked scan.

    S_t = exp(dt_t A) S_{t-1} + dt_t (x_t outer B_t)        S: [H, P, N]
    y_t = S_t C_t                                           (D x_t is the caller's)

Two callers, two shapes of the same recurrence:

  * decode (`ssm_state_update`): one token a row. Memory-bound: it reads
    and writes the whole float32 state of every live row (H*P*N*4 bytes
    each way) for a few hundred FLOPs a byte. The Pallas kernel updates
    the state IN PLACE (input_output_aliases), visits only live rows
    (their slot ids are prefetched; the rest of the grid re-points at the
    last live block, so nothing moves for them) and never round-trips the
    state through HBM between the update and the `S C` read-out.
  * prefill (`ssm_chunk_scan`): T tokens a row in chunks of `chunk`
    (published: 128), matmul form: inside a chunk the recurrence is a
    masked [L, L] attention-like product, between chunks a short scan
    carries the state. Takes an initial state and returns the final one,
    so the scheduler's chunked and batched prefill carry state between
    launches; a position with dt = 0 (padding) leaves the state as it was.
    XLA operations, no kernel: a Pallas kernel of the [L, L] products
    and the carry was faster alone (0.55 against 0.79 ms at 2 x 1024) and
    10-25% slower inside the layer at every shape the cell reaches (5.4
    against 4.3 ms a layer at 8 x 512), because its operands want heads
    before positions and XLA fuses this form into its neighbours (my
    chip runs, PR 30; PERF.md), so it was taken out.

State is float32 whatever the activations are (NVIDIA's serving notes for
the family ask for a float32 SSM cache; `ModelConfig.ssm_state_dtype`, and
no preset states another). The update rounds to the state's dtype before
it reads out, as a cache in that precision would.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def expand_groups(x: jax.Array, n_heads: int) -> jax.Array:
    """[..., G, N] -> [..., H, N]: head j reads group j // (H / G)."""
    g = x.shape[-2]
    return jnp.repeat(x, n_heads // g, axis=-2)


# ---------------------------------------------------------------------------
# decode: one token a row
# ---------------------------------------------------------------------------


def ssm_state_update_xla(state, dt, a, x, b, c, active):
    """Reference / CPU path. state [S, H, P, N] (all slots; row i is slot
    i), dt [S, H] f32 (after softplus), a [H] f32 (negative), x [S, H, P],
    b, c [S, H, N] (groups already expanded), active [S] bool.
    Returns (new state, y [S, H, P] f32); inactive rows keep their state
    and read y = 0."""
    s32 = state.astype(jnp.float32)
    decay = jnp.exp(dt * a[None, :])[:, :, None, None]
    dtx = (dt[:, :, None] * x.astype(jnp.float32))[..., None]
    new = decay * s32 + dtx * b.astype(jnp.float32)[:, :, None, :]
    new = new.astype(state.dtype)
    # read-out from the state AS STORED: a bf16 state (the control) is
    # rounded before it is read, as a cache in that precision would be
    y = jnp.sum(new.astype(jnp.float32)
                * c.astype(jnp.float32)[:, :, None, :], axis=-1)
    keep = active[:, None, None, None]
    return (jnp.where(keep, new, state),
            jnp.where(active[:, None, None], y, 0.0))


def _state_update_kernel(rows_ref, n_live_ref, s_ref, decay_ref, dtx_ref,
                         b_ref, c_ref, o_ref, y_ref, *, heads_per_step):
    """One grid step = one live row x `heads_per_step` heads.

    s_ref/o_ref [1, hb, P, N] f32 (the same HBM buffer); decay_ref
    [1, H, N] (exp(dt A), lane-replicated); dtx_ref [1, P, H] (dt x,
    TRANSPOSED: p on sublanes so a head's column broadcasts along lanes);
    b_ref/c_ref [1, H, N]; y_ref [1, P, H] (transposed likewise)."""
    i, j = pl.program_id(0), pl.program_id(1)
    n_live = n_live_ref[0]
    p, n = s_ref.shape[2], s_ref.shape[3]
    hb = heads_per_step

    @pl.when(i < n_live)
    def _update():
        lane = jax.lax.broadcasted_iota(jnp.int32, y_ref.shape[1:], 1)

        @pl.when(j == 0)
        def _():
            y_ref[0] = jnp.zeros(y_ref.shape[1:], y_ref.dtype)

        y = y_ref[0]
        for h in range(hb):
            head = j * hb + h
            decay = decay_ref[0, pl.ds(head, 1), :]  # [1, N]
            brow = b_ref[0, pl.ds(head, 1), :]
            crow = c_ref[0, pl.ds(head, 1), :]
            onehot = lane == head  # [P, H]
            col = jnp.sum(jnp.where(onehot, dtx_ref[0], 0.0), axis=1,
                          keepdims=True)  # [P, 1] = dt x of this head
            new = decay * s_ref[0, h].astype(jnp.float32) + col * brow
            new = new.astype(o_ref.dtype)  # [P, N], as stored
            o_ref[0, h] = new
            out = jnp.sum(new.astype(jnp.float32) * crow, axis=1,
                          keepdims=True)  # [P, 1]
            y = jnp.where(onehot, out, y)
        y_ref[0] = y

    # No live row at all (a warm-up call): step 0 still owns block 0 and
    # writes it back, so hand it through untouched.
    @pl.when(jnp.logical_and(n_live == 0, i == 0))
    def _through():
        o_ref[...] = s_ref[...]
        y_ref[0] = jnp.zeros(y_ref.shape[1:], y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads_per_step", "interpret"),
                   donate_argnums=(0,))
def ssm_state_update(state, dt, a, x, b, c, active, *,
                     heads_per_step: int | None = None,
                     interpret: bool = False):
    """Pallas decode update, in place over `state` [S, H, P, N] float32.
    Same contract as `ssm_state_update_xla`."""
    s, h, p, n = state.shape
    hb = heads_per_step or h
    assert h % hb == 0, (h, hb)
    # Live rows first, then the last live row again and again: a grid
    # step whose block index equals the one before moves nothing.
    order = jnp.argsort(jnp.logical_not(active), stable=True)
    n_live = jnp.sum(active).astype(jnp.int32)
    last = order[jnp.maximum(n_live - 1, 0)]
    rows = jnp.where(jnp.arange(s) < n_live, order, last).astype(jnp.int32)
    rows = jnp.where(n_live > 0, rows, 0)
    decay = jnp.broadcast_to(jnp.exp(dt * a[None, :])[:, :, None],
                             (s, h, n)).astype(jnp.float32)
    dtx = jnp.swapaxes(dt[:, :, None] * x.astype(jnp.float32), 1, 2)

    def row_map(i, j, rows_ref, n_ref):
        del j, n_ref
        return (rows_ref[i], 0, 0)

    def state_map(i, j, rows_ref, n_ref):
        # a dead grid step keeps the last live step's head block too
        return (rows_ref[i], jnp.where(i < n_ref[0], j, h // hb - 1), 0, 0)

    new_state, y_t = pl.pallas_call(
        functools.partial(_state_update_kernel, heads_per_step=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s, h // hb),
            in_specs=[
                pl.BlockSpec((1, hb, p, n), state_map),
                pl.BlockSpec((1, h, n), row_map),
                pl.BlockSpec((1, p, h), row_map),
                pl.BlockSpec((1, h, n), row_map),
                pl.BlockSpec((1, h, n), row_map),
            ],
            out_specs=[
                pl.BlockSpec((1, hb, p, n), state_map),
                pl.BlockSpec((1, p, h), row_map),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((s, p, h), jnp.float32),
        ],
        input_output_aliases={2: 0},
        interpret=interpret,
        name="ssm_state_update",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
    )(rows, n_live.reshape(1), state, decay, dtx,
      b.astype(jnp.float32), c.astype(jnp.float32))
    y = jnp.where(active[:, None, None], jnp.swapaxes(y_t, 1, 2), 0.0)
    return new_state, y


# ---------------------------------------------------------------------------
# prefill: a chunk of tokens a row
# ---------------------------------------------------------------------------


def ssm_chunk_scan(state, dt, a, x, b, c, *, chunk: int):
    """Chunked scan over T positions. state [B, H, P, N] (the rows'
    initial state, float32), dt [B, T, H] f32 (0 at padding), a [H] f32,
    x [B, T, H, P], b, c [B, T, G, N] (groups NOT expanded: the [L, L]
    product is shared by a group's heads). Returns (final state,
    y [B, T, H, P] f32)."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    length = min(chunk, t)
    assert t % length == 0, (t, length)
    nc = t // length
    hg = h // g
    f32 = jnp.float32
    with jax.named_scope("ssm_scan"):
        da = (dt * a[None, None, :]).reshape(bsz, nc, length, g, hg)
        cs = jnp.cumsum(da, axis=2)  # inclusive, within the chunk
        dtc = dt.reshape(bsz, nc, length, g, hg)
        xc = x.reshape(bsz, nc, length, g, hg, p)
        bc = b.reshape(bsz, nc, length, g, n)
        cc = c.reshape(bsz, nc, length, g, n)
        # inside a chunk: y_t += sum_{s<=t} exp(cs_t - cs_s) dt_s (C_t.B_s) x_s
        cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc,
                        preferred_element_type=f32)
        seg = cs[:, :, :, None] - cs[:, :, None, :]  # [b, c, l, s, g, hg]
        causal = jnp.tril(jnp.ones((length, length), bool))
        weights = jnp.where(causal[None, None, :, :, None, None],
                            jnp.exp(jnp.where(
                                causal[None, None, :, :, None, None],
                                seg, 0.0)), 0.0)
        weights = (weights * dtc[:, :, None]
                   * jnp.moveaxis(cb, 2, 4)[..., None])
        y = jnp.einsum("bclsgh,bcsghp->bclghp", weights.astype(x.dtype), xc,
                       preferred_element_type=f32)
        # what a chunk adds to the state, decayed to the chunk's end
        to_end = jnp.exp(cs[:, :, -1:] - cs) * dtc  # [b, c, l, g, hg]
        local = jnp.einsum("bclghp,bclgn->bcghpn",
                           (to_end[..., None] * xc).astype(x.dtype), bc,
                           preferred_element_type=f32)
        total = jnp.exp(cs[:, :, -1])  # [b, c, g, hg]: a whole chunk's decay

        def carry(s, inputs):
            chunk_decay, chunk_local = inputs
            return chunk_decay[..., None, None] * s + chunk_local, s

        s0 = state.astype(f32).reshape(bsz, g, hg, p, n)
        final, entering = jax.lax.scan(
            carry, s0, (jnp.moveaxis(total, 1, 0),
                        jnp.moveaxis(local, 1, 0)))
        entering = jnp.moveaxis(entering, 0, 1)  # [b, c, g, hg, p, n]
        # across chunks: y_t += exp(cs_t) C_t . S_entering
        y = y + jnp.exp(cs)[..., None] * jnp.einsum(
            "bclgn,bcghpn->bclghp", cc.astype(f32), entering,
            preferred_element_type=f32)
    return (final.reshape(bsz, h, p, n).astype(state.dtype),
            y.reshape(bsz, t, h, p))


# ---------------------------------------------------------------------------
# the causal depthwise convolution in front of the scan
# ---------------------------------------------------------------------------


def causal_conv(carry, x, weight, bias, n_valid):
    """Depthwise causal convolution of width K over T positions with a
    carry of the K-1 inputs before them. carry [B, K-1, C], x [B, T, C],
    weight [K, C] (tap K-1 multiplies the current position), bias [C],
    n_valid [B]: how many leading positions of each row are real. Returns
    (silu(conv + bias) [B, T, C], new carry): the last K-1 REAL inputs of
    each row, so padding never enters it (n_valid = 0 keeps the carry)."""
    k = weight.shape[0]
    seq = jnp.concatenate([carry.astype(x.dtype), x], axis=1)
    t = x.shape[1]
    out = sum(seq[:, i:i + t].astype(jnp.float32)
              * weight[i].astype(jnp.float32) for i in range(k))
    out = jax.nn.silu(out + bias.astype(jnp.float32)).astype(x.dtype)
    idx = n_valid[:, None] + jnp.arange(k - 1)[None, :]  # [B, K-1]
    new_carry = jnp.take_along_axis(seq, idx[:, :, None], axis=1)
    return out, new_carry.astype(carry.dtype)
