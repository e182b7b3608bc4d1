"""State-space mixers' ops: Mamba-2 (SSD)'s decode state update and
chunked scan, and behind them Mamba-1's (`selective_scan`,
`selective_state_update`).

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
  * prefill (`ssm_chunk_scan`, `ssm_chunk_scan_kernel`): T tokens a row
    in chunks of `chunk` (published: 128), matmul form: inside a chunk
    the recurrence is a masked [L, L] attention-like product, between
    chunks the state is carried. Takes an initial state and returns the
    final one, so the scheduler's chunked and batched prefill carry
    state between launches; a position with dt = 0 (padding) leaves the
    state as it was. Two forms of one contract:
      - `ssm_chunk_scan`, XLA operations: the CPU path, the oracle and
        the fallback for shapes `scan_kernel_tiles` refuses. On the
        chip it writes every [L, L] product, every chunk's state and
        relaid copies of x and y (heads before positions and back) to
        HBM: a granite layer moved 2.3-2.8 GB where 0.6 would do.
      - `ssm_chunk_scan_kernel`, one Pallas kernel a layer (PR 43),
        which reads x | b | c where the conv wrote them, [positions,
        heads x head_dim] with heads along lanes, and writes y the same
        way: a head block is a slice of lanes picked by a BlockSpec, a
        chunk's products and the carried state stay in VMEM, and
        nothing is transposed on either side of the call.
    PR 30 had a Pallas scan too and took it out: its operands wanted
    heads before positions, so it paid the relayouts XLA pays and more
    (0.55 against 0.79 ms alone at 2 x 1024 x 64 heads, 5.4 against
    4.3 ms a layer at 8 x 512). The layout is what differs now.
    Measured on a v5e (my chip runs, PR 43; kernel | XLA form, ms):
    alone at [1, 2048] x 128 heads in one group 0.51 | 1.04, at
    [8, 512] x 64 heads in 8 groups 0.65 | 2.38, y and state bit-equal;
    a whole `mamba_prefill` layer at granite's widths [1, 2048]
    3.41 | 4.53, at nemotron's [8, 512] 2.73 | 5.03.

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


# Lanes of a vreg: a head block of `x` and `y` is a slice of whole lane
# tiles, and the kernel walks it a tile (LANES // head_dim heads) at a time.
LANES = 128
# Heads a grid step: 16 x 64 lanes of `x` and `y` and a 0.5 MB float32
# state block. 32 read 4% under it alone at twice the VMEM, 8 8% over it
# (0.49 | 0.51 | 0.55 ms at [1, 2048] x 128 heads: my chip run, PR 43).
SCAN_HEAD_BLOCK = 16


def scan_kernel_tiles(t: int, heads: int, head_dim: int, groups: int,
                      state: int, chunk: int) -> int | None:
    """Heads a grid step of `ssm_chunk_scan_kernel` for a launch of `t`
    positions a row, or None where the kernel has no tiling and the
    caller takes `ssm_chunk_scan`. A function of shapes alone:

      * two heads fill a 128-lane tile (head_dim 64: the kernel keeps a
        pair's states side by side as one [N, 128] tile);
      * a block lies inside one group and its rows of the per-head
        vectors are whole sublane tiles: a multiple of 8 heads dividing
        heads / groups;
      * a group's `b` and `c` are whole lane tiles (state % 128 == 0) and
        so are a chunk's positions (chunk % 128 == 0, t a multiple of it:
        a launch shorter than a chunk has no [L, L] tile to fill); `b`
        and `c` lie behind `x` in the conv's output, which the kernel
        reads in blocks of `state` lanes, so x's lanes are whole blocks."""
    if (head_dim * 2 != LANES or groups < 1 or heads % groups
            or state % LANES or heads * head_dim % state
            or chunk % LANES or t % chunk):
        return None
    per_group = heads // groups
    for hb in range(min(SCAN_HEAD_BLOCK, per_group), 7, -1):
        if hb % 8 == 0 and per_group % hb == 0:
            return hb
    return None


def _chunk_scan_kernel(rows_ref, x_ref, b_ref, c_ref, s_ref, y_ref, o_ref,
                       st_ref):
    """One grid step = one row x one head block x one chunk of L
    positions; the chunk axis is innermost and sequential.

    rows_ref [1, 4, hb, L] f32, positions on LANES: cs (the inclusive
    cumulative dt A inside the chunk) | dt | exp(cs_L - cs) dt | exp(cs);
    x_ref [1, L, hb*P] and y_ref (f32) likewise: lanes as `in_proj` and
    the conv wrote them; b_ref, c_ref [1, L, N]: the block's group;
    s_ref / o_ref [1, hb, P, N] f32: read at chunk 0, written at the last;
    st_ref [hb/2, N, 2P] f32 scratch: the states of a PAIR of heads,
    transposed and side by side, so a pair's read-out `C S^T` and update
    `B^T (to_end x)` are one [., 128]-lane matmul each and `x`, `y` move
    as whole lane tiles."""
    k, last = pl.program_id(2), pl.num_programs(2) - 1
    hb, p, n = s_ref.shape[1:]
    length = x_ref.shape[1]
    pairs = hb // 2
    f32 = jnp.float32

    @pl.when(k == 0)
    def _enter():
        def load(i, carry):
            states = s_ref[0, pl.ds(2 * i, 2)].astype(f32)  # [2, P, N]
            st_ref[i] = states.reshape(2 * p, n).T
            return carry

        jax.lax.fori_loop(0, pairs, load, 0)

    bmat, cmat = b_ref[0], c_ref[0]  # [L, N]
    dtype = x_ref.dtype
    # C B^T once a (group, chunk); B^T for the pairs' state updates
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)  # [L, L]
    b_t = bmat.astype(f32).T.astype(dtype)  # [N, L]
    c32 = cmat.astype(f32)
    causal = (jax.lax.broadcasted_iota(jnp.int32, (length, length), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (length, length), 1))
    # lanes (and, before a transpose, sublanes) of a pair's second head
    second = jax.lax.broadcasted_iota(jnp.int32, (length, 2 * p), 1) >= p
    second_t = jax.lax.broadcasted_iota(jnp.int32, (2 * p, length), 0) >= p

    def column(row):
        """[1, L] (positions on lanes) -> [L, L]: entry [l, s] = row[l]."""
        return jnp.broadcast_to(row, (length, length)).T

    def pair_columns(row0, row1):
        """Two heads' [1, L] -> [L, 2P]: a position's value of head 0 on
        the first P lanes, of head 1 on the rest."""
        return jnp.where(second_t, jnp.broadcast_to(row1, (2 * p, length)),
                         jnp.broadcast_to(row0, (2 * p, length))).T

    def pair(i, carry):
        def rows(q, j):
            return rows_ref[0, q, pl.ds(2 * i + j, 1), :]  # [1, L]

        lanes = pl.ds(pl.multiple_of(i * 2 * p, 2 * p), 2 * p)
        x = x_ref[0, :, lanes]  # [L, 2P]
        ys = []
        for j in range(2):
            cs = rows(0, j)
            # y_l += sum_{s<=l} exp(cs_l - cs_s) dt_s (C_l . B_s) x_s
            seg = jnp.where(causal, column(cs) - cs, -1e30)
            weights = jnp.exp(seg) * rows(1, j) * cb
            ys.append(jnp.dot(weights.astype(dtype), x,
                              preferred_element_type=f32))
        y = jnp.where(second, ys[1], ys[0])
        entering = st_ref[i]  # [N, 2P]
        decay = pair_columns(rows(3, 0), rows(3, 1))  # exp(cs) [L, 2P]
        # across chunks: y_l += exp(cs_l) C_l . S_entering
        y_ref[0, :, lanes] = y + decay * jnp.dot(
            c32, entering, preferred_element_type=f32)
        # S <- exp(cs_L) S + (to_end x)^T B, transposed
        to_end = pair_columns(rows(2, 0), rows(2, 1))
        st_ref[i] = decay[length - 1:] * entering + jnp.dot(
            b_t, (to_end * x.astype(f32)).astype(dtype),
            preferred_element_type=f32)
        return carry

    # a loop, not an unroll: four pairs unrolled an iteration read 0.40
    # against 0.51 ms a granite layer alone and cost every prefill
    # program 0.35 s more of lowering at each start (26 programs in the
    # two Mamba cells; my chip runs, PR 43; ROADMAP rule 7)
    jax.lax.fori_loop(0, pairs, pair, 0)

    @pl.when(k == last)
    def _leave():
        def store(i, carry):
            o_ref[0, pl.ds(2 * i, 2)] = st_ref[i].T.reshape(2, p, n).astype(
                o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, pairs, store, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "heads_per_block",
                                             "interpret"))
def ssm_chunk_scan_kernel(state, dt, a, xbc, *, chunk: int,
                          heads_per_block: int, interpret: bool = False):
    """`ssm_chunk_scan` as one Pallas kernel that reads the conv's output
    as the conv wrote it: xbc [B, T, H*P + 2*G*N] = x | b | c along
    lanes (block specs pick a head block's lanes of x and its group's of
    b and c: no slice of it is copied), dt [B, T, H] f32 (0 at padding),
    a [H], state [B, H, P, N] f32. Returns (final state,
    y [B, T, H*P] f32), lanes as x's. No [chunk, chunk] product,
    per-chunk state or relaid copy of x or y reaches HBM: the per-head
    vectors (cs, dt and the two decays, T x H floats each) are all that
    XLA prepares, transposed so that a head's are a row.
    `heads_per_block`: `scan_kernel_tiles`' answer."""
    bsz, t, _ = xbc.shape
    h, (p, n) = dt.shape[2], state.shape[2:]
    inner, hb = h * p, heads_per_block
    g = (xbc.shape[2] - inner) // (2 * n)
    assert (xbc.shape[2] == inner + 2 * g * n and t % chunk == 0
            and (h // g) % hb == 0 and inner % n == 0), (
        xbc.shape, dt.shape, state.shape, chunk, hb)
    nc = t // chunk
    f32 = jnp.float32
    with jax.named_scope("ssm_scan"):
        dtc = dt.reshape(bsz, nc, chunk, h)
        cs = jnp.cumsum(dtc * a[None, None, None, :], axis=2)
        rows = jnp.stack([cs, dtc, jnp.exp(cs[:, :, -1:] - cs) * dtc,
                          jnp.exp(cs)], axis=1)  # [B, 4, nc, L, H]
        rows = jnp.swapaxes(rows.reshape(bsz, 4, t, h), 2, 3)  # [B, 4, H, T]

        def lanes_map(i, j, k):
            return (i, k, j)

        def group_map(offset):
            # in blocks of N lanes: behind x, a group's b, then its c
            return lambda i, j, k: (i, k, offset + j * hb // (h // g))

        def state_map(i, j, k):
            return (i, j, 0, 0)

        y, final = pl.pallas_call(
            _chunk_scan_kernel,
            grid=(bsz, h // hb, nc),
            in_specs=[
                pl.BlockSpec((1, 4, hb, chunk), lambda i, j, k: (i, 0, j, k)),
                pl.BlockSpec((1, chunk, hb * p), lanes_map),
                pl.BlockSpec((1, chunk, n), group_map(inner // n)),
                pl.BlockSpec((1, chunk, n), group_map(inner // n + g)),
                pl.BlockSpec((1, hb, p, n), state_map),
            ],
            out_specs=[
                pl.BlockSpec((1, chunk, hb * p), lanes_map),
                pl.BlockSpec((1, hb, p, n), state_map),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bsz, t, inner), f32),
                jax.ShapeDtypeStruct(state.shape, state.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((hb // 2, n, 2 * p), f32)],
            interpret=interpret,
            name="ssm_chunk_scan",
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=64 * 1024 * 1024),
        )(rows, xbc, xbc, xbc, state)
    return final, y


# ---------------------------------------------------------------------------
# Mamba-1: a decay a channel AND a state column
# ---------------------------------------------------------------------------
#
#     s_t = exp(dt_t (x) A) * s_{t-1} + (dt_t * u_t) (x) B_t    s: [N, D]
#     y_t = s_t^T C_t                               (D * u_t is the caller's)
#
# A is [N, D]: the decay of s[n, d] differs by column n, so Mamba-2's
# chunked matmul form (one decay a head: a chunk's [L, L] product is
# shared by a head's lanes) does not apply; its segment matrix would be
# [L, L, D, N]. The state is kept [N, D], channels along lanes: N = 16
# rows of 5,120 lanes are whole (8, 128) tiles, where [D, N] pads 16
# lanes to 128 and holds eight times the bytes.


def selective_state_update(state, dt, a, u, b, c, active):
    """One token a row. state [S, N, D] (all slots; row i is slot i), dt
    [S, D] f32 (after softplus), a [N, D] f32 (negative), u [S, D], b, c
    [S, N], active [S] bool. Returns (new state, y [S, D] f32); inactive
    rows keep their state and read y = 0. One fusion over the donated
    state: 2 x 4 N D bytes a live row."""
    with jax.named_scope("selective_update"):
        f32 = jnp.float32
        decay = jnp.exp(dt[:, None, :] * a[None])
        dtu = (dt * u.astype(f32))[:, None, :]
        new = decay * state.astype(f32) + dtu * b.astype(f32)[:, :, None]
        new = new.astype(state.dtype)  # read out of the state AS STORED
        y = jnp.sum(new.astype(f32) * c.astype(f32)[:, :, None], axis=1)
        return (jnp.where(active[:, None, None], new, state),
                jnp.where(active[:, None], y, 0.0))


# Positions a turn of `selective_scan`'s loop: the recurrence is
# sequential in time, and a turn's work ([rows, N, D] floats) is small
# beside what a turn of a loop costs, so a turn does several.
SELECTIVE_SCAN_UNROLL = 8


def selective_scan(state, dt, a, u, b, c):
    """The recurrence over T positions a row, in time order. state [B, N,
    D] (the rows' state going in, float32), dt [B, T, D] f32 (0 at
    padding: such a position leaves the state as it was), a [N, D] f32,
    u [B, T, D], b, c [B, T, N]. Returns (state coming out, y [B, T, D]
    f32). Nothing over (T, N, D) exists: the carry is [B, N, D] and a
    turn of the loop reads a position's dt, u, B, C and writes its y."""
    f32 = jnp.float32
    with jax.named_scope("selective_scan"):
        def step(s, xs):
            dt_t, u_t, b_t, c_t = xs  # [B, D], [B, D], [B, N], [B, N]
            s = (jnp.exp(dt_t[:, None, :] * a[None]) * s
                 + (dt_t * u_t.astype(f32))[:, None, :]
                 * b_t.astype(f32)[:, :, None])
            return s, jnp.sum(s * c_t.astype(f32)[:, :, None], axis=1)

        final, y = jax.lax.scan(
            step, state.astype(f32),
            tuple(jnp.swapaxes(x, 0, 1) for x in (dt, u, b, c)),
            unroll=min(SELECTIVE_SCAN_UNROLL, dt.shape[1]))
    return final.astype(state.dtype), jnp.swapaxes(y, 0, 1)


# ---------------------------------------------------------------------------
# the causal depthwise convolution in front of the scan
# ---------------------------------------------------------------------------


def causal_conv(carry, x, weight, n_valid):
    """Depthwise causal convolution of width K over T positions with a
    carry of the K-1 inputs before them. carry [B, K-1, C], x [B, T, C],
    weight [K, C] (tap K-1 multiplies the current position), n_valid [B]:
    how many leading positions of each row are real. Returns (the taps'
    sum [B, T, C] in float32: bias, activation and rounding are the
    caller's, Mamba-2's silu(conv + b) and the gated short convolution's
    bare sum alike; new carry): the last K-1 REAL inputs of each row, so
    padding never enters it (n_valid = 0 keeps the carry)."""
    k = weight.shape[0]
    prev = carry.astype(x.dtype)
    seq = jnp.concatenate([prev, x], axis=1)
    t = x.shape[1]
    out = sum(seq[:, i:i + t].astype(jnp.float32)
              * weight[i].astype(jnp.float32) for i in range(k))
    # rows n_valid .. n_valid + K-2 of `seq`, taken from its two parts:
    # gathered from `seq` itself, XLA writes all [K-1 + T, C] of it first
    idx = n_valid[:, None] + jnp.arange(k - 1)[None, :]  # [B, K-1]
    new_carry = jnp.where(
        (idx >= k - 1)[:, :, None],
        jnp.take_along_axis(
            x, jnp.clip(idx - (k - 1), 0, t - 1)[:, :, None], axis=1),
        jnp.take_along_axis(prev, jnp.minimum(idx, k - 2)[:, :, None],
                            axis=1))
    return out, new_carry.astype(carry.dtype)
