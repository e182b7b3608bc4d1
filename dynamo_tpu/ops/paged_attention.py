"""Pallas paged-attention kernels for TPU.

The decode hot loop of the engine: every step, each active sequence's single
query token attends over its paged KV cache via a block table. The reference
stack gets this from its engines' CUDA kernels (vLLM PagedAttention); here it
is a TPU-first Pallas kernel:

  * grid = (batch, kv_heads, page_chunks); the page dimension of the KV
    pools is blocked by the page size and indexed THROUGH the block table
    using scalar prefetch (`PrefetchScalarGridSpec`), so the kernel only
    ever streams the pages a sequence actually owns — HBM -> VMEM DMA per
    grid step, overlapped by the Pallas pipeline.
  * online-softmax (flash) accumulation in fp32 VMEM scratch across page
    chunks; output written on the last chunk.
  * GQA: q-heads grouped per kv-head; the group dim rides the MXU sublanes.

Prefill launches (T > 1) have a blocked kernel of their own over the same
pool, `paged_prefill_attention_pool`: (row, query block, key chunk) grid,
causal and padding blocks skipped, no [T, S] score tensor anywhere.

On CPU (tests, dev boxes) the same kernels run in interpret mode; the
pure-XLA fallback (`models.transformer.paged_attention_xla`) remains the
reference oracle.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _decode_kernel(
    # scalar prefetch
    block_tables_ref,  # [B, max_pages] int32 (SMEM)
    kv_lens_ref,  # [B] int32 (SMEM)
    # inputs (blocked)
    q_ref,  # [1, 1, group, head_dim]  (this b, this kv head)
    k_ref,  # [1, 1, page_size, head_dim] (the page this grid step covers)
    v_ref,  # [1, 1, page_size, head_dim]
    # output
    o_ref,  # [1, 1, group, head_dim]
    # scratch
    m_ref,  # [group, 128] fp32 running max (broadcast over lanes)
    l_ref,  # [group, 128] fp32 running denom
    acc_ref,  # [group, head_dim] fp32 accumulator
):
    b = pl.program_id(0)
    p = pl.program_id(2)
    n_pages = pl.num_programs(2)
    page_size = k_ref.shape[2]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kv_lens_ref[b]
    start = p * page_size

    @pl.when(start < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [group, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [ps, hd]
        v = v_ref[0, 0].astype(jnp.float32)  # [ps, hd]
        scale = 1.0 / math.sqrt(q.shape[-1])
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [group, ps]
        token_pos = start + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1
        )
        scores = jnp.where(token_pos < kv_len, scores, -jnp.inf)

        m_prev = m_ref[:, 0:1]  # [group, 1]
        l_prev = l_ref[:, 0:1]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)  # [group, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        # probs relative to the new max; correction for the old accumulator
        probs = jnp.exp(scores - m_new)  # [group, ps]
        alpha = jnp.exp(m_prev - m_new)  # [group, 1]
        l_new = l_prev * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            probs, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [group, hd]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p == n_pages - 1)
    def _finish():
        l = l_ref[:, 0:1]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(
    q: jax.Array,  # [B, qh, hd] one query token per sequence
    k_pages: jax.Array,  # [P, ps, kh, hd]
    v_pages: jax.Array,  # [P, ps, kh, hd]
    block_tables: jax.Array,  # [B, max_pages] int32
    kv_lens: jax.Array,  # [B] int32
    *,
    interpret: bool = False,
) -> jax.Array:
    """Flash decode attention over paged KV. Returns [B, qh, hd]."""
    b, qh, hd = q.shape
    _, ps, kh, _ = k_pages.shape
    group = qh // kh
    max_pages = block_tables.shape[1]

    # [P, ps, kh, hd] -> [kh, P, ps, hd]: the page-id dim must be a leading
    # blocked dim so the block table can index it, and kv-head its own grid
    # axis so each step DMAs only one head's page slice.
    kp = k_pages.transpose(2, 0, 1, 3)
    vp = v_pages.transpose(2, 0, 1, 3)
    qg = q.reshape(b, kh, group, hd)

    grid = (b, kh, max_pages)

    def q_map(bi, hi, pi, bt, kl):
        del pi, bt, kl
        return (bi, hi, 0, 0)

    def kv_map(bi, hi, pi, bt, kl):
        del kl
        return (hi, bt[bi, pi], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, group, hd), q_map),
            pl.BlockSpec((1, 1, ps, hd), kv_map),
            pl.BlockSpec((1, 1, ps, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, hd), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        _decode_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, group, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(block_tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
      qg, kp, vp)
    return out.reshape(b, qh, hd)


def _decode_kernel_partial(
    # scalar prefetch
    block_tables_ref,  # [B, max_pages] int32 (SMEM)
    kv_lens_ref,  # [B] int32 (SMEM) — HISTORY length (current excluded)
    # inputs (blocked)
    q_ref,  # [1, 1, group, head_dim]
    k_ref,  # [1, 1, page_size, head_dim]
    v_ref,  # [1, 1, page_size, head_dim]
    # outputs: UNNORMALIZED flash partials, combined with the in-register
    # current token outside the kernel (deferred-write decode)
    o_ref,  # [1, 1, group, head_dim] fp32 accumulator sum(exp(s-m))*v
    m_ref_out,  # [1, 1, group, 128] fp32 running max
    l_ref_out,  # [1, 1, group, 128] fp32 denom
    # scratch
    m_ref,
    l_ref,
    acc_ref,
):
    b = pl.program_id(0)
    p = pl.program_id(2)
    n_pages = pl.num_programs(2)
    page_size = k_ref.shape[2]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kv_lens_ref[b]
    start = p * page_size

    @pl.when(start < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        scale = 1.0 / math.sqrt(q.shape[-1])
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        token_pos = start + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1
        )
        scores = jnp.where(token_pos < kv_len, scores, -jnp.inf)
        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        probs = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            probs, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p == n_pages - 1)
    def _finish():
        o_ref[0, 0] = acc_ref[...]
        m_ref_out[0, 0] = m_ref[...]
        l_ref_out[0, 0] = l_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_partial(
    q: jax.Array,  # [B, qh, hd]
    k_pages: jax.Array,  # [P, ps, kh, hd]
    v_pages: jax.Array,  # [P, ps, kh, hd]
    block_tables: jax.Array,  # [B, max_pages] int32
    kv_lens_hist: jax.Array,  # [B] int32 HISTORY length (current excluded)
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partials over the paged HISTORY: returns (acc, m, l) with
    acc = sum(exp(s - m)) * v unnormalized, so the caller can fold in the
    current token's in-register K/V (deferred cache writes keep the
    (TPU-slow) scatter out of the per-layer loop — forward_decode)."""
    b, qh, hd = q.shape
    _, ps, kh, _ = k_pages.shape
    group = qh // kh
    max_pages = block_tables.shape[1]
    kp = k_pages.transpose(2, 0, 1, 3)
    vp = v_pages.transpose(2, 0, 1, 3)
    qg = q.reshape(b, kh, group, hd)
    grid = (b, kh, max_pages)

    def q_map(bi, hi, pi, bt, kl):
        del pi, bt, kl
        return (bi, hi, 0, 0)

    def kv_map(bi, hi, pi, bt, kl):
        del kl
        return (hi, bt[bi, pi], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, group, hd), q_map),
            pl.BlockSpec((1, 1, ps, hd), kv_map),
            pl.BlockSpec((1, 1, ps, hd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, group, hd), q_map),
            pl.BlockSpec((1, 1, group, 128), q_map),
            pl.BlockSpec((1, 1, group, 128), q_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, hd), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        _decode_kernel_partial,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kh, group, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, kh, group, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, kh, group, 128), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(block_tables.astype(jnp.int32), kv_lens_hist.astype(jnp.int32),
      qg, kp, vp)
    return acc, m[..., 0], l[..., 0]


# The pool kernel walks a row's pages in chunks of at most
# _POOL_CHUNK_TOKENS, _POOL_SLOTS chunks in VMEM: one is scored while the
# next one's pages land. A chunk's pages are copied in blocks, each block
# on its own semaphore and awaited once, so a ragged end copies half a
# block too many on average; a chunk's live blocks are scored in ONE
# straight line, a flash update a block. A block is the tokens whose
# score tile [kh*g, tok*kh] holds _SCORE_ELEMS float32 (lfm2's and
# mellum's 32 rows x 4 kv heads: 256 tokens; mistral's 32 x 8: 128),
# between _POOL_BLOCK_TOKENS' fewest and most (the spec fold's 160 rows
# x 8: 64), and a chunk holds at most _POOL_CHUNK_BLOCKS of them (a path
# a count of live blocks is compiled) and _POOL_SLOT_BYTES. The copies
# are started _POOL_START_PAGES a turn of a loop.
# Timed alone at the cells' shapes (PERF.md section 6, PR 53; ms a layer
# a step, the grid of (row, chunk) it replaces beside it): lfm2's 256
# rows at tables of 192 | 64 | 8 pages 0.89 | 0.66 | 0.24 (1.96 | 1.60 |
# 1.06), blocks of 128 level (0.89) and of 512 slower (0.96, with chunks
# of 2,048 0.95), a third slot 0.85, 16 pages a turn 0.885, a block
# scored one block before its update and not all of a chunk's first
# 0.88; mistral's 32 rows of int8 at 64 pages 0.154 at blocks of 128,
# 0.167 at 256, 0.165 at 512 (0.188); the hybrid's 128 rows 0.20 (0.57);
# mellum's window layers 0.23 (0.36) and full layers at 512 pages 0.92
# (1.23); phi4's 640-lane rows at 560 pages (not merged: two blocks of
# 224 a chunk) 2.74 (2.79), four blocks of 160 and slots of 5 MiB level.
_POOL_CHUNK_TOKENS = 1024
_POOL_BLOCK_TOKENS = (64, 256)
_POOL_CHUNK_BLOCKS = 4
_POOL_START_PAGES = 8
_POOL_SLOTS = 2
_POOL_SLOT_BYTES = 4 * 1024 * 1024
_SCORE_ELEMS = 32 * 1024


def _largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of `n` that is <= `cap` (>= 1)."""
    d = max(1, min(n, cap))
    while n % d:
        d -= 1
    return d


def _div(x, d: int):
    """x // d for a static positive d; a shift where d is a power of two
    (every geometry that reaches Mosaic), so no vector division is
    lowered."""
    return x >> (d.bit_length() - 1) if d & (d - 1) == 0 else x // d


def _mod(x, d: int):
    return x & (d - 1) if d & (d - 1) == 0 else x % d


def _pool_tiles(max_pages: int, page_bytes: int, ps: int, rows: int,
                kh: int, pages_per_chunk: int | None,
                chunk_blocks: int) -> tuple[int, int]:
    """(pages a chunk, pages a block) of the pool decode kernel for a
    table of `max_pages`: the largest chunk that divides the table, stays
    within `pages_per_chunk` (None: `_POOL_CHUNK_TOKENS`) and
    `_POOL_SLOT_BYTES`, and is at most `chunk_blocks` blocks of its
    largest divisor within a `_SCORE_ELEMS` score tile's tokens."""
    fewest, most = _POOL_BLOCK_TOKENS
    block_cap = max(1, min(most, max(fewest, _SCORE_ELEMS // (rows * kh)))
                    // ps)
    cap = min(pages_per_chunk or max(1, _POOL_CHUNK_TOKENS // ps),
              max(1, _POOL_SLOT_BYTES // page_bytes))
    for ppc in range(max(1, min(max_pages, cap)), 0, -1):
        block_pages = _largest_divisor(ppc, block_cap)
        if max_pages % ppc == 0 and ppc <= block_pages * chunk_blocks:
            return ppc, block_pages
    raise AssertionError("unreachable: one page a chunk divides any table")


def _chunk_walk(kv_lens_hist, block_tables, layer, page_size: int,
                pages_per_chunk: int, slots: int):
    """The first five scalar-prefetch operands of a decode kernel whose
    grid step is a ROW (`_pool_decode_kernel`, `_latent_decode_kernel`):
    the live chunks of all rows numbered in the order they are scored, so
    that chunk g lands in slot g mod `slots` and nothing in the kernel
    looks for the next row. (lengths [B], clipped to the table: a stale
    slot reads its table's width; tables [B * max_pages]; layer [1];
    first [B], a row's first chunk in that order; row_of [B * chunks a
    table + slots], the g-th live chunk's row and B past the last one,
    far enough for the deepest prefetch to read.)"""
    b, max_pages = block_tables.shape
    chunk = pages_per_chunk * page_size
    lengths = jnp.clip(kv_lens_hist.astype(jnp.int32), 0,
                       max_pages * page_size)
    per_row = (lengths + chunk - 1) // chunk
    ends = jnp.cumsum(per_row)
    row_of = jnp.searchsorted(
        ends, jnp.arange(b * (max_pages // pages_per_chunk) + slots,
                         dtype=jnp.int32), side="right",
        method="compare_all").astype(jnp.int32)
    return (lengths, block_tables.reshape(-1).astype(jnp.int32),
            jnp.asarray(layer, jnp.int32).reshape(1), ends - per_row, row_of)


class _PrefillWalk:
    """The liveness rule of a prefill kernel's (row, query block, key
    chunk) grid on its scalar-prefetched `starts` and `lengths`, and the
    walk to the next live step in grid order: what both prefill kernels
    (`_pool_prefill_kernel`, `_latent_prefill_kernel`) fetch and score,
    and what `count_prefill_blocks` counts on the host. `window` > 0: a
    window layer's lower edge (`_pool_prefill_kernel`)."""

    def __init__(self, starts_ref, lengths_ref, *, block_q: int, bk: int,
                 batch_size: int, window: int = 0):
        self.starts_ref, self.lengths_ref = starts_ref, lengths_ref
        self.block_q, self.bk = block_q, bk
        self.batch_size, self.window = batch_size, window

    def block_live(self, bi, qi):
        return qi * self.block_q < self.lengths_ref[bi] - self.starts_ref[bi]

    def key_limit(self, bi, qi):  # keys the block's last query sees
        return jnp.minimum(self.lengths_ref[bi],
                           self.starts_ref[bi] + (qi + 1) * self.block_q)

    def first_chunk(self, bi, qi):  # the block's lowest live chunk
        if not self.window:
            return jnp.int32(0)
        edge = self.starts_ref[jnp.minimum(bi, self.batch_size - 1)] + (
            qi * self.block_q - (self.window - 1))
        return _div(jnp.maximum(edge, 0), self.bk)

    def next_step(self, b, i, c, n_q, n_chunks):
        """The next live (row, query block, chunk) after grid step
        (b, i, c); row == batch_size when nothing is left."""
        batch_size = self.batch_size

        def next_row():
            nb = jax.lax.fori_loop(
                0, batch_size,
                lambda _, cur: jnp.where(
                    jnp.logical_and(
                        cur < batch_size,
                        jnp.logical_not(self.block_live(
                            jnp.clip(cur, 0, batch_size - 1), 0))),
                    cur + 1, cur),
                b + 1)
            return nb, jnp.int32(0), self.first_chunk(nb, 0)

        def next_block():
            more = jnp.logical_and(i + 1 < n_q, self.block_live(b, i + 1))
            return jax.lax.cond(
                more, lambda: (b, i + 1, self.first_chunk(b, i + 1)),
                next_row)

        more = jnp.logical_and(c + 1 < n_chunks,
                               (c + 1) * self.bk < self.key_limit(b, i))
        return jax.lax.cond(more, lambda: (b, i, c + 1), next_block)


def _token_scale_row(sc, kh: int):
    """[n_tok, lanes] lane-broadcast per-token scales (tokens on sublanes,
    as the pool stores them) -> f32 [1, n_tok * kh] with entry t*kh + h =
    scale[t]: the column order of a score tile over the flattened
    [n_tok * kh, hd] chunk. One selector matmul instead of a transpose:
    the tile is masked to its block diagonal (lane l keeps token l // kh
    of each group of `per` tokens) and the groups are summed by a 0/1
    matrix; one nonzero term per output, so the result is exact."""
    n_tok, lanes = sc.shape
    per = _largest_divisor(n_tok, max(1, lanes // kh))  # tokens a lane tile
    width = per * kh
    groups = n_tok // per
    tok = jax.lax.broadcasted_iota(jnp.int32, (n_tok, width), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_tok, width), 1)
    diag = jnp.where(_div(lane, kh) == _mod(tok, per), sc[:, :width], 0)
    pick = (_div(jax.lax.broadcasted_iota(jnp.int32, (groups, n_tok), 1),
                 per)
            == jax.lax.broadcasted_iota(jnp.int32, (groups, n_tok), 0))
    rows = jnp.dot(pick.astype(sc.dtype), diag,
                   preferred_element_type=jnp.float32)  # [groups, width]
    return jnp.concatenate([rows[j:j + 1] for j in range(groups)], axis=1)


def _pool_decode_kernel(
    # scalar prefetch (`_chunk_walk`)
    lengths_ref,  # [B] int32 HISTORY lengths (current token excluded)
    tables_ref,  # [B * max_pages] int32 flattened block tables
    layer_ref,  # [1] int32
    first_ref,  # [B] int32: a row's first chunk in the order of the walk
    row_ref,  # [G] int32: the row of the walk's g-th live chunk, else B
    # `windowed`: one more scalar prefetch, then inputs + outputs +
    # scratch, `quantized` adding two — unpacked below (Pallas passes
    # refs positionally)
    #   starts_ref  [B] int32: the first history token a row still sees
    #   q_ref       [1, kh*g, hd] (block for this b; rows head-major)
    #   pool_ref    FULL [L, 2, P, ps*kh, hd] in HBM (memory_space=ANY),
    #               or [L, 2, P, ps, kh, hd] where that is no bitcast
    #   scale_ref   FULL bf16 [L, 2, P, ps, LANES] in HBM (ANY)
    #   acc_ref     [1, kh*g, hd] f32 unnormalized accumulator
    #   m_ref, l_ref [1, kh*g, 128] f32
    #   kv_buf      [slots, 2, C*ps*kh, hd] (slot, K|V) page chunks, or
    #               [slots, 2, C*ps, kh, hd]
    #   sc_buf      [slots, 2, C*ps, LANES] lane-broadcast scales
    #   sems        DMA semaphores (slots, blocks a chunk)
    *rest,
    page_size: int,
    kv_heads: int,
    pages_per_chunk: int,
    block_pages: int,
    max_pages: int,
    batch_size: int,
    quantized: bool = False,
    windowed: bool = False,
    sm_scale: float | None = None,
):
    """Flash decode over the paged HISTORY reading the WHOLE pool ref.

    Why this shape:
      * the pool stays in HBM and the kernel DMAs only owned pages of
        rows that have a history: an XLA-level `kv_cache[layer]` slice
        would materialize a copy per layer per step (a custom call cannot
        fuse the slice), and a row whose length is 0 (the caller masks
        every inactive slot to 0) costs a grid step that does nothing;
      * one DMA moves a page's K and V for ALL kv heads (the pool's
        page-major layout);
      * all heads in one MXU pass, no per-head gather: a block
        [tok, kh, hd] is read as [tok*kh, hd] (a token's kh rows are
        consecutive) and scored against all kh*g query rows at once,
        S = Q K^T [kh*g, tok*kh]. Entries whose row and column belong to
        different kv heads are masked to -inf together with the length
        mask, so softmax and P V give each head exactly its own result.
        The kh-fold extra MXU work is free here (the MXU idles on 4-row
        matmuls); what it buys is 128-row weight tiles and no relayout;
      * matmul operands in the query's dtype (bf16 on the chip), f32
        accumulation; softmax statistics, accumulator and the
        unnormalized (acc, m, l) partials in f32. The probabilities are
        rounded once, to the operand dtype, for P V.

    The walk (the latent kernel's, `_latent_decode_kernel`):
      * a grid step is a ROW, and its live chunks are a loop inside. The
        caller numbers the live chunks of all rows in the order they are
        scored (`_chunk_walk`); chunk g lands in slot g mod `slots`, and
        while it is scored chunk g + slots - 1 is started, whichever
        row's it is. No step and no turn is spent on a chunk without
        history, and nothing here looks for the next row;
      * a chunk's copies are started in a loop, `_POOL_START_PAGES` a
        turn, each on the semaphore of its BLOCK of `block_pages` pages;
        a block is awaited once, by a descriptor of the block's size, so
        block 0 is scored while the later ones are landing. A block past
        the row's history is neither started nor awaited;
      * a chunk's live blocks are scored in one straight line (a path a
        count of live blocks), a flash update a block, so that Mosaic
        runs one block's Q K^T under another's softmax and no update
        waits on a `pl.when`;
      * a ragged last block is copied whole, so that the wait's size is
        static. The last update of a line masks its scores, and it
        zeroes the values behind the history (an int8 pool's: their
        scales), which may be pages nobody has written (0 x NaN is NaN
        in P V).

    The call is compiled without Mosaic's bounds checks on its copies
    (two a descriptor): the caller clips lengths to the table, and a
    table holds page numbers of the pool.

    `quantized` (static): pages stream as int8 (half the bytes of bf16)
    plus per-token head-shared bf16 scale rows ([ps, LANES],
    lane-broadcast so a page's DMA slice is tiling-aligned), a page's on
    the semaphore of its block, whose wait is for both. The codes
    convert to the operand dtype exactly; the K scale multiplies the f32
    scores and the V scale the f32 probabilities (`_token_scale_row`),
    never the [tok*kh, hd] tiles.

    `windowed` (static): a window layer's page group. The table's column
    0 is the first block the row still holds and the lengths count from
    that block's first token (engine/pages.py frees behind the window),
    so the pages streamed ARE the live window; `starts_ref` masks what
    is left of the oldest page below the window's lower edge. The copies
    and their order are the unwindowed kernel's: a start past the first
    block would leave that block without a live token, and is the
    caller's to rule out (it holds one page of slack at most).
    """
    starts_ref = scale_ref = sc_buf = None
    if windowed:
        starts_ref, *rest = rest
    q_ref, pool_ref, *rest = rest
    if quantized:
        scale_ref, *rest = rest
    acc_ref, m_ref, l_ref, kv_buf, *rest = rest
    if quantized:
        sc_buf, *rest = rest
    (sems,) = rest
    b = pl.program_id(0)
    slots, hd = kv_buf.shape[0], kv_buf.shape[-1]
    ps, kh = page_size, kv_heads
    # buffer rows a token: its kh rows where the caller merged a page's
    # (token, kv head) dimensions (`_pool_flash_partials`), else one
    unit = kh if len(kv_buf.shape) == 4 else 1
    rows = q_ref.shape[1]
    g = rows // kh
    bk = pages_per_chunk * ps
    block_tok = block_pages * ps
    n_blocks = pages_per_chunk // block_pages
    cols = block_tok * kh
    if sm_scale is None:  # a model that states no scale of its own
        sm_scale = 1.0 / math.sqrt(hd)
    length = lengths_ref[b]  # <= max_pages * ps
    per_turn = _largest_divisor(block_pages, _POOL_START_PAGES)
    pool_layer = pool_ref.at[layer_ref[0]]
    scale_layer = scale_ref.at[layer_ref[0]] if quantized else None

    def blocks_of(left):  # a chunk's live blocks, `left` tokens to go
        return jnp.minimum(n_blocks, _div(left + block_tok - 1, block_tok))

    def start_chunk(bi, ci, slot):
        base = bi * max_pages + ci * pages_per_chunk
        blocks = blocks_of(lengths_ref[bi] - ci * bk)

        def turn(t, _):
            first = t * per_turn
            into = kv_buf.at[slot, :, pl.ds(first * ps * unit,
                                            per_turn * ps * unit)]
            sem = sems.at[slot, _div(first, block_pages)]
            if quantized:
                sc_into = sc_buf.at[slot, :, pl.ds(first * ps,
                                                   per_turn * ps)]
            for j in range(per_turn):
                page = tables_ref[base + first + j]
                pltpu.make_async_copy(
                    pool_layer.at[:, page],
                    into.at[:, pl.ds(j * ps * unit, ps * unit)],
                    sem).start()
                if quantized:
                    pltpu.make_async_copy(
                        scale_layer.at[:, page],
                        sc_into.at[:, pl.ds(j * ps, ps)], sem).start()

        jax.lax.fori_loop(0, blocks * (block_pages // per_turn), turn, None)

    def start_nth(n):
        """Start the copies of the walk's n-th live chunk, if there is
        one, into the slot that is its turn."""
        bi = row_ref[n]

        @pl.when(bi < batch_size)
        def _():
            start_chunk(bi, n - first_ref[bi], n % slots)

    @pl.when(b == 0)
    def _first():
        for n in range(slots - 1):
            start_nth(n)

    m_view, l_view, o_view = m_ref.at[0], l_ref.at[0], acc_ref.at[0]
    m_view[...] = jnp.full_like(m_view, -jnp.inf)
    l_view[...] = jnp.zeros_like(l_view)
    o_view[...] = jnp.zeros_like(o_view)
    # Column c of a score tile is (token c // kh, kv head c % kh); row r
    # belongs to kv head r // g. Static but for the length.
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    row_head = sum((row >= h * g).astype(jnp.int32)
                   for h in range(1, kh))  # r // g without a division
    same_head = _mod(col, kh) == row_head  # [kh*g, cols]
    col_tok = _div(col, kh)  # [1, cols]

    def chunk(ci, _):
        n = first_ref[b] + ci
        start_nth(n + slots - 1)
        slot = n % slots
        left = length - ci * bk  # > 0

        def rows_of(u: int):  # block u's rows of a slot's K or V
            return pl.ds(u * block_tok * unit, block_tok * unit)

        def landed(u: int):
            """Block u's pages (and scale rows), once they are in."""
            at = kv_buf.at[slot, :, rows_of(u)]
            pltpu.make_async_copy(at, at, sems.at[slot, u]).wait()
            if quantized:
                at = sc_buf.at[slot, :, pl.ds(u * block_tok, block_tok)]
                pltpu.make_async_copy(at, at, sems.at[slot, u]).wait()

        def tile(which: int, u: int):
            # block u's K or V rows [tok*kh, hd] in the operand dtype
            # (int8 codes convert exactly). Where the buffer keeps kv
            # heads apart, merging them here relays every tile.
            return kv_buf[slot, which, rows_of(u)].reshape(cols, hd).astype(
                q_ref.dtype)

        def scale_row(which: int, u: int, live):
            # zeros behind the history: the selector matmul would spread
            # one token's NaN over the row
            sc = sc_buf[slot, which, pl.ds(u * block_tok, block_tok)]
            if live is not None:
                tok = jax.lax.broadcasted_iota(jnp.int32, (block_tok, 1), 0)
                sc = jnp.where(tok < live, sc, jnp.zeros_like(sc))
            return _token_scale_row(sc, kh)

        def score(q, u: int, live):
            """Block u's masked scores [kh*g, cols], once its pages are
            in; `live`: the tokens of a ragged block inside the
            history."""
            landed(u)
            s = jax.lax.dot_general(
                q, tile(0, u), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if quantized:
                s = s * (scale_row(0, u, live) * sm_scale)
            else:
                s = s * sm_scale
            seen = same_head
            if live is not None:
                seen = jnp.logical_and(seen, col_tok < live)
            if windowed:
                seen = jnp.logical_and(
                    seen,
                    col_tok >= starts_ref[b] - (ci * bk + u * block_tok))
            return jnp.where(seen, s, -jnp.inf)

        def update(q, s, u: int, live, m_prev, l_prev, o):
            """Block u folded into the running softmax (m, l [kh*g, 1],
            o [kh*g, hd])."""
            # finite: the block's first token is live for every head
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            v = tile(1, u)
            if quantized:
                p = p * scale_row(1, u, live)
            elif live is not None:
                tok = _div(jax.lax.broadcasted_iota(jnp.int32, (cols, 1), 0),
                           kh)
                v = jnp.where(tok < live, v, jnp.zeros_like(v))
            pv = jax.lax.dot_general(
                p.astype(q.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [kh*g, hd]
            return m_new, l_new, o * alpha + pv

        def path(k: int):
            """A chunk of k live blocks, the last one maybe ragged, in
            one straight line: every block's scores, then the updates
            (Mosaic's scheduler orders a straight line itself)."""
            q = q_ref[0]  # [kh*g, hd], the matmul operand dtype
            # tokens of a block inside the history: the last one's
            lives = [None] * (k - 1) + [left - (k - 1) * block_tok]
            scores = [score(q, u, live) for u, live in enumerate(lives)]
            state = (m_view[:, 0:1], l_view[:, 0:1], o_view[...])
            for u, (s, live) in enumerate(zip(scores, lives)):
                state = update(q, s, u, live, *state)
            m_new, l_new, o = state
            o_view[...] = o
            m_view[...] = jnp.broadcast_to(m_new, m_view.shape)
            l_view[...] = jnp.broadcast_to(l_new, l_view.shape)

        for k in range(1, n_blocks + 1):
            pl.when(blocks_of(left) == k)(functools.partial(path, k))

    jax.lax.fori_loop(0, _div(length + bk - 1, bk), chunk, None)


def _pool_flash_partials(q, kv_pool, layer, block_tables, kv_lens_hist,
                         kv_scales, starts, pages_per_chunk, interpret,
                         sm_scale=None):
    """The pallas_call both jitted entry points share; `starts` not None
    is the windowed kernel (one more scalar prefetch); `sm_scale` the
    score scale of a model that states one (None: 1/sqrt(hd))."""
    quantized = kv_scales is not None
    windowed = starts is not None
    b, qh, hd = q.shape
    ps, kh = kv_pool.shape[3], kv_pool.shape[4]
    max_pages = block_tables.shape[1]
    # A page is copied and read AS [ps * kh, hd] rows where handing the
    # pool in with the two dimensions merged is a bitcast of its tiled
    # HBM layout: rows one lane tile wide whose kh heads fill whole
    # 32-bit words (merging them on the loaded value is a relayout of
    # every K and V tile: 510 of a block's 700 bundles at lfm2's shape).
    # A wider row's lane tiles lie a token apart, and XLA would copy the
    # pool: such a kernel keeps the relayout, and two blocks a chunk
    # (three paths of it, not ten: phi4's 640-lane rows, whose chunk is
    # the 448 tokens it was).
    merged = hd == 128 and kh * kv_pool.dtype.itemsize % 4 == 0
    page_bytes = 2 * ps * kh * hd * kv_pool.dtype.itemsize
    ppc, block_pages = _pool_tiles(
        max_pages, page_bytes, ps, qh, kh, pages_per_chunk,
        _POOL_CHUNK_BLOCKS if merged else 2)

    def row_map(bi, *refs):
        del refs
        return (bi, 0, 0)

    in_specs = [
        pl.BlockSpec((1, qh, hd), row_map),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    if merged:
        kv_pool = kv_pool.reshape(*kv_pool.shape[:3], ps * kh, hd)
        scratch = [pltpu.VMEM((_POOL_SLOTS, 2, ppc * ps * kh, hd),
                              kv_pool.dtype)]
    else:
        scratch = [pltpu.VMEM((_POOL_SLOTS, 2, ppc * ps, kh, hd),
                              kv_pool.dtype)]
    operands = [q, kv_pool]
    if quantized:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        scratch.append(pltpu.VMEM(
            (_POOL_SLOTS, 2, ppc * ps, kv_scales.shape[-1]),
            kv_scales.dtype))
        operands.append(kv_scales)
    scratch.append(pltpu.SemaphoreType.DMA((_POOL_SLOTS, ppc // block_pages)))
    prefetch = list(_chunk_walk(kv_lens_hist, block_tables, layer, ps, ppc,
                                _POOL_SLOTS))
    if windowed:
        prefetch.append(starts.astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, qh, hd), row_map),
            pl.BlockSpec((1, qh, 128), row_map),
            pl.BlockSpec((1, qh, 128), row_map),
        ],
        scratch_shapes=scratch,
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_pool_decode_kernel, page_size=ps, kv_heads=kh,
                          pages_per_chunk=ppc,
                          block_pages=block_pages, max_pages=max_pages,
                          batch_size=b, quantized=quantized,
                          windowed=windowed, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, qh, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, qh, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, qh, 128), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            disable_bounds_checks=True),
        **({"name": "paged_decode_attention_window"} if windowed else {}),
    )(*prefetch, *operands)
    group = qh // kh
    return (acc.reshape(b, kh, group, hd),
            m[..., 0].reshape(b, kh, group), l[..., 0].reshape(b, kh, group))


@functools.partial(jax.jit,
                   static_argnames=("pages_per_chunk", "interpret",
                                    "sm_scale"),
                   # Read-only on the WHOLE paged pool by design: the
                   # decode step that calls this still owns (and
                   # donates) the cache through its own jit boundary.
                   donate_argnums=())
def paged_decode_attention_pool(
    q: jax.Array,  # [B, qh, hd]
    kv_pool: jax.Array,  # [L, 2, P, ps, kh, hd] — the WHOLE cache
    layer: jax.Array,  # scalar int32
    block_tables: jax.Array,  # [B, max_pages] int32
    kv_lens_hist: jax.Array,  # [B] int32 history length (current excluded)
    kv_scales=None,  # bf16 [L, 2, P, ps, LANES] for an int8 pool
    *,
    pages_per_chunk: int | None = None,
    interpret: bool = False,
    sm_scale: float | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked-DMA flash partials over the paged history; see
    _pool_decode_kernel for what it streams and how it scores. Returns
    (acc, m, l) unnormalized for the deferred current-token combine. With
    `kv_scales` the pool is int8 (the q8 path). A row with history 0 is
    skipped: callers pass 0 for every slot that is not active; a length
    past the table's width (a stale slot) reads the table's width.

    `pages_per_chunk` is a cap on the DMA chunk; left None it follows
    from the static table width and the geometry (`_pool_tiles`)."""
    return _pool_flash_partials(q, kv_pool, layer, block_tables,
                                kv_lens_hist, kv_scales, None,
                                pages_per_chunk, interpret, sm_scale)


@functools.partial(jax.jit,
                   static_argnames=("pages_per_chunk", "interpret",
                                    "sm_scale"),
                   donate_argnums=())
def paged_decode_attention_window(
    q: jax.Array,  # [B, qh, hd]
    kv_pool: jax.Array,  # the window group's WHOLE cache
    layer: jax.Array,
    block_tables: jax.Array,  # [B, window pages] the group's own table
    kv_lens_hist: jax.Array,  # [B] history length in the table's frame
    starts: jax.Array,  # [B] first history token still seen, same frame
    *,
    pages_per_chunk: int | None = None,
    interpret: bool = False,
    sm_scale: float | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """`paged_decode_attention_pool` over a window layer's page group,
    under a name of its own so that a device trace tells the window
    layers' events from the full layers'. Streams the table's pages up
    to the history length, which the allocator keeps to the live window,
    and masks the tokens before `starts`."""
    return _pool_flash_partials(q, kv_pool, layer, block_tables,
                                kv_lens_hist, None, starts,
                                pages_per_chunk, interpret, sm_scale)


def paged_attention_decode_fused(
    q: jax.Array,  # [B, 1, qh, hd]
    kv_cache: jax.Array,  # [L, 2, P, ps, kh, hd]
    layer: int,
    block_tables: jax.Array,  # [B, max_pages]
    kv_lens: jax.Array,  # [B] INCLUDING the current token
    k_cur: jax.Array,  # [B, 1, kh, hd] current token's K (not yet cached)
    v_cur: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Deferred-write decode attention: Pallas flash partials over the
    paged history (only owned pages are streamed — the XLA gather reads
    the table extent through scatter-shaped HLO an order of magnitude
    slower on TPU), combined with the in-register current token here.
    Drop-in for `transformer.paged_attention_decode_xla`."""
    acc, m, l = paged_decode_attention_partial(
        q[:, 0], kv_cache[layer, 0], kv_cache[layer, 1],
        block_tables, kv_lens - 1, interpret=interpret,
    )  # acc [B, kh, g, hd] f32; m, l [B, kh, g]
    return _combine_current(q, acc, m, l, k_cur, v_cur)


def _q8_needs_xla(values, scales, interpret: bool) -> bool:
    """An int8 pool whose head_dim is not the scale lane width (128): the
    one int8 geometry Mosaic has compiled and the chip has checked is
    head_dim == 128; others take the XLA dequant path."""
    return (scales is not None and not interpret
            and values.shape[5] != scales.shape[-1])


def _combine_current(q, acc, m, l, k_cur, v_cur, sm_scale=None):
    """Fold the in-register current token into unnormalized flash partials
    (the deferred-write combine shared by both kernel variants)."""
    b, _, qh, hd = q.shape
    kh = k_cur.shape[2]
    group = qh // kh
    qg = q[:, 0].reshape(b, kh, group, hd)
    s_cur = jnp.einsum(
        "bkgh,bkh->bkg", qg.astype(jnp.float32),
        k_cur[:, 0].astype(jnp.float32))
    s_cur = (s_cur / math.sqrt(hd) if sm_scale is None
             else s_cur * sm_scale)
    m_new = jnp.maximum(m, s_cur)
    alpha = jnp.exp(m - m_new)  # 0 when history empty (m = -inf)
    beta = jnp.exp(s_cur - m_new)
    out = (acc * alpha[..., None]
           + beta[..., None] * v_cur[:, 0].astype(jnp.float32)[:, :, None, :])
    out = out / (l * alpha + beta)[..., None]
    return out.reshape(b, 1, qh, hd).astype(q.dtype)


def _packed_pool_partials(q, kv_pool, layer, block_tables, kv_lens_hist,
                          kh: int, pages_per_chunk, interpret, sm_scale):
    """`paged_decode_attention_pool` over a pool that packs `per` = 128 /
    head_dim kv heads into every 128-lane row ([L, 2, P, ps, kh / per,
    128]: `ModelConfig.kv_heads_per_lane_tile`; at head_dim 64 Mosaic
    refuses a page's 64-lane slice and the TPU pads such a pool to twice
    its bytes). The kernel is the one every other pool runs, handed the
    pool as it lies: to it a lane tile is ONE kv head 128 wide with per x
    group query rows. Each query row carries its values in the lanes its
    own kv head has in the tile and zeros in the others, so q . row =
    q_h . k_h exactly (the other head's lanes meet zeros) and P V comes
    back 128 wide, of which the row's own lanes are its head's context.
    Twice the MXU columns of a bandwidth-bound kernel; the bytes
    streamed are the pool's own. q [B, qh, hd] -> (acc [B, kh, group,
    hd], m, l [B, kh, group]) as the unpacked entry gives them."""
    b, qh, hd = q.shape
    per = kv_pool.shape[5] // hd
    group, tiles = qh // kh, kh // per
    own = jnp.eye(per, dtype=q.dtype)  # [head's slot, lane block]
    wide = (q.reshape(b, tiles, per, group, 1, hd)
            * own[None, None, :, None, :, None]).reshape(b, qh, per * hd)
    acc, m, l = paged_decode_attention_pool(
        wide, kv_pool, layer, block_tables, kv_lens_hist,
        pages_per_chunk=pages_per_chunk, interpret=interpret,
        sm_scale=sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd))
    acc = jnp.einsum("btsgrh,sr->btsgh",
                     acc.reshape(b, tiles, per, group, per, hd),
                     own.astype(acc.dtype))
    return (acc.reshape(b, kh, group, hd), m.reshape(b, kh, group),
            l.reshape(b, kh, group))


def paged_attention_decode_pool(
    q: jax.Array,  # [B, 1, qh, hd]
    kv_cache,  # [L, 2, P, ps, kh, hd] or int8 (values, scales) pair
    layer,
    block_tables: jax.Array,
    kv_lens: jax.Array,  # [B] INCLUDING the current token
    k_cur: jax.Array,  # [B, 1, kh, hd]
    v_cur: jax.Array,
    *,
    window: int = 0,
    pages_per_chunk: int | None = None,
    interpret: bool = False,
    sm_scale: float | None = None,
) -> jax.Array:
    """Deferred-write decode attention via the whole-pool chunked-DMA
    kernel — the production TPU path: no per-layer pool slices (no copies),
    one DMA per page for K and V of all kv heads, double-buffered against
    the flash compute, all heads scored in one bf16 MXU pass over the
    flattened chunk (`_pool_decode_kernel`). Drop-in for
    `transformer.paged_attention_decode_xla`. A row whose `kv_lens` is 0
    or 1 has no history and is skipped: `forward_decode` passes 0 for
    every slot that is not active. `pages_per_chunk` caps the DMA chunk;
    None sizes it from the table width. An int8 (values, scales) cache
    takes the q8 path: half the page bytes, the per-token scales applied
    to the scores and the probabilities. `window` > 0: a window layer's
    page group (its own table and lengths; bf16 pool only), through
    `paged_decode_attention_window`. `sm_scale`: the score scale of a
    model that states one (a bf16 pool only; None: 1/sqrt(hd), and
    nothing of it is traced). A pool whose rows pack
    several kv heads into a lane tile (head_dim 64) runs the same kernel
    through `_packed_pool_partials`; the geometry decides."""
    values, scales = (kv_cache if isinstance(kv_cache, tuple)
                      else (kv_cache, None))
    assert sm_scale is None or scales is None, "sm_scale: a bf16 pool"
    packed = values.shape[5] != q.shape[-1]  # kv heads share lane tiles
    if _q8_needs_xla(values, scales, interpret) or (
            packed and (window or scales is not None)):
        from ..models.transformer import paged_attention_decode_xla

        return paged_attention_decode_xla(q, kv_cache, layer, block_tables,
                                          kv_lens, k_cur, v_cur,
                                          window=window, sm_scale=sm_scale)
    if packed:
        acc, m, l = _packed_pool_partials(
            q[:, 0], values, layer, block_tables,
            jnp.maximum(kv_lens - 1, 0), k_cur.shape[2], pages_per_chunk,
            interpret, sm_scale)
        return _combine_current(q, acc, m, l, k_cur, v_cur, sm_scale)
    if window:
        acc, m, l = paged_decode_attention_window(
            q[:, 0], values, layer, block_tables,
            jnp.maximum(kv_lens - 1, 0), jnp.maximum(kv_lens - window, 0),
            pages_per_chunk=pages_per_chunk, interpret=interpret,
            sm_scale=sm_scale)
        return _combine_current(q, acc, m, l, k_cur, v_cur, sm_scale)
    acc, m, l = paged_decode_attention_pool(
        q[:, 0], values, layer, block_tables,
        jnp.maximum(kv_lens - 1, 0), kv_scales=scales,
        pages_per_chunk=pages_per_chunk, interpret=interpret,
        sm_scale=sm_scale,
    )
    return _combine_current(q, acc, m, l, k_cur, v_cur, sm_scale)


# The latent pool's kernel walks a row's pages in chunks of
# _LATENT_CHUNK_TOKENS, _LATENT_SLOTS chunks in VMEM (1.3 MB each at the
# published row): one is scored while the next one's pages land. A chunk's
# pages are copied in blocks of _LATENT_BLOCK_TOKENS, each block on its
# own semaphore and awaited once, so a ragged end copies half a block too
# many on average; a chunk's live blocks are scored in ONE straight line,
# _LATENT_SCORE_BLOCKS of them a flash update (a [heads, 512] float32
# score tile), so that Mosaic runs one update's Q K^T under another's
# softmax. The copies are started _LATENT_START_PAGES a turn of a loop.
# Timed alone at the pangu cell's shapes (PERF.md section 6, PR 51; ms a
# layer a step at a table of 384 pages, the grid of (row, chunk) it
# replaces 1.37): 1,024 / 256 / 2 / 8 is 0.72; blocks of 512 copied
# whole 0.79, one 512-token update a line 0.88, chunks of 512 0.88, of
# 2,048 0.87; 16 pages a turn are level and lower slower; 3 and 4 slots
# are level (0.717, 0.712).
_LATENT_CHUNK_TOKENS = 1024
_LATENT_BLOCK_TOKENS = 256
_LATENT_SCORE_BLOCKS = 2
_LATENT_START_PAGES = 8
_LATENT_SLOTS = 2


def _latent_flash_update(s, rows, rank: int, dtype, m_ref, l_ref, o_ref):
    """One block of cached rows folded into a running softmax, the step
    both latent kernels share: s [queries, tokens] float32 scores,
    masked to -inf (every query row's running max is finite once this
    block is in: the caller's to see to), rows [tokens, width] the
    block, whose first `rank` lanes are its values; the probabilities
    are rounded once, to `dtype`, for the MXU. m_ref, l_ref [queries,
    128] lane-broadcast and o_ref [queries, rank], float32 scratch."""
    m_prev = m_ref[:, 0:1]
    l_prev = l_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # [queries, rank]
    o_ref[...] = o_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _latent_decode_kernel(
    # scalar prefetch
    lengths_ref,  # [B] int32 HISTORY lengths (current token excluded)
    tables_ref,  # [B * max_pages] int32 flattened block tables
    layer_ref,  # [1] int32
    first_ref,  # [B] int32: a row's first chunk in the order of the walk
    row_ref,  # [G] int32: the row of the walk's g-th live chunk, else B
    q_ref,  # [1, heads, width]: absorbed queries [q W_uk^T | q_rope | 0]
    pool_ref,  # FULL [L, P, ps, width] in HBM (memory_space=ANY)
    acc_ref,  # [1, heads, rank] f32 unnormalized sum of p x latent
    m_ref, l_ref,  # [1, heads, 128] f32
    kv_buf,  # [slots, C, ps, width] page chunks
    sems,  # DMA semaphores (slots, blocks a chunk)
    *,
    pages_per_chunk: int,
    block_pages: int,
    max_pages: int,
    batch_size: int,
    sm_scale: float,
):
    """Flash decode over a single-stack LATENT pool (latent attention in
    its absorbed form): a cached token is ONE row of `width` lanes that
    every query head shares: `rank` latent values, the rope key behind
    them, zeros up to a lane tile. All heads score a block of rows in
    one MXU pass, S = Q K^T [heads, tokens] over all `width` lanes (the
    padding adds nothing), and the values are the same rows' first
    `rank` lanes: P K[:, :rank]. So a page is read once for all 128
    heads, where the pool kernel above reads one K and one V row a kv
    head. The pool stays in HBM and (acc, m, l) leave unnormalized for
    the current token's combine, as in `_pool_decode_kernel`; the walk
    is this kernel's own:

    - A grid step is a ROW, and its live chunks are a loop inside. The
      caller numbers the live chunks of all rows in the order they are
      scored (`first_ref`, `row_ref`); chunk g lands in slot g mod
      `slots`, and while it is scored chunk g + slots - 1 is started,
      whichever row's it is. No step and no turn is spent on a chunk
      without history, and nothing here looks for the next row.
    - A chunk's copies are started in a loop, `_LATENT_START_PAGES` a
      turn, each on the semaphore of its BLOCK; a block is awaited once,
      by a descriptor of the block's size, so block 0 is scored while
      the later ones are landing.
    - A chunk's live blocks are scored in one straight line (a path a
      count of live blocks), `_LATENT_SCORE_BLOCKS` of them a flash
      update, so that Mosaic runs one update's Q K^T under another's
      softmax and no update waits on a `pl.when`.
    - A ragged last block is copied whole, so that the wait's size is
      static. The last update of a line masks its scores, and it zeroes
      the rows behind the history, which may be pages nobody has written
      (0 x NaN is NaN in P V).

    The call is compiled without Mosaic's bounds checks on its copies
    (two a descriptor; 0.17 of 0.97 ms a layer at the cell's shapes):
    the caller clips lengths to the table, and a table holds page
    numbers of the pool."""
    b = pl.program_id(0)
    slots, _, ps, width = kv_buf.shape
    rank = acc_ref.shape[2]
    bk = pages_per_chunk * ps
    block_tok = block_pages * ps
    n_blocks = pages_per_chunk // block_pages
    length = lengths_ref[b]  # <= max_pages * ps
    per_turn = _largest_divisor(block_pages, _LATENT_START_PAGES)
    pool_layer = pool_ref.at[layer_ref[0]]

    def blocks_of(left):  # a chunk's live blocks, `left` tokens to go
        return jnp.minimum(n_blocks, _div(left + block_tok - 1, block_tok))

    def start_chunk(bi, ci, slot):
        base = bi * max_pages + ci * pages_per_chunk
        blocks = blocks_of(lengths_ref[bi] - ci * bk)

        def turn(t, _):
            first = t * per_turn
            into = kv_buf.at[slot, pl.ds(first, per_turn)]
            sem = sems.at[slot, _div(first, block_pages)]
            for j in range(per_turn):
                pltpu.make_async_copy(
                    pool_layer.at[tables_ref[base + first + j]],
                    into.at[j], sem).start()

        jax.lax.fori_loop(0, blocks * (block_pages // per_turn), turn, None)

    def start_nth(g):
        """Start the copies of the walk's g-th live chunk, if there is
        one, into the slot that is its turn."""
        bi = row_ref[g]

        @pl.when(bi < batch_size)
        def _():
            start_chunk(bi, g - first_ref[bi], g % slots)

    @pl.when(b == 0)
    def _first():
        for g in range(slots - 1):
            start_nth(g)

    m_view, l_view, o_view = m_ref.at[0], l_ref.at[0], acc_ref.at[0]
    m_view[...] = jnp.full_like(m_view, -jnp.inf)
    l_view[...] = jnp.zeros_like(l_view)
    o_view[...] = jnp.zeros_like(o_view)
    n_live = _div(length + bk - 1, bk)

    def chunk(ci, _):
        g = first_ref[b] + ci
        start_nth(g + slots - 1)
        slot = g % slots
        left = length - ci * bk  # > 0

        def landed(first: int, count: int, live):
            """Blocks first .. first + count as rows, once they are in;
            `live`: the rows from there on are zeroed."""
            for u in range(first, first + count):
                at = kv_buf.at[slot, pl.ds(u * block_pages, block_pages)]
                pltpu.make_async_copy(at, at, sems.at[slot, u]).wait()
            rows = kv_buf[slot, pl.ds(first * block_pages,
                                      count * block_pages)]
            rows = rows.reshape(count * block_tok, width)
            if live is not None:
                tok = jax.lax.broadcasted_iota(jnp.int32, (rows.shape[0], 1),
                                               0)
                rows = jnp.where(tok < live, rows, jnp.zeros_like(rows))
            return rows

        def score(q, rows, live):
            s = jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if live is not None:
                col = jax.lax.broadcasted_iota(jnp.int32, (1, rows.shape[0]),
                                               1)
                s = jnp.where(col < live, s, -jnp.inf)
            return s

        def path(k: int):
            """A chunk of k live blocks, the last one maybe ragged, in
            one straight line."""
            q = q_ref[0]  # [heads, width]
            rows, scores = [], []
            for first in range(0, k, _LATENT_SCORE_BLOCKS):
                count = min(_LATENT_SCORE_BLOCKS, k - first)
                live = (left - first * block_tok if first + count == k
                        else None)
                rows.append(landed(first, count, live))
                # finite: a unit's first token is live
                scores.append(score(q, rows[-1], live))
            for s, r in zip(scores, rows):
                _latent_flash_update(s, r, rank, q.dtype, m_view, l_view,
                                     o_view)

        for k in range(1, n_blocks + 1):
            pl.when(blocks_of(left) == k)(functools.partial(path, k))

    jax.lax.fori_loop(0, n_live, chunk, None)


@functools.partial(jax.jit,
                   static_argnames=("rank", "sm_scale", "pages_per_chunk",
                                    "interpret"),
                   donate_argnums=())  # read-only on the whole pool
def paged_decode_attention_latent(
    q: jax.Array,  # [B, heads, width] absorbed queries, padded as the rows
    kv_pool: jax.Array,  # [L, 1, P, ps, 1, width]: the WHOLE latent cache
    layer: jax.Array,  # scalar int32
    block_tables: jax.Array,  # [B, max_pages] int32
    kv_lens_hist: jax.Array,  # [B] int32 history length (current excluded)
    *,
    rank: int,  # leading lanes of a row that are its values
    sm_scale: float,
    pages_per_chunk: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partials over the paged history of a latent pool
    (`_latent_decode_kernel`): (acc [B, heads, rank], m, l [B, heads])
    float32, unnormalized. A row with history 0 is skipped; a length
    past the table's width (a stale slot) reads the table's width."""
    b, heads, width = q.shape
    n_layers, _, n_pages, ps = kv_pool.shape[:4]
    max_pages = block_tables.shape[1]
    ppc = _largest_divisor(
        max_pages, pages_per_chunk or max(1, _LATENT_CHUNK_TOKENS // ps))
    block_pages = _largest_divisor(
        ppc, max(1, _LATENT_BLOCK_TOKENS // ps))

    def row_map(bi, *refs):
        del refs
        return (bi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, heads, width), row_map),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((1, heads, rank), row_map),
                   pl.BlockSpec((1, heads, 128), row_map),
                   pl.BlockSpec((1, heads, 128), row_map)],
        scratch_shapes=[
            pltpu.VMEM((_LATENT_SLOTS, ppc, ps, width), kv_pool.dtype),
            pltpu.SemaphoreType.DMA((_LATENT_SLOTS, ppc // block_pages)),
        ],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_latent_decode_kernel, pages_per_chunk=ppc,
                          block_pages=block_pages, max_pages=max_pages,
                          batch_size=b, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, heads, rank), jnp.float32),
                   jax.ShapeDtypeStruct((b, heads, 128), jnp.float32),
                   jax.ShapeDtypeStruct((b, heads, 128), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            disable_bounds_checks=True),
        name="paged_decode_attention_latent",
    )(*_chunk_walk(kv_lens_hist, block_tables, layer, ps, ppc,
                   _LATENT_SLOTS),
      # the two unit dimensions are the dense pool's (k|v, kv heads): a
      # row-major bitcast
      q, kv_pool.reshape(n_layers, n_pages, ps, width))
    return acc, m[..., 0], l[..., 0]


def combine_current_latent(q, acc, m, l, row_cur, rank: int,
                           sm_scale: float):
    """Fold the current token's own row (not yet in the pool) into the
    history's unnormalized partials: q [B, heads, width], row_cur
    [B, width] -> the normalized context [B, heads, rank] float32."""
    row = row_cur.astype(jnp.float32)
    s_cur = jnp.einsum("bhw,bw->bh", q.astype(jnp.float32), row) * sm_scale
    m_new = jnp.maximum(m, s_cur)
    alpha = jnp.exp(m - m_new)  # 0 where the history is empty (m = -inf)
    beta = jnp.exp(s_cur - m_new)
    out = acc * alpha[..., None] + beta[..., None] * row[:, None, :rank]
    return out / (l * alpha + beta)[..., None]


def paged_attention_decode_latent(
    q: jax.Array,  # [B, heads, width]
    kv_cache: jax.Array,  # [L, 1, P, ps, 1, width]
    layer,
    block_tables: jax.Array,
    kv_lens: jax.Array,  # [B] INCLUDING the current token; 0 = inactive
    row_cur: jax.Array,  # [B, width] the current token's row
    *,
    rank: int,
    sm_scale: float,
    pages_per_chunk: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Deferred-write decode attention over a latent pool: the kernel's
    partials over the cached history, then the current token. Drop-in
    for `models/hybrid.paged_attention_decode_latent_xla`; returns the
    context in latent space [B, heads, rank] float32 (W_uv is the
    caller's)."""
    acc, m, l = paged_decode_attention_latent(
        q, kv_cache, layer, block_tables, jnp.maximum(kv_lens - 1, 0),
        rank=rank, sm_scale=sm_scale, pages_per_chunk=pages_per_chunk,
        interpret=interpret)
    return combine_current_latent(q, acc, m, l, row_cur, rank, sm_scale)


# A latent layer's prefill tiles: a grid step scores one key chunk of
# _LATENT_PREFILL_CHUNK_TOKENS cached rows against a query block of
# _LATENT_PREFILL_POSITIONS positions, for a group of
# _LATENT_PREFILL_HEADS heads in turn, whose W_uk and W_uv blocks are in
# VMEM (2 x 2 MB at the published sizes), _LATENT_PREFILL_UNROLL of them
# a turn of the loop. At 512 positions a chunk's expansion (262 kFLOP a
# key a head) is 40% of a step's operations; two heads a turn run in
# 89-94% of one's time and lower as fast, four in 84-87% and lower 1 s a
# kernel slower (PERF.md, PR 50: five kernels a program, twelve
# programs).
_LATENT_PREFILL_POSITIONS = 512
_LATENT_PREFILL_CHUNK_TOKENS = 512
_LATENT_PREFILL_HEADS = 16
_LATENT_PREFILL_UNROLL = 2


def latent_prefill_tiles(t: int, nope: int, v: int, rank: int, width: int,
                         page_size: int, max_pages: int, pool_dtype,
                         interpret: bool = False):
    """(query positions a block, key tokens a chunk) of
    `paged_prefill_attention_latent` for a launch of `t` positions a row
    over `max_pages`-wide tables of a single-stack pool of `width`-lane
    rows (`rank` latent values, the rope key behind them), heads of
    `nope` + rope query lanes and `v` value lanes; or None where the
    launch takes `models.hybrid.latent_prefill_attention`, the XLA form:
    Mosaic wants a bf16 pool, every lane count in whole lane tiles (the
    published 128 | 128 | 512 | 640: the rope lanes are padded to the
    rows' own behind the latent), query blocks and pages in whole
    sublane tiles and a chunk in whole lane tiles; the interpreter takes
    any geometry."""
    block_q = _largest_divisor(t, _LATENT_PREFILL_POSITIONS)
    chunk = page_size * _largest_divisor(
        max_pages, max(1, _LATENT_PREFILL_CHUNK_TOKENS // page_size))
    if t < 2 or not 0 < rank < width:
        return None
    if not interpret and (
            jnp.dtype(pool_dtype) != jnp.dtype(jnp.bfloat16)
            or nope % 128 or v % 128 or rank % 128 or width % 128
            or block_q % 16 or page_size % 16 or chunk % 128):
        return None
    return block_q, chunk


def _latent_prefill_kernel(
    # scalar prefetch
    starts_ref,  # [B] int32 position of a row's first query
    lengths_ref,  # [B] int32 keys a row sees, this chunk's included
    tables_ref,  # [B * max_pages] int32 flattened block tables
    layer_ref,  # [1] int32
    buf_idx_ref,  # [1] int32 (double-buffer slot)
    init_ref,  # [1] int32 (1 where the next live step starts its own DMA)
    q_ref,  # [1, G, block_q, nope + rope lanes]: [q_nope | q_rope | 0]
    w_uk_ref,  # [G, nope, rank]: this head group's
    w_uv_ref,  # [G, rank, v]
    pool_ref,  # FULL [L, P, ps, width] in HBM (memory_space=ANY)
    o_ref,  # [1, G, block_q, v]
    kv_buf,  # [2, C, ps, width] page chunks
    sems,  # DMA semaphores (2,)
    m_ref, l_ref,  # [G, block_q, 128] f32
    acc_ref,  # [G, block_q, v] f32
    *,
    block_q: int,
    pages_per_chunk: int,
    max_pages: int,
    batch_size: int,
    sm_scale: float,
):
    """Blocked causal attention of a prefill launch over a single-stack
    LATENT pool, the launch's own rows included (`write_latent_pages`
    has put them there), in the form that does NOT absorb: the algebra
    of `models.hybrid.latent_prefill_attention`, with the keys and
    values of a chunk rebuilt in VMEM and no score or accumulator in
    HBM.

    Grid (head group, row, query block, key chunk), run in order. Within
    a head group the liveness rule and the walk are
    `_pool_prefill_kernel`'s (`_PrefillWalk`): chunks wholly above a
    query block's last position, chunks past the row's keys, query
    blocks past the row's valid positions and rows of length 0 are
    never fetched or scored; a dead block's output is zeros. A query
    block is `block_q` consecutive positions of one row for a group of
    G heads, head-major ([B, heads, T, lanes]: a head is a leading
    index, so the heads of a group are a loop, not unrolled). A step
    takes the chunk's cached rows [c_kv | k_r] once for the group and,
    a head: k_h = c_kv W_uk,h^T and v_h = c_kv W_uv,h (the expansion,
    rounded to the operand dtype as the XLA form's), S = q_nope k_h^T +
    q_rope k_r^T, the causal edge a compare a row, and the flash update
    `_latent_decode_kernel` has, on the head's own float32 state. The
    pool streams as there: it stays in HBM, a page a DMA through the
    scalar-prefetched table into a double-buffered chunk, the next live
    step's chunk started before this one's is awaited (not across a
    head group's end: its first live step starts its own); the pages of
    a chunk are a loop. The keys are streamed once a head group (1.28 KB
    a key against 0.4 MFLOP a key a head)."""
    b = pl.program_id(1)
    i = pl.program_id(2)
    c = pl.program_id(3)
    n_chunks = pl.num_programs(3)
    ps, width = kv_buf.shape[2:]
    group, nope, rank = w_uk_ref.shape
    bk = pages_per_chunk * ps
    layer = layer_ref[0]
    walk = _PrefillWalk(starts_ref, lengths_ref, block_q=block_q, bk=bk,
                        batch_size=batch_size)

    def chunk_copies(bi, ci, slot, fn):
        base = bi * max_pages + ci * pages_per_chunk

        @pl.loop(0, pages_per_chunk)
        def _page(j):
            fn(pltpu.make_async_copy(
                pool_ref.at[layer, tables_ref[base + j]],
                kv_buf.at[slot, j], sems.at[slot]))

    live = walk.block_live(b, i)
    active = jnp.logical_and(live, c * bk < walk.key_limit(b, i))

    @pl.when(jnp.logical_and(active, init_ref[0] == 1))
    def _first():
        chunk_copies(b, c, buf_idx_ref[0], lambda cp: cp.start())
        init_ref[0] = 0

    @pl.when(jnp.logical_and(c == 0, live))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(active)
    def _compute():
        slot = buf_idx_ref[0]
        nb, _, nc = walk.next_step(b, i, c, pl.num_programs(2), n_chunks)

        @pl.when(nb < batch_size)
        def _prefetch():
            nslot = jnp.where(slot == 0, 1, 0)
            chunk_copies(nb, nc, nslot, lambda cp: cp.start())
            buf_idx_ref[0] = nslot

        @pl.when(nb >= batch_size)
        def _group_ends():
            init_ref[0] = 1

        chunk_copies(b, c, slot, lambda cp: cp.wait())
        # Row r of a head's tile is position r of the block; column j is
        # key c * bk + j. Key 0 is seen by every query of a live row, so
        # the running max is finite from chunk 0 on.
        q_pos = (starts_ref[b] + i * block_q
                 + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0))
        k_pos = c * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        seen = jnp.logical_and(k_pos <= q_pos, k_pos < lengths_ref[b])
        rows = kv_buf[slot].reshape(bk, width)
        c_kv, k_r = rows[:, :rank], rows[:, rank:]
        nt = (((1,), (1,)), ((), ()))

        def flash_head(h):
            q = q_ref[0, h]  # [block_q, nope + rope lanes]
            k = jax.lax.dot_general(
                c_kv, w_uk_ref[h], nt,
                preferred_element_type=jnp.float32).astype(q.dtype)
            v = jnp.dot(c_kv, w_uv_ref[h],
                        preferred_element_type=jnp.float32).astype(q.dtype)
            s = (jax.lax.dot_general(q[:, :nope], k, nt,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(q[:, nope:], k_r, nt,
                                       preferred_element_type=jnp.float32)
                 ) * sm_scale
            _latent_flash_update(jnp.where(seen, s, -jnp.inf), v,
                                 v.shape[1], q.dtype, m_ref.at[h],
                                 l_ref.at[h], acc_ref.at[h])

        # _LATENT_PREFILL_UNROLL heads a turn: independent chains, so
        # that one head's softmax overlaps another's matmuls
        unroll = _largest_divisor(group, _LATENT_PREFILL_UNROLL)

        @pl.loop(0, group // unroll)
        def _heads(g):
            for u in range(unroll):
                flash_head(g * unroll + u)

    @pl.when(jnp.logical_and(c == n_chunks - 1, live))
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[:, :, 0:1]).astype(o_ref.dtype)

    @pl.when(jnp.logical_and(c == n_chunks - 1, jnp.logical_not(live)))
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"),
                   donate_argnums=())  # read-only on the whole pool
def paged_prefill_attention_latent(
    q: jax.Array,  # [B, heads, T, nope + rope lanes]: [q_nope | q_rope | 0]
    w_uk: jax.Array,  # [heads, nope, rank]
    w_uv: jax.Array,  # [heads, rank, v]
    kv_pool: jax.Array,  # [L, 1, P, ps, 1, width]: the WHOLE latent cache
    layer: jax.Array,  # scalar int32
    block_tables: jax.Array,  # [B, max_pages] int32
    starts: jax.Array,  # [B] int32 position of each row's first query
    kv_lens: jax.Array,  # [B] int32 keys a row sees, this chunk's included
    *,
    sm_scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention of a prefill launch over a latent pool,
    `_latent_prefill_kernel`: row b holds the consecutive positions
    starts[b].. of which the first kv_lens[b] - starts[b] are real; the
    queries head-major with the rope lanes padded to the rows' own
    (`width - rank`); returns [B, heads, T, v] in their dtype. The
    caller (`paged_attention_latent`) has checked the geometry with
    `latent_prefill_tiles`."""
    b, heads, t, lanes = q.shape
    nope, rank = w_uk.shape[1:]
    vd = w_uv.shape[-1]
    n_layers, _, n_pages, ps = kv_pool.shape[:4]
    width = kv_pool.shape[-1]
    max_pages = block_tables.shape[1]
    block_q, chunk = latent_prefill_tiles(
        t, nope, vd, rank, width, ps, max_pages, kv_pool.dtype, interpret)
    group = _largest_divisor(heads, _LATENT_PREFILL_HEADS)
    ppc = chunk // ps
    assert t % block_q == 0 and max_pages % ppc == 0
    assert lanes - nope == width - rank

    def q_map(hg, bi, qi, ci, *refs):
        del ci, refs
        return (bi, hg, qi, 0)

    def w_map(hg, bi, qi, ci, *refs):
        del bi, qi, ci, refs
        return (hg, 0, 0)

    return pl.pallas_call(
        functools.partial(_latent_prefill_kernel, block_q=block_q,
                          pages_per_chunk=ppc, max_pages=max_pages,
                          batch_size=b, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(heads // group, b, t // block_q, max_pages // ppc),
            in_specs=[pl.BlockSpec((1, group, block_q, lanes), q_map),
                      pl.BlockSpec((group, nope, rank), w_map),
                      pl.BlockSpec((group, rank, vd), w_map),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, group, block_q, vd), q_map),
            scratch_shapes=[
                pltpu.VMEM((2, ppc, ps, width), kv_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((group, block_q, 128), jnp.float32),
                pltpu.VMEM((group, block_q, 128), jnp.float32),
                pltpu.VMEM((group, block_q, vd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, t, vd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4,
            vmem_limit_bytes=_PREFILL_VMEM_BYTES),
        name="paged_prefill_attention_latent",
    )(starts.astype(jnp.int32), kv_lens.astype(jnp.int32),
      block_tables.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32),
      q, w_uk, w_uv, kv_pool.reshape(n_layers, n_pages, ps, width))


def paged_attention_latent(
    q_nope: jax.Array,  # [B, T, heads, nope]
    q_rope: jax.Array,  # [B, T, heads, rope] roped
    kv_cache: jax.Array,  # [L, 1, P, ps, 1, width]
    layer,
    block_tables: jax.Array,
    positions: jax.Array,  # [B, T]: a row's positions are consecutive
    kv_lens: jax.Array,
    w_uk: jax.Array,  # [heads, nope, rank]
    w_uv: jax.Array,  # [heads, rank, v]
    config,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in for `models.hybrid.latent_prefill_attention`, a latent
    layer's `attention_fn`: wherever `latent_prefill_tiles` admits the
    geometry the launch runs the blocked kernel over the latent pool
    (`paged_prefill_attention_latent`), the same algebra with nothing of
    it in HBM: the XLA form wrote float32 scores [rows, heads, T, 128]
    four or five times a key block and an accumulator as large beside
    them, a third of the pangu cell's device time (PERF.md, PR 50). The
    relayout to head-major and back is XLA's, under 0.1 ms a layer. A row's
    first query position is `positions[:, 0]` and its valid count
    `kv_lens - positions[:, 0]`, as every prefill launch lays its rows
    out. Everything else takes the XLA form, the CPU path and the
    oracle. Returns [B, T, heads, v] in q's dtype."""
    from ..models.hybrid import _latent_sizes, latent_prefill_attention

    rank, _, scale = _latent_sizes(config)
    nope, width = q_nope.shape[-1], kv_cache.shape[-1]
    if latent_prefill_tiles(
            q_nope.shape[1], nope, w_uv.shape[-1], rank, width,
            kv_cache.shape[3], block_tables.shape[1], kv_cache.dtype,
            interpret) is None:
        return latent_prefill_attention(
            q_nope, q_rope, kv_cache, layer, block_tables, positions,
            kv_lens, w_uk, w_uv, config)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    q = jnp.pad(q, [(0, 0)] * 3 + [(0, nope + width - rank - q.shape[-1])])
    out = paged_prefill_attention_latent(
        jnp.moveaxis(q, 2, 1), w_uk, w_uv, kv_cache, layer, block_tables,
        positions[:, 0], kv_lens, sm_scale=scale, interpret=interpret)
    return jnp.moveaxis(out, 1, 2)


def make_paged_attention_decode_pool_tp(mesh, *,
                                        pages_per_chunk: int | None = None,
                                        interpret: bool = False):
    """Whole-pool decode kernel under tensor parallelism: shard_map over
    the kv-head axis, so each tp shard streams ONLY its local slice of the
    paged pool ([L, 2, P, ps, kh/tp, hd]) through its own chunked-DMA
    flash kernel. Attention is embarrassingly parallel over kv heads —
    no collectives inside; the output stays head-sharded and the
    downstream wo projection's psum (inserted by pjit) is the only
    cross-chip hop, exactly as on the XLA path.

    Returns a drop-in `decode_attention_fn` for `forward_decode`.
    (VERDICT r2 weak #3: the flagship kernel was gated off every
    multi-device mesh; this ships it under tp>1.)"""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import AXIS_TP

    q_spec = P(None, None, AXIS_TP, None)  # [B, 1, heads, hd]
    kv_spec = P(None, None, None, None, AXIS_TP, None)
    # per-token scales are head-shared: replicated across tp shards
    scale_spec = P()
    rep = P()

    def local(q, kv_cache, layer, block_tables, kv_lens, k_cur, v_cur):
        return paged_attention_decode_pool(
            q, kv_cache, layer, block_tables, kv_lens, k_cur, v_cur,
            pages_per_chunk=pages_per_chunk, interpret=interpret)

    def build(cache_spec):
        return shard_map(
            local, mesh=mesh,
            in_specs=(q_spec, cache_spec, rep, rep, rep, q_spec, q_spec),
            out_specs=q_spec,
            # pallas_call's out_shape carries no varying-mesh-axes
            # annotation; the kernel is per-shard pure (no collectives),
            # so the static check adds nothing here.
            check_vma=False,
        )

    variants = {}  # plain | q8, built on first use

    def fn(q, kv_cache, layer, block_tables, kv_lens, k_cur, v_cur):
        quantized = isinstance(kv_cache, tuple)
        key = "q8" if quantized else "plain"
        sharded = variants.get(key)
        if sharded is None:
            sharded = build((kv_spec, scale_spec) if quantized else kv_spec)
            variants[key] = sharded
        return sharded(q, kv_cache, jnp.asarray(layer, jnp.int32),
                       block_tables, kv_lens, k_cur, v_cur)

    return fn


def _fold_chunk(q: jax.Array, kh: int) -> jax.Array:
    """[B, T, qh, hd] -> [B, kh*(T*group), hd]: fold the chunk dim into
    the GQA group dim so the flash-decode kernels score T candidate
    positions per sequence in ONE dispatch. Sound because every chunk
    query shares the same history mask (positions < kv_len - 1) — the
    kernels never look at per-query positions; the causal in-chunk part
    is combined outside (`_combine_chunk`)."""
    b, t, qh, hd = q.shape
    group = qh // kh
    return q.reshape(b, t, kh, group, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(b, kh * t * group, hd)


def _unfold_chunk(acc, m, l, t: int):
    """Undo `_fold_chunk` on kernel outputs: acc [B, kh, T*g, hd] ->
    [B, T, kh, g, hd]; m/l [B, kh, T*g] -> [B, T, kh, g]."""
    b, kh, tg, hd = acc.shape
    g = tg // t
    acc = acc.reshape(b, kh, t, g, hd).transpose(0, 2, 1, 3, 4)
    m = m.reshape(b, kh, t, g).transpose(0, 2, 1, 3)
    l = l.reshape(b, kh, t, g).transpose(0, 2, 1, 3)
    return acc, m, l


def _combine_chunk(q, acc, m, l, k_cur, v_cur):
    """Fold the in-register chunk tokens into unnormalized flash
    partials with CAUSAL in-chunk masking (query i sees chunk tokens
    j <= i) — the T-token generalization of `_combine_current`.

    q [B, T, qh, hd]; acc [B, T, kh, g, hd] f32; m/l [B, T, kh, g];
    k_cur/v_cur [B, T, kh, hd]. Returns [B, T, qh, hd] in q's dtype."""
    b, t, qh, hd = q.shape
    kh = k_cur.shape[2]
    g = qh // kh
    qg = q.reshape(b, t, kh, g, hd).astype(jnp.float32)
    s = jnp.einsum("btkgh,bskh->btkgs", qg,
                   k_cur.astype(jnp.float32)) / math.sqrt(hd)
    causal = (jnp.arange(t)[None, :]
              <= jnp.arange(t)[:, None])  # [Tq, Tk]: key j <= query i
    s = jnp.where(causal[None, :, None, None, :], s, -jnp.inf)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_cur)  # finite: the diagonal is never masked
    alpha = jnp.exp(m - m_new)  # 0 when history empty (m = -inf)
    probs = jnp.exp(s - m_new[..., None])  # masked entries -> exact 0
    out = (acc * alpha[..., None]
           + jnp.einsum("btkgs,bskh->btkgh", probs,
                        v_cur.astype(jnp.float32)))
    denom = l * alpha + jnp.sum(probs, axis=-1)
    return (out / denom[..., None]).reshape(b, t, qh, hd).astype(q.dtype)


def paged_attention_spec(
    q: jax.Array,  # [B, T, qh, hd] chunk queries (token 0 = committed)
    kv_cache: jax.Array,  # [L, 2, P, ps, kh, hd]
    layer: int,
    block_tables: jax.Array,  # [B, max_pages]
    kv_lens: jax.Array,  # [B] committed length INCLUDING chunk token 0
    k_cur: jax.Array,  # [B, T, kh, hd] chunk K (not yet cached)
    v_cur: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Speculative batched-verification attention via the per-layer-slice
    flash kernel: T chunk queries folded into the GQA group dim stream
    the paged history once, then the causal in-chunk combine runs in
    XLA. Drop-in for `transformer.paged_attention_spec_xla` (the CPU
    interpret-mode oracle test pins the equivalence)."""
    t = q.shape[1]
    kh = k_cur.shape[2]
    acc, m, l = paged_decode_attention_partial(
        _fold_chunk(q, kh), kv_cache[layer, 0], kv_cache[layer, 1],
        block_tables, kv_lens - 1, interpret=interpret,
    )
    acc, m, l = _unfold_chunk(acc, m, l, t)
    return _combine_chunk(q, acc, m, l, k_cur, v_cur)


def paged_attention_spec_pool(
    q: jax.Array,  # [B, T, qh, hd]
    kv_cache,  # [L, 2, P, ps, kh, hd] or int8 (values, scales) pair
    layer,
    block_tables: jax.Array,
    kv_lens: jax.Array,  # [B] committed length INCLUDING chunk token 0
    k_cur: jax.Array,  # [B, T, kh, hd]
    v_cur: jax.Array,
    *,
    pages_per_chunk: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Speculative verification via the whole-pool chunked-DMA kernel —
    the production TPU path: one dispatch streams each owned page ONCE
    for all T candidate positions (the entire point of speculation on a
    memory-bound decode: k extra scores ride along for free). int8
    (values, scales) pools take the q8 path, same as single-token
    decode. Drop-in for
    `transformer.paged_attention_spec_xla` in `forward_spec`."""
    t = q.shape[1]
    kh = k_cur.shape[2]
    qf = _fold_chunk(q, kh)
    values, scales = (kv_cache if isinstance(kv_cache, tuple)
                      else (kv_cache, None))
    if _q8_needs_xla(values, scales, interpret):
        from ..models.transformer import paged_attention_spec_xla

        return paged_attention_spec_xla(q, kv_cache, layer, block_tables,
                                        kv_lens, k_cur, v_cur)
    acc, m, l = paged_decode_attention_pool(
        qf, values, layer, block_tables,
        jnp.maximum(kv_lens - 1, 0), kv_scales=scales,
        pages_per_chunk=pages_per_chunk, interpret=interpret,
    )
    acc, m, l = _unfold_chunk(acc, m, l, t)
    return _combine_chunk(q, acc, m, l, k_cur, v_cur)


# Prefill tiles: a grid step scores one key chunk of _PREFILL_CHUNK_TOKENS
# against a query block of _PREFILL_ROWS rows a kv head (positions x
# group), for every kv head in turn.
_PREFILL_ROWS = 1024
_PREFILL_CHUNK_TOKENS = 256
_PREFILL_VMEM_BYTES = 64 * 1024 * 1024  # of a v5e's 128 MiB


def prefill_kernel_tiles(t: int, qh: int, kh: int, hd: int, page_size: int,
                         max_pages: int, pool_dtype,
                         scale_lanes: int | None = None):
    """(query positions a block, key tokens a chunk) of
    `paged_prefill_attention_pool` for a launch of `t` positions a row
    over `max_pages`-wide tables, or None where Mosaic has no such
    geometry and `paged_attention` takes the XLA path: head_dim a lane
    tile or more, a power-of-two group, a pool whose kv heads fill whole
    32-bit words (the per-head read unpacks words: bf16 in pairs, int8 in
    fours), an int8 pool at head_dim == its scale lanes only, and tiles
    that are whole (sublane, lane) tiles."""
    pool_dtype = jnp.dtype(pool_dtype)
    group = qh // max(kh, 1)
    if (t < 2 or hd % 128 or qh != group * kh or group & (group - 1)
            or pool_dtype not in (jnp.dtype(jnp.int8),
                                  jnp.dtype(jnp.bfloat16))
            or kh % (4 // pool_dtype.itemsize)
            or (pool_dtype == jnp.int8) != (scale_lanes is not None)
            or (scale_lanes is not None and scale_lanes != hd)):
        return None
    block_q = _largest_divisor(t, max(1, _PREFILL_ROWS // group))
    chunk = page_size * _largest_divisor(
        max_pages, max(1, _PREFILL_CHUNK_TOKENS // page_size))
    if (block_q * group) % 16 or chunk % 128:
        return None
    return block_q, chunk


def prefill_table_pages(pages: int, page_size: int) -> int:
    """`pages` table columns rounded up to whole key chunks of the
    prefill kernel (and to 8): a width `prefill_kernel_tiles` admits at
    its full chunk, for a table the caller pads (a window group's)."""
    step = max(8, _PREFILL_CHUNK_TOKENS // page_size)
    return -(-pages // step) * step


def count_prefill_blocks(starts, kv_lens, rows: int, t: int, block_q: int,
                         chunk_tokens: int, table_tokens: int,
                         window: int = 0) -> tuple[int, int]:
    """(live, skipped) (query block, key chunk) pairs of one attention
    layer of a launch of `rows` x `t` positions over tables of
    `table_tokens`: the kernel's own liveness rule on the host's numbers,
    for the engine's counters. Row i's queries start at `starts[i]` and
    see `kv_lens[i]` keys, a window layer's (`window` > 0) the last
    `window` of them, all in the table's frame; rows past the lists are
    padding."""
    total = rows * (t // block_q) * (table_tokens // chunk_tokens)
    live = 0
    for start, kv_len in zip(starts, kv_lens):
        for qi in range(-(-(kv_len - start) // block_q)):
            limit = min(kv_len, start + (qi + 1) * block_q)
            live += -(-min(limit, table_tokens) // chunk_tokens)
            if window:
                edge = max(0, start + qi * block_q - (window - 1))
                live -= edge // chunk_tokens
    return live, total - live


def _head_rows(words_ref, group: int, sub, n_tok: int, kh: int, dtype):
    """One kv head of a chunk, float32 [n_tok, hd], read out of the
    chunk's [n_tok * kh, hd] rows (a token's kh rows consecutive) viewed
    as 32-bit words: a word holds the same lane of `pack` consecutive
    rows, the lowest row in its lowest bits, so head `group * pack + sub`
    is every (kh / pack)-th word row from `group` on (static: a strided
    load), shifted down by `sub` elements (traced where the caller loops
    over a word's heads without unrolling them, a Python int where it
    unrolls them). int8 codes and bf16 values both convert exactly."""
    bits = 8 * jnp.dtype(dtype).itemsize
    words = words_ref[pl.ds(group, n_tok, stride=kh * bits // 32), :]
    if not isinstance(sub, int):
        words = words >> (sub * bits).astype(jnp.uint32)
    elif sub:
        words = words >> jnp.uint32(sub * bits)
    if jnp.dtype(dtype) == jnp.int8:
        return pltpu.bitcast(words.astype(jnp.uint8),
                             jnp.int8).astype(jnp.float32)
    return pltpu.bitcast(words << 16, jnp.float32)


def _pool_prefill_kernel(
    # scalar prefetch
    starts_ref,  # [B] int32 position of a row's first query
    lengths_ref,  # [B] int32 keys a row sees, this chunk's included
    tables_ref,  # [B * max_pages] int32 flattened block tables
    layer_ref,  # [1] int32
    buf_idx_ref,  # [1] int32 (double-buffer slot)
    init_ref,  # [1] int32 (1 until the first DMA was issued)
    q_ref,  # [1, block_q, qh, hd]
    pool_ref,  # FULL [L, 2, P, ps, kh, hd] in HBM (memory_space=ANY)
    *rest,
    block_q: int,
    group: int,
    pages_per_chunk: int,
    max_pages: int,
    batch_size: int,
    quantized: bool,
    window: int = 0,
    sm_scale: float | None = None,
):
    """Blocked causal attention of a prefill launch over the paged pool,
    the launch's own keys included (`write_kv_pages` has put them there).

    Grid (row, query block, key chunk), run in order. A query block is
    `block_q` consecutive positions of one row for all its heads, read
    and written in the launch's own [B, T, qh, hd] layout (no relayout
    around the kernel) and folded once a block into a kv head's
    contiguous `block_q * g` query rows (`q_buf`); per kv head those
    rows score one chunk of `pages_per_chunk` pages in one MXU pass,
    S = Q K^T [block_q * g, chunk tokens], and the flash state (running
    max, sum, accumulator: float32 VMEM, a set a kv head) carries it
    across the row's chunks. [T, S] exists nowhere.

    What is never fetched or scored: chunks wholly above a query block's
    last position (the causal half), chunks past the row's keys, query
    blocks wholly past the row's valid positions (bucket padding), rows
    of length 0 (a pow2 launch's padding). Their grid steps do nothing;
    the output of a dead query block is zeros. Queries past a row's
    valid count inside a live block score every key of the row: their
    output is finite and nobody reads it.

    `window` > 0 (static) is a window layer's mask: a query at position
    q sees the keys q - window < k <= q, positions, lengths and table in
    the page group's own frame. The chunks wholly below the lower edge
    of a query block's FIRST query are never fetched either, so a
    block's flash state starts at its first live chunk, not at chunk 0.
    There its later queries may see no key at all (their edge lies in
    the next chunk): masked scores are a large finite negative, as the
    XLA oracle's, so such a row carries a finite average that the first
    seen key's weight wipes out (exp(-1e30 - m) == 0), where -inf less
    -inf would be a NaN. With `window` == 0 nothing of this is traced.

    `_pool_decode_kernel` has the why of the streaming: the pool stays in
    HBM, a page's K and V for all kv heads come in one DMA through the
    scalar-prefetched table into a double-buffered chunk, and a step
    starts the next live step's chunk before it waits for its own. Here
    a kv head's rows are then read out of the chunk on their own
    (`_head_rows`), so the MXU does no work across heads. The heads of
    one 32-bit word and a chunk's pages are loops, not unrolled: a
    program's trace, its Mosaic module and its compile hold kh / pack
    flash bodies and three DMA sites (11 s -> 7 s of Mosaic a shape,
    0.35 -> 0.13 s of lowering a program: PERF.md, PR 39).

    Precision: bf16 MXU operands (the query's dtype), float32 scores,
    softmax and accumulation. `quantized`: int8 pages and per-token bf16
    scale rows ([ps, LANES], LANES == hd); codes x scale (x 1/sqrt(hd)
    for K) are rounded once to the operand dtype, as a float32 matmul at
    the TPU's default precision rounds the dequantised keys.
    """
    if quantized:
        (scale_ref,  # FULL bf16 [L, 2, P, ps, LANES] in HBM (ANY)
         o_ref,  # [1, block_q, qh, hd]
         kv_buf,  # [2, 2, C, ps, kh, hd] (slot, K|V) page chunks
         sc_buf,  # [2, 2, C, ps, LANES]
         sems, q_buf, m_ref, l_ref, acc_ref) = rest
    else:
        scale_ref = sc_buf = None
        (o_ref, kv_buf,
         sems,  # DMA semaphores (2,): one per slot
         q_buf,  # [kh, block_q * g, hd]: row t * g + j = position t, head j
         m_ref, l_ref,  # [kh, block_q * g, 128] f32
         acc_ref) = rest  # [kh, block_q * g, hd] f32
    b = pl.program_id(0)
    i = pl.program_id(1)
    c = pl.program_id(2)
    n_q = pl.num_programs(1)
    n_chunks = pl.num_programs(2)
    ps, kh, hd = kv_buf.shape[3:]
    rows = block_q * group
    bk = pages_per_chunk * ps
    layer = layer_ref[0]
    if sm_scale is None:  # a model that states no scale of its own
        sm_scale = 1.0 / math.sqrt(hd)

    walk = _PrefillWalk(starts_ref, lengths_ref, block_q=block_q, bk=bk,
                        batch_size=batch_size, window=window)
    block_live, key_limit = walk.block_live, walk.key_limit
    first_chunk = walk.first_chunk

    def chunk_copies(bi, ci, slot, fn):
        base = bi * max_pages + ci * pages_per_chunk

        @pl.loop(0, pages_per_chunk)
        def _page(j):
            page = tables_ref[base + j]
            fn(pltpu.make_async_copy(
                pool_ref.at[layer, :, page], kv_buf.at[slot, :, j],
                sems.at[slot]))
            if quantized:
                fn(pltpu.make_async_copy(
                    scale_ref.at[layer, :, page], sc_buf.at[slot, :, j],
                    sems.at[slot]))

    live = block_live(b, i)
    active = jnp.logical_and(live, c * bk < key_limit(b, i))
    if window:
        active = jnp.logical_and(active, c >= first_chunk(b, i))

    @pl.when(jnp.logical_and(active, init_ref[0] == 1))
    def _first():
        chunk_copies(b, c, buf_idx_ref[0], lambda cp: cp.start())
        init_ref[0] = 0

    @pl.when(jnp.logical_and(c == 0, live))
    def _fold_queries():
        # a kv head's query rows, contiguous: once a query block
        for h in range(kh):
            q_buf[h] = q_ref[0, :, h * group:(h + 1) * group, :].reshape(
                rows, hd)

    @pl.when(active)
    def _compute():
        slot = buf_idx_ref[0]
        nb, _, nc = walk.next_step(b, i, c, n_q, n_chunks)

        @pl.when(nb < batch_size)
        def _prefetch():
            nslot = jnp.where(slot == 0, 1, 0)
            chunk_copies(nb, nc, nslot, lambda cp: cp.start())
            buf_idx_ref[0] = nslot

        chunk_copies(b, c, slot, lambda cp: cp.wait())
        # Row r of a head's tile is position r // g of the block; column
        # j is key c * bk + j.
        q_pos = (starts_ref[b] + i * block_q + _div(
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), group))
        k_pos = c * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        seen = jnp.logical_and(k_pos <= q_pos, k_pos < lengths_ref[b])
        if window:
            seen = jnp.logical_and(seen, k_pos > q_pos - window)
        first = c == first_chunk(b, i)
        masked = -1e30 if window else -jnp.inf
        pool_dtype = kv_buf.dtype
        k_words = kv_buf.at[slot, 0].reshape(bk * kh, hd).bitcast(jnp.uint32)
        v_words = kv_buf.at[slot, 1].reshape(bk * kh, hd).bitcast(jnp.uint32)
        if quantized:
            k_scale = (sc_buf[slot, 0].reshape(bk, hd).astype(jnp.float32)
                       * sm_scale)
            v_scale = sc_buf[slot, 1].reshape(bk, hd).astype(jnp.float32)
        pack = 4 // jnp.dtype(pool_dtype).itemsize  # kv heads a word

        def flash_head(word_group, sub):
            h = word_group * pack + sub
            q = q_buf[h]  # [rows, hd], the matmul operand dtype
            k = _head_rows(k_words, word_group, sub, bk, kh, pool_dtype)
            v = _head_rows(v_words, word_group, sub, bk, kh, pool_dtype)
            if quantized:
                k, v = k * k_scale, v * v_scale
            s = jax.lax.dot_general(
                q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [rows, bk]
            if not quantized:
                s = s * sm_scale
            s = jnp.where(seen, s, masked)
            # No reset between query blocks: a block's first live chunk
            # takes an empty state instead of the scratch's leftovers.
            # Finite from there on: without a window key 0 is seen by
            # every query of a live row; with one `masked` is finite.
            m_prev = jnp.where(first, masked, m_ref[h, :, 0:1])
            l_prev = jnp.where(first, 0.0, l_ref[h, :, 0:1])
            o_prev = jnp.where(first, 0.0, acc_ref[h])
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(q.dtype), v.astype(q.dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [rows, hd]
            acc_ref[h] = o_prev * alpha + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

        # A loop over a word's heads, each turn the same head of every
        # word row: a program's trace and Mosaic's module hold kh / pack
        # flash bodies, and the bodies of one turn are independent, so
        # one head's softmax overlaps another's matmuls. The window form
        # unrolls the loop: kh independent bodies a step run in 73% of
        # the looped form's time (PERF.md, PR 41), and a model with
        # window layers lowers this kernel in six prefill programs,
        # where the dense cell's thirteen could not bear 8 bodies'
        # lowering (PR 39).
        if window:
            for sub in range(pack):
                for word_group in range(kh // pack):
                    flash_head(word_group, sub)
        else:
            @pl.loop(0, pack)
            def _heads(sub):
                for word_group in range(kh // pack):
                    flash_head(word_group, sub)

    @pl.when(jnp.logical_and(c == n_chunks - 1, live))
    def _finish():
        for h in range(kh):
            o_ref[0, :, h * group:(h + 1) * group, :] = (
                acc_ref[h] / l_ref[h, :, 0:1]).astype(o_ref.dtype).reshape(
                    block_q, group, hd)

    @pl.when(jnp.logical_and(c == n_chunks - 1, jnp.logical_not(live)))
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)


def _pool_prefill_call(q, kv_pool, layer, block_tables, starts, kv_lens,
                       kv_scales, window, interpret, sm_scale=None):
    """The pallas_call both jitted entry points share; `window` > 0 is
    the window layers' form, under its own name; `sm_scale` the score
    scale of a model that states one (None: 1/sqrt(hd))."""
    quantized = kv_scales is not None
    b, t, qh, hd = q.shape
    ps, kh = kv_pool.shape[3], kv_pool.shape[4]
    group = qh // kh
    max_pages = block_tables.shape[1]
    block_q, chunk = prefill_kernel_tiles(
        t, qh, kh, hd, ps, max_pages, kv_pool.dtype,
        kv_scales.shape[-1] if quantized else None)
    ppc = chunk // ps
    assert t % block_q == 0 and max_pages % ppc == 0
    rows = block_q * group

    def q_map(bi, qi, ci, *refs):
        del ci, refs
        return (bi, qi, 0, 0)

    in_specs = [pl.BlockSpec((1, block_q, qh, hd), q_map),
                pl.BlockSpec(memory_space=pl.ANY)]
    scratch = [pltpu.VMEM((2, 2, ppc, ps, kh, hd), kv_pool.dtype)]
    operands = [q, kv_pool]
    if quantized:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        scratch.append(pltpu.VMEM((2, 2, ppc, ps, kv_scales.shape[-1]),
                                  kv_scales.dtype))
        operands.append(kv_scales)
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.VMEM((kh, rows, hd), q.dtype),
        pltpu.VMEM((kh, rows, 128), jnp.float32),
        pltpu.VMEM((kh, rows, 128), jnp.float32),
        pltpu.VMEM((kh, rows, hd), jnp.float32),
    ]
    return pl.pallas_call(
        functools.partial(_pool_prefill_kernel, block_q=block_q,
                          group=group, pages_per_chunk=ppc,
                          max_pages=max_pages, batch_size=b,
                          quantized=quantized, window=window,
                          sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(b, t // block_q, max_pages // ppc),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block_q, qh, hd), q_map),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_PREFILL_VMEM_BYTES),
        name=("paged_prefill_attention_window" if window
              else "paged_prefill_attention_pool"),
    )(starts.astype(jnp.int32), kv_lens.astype(jnp.int32),
      block_tables.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32), *operands)


@functools.partial(jax.jit, static_argnames=("interpret", "sm_scale"),
                   donate_argnums=())  # read-only on the whole pool
def paged_prefill_attention_pool(
    q: jax.Array,  # [B, T, qh, hd]
    kv_pool: jax.Array,  # [L, 2, P, ps, kh, hd]: the WHOLE cache
    layer: jax.Array,  # scalar int32
    block_tables: jax.Array,  # [B, max_pages] int32
    starts: jax.Array,  # [B] int32 position of each row's first query
    kv_lens: jax.Array,  # [B] int32 keys a row sees, this chunk's included
    kv_scales=None,  # bf16 [L, 2, P, ps, LANES] for an int8 pool
    *,
    interpret: bool = False,
    sm_scale: float | None = None,
) -> jax.Array:
    """Causal attention of a prefill launch, `_pool_prefill_kernel`: row b
    holds the consecutive positions starts[b].. of which the first
    kv_lens[b] - starts[b] are real; returns [B, T, qh, hd]. The caller
    (`paged_attention`) has checked the geometry with
    `prefill_kernel_tiles`."""
    return _pool_prefill_call(q, kv_pool, layer, block_tables, starts,
                              kv_lens, kv_scales, 0, interpret, sm_scale)


@functools.partial(jax.jit,
                   static_argnames=("window", "interpret", "sm_scale"),
                   donate_argnums=())
def paged_prefill_attention_window(
    q: jax.Array,  # [B, T, qh, hd]
    kv_pool: jax.Array,  # the window group's WHOLE cache
    layer: jax.Array,
    block_tables: jax.Array,  # [B, pages] the group's own table
    starts: jax.Array,  # [B] first query's position in the table's frame
    kv_lens: jax.Array,  # [B] keys a row sees, same frame
    kv_scales=None,
    *,
    window: int,
    interpret: bool = False,
    sm_scale: float | None = None,
) -> jax.Array:
    """`paged_prefill_attention_pool` for a window layer: a query sees
    the last `window` keys up to its own, and the chunks below a query
    block's window are never fetched. Under a name of its own, as the
    decode kernels are, so that a device trace tells the window layers'
    events from the full layers'."""
    return _pool_prefill_call(q, kv_pool, layer, block_tables, starts,
                              kv_lens, kv_scales, window, interpret,
                              sm_scale)


def paged_attention(
    q: jax.Array,  # [B, T, qh, hd]
    kv_cache,  # [L, 2, P, ps, kh, hd] or int8 (values, scales) pair
    layer: int,
    block_tables: jax.Array,
    positions: jax.Array,  # [B, T]: a row's positions are consecutive
    kv_lens: jax.Array,
    *,
    window: int = 0,
    interpret: bool = False,
    sm_scale: float | None = None,
) -> jax.Array:
    """Drop-in `attention_fn` for `models.transformer.forward` and the
    attention layers of `models.hybrid.forward_hybrid`, full and window
    (`window` > 0: the mask's lower edge, with table, positions and
    lengths in the window group's own frame).

    A prefill chunk (T > 1) runs the blocked kernel over the paged pool
    (`paged_prefill_attention_pool`, `paged_prefill_attention_window`)
    wherever `prefill_kernel_tiles` admits the geometry: XLA's attention
    there writes and reads a float32 score tensor [B, T, heads, table
    tokens] several times over and was 30% of the flagship cell's device
    time (PERF.md, PR 39). A row's first query position is
    `positions[:, 0]` and its valid count `kv_lens - positions[:, 0]`, as
    every prefill launch lays its rows out. One token (T == 1) over a
    bf16 pool runs the per-layer flash decode kernel. Everything else
    takes `paged_attention_xla`, the CPU path and the oracle of both.
    `sm_scale`: the score scale of a model that states one (None:
    1/sqrt(hd), nothing traced)."""
    from ..models.transformer import paged_attention_xla

    values, scales = (kv_cache if isinstance(kv_cache, tuple)
                      else (kv_cache, None))
    _, t, qh, hd = q.shape
    if t == 1 and scales is None and not window and sm_scale is None:
        out = paged_decode_attention(
            q[:, 0], values[layer, 0], values[layer, 1],
            block_tables, kv_lens, interpret=interpret,
        )
        return out[:, None]
    if prefill_kernel_tiles(
            t, qh, values.shape[4], hd, values.shape[3],
            block_tables.shape[1], values.dtype,
            None if scales is None else scales.shape[-1]) is not None:
        if window:
            return paged_prefill_attention_window(
                q, values, layer, block_tables, positions[:, 0], kv_lens,
                kv_scales=scales, window=window, interpret=interpret,
                sm_scale=sm_scale)
        return paged_prefill_attention_pool(
            q, values, layer, block_tables, positions[:, 0], kv_lens,
            kv_scales=scales, interpret=interpret, sm_scale=sm_scale)
    return paged_attention_xla(q, kv_cache, layer, block_tables,
                               positions, kv_lens, window=window,
                               sm_scale=sm_scale)
