"""The blocked prefill attention kernel (`paged_prefill_attention_pool`
and, with a window's lower edge, `paged_prefill_attention_window`)
against `paged_attention_xla`, in the Pallas interpreter on the CPU:
what a prefill launch hands `attention_fn`, row by row. Mosaic's view of
the same kernel is in tests/test_tpu_compile.py.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.transformer import (
    KV_SCALE_LANES,
    paged_attention_xla,
)
from dynamo_tpu.ops.paged_attention import (
    count_prefill_blocks,
    latent_prefill_tiles,
    paged_attention,
    prefill_kernel_tiles,
    prefill_table_pages,
)

PAGE, HEAD_DIM, LAYERS = 16, 128, 2

# name: (pool, kv heads, group, positions a row (the bucket), table width
# in pages, rows as (first position, valid positions); (0, 0) is a row a
# pow2 launch pads in[, window: the layer's, with positions and table in
# the window group's frame, column 0 = the row's first held block])
CASES = {
    "bf16-g4-fresh": ("bf16", 2, 4, 512, 32, [(0, 512)]),
    "int8-g4-fresh": ("int8", 4, 4, 512, 32, [(0, 512)]),
    "bf16-g8-fresh": ("bf16", 2, 8, 256, 16, [(0, 256)]),
    "int8-g8-fresh": ("int8", 4, 8, 256, 16, [(0, 256)]),
    "bf16-g16-fresh": ("bf16", 2, 16, 128, 16, [(0, 128)]),
    "int8-g16-fresh": ("int8", 4, 16, 128, 16, [(0, 128)]),
    "bf16-g4-continuation": ("bf16", 2, 4, 512, 64, [(512, 512)]),
    "int8-g4-continuation": ("int8", 4, 4, 512, 64, [(512, 512)]),
    "bf16-g4-short-row": ("bf16", 2, 4, 512, 32, [(0, 300)]),
    "int8-g4-short-row-mid-page": ("int8", 4, 4, 512, 32, [(0, 333)]),
    "bf16-g4-padded-launch": ("bf16", 2, 4, 256, 16,
                              [(0, 256), (0, 100), (0, 201), (0, 0)]),
    "int8-g4-padded-launch": ("int8", 4, 4, 256, 16,
                              [(0, 77), (0, 0)]),
    "bf16-g16-wide-table": ("bf16", 2, 16, 128, 64, [(0, 90), (64, 128)]),
    "int8-g4-wide-table": ("int8", 4, 4, 256, 64, [(0, 200)]),
    "int8-g4-mixed-rows": ("int8", 8, 4, 512, 64,
                           [(0, 512), (512, 300), (250, 77), (0, 0)]),
    "bf16-g8-continuation-mid-page": ("bf16", 2, 8, 256, 32,
                                      [(131, 256), (7, 120)]),
    # a fresh row shorter than the window: the mask is the causal one
    "window-fresh-short-row": ("bf16", 2, 8, 512, 112, [(0, 300)], 1024),
    # pages freed behind: the frame starts at the block of the first
    # query's lower edge, so the row's first position is window - 1 + 7
    "window-frame-past-0-continues": ("bf16", 2, 8, 512, 112,
                                      [(1030, 512)], 1024),
    # the edge of every block but the first falls inside a chunk, and
    # blocks 2.. start at chunk 1, 2: chunks below are never fetched
    "window-edge-inside-a-chunk": ("bf16", 2, 4, 1024, 80,
                                   [(0, 1024)], 300),
    "int8-window-edge-inside-a-chunk": ("int8", 4, 4, 512, 48,
                                        [(150, 512)], 200),
    # block 0 holds positions 250..377 and starts at chunk 0 (its first
    # query's edge is 187); its queries from 319 on see no key there
    "window-later-queries-see-no-key-in-first-chunk": (
        "bf16", 2, 8, 256, 32, [(250, 256)], 64),
    # the same past a row's end: padding queries far above the last key
    # see none in any chunk
    "window-padded-queries-see-no-key": ("bf16", 2, 8, 256, 32,
                                         [(250, 40)], 16),
    "window-padded-rows-and-last-block": (
        "bf16", 2, 8, 512, 112,
        [(1030, 300), (0, 0), (1024, 130), (0, 77)], 1024),
    # the mellum cell's three buckets at its window tables' widths
    "window-cell-512-w112": ("bf16", 2, 8, 512, 112, [(1038, 470)], 1024),
    "window-cell-1024-w144": ("bf16", 2, 8, 1024, 144, [(1023, 1000)],
                              1024),
    "window-cell-2048-w208": ("bf16", 2, 8, 2048, 208, [(1027, 1567)],
                              1024),
}


def _launch(case, seed=0):
    pool, kh, g, t, width, rows = CASES[case][:6]
    rng = np.random.default_rng(seed)
    b = len(rows)
    n_pages = b * width + 1
    shape = (LAYERS, 2, n_pages, PAGE, kh, HEAD_DIM)
    if pool == "int8":
        values = jnp.asarray(rng.integers(-127, 128, shape, dtype=np.int8))
        scale = rng.uniform(0.002, 0.02, shape[:4] + (1,))
        scales = jnp.asarray(np.broadcast_to(
            scale, shape[:4] + (KV_SCALE_LANES,)), jnp.bfloat16)
        cache = (values, scales)
    else:
        cache = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(b, t, kh * g, HEAD_DIM)), jnp.bfloat16)
    tables = np.zeros((b, width), np.int32)  # a padded row: the scratch page
    positions = np.zeros((b, t), np.int32)
    kv_lens = np.zeros(b, np.int32)
    pages = rng.permutation(np.arange(1, n_pages))
    for i, (start, n) in enumerate(rows):
        if n:
            tables[i] = pages[i * width:(i + 1) * width]
            positions[i, :n] = np.arange(start, start + n)
            kv_lens[i] = start + n
    return (q, cache, jnp.asarray(tables), jnp.asarray(positions),
            jnp.asarray(kv_lens))


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_prefill_kernel_matches_the_xla_oracle(case):
    _, kh, g, t, width, rows, *window = CASES[case]
    window = window[0] if window else 0
    q, cache, tables, positions, kv_lens = _launch(case)
    values = cache[0] if isinstance(cache, tuple) else cache
    block_q, _ = prefill_kernel_tiles(
        t, kh * g, kh, HEAD_DIM, PAGE, width, values.dtype,
        KV_SCALE_LANES if isinstance(cache, tuple) else None)
    layer = LAYERS - 1
    got = np.asarray(paged_attention(
        q, cache, layer, tables, positions, kv_lens, window=window,
        interpret=True), np.float32)
    want = np.asarray(paged_attention_xla(
        q, cache, layer, tables, positions, kv_lens, window=window),
        np.float32)
    assert got.shape == want.shape and np.isfinite(got).all()
    for i, (_, n) in enumerate(rows):
        # bf16 operands and probabilities against a float32 oracle
        np.testing.assert_allclose(got[i, :n], want[i, :n], atol=0.03,
                                   rtol=0.03)
    # what nobody reads: zeros for a query block wholly past the row's end
    for i, (_, n) in enumerate(rows):
        dead = -(-n // block_q) * block_q
        assert not got[i, dead:].any()


@pytest.mark.parametrize("why,args", [
    ("head_dim under a lane tile", (256, 8, 2, 64, 16, 64, jnp.bfloat16)),
    ("one token", (1, 32, 8, 128, 16, 64, jnp.bfloat16)),
    ("a group that is no power of two", (256, 6, 2, 128, 16, 64,
                                         jnp.bfloat16)),
    ("one bf16 kv head: half a word", (256, 8, 1, 128, 16, 64,
                                       jnp.bfloat16)),
    ("two int8 kv heads: half a word", (256, 8, 2, 128, 16, 64, jnp.int8,
                                        128)),
    ("an int8 pool without scales", (256, 32, 8, 128, 16, 64, jnp.int8)),
    ("a float32 pool", (256, 32, 8, 128, 16, 64, jnp.float32)),
    ("a key chunk under a lane tile", (256, 32, 8, 128, 16, 4,
                                       jnp.bfloat16)),
])
def test_geometries_the_kernel_leaves_to_xla(why, args):
    assert prefill_kernel_tiles(*args) is None, why


def test_the_cells_geometries_take_the_kernel():
    # mistral-7b w4kv8: 32 heads over 8, [rows, 1024] over 64-page tables
    assert prefill_kernel_tiles(1024, 32, 8, 128, 16, 64, jnp.int8,
                                128) == (256, 256)
    # nemotron-3-nano's attention layers: 32 heads over 2, bf16
    assert prefill_kernel_tiles(128, 32, 2, 128, 16, 64,
                                jnp.bfloat16) == (64, 256)


def test_block_counts_follow_the_kernels_liveness():
    # [4, 1024] over 64-page tables in blocks of 256 x 256: a full bucket
    # skips the causal half only (1 + 2 + 3 + 4 of 16 pairs)
    assert count_prefill_blocks([0], [1024], 1, 1024, 256, 256,
                                1024) == (10, 6)
    # a 736-token prompt: three live query blocks, 1 + 2 + 3 chunks; two
    # rows of padding
    live, skipped = count_prefill_blocks([0, 0], [736, 736], 4, 1024, 256,
                                         256, 1024)
    assert (live, skipped) == (12, 52)
    # a continuation sees its prefix whole: 3 + 4 chunks
    assert count_prefill_blocks([512], [1024], 1, 512, 256, 256,
                                1024) == (7, 1)


def _brute_force_blocks(starts, kv_lens, rows, t, block_q, chunk, table,
                        window):
    """Pairs that hold at least one (query, key) the mask admits, the
    block's first query alone deciding the lower edge as in the kernel;
    query blocks are live by their first position."""
    live = 0
    for start, kv_len in zip(starts, kv_lens):
        for qi in range(t // block_q):
            first = start + qi * block_q
            if first >= kv_len:
                continue
            last = min(first + block_q, kv_len) - 1
            lo = max(0, first - (window - 1)) if window else 0
            for c in range(table // chunk):
                keys = range(c * chunk, (c + 1) * chunk)
                live += keys[-1] >= lo and keys[0] <= last
    return live, rows * (t // block_q) * (table // chunk) - live


@pytest.mark.parametrize("window", [0, 16, 64, 300, 1024])
def test_block_counts_with_a_window_match_a_brute_force_count(window):
    rng = np.random.default_rng(window)
    for t, block_q, chunk, table in ((512, 128, 256, 1792),
                                     (2048, 128, 256, 3328),
                                     (256, 64, 128, 1024)):
        starts = [int(s) for s in rng.integers(0, table - t, 3)]
        lens = [s + int(n) for s, n in zip(starts, rng.integers(1, t + 1, 3))]
        assert count_prefill_blocks(
            starts, lens, 4, t, block_q, chunk, table, window
        ) == _brute_force_blocks(starts, lens, 4, t, block_q, chunk, table,
                                 window)


@pytest.mark.parametrize("bucket,width", [(512, 112), (1024, 144),
                                          (2048, 208)])
def test_the_kernel_admits_the_mellum_cells_window_tables(bucket, width):
    # window 1024 + the bucket's keys, wherever they start within a page,
    # in pages of 16, padded to whole key chunks
    assert prefill_table_pages(-(-(1024 + bucket) // PAGE) + 1, PAGE) == width
    assert prefill_kernel_tiles(bucket, 32, 4, HEAD_DIM, PAGE, width,
                                jnp.bfloat16) == (128, 256)


# -- a latent layer's kernel (`paged_prefill_attention_latent`) ---------------
# latent_prefill_tiles(t, nope, v, rank, width, page, table pages, pool)

PANGU = (128, 128, 512, 640, PAGE, 384, jnp.bfloat16)  # the cell's geometry


@pytest.mark.parametrize("bucket", [256, 512, 1024, 1536, 2048])
def test_the_pangu_cells_buckets_take_the_latent_kernel(bucket):
    # query blocks of at most 512 positions, key chunks of 32 pages
    assert latent_prefill_tiles(bucket, *PANGU) == (min(bucket, 512), 512)


@pytest.mark.parametrize("why,args", [
    ("one token", (1, *PANGU)),
    ("a float32 pool", (256, *PANGU[:-1], jnp.float32)),
    ("an int8 pool", (256, *PANGU[:-1], jnp.int8)),
    ("rows that are all latent: a two-stack pool's", (256, 128, 128, 512,
                                                      512, PAGE, 384,
                                                      jnp.bfloat16)),
    ("the loader's unpadded rows of 576 lanes", (256, 128, 128, 512, 576,
                                                 PAGE, 384, jnp.bfloat16)),
    ("nope lanes under a lane tile", (256, 64, 128, 512, 640, PAGE, 384,
                                      jnp.bfloat16)),
    ("value lanes under a lane tile", (256, 128, 64, 512, 640, PAGE, 384,
                                       jnp.bfloat16)),
    ("a rank that is no lane tile", (256, 128, 128, 448, 640, PAGE, 384,
                                     jnp.bfloat16)),
    ("pages under a sublane tile", (256, 128, 128, 512, 640, 8, 384,
                                    jnp.bfloat16)),
    ("a key chunk under a lane tile", (256, 128, 128, 512, 640, PAGE, 4,
                                       jnp.bfloat16)),
    ("a query block under a sublane tile", (250, *PANGU)),
])
def test_latent_geometries_the_kernel_leaves_to_xla(why, args):
    assert latent_prefill_tiles(*args) is None, why
    # the interpreter has no tiles to fill: any single stack of rows
    # with something behind the latent, two positions or more
    took = latent_prefill_tiles(*args, interpret=True)
    assert (took is None) == (args[0] < 2 or args[3] >= args[4]), why


def test_the_latent_kernel_is_the_slot_of_one_device_off_the_xla_path(
        monkeypatch):
    """Where a latent layer's prefill goes: the runner's mesh and
    backend fill the slot (`_mesh_kernels`), the geometry rule decides a
    launch. On the CPU the slot is empty unless DYNT_ATTENTION asks for
    the interpreter; with more than one device it is empty."""
    import jax

    from dynamo_tpu.engine.model_runner import _mesh_kernels
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    one = make_mesh(MeshConfig(), jax.devices()[:1])
    two = make_mesh(MeshConfig(tp=2), jax.devices()[:2])
    assert _mesh_kernels(one)["prefill_latent"] is None  # the CPU: XLA
    monkeypatch.setenv("DYNT_ATTENTION", "pallas")
    slot = _mesh_kernels(one)["prefill_latent"]
    assert slot.func.__name__ == "paged_attention_latent"
    assert slot.keywords == {"interpret": True}
    assert _mesh_kernels(two)["prefill_latent"] is None
    assert _mesh_kernels(two)["decode_latent"] is None


@pytest.mark.parametrize("seed", range(4))
def test_block_counts_with_the_latent_kernels_tiles_match_brute_force(seed):
    """`count_prefill_blocks` on the tiles the latent kernel runs the
    cell's launches with (rows x bucket = 2,048 positions over tables of
    6,144 tokens) against the pairs that hold a (query, key) the causal
    mask admits."""
    rng = np.random.default_rng(seed)
    table = 384 * PAGE
    for rows, bucket in ((1, 2048), (2, 1024), (4, 512), (8, 256)):
        block_q, chunk = latent_prefill_tiles(bucket, *PANGU)
        used = int(rng.integers(1, rows + 1))
        starts = [int(s) for s in rng.integers(0, table - bucket, used)]
        lens = [s + int(n)
                for s, n in zip(starts, rng.integers(1, bucket + 1, used))]
        assert count_prefill_blocks(
            starts, lens, rows, bucket, block_q, chunk, table
        ) == _brute_force_blocks(starts, lens, rows, bucket, block_q, chunk,
                                 table, 0)
    # a fresh 2,048-token prompt: 1 + 2 + 3 + 4 of 4 x 12 pairs
    assert count_prefill_blocks([0], [2048], 1, 2048, 512, 512,
                                table) == (10, 38)
