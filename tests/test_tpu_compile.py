"""The whole-pool decode attention kernel compiled by Mosaic for a TPU
v5e that is described, not attached, at the widths the chip runs.

Interpret mode proves the Python and says nothing about the compiler:
an unaligned slice, a reshape Mosaic cannot lay out or too much VMEM is
refused only here (or on the chip, at the price of a chip call). Nothing
runs, so these tests say nothing about results or times. The topology is
described inside a fixture, never while a module is imported: only one
process may hold the TPU's library, and every xdist worker imports every
test file.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

PAGE, LANES, HEAD_DIM, ROWS, LAYERS = 16, 128, 128, 32, 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this box
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to jax's persistent
    cache and can never be read back without the chip (the next one
    warns and compiles again): the cache is off around every compile of
    this file, as the on-chip-measurement guide asks."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (kv heads, query rows a kv head, int8 pool, table width[, rows]): the
# benchmark cell (mistral-7b: 8 kv heads, group 4, int8, 64-page tables),
# its narrowest and widest tables, a bf16 pool, the spec fold (group 4 x
# 5 positions), one shard of `--tp 4` / `--tp 2`, and lfm2's geometry as
# the kernel sees it (256 rows; 8 kv heads of 64 packed two a lane tile
# are 4 "kv heads" 128 wide with 8 wide query rows each; the cell's
# narrowest and widest tables).
CASES = {
    "q8-w64": (8, 4, True, 64),
    "q8-w8": (8, 4, True, 8),
    "q8-w128": (8, 4, True, 128),
    "bf16-w64": (8, 4, False, 64),
    "q8-spec5-w64": (8, 20, True, 64),
    "bf16-tp4-shard-w64": (2, 4, False, 64),
    "q8-tp2-shard-w64": (4, 4, True, 64),
    "bf16-lfm2-packed-w8": (4, 8, False, 8, 256),
    "bf16-lfm2-packed-w192": (4, 8, False, 192, 256),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_pool_decode_kernel_compiles_for_v5e(one_chip, case):
    from dynamo_tpu.ops.paged_attention import paged_decode_attention_pool

    kh, g, quantized, width, rows = (*CASES[case], ROWS)[:5]
    n_pages = rows * width + 1

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = (LAYERS, 2, n_pages, PAGE, kh, HEAD_DIM)
    args = [
        shape((rows, kh * g, HEAD_DIM), jnp.bfloat16),
        shape(pool, jnp.int8 if quantized else jnp.bfloat16),
        shape((), jnp.int32),
        shape((rows, width), jnp.int32),
        shape((rows,), jnp.int32),
    ]
    if quantized:
        args.append(shape((LAYERS, 2, n_pages, PAGE, LANES), jnp.bfloat16))
    compiled = paged_decode_attention_pool.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "paged_decode_attention_pool" in text  # the trace's name
    # the kernel reads the pool where it lies: handing it in with a
    # page's (token, kv head) dimensions merged is a bitcast, not a copy
    assert compiled.memory_analysis().temp_size_in_bytes < math.prod(
        pool) // LAYERS // 4
    assert not re.search(rf"\[{LAYERS},2,{n_pages},[0-9,]*\][^ ]* copy\(", text)


# The hybrid stack's kernels (models/hybrid.py) at the published widths of
# nemotron3-nano-30b-a3b: 64 Mamba heads of 64 over 8 groups, state 128;
# experts of width 1856 over hidden 2688, 64 held.
MAMBA = {"heads": 64, "p": 64, "groups": 8, "n": 128}


def _shape(one_chip, dims, dtype):
    return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)


def test_the_ssm_decode_state_update_compiles_for_v5e(one_chip):
    from dynamo_tpu.ops.ssm import ssm_state_update

    slots, h, p, n = 128, MAMBA["heads"], MAMBA["p"], MAMBA["n"]
    f32, bf16 = jnp.float32, jnp.bfloat16
    compiled = ssm_state_update.lower(
        _shape(one_chip, (slots, h, p, n), f32),
        _shape(one_chip, (slots, h), f32), _shape(one_chip, (h,), f32),
        _shape(one_chip, (slots, h, p), bf16),
        _shape(one_chip, (slots, h, n), bf16),
        _shape(one_chip, (slots, h, n), bf16),
        _shape(one_chip, (slots,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # in place: the state's buffer is the first output's (no 1.6 GB copy)
    assert "input_output_alias" in text


@pytest.mark.parametrize("rows", [768, 12288])  # a decode step; a prefill
def test_the_expert_grouped_matmuls_compile_for_v5e(one_chip, rows):
    from dynamo_tpu.ops.grouped_matmul import expert_gmm

    held, hidden, width = 64, 2688, 1856
    bf16 = jnp.bfloat16
    sizes = _shape(one_chip, (held,), jnp.int32)
    for k, transpose in ((hidden, True), (width, False)):
        compiled = expert_gmm.lower(
            _shape(one_chip, (rows, k), bf16),
            _shape(one_chip, (held, width, hidden), bf16), sizes,
            path="pallas", transpose_rhs=transpose).compile()
        assert "tpu_custom_call" in compiled.as_text()


# mellum2-12b-a2.5b at its published widths (4 kv heads, 8 query heads a
# kv head, bf16 pools, 64 rows): the window layers' kernel over their
# page group's 72-column table, the full layers' over the widest and
# narrowest tables the cell's contexts reach, and the SwiGLU experts'
# grouped matmuls (a fused [gate | up] of 2 x 896 rows over hidden 2304,
# 64 held, 8 of 64 a token) at a decode step's and a prefill launch's rows.
MELLUM_ROWS = 64


@pytest.mark.parametrize("width", [72, 32, 512])
def test_the_window_and_full_decode_kernels_compile_for_mellum(one_chip,
                                                               width):
    from dynamo_tpu.ops.paged_attention import (
        paged_decode_attention_pool,
        paged_decode_attention_window,
    )

    windowed = width == 72
    layers, n_pages = (6, 5120) if windowed else (2, 32768)
    args = [
        _shape(one_chip, (MELLUM_ROWS, 32, HEAD_DIM), jnp.bfloat16),
        _shape(one_chip, (layers, 2, n_pages, PAGE, 4, HEAD_DIM),
               jnp.bfloat16),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (MELLUM_ROWS, width), jnp.int32),
        _shape(one_chip, (MELLUM_ROWS,), jnp.int32),
    ]
    if windowed:
        text = paged_decode_attention_window.lower(
            *args, _shape(one_chip, (MELLUM_ROWS,), jnp.int32)
        ).compile().as_text()
        assert "paged_decode_attention_window" in text
    else:
        text = paged_decode_attention_pool.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("window", [0, 1024])
def test_mellums_prefill_attention_copies_no_pool(one_chip, window):
    """`models/hybrid.prefill_attention` at the cell's widest launch
    gathers its pages straight from the page group's whole cache: inside
    its `lax.map` body and the full layers' `lax.switch` branches a
    `cache[layer, 0][tables]` was a copy of the layer's K and V pools
    first (1.07 GB a full layer a block of queries: PERF.md, PR 36)."""
    import functools

    from dynamo_tpu.models.hybrid import prefill_attention

    layers, n_pages, width = (6, 5120, 200) if window else (2, 32768, 512)
    pool = 2 * n_pages * PAGE * 4 * HEAD_DIM * 2  # one layer's K and V
    compiled = jax.jit(functools.partial(
        prefill_attention, layer=layers - 1, window=window)).lower(
        _shape(one_chip, (1, 2048, 32, HEAD_DIM), jnp.bfloat16),
        _shape(one_chip, (layers, 2, n_pages, PAGE, 4, HEAD_DIM),
               jnp.bfloat16),
        block_tables=_shape(one_chip, (1, width), jnp.int32),
        positions=_shape(one_chip, (1, 2048), jnp.int32),
        kv_lens=_shape(one_chip, (1,), jnp.int32)).compile()
    assert "slice_bitcast_fusion" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < pool


@pytest.mark.parametrize("rows", [512, 16384])
def test_the_swiglu_expert_grouped_matmuls_compile_for_v5e(one_chip, rows):
    from dynamo_tpu.ops.grouped_matmul import expert_gmm

    held, hidden, width = 64, 2304, 896
    bf16 = jnp.bfloat16
    sizes = _shape(one_chip, (held,), jnp.int32)
    for k, n, transpose in ((hidden, 2 * width, True),
                            (width, width, False)):
        compiled = expert_gmm.lower(
            _shape(one_chip, (rows, k), bf16),
            _shape(one_chip, (held, n, hidden), bf16), sizes,
            path="pallas", transpose_rhs=transpose).compile()
        assert "tpu_custom_call" in compiled.as_text()


# mistral-7b's five int4 projections (K, N): wq/wo, wk/wv, gate/up,
# down, the head.
Q4_PROJECTIONS = {"wq-wo": (4096, 4096), "wk-wv": (4096, 1024),
                  "gate-up": (4096, 14336), "down": (14336, 4096),
                  "head": (4096, 32768)}


@pytest.mark.parametrize("name", sorted(Q4_PROJECTIONS))
def test_the_q4_matmul_with_a_row_block_map_compiles_for_v5e(one_chip, name):
    """A [4, 1024] prefill launch's matmul with its `live` map, and a
    32-row decode step's without one, under the kernel's own name (the
    trace and `breakdown.device_ops` find it by that)."""
    from dynamo_tpu.ops.q4_linear import BLOCK_M, q4_matmul

    k, n = Q4_PROJECTIONS[name]
    weight = [_shape(one_chip, (k // 2, n), jnp.uint8),
              _shape(one_chip, (k // 256, n), jnp.float32),
              _shape(one_chip, (k // 256, n), jnp.float32)]
    prefill = jax.jit(
        lambda x, q4, s, z, live: q4_matmul(x, q4, s, z, live=live)).lower(
        _shape(one_chip, (4096, k), jnp.bfloat16), *weight,
        _shape(one_chip, (4096 // BLOCK_M,), jnp.int32)).compile().as_text()
    decode = q4_matmul.lower(
        _shape(one_chip, (32, k), jnp.bfloat16), *weight).compile().as_text()
    for text in (prefill, decode):
        assert "tpu_custom_call" in text and "q4_matmul" in text
    # the map is an operand of the prefill call alone
    assert "s32[16]" in prefill and "s32[" not in decode


def _copies(text, at_least, ops=("copy", "copy-start"), scope=None):
    """Result shapes, of `at_least` bytes or more, of the `copy` and
    `copy-start` instructions (or `ops`) in an optimised HLO text; with
    `scope`, of those whose metadata names it."""
    op = "|".join(re.escape(o) for o in ops)
    found = []
    for line in text.splitlines():
        result = re.match(
            rf"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) (?:{op})\(", line)
        if not result or (scope and scope not in line):
            continue
        for bits, dims in re.findall(r"\b[a-z]+(\d+)\[([\d,]+)\]",
                                     result.group(1)):
            size = math.prod(int(d) for d in dims.split(","))
            if size * max(int(bits), 8) // 8 >= at_least:
                found.append(line.strip()[:120])
    return found


def _unfused(text):
    """An optimised HLO text less its fused computations: the entry and
    the loop bodies a scan leaves, whose every result is an array in
    memory (inside a fusion a reshape or a transpose is free)."""
    kept, fused = [], False
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            fused = "fused_computation" in line.split("(", 1)[0]
        if not fused:
            kept.append(line)
    return "\n".join(kept)


def _assert_the_experts_combine_is_two_passes(text, t, k, h, layers):
    """Under the `moe_experts` scope of a compiled program, a layer
    makes ONE more array the size of the down-projection's float32 rows
    [t*k, h]: their gather, slots major (a fusion). No `[t, k, h]`
    relaid, no pass that masks the sorted rows (`broadcast_select_
    fusion`), nothing that size or larger copied, reshaped or
    transposed: `ops/grouped_matmul.dropless_experts`."""
    program = _unfused(text)
    assert _copies(program, t * k * h * 4, scope="moe_experts",
                   ops=("copy", "copy-start", "reshape", "transpose")) == []
    assert not re.search(rf"f32\[{t},{k},{h}\]", program)
    made = []
    for line in program.splitlines():
        found = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+?)[.\d]* = (.*?) ([\w\-]+)\(", line)
        if not found or "moe_experts" not in line:
            continue
        name, result, op = found.groups()
        if op != "bitcast" and any(
                math.prod(int(d) for d in dims.split(",")) == t * k * h
                for dims in re.findall(r"\bf32\[([\d,]+)\]", result)):
            made.append((name, op))
    # the grouped matmul's rows are padded to a tile of 128: another size
    gmm = [("gmm", "custom-call")] * (layers if t * k % 128 == 0 else 0)
    assert sorted(made) == sorted(gmm + [("fusion", "fusion")] * layers)


# (tokens, hidden, k, held experts, expert width, SwiGLU): a launch of
# the lfm2, nemotron and granite cells. 4, 6 and 10 slots are no whole
# tile of 8 sublanes; 8 (mellum, pangu) is, and compiles to the same two
# passes inside `test_pangus_step_programs_fit_the_chip_and_copy_no_pool`.
COMBINE_CASES = {
    "k4-lfm2": (2048, 2048, 4, 32, 1792, True),
    "k6-nemotron": (4096, 2688, 6, 64, 1856, False),
    "k10-granite": (2048, 4096, 10, 36, 768, True),
}


@pytest.mark.parametrize("case", sorted(COMBINE_CASES))
def test_the_experts_combine_is_one_gather_and_one_sum(one_chip, case):
    """`dropless_experts` alone at a cell's launch: behind the second
    grouped matmul the entry holds the gather of its float32 rows, slots
    major, and one fusion that masks, weighs and sums them; nowhere a
    `[T, k, h]` array, whose k slots the TPU would pad to 8 or 16
    sublanes by copying it whole (k = 10: 1.6 times its size)."""
    from dynamo_tpu.models.hybrid import _relu2, _swiglu
    from dynamo_tpu.ops.grouped_matmul import dropless_experts

    t, h, k, held, width, swiglu = COMBINE_CASES[case]

    def experts(x, weights, topi, valid, w_up, w_down):
        with jax.named_scope("moe_experts"):
            return dropless_experts(
                x, weights, topi, valid, w_up, w_down, (0, held),
                _swiglu if swiglu else _relu2, path="pallas")

    bf16 = jnp.bfloat16
    text = jax.jit(experts).lower(
        _shape(one_chip, (t, h), bf16), _shape(one_chip, (t, k), jnp.float32),
        _shape(one_chip, (t, k), jnp.int32), _shape(one_chip, (t,), jnp.bool_),
        _shape(one_chip, (held, width * (2 if swiglu else 1), h), bf16),
        _shape(one_chip, (held, width, h), bf16)).compile().as_text()
    _assert_the_experts_combine_is_two_passes(text, t, k, h, 1)
    assert re.search(rf"f32\[{k},{t},{h}\]\S* bitcast\(", text)


@pytest.mark.parametrize("pool", ["int8", "bf16"])
@pytest.mark.parametrize("block", [8, 1])
def test_the_dense_decode_programs_copy_no_pool(one_chip, monkeypatch,
                                                block, pool):
    """The flagship cell's decode programs (mistral-7b, int4 weights,
    5120 pages of 16, 32 rows, 64-page tables; 4 of its 32 layers) as
    `ModelRunner._build_decode_multi` (a `lax.scan` of 8 steps) and
    `_build_decode` (one step) build them. The pool is donated and
    written in place: no instruction copies or relays out an array of
    the scale array's size, in the loop or at the entry. Indexed on
    (layer, k|v, page, offset) the scales' scatter took the layout
    {4,0,3,2,1} and every step copied the whole array out of and back
    into the row-major one the kernel reads: 3 copies and 0.18 GB of
    temporaries here, 1.34 GB a copy at 32 layers (PERF.md, PR 37)."""
    import functools

    import dynamo_tpu.ops.q4_linear as q4_linear
    from dynamo_tpu.engine.sampler import sample
    from dynamo_tpu.models.config import cut_config, get_config
    from dynamo_tpu.models.quantize import quantize_params_int4
    from dynamo_tpu.models.transformer import (
        forward_decode,
        init_params,
        make_kv_cache,
        make_kv_cache_int8,
    )
    from dynamo_tpu.ops.paged_attention import paged_attention_decode_pool

    # the process's backend is the CPU; the program is the chip's
    monkeypatch.setattr(q4_linear, "kernel_path", lambda option: "pallas")
    cfg = cut_config(get_config("mistral-7b"), layers=LAYERS)
    n_pages, width = 5120, 64
    scale_bytes = LAYERS * 2 * n_pages * PAGE * LANES * 2

    def on_chip(tree):
        return jax.tree.map(
            lambda x: _shape(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(lambda: quantize_params_int4(
        init_params(jax.random.PRNGKey(0), cfg), cfg)))
    kv = on_chip(jax.eval_shape(
        lambda: make_kv_cache_int8(cfg, n_pages, PAGE) if pool == "int8"
        else make_kv_cache(cfg, n_pages, PAGE)))
    attention = functools.partial(paged_attention_decode_pool,
                                  interpret=False)

    def step(params, kv, tokens, positions, tables, kv_lens, active,
             temperature, top_p, top_k, seeds, step_idx):
        def one(kv, toks, pos, lens, sidx):
            kv, logits = forward_decode(
                params, cfg, toks, pos, kv, tables, lens, active,
                decode_attention_fn=attention)
            return kv, sample(logits[:, 0, :], temperature, top_p, top_k,
                              seeds, sidx)

        if block == 1:
            return one(kv, tokens, positions, kv_lens, step_idx)

        def body(carry, _):
            kv, toks, pos, lens, sidx = carry
            kv, nxt = one(kv, toks, pos, lens, sidx)
            return (kv, nxt, pos + 1, lens + 1, sidx + 1), nxt

        (kv, *_), toks = jax.lax.scan(
            body, (kv, tokens, positions, kv_lens, step_idx), None,
            length=block)
        return kv, toks

    def rows(dtype):
        return _shape(one_chip, (ROWS,), dtype)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, kv, rows(jnp.int32), rows(jnp.int32),
        _shape(one_chip, (ROWS, width), jnp.int32), rows(jnp.int32),
        rows(jnp.bool_), rows(jnp.float32), rows(jnp.float32),
        rows(jnp.int32), rows(jnp.uint32), rows(jnp.int32)).compile()
    text = compiled.as_text()
    assert "paged_decode_attention_pool" in text and "q4_matmul" in text
    assert _copies(text, scale_bytes) == []
    assert compiled.memory_analysis().temp_size_in_bytes < scale_bytes


# openpangu-ultra-moe-718b as the benchmark's cell cuts it (1 dense + 4
# expert blocks, experts 0:16, 19,200 vocabulary rows; every width as
# published): 128 rows, 24,576 pages of 16 in ONE latent stack of 640-lane
# rows, 384-page tables.
PANGU = {"rows": 128, "pages": 24576, "width": 384}


def _pangu_programs(one_chip):
    """(config, params, caches) as shapes on the described chip."""
    from dynamo_tpu.models.config import cut_config, get_config
    from dynamo_tpu.models.hybrid import make_state_cache
    from dynamo_tpu.models.transformer import init_params, make_kv_cache

    cfg = cut_config(get_config("openpangu-ultra-moe-718b"), 5, "0:16",
                     19200)

    def on_chip(tree):
        return jax.tree.map(
            lambda x: _shape(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    kv = on_chip(jax.eval_shape(
        lambda: make_kv_cache(cfg, PANGU["pages"], PAGE)))
    state = on_chip(jax.eval_shape(
        lambda: make_state_cache(cfg, PANGU["rows"])))
    return cfg, params, (kv, state)


@pytest.mark.parametrize("program", ["decode-block", "prefill-1x2048"])
def test_pangus_step_programs_fit_the_chip_and_copy_no_pool(one_chip,
                                                            program):
    """The fused 8-step decode block at the widest table and the widest
    prefill launch at the cell's sizes, as `ModelRunner` builds them,
    compile for a described v5e: the Mosaic latent kernel is in the
    decode program, nothing copies or relays out an array of the pool's
    size (indexed on (layer, page, offset) the stacked latent write gave
    the donated pool a layout with the layer dimension next to the
    lanes: a 3.75 GB copy, and the program did not fit), and weights
    (9.84 GB) + pool (2.52 GB) + the program's temporaries stay under
    the 15.75 GiB the compiler gives a v5e."""
    import functools

    from dynamo_tpu.engine.sampler import sample, sample_with_logprobs
    from dynamo_tpu.models.hybrid import (
        forward_hybrid,
        forward_hybrid_decode,
        moe_stats_size,
    )
    from dynamo_tpu.ops.paged_attention import (
        paged_attention_decode_latent,
        paged_attention_latent,
    )

    cfg, params, cache = _pangu_programs(one_chip)
    n, width = PANGU["rows"], PANGU["width"]
    pool_bytes = 5 * PANGU["pages"] * PAGE * 640 * 2
    assert cache[0].shape == (5, 1, PANGU["pages"], PAGE, 1, 640)
    attention = functools.partial(paged_attention_decode_latent,
                                  interpret=False)

    def decode(params, cache, tokens, positions, tables, kv_lens, active,
               temperature, top_p, top_k, seeds, step_idx):
        def body(carry, _):
            (kv, state), toks, pos, lens, sidx, acc = carry
            kv, state, logits, stats = forward_hybrid_decode(
                params, cfg, toks, pos, kv, state, tables, lens, active,
                decode_attention_fn=attention, gmm_path="pallas")
            nxt = sample(logits[:, 0, :], temperature, top_p, top_k, seeds,
                         sidx)
            return ((kv, state), nxt, pos + 1, lens + 1, sidx + 1,
                    acc + stats), nxt

        (cache, *_, acc), toks = jax.lax.scan(
            body, (cache, tokens, positions, kv_lens, step_idx,
                   jnp.zeros(moe_stats_size(cfg), jnp.int32)), None, length=8)
        return cache, toks, acc

    def prefill(params, cache, tokens, positions, tables, kv_lens, valid,
                last_idx, temperature, top_p, top_k, seeds, slots):
        kv, state = cache
        kv, state, last, stats = forward_hybrid(
            params, cfg, tokens, positions, kv, state, slots, tables,
            kv_lens, valid, last_idx, gmm_path="pallas",
            attention_fn=functools.partial(paged_attention_latent,
                                           interpret=False))
        return ((kv, state), *sample_with_logprobs(
            last, temperature, top_p, top_k, seeds, jnp.int32(0)), stats)

    def vec(rows, dtype):
        return _shape(one_chip, (rows,), dtype)

    if program == "decode-block":
        compiled = jax.jit(decode, donate_argnums=(1,)).lower(
            params, cache, vec(n, jnp.int32), vec(n, jnp.int32),
            _shape(one_chip, (n, width), jnp.int32), vec(n, jnp.int32),
            vec(n, jnp.bool_), vec(n, jnp.float32), vec(n, jnp.float32),
            vec(n, jnp.int32), vec(n, jnp.uint32),
            vec(n, jnp.int32)).compile()
        assert "paged_decode_attention_latent" in compiled.as_text()
    else:
        def chunk(dtype):
            return _shape(one_chip, (1, 2048), dtype)

        compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
            params, cache, chunk(jnp.int32), chunk(jnp.int32),
            _shape(one_chip, (1, width), jnp.int32), vec(1, jnp.int32),
            chunk(jnp.bool_), vec(1, jnp.int32), vec(1, jnp.float32),
            vec(1, jnp.float32), vec(1, jnp.int32), vec(1, jnp.uint32),
            vec(1, jnp.int32)).compile()
        # a latent layer's prefill is the latent pool's own kernel, five
        # calls of it, and the float32 scores and accumulator of the XLA
        # form ([1, 128 heads, 2048, 128 keys | values]: 134 MB each,
        # written and read a key block) are in no array of the program
        assert len(re.findall(
            r" custom-call\(.*paged_prefill_attention_latent",
            compiled.as_text())) == 5
        assert not re.search(r"f32\[(1,)?128,2048,128\]", compiled.as_text())
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert "tpu_custom_call" in text  # the experts' grouped matmul at least
    assert "paged_prefill_attention_pool" not in text
    assert _copies(text, pool_bytes // 5) == []  # not even one layer's
    _assert_the_experts_combine_is_two_passes(
        text, n if program == "decode-block" else 2048, 8, 7680, 4)
    # 1.38 GB at [1, 2048] (the experts' float32 rows, not attention)
    assert memory.temp_size_in_bytes < (1.0e9 if program == "decode-block"
                                        else 1.45e9)
    assert memory.argument_size_in_bytes < 12.4e9
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.75 * 2 ** 30)


@pytest.mark.parametrize("width", [8, 384])
def test_the_latent_decode_kernel_compiles_for_v5e(one_chip, width):
    import math

    from dynamo_tpu.ops.paged_attention import paged_decode_attention_latent

    compiled = paged_decode_attention_latent.lower(
        _shape(one_chip, (PANGU["rows"], 128, 640), jnp.bfloat16),
        _shape(one_chip, (5, 1, PANGU["pages"], PAGE, 1, 640), jnp.bfloat16),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (PANGU["rows"], width), jnp.int32),
        _shape(one_chip, (PANGU["rows"],), jnp.int32),
        rank=512, sm_scale=1 / math.sqrt(192)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the pool is read in place: no row-major copy in front of the call
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# sha256 of the latent decode kernel's normalised lowered text
# (`tools.lowered_text.normalise`: locations stripped, the Mosaic body as
# the digest of its assembly) at the cell's shapes, by table width, as
# PR 51 lowers it: the kernel that walks a row's pages by the row (a grid
# over rows, one wait a block, a chunk's live blocks in one straight
# line). PR 50 pinned the text of its parent (60a4137) here while it
# moved the flash update into a helper the prefill kernel shares; PR 51
# is the one that moved the kernel, and re-took both. A PR that edits
# `_latent_flash_update` or `_latent_decode_kernel` for another kernel's
# sake must leave these as they are.
LATENT_DECODE_TEXT = {
    8: "25986d0cecd839fe29087d897c043fc45a9e2ea76bad4de1403f2f87b110b96b",
    384: "aff12820dc049c37e9bfc2bbdf1e562acee2bcd2b5a31de1018b1065c50d7e77",
}


@pytest.mark.parametrize("width", sorted(LATENT_DECODE_TEXT))
def test_the_latent_decode_kernel_lowers_to_the_text_it_had(one_chip, width):
    import hashlib

    from dynamo_tpu.ops.paged_attention import paged_decode_attention_latent
    from tools.lowered_text import normalise

    text = normalise(paged_decode_attention_latent.lower(
        _shape(one_chip, (PANGU["rows"], 128, 640), jnp.bfloat16),
        _shape(one_chip, (5, 1, PANGU["pages"], PAGE, 1, 640), jnp.bfloat16),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (PANGU["rows"], width), jnp.int32),
        _shape(one_chip, (PANGU["rows"],), jnp.int32),
        rank=512, sm_scale=1 / math.sqrt(192)).as_text())
    assert "mosaic:" in text  # the kernel's body, as its digest
    assert hashlib.sha256(text.encode()).hexdigest() == LATENT_DECODE_TEXT[
        width]


@pytest.mark.parametrize("rows,t", [(1, 2048), (2, 1024), (8, 256)])
def test_the_latent_prefill_kernel_compiles_for_v5e(one_chip, rows, t):
    """`paged_prefill_attention_latent` at the pangu cell's widest
    launches (rows x bucket = 2,048 positions over 384-page tables; 128
    heads of 128 + 64 query lanes and 128 value lanes, rows of 512 + 128
    lanes): Mosaic takes the geometry the rule admits (query blocks of
    at most 512 positions, chunks of 512 keys), the pool is read in
    place, and the only temporaries are the kernel's own."""
    from dynamo_tpu.ops.paged_attention import (
        latent_prefill_tiles,
        paged_prefill_attention_latent,
    )

    assert latent_prefill_tiles(t, 128, 128, 512, 640, PAGE, PANGU["width"],
                                jnp.bfloat16) == (min(t, 512), 512)
    compiled = paged_prefill_attention_latent.lower(
        _shape(one_chip, (rows, 128, t, 256), jnp.bfloat16),
        _shape(one_chip, (128, 128, 512), jnp.bfloat16),
        _shape(one_chip, (128, 512, 128), jnp.bfloat16),
        _shape(one_chip, (5, 1, PANGU["pages"], PAGE, 1, 640), jnp.bfloat16),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (rows, PANGU["width"]), jnp.int32),
        _shape(one_chip, (rows,), jnp.int32),
        _shape(one_chip, (rows,), jnp.int32),
        sm_scale=1 / math.sqrt(192)).compile()
    assert "paged_prefill_attention_latent" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# `paged_prefill_attention_pool` (query rows a launch, positions a row, kv
# heads, group, int8 pool, table width): the flagship cell's widest and
# narrowest launches, the hybrid's attention layers (32 heads over 2,
# bf16) at its shortest bucket, a bf16 pool of 8 kv heads.
PREFILL_CASES = {
    "q8-4x1024-w64": (4, 1024, 8, 4, True, 64),
    "q8-8x128-w64": (8, 128, 8, 4, True, 64),
    "bf16-g16-8x128-w64": (8, 128, 2, 16, False, 64),
    "bf16-2x1024-w64": (2, 1024, 8, 4, False, 64),
}


@pytest.mark.parametrize("case", sorted(PREFILL_CASES))
def test_the_prefill_attention_kernel_compiles_for_v5e(one_chip, case):
    from dynamo_tpu.ops.paged_attention import (
        paged_prefill_attention_pool,
        prefill_kernel_tiles,
    )

    rows, t, kh, g, quantized, width = PREFILL_CASES[case]
    n_pages = 5120
    pool_dtype = jnp.int8 if quantized else jnp.bfloat16
    assert prefill_kernel_tiles(t, kh * g, kh, HEAD_DIM, PAGE, width,
                                pool_dtype, LANES if quantized else None)
    args = [
        _shape(one_chip, (rows, t, kh * g, HEAD_DIM), jnp.bfloat16),
        _shape(one_chip, (LAYERS, 2, n_pages, PAGE, kh, HEAD_DIM),
               pool_dtype),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (rows, width), jnp.int32),
        _shape(one_chip, (rows,), jnp.int32),
        _shape(one_chip, (rows,), jnp.int32),
    ]
    if quantized:
        args.append(_shape(one_chip, (LAYERS, 2, n_pages, PAGE, LANES),
                           jnp.bfloat16))
    compiled = paged_prefill_attention_pool.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "paged_prefill_attention_pool" in text
    # a device trace tells it from the decode kernels by its name's head
    assert not re.search(r"paged_decode_attention\w* = ", text)
    # the pool is read in place: nothing the size of a layer's K or V
    assert _copies(text, n_pages * PAGE * kh * HEAD_DIM) == []


# `paged_prefill_attention_window` at the mellum cell's launches (rows x
# bucket within 2,048 positions, the bucket's window table:
# `ModelRunner.window_prefill_width`): 32 heads over 4, window 1,024, the
# window group's 6 layers of 5,120 pages.
WINDOW_PREFILL_CASES = {
    "1x2048-w208": (1, 2048, 208),
    "2x1024-w144": (2, 1024, 144),
    "4x512-w112": (4, 512, 112),
    "1x512-w112": (1, 512, 112),
}


@pytest.mark.parametrize("case", sorted(WINDOW_PREFILL_CASES))
def test_the_window_prefill_kernel_compiles_for_mellums_launches(one_chip,
                                                                 case):
    from dynamo_tpu.ops.paged_attention import (
        paged_prefill_attention_window,
        prefill_kernel_tiles,
    )

    rows, t, width = WINDOW_PREFILL_CASES[case]
    n_pages, kh, g = 5120, 4, 8
    assert prefill_kernel_tiles(t, kh * g, kh, HEAD_DIM, PAGE, width,
                                jnp.bfloat16) == (128, 256)
    compiled = paged_prefill_attention_window.lower(
        _shape(one_chip, (rows, t, kh * g, HEAD_DIM), jnp.bfloat16),
        _shape(one_chip, (6, 2, n_pages, PAGE, kh, HEAD_DIM), jnp.bfloat16),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (rows, width), jnp.int32),
        _shape(one_chip, (rows,), jnp.int32),
        _shape(one_chip, (rows,), jnp.int32), window=1024).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # a name of its own: a breakdown lists the window layers' kernel apart
    # from the full layers', and the decode kernels' roofline reads neither
    assert "paged_prefill_attention_window" in text
    assert "paged_prefill_attention_pool" not in text
    assert not re.search(r"paged_decode_attention\w* = ", text)
    assert _copies(text, n_pages * PAGE * kh * HEAD_DIM) == []


# command-a-plus-05-2026 as the `command-a-plus-ep8` cell serves it: 128
# query heads over 8 kv heads of 128 (16 queries a kv head, a group no
# other cell's kernels had seen at 8 kv heads), window 4,096, 32 rows; the
# window group's 3 layers of 9,280 pages under a 264-column decode table
# (a row holds 258) and the bucket's window + chunk prefill table, the
# full group's 1 layer of 24,576 pages under tables of 8 to 768 columns.
COMMAND_A = {"rows": 32, "qh": 128, "kh": 8, "window": 4096,
             "window_pages": 9280, "pages": 24576}
COMMAND_A_CASES = {
    "decode-window-w264": ("decode", True, 264),
    "decode-full-w8": ("decode", False, 8),
    "decode-full-w768": ("decode", False, 768),
    "prefill-window-1x2048-w400": ("prefill", True, (1, 2048, 400)),
    "prefill-window-4x512-w304": ("prefill", True, (4, 512, 304)),
    "prefill-full-1x2048-w768": ("prefill", False, (1, 2048, 768)),
    "prefill-full-2x1024-w768": ("prefill", False, (2, 1024, 768)),
}


@pytest.mark.parametrize("case", sorted(COMMAND_A_CASES))
def test_the_attention_kernels_compile_at_command_a_plus_shapes(one_chip,
                                                                case):
    """No new kernel came with the parallel block; the four that exist
    run at shapes none had seen. Mosaic takes them all (VMEM decides, and
    does: 64 query positions x 16 queries a kv head = 1,024 rows a block
    against 256-key chunks)."""
    from dynamo_tpu.ops.paged_attention import (
        paged_decode_attention_pool,
        paged_decode_attention_window,
        paged_prefill_attention_pool,
        paged_prefill_attention_window,
        prefill_kernel_tiles,
    )

    phase, windowed, shape = COMMAND_A_CASES[case]
    z = COMMAND_A
    layers, n_pages = ((3, z["window_pages"]) if windowed
                       else (1, z["pages"]))
    pool = _shape(one_chip, (layers, 2, n_pages, PAGE, z["kh"], HEAD_DIM),
                  jnp.bfloat16)
    layer = _shape(one_chip, (), jnp.int32)
    if phase == "decode":
        n = z["rows"]
        args = [_shape(one_chip, (n, z["qh"], HEAD_DIM), jnp.bfloat16), pool,
                layer, _shape(one_chip, (n, shape), jnp.int32),
                _shape(one_chip, (n,), jnp.int32)]
        if windowed:
            lowered = paged_decode_attention_window.lower(
                *args, _shape(one_chip, (n,), jnp.int32))
        else:
            lowered = paged_decode_attention_pool.lower(*args)
        name = "paged_decode_attention_" + ("window" if windowed else "pool")
    else:
        rows, t, width = shape
        assert prefill_kernel_tiles(t, z["qh"], z["kh"], HEAD_DIM, PAGE,
                                    width, jnp.bfloat16) == (64, 256)
        args = [_shape(one_chip, (rows, t, z["qh"], HEAD_DIM), jnp.bfloat16),
                pool, layer, _shape(one_chip, (rows, width), jnp.int32),
                _shape(one_chip, (rows,), jnp.int32),
                _shape(one_chip, (rows,), jnp.int32)]
        if windowed:
            lowered = paged_prefill_attention_window.lower(
                *args, window=z["window"])
        else:
            lowered = paged_prefill_attention_pool.lower(*args)
        name = "paged_prefill_attention_" + ("window" if windowed else "pool")
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text and name in text
    # the pool is read in place: nothing the size of a layer's K or V
    assert _copies(text, n_pages * PAGE * z["kh"] * HEAD_DIM) == []


def test_without_a_window_the_prefill_kernel_traces_as_before():
    """`window` is a static branch of `_pool_prefill_kernel`: with 0 the
    dense and the hybrid cells' kernel keeps its six scalar-prefetch
    operands, its -inf mask and its comparisons (counted at PR 39's
    kernel; its Mosaic module was compared with the parent's op for op
    when the window came: PERF.md, PR 41), so their programs keep their
    text. With a window: the same operands, a finite mask, the lower
    edge's comparisons."""
    import collections

    from dynamo_tpu.ops.paged_attention import (
        paged_prefill_attention_pool,
        paged_prefill_attention_window,
    )

    shape = jax.ShapeDtypeStruct
    args = [shape((1, 512, 32, HEAD_DIM), jnp.bfloat16),
            shape((2, 2, 64, PAGE, 4, HEAD_DIM), jnp.bfloat16),
            shape((), jnp.int32), shape((1, 112), jnp.int32),
            shape((1,), jnp.int32), shape((1,), jnp.int32)]

    def traced(fn, **kw):
        jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args)
        (call,) = [e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
                   if e.primitive.name == "pallas_call"]
        text = str(jaxpr)
        ops = collections.Counter(
            re.findall(r"= (lt|le|gt|ge|eq|ne|and|min|max)\b", text))
        return call.params["grid_mapping"].num_index_operands, ops, text

    operands, ops, text = traced(paged_prefill_attention_pool)
    assert operands == 6
    assert ops == {"lt": 9, "le": 1, "gt": 1, "eq": 6, "and": 9, "min": 3,
                   "max": 3}
    assert "-inf" in text and "-1e+30" not in text
    operands, win_ops, text = traced(paged_prefill_attention_window,
                                     window=1024)
    assert operands == 6
    assert win_ops["ge"] == 1 and win_ops["gt"] == ops["gt"] + 1
    assert "-1e+30" in text and "-inf" not in text


def test_the_dense_prefill_program_holds_the_kernel_and_no_score_tensor(
        one_chip, monkeypatch):
    """The flagship cell's `[4, 1024]` prefill program (mistral-7b, int4
    weights, int8 pool of 5120 pages, 64-page tables; 2 of its 32
    layers) as `ModelRunner._build_prefill` builds it around the default
    `attention_fn`: attention is the Mosaic kernel, no float32 array of
    rows x positions x heads x table tokens exists (XLA's path held
    `f32[4,1024,8,4,1024]`, 537 MB, written and read four or five times
    a layer: PERF.md, PR 39), no layer's pool is sliced out of the cache
    (`s8[5120,16,8,128]`: ROADMAP A6), and the temporaries are the
    all-position float32 logits (fault 3: 537 MB) and little else."""
    import functools

    import dynamo_tpu.ops.q4_linear as q4_linear
    from dynamo_tpu.engine.sampler import sample_with_logprobs
    from dynamo_tpu.models.config import cut_config, get_config
    from dynamo_tpu.models.quantize import quantize_params_int4
    from dynamo_tpu.models.transformer import (
        forward,
        init_params,
        make_kv_cache_int8,
    )
    from dynamo_tpu.ops.paged_attention import paged_attention

    monkeypatch.setattr(q4_linear, "kernel_path", lambda option: "pallas")
    cfg = cut_config(get_config("mistral-7b"), layers=2)
    rows, t, n_pages, width = 4, 1024, 5120, 64

    def on_chip(tree):
        return jax.tree.map(
            lambda x: _shape(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(lambda: quantize_params_int4(
        init_params(jax.random.PRNGKey(0), cfg), cfg)))
    kv = on_chip(jax.eval_shape(
        lambda: make_kv_cache_int8(cfg, n_pages, PAGE)))
    attention = functools.partial(paged_attention, interpret=False)

    def step(params, kv, tokens, positions, tables, kv_lens, valid,
             last_idx, temperature, top_p, top_k, seeds):
        kv, logits = forward(params, cfg, tokens, positions, kv, tables,
                             kv_lens, valid=valid, attention_fn=attention)
        last = jnp.take_along_axis(
            logits, last_idx[:, None, None], axis=1)[:, 0, :]
        return (kv, *sample_with_logprobs(
            last, temperature, top_p, top_k, seeds, jnp.int32(0)))

    def vec(dtype):
        return _shape(one_chip, (rows,), dtype)

    def chunk(dtype):
        return _shape(one_chip, (rows, t), dtype)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, kv, chunk(jnp.int32), chunk(jnp.int32),
        _shape(one_chip, (rows, width), jnp.int32), vec(jnp.int32),
        chunk(jnp.bool_), vec(jnp.int32), vec(jnp.float32),
        vec(jnp.float32), vec(jnp.int32), vec(jnp.uint32)).compile()
    text = compiled.as_text()
    assert "paged_prefill_attention_pool" in text and "q4_matmul" in text
    scores = rows * t * cfg.n_q_heads * width * PAGE
    for dims in re.findall(r"\bf32\[([\d,]+)\]", text):
        shape = tuple(int(d) for d in dims.split(","))
        # the logits have as many elements: 32 heads x 1,024 keys
        assert (math.prod(shape) < scores
                or shape[-1] == cfg.vocab_size), shape
    assert "slice_bitcast_fusion" not in text
    assert f"s8[{n_pages},{PAGE},{cfg.n_kv_heads},{HEAD_DIM}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.9e9


def _lowered_hybrid_prefill(one_chip, name, layers, experts, rows, t,
                            window_pages=0, pages=1024, width=64,
                            window_width=128, **paths):
    """`forward_hybrid` for a cut of a preset, lowered for the described
    chip around the default `attention_fn` (`.as_text()`: a kernel call
    is in the StableHLO or it is not; `.compile()` for what XLA makes of
    the rest)."""
    import functools

    from dynamo_tpu.models.config import cut_config, get_config
    from dynamo_tpu.models.hybrid import forward_hybrid, make_state_cache
    from dynamo_tpu.models.transformer import init_params, make_kv_cache
    from dynamo_tpu.ops.paged_attention import (
        paged_attention,
        paged_attention_latent,
    )

    cfg = cut_config(get_config(name), layers, experts)
    attention = (paged_attention_latent if cfg.has_latent_layers
                 else paged_attention)

    def on_chip(make):
        return jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype),
                            jax.eval_shape(make))

    params = on_chip(lambda: init_params(jax.random.PRNGKey(0), cfg))
    kv = on_chip(lambda: make_kv_cache(cfg, pages, PAGE))
    state = on_chip(lambda: make_state_cache(cfg, rows))
    window = None
    if window_pages:
        window = (on_chip(lambda: make_kv_cache(cfg, window_pages, PAGE,
                                                group="window")),
                  _shape(one_chip, (rows, window_width), jnp.int32),
                  _shape(one_chip, (rows,), jnp.int32))

    def prefill(params, kv, state, tokens, positions, tables, kv_lens,
                valid, last_idx, slots, window):
        return forward_hybrid(
            params, cfg, tokens, positions, kv, state, slots, tables,
            kv_lens, valid, last_idx, window=window,
            attention_fn=functools.partial(attention, interpret=False),
            **paths)

    def vec(dtype):
        return _shape(one_chip, (rows,), dtype)

    def chunk(dtype):
        return _shape(one_chip, (rows, t), dtype)

    return cfg, jax.jit(prefill).lower(
        params, kv, state, chunk(jnp.int32), chunk(jnp.int32),
        _shape(one_chip, (rows, width), jnp.int32), vec(jnp.int32),
        chunk(jnp.bool_), vec(jnp.int32), vec(jnp.int32), window)


def test_a_hybrid_stacks_full_attention_layers_call_the_prefill_kernel(
        one_chip):
    cfg, lowered = _lowered_hybrid_prefill(
        one_chip, "nemotron3-nano-30b-a3b", 13, "0:8", rows=8, t=128)
    assert cfg.layer_pattern.count("*") == 2
    text = lowered.as_text()
    assert "paged_prefill_attention_pool" in text
    assert "paged_prefill_attention_window" not in text


def test_nemotrons_widest_launch_scans_in_the_kernel_and_relays_nothing(
        one_chip):
    """The hybrid cell's `[8, 512]` launch (all 14 layers, experts 0:64):
    its six Mamba mixers call the chunked-scan kernel once each, with 8
    heads a group a grid step, and nothing of 32 MB or more is copied,
    reshaped or transposed in a mixer's scope. Two things beside the
    kernel make that so (PERF.md, PR 43): `in_proj`'s 10,304 columns are
    not whole lane tiles, and projected as one XLA lays the product out
    with positions minor and relays z (33.6 MB) and xbc (50.3 MB) for
    the conv and the kernel: [z | xbc] and dt are projected apart; and
    the gated norm's 8 groups as a [.., 8, 512] reshape left a 67 MB
    float32 broadcast of the scales and a relaid copy of its input: the
    groups are normed as lane slices."""
    cfg, lowered = _lowered_hybrid_prefill(
        one_chip, "nemotron3-nano-30b-a3b", 14, "0:64", rows=8, t=512,
        ssm_path="pallas", gmm_path="pallas")
    assert cfg.layer_pattern.count("M") == 6
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(re.findall(r" custom-call\(.*ssm_chunk_scan", text)) == 6
    assert _relaid_in_mixers(text, 32 << 20) == []
    # 0.82 GB, as with the XLA form of the scan (0.79): the experts' rows
    assert compiled.memory_analysis().temp_size_in_bytes < 0.85e9


@pytest.mark.parametrize("stack", ["mellum", "pangu"])
def test_a_stack_without_mamba_mixers_lowers_to_the_text_it_had(one_chip,
                                                                stack):
    """`forward_hybrid`'s `ssm_path` reaches Mamba mixers only: the
    mellum and pangu cells' prefill programs lower to the same text
    whatever it says (their cells' programs are PR 42's)."""
    name, layers, kw = {
        "mellum": ("mellum2-12b-a2.5b", 4,
                   dict(window_pages=512, pages=1024, width=64,
                        window_width=80)),
        "pangu": ("openpangu-ultra-moe-718b", 3, {}),
    }[stack]
    texts = []
    for paths in ({}, {"ssm_path": "pallas"}, {"ssm_path": "xla"}):
        cfg, lowered = _lowered_hybrid_prefill(
            one_chip, name, layers, None, rows=1, t=512, **kw, **paths)
        assert "M" not in cfg.layer_pattern
        texts.append(lowered.as_text())
    assert "ssm_chunk_scan" not in texts[0]
    assert texts[0] == texts[1] == texts[2]


def test_a_stack_with_window_layers_runs_both_page_groups_through_the_kernel(
        one_chip):
    """The mellum cell's widest prefill launch (`[1, 2048]`, 512-page
    tables over the full group's 32,768 pages, the window group's
    208-column table over 5,120; one period of the pattern, all 64
    experts): the window layers call `paged_prefill_attention_window`,
    the full layer `paged_prefill_attention_pool`, and no float32 array
    of a block of positions x heads x keys is left of
    `models/hybrid.prefill_attention` (`f32[1,512,4,8,1552]` a window
    layer, up to `f32[1,512,4,8,8192]` a full one, each written and read
    five or six times: PERF.md, PR 41), nor its `lax.map` (a `while`)
    and four-way `lax.switch`. What is left of the temporaries is the
    experts' (`[2048 x 8, 1792]` float32 gate|up and its neighbours):
    as large as the scores were, so the launch's memory does not fall."""
    cfg, lowered = _lowered_hybrid_prefill(
        one_chip, "mellum2-12b-a2.5b", 4, None, rows=1, t=2048,
        window_pages=5120, pages=32768, width=512, window_width=208)
    assert cfg.layer_pattern == "WEWEWE*E"
    text = lowered.as_text()
    assert text.count("paged_prefill_attention_window") >= 3
    assert "paged_prefill_attention_pool" in text
    assert "stablehlo.case" not in text
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert not re.search(r"paged_decode_attention\w* = ", hlo)
    for dims in re.findall(r"\bf32\[([\d,]+)\]", hlo):
        shape = tuple(int(d) for d in dims.split(","))
        # by heads: nothing the size of a query block's scores over the
        # fewest keys the XLA form gathered (the experts' float32 rows
        # are [positions x 8, width])
        assert len(shape) < 4 or math.prod(shape) < 512 * 32 * 1024, shape
    # 0.63 GB, and 0.62 GB with the XLA form: the experts' rows are as
    # large as its scores were, and XLA had given both one buffer
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7e9


# granite-4.0-h-small as the `granite4-h-small-ep2` cell serves it (one
# period of ten blocks = 20 mixers, experts 0:36, 50,176 rows of the tied
# matrix; every width as published): 48 rows, 24,576 pages of 16, 512-page
# tables; 128 Mamba heads of 64 in ONE group, state 128; 36 SwiGLU experts
# 768 wide over hidden 4096; scores at 1/128.
GRANITE = {"rows": 48, "pages": 24576, "width": 512}


def test_granites_state_update_and_grouped_matmuls_compile_for_v5e(one_chip):
    """The decode state update at 128 heads (a row's block is 4.19 MB of
    float32, in and out double-buffered: twice nemotron's) in place, and
    the experts' grouped matmuls at a decode step's and a 2,048-token
    launch's rows: contraction 4096 over the fused [gate | up] of 1536
    rows and 768 over 4096 (`_tiling`: a [k, 512] slab for both; the
    768-deep down-projection is one k tile)."""
    from dynamo_tpu.ops.grouped_matmul import _tiling, expert_gmm
    from dynamo_tpu.ops.ssm import ssm_state_update

    slots, h, p, n = GRANITE["rows"], 128, 64, 128
    f32, bf16 = jnp.float32, jnp.bfloat16
    compiled = ssm_state_update.lower(
        _shape(one_chip, (slots, h, p, n), f32),
        _shape(one_chip, (slots, h), f32), _shape(one_chip, (h,), f32),
        _shape(one_chip, (slots, h, p), bf16),
        _shape(one_chip, (slots, h, n), bf16),
        _shape(one_chip, (slots, h, n), bf16),
        _shape(one_chip, (slots,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "input_output_alias" in text
    held, hidden, width = 36, 4096, 768
    assert _tiling(hidden, 2 * width) == (128, hidden, 512)
    assert _tiling(width, hidden) == (128, width, 512)
    sizes = _shape(one_chip, (held,), jnp.int32)
    for rows in (512, 20480):  # 48 x 10 and 2,048 x 10 slots, padded
        for k, n_out, weights, transpose in (
                (hidden, 2 * width, (held, 2 * width, hidden), True),
                (width, hidden, (held, width, hidden), False)):
            compiled = expert_gmm.lower(
                _shape(one_chip, (rows, k), bf16),
                _shape(one_chip, weights, bf16), sizes,
                path="pallas", transpose_rhs=transpose).compile()
            assert "tpu_custom_call" in compiled.as_text()


def test_a_stated_score_scale_is_a_constant_of_the_same_kernels():
    """`sm_scale` is static: handed the default (1/sqrt(head_dim)) the
    pool decode kernel and the blocked prefill kernel trace to the very
    text they trace to without it (every accepted cell's programs keep
    theirs), and handed granite's 1/128 to the same text but for that
    constant."""
    from dynamo_tpu.ops.paged_attention import (
        paged_decode_attention_pool,
        paged_prefill_attention_pool,
    )

    shape = jax.ShapeDtypeStruct
    bf16, i32 = jnp.bfloat16, jnp.int32
    cases = {
        paged_decode_attention_pool: [
            shape((4, 32, HEAD_DIM), bf16),
            shape((2, 2, 64, PAGE, 8, HEAD_DIM), bf16), shape((), i32),
            shape((4, 16), i32), shape((4,), i32)],
        paged_prefill_attention_pool: [
            shape((1, 512, 32, HEAD_DIM), bf16),
            shape((2, 2, 64, PAGE, 8, HEAD_DIM), bf16), shape((), i32),
            shape((1, 112), i32), shape((1,), i32), shape((1,), i32)],
    }
    for fn, args in cases.items():
        def text(**kw):
            return str(jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args))

        plain = text()
        assert text(sm_scale=1.0 / math.sqrt(HEAD_DIM)) == plain
        stated = text(sm_scale=1.0 / 128)
        assert stated != plain
        assert stated.replace("0.0078125", "C") == re.sub(
            r"0\.08838834\d*", "C", plain)


def _granite_programs(one_chip):
    """(config, params, caches) as shapes on the described chip."""
    from dynamo_tpu.models.config import cut_config, get_config
    from dynamo_tpu.models.hybrid import make_state_cache
    from dynamo_tpu.models.transformer import init_params, make_kv_cache

    cfg = cut_config(get_config("granite-4.0-h-small"), 10, "0:36", 50176)

    def on_chip(tree):
        return jax.tree.map(
            lambda x: _shape(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    kv = on_chip(jax.eval_shape(
        lambda: make_kv_cache(cfg, GRANITE["pages"], PAGE)))
    state = on_chip(jax.eval_shape(
        lambda: make_state_cache(cfg, GRANITE["rows"])))
    return cfg, params, (kv, state)


def _relaid_in_mixers(text, at_least):
    """Results of `at_least` bytes or more that a program copies,
    reshapes or transposes, outside fusions, inside a Mamba mixer's
    scope: what a relaid `x`, `y` or state would be (inside a fusion a
    reshape is free)."""
    return _copies(_unfused(text), at_least,
                   ops=("copy", "copy-start", "reshape", "transpose"),
                   scope="mamba_mixer")


@pytest.mark.parametrize("program", ["decode-block", "prefill-1x2048",
                                     "prefill-4x512"])
def test_granites_step_programs_fit_the_chip_and_copy_nothing_large(
        one_chip, program):
    """The fused 8-step decode block at the widest table and the widest
    prefill launch at the cell's sizes, as `ModelRunner` builds them,
    compile for a described v5e with 48 slots: the tied head contracts
    the embedding's own [50176, 4096] array (no 0.41 GB transpose or
    copy of it in the step, nor of the pool or of a layer's state), the
    state kernel, the pool decode kernel at the model's score scale and
    the grouped matmul are in the decode program, the blocked prefill
    kernel in the launch, and weights (9.51 GB) + state (1.83 GB) + pool
    (1.61 GB) + the program's temporaries stay under the 15.75 GiB the
    compiler gives a v5e. The nine Mamba mixers of a launch call the
    chunked-scan kernel once each and nothing of 32 MB or more is copied,
    reshaped or transposed in a mixer's scope: `x`, `y` and the state
    reach and leave the kernel as the projection, the conv and the cache
    hold them (the XLA form relaid `x` to heads-before-positions and `y`
    back, 33.6 and 67.1 MB each, several times a mixer: PERF.md, PR 43).
    A launch's temporaries are the experts' float32 rows twice, as the
    down-projection wrote them and gathered slots-major ([10 x 2048,
    4096], 335.5 MB each): 0.86 GB, where a `[2048, 10, 4096]` relaid to
    16 sublanes and a masked copy of the sorted rows held 1.06
    (PERF.md, PR 46)."""
    import functools

    from dynamo_tpu.engine.sampler import sample, sample_with_logprobs
    from dynamo_tpu.models.hybrid import (
        forward_hybrid,
        forward_hybrid_decode,
        moe_stats_size,
    )
    from dynamo_tpu.ops.paged_attention import (
        paged_attention,
        paged_attention_decode_pool,
    )

    cfg, params, cache = _granite_programs(one_chip)
    n, width = GRANITE["rows"], GRANITE["width"]
    assert cache[0].shape == (1, 2, GRANITE["pages"], PAGE, 8, HEAD_DIM)
    assert len(cache[1]["ssm"]) == 9
    assert cache[1]["ssm"][0].shape == (n, 128, 64, 128)
    assert "lm_head" not in params
    tied_bytes = 50176 * 4096 * 2

    def decode(params, cache, tokens, positions, tables, kv_lens, active,
               temperature, top_p, top_k, seeds, step_idx):
        def body(carry, _):
            (kv, state), toks, pos, lens, sidx, acc = carry
            kv, state, logits, stats = forward_hybrid_decode(
                params, cfg, toks, pos, kv, state, tables, lens, active,
                decode_attention_fn=functools.partial(
                    paged_attention_decode_pool, interpret=False),
                ssm_path="pallas", gmm_path="pallas")
            nxt = sample(logits[:, 0, :], temperature, top_p, top_k, seeds,
                         sidx)
            return ((kv, state), nxt, pos + 1, lens + 1, sidx + 1,
                    acc + stats), nxt

        (cache, *_, acc), toks = jax.lax.scan(
            body, (cache, tokens, positions, kv_lens, step_idx,
                   jnp.zeros(moe_stats_size(cfg), jnp.int32)), None, length=8)
        return cache, toks, acc

    def prefill(params, cache, tokens, positions, tables, kv_lens, valid,
                last_idx, temperature, top_p, top_k, seeds, slots):
        kv, state = cache
        kv, state, last, stats = forward_hybrid(
            params, cfg, tokens, positions, kv, state, slots, tables,
            kv_lens, valid, last_idx, gmm_path="pallas", ssm_path="pallas",
            attention_fn=functools.partial(paged_attention,
                                           interpret=False))
        return ((kv, state), *sample_with_logprobs(
            last, temperature, top_p, top_k, seeds, jnp.int32(0)), stats)

    def vec(rows, dtype):
        return _shape(one_chip, (rows,), dtype)

    if program == "decode-block":
        compiled = jax.jit(decode, donate_argnums=(1,)).lower(
            params, cache, vec(n, jnp.int32), vec(n, jnp.int32),
            _shape(one_chip, (n, width), jnp.int32), vec(n, jnp.int32),
            vec(n, jnp.bool_), vec(n, jnp.float32), vec(n, jnp.float32),
            vec(n, jnp.int32), vec(n, jnp.uint32),
            vec(n, jnp.int32)).compile()
        text = compiled.as_text()
        for kernel in ("ssm_state_update", "paged_decode_attention_pool"):
            assert kernel in text, kernel
    else:
        rows, t = (1, 2048) if program == "prefill-1x2048" else (4, 512)

        def chunk(dtype):
            return _shape(one_chip, (rows, t), dtype)

        compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
            params, cache, chunk(jnp.int32), chunk(jnp.int32),
            _shape(one_chip, (rows, width), jnp.int32),
            vec(rows, jnp.int32), chunk(jnp.bool_), vec(rows, jnp.int32),
            vec(rows, jnp.float32), vec(rows, jnp.float32),
            vec(rows, jnp.int32), vec(rows, jnp.uint32),
            vec(rows, jnp.int32)).compile()
        text = compiled.as_text()
        assert "paged_prefill_attention_pool" in text
        assert len(re.findall(r" custom-call\(.*ssm_chunk_scan", text)) == 9
        assert "ssm_state_update" not in text
        assert _relaid_in_mixers(text, 32 << 20) == []
    _assert_the_experts_combine_is_two_passes(
        text, n if program == "decode-block" else rows * t, 10, 4096, 10)
    memory = compiled.memory_analysis()
    assert "tpu_custom_call" in text and "tied_head" in text
    # nothing the size of the tied matrix, a layer's state or the pool
    # is copied or transposed (`_copies` reads `copy`; a transpose of
    # the embedding would be a bf16[4096,50176] result)
    assert _copies(text, min(tied_bytes, n * 128 * 64 * 128 * 4) // 2) == []
    assert not re.search(r"bf16\[4096,50176\]", text)
    # 0.864 GB at [1, 2048] and 0.908 at [4, 512] (1.06 and 1.11 with
    # the combine token-major, 1.33 and 1.10 with the XLA form of the
    # scan before that: what is left is the experts')
    assert memory.temp_size_in_bytes < (0.3e9 if program == "decode-block"
                                        else 0.92e9)
    assert 12.9e9 < memory.argument_size_in_bytes < 13.0e9
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.75 * 2 ** 30)


# lfm2-8b-a1b's first pipeline stage as the benchmark's cell serves it
# (12 of 24 blocks: 9 conv and 3 attention mixers at head_dim 64, both
# dense blocks, 10 expert layers of 32; 256 slots, 192-page tables).
LFM2 = {"rows": 256, "width": 192, "pages": 49152}


@pytest.mark.parametrize("width", [8, 192])
def test_the_pool_decode_kernel_compiles_at_head_dim_64(one_chip, width):
    """A pool of [.., 16, 8, 64] pages is refused by Mosaic ("Slice shape
    along dimension 5 must be aligned to tiling (128), but is 64": the
    TPU pads such a pool to 128 lanes, twice its bytes); stored [.., 16,
    4, 128], two kv heads a lane tile, the same kernel compiles through
    `paged_attention_decode_pool` at 256 rows, 4 queries a kv head, and
    the narrowest and widest tables the cell serves."""
    import functools

    from dynamo_tpu.ops.paged_attention import (
        paged_attention_decode_pool,
        paged_decode_attention_pool,
    )

    n = LFM2["rows"]
    q = _shape(one_chip, (n, 1, 32, 64), jnp.bfloat16)
    cur = _shape(one_chip, (n, 1, 8, 64), jnp.bfloat16)
    tables = _shape(one_chip, (n, width), jnp.int32)
    lens = _shape(one_chip, (n,), jnp.int32)
    packed = _shape(one_chip, (3, 2, LFM2["pages"], PAGE, 4, 128),
                    jnp.bfloat16)
    compiled = jax.jit(functools.partial(
        paged_attention_decode_pool, interpret=False)).lower(
            q, packed, _shape(one_chip, (), jnp.int32), tables, lens, cur,
            cur).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if width == 8:  # what the unpacked pool meets, in the compiler's words
        unpacked = _shape(one_chip, (3, 2, 1025, PAGE, 8, 64), jnp.bfloat16)
        with pytest.raises(Exception, match="aligned to tiling"):
            paged_decode_attention_pool.lower(
                _shape(one_chip, (n, 32, 64), jnp.bfloat16), unpacked,
                _shape(one_chip, (), jnp.int32), tables, lens).compile()


def _lfm2_programs(one_chip):
    """(config, params, caches) as shapes on the described chip."""
    from dynamo_tpu.models.config import cut_config, get_config
    from dynamo_tpu.models.hybrid import make_state_cache
    from dynamo_tpu.models.transformer import init_params, make_kv_cache

    cfg = cut_config(get_config("lfm2-8b-a1b"), 12)

    def on_chip(tree):
        return jax.tree.map(
            lambda x: _shape(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    kv = on_chip(jax.eval_shape(
        lambda: make_kv_cache(cfg, LFM2["pages"], PAGE)))
    state = on_chip(jax.eval_shape(
        lambda: make_state_cache(cfg, LFM2["rows"])))
    return cfg, params, (kv, state)


@pytest.mark.parametrize("program", ["decode-block", "prefill-1x2048",
                                     "prefill-4x512"])
def test_lfm2s_step_programs_fit_the_chip_and_copy_nothing_large(
        one_chip, program):
    """The fused 8-step decode block at 256 rows and the widest table,
    and the widest prefill launches, as `ModelRunner` builds them,
    compile for a described v5e: the pool ([3, 2, 49152, 16, 4, 128]:
    4.83 GB, not the 9.66 an unpacked head_dim 64 would be padded to),
    the conv carries (18.9 MB) and the weights (7.86 GB) are
    12.71 GB of arguments, and with the program's temporaries stay under
    the 15.75 GiB the compiler gives a v5e; the tied head contracts the
    embedding's own [65536, 2048] array; the pool decode kernel and the
    grouped matmul are in the decode program (the conv mixers are XLA's
    in both); a prefill launch holds no Pallas attention kernel (head_dim
    64: the blocked XLA form) and copies neither the pool nor the tied
    matrix."""
    import functools

    from dynamo_tpu.engine.sampler import sample, sample_with_logprobs
    from dynamo_tpu.models.hybrid import (
        forward_hybrid,
        forward_hybrid_decode,
        moe_stats_size,
    )
    from dynamo_tpu.ops.paged_attention import (
        paged_attention,
        paged_attention_decode_pool,
    )

    cfg, params, cache = _lfm2_programs(one_chip)
    n, width = LFM2["rows"], LFM2["width"]
    assert cache[0].shape == (3, 2, LFM2["pages"], PAGE, 4, 128)
    assert [c.shape for c in cache[1]["conv"]] == [(n, 2, 2048)] * 9
    assert cache[1]["ssm"] == [] and "lm_head" not in params
    tied_bytes = 65536 * 2048 * 2

    def decode(params, cache, tokens, positions, tables, kv_lens, active,
               temperature, top_p, top_k, seeds, step_idx):
        def body(carry, _):
            (kv, state), toks, pos, lens, sidx, acc = carry
            kv, state, logits, stats = forward_hybrid_decode(
                params, cfg, toks, pos, kv, state, tables, lens, active,
                decode_attention_fn=functools.partial(
                    paged_attention_decode_pool, interpret=False),
                ssm_path="pallas", gmm_path="pallas")
            nxt = sample(logits[:, 0, :], temperature, top_p, top_k, seeds,
                         sidx)
            return ((kv, state), nxt, pos + 1, lens + 1, sidx + 1,
                    acc + stats), nxt

        (cache, *_, acc), toks = jax.lax.scan(
            body, (cache, tokens, positions, kv_lens, step_idx,
                   jnp.zeros(moe_stats_size(cfg), jnp.int32)), None, length=8)
        return cache, toks, acc

    def prefill(params, cache, tokens, positions, tables, kv_lens, valid,
                last_idx, temperature, top_p, top_k, seeds, slots):
        kv, state = cache
        kv, state, last, stats = forward_hybrid(
            params, cfg, tokens, positions, kv, state, slots, tables,
            kv_lens, valid, last_idx, gmm_path="pallas", ssm_path="pallas",
            attention_fn=functools.partial(paged_attention,
                                           interpret=False))
        return ((kv, state), *sample_with_logprobs(
            last, temperature, top_p, top_k, seeds, jnp.int32(0)), stats)

    def vec(rows, dtype):
        return _shape(one_chip, (rows,), dtype)

    if program == "decode-block":
        compiled = jax.jit(decode, donate_argnums=(1,)).lower(
            params, cache, vec(n, jnp.int32), vec(n, jnp.int32),
            _shape(one_chip, (n, width), jnp.int32), vec(n, jnp.int32),
            vec(n, jnp.bool_), vec(n, jnp.float32), vec(n, jnp.float32),
            vec(n, jnp.int32), vec(n, jnp.uint32),
            vec(n, jnp.int32)).compile()
        text = compiled.as_text()
        assert "paged_decode_attention_pool" in text
    else:
        rows, t = (1, 2048) if program == "prefill-1x2048" else (4, 512)

        def chunk(dtype):
            return _shape(one_chip, (rows, t), dtype)

        compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
            params, cache, chunk(jnp.int32), chunk(jnp.int32),
            _shape(one_chip, (rows, width), jnp.int32),
            vec(rows, jnp.int32), chunk(jnp.bool_), vec(rows, jnp.int32),
            vec(rows, jnp.float32), vec(rows, jnp.float32),
            vec(rows, jnp.int32), vec(rows, jnp.uint32),
            vec(rows, jnp.int32)).compile()
        text = compiled.as_text()
        # the kernels' names, not their stem: the text's table of source
        # files may hold tests/test_paged_prefill_attention.py
        assert "paged_prefill_attention_pool" not in text
        assert "paged_prefill_attention_window" not in text
    _assert_the_experts_combine_is_two_passes(
        text, n if program == "decode-block" else rows * t, 4, 2048, 10)
    memory = compiled.memory_analysis()
    assert "tpu_custom_call" in text and "tied_head" in text
    # nothing the size of the tied matrix or a layer's pool is copied
    assert _copies(text, tied_bytes // 2) == []
    assert not re.search(r"bf16\[2048,65536\]", text)
    assert 12.70e9 < memory.argument_size_in_bytes < 12.72e9
    # 0.23 GB the fused block, 0.32 GB at [1, 2048], 0.88 GB at [4, 512]
    # (the experts' float32 rows and the blocked scores)
    assert memory.temp_size_in_bytes < (0.3e9 if program == "decode-block"
                                        else 1.0e9)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.75 * 2 ** 30)


# -- the adapter between ModelRunner's programs and a stack (PR 47) -----------

SEAM = {
    "dense": ("tiny-test", {}),
    "state": ("tiny-hybrid-test", {}),
    "window": ("tiny-mellum-test", {"window_pages": 16}),
}


@pytest.mark.parametrize("stack", sorted(SEAM))
def test_the_runners_programs_are_the_forwards_called_directly(one_chip,
                                                               stack):
    """A batched prefill program and a fused decode block as
    `ModelRunner`'s builders make them (the cache one `(pools, state)`
    pytree, the tables a tuple, `slots` always passed, the model behind
    its adapter) lower to the text of the stack's forward called
    directly with the arrays unpacked as the programs took them before
    PR 47: the adapter adds no parameter, no copy and no operation, and
    a dense program's unread `slots` is not in it."""
    from dynamo_tpu.engine import ModelRunner, PrefillRow, RunnerConfig
    from dynamo_tpu.engine.model_runner import IDLE_WINDOW
    from dynamo_tpu.engine.sampler import sample, sample_with_logprobs
    from dynamo_tpu.models import get_config
    from dynamo_tpu.models.hybrid import (
        forward_hybrid,
        forward_hybrid_decode,
        moe_stats_size,
    )
    from dynamo_tpu.models.transformer import forward, forward_decode
    from dynamo_tpu.parallel import MeshConfig, make_mesh
    from tools.lowered_text import lowering, normalise

    preset, serve = SEAM[stack]
    cfg = get_config(preset)
    hybrid = bool(cfg.layer_pattern)
    (device,) = one_chip.device_set
    b, width, block = 4, 8, 2
    with lowering(device) as built:
        runner = ModelRunner(
            cfg, RunnerConfig(page_size=16, num_pages=32, max_batch=b,
                              max_pages_per_seq=width,
                              prefill_buckets=(16, 32), **serve),
            make_mesh(MeshConfig(), [device]))
        row = PrefillRow(np.zeros(9, np.int32), 0, np.zeros(width, np.int32),
                         9, (0.0, 1.0, 0, 0), window=IDLE_WINDOW)
        runner.prefill_chunk_batch([row, row])
        zeros = np.zeros(b, np.int32)
        runner.decode_multi(
            zeros, zeros, runner._idle_tables(b, width), zeros,
            np.zeros(b, bool), np.zeros(b, np.float32),
            np.ones(b, np.float32), zeros, np.zeros(b, np.uint32), k=block,
            return_device=True)
    rep, (pool_shardings, _) = runner._rep, runner._cache_sharding
    windowed = len(pool_shardings) == 2

    def unpacked(cache):
        """The cache as the programs took it before: the pool alone, or
        (pool, state) for a hybrid stack, a window group riding the pool
        as a pair."""
        pools, state = cache
        kv = pools if windowed else pools[0]
        return (kv, state) if hybrid else kv

    kv_sharding = unpacked(runner._cache_sharding)
    if hybrid:
        kv_sharding = (kv_sharding[0], rep)

    def model(params, cache, window, call):
        """One forward over the unpacked cache; `call(kv, state,
        window)` -> (kv, state, logits, stats)."""
        kv, state = cache if hybrid else (cache, None)
        if windowed:
            kv, win = kv
            window = (win, *window)
        kv, state, logits, stats = call(kv, state, window or None)
        return ((kv, state) if hybrid else kv), logits, stats

    def step(params, cache, tokens, positions, table, kv_lens, valid,
             last_idx, temperature, top_p, top_k, seeds, slots=None,
             *window):
        def call(kv, state, window):
            if hybrid:
                return forward_hybrid(
                    params, cfg, tokens, positions, kv, state, slots, table,
                    kv_lens, valid, last_idx, window=window)
            kv, logits = forward(params, cfg, tokens, positions, kv, table,
                                 kv_lens, valid=valid)
            return kv, None, jnp.take_along_axis(
                logits, last_idx[:, None, None], axis=1)[:, 0, :], None

        cache, last, stats = model(params, cache, window, call)
        sampled = sample_with_logprobs(last, temperature, top_p, top_k,
                                       seeds, jnp.int32(0))
        return (cache, *sampled, stats) if hybrid else (cache, *sampled)

    def multi(params, cache, tokens, positions, table, *rest):
        *window, kv_lens, active, temperature, top_p, top_k, seeds, \
            step_idx = rest

        def body(carry, _):
            cache, toks, pos, lens, sidx, acc = carry

            def call(kv, state, window):
                if hybrid:
                    return forward_hybrid_decode(
                        params, cfg, toks, pos, kv, state, table, lens,
                        active, window=window)
                kv, logits = forward_decode(params, cfg, toks, pos, kv,
                                            table, lens, active)
                return kv, None, logits, None

            cache, logits, stats = model(params, cache, tuple(window), call)
            nxt = sample(logits[:, 0, :], temperature, top_p, top_k, seeds,
                         sidx)
            acc = acc + stats if hybrid else acc
            return (cache, nxt, pos + 1, lens + 1, sidx + 1, acc), nxt

        acc0 = jnp.zeros(moe_stats_size(cfg), jnp.int32) if hybrid else None
        (cache, *_, acc), toks_k = jax.lax.scan(
            body, (cache, tokens, positions, kv_lens, step_idx, acc0), None,
            length=block)
        return (cache, toks_k, acc) if hybrid else (cache, toks_k)

    for name, direct, n_out in (("step", step, 4), ("multi", multi, 1)):
        (params, cache, *args), kwargs = built.calls[name]
        leaves = [*jax.tree.leaves(args), *jax.tree.leaves(kwargs)]
        if not hybrid and name == "step":
            leaves.pop()  # `slots`: a dense program does not read it
        outs = (kv_sharding, *[rep] * n_out, *([rep] if hybrid else []))
        text = normalise(jax.jit(
            direct, donate_argnums=(1,), out_shardings=outs).lower(
                params, unpacked(cache), *leaves).as_text())
        assert text == built[name], (stack, name)


def test_the_lowering_tool_compiles_the_programs_its_regex_finds(one_chip):
    """`tools.lowered_text.lowering(compile_keys=...)` (the tool's
    `--compile`): a program whose key the regex finds is compiled for the
    described chip too, and what the compiler says of it is kept: the
    bytes it takes and its optimised HLO. The others are lowered only."""
    from dynamo_tpu.engine import ModelRunner, PrefillRow, RunnerConfig
    from dynamo_tpu.engine.model_runner import IDLE_WINDOW
    from dynamo_tpu.models import get_config
    from dynamo_tpu.parallel import MeshConfig, make_mesh
    from tools.lowered_text import lowering

    (device,) = one_chip.device_set
    with lowering(device, compile_keys="^step$") as built:
        runner = ModelRunner(
            get_config("tiny-cohere2-test"),
            RunnerConfig(page_size=16, num_pages=32, max_batch=4,
                         max_pages_per_seq=8, prefill_buckets=(16,),
                         window_pages=16),
            make_mesh(MeshConfig(), [device]))
        row = PrefillRow(np.zeros(9, np.int32), 0, np.zeros(8, np.int32), 9,
                         (0.0, 1.0, 0, 0), window=IDLE_WINDOW)
        runner.prefill_chunk_batch([row, row])
    assert "step" in built and set(built.memory) == {"step"}
    assert "argument_size_in_bytes" in built.memory["step"]
    assert "HloModule" in built.compiled["step"]


# phi4-mini-flash-reasoning as the `phi4-mini-flash` cell serves it: the
# whole model (7.71 GB of bf16 weights), 64 rows; differential attention
# handed to the kernels a ROW of five KV pairs wide (64 query rows, 40 of
# them real, over 2 rows of 640 lanes, scale 1/8: 10 pairs of 128 lanes
# would be padded to 16 and Mosaic refuses a page's slice of 10); the
# full group's ONE layer of 35,856 pages under
# tables of 8 to 560 columns, the window group's 8 layers of 3,200 pages
# (window 512: a row holds 34).
PHI4 = {"rows": 64, "pages": 35856, "window_pages": 3200, "width": 560}


def _phi4_programs(one_chip):
    """(config, params, caches) as shapes on the described chip."""
    from dynamo_tpu.models.config import get_config
    from dynamo_tpu.models.hybrid import make_state_cache
    from dynamo_tpu.models.transformer import init_params, make_kv_cache

    cfg = get_config("phi4-mini-flash-reasoning")

    def on_chip(tree):
        return jax.tree.map(
            lambda x: _shape(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    pools = tuple(on_chip(jax.eval_shape(
        lambda group=group, pages=pages: make_kv_cache(
            cfg, pages, PAGE, group=group)))
        for group, pages in (("full", PHI4["pages"]),
                             ("window", PHI4["window_pages"])))
    state = on_chip(jax.eval_shape(
        lambda: make_state_cache(cfg, PHI4["rows"])))
    return cfg, params, (pools, state)


@pytest.mark.parametrize("program", ["decode-block", "prefill-1x2048"])
def test_phi4flashs_step_programs_fit_the_chip_and_copy_no_pool(one_chip,
                                                                program):
    """The fused 4-step decode block at the widest table and the widest
    prefill launch at the cell's sizes, as `HybridSteps` builds them,
    compile for a described v5e: sixteen attention layers through the
    accepted head_dim-128 kernels (nine of them in prefill: the tail's
    seven cross-attention layers read one position a row through the
    DECODE kernel), the rolled sections as loops, nothing the size of a
    pool's layer copied, and weights (7.71 GB) + pools (2.94 + 2.10 GB)
    + state (0.21 GB) + the program's temporaries under the 15.75 GiB
    the compiler gives a v5e."""
    import functools

    from dynamo_tpu.engine.sampler import sample, sample_with_logprobs
    from dynamo_tpu.models.hybrid import HybridSteps
    from dynamo_tpu.ops.paged_attention import (
        paged_attention,
        paged_attention_decode_pool,
    )

    cfg, params, cache = _phi4_programs(one_chip)
    # the kernels a one-chip mesh hands the stack (`_mesh_kernels`)
    steps = HybridSteps(cfg, {
        "prefill": functools.partial(paged_attention, interpret=False),
        "decode": functools.partial(paged_attention_decode_pool,
                                    interpret=False)})
    n, width = PHI4["rows"], PHI4["width"]
    (full, window), state = cache
    assert full.shape == (1, 2, PHI4["pages"], PAGE, 2, 640)
    assert window.shape == (8, 2, PHI4["window_pages"], PAGE, 2, 640)
    assert state["ssm"][0].shape == (8, n, 16, 5120)  # a rolled section's

    def tables(rows):
        return (_shape(one_chip, (rows, width), jnp.int32),
                _shape(one_chip, (rows, 40), jnp.int32),
                _shape(one_chip, (rows,), jnp.int32))

    def decode(params, cache, tokens, positions, tables, kv_lens, active,
               temperature, top_p, top_k, seeds, step_idx):
        def body(carry, _):
            cache, toks, pos, lens, sidx = carry
            cache, logits, _ = steps.decode(params, cache, toks, pos, tables,
                                            lens, active)
            nxt = sample(logits[:, 0, :], temperature, top_p, top_k, seeds,
                         sidx)
            return (cache, nxt, pos + 1, lens + 1, sidx + 1), nxt

        (cache, *_), toks = jax.lax.scan(
            body, (cache, tokens, positions, kv_lens, step_idx), None,
            length=4)
        return cache, toks

    def prefill(params, cache, tokens, positions, tables, kv_lens, valid,
                last_idx, temperature, top_p, top_k, seeds, slots):
        cache, last, _ = steps.prefill(params, cache, tokens, positions,
                                       tables, kv_lens, valid, last_idx,
                                       slots)
        return (cache, *sample_with_logprobs(
            last, temperature, top_p, top_k, seeds, jnp.int32(0)))

    def vec(rows, dtype):
        return _shape(one_chip, (rows,), dtype)

    if program == "decode-block":
        compiled = jax.jit(decode, donate_argnums=(1,)).lower(
            params, cache, vec(n, jnp.int32), vec(n, jnp.int32), tables(n),
            vec(n, jnp.int32), vec(n, jnp.bool_), vec(n, jnp.float32),
            vec(n, jnp.float32), vec(n, jnp.int32), vec(n, jnp.uint32),
            vec(n, jnp.int32)).compile()
    else:
        def chunk(dtype):
            return _shape(one_chip, (1, 2048), dtype)

        # the window group's prefill table: window + chunk keys in whole
        # key chunks of the kernel (`ModelRunner.window_prefill_width`)
        win = (_shape(one_chip, (1, 176), jnp.int32), vec(1, jnp.int32))
        compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
            params, cache, chunk(jnp.int32), chunk(jnp.int32),
            (_shape(one_chip, (1, width), jnp.int32), *win),
            vec(1, jnp.int32), chunk(jnp.bool_), vec(1, jnp.int32),
            vec(1, jnp.float32), vec(1, jnp.float32), vec(1, jnp.int32),
            vec(1, jnp.uint32), vec(1, jnp.int32)).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    for kernel in ("paged_decode_attention_pool",) + (
            ("paged_decode_attention_window",) if program == "decode-block"
            else ("paged_prefill_attention_pool",
                  "paged_prefill_attention_window")):
        assert kernel in text, kernel
    # no float32 [rows, T, channels, columns] of the scan anywhere
    assert not re.search(r"f32\[(1,)?2048,(16,5120|5120,16)\]", text)
    layer_bytes = PHI4["window_pages"] * PAGE * 2 * 640 * 2
    # nothing of a pool: not even one layer's K and V. (What a prefill
    # launch does copy, 105 and 168 MB an attention layer, are the wide
    # queries and the kernel's output relaid between the kernel's
    # [rows, T, 64, 640] and the epilogue's: left by PR 52, PERF.md 7.)
    assert [c for c in _copies(text, layer_bytes)
            if "16,2,640]" in c] == []
    assert _copies(text, 2 * layer_bytes) == []
    assert memory.argument_size_in_bytes < 13.2e9
    # 0.25 GB the decode block, 0.45 GB the launch (with ONE scatter over
    # the window group's stacked layers the block copied that pool in
    # and out of a layout with the layers next to the lanes: 2.4 GB)
    assert memory.temp_size_in_bytes < 0.6e9
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.75 * 2 ** 30)
