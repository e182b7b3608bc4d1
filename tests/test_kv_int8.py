"""int8 KV cache: quantized pool + per-token-per-head scales.

Halves the decode KV HBM traffic and doubles KV capacity (the reference
gets fp8 KV from its engines' quantized cache modes; BASELINE.md decode-
wall analysis motivates it here). Accuracy oracle: the same forward with
a full-precision cache."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.models import get_config, init_params
from dynamo_tpu.models.transformer import (
    forward,
    forward_decode,
    make_kv_cache,
    make_kv_cache_int8,
    paged_attention_decode_xla,
    quantize_kv,
    write_kv_stack,
)


class TestQuantize:
    def test_roundtrip_error_bound(self):
        rng = np.random.default_rng(0)
        # [B, T, kh, hd]: one scale per (B, T) token, shared across heads,
        # returned lane-broadcast [B, T, 128] in bf16
        x = jnp.asarray(rng.normal(size=(2, 5, 4, 128)) * 3.0, jnp.float32)
        q, s = quantize_kv(x)
        assert q.dtype == jnp.int8 and s.shape == (2, 5, 128)
        assert s.dtype == jnp.bfloat16
        # lane-broadcast rows: every lane carries the same scalar
        s_np = np.asarray(s, np.float32)
        assert (s_np == s_np[..., :1]).all()
        deq = np.asarray(q, np.float32) * s_np[:, :, :1][..., None]
        err = np.abs(deq - np.asarray(x))
        # half an int8 lsb + bf16 scale rounding slack
        bound = s_np[:, :, :1][..., None] * 0.51 + 1e-6
        assert (err <= bound).all()

    def test_zero_rows_stay_zero(self):
        q, s = quantize_kv(jnp.zeros((2, 5, 4, 16)))
        assert np.asarray(q).sum() == 0
        assert np.asarray(s, np.float32).sum() == 0


# write_kv_stack over an int8 pool: 3 layers, 6 pages of 4, 4-page tables.
W_LAYERS, W_PAGES, W_PS, W_KH = 3, 6, 4, 2


def _rows(tables, positions, valid):
    return (np.asarray(tables, np.int32), np.asarray(positions, np.int32),
            np.asarray(valid, bool))


# name -> (block tables [B, 4], positions [B, T], valid [B, T])
WRITE_CASES = {
    "decode-active-and-inactive": _rows(
        [[1, 2, 0, 0], [3, 0, 0, 0], [4, 5, 0, 0]],
        [[5], [2], [7]], [[True], [False], [True]]),
    "chunk-with-padding": _rows(
        [[1, 2, 3, 0], [4, 5, 0, 0]],
        [[2, 3, 4, 5, 6], [0, 1, 2, 3, 4]],
        [[True] * 5, [True, True, True, False, False]]),
    # every row lands on scratch page 0, several on the same offset
    "duplicates-on-page-0": _rows(
        [[0, 0, 0, 0], [0, 0, 0, 0], [1, 0, 0, 0]],
        [[0, 1, 4], [0, 5, 1], [1, 4, 9]],
        [[True, True, True], [True, False, True], [False, True, True]]),
    # a table entry past the pool (6, 40), and one a negative index wraps
    "page-past-the-pool": _rows(
        [[1, 6, 2, 0], [40, -1, -7, 3]],
        [[3, 4, 8], [0, 5, 9]], [[True] * 3, [True] * 3]),
    # the ring write-back: positions run on past a 4-page table's 16
    "position-past-the-table": _rows(
        [[1, 2, 3, 4], [5, 1, 0, 0]],
        [list(range(12, 20)), list(range(8))],
        [[True] * 8, [True] * 5 + [False] * 3]),
    "position-past-the-table-t1": _rows(
        [[1, 2, 3, 4], [5, 0, 0, 0]], [[16], [3]], [[True], [True]]),
}


def _write_inputs(case):
    tables, positions, valid = WRITE_CASES[case]
    rng = np.random.default_rng(sorted(WRITE_CASES).index(case))
    b, t = positions.shape
    shape = (W_LAYERS, b, t, W_KH, 128)
    k = jnp.asarray(rng.normal(size=shape) * 2.0, jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=shape) * 0.5, jnp.bfloat16)
    # a pool that already holds something everywhere, so a write that
    # lands where it should not shows
    values = jnp.asarray(rng.integers(
        -127, 128, (W_LAYERS, 2, W_PAGES, W_PS, W_KH, 128)), jnp.int8)
    scales = jnp.asarray(rng.uniform(
        0.01, 1.0, (W_LAYERS, 2, W_PAGES, W_PS, 128)), jnp.bfloat16)
    return ((values, scales), k, v, jnp.asarray(tables),
            jnp.asarray(positions), jnp.asarray(valid))


def _write_numpy(kv, k, v, tables, positions, valid):
    """Element by element, in row order (a later duplicate wins, as on
    the CPU's scatter): an invalid row goes to page 0, a position past
    the table and a page outside the pool (after numpy's wrap of a
    negative index) are dropped."""
    values, scales = (np.array(a) for a in kv)
    tables, positions, valid = (np.asarray(a) for a in
                                (tables, positions, valid))
    for kv_i, x in enumerate((k, v)):
        q, s = (np.asarray(a) for a in quantize_kv(x))
        for layer in range(q.shape[0]):
            for b in range(q.shape[1]):
                for t in range(q.shape[2]):
                    pos = int(positions[b, t])
                    if not valid[b, t]:
                        page = 0
                    elif pos // W_PS >= tables.shape[1]:
                        continue
                    else:
                        page = int(tables[b, pos // W_PS])
                    if page < 0:
                        page += W_PAGES
                    if not 0 <= page < W_PAGES:
                        continue
                    values[layer, kv_i, page, pos % W_PS] = q[layer, b, t]
                    scales[layer, kv_i, page, pos % W_PS] = s[layer, b, t]
    return values, scales


def _write_indexed(kv, k, v, tables, positions, valid):
    """The write as it stood before PR 37: four scatters, each indexed
    on (layer, k|v, page, offset)."""
    values, scales = kv
    n_layers, b, t = k.shape[:3]
    page = jnp.take_along_axis(tables, positions // W_PS, axis=1)
    pages = jnp.where(valid, page, 0).reshape(-1)
    offs = (positions % W_PS).reshape(-1)
    for kv_i, x in enumerate((k, v)):
        q, s = quantize_kv(x)
        values = values.at[:, kv_i, pages, offs].set(
            q.reshape(n_layers, b * t, *q.shape[3:]), mode="drop")
        scales = scales.at[:, kv_i, pages, offs].set(
            s.reshape(n_layers, b * t, s.shape[-1]), mode="drop")
    return values, scales


class TestWriteKvStackInt8:
    """The scales go in as rows of the array's flat view (the layout the
    decode kernel reads: PERF.md, PR 37); what lands where, and what is
    dropped, is what the indexed write gave, to the bit."""

    @pytest.mark.parametrize("reference", [_write_numpy, _write_indexed],
                             ids=["numpy", "indexed"])
    @pytest.mark.parametrize("case", sorted(WRITE_CASES))
    def test_bit_equal_to(self, case, reference):
        args = _write_inputs(case)
        got_v, got_s = jax.jit(write_kv_stack)(*args)
        want_v, want_s = reference(*args)
        assert got_v.dtype == jnp.int8 and got_s.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(got_v),
                                      np.asarray(want_v))
        np.testing.assert_array_equal(
            np.asarray(got_s).view(np.uint16),
            np.asarray(want_s).view(np.uint16))

    def test_the_cases_write_and_drop(self):
        """The cases are not vacuous: each changes the pool, and the
        drop cases leave rows out."""
        for case in sorted(WRITE_CASES):
            args = _write_inputs(case)
            _, got_s = write_kv_stack(*args)
            assert (np.asarray(got_s) != np.asarray(args[0][1])).any(), case
        args = _write_inputs("position-past-the-table-t1")
        _, got_s = write_kv_stack(*args)
        changed = (np.asarray(got_s) != np.asarray(args[0][1])).any(-1)
        # row 0 (position 16 of a 16-position table) is dropped: only
        # row 1's page 5, offset 3 changes, in every layer's K and V
        assert changed.sum() == W_LAYERS * 2 and changed[:, :, 5, 3].all()


def _fp32_cfg():
    return dataclasses.replace(get_config("tiny-test"), dtype="float32")


def _prefill_both(cfg, n_pages=16, page_size=4, t=12):
    """Populate a plain fp32 cache and an int8 cache with the same chunk;
    returns (tokens, positions, tables, caches...)."""
    rng = np.random.default_rng(3)
    params = init_params(jax.random.PRNGKey(5), cfg)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, t)), jnp.int32)
    positions = jnp.arange(t, dtype=jnp.int32)[None, :]
    tables = jnp.arange(1, n_pages, dtype=jnp.int32)[None, :]
    kv_plain = make_kv_cache(cfg, n_pages, page_size)
    kv_q8 = make_kv_cache_int8(cfg, n_pages, page_size)
    kv_lens = jnp.asarray([t], jnp.int32)
    kv_plain, logits_plain = forward(params, cfg, tokens, positions,
                                     kv_plain, tables, kv_lens)
    kv_q8, logits_q8 = forward(params, cfg, tokens, positions,
                               kv_q8, tables, kv_lens)
    return params, tokens, tables, kv_plain, kv_q8, logits_plain, logits_q8


class TestForwardWithInt8Cache:
    def test_prefill_and_decode_match_fp32_cache(self):
        cfg = _fp32_cfg()
        (params, tokens, tables, kv_plain, kv_q8,
         logits_plain, logits_q8) = _prefill_both(cfg)
        # Prefill logits: in-chunk attention reads the just-written pages;
        # int8 error is bounded by the quantization step.
        np.testing.assert_allclose(np.asarray(logits_q8),
                                   np.asarray(logits_plain),
                                   atol=0.3, rtol=0.08)
        t = tokens.shape[1]
        nxt = jnp.asarray([7], jnp.int32)
        kv_lens = jnp.asarray([t + 1], jnp.int32)
        active = jnp.ones((1,), bool)
        _, dec_plain = forward_decode(params, cfg, nxt,
                                      jnp.asarray([t], jnp.int32),
                                      kv_plain, tables, kv_lens, active)
        _, dec_q8 = forward_decode(params, cfg, nxt,
                                   jnp.asarray([t], jnp.int32),
                                   kv_q8, tables, kv_lens, active)
        np.testing.assert_allclose(np.asarray(dec_q8),
                                   np.asarray(dec_plain),
                                   atol=0.3, rtol=0.08)
        # greedy choice is stable under the quantization noise here
        assert (int(np.argmax(np.asarray(dec_q8)[0, 0]))
                == int(np.argmax(np.asarray(dec_plain)[0, 0])))

    def test_int8_cache_updates_are_tuples(self):
        cfg = _fp32_cfg()
        _params, _tok, _tables, _plain, kv_q8, _a, _b = _prefill_both(cfg)
        assert isinstance(kv_q8, tuple) and len(kv_q8) == 2
        assert kv_q8[0].dtype == jnp.int8
        assert kv_q8[1].dtype == jnp.bfloat16


class TestPoolKernelQ8:
    """float32 queries against an int8 pool: the kernel's operands hold
    the codes and the bf16 scales exactly, Q K^T is summed over codes and
    scaled once per score, so it differs from the XLA dequant oracle by
    float32 rounding alone."""

    TOL = 3e-6

    def _case(self, b=4, qh=8, kh=4, hd=64, ps=8, n_pages=32, max_pages=6,
              seed=5):
        rng = np.random.default_rng(seed)
        L = 2
        kf = jnp.asarray(rng.normal(size=(L, 2, n_pages, ps, kh, hd)),
                         jnp.float32)
        qv, qs = quantize_kv(kf)
        q = jnp.asarray(rng.normal(size=(b, 1, qh, hd)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(b, 1, kh, hd)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(b, 1, kh, hd)), jnp.float32)
        ids = rng.permutation(n_pages - 1)[: b * max_pages] \
            .reshape(b, max_pages)
        bt = jnp.asarray(ids + 1, jnp.int32) % n_pages
        kl = jnp.asarray([1, 13, 47, 30], jnp.int32)
        return q, (qv, qs), bt, kl, kc, vc

    @pytest.mark.parametrize("ppc", [2, 3])
    def test_q8_kernel_matches_xla_dequant(self, ppc):
        from dynamo_tpu.ops.paged_attention import (
            paged_attention_decode_pool,
        )

        q, kv_q8, bt, kl, kc, vc = self._case()
        for layer in (0, 1):
            got = paged_attention_decode_pool(
                q, kv_q8, layer, bt, kl, kc, vc, pages_per_chunk=ppc,
                interpret=True)
            want = paged_attention_decode_xla(q, kv_q8, layer, bt, kl,
                                              kc, vc)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=self.TOL, atol=self.TOL)

    def test_q8_kernel_tp2_matches_oracle(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dynamo_tpu.ops.paged_attention import (
            make_paged_attention_decode_pool_tp,
        )
        from dynamo_tpu.parallel import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(tp=2))
        q, (qv, qs), bt, kl, kc, vc = self._case()
        qv = jax.device_put(qv, NamedSharding(
            mesh, P(None, None, None, None, "tp", None)))
        qs = jax.device_put(qs, NamedSharding(mesh, P()))  # head-shared
        fn = make_paged_attention_decode_pool_tp(mesh, pages_per_chunk=2,
                                                 interpret=True)
        got = fn(q, (qv, qs), 1, bt, kl, kc, vc)
        want = paged_attention_decode_xla(q, (qv, qs), 1, bt, kl, kc, vc)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=self.TOL, atol=self.TOL)


class TestRunnerInt8:
    def _runner(self, kv_dtype):
        from dynamo_tpu.engine.model_runner import ModelRunner, RunnerConfig
        from dynamo_tpu.parallel import MeshConfig, make_mesh

        # int8 requires head_dim == the 128 scale-lane width (flagship
        # geometry); widen the tiny model's heads accordingly.
        cfg = dataclasses.replace(get_config("tiny-test"), head_dim=128)
        return ModelRunner(
            cfg,
            RunnerConfig(page_size=4, num_pages=64, max_batch=2,
                         max_pages_per_seq=16, prefill_buckets=(16, 32),
                         kv_dtype=kv_dtype),
            make_mesh(MeshConfig()),
            seed=0,
        )

    def test_serving_loop_runs_and_matches_bf16_greedy(self):
        rng = np.random.default_rng(1)
        prompt = rng.integers(1, 500, 20).astype(np.int32)
        table = np.zeros(16, np.int32)
        table[:8] = np.arange(1, 9)
        outs = {}
        for dtype in ("model", "int8"):
            r = self._runner(dtype)
            first = r.prefill_chunk(prompt, 0, table, len(prompt),
                                    (0.0, 1.0, 0, 0))
            toks = [first]
            tok = first
            for i in range(6):
                pos = len(prompt) + i
                nxt = r.decode(
                    np.array([tok], np.int32), np.array([pos], np.int32),
                    table[None, :], np.array([pos + 1], np.int32),
                    np.array([True]), np.zeros(1, np.float32),
                    np.ones(1, np.float32), np.zeros(1, np.int32),
                    np.zeros(1, np.uint32), np.array([i], np.int32))
                tok = int(nxt[0])
                toks.append(tok)
            outs[dtype] = toks
        # bf16's own rounding noise is larger than int8-KV quantization
        # noise at this scale; greedy streams agree on the tiny model.
        assert outs["int8"] == outs["model"]

    def test_packed_gather_scatter_roundtrip(self):
        """int8 transfers (r5, VERDICT item 6): the pool's quantized
        blocks travel as PACKED uint8 bytes (values + scale rows) and
        survive a gather -> scatter -> gather roundtrip bit-exactly —
        no dequant/requant drift through the tiers."""
        from dynamo_tpu.block_manager import BlockLayoutSpec

        r = self._runner("int8")
        rng = np.random.default_rng(3)
        prompt = rng.integers(1, 500, 12).astype(np.int32)
        table = np.zeros(16, np.int32)
        table[:4] = np.arange(1, 5)
        r.prefill_chunk(prompt, 0, table, len(prompt), (0.0, 1.0, 0, 0))

        pages = np.array([1, 2, 3], np.int32)
        packed = r.gather_pages(pages)
        assert packed.dtype == np.uint8 and packed.ndim == 2
        spec = BlockLayoutSpec.from_runner_layout(r.kv_layout())
        assert spec.quantized
        assert packed.shape[1] == spec.block_shape[0]
        assert packed.any()  # real bytes, not zeros

        target = np.array([10, 11, 12], np.int32)
        r.scatter_pages(target, packed)
        back = r.gather_pages(target)
        np.testing.assert_array_equal(back, packed)

    def test_kvbm_offload_onboard_int8_e2e(self, tmp_path):
        """Scheduler-level compose (bench_serve --kv-dtype int8
        --kvbm-host-blocks N): blocks offloaded from a quantized pool
        onboard back after the G1 prefix cache is cleared, and the
        greedy completion is unchanged — the int8 and KVBM capacity
        levers no longer exclude each other."""
        import queue as thread_queue
        import uuid

        from dynamo_tpu.block_manager import (
            BlockLayoutSpec,
            KvbmConfig,
            KvBlockManager,
        )
        from dynamo_tpu.engine import InferenceScheduler
        from dynamo_tpu.llm.protocols import (
            PreprocessedRequest,
            SamplingOptions,
            StopConditions,
        )

        runner = self._runner("int8")
        mgr = KvBlockManager(
            KvbmConfig(host_blocks=16, disk_blocks=16,
                       disk_path=str(tmp_path / "g3.bin"),
                       admission=False),
            BlockLayoutSpec.from_runner_layout(runner.kv_layout()))
        sched = InferenceScheduler(runner, kvbm=mgr)
        sched.start()

        def run_one(prompt):
            done = thread_queue.Queue()
            outs = []

            def emit(o):
                outs.append(o)
                if o.finish_reason is not None:
                    done.put(o)

            sched.submit(PreprocessedRequest(
                request_id=uuid.uuid4().hex, token_ids=list(prompt),
                sampling=SamplingOptions(max_tokens=2, temperature=0.0),
                stop=StopConditions(ignore_eos=True)), emit)
            done.get(timeout=120.0)
            return [t for o in outs for t in o.token_ids]

        try:
            prompt = list(range(1, 13))  # 3 blocks of 4
            toks1 = run_one(prompt)
            import time as _t

            deadline = _t.time() + 30.0
            while mgr.stats.offloaded < 2 and _t.time() < deadline:
                mgr.flush(1.0)
                _t.sleep(0.02)
            assert mgr.stats.offloaded >= 2
            sched.run_in_step(sched.pool.clear).get(timeout=30.0)
            toks2 = run_one(prompt)
            assert sched.stats.kvbm_onboarded_blocks >= 2
            assert toks1 == toks2  # onboarded quantized KV == computed
        finally:
            mgr.flush(5.0)
            sched.stop()
            mgr.close()

    def test_bad_kv_dtype_rejected(self):
        from dynamo_tpu.engine.model_runner import ModelRunner, RunnerConfig
        from dynamo_tpu.parallel import MeshConfig, make_mesh

        with pytest.raises(ValueError, match="unknown kv_dtype"):
            ModelRunner(get_config("tiny-test"),
                        RunnerConfig(prefill_buckets=(16,),
                                     kv_dtype="fp8"),
                        make_mesh(MeshConfig()), seed=0)

    def test_narrow_head_dim_rejected(self):
        from dynamo_tpu.engine.model_runner import ModelRunner, RunnerConfig
        from dynamo_tpu.parallel import MeshConfig, make_mesh

        with pytest.raises(ValueError, match="head_dim"):
            ModelRunner(get_config("tiny-test"),  # head_dim=16
                        RunnerConfig(prefill_buckets=(16,),
                                     kv_dtype="int8"),
                        make_mesh(MeshConfig()), seed=0)

    def test_mla_rejected(self):
        from dynamo_tpu.engine.model_runner import ModelRunner, RunnerConfig
        from dynamo_tpu.parallel import MeshConfig, make_mesh

        with pytest.raises(ValueError, match="int8 KV"):
            ModelRunner(get_config("tiny-mla-test"),
                        RunnerConfig(page_size=4, num_pages=32,
                                     max_batch=2, max_pages_per_seq=8,
                                     prefill_buckets=(16,),
                                     kv_dtype="int8"),
                        make_mesh(MeshConfig()), seed=0)
