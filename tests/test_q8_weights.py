"""Weight-only int8 (W8A16) — VERDICT r4 item 9: the Pallas dequant
matmul, the per-output-channel quantizer, and the runner integration
(BASELINE.md: decode at 7B is weight-streaming-bound; int8 weights are
the named lever)."""

import dataclasses

import numpy as np
import pytest

from dynamo_tpu.models import get_config


class TestQ8Matmul:
    def _case(self, m, k, n, seed=0):
        import jax.numpy as jnp

        from dynamo_tpu.ops.q8_linear import quantize_weight

        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        qw = quantize_weight(w, 1)
        return x, w, qw

    @pytest.mark.parametrize("m,k,n", [(8, 512, 512), (3, 1024, 512),
                                       (33, 512, 1536)])
    def test_kernel_matches_reference(self, m, k, n):
        from dynamo_tpu.ops.q8_linear import q8_matmul, q8_matmul_ref

        x, _, qw = self._case(m, k, n)
        ref = q8_matmul_ref(x, qw["q8"], qw["qs"])
        out = q8_matmul(x, qw["q8"], qw["qs"], interpret=True)
        # k-tiled f32 accumulation reorders the sum vs the single-dot
        # reference: agreement to f32 reassociation noise, not bitwise.
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-3)

    def test_quantization_error_bounded(self):
        """Per-output-channel absmax: dequantized weight within one LSB
        of the original, so the matmul error is the textbook bound."""
        import jax.numpy as jnp

        from dynamo_tpu.ops.q8_linear import q8_matmul_ref

        x, w, qw = self._case(4, 512, 512)
        exact = np.asarray(x @ w)
        quant = np.asarray(q8_matmul_ref(x, qw["q8"], qw["qs"]))
        deq = np.asarray(qw["q8"], np.float32) * np.asarray(qw["qs"])
        assert np.max(np.abs(deq - np.asarray(w))) <= \
            np.max(np.asarray(qw["qs"])) * 0.5 + 1e-6
        # Error measured against the output SCALE (rms), not per-entry:
        # near-zero outputs make per-entry relative error meaningless.
        rel = np.abs(quant - exact) / np.sqrt(np.mean(exact ** 2))
        assert np.percentile(rel, 99) < 0.05

    def test_einsum_specs(self):
        """Every dense-projection spec reshapes correctly."""
        import jax.numpy as jnp

        from dynamo_tpu.ops.q8_linear import q8_einsum, quantize_weight

        rng = np.random.default_rng(1)
        b, t, h, qh, hd, mdim = 2, 3, 512, 8, 128, 1024
        x = jnp.asarray(rng.standard_normal((b, t, h)), jnp.float32)
        for spec, wshape, nc in [
            ("bth,hm->btm", (h, mdim), 1),
            ("bth,hqd->btqd", (h, qh, hd), 1),
            ("bth,hv->btv", (h, 1024), 1),
        ]:
            w = jnp.asarray(rng.standard_normal(wshape), jnp.float32)
            qw = quantize_weight(w, nc)
            out = q8_einsum(spec, x, qw["q8"], qw["qs"])
            ref = jnp.einsum(spec, x, np.asarray(qw["q8"], np.float32)
                             * np.asarray(qw["qs"]))
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)
        xo = jnp.asarray(rng.standard_normal((b, t, qh, hd)), jnp.float32)
        wo = jnp.asarray(rng.standard_normal((qh, hd, h)), jnp.float32)
        qo = quantize_weight(wo, 2)
        out = q8_einsum("btqd,qdh->bth", xo, qo["q8"], qo["qs"])
        ref = jnp.einsum("btqd,qdh->bth", xo,
                         np.asarray(qo["q8"], np.float32)
                         * np.asarray(qo["qs"]))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


class TestRunnerInt8Weights:
    def _runner(self, weight_dtype):
        from dynamo_tpu.engine.model_runner import ModelRunner, RunnerConfig
        from dynamo_tpu.parallel import MeshConfig, make_mesh

        return ModelRunner(
            get_config("tiny-test"),
            RunnerConfig(page_size=4, num_pages=64, max_batch=2,
                         max_pages_per_seq=16, prefill_buckets=(16, 32),
                         weight_dtype=weight_dtype),
            make_mesh(MeshConfig()),
            seed=0,
        )

    def test_serving_loop_matches_bf16_closely(self):
        """Greedy prefill+decode with int8 weights: logit perturbation is
        quantization-bounded; the stream matches bf16 on the tiny model
        (parity-tolerance style of tests/test_kv_int8.py)."""
        rng = np.random.default_rng(2)
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
        same = sum(a == b for a, b in zip(outs["model"], outs["int8"]))
        assert same >= len(outs["model"]) - 1, outs

    def test_quantized_leaf_structure(self):
        r = self._runner("int8")
        layer = r.params["layers"][0]
        for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
            assert isinstance(layer[name], dict), name
            assert layer[name]["q8"].dtype == np.int8
        # norms / embeddings untouched
        assert not isinstance(layer["attn_norm"], dict)
        assert not isinstance(r.params["embed"], dict)

    def test_unsupported_families_rejected(self):
        from dynamo_tpu.models.quantize import check_quantizable

        with pytest.raises(ValueError, match="dense"):
            check_quantizable(get_config("tiny-mla-test"))
        with pytest.raises(ValueError, match="single-device"):
            check_quantizable(get_config("tiny-test"), tp=2)
        with pytest.raises(ValueError, match="single-device"):
            check_quantizable(get_config("tiny-test"), n_devices=8)

    def test_bad_weight_dtype_rejected(self):
        from dynamo_tpu.engine.model_runner import ModelRunner, RunnerConfig
        from dynamo_tpu.parallel import MeshConfig, make_mesh

        with pytest.raises(ValueError, match="weight_dtype"):
            ModelRunner(get_config("tiny-test"),
                        RunnerConfig(prefill_buckets=(16,),
                                     weight_dtype="fp4"),
                        make_mesh(MeshConfig()), seed=0)
