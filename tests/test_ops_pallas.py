"""Pallas kernels vs XLA reference oracle (interpret mode on CPU).

Mirrors the reference's kernel test strategy (CUDA kernels tested against
torch reference impls in lib/kvbm-kernels); here the oracle is
`paged_attention_xla` and pure-numpy layouts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import ModelConfig, make_kv_cache
from dynamo_tpu.models.transformer import paged_attention_xla, write_kv_pages
from dynamo_tpu.ops import (
    gather_kv_blocks,
    paged_attention,
    paged_decode_attention,
    scatter_kv_blocks,
    swap_kv_blocks,
)
from dynamo_tpu.ops.layout import (
    layered_to_universal,
    nhd_to_universal,
    reshard_heads,
    universal_to_layered,
    universal_to_nhd,
)


def _make_case(b=4, qh=8, kh=4, hd=64, ps=8, n_pages=32, max_pages=6,
               seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, qh, hd)), dtype)
    k_pages = jnp.asarray(rng.normal(size=(n_pages, ps, kh, hd)), dtype)
    v_pages = jnp.asarray(rng.normal(size=(n_pages, ps, kh, hd)), dtype)
    # distinct pages per sequence, page 0 reserved
    ids = rng.permutation(n_pages - 1)[: b * max_pages].reshape(b, max_pages)
    block_tables = jnp.asarray(ids + 1, jnp.int32) % n_pages
    kv_lens = jnp.asarray(rng.integers(1, ps * max_pages, size=b), jnp.int32)
    return q, k_pages, v_pages, block_tables, kv_lens


def _oracle(q, k_pages, v_pages, block_tables, kv_lens):
    """Dense masked attention over gathered pages (fp32)."""
    b, qh, hd = q.shape
    _, ps, kh, _ = k_pages.shape
    group = qh // kh
    ctx = block_tables.shape[1] * ps
    k = np.asarray(k_pages)[np.asarray(block_tables)].reshape(b, ctx, kh, hd)
    v = np.asarray(v_pages)[np.asarray(block_tables)].reshape(b, ctx, kh, hd)
    qn = np.asarray(q, np.float32).reshape(b, kh, group, hd)
    scores = np.einsum("bkgh,bskh->bkgs", qn,
                       k.astype(np.float32)) / np.sqrt(hd)
    mask = np.arange(ctx)[None, :] < np.asarray(kv_lens)[:, None]
    scores = np.where(mask[:, None, None, :], scores, -np.inf)
    scores -= scores.max(axis=-1, keepdims=True)
    probs = np.exp(scores)
    probs /= probs.sum(axis=-1, keepdims=True)
    out = np.einsum("bkgs,bskh->bkgh", probs, v.astype(np.float32))
    return out.reshape(b, qh, hd)


class TestPagedDecodeAttention:
    def test_matches_oracle_fp32(self):
        q, kp, vp, bt, kl = _make_case()
        got = paged_decode_attention(q, kp, vp, bt, kl, interpret=True)
        want = _oracle(q, kp, vp, bt, kl)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5)

    def test_matches_oracle_bf16(self):
        q, kp, vp, bt, kl = _make_case(dtype=jnp.bfloat16)
        got = paged_decode_attention(q, kp, vp, bt, kl, interpret=True)
        want = _oracle(q, kp, vp, bt, kl)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), want, rtol=5e-2, atol=5e-2
        )

    def test_mha_group1(self):
        q, kp, vp, bt, kl = _make_case(qh=4, kh=4)
        got = paged_decode_attention(q, kp, vp, bt, kl, interpret=True)
        want = _oracle(q, kp, vp, bt, kl)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5)

    def test_short_sequences(self):
        q, kp, vp, bt, kl = _make_case()
        kl = jnp.ones_like(kl)  # every sequence sees exactly 1 token
        got = paged_decode_attention(q, kp, vp, bt, kl, interpret=True)
        want = _oracle(q, kp, vp, bt, kl)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5)

    def test_matches_xla_attention_fn_path(self):
        """The attention_fn wrapper agrees with the model's XLA path on a
        real paged cache written through write_kv_pages."""
        config = ModelConfig(name="t", vocab_size=64, hidden=32, n_layers=1,
                             n_q_heads=4, n_kv_heads=2, head_dim=16,
                             mlp_hidden=64, dtype="float32")
        ps, n_pages, max_pages, b, t = 4, 16, 4, 2, 8
        rng = np.random.default_rng(1)
        kv = make_kv_cache(config, n_pages, ps, "float32")
        bt = jnp.asarray(
            rng.permutation(n_pages - 1)[: b * max_pages].reshape(
                b, max_pages) + 1, jnp.int32) % n_pages
        k = jnp.asarray(rng.normal(size=(b, t, 2, 16)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, t, 2, 16)), jnp.float32)
        positions = jnp.tile(jnp.arange(t)[None], (b, 1))
        valid = jnp.ones((b, t), bool)
        kv = write_kv_pages(kv, 0, k, v, bt, positions, valid)

        q = jnp.asarray(rng.normal(size=(b, 1, 4, 16)), jnp.float32)
        qpos = jnp.full((b, 1), t - 1, jnp.int32)
        kv_lens = jnp.full((b,), t, jnp.int32)
        got = paged_attention(q, kv, 0, bt, qpos, kv_lens, interpret=True)
        want = paged_attention_xla(q, kv, 0, bt, qpos, kv_lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestPagedDecodeAttentionPartial:
    """The unnormalized flash-partials kernel (acc, m, l over the paged
    HISTORY) vs the dense oracle: acc / l must equal masked softmax
    attention, and the partials must be foldable (the contract the
    deferred-write combine in forward_decode relies on)."""

    def test_normalized_partials_match_oracle(self):
        from dynamo_tpu.ops.paged_attention import (
            paged_decode_attention_partial,
        )

        q, kp, vp, bt, kl = _make_case()
        acc, m, l = paged_decode_attention_partial(q, kp, vp, bt, kl,
                                                   interpret=True)
        b, qh, hd = q.shape
        kh = kp.shape[2]
        out = (np.asarray(acc) / np.asarray(l)[..., None]).reshape(
            b, qh, hd)
        want = _oracle(q, kp, vp, bt, kl)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)

    def test_partials_fold_across_a_page_split(self):
        """m is the row max and l the exp-sum at that max: the standard
        flash rescale over the partials of the first two pages and the
        last two pages must reproduce attention over the full history —
        the exact combine forward_decode's deferred-write path runs."""
        from dynamo_tpu.ops.paged_attention import (
            paged_decode_attention_partial,
        )

        ps = 8
        q, kp, vp, bt, kl = _make_case(max_pages=4, ps=ps)
        lo_len = np.minimum(np.asarray(kl), 2 * ps)
        hi_len = np.clip(np.asarray(kl) - 2 * ps, 0, 2 * ps)
        a1, m1, l1 = paged_decode_attention_partial(
            q, kp, vp, bt[:, :2], jnp.asarray(lo_len, jnp.int32),
            interpret=True)
        a2, m2, l2 = paged_decode_attention_partial(
            q, kp, vp, bt[:, 2:], jnp.asarray(hi_len, jnp.int32),
            interpret=True)
        a1, m1, l1 = (np.asarray(x, np.float64) for x in (a1, m1, l1))
        a2, m2, l2 = (np.asarray(x, np.float64) for x in (a2, m2, l2))
        m12 = np.maximum(m1, m2)
        c1 = np.exp(m1 - m12)
        c2 = np.exp(m2 - m12)
        acc = a1 * c1[..., None] + a2 * c2[..., None]
        tot = l1 * c1 + l2 * c2
        want = _oracle(q, kp, vp, bt, kl)
        b, qh, hd = q.shape
        got = (acc / tot[..., None]).reshape(b, qh, hd)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


class TestPagedAttentionDecodeFused:
    """The deferred-write Pallas path (history partials + in-register
    current token) vs paged_attention_decode_xla as oracle."""

    def _case(self, b=4, qh=8, kh=4, hd=64, ps=8, n_pages=32, max_pages=6,
              seed=3, dtype=jnp.float32, min_len=1):
        rng = np.random.default_rng(seed)
        L = 2
        kv_cache = jnp.asarray(
            rng.normal(size=(L, 2, n_pages, ps, kh, hd)), dtype)
        q = jnp.asarray(rng.normal(size=(b, 1, qh, hd)), dtype)
        k_cur = jnp.asarray(rng.normal(size=(b, 1, kh, hd)), dtype)
        v_cur = jnp.asarray(rng.normal(size=(b, 1, kh, hd)), dtype)
        ids = rng.permutation(n_pages - 1)[: b * max_pages] \
            .reshape(b, max_pages)
        bt = jnp.asarray(ids + 1, jnp.int32) % n_pages
        # kv_lens INCLUDE the current token
        kl = jnp.asarray(
            rng.integers(min_len, ps * max_pages, size=b), jnp.int32)
        return q, kv_cache, bt, kl, k_cur, v_cur

    def test_matches_xla_deferred_path(self):
        from dynamo_tpu.models.transformer import paged_attention_decode_xla
        from dynamo_tpu.ops.paged_attention import (
            paged_attention_decode_fused,
        )

        q, kv, bt, kl, kc, vc = self._case()
        for layer in (0, 1):
            got = paged_attention_decode_fused(
                q, kv, layer, bt, kl, kc, vc, interpret=True)
            want = paged_attention_decode_xla(q, kv, layer, bt, kl, kc, vc)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)

    def test_first_token_no_history(self):
        """kv_len == 1: only the in-register current token attends (the
        kernel's history pass sees zero tokens -> m=-inf branch)."""
        from dynamo_tpu.models.transformer import paged_attention_decode_xla
        from dynamo_tpu.ops.paged_attention import (
            paged_attention_decode_fused,
        )

        q, kv, bt, kl, kc, vc = self._case()
        kl = jnp.ones_like(kl)
        got = paged_attention_decode_fused(
            q, kv, 0, bt, kl, kc, vc, interpret=True)
        want = paged_attention_decode_xla(q, kv, 0, bt, kl, kc, vc)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        # degenerate case is exactly v_cur
        np.testing.assert_allclose(np.asarray(got)[:, 0, 0],
                                   np.asarray(vc)[:, 0, 0],
                                   rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        from dynamo_tpu.models.transformer import paged_attention_decode_xla
        from dynamo_tpu.ops.paged_attention import (
            paged_attention_decode_fused,
        )

        q, kv, bt, kl, kc, vc = self._case(dtype=jnp.bfloat16)
        got = paged_attention_decode_fused(
            q, kv, 0, bt, kl, kc, vc, interpret=True)
        want = paged_attention_decode_xla(q, kv, 0, bt, kl, kc, vc)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=5e-2, atol=5e-2)

    def test_forward_decode_with_fused_kernel_matches_xla(self):
        """Whole forward_decode equality: kernel path vs XLA path on a
        real model config and populated cache."""
        import functools

        from dynamo_tpu.models import get_config, init_params, make_kv_cache
        from dynamo_tpu.models.transformer import forward_decode
        from dynamo_tpu.ops.paged_attention import (
            paged_attention_decode_fused,
        )

        cfg = get_config("tiny-test")
        params = init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        kv = make_kv_cache(cfg, 32, 4)
        kv = jnp.asarray(rng.normal(size=kv.shape), kv.dtype)
        b = 2
        bt = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
        kv_lens = jnp.asarray([7, 11], jnp.int32)
        tokens = jnp.asarray([3, 5], jnp.int32)
        positions = kv_lens - 1
        active = jnp.ones((b,), bool)

        kv_x, logits_x = forward_decode(params, cfg, tokens, positions, kv,
                                        bt, kv_lens, active)
        kv_p, logits_p = forward_decode(
            params, cfg, tokens, positions, kv, bt, kv_lens, active,
            decode_attention_fn=functools.partial(
                paged_attention_decode_fused, interpret=True))
        np.testing.assert_allclose(np.asarray(logits_p),
                                   np.asarray(logits_x),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(kv_p, np.float32), np.asarray(kv_x, np.float32),
            rtol=1e-5, atol=1e-5)


# forward_decode through the pool kernel against the XLA path: exact for
# a float32 model; a bf16 model's kernel rounds q's dtype into P V.
_MODEL_DTYPES = [("float32", 2e-4), ("bfloat16", 6e-2)]


class TestPagedAttentionDecodePool:
    """The production TPU decode path: whole-pool chunked-DMA kernel
    (paged_decode_attention_pool + combine) vs paged_attention_decode_xla
    as oracle, across layers, chunk sizes, history lengths, and dtypes."""

    def _case(self, b=4, qh=8, kh=4, hd=64, ps=8, n_pages=32, max_pages=6,
              seed=5, dtype=jnp.float32):
        rng = np.random.default_rng(seed)
        L = 2
        kv = jnp.asarray(rng.normal(size=(L, 2, n_pages, ps, kh, hd)),
                         dtype)
        q = jnp.asarray(rng.normal(size=(b, 1, qh, hd)), dtype)
        kc = jnp.asarray(rng.normal(size=(b, 1, kh, hd)), dtype)
        vc = jnp.asarray(rng.normal(size=(b, 1, kh, hd)), dtype)
        ids = rng.permutation(n_pages - 1)[: b * max_pages] \
            .reshape(b, max_pages)
        bt = jnp.asarray(ids + 1, jnp.int32) % n_pages
        kl = jnp.asarray(rng.integers(1, ps * max_pages, size=b),
                         jnp.int32)
        return q, kv, bt, kl, kc, vc

    @pytest.mark.parametrize("ppc", [1, 2, 3, 6])
    def test_matches_xla_across_chunk_sizes(self, ppc):
        from dynamo_tpu.models.transformer import paged_attention_decode_xla
        from dynamo_tpu.ops.paged_attention import (
            paged_attention_decode_pool,
        )

        q, kv, bt, kl, kc, vc = self._case()
        for layer in (0, 1):
            got = paged_attention_decode_pool(
                q, kv, layer, bt, kl, kc, vc, pages_per_chunk=ppc,
                interpret=True)
            want = paged_attention_decode_xla(q, kv, layer, bt, kl, kc, vc)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)

    def test_zero_history_and_mixed_lengths(self):
        """kv_len == 1 slots (no history: kernel never DMAs for them) mixed
        with long ones — the next_active skip logic must not corrupt
        neighbours."""
        from dynamo_tpu.models.transformer import paged_attention_decode_xla
        from dynamo_tpu.ops.paged_attention import (
            paged_attention_decode_pool,
        )

        q, kv, bt, kl, kc, vc = self._case()
        kl = jnp.asarray([1, 47, 1, 13], jnp.int32)
        got = paged_attention_decode_pool(q, kv, 0, bt, kl, kc, vc,
                                          pages_per_chunk=2, interpret=True)
        want = paged_attention_decode_xla(q, kv, 0, bt, kl, kc, vc)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        # zero-history rows degenerate to exactly v_cur
        for row in (0, 2):
            np.testing.assert_allclose(
                np.asarray(got)[row, 0].reshape(4, 2, -1)[:, 0],
                np.asarray(vc)[row, 0], rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        from dynamo_tpu.models.transformer import paged_attention_decode_xla
        from dynamo_tpu.ops.paged_attention import (
            paged_attention_decode_pool,
        )

        q, kv, bt, kl, kc, vc = self._case(dtype=jnp.bfloat16)
        got = paged_attention_decode_pool(q, kv, 1, bt, kl, kc, vc,
                                          pages_per_chunk=3, interpret=True)
        want = paged_attention_decode_xla(q, kv, 1, bt, kl, kc, vc)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=5e-2, atol=5e-2)

    @pytest.mark.parametrize("dtype,tol", _MODEL_DTYPES)
    def test_forward_decode_with_pool_kernel_matches_xla(self, dtype, tol):
        """Whole forward_decode equality on a real model config — the
        integration the runner wires on TPU. A float32 model is exact;
        a bf16 model rounds its probabilities to bf16 for P V (the
        kernel's matmul operands are in the model's dtype)."""
        import dataclasses
        import functools

        from dynamo_tpu.models import get_config, init_params, make_kv_cache
        from dynamo_tpu.models.transformer import forward_decode
        from dynamo_tpu.ops.paged_attention import (
            paged_attention_decode_pool,
        )

        cfg = dataclasses.replace(get_config("tiny-test"), dtype=dtype)
        params = init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        kv = make_kv_cache(cfg, 32, 4)
        kv = jnp.asarray(rng.normal(size=kv.shape), kv.dtype)
        bt = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
        kv_lens = jnp.asarray([7, 11], jnp.int32)
        tokens = jnp.asarray([3, 5], jnp.int32)
        active = jnp.ones((2,), bool)

        kv_x, logits_x = forward_decode(params, cfg, tokens, kv_lens - 1,
                                        kv, bt, kv_lens, active)
        kv_p, logits_p = forward_decode(
            params, cfg, tokens, kv_lens - 1, kv, bt, kv_lens, active,
            decode_attention_fn=functools.partial(
                paged_attention_decode_pool, pages_per_chunk=2,
                interpret=True))
        np.testing.assert_allclose(np.asarray(logits_p),
                                   np.asarray(logits_x),
                                   rtol=tol, atol=tol)
        kv_tol = 1e-5 if dtype == "float32" else tol  # later layers' K/V
        np.testing.assert_allclose(
            np.asarray(kv_p, np.float32), np.asarray(kv_x, np.float32),
            rtol=kv_tol, atol=kv_tol)


def _pool_case(rng, *, b, kh, g, hd, ps, max_pages, kind, t=1):
    """A paged pool with distinct pages per row. `kind`: "int8" is the q8
    pool with float32 queries (codes and scales exact in the kernel's
    float32 operands, so the comparison is tight), "bf16" a bf16 pool
    with bf16 queries (the chip's operand dtype)."""
    from dynamo_tpu.models.transformer import quantize_kv

    n_pages = 1 + b * max_pages
    raw = rng.normal(size=(2, 2, n_pages, ps, kh, hd))
    qdt = jnp.float32 if kind == "int8" else jnp.bfloat16
    kv = (quantize_kv(jnp.asarray(raw, jnp.float32)) if kind == "int8"
          else jnp.asarray(raw, jnp.bfloat16))
    q = jnp.asarray(rng.normal(size=(b, t, kh * g, hd)), qdt)
    kc = jnp.asarray(rng.normal(size=(b, t, kh, hd)), qdt)
    vc = jnp.asarray(rng.normal(size=(b, t, kh, hd)), qdt)
    bt = jnp.asarray(
        1 + rng.permutation(n_pages - 1).reshape(b, max_pages), jnp.int32)
    return q, kv, bt, kc, vc


# int8: float32 operands, a reordered sum only; bf16: P rounded to bf16.
_POOL_TOL = {"int8": 5e-6, "bf16": 3e-2}


class TestPagedAttentionDecodePoolGrid:
    """The flattened-heads kernel against the XLA oracle over what it
    adapts to: kv heads a shard or a model can have, the GQA group (20 =
    group 4 x 5 speculative positions, the fold of
    paged_attention_spec_pool), both pool kinds, and the chunk the table
    width gives (None: the 256-token table as one chunk, cut in blocks
    where kh*g rows would outgrow the score tile) or a cap (two chunks,
    four chunks)."""

    @pytest.mark.parametrize("ppc", [None, 16, 8])
    @pytest.mark.parametrize("kind", ["int8", "bf16"])
    @pytest.mark.parametrize("g", [1, 4, 20])
    @pytest.mark.parametrize("kh", [2, 4, 8])
    def test_matches_xla(self, kh, g, kind, ppc):
        from dynamo_tpu.models.transformer import paged_attention_decode_xla
        from dynamo_tpu.ops.paged_attention import (
            paged_attention_decode_pool,
        )

        ps, max_pages = 8, 32
        q, kv, bt, kc, vc = _pool_case(
            np.random.default_rng(kh * 100 + g), b=4, kh=kh, g=g, hd=32,
            ps=ps, max_pages=max_pages, kind=kind)
        # empty, inside the first block, across the block boundary of a
        # chunk (129 history tokens), a full table
        kl = jnp.asarray([1, 40, 130, ps * max_pages], jnp.int32)
        got = paged_attention_decode_pool(
            q, kv, 1, bt, kl, kc, vc, pages_per_chunk=ppc, interpret=True)
        want = paged_attention_decode_xla(q, kv, 1, bt, kl, kc, vc)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=_POOL_TOL[kind], atol=_POOL_TOL[kind])

    @pytest.mark.parametrize("kind", ["int8", "bf16"])
    def test_spec_fold_matches_xla(self, kind):
        """T = 5 chunk queries folded into the group dim (g = 20 at
        group 4) through the same kernel body."""
        from dynamo_tpu.models.transformer import paged_attention_spec_xla
        from dynamo_tpu.ops.paged_attention import paged_attention_spec_pool

        q, kv, bt, kc, vc = _pool_case(
            np.random.default_rng(9), b=3, kh=4, g=4, hd=32, ps=8,
            max_pages=32, kind=kind, t=5)
        kl = jnp.asarray([1, 77, 200], jnp.int32)
        got = paged_attention_spec_pool(q, kv, 0, bt, kl, kc, vc,
                                        interpret=True)
        want = paged_attention_spec_xla(q, kv, 0, bt, kl, kc, vc)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=_POOL_TOL[kind], atol=_POOL_TOL[kind])


class TestPoolKernelStaleSlots:
    """Fault 1 (PERF.md section 6, PR 25): a free slot keeps its last
    sequence's length and table. Unmasked, the kernel streamed its
    history every layer of every step, and a stale length past the table
    width in use made it start a DMA that no grid step awaits, which
    halts the device. The interpreter runs a DMA where it is started and
    has no semaphores to leave nonzero, so it cannot show the halt; what
    these tests pin is the two causes."""

    @pytest.mark.parametrize("entry", ["decode", "spec"])
    def test_inactive_rows_reach_attention_as_empty_history(self, entry):
        from dynamo_tpu.models import get_config, init_params, make_kv_cache
        from dynamo_tpu.models.transformer import (
            forward_decode,
            forward_spec,
            paged_attention_decode_xla,
            paged_attention_spec_xla,
        )

        cfg = get_config("tiny-test")
        params = init_params(jax.random.PRNGKey(0), cfg)
        kv = make_kv_cache(cfg, 32, 4)
        bt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]],
                         jnp.int32)
        # row 1 is a free slot: its length is stale, and past its table
        kv_lens = jnp.asarray([7, 4000, 11], jnp.int32)
        active = jnp.asarray([True, False, True])
        seen = []

        def recording(oracle):
            def fn(q, cache, layer, tables, lens, k, v):
                seen.append(np.asarray(lens))
                return oracle(q, cache, layer, tables, lens, k, v)
            return fn

        if entry == "decode":
            forward_decode(
                params, cfg, jnp.asarray([3, 5, 7], jnp.int32), kv_lens - 1,
                kv, bt, kv_lens, active,
                decode_attention_fn=recording(paged_attention_decode_xla))
        else:
            positions = (kv_lens - 1)[:, None] + jnp.arange(2)[None, :]
            forward_spec(
                params, cfg, jnp.asarray([[3, 4], [5, 6], [7, 8]], jnp.int32),
                positions, kv, bt, kv_lens, active,
                spec_attention_fn=recording(paged_attention_spec_xla))
        assert len(seen) == cfg.n_layers
        for lens in seen:
            history = np.maximum(lens - 1, 0)
            assert history[1] == 0  # the free slot streams nothing
            np.testing.assert_array_equal(lens[[0, 2]], [7, 11])

    @pytest.mark.parametrize("lengths,bk,n_chunks", [
        ([900, 0, 300, 2000], 128, 8),  # the last row's length is stale
        ([5000, 0, 0, 0], 256, 4),  # ... and nothing follows it
        ([100, 5000, 0, 700], 512, 2),
        ([0, 0, 0, 0], 128, 8),
        ([1024, 1024, 1024, 1024], 512, 2),
        ([0, 1, 0, 129, 128, 0], 128, 2),  # empty rows in every place
    ])
    @pytest.mark.parametrize("slots", [2, 3])
    def test_every_live_chunk_is_numbered_once_in_row_order(
            self, lengths, bk, n_chunks, slots):
        """Walk the rows as the kernel does on what `_chunk_walk` hands
        it: row b scores its live chunks first[b] .. in turn, chunk n out
        of slot n mod slots, and starts chunk n + slots - 1, whichever
        row's it is (the first row starts the first slots - 1 itself).
        The copies started must be exactly the chunks scored, in order,
        each into a slot whose last chunk has been scored, and none may
        lie past the table, whatever the lengths say (2000 and 5000 here
        are past the table width, n_chunks * bk)."""
        from dynamo_tpu.ops.paged_attention import _chunk_walk

        page, batch = 16, len(lengths)
        lens, tables, layer, first, row_of = (np.asarray(x) for x in (
            _chunk_walk(jnp.asarray(lengths, jnp.int32),
                        jnp.zeros((batch, n_chunks * bk // page), jnp.int32),
                        3, page, bk // page, slots)))
        np.testing.assert_array_equal(
            lens, np.minimum(lengths, n_chunks * bk))
        assert tables.shape == (batch * n_chunks * bk // page,)
        assert layer.tolist() == [3]
        live = [(b, c) for b in range(batch)
                for c in range(-(-int(lens[b]) // bk))]
        # every live chunk once, in row order; `slots` entries past the
        # last one, and every entry behind them, name no row
        assert [(int(row_of[n]), n - int(first[row_of[n]]))
                for n in range(len(live))] == live
        assert len(row_of) == batch * n_chunks + slots
        assert (row_of[len(live):] == batch).all()
        started, scored = [], []

        def start(n):
            if row_of[n] < batch:
                # the slot's last tenant has been scored
                assert n < slots or len(scored) > n - slots
                started.append((int(row_of[n]), n - int(first[row_of[n]]),
                                n % slots))

        for n in range(slots - 1):
            start(n)
        for b in range(batch):
            for c in range(-(-int(lens[b]) // bk)):
                n = int(first[b]) + c
                start(n + slots - 1)
                assert (b, c, n % slots) in started  # started before
                scored.append((b, c, n % slots))
        assert started == scored
        assert all(0 <= c < n_chunks for _, c, _ in started)

    @pytest.mark.parametrize("kind", ["int8", "bf16"])
    def test_length_past_the_table_width(self, kind):
        """A row whose length exceeds its table (a stale slot the caller
        did not mask) attends its whole table and no more; the rows
        around it, a zero-history row after it included, match the
        oracle."""
        from dynamo_tpu.models.transformer import paged_attention_decode_xla
        from dynamo_tpu.ops.paged_attention import (
            paged_attention_decode_pool,
        )

        ps, max_pages = 8, 16
        q, kv, bt, kc, vc = _pool_case(
            np.random.default_rng(3), b=4, kh=4, g=2, hd=32, ps=ps,
            max_pages=max_pages, kind=kind)
        kl = jnp.asarray([50, 4000, 1, 4000], jnp.int32)
        got = paged_attention_decode_pool(q, kv, 0, bt, kl, kc, vc,
                                          pages_per_chunk=4, interpret=True)
        want = paged_attention_decode_xla(q, kv, 0, bt, kl, kc, vc)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=_POOL_TOL[kind], atol=_POOL_TOL[kind])


# The walk's edges at the pool kernel's own tiles (pages of 16: blocks of
# 256 tokens, chunks of 1,024, so four blocks a chunk): (HISTORY lengths,
# table pages, pages_per_chunk[, what lies behind a row's last live
# page]).
_WALK_PAGE, _WALK_BLOCK, _WALK_CHUNK, _WALK_WIDE = 16, 256, 1024, 128
POOL_WALKS = {
    "one-chunk-a-row": ([74, 0, 32, 1, 127, 128], 8, None),
    "a-page-pair-a-chunk": ([74, 0, 32, 1, 127, 128], 8, 2),
    # one token; a block's last token, the next one's first and second
    "around-a-block": ([1, _WALK_BLOCK, _WALK_BLOCK + 1, _WALK_BLOCK + 2],
                       _WALK_WIDE, None),
    "around-a-chunk": ([_WALK_CHUNK - 1, _WALK_CHUNK, _WALK_CHUNK + 1,
                        _WALK_BLOCK // 2], _WALK_WIDE, None),
    "the-whole-table": ([_WALK_WIDE * _WALK_PAGE,
                         _WALK_CHUNK + _WALK_BLOCK, 3], _WALK_WIDE, None),
    "empty-rows-between-live-rows": ([0, 300, 0, 0, 700, 0], 64, None),
    "every-row-empty": ([0, 0, 0], 64, None),
    "one-live-row-of-256": ([0] * 200 + [700] + [0] * 55, 64, None),
    "a-stale-length-past-the-table": ([300, 5000, 0, 20], 64, None),
    "nan-pages-behind-a-ragged-end": (
        [300, 1, _WALK_BLOCK + 1, _WALK_BLOCK + 18, 0], 64, None, np.nan),
}


def _pool_walk_case(lens, pages, variant, behind=None):
    """A float32 pool of four kv heads 128 lanes wide with distinct
    pages per live row (the operands are the queries' float32, so every
    variant compares tightly; the geometry is one whose pages the kernel
    copies as merged rows, as every cell's but phi4's); `int8` quantises
    it and compares against the dequantised values; `packed` has heads
    of 64 lanes and is handed over two a 128-lane row; `window` draws a
    lower edge inside each row's first page. `behind`: table entries
    past a row's last live page name a page holding that (for the int8
    pool: in its scales) all over."""
    from dynamo_tpu.models.transformer import quantize_kv

    rng = np.random.default_rng(len(lens) * 1000 + pages)
    kh, g, hd = 4, 2, 64 if variant == "packed" else 128
    used = [-(-min(n, pages * _WALK_PAGE) // _WALK_PAGE) for n in lens]
    n_pages = 2 + sum(used)
    values = rng.normal(size=(2, 2, n_pages, _WALK_PAGE, kh, hd)).astype(
        np.float32)
    tables = np.zeros((len(lens), pages), np.int32)
    at = 1
    for i, n in enumerate(used):
        tables[i, :n] = np.arange(at, at + n)
        at += n
        if behind is not None:
            tables[i, n:] = n_pages - 1
    scales = None
    if variant == "int8":
        codes, scales = quantize_kv(jnp.asarray(values))
        values = np.asarray(codes.astype(jnp.float32)
                            * scales[..., :1, None].astype(jnp.float32))
        if behind is not None:
            scales = scales.at[:, :, n_pages - 1].set(behind)
        pool = codes
    else:
        if behind is not None:
            values[:, :, n_pages - 1] = behind
        pool = jnp.asarray(values)
    starts = np.zeros(len(lens), np.int32)
    if variant == "window":
        starts = np.minimum(rng.integers(0, _WALK_PAGE, len(lens)),
                            np.maximum(np.asarray(lens) - 1, 0)).astype(
                                np.int32)
    q = rng.normal(size=(len(lens), kh * g, hd)).astype(np.float32)
    return q, pool, scales, values, tables, starts


def _history_oracle(q, values, layer, tables, lens, starts, kh):
    """The unnormalised flash partials (acc, m, l) of each row over its
    history [start, length) in float64, [B, kh, g, hd] and [B, kh, g]."""
    b, qh, hd = q.shape
    g = qh // kh
    acc = np.zeros((b, kh, g, hd))
    m = np.full((b, kh, g), -np.inf)
    l = np.zeros((b, kh, g))
    for i in range(b):
        n = min(int(lens[i]), tables.shape[1] * _WALK_PAGE)
        if n == 0:
            continue
        k, v = (values[layer, w][tables[i]].reshape(-1, kh, hd)[
            starts[i]:n].astype(np.float64) for w in (0, 1))
        s = np.einsum("kgh,tkh->kgt", q[i].reshape(kh, g, hd).astype(
            np.float64), k) / np.sqrt(hd)
        m[i] = s.max(-1)
        p = np.exp(s - m[i][..., None])
        l[i] = p.sum(-1)
        acc[i] = np.einsum("kgt,tkh->kgh", p, v)
    return acc, m, l


@pytest.mark.parametrize("variant", ["bf16", "int8", "window", "packed"])
@pytest.mark.parametrize("walk", sorted(POOL_WALKS))
def test_the_pool_kernel_walks_every_edge_as_the_oracle_does(walk, variant):
    """`_pool_decode_kernel` (interpreted) through each of its entries
    against a float64 oracle over the whole table: histories of one
    token, one under, at and one over a block and a chunk and as wide as
    the table, rows without a history wherever the walk can meet them
    (first, last, two in a row, all but one of 256, all), a length past
    the table (which reads the table's width), and table entries behind
    a ragged end that name a page of NaN: a ragged block copies such
    pages whole, and the answer is the one over zeros there (0 x NaN
    would be NaN in P V)."""
    import importlib

    ops = importlib.import_module("dynamo_tpu.ops.paged_attention")
    assert (ops._POOL_BLOCK_TOKENS[1], ops._POOL_CHUNK_TOKENS) == (
        _WALK_BLOCK, _WALK_CHUNK)  # the edges above are the kernel's own
    lens, pages, chunk, behind = (*POOL_WALKS[walk], None)[:4]
    q, pool, scales, values, tables, starts = _pool_walk_case(
        lens, pages, variant, behind)
    if behind is not None:  # the oracle's pool holds zeros there
        values = _pool_walk_case(lens, pages, variant, 0.0)[3]
    hist = jnp.asarray(lens, jnp.int32)
    args = (jnp.asarray(q), pool, jnp.int32(1), jnp.asarray(tables), hist)
    kw = {"pages_per_chunk": chunk, "interpret": True}
    if variant == "window":
        got = ops.paged_decode_attention_window(
            *args, jnp.asarray(starts), **kw)
    elif variant == "packed":  # two kv heads of 64 lanes a 128-lane row
        got = ops._packed_pool_partials(
            args[0], pool.reshape(*pool.shape[:4], 2, 128), *args[2:], 4,
            chunk, True, None)
    else:
        got = ops.paged_decode_attention_pool(*args, scales, **kw)
    want = _history_oracle(q, values, 1, tables, lens, starts, 4)
    live = [i for i, n in enumerate(lens) if n > 0]
    for name, g_, w_ in zip(("acc", "m", "l"), got, want):
        g_ = np.asarray(g_)
        assert g_.shape == w_.shape, name
        assert np.isfinite(g_[live]).all(), name
        np.testing.assert_allclose(g_[live], w_[live], rtol=2e-5, atol=2e-5,
                                   err_msg=name)
    # a row without a history leaves the identity of the combine
    empty = [i for i, n in enumerate(lens) if n == 0]
    assert (np.asarray(got[0])[empty] == 0).all()
    assert (np.asarray(got[1])[empty] == -np.inf).all()
    assert (np.asarray(got[2])[empty] == 0).all()


class TestPagedAttentionDecodePoolTp:
    """The pool kernel under tensor parallelism (VERDICT r2 weak #3):
    shard_map over the kv-head axis, each shard streaming its local pool
    slice. Oracle = single-device kernel / XLA path on the same data."""

    def _mesh(self, tp):
        from dynamo_tpu.parallel import MeshConfig, make_mesh

        return make_mesh(MeshConfig(tp=tp))

    @pytest.mark.parametrize("tp", [2, 4])
    def test_matches_xla_oracle(self, tp):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dynamo_tpu.models.transformer import paged_attention_decode_xla
        from dynamo_tpu.ops.paged_attention import (
            make_paged_attention_decode_pool_tp,
        )

        mesh = self._mesh(tp)
        rng = np.random.default_rng(11)
        b, qh, kh, hd, ps, n_pages, max_pages = 4, 8, 4, 64, 8, 32, 6
        kv = jnp.asarray(rng.normal(size=(2, 2, n_pages, ps, kh, hd)),
                         jnp.float32)
        kv = jax.device_put(kv, NamedSharding(
            mesh, P(None, None, None, None, "tp", None)))
        q = jnp.asarray(rng.normal(size=(b, 1, qh, hd)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(b, 1, kh, hd)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(b, 1, kh, hd)), jnp.float32)
        ids = rng.permutation(n_pages - 1)[: b * max_pages] \
            .reshape(b, max_pages)
        bt = jnp.asarray(ids + 1, jnp.int32) % n_pages
        kl = jnp.asarray([1, 13, 47, 30], jnp.int32)

        fn = make_paged_attention_decode_pool_tp(mesh, pages_per_chunk=2,
                                                 interpret=True)
        for layer in (0, 1):
            got = fn(q, kv, layer, bt, kl, kc, vc)
            want = paged_attention_decode_xla(q, kv, layer, bt, kl, kc, vc)
            assert got.shape == want.shape == (b, 1, qh, hd)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("dtype,tol", _MODEL_DTYPES)
    def test_forward_decode_tp2_matches_xla(self, dtype, tol):
        """Whole forward_decode under a tp=2 mesh with the sharded kernel —
        the exact integration the runner wires on multi-chip TPU."""
        import dataclasses

        from jax.sharding import NamedSharding, PartitionSpec as P

        from dynamo_tpu.models import get_config, init_params
        from dynamo_tpu.models.transformer import forward_decode
        from dynamo_tpu.ops.paged_attention import (
            make_paged_attention_decode_pool_tp,
        )
        from dynamo_tpu.parallel import kv_cache_sharding, param_shardings
        from dynamo_tpu.models import param_axes

        mesh = self._mesh(2)
        cfg = dataclasses.replace(get_config("tiny-test"), dtype=dtype)
        params = init_params(jax.random.PRNGKey(0), cfg)
        params = jax.tree.map(jax.device_put, params,
                              param_shardings(mesh, param_axes(cfg)))
        rng = np.random.default_rng(0)
        kv = jnp.asarray(rng.normal(size=(cfg.n_layers, 2, 32, 4,
                                          cfg.n_kv_heads, cfg.head_dim)),
                         jnp.dtype(cfg.dtype))
        kv = jax.device_put(kv, kv_cache_sharding(mesh))
        bt = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
        kv_lens = jnp.asarray([7, 11], jnp.int32)
        tokens = jnp.asarray([3, 5], jnp.int32)
        active = jnp.ones((2,), bool)

        kv_x, logits_x = forward_decode(params, cfg, tokens, kv_lens - 1,
                                        kv, bt, kv_lens, active)
        kv_p, logits_p = forward_decode(
            params, cfg, tokens, kv_lens - 1, kv, bt, kv_lens, active,
            decode_attention_fn=make_paged_attention_decode_pool_tp(
                mesh, pages_per_chunk=2, interpret=True))
        np.testing.assert_allclose(np.asarray(logits_p),
                                   np.asarray(logits_x),
                                   rtol=tol, atol=tol)
        kv_tol = 1e-5 if dtype == "float32" else tol  # later layers' K/V
        np.testing.assert_allclose(
            np.asarray(kv_p, np.float32), np.asarray(kv_x, np.float32),
            rtol=kv_tol, atol=kv_tol)

    def test_runner_selects_tp_kernel(self, monkeypatch):
        """The gate: DYNT_ATTENTION=pallas on a tp-only mesh selects the
        sharded kernel (was: disabled on every multi-device mesh), and the
        runner's decode output matches its own XLA-mode twin."""
        from dynamo_tpu.engine.model_runner import (
            ModelRunner,
            RunnerConfig,
            _default_decode_attention_fn,
        )
        from dynamo_tpu.models import get_config
        from dynamo_tpu.parallel import MeshConfig, make_mesh

        mesh = self._mesh(2)
        monkeypatch.setenv("DYNT_ATTENTION", "pallas")
        assert _default_decode_attention_fn(mesh) is not None
        # dp>1 mesh still falls back to XLA
        assert _default_decode_attention_fn(
            make_mesh(MeshConfig(dp=2, tp=2))) is None

        rc = RunnerConfig(page_size=4, num_pages=32, max_batch=2,
                          max_pages_per_seq=8, prefill_buckets=(16,))
        r_pallas = ModelRunner(get_config("tiny-test"), rc, mesh, seed=0)
        assert r_pallas._steps.decode_attention_fn is not None
        monkeypatch.setenv("DYNT_ATTENTION", "xla")
        r_xla = ModelRunner(get_config("tiny-test"), rc, self._mesh(2),
                            seed=0)
        table = np.zeros(8, np.int32)
        table[:4] = np.arange(1, 5)
        prompt = np.arange(1, 11, dtype=np.int32)
        t1 = r_pallas.prefill_chunk(prompt, 0, table, 10, (0.0, 1.0, 0, 0))
        t2 = r_xla.prefill_chunk(prompt, 0, table, 10, (0.0, 1.0, 0, 0))
        assert t1 == t2
        args = ([t1], [10], table[None, :], [11], [True],
                np.zeros(1, np.float32), np.ones(1, np.float32),
                np.zeros(1, np.int32), np.zeros(1, np.uint32))
        n1 = r_pallas.decode(*[np.asarray(a) for a in args])
        n2 = r_xla.decode(*[np.asarray(a) for a in args])
        assert int(n1[0]) == int(n2[0])


class TestBlockCopy:
    def _cache(self, L=2, P=16, ps=4, kh=2, hd=8, seed=0):
        rng = np.random.default_rng(seed)
        return jnp.asarray(
            rng.normal(size=(L, 2, P, ps, kh, hd)), jnp.float32
        )

    def test_gather_scatter_roundtrip(self):
        kv = self._cache()
        ids = jnp.asarray([3, 7, 1], jnp.int32)
        bundle = gather_kv_blocks(kv, ids)
        assert bundle.shape == (3, 2, 2, 4, 2, 8)
        kv2 = jnp.zeros_like(kv)
        kv2 = scatter_kv_blocks(kv2, ids, bundle)
        np.testing.assert_array_equal(
            np.asarray(kv2[:, :, np.asarray(ids)]),
            np.asarray(kv[:, :, np.asarray(ids)]),
        )

    def test_swap(self):
        kv = self._cache()
        orig = np.asarray(kv)
        out = swap_kv_blocks(kv, jnp.asarray([2, 5], jnp.int32),
                             jnp.asarray([9, 11], jnp.int32))
        np.testing.assert_array_equal(np.asarray(out[:, :, 9]), orig[:, :, 2])
        np.testing.assert_array_equal(np.asarray(out[:, :, 11]), orig[:, :, 5])


class TestLayout:
    def test_universal_layered_roundtrip(self):
        rng = np.random.default_rng(0)
        blocks = jnp.asarray(rng.normal(size=(3, 2, 2, 4, 2, 8)), jnp.float32)
        back = layered_to_universal(universal_to_layered(blocks))
        np.testing.assert_array_equal(np.asarray(back), np.asarray(blocks))

    def test_nhd_roundtrip(self):
        rng = np.random.default_rng(0)
        blocks = jnp.asarray(rng.normal(size=(3, 2, 2, 4, 2, 8)), jnp.float32)
        back = nhd_to_universal(universal_to_nhd(blocks), kv_heads=2)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(blocks))

    def test_reshard_heads(self):
        rng = np.random.default_rng(0)
        full = jnp.asarray(rng.normal(size=(2, 1, 2, 4, 8, 4)), jnp.float32)
        # tp=2 -> tp=4: dst shard 1 owns heads [2:4]
        shard = reshard_heads(full, src_shards=2, dst_shards=4, shard_index=1)
        np.testing.assert_array_equal(
            np.asarray(shard), np.asarray(full[:, :, :, :, 2:4])
        )
