"""Weight-only packed int4 (W4A16; ops/q4_linear.py): the pack layout's
round trip, per-group quantization error, the Pallas kernel (interpreted)
against the XLA reference at both documented group sizes and at the
dense cell's contraction depths, the k-block rule, zero-point edges, the
row-block map, what is refused (geometry; a packed leaf that is not
uint8), einsum-spec plumbing, and the runner: quantize and serve, and a
tree that arrives already packed."""

import numpy as np
import pytest

from dynamo_tpu.models import get_config


@pytest.fixture
def group128(monkeypatch):
    """DYNT_Q4_GROUP's other documented value: twice the scale and zero
    rows for the same contraction."""
    monkeypatch.setenv("DYNT_Q4_GROUP", "128")


class TestQ4Pack:
    def test_pack_roundtrip(self):
        import jax.numpy as jnp

        from dynamo_tpu.ops.q4_linear import _pack_codes, _unpack_codes

        rng = np.random.default_rng(0)
        u = jnp.asarray(rng.integers(0, 16, (256, 128)), jnp.uint8)
        packed = _pack_codes(u, 128)
        assert packed.shape == (128, 128)
        np.testing.assert_array_equal(
            np.asarray(_unpack_codes(packed, 128)), np.asarray(u))

    def test_quantizer_round_trip_at_group_128(self, group128):
        """What the quantizer packs under DYNT_Q4_GROUP=128 unpacks to
        the codes it chose: 128 contracted rows a scale row, and every
        code the nearest of its group's sixteen levels."""
        import jax.numpy as jnp

        from dynamo_tpu.ops.q4_linear import (
            _unpack_codes,
            quantize_weight_q4,
        )

        rng = np.random.default_rng(11)
        w = rng.standard_normal((512, 128)).astype(np.float32)
        qw = quantize_weight_q4(jnp.asarray(w), 1)
        assert qw["q4"].dtype == jnp.uint8 and qw["q4"].shape == (256, 128)
        assert qw["qs4"].shape == qw["qz4"].shape == (4, 128)
        s = np.repeat(np.asarray(qw["qs4"]), 128, axis=0)
        z = np.repeat(np.asarray(qw["qz4"]), 128, axis=0)
        want = np.clip(np.round(w / s) + z, 0, 15).astype(np.uint8)
        np.testing.assert_array_equal(
            np.asarray(_unpack_codes(qw["q4"], 128)), want)

    @pytest.mark.parametrize("group", [256, 128])
    def test_dequant_error_within_half_lsb(self, group, monkeypatch):
        """Asymmetric per-group codes reconstruct within scale/2."""
        import jax.numpy as jnp

        from dynamo_tpu.ops.q4_linear import (
            dequantize_q4,
            quantize_weight_q4,
        )

        monkeypatch.setenv("DYNT_Q4_GROUP", str(group))
        rng = np.random.default_rng(1)
        w = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
        qw = quantize_weight_q4(w, 1)
        assert qw["qs4"].shape[0] == 512 // group
        deq = np.asarray(dequantize_q4(qw["q4"], qw["qs4"], qw["qz4"]))
        s = np.repeat(np.asarray(qw["qs4"]), group, axis=0)
        assert np.max(np.abs(deq - np.asarray(w)) - s * 0.5) <= 1e-5

    def test_constant_and_one_sided_groups_reconstruct(self):
        """A constant group and an all-positive group must dequantize to
        ~their values: the f32 zero-point row is NOT clipped to the code
        range (clipping it shifted such groups toward 0)."""
        import jax.numpy as jnp

        from dynamo_tpu.ops.q4_linear import (
            dequantize_q4,
            quantize_weight_q4,
        )

        const = jnp.full((256, 128), 3.0, jnp.float32)
        qw = quantize_weight_q4(const, 1)
        deq = np.asarray(dequantize_q4(qw["q4"], qw["qs4"], qw["qz4"]))
        np.testing.assert_allclose(deq, 3.0, rtol=1e-5)

        rng = np.random.default_rng(7)
        pos = jnp.asarray(rng.uniform(2.0, 4.0, (256, 128)), jnp.float32)
        qw = quantize_weight_q4(pos, 1)
        deq = np.asarray(dequantize_q4(qw["q4"], qw["qs4"], qw["qz4"]))
        # within half an LSB of the true values (range 2 / 15 codes)
        assert np.max(np.abs(deq - np.asarray(pos))) <= 2.0 / 15.0


    def test_non_divisible_k_rejected(self):
        import jax.numpy as jnp

        from dynamo_tpu.ops.q4_linear import quantize_weight_q4

        with pytest.raises(ValueError, match="group"):
            quantize_weight_q4(jnp.zeros((101, 128)), 1)


class TestQ4Matmul:
    def _case(self, m, k, n, seed=0):
        import jax.numpy as jnp

        from dynamo_tpu.ops.q4_linear import quantize_weight_q4

        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        return x, w, quantize_weight_q4(w, 1)

    @pytest.mark.parametrize("m,k,n", [
        (8, 512, 512),     # one k-step at group 256
        (1, 512, 512),     # M=1 decode row
        (3, 1024, 512),    # k-block of four groups
        (16, 1024, 128),   # lane-minimal N
        (33, 2048, 256),   # padded M, k-block of eight
        (33, 384, 1536),   # K not a multiple of 256: group 128; bn 512
        (16, 128, 128),    # K below the preferred group: one group
        (16, 512, 640),    # bn halves to 128: five column blocks
        # the dense cell's contraction depths (wq..up, the head; down)
        (1, 4096, 256),    # k-block of sixteen, one k step
        (33, 4096, 256),
        (8, 14336, 128),   # 56 groups: k-block of eight, seven k steps
    ])
    def test_kernel_matches_reference(self, m, k, n):
        from dynamo_tpu.ops.q4_linear import q4_matmul, q4_matmul_ref

        x, _, qw = self._case(m, k, n)
        ref = q4_matmul_ref(x, qw["q4"], qw["qs4"], qw["qz4"])
        out = q4_matmul(x, qw["q4"], qw["qs4"], qw["qz4"],
                        interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-3)

    @pytest.mark.parametrize("m,k,n", [
        (8, 512, 512),     # four scale rows, k-block of four
        (1, 1024, 256),    # a decode row, k-block of eight
        (33, 4096, 256),   # the cell's depth: 32 groups, one k step
    ])
    def test_kernel_matches_reference_at_group_128(self, m, k, n,
                                                   group128):
        from dynamo_tpu.ops.q4_linear import (
            _k_block_groups,
            q4_matmul,
            q4_matmul_ref,
        )

        x, _, qw = self._case(m, k, n, seed=12)
        assert qw["qs4"].shape[0] == k // 128
        assert _k_block_groups(k, 128) == min(32, k // 128)
        ref = q4_matmul_ref(x, qw["q4"], qw["qs4"], qw["qz4"])
        out = q4_matmul(x, qw["q4"], qw["qs4"], qw["qz4"],
                        interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-3)

    @pytest.mark.parametrize("k,want", [(256, 1), (512, 2), (1024, 4)])
    def test_k_block_rule(self, k, want):
        """The rule picks the groups a k step contracts (the largest
        power of two, 32 at most, whose blocks divide K), and the kernel
        it sizes matches the reference: one, two and four groups a
        step."""
        from dynamo_tpu.ops.q4_linear import (
            _k_block_groups,
            q4_matmul,
            q4_matmul_ref,
        )

        assert _k_block_groups(k, 256) == want
        x, _, qw = self._case(5, k, 256, seed=k)
        ref = q4_matmul_ref(x, qw["q4"], qw["qs4"], qw["qz4"])
        out = q4_matmul(x, qw["q4"], qw["qs4"], qw["qz4"],
                        interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-3)

    @pytest.mark.parametrize("edge", ["constant", "one-sided"])
    def test_zero_point_edge_through_the_kernel(self, edge):
        """A constant group (zero point ~ -lo / 1e-12) and an
        all-positive group (zero point outside the code range) beside an
        ordinary group: the kernel folds each group's zero point in an
        epilogue of its own, in float32, and must land where the
        reference does."""
        import jax.numpy as jnp

        from dynamo_tpu.ops.q4_linear import (
            q4_matmul,
            q4_matmul_ref,
            quantize_weight_q4,
        )

        rng = np.random.default_rng(7)
        first = (np.full((256, 128), 3.0) if edge == "constant"
                 else rng.uniform(3.0, 4.0, (256, 128)))
        w = jnp.asarray(np.concatenate(
            [first, rng.standard_normal((256, 128))]), jnp.float32)
        qw = quantize_weight_q4(w, 1)
        zero = np.asarray(qw["qz4"])
        assert (zero[0] < -15).all() and (np.abs(zero[1]) <= 15).all()
        x = jnp.asarray(rng.standard_normal((4, 512)), jnp.float32)
        ref = q4_matmul_ref(x, qw["q4"], qw["qs4"], qw["qz4"])
        out = q4_matmul(x, qw["q4"], qw["qs4"], qw["qz4"],
                        interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=2e-3)

    def test_matmul_error_bounded(self):
        """Output error vs exact is within the textbook per-group
        bound (measured against output rms, as in the q8 tests)."""
        from dynamo_tpu.ops.q4_linear import q4_matmul_ref

        x, w, qw = self._case(4, 512, 512)
        exact = np.asarray(x @ w)
        quant = np.asarray(q4_matmul_ref(x, qw["q4"], qw["qs4"],
                                         qw["qz4"]))
        # 4-bit LSB on N(0,1) weights: per-weight err sigma ~= s/sqrt(12)
        # ~= 0.12, accumulated over K=512 against output rms sqrt(K) ->
        # relative sigma ~0.12, p99 ~2.6 sigma.
        rel = np.abs(quant - exact) / np.sqrt(np.mean(exact ** 2))
        assert np.sqrt(np.mean(rel ** 2)) < 0.16
        assert np.percentile(rel, 99) < 0.38

    def test_einsum_specs(self):
        """Every dense-projection spec reshapes correctly (head
        projections keep out axes; wo stores flat because pack blocks
        span heads)."""
        import jax.numpy as jnp

        from dynamo_tpu.ops.q4_linear import (
            dequantize_q4,
            q4_einsum,
            quantize_weight_q4,
        )

        rng = np.random.default_rng(2)
        b, t, h, qh, hd, mdim = 2, 3, 512, 8, 128, 1024
        x = jnp.asarray(rng.standard_normal((b, t, h)), jnp.float32)
        for spec, wshape, nc in [
            ("bth,hm->btm", (h, mdim), 1),
            ("bth,hqd->btqd", (h, qh, hd), 1),
            ("bth,hkd->btkd", (h, 4, hd), 1),
            ("bth,hv->btv", (h, 1024), 1),
        ]:
            w = jnp.asarray(rng.standard_normal(wshape), jnp.float32)
            qw = quantize_weight_q4(w, nc)
            out = q4_einsum(spec, x, qw["q4"], qw["qs4"], qw["qz4"])
            deq = dequantize_q4(qw["q4"], qw["qs4"], qw["qz4"])
            ref = jnp.einsum(spec, x,
                             deq.reshape(wshape).astype(jnp.float32))
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)
        xo = jnp.asarray(rng.standard_normal((b, t, qh, hd)), jnp.float32)
        wo = jnp.asarray(rng.standard_normal((qh, hd, h)), jnp.float32)
        qo = quantize_weight_q4(wo, 2)
        assert qo["q4"].shape == (qh * hd // 2, h)
        out = q4_einsum("btqd,qdh->bth", xo, qo["q4"], qo["qs4"],
                        qo["qz4"])
        deq = dequantize_q4(qo["q4"], qo["qs4"], qo["qz4"])
        ref = jnp.einsum("btqd,qdh->bth", xo,
                         deq.reshape(qh, hd, h).astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


    def test_geometry_errors_are_value_errors(self):
        """Geometry validation raises explicit ValueError (survives
        python -O), matching the lane-divisibility error."""
        import jax.numpy as jnp

        from dynamo_tpu.ops.q4_linear import q4_matmul, quantize_weight_q4

        rng = np.random.default_rng(9)
        w = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
        qw = quantize_weight_q4(w, 1)
        x = jnp.asarray(rng.standard_normal((2, 512)), jnp.float32)
        with pytest.raises(ValueError, match="x columns"):
            q4_matmul(x[:, :256], qw["q4"], qw["qs4"], qw["qz4"],
                      interpret=True)
        with pytest.raises(ValueError, match="zero"):
            q4_matmul(x, qw["q4"], qw["qs4"], qw["qz4"][:1],
                      interpret=True)

    @pytest.mark.parametrize("entry", ["q4_matmul", "q4_einsum"])
    def test_a_packed_leaf_that_is_not_uint8_is_refused(self, entry):
        """A leaf from outside the program (a checkpoint written by
        another packer, a peer's stream) whose bytes are not uint8 is
        refused by name of its dtype, not multiplied."""
        import jax.numpy as jnp

        from dynamo_tpu.ops.q4_linear import (
            dequantize_q4,
            q4_einsum,
            q4_matmul,
        )

        x, _, qw = self._case(2, 512, 256)
        foreign = qw["q4"].astype(jnp.int8)
        with pytest.raises(ValueError, match="uint8.*int8"):
            if entry == "q4_matmul":
                q4_matmul(x, foreign, qw["qs4"], qw["qz4"],
                          interpret=True)
            else:
                q4_einsum("bth,hm->btm", x[None], foreign, qw["qs4"],
                          qw["qz4"])
        with pytest.raises(ValueError, match="uint8.*int8"):
            dequantize_q4(foreign, qw["qs4"], qw["qz4"])


def _prefetch_operands(jaxpr) -> list[int]:
    """Scalar-prefetch operand counts of every pallas_call under a
    jaxpr, nested calls included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["grid_mapping"].num_index_operands)
        for sub in eqn.params.values():
            for inner in (sub if isinstance(sub, (list, tuple)) else [sub]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found += _prefetch_operands(inner)
    return found


def _mask(rows, bucket, lengths):
    valid = np.zeros((rows, bucket), bool)
    for i, n in enumerate(lengths):
        valid[i, :n] = True
    return valid


def _block_map(valid):
    """The mask's `any` over each 256-row block of its flattened
    positions, in numpy."""
    return valid.reshape(-1, 256).any(axis=1).astype(np.int32).tolist()


# The dense cell's launches and scaled-down ones (256-row blocks):
# (rows, bucket, lengths, live blocks). A bucket under 1024 takes no map
# through live_row_blocks; the kernel is still handed one here.
LIVE_ROW_CASES = {
    "all-live": (2, 512, [512, 512], [1, 1, 1, 1]),
    "736-of-1024": (1, 1024, [736], [1, 1, 1, 0]),
    "remainder-beside-long-row": (2, 1024, [736, 160],
                                  [1, 1, 1, 0, 1, 0, 0, 0]),
    "three-rows-padded-to-four": (4, 1024, [736, 736, 576],
                                  [1, 1, 1, 0, 1, 1, 1, 0,
                                   1, 1, 1, 0, 0, 0, 0, 0]),
    "short-rows-padded-to-four": (4, 512, [368, 368, 288],
                                  [1, 1, 1, 1, 1, 1, 0, 0]),
    "ends-on-a-block": (2, 1024, [256, 1024], [1, 0, 0, 0, 1, 1, 1, 1]),
}


class TestQ4LiveRows:
    """q4_matmul told which row blocks hold a real position (PR 31): the
    others do no work and come back zero, the live ones are computed as
    without the map, bit for bit."""

    @pytest.mark.parametrize("group", [256, 128])
    @pytest.mark.parametrize("case", sorted(LIVE_ROW_CASES))
    def test_dead_blocks_are_zero_and_live_rows_unchanged(self, case,
                                                          group,
                                                          monkeypatch):
        import jax.numpy as jnp

        from dynamo_tpu.ops.q4_linear import (
            BLOCK_M,
            MAP_MIN_ROW,
            count_row_blocks,
            live_row_blocks,
            q4_matmul,
            quantize_weight_q4,
        )

        monkeypatch.setenv("DYNT_Q4_GROUP", str(group))
        rows, bucket, lengths, want = LIVE_ROW_CASES[case]
        valid = _mask(rows, bucket, lengths)
        assert _block_map(valid) == want
        live = live_row_blocks(jnp.asarray(valid))
        if bucket < MAP_MIN_ROW:  # the launch itself would take no map
            assert live is None
            assert count_row_blocks(lengths, rows, bucket) == (
                len(want), 0)
            live = jnp.asarray(want, jnp.int32)
        else:
            assert np.asarray(live).tolist() == want
            assert count_row_blocks(lengths, rows, bucket) == (
                sum(want), len(want) - sum(want))
        rng = np.random.default_rng(3)
        k, n = 512, 256
        # bf16 inputs, as served (float32 accumulation inside)
        x = jnp.asarray(rng.standard_normal((rows * bucket, k)),
                        jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        qw = quantize_weight_q4(w, 1)
        assert qw["qs4"].shape[0] == k // group
        plain = np.asarray(q4_matmul(x, qw["q4"], qw["qs4"], qw["qz4"],
                                     interpret=True), np.float32)
        keep = np.repeat(np.asarray(want, bool), BLOCK_M)
        # what a dead block holds reaches nothing
        x = jnp.where(keep[:, None], x, jnp.nan)
        out = np.asarray(q4_matmul(x, qw["q4"], qw["qs4"], qw["qz4"],
                                   interpret=True, live=live), np.float32)
        assert np.array_equal(out[keep], plain[keep])
        assert not out[~keep].any()
        assert not np.isnan(out).any()

    def test_short_rows_or_no_mask_are_the_kernel_without_a_map(self):
        """The decode programs do not change: a call whose rows are
        under 1024 positions, or without a mask, has no prefetch
        operand; a launch of longer rows has one."""
        import jax
        import jax.numpy as jnp

        from dynamo_tpu.ops.q4_linear import (
            live_row_blocks,
            q4_matmul,
            quantize_weight_q4,
        )

        for shape in ((32, 1), (1, 256), (4, 64), (4, 512)):
            assert live_row_blocks(jnp.ones(shape, bool)) is None
        rng = np.random.default_rng(4)
        w = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
        qw = quantize_weight_q4(w, 1)

        def call(x, live=None):
            return q4_matmul(x, qw["q4"], qw["qs4"], qw["qz4"],
                             interpret=True, live=live)

        x = jnp.zeros((1024, 512), jnp.float32)
        assert _prefetch_operands(jax.make_jaxpr(call)(x).jaxpr) == [0]
        assert _prefetch_operands(
            jax.make_jaxpr(call)(x[:32]).jaxpr) == [0]
        live = live_row_blocks(jnp.ones((1, 1024), bool))
        assert _prefetch_operands(
            jax.make_jaxpr(call)(x, live).jaxpr) == [1]
        with pytest.raises(ValueError, match="every row block"):
            call(x, live[:3])

    @pytest.mark.parametrize("group", [256, 128])
    @pytest.mark.parametrize("path", ["xla", "pallas"])
    def test_einsum_specs_with_rows_including_flat_wo(self, path, group,
                                                      monkeypatch):
        """Every projection spec hands the block map on, on the
        reference path and through the kernel alike: live rows as
        without it, the padded row zero."""
        import jax.numpy as jnp

        from dynamo_tpu.ops.q4_linear import (
            live_row_blocks,
            q4_einsum,
            quantize_weight_q4,
        )

        monkeypatch.setenv("DYNT_Q4_MATMUL", path)
        monkeypatch.setenv("DYNT_Q4_GROUP", str(group))
        rng = np.random.default_rng(5)
        b, t, h, qh, hd, mdim = 2, 1024, 512, 4, 128, 1024
        valid = _mask(b, t, [700])  # the second row is padding
        rows = live_row_blocks(jnp.asarray(valid))
        x = jnp.asarray(rng.standard_normal((b, t, h)), jnp.bfloat16)
        xo = jnp.asarray(rng.standard_normal((b, t, qh, hd)), jnp.bfloat16)
        for spec, lhs, wshape, nc in [
            ("bth,hm->btm", x, (h, mdim), 1),
            ("btm,mh->bth", x, (h, 256), 1),
            ("bth,hqd->btqd", x, (h, qh, hd), 1),
            ("bth,hkd->btkd", x, (h, 2, hd), 1),
            ("bth,hv->btv", x, (h, 1024), 1),
            ("btqd,qdh->bth", xo, (qh, hd, h), 2),
        ]:
            w = jnp.asarray(rng.standard_normal(wshape), jnp.float32)
            qw = quantize_weight_q4(w, nc)
            assert qw["qs4"].shape[0] == h // group
            plain = np.asarray(q4_einsum(spec, lhs, qw["q4"], qw["qs4"],
                                         qw["qz4"]), np.float32)
            out = np.asarray(q4_einsum(spec, lhs, qw["q4"], qw["qs4"],
                                       qw["qz4"], rows=rows), np.float32)
            assert np.array_equal(out[0, :768], plain[0, :768]), spec
            assert not out[0, 768:].any() and not out[1].any(), spec

    @pytest.mark.parametrize("lengths,rows,bucket,want", [
        ([736, 736, 576], 4, 1024, (9, 7)),  # the cell's commonest launch
        ([736], 1, 1024, (3, 1)),
        ([160], 1, 1024, (1, 3)),
        ([736, 160], 2, 1024, (4, 4)),
        ([1500, 700], 2, 2048, (9, 7)),
        # rows under 1024 positions: no map, every block is run
        ([40], 1, 64, (1, 0)),
        ([32] * 32, 32, 32, (4, 0)),
        ([100, 100, 100], 4, 128, (2, 0)),
        ([368, 368, 288], 4, 512, (8, 0)),
    ])
    def test_the_hosts_count_is_the_maps(self, lengths, rows, bucket,
                                         want):
        import jax.numpy as jnp

        from dynamo_tpu.ops.q4_linear import (
            count_row_blocks,
            live_row_blocks,
        )

        assert count_row_blocks(lengths, rows, bucket) == want
        live = live_row_blocks(jnp.asarray(_mask(rows, bucket, lengths)))
        if live is None:
            assert bucket < 1024 and want[1] == 0
        else:
            assert (int(live.sum()), int((live == 0).sum())) == want


class TestRunnerInt4Weights:
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

    def test_serving_loop_matches_dequantized_oracle(self):
        """The quantize->serve invariant: an int4 runner's greedy stream
        equals a bf16 runner serving the explicitly DEQUANTIZED weights
        (the two compute the same math; a plain bf16-vs-int4 comparison
        would only measure 4-bit noise on a random tiny model)."""
        import jax.numpy as jnp

        from dynamo_tpu.engine.model_runner import ModelRunner, RunnerConfig
        from dynamo_tpu.models import get_config as gc
        from dynamo_tpu.ops.q4_linear import dequantize_q4
        from dynamo_tpu.parallel import MeshConfig, make_mesh

        config = gc("tiny-test")
        r4 = self._runner("int4")

        def deq(leaf, orig_shape):
            w = dequantize_q4(leaf["q4"].reshape(leaf["q4"].shape[0], -1),
                              leaf["qs4"], leaf["qz4"])
            return np.asarray(w.reshape(orig_shape).astype(jnp.bfloat16))

        h, qh, kh, hd = (config.hidden, config.n_q_heads,
                         config.n_kv_heads, config.head_dim)
        m = config.mlp_hidden
        shapes = {"wq": (h, qh, hd), "wk": (h, kh, hd), "wv": (h, kh, hd),
                  "wo": (qh, hd, h), "w_gate": (h, m), "w_up": (h, m),
                  "w_down": (m, h)}
        params = {k: np.asarray(v) for k, v in r4.params.items()
                  if not isinstance(v, (dict, list))}
        params["layers"] = [
            {name: (deq(leaf, shapes[name]) if isinstance(leaf, dict)
                    else np.asarray(leaf))
             for name, leaf in layer.items()}
            for layer in r4.params["layers"]
        ]
        rd = ModelRunner(
            config,
            RunnerConfig(page_size=4, num_pages=64, max_batch=2,
                         max_pages_per_seq=16, prefill_buckets=(16, 32)),
            make_mesh(MeshConfig()),
            params=params,
            seed=0,
        )

        rng = np.random.default_rng(2)
        prompt = rng.integers(1, 500, 20).astype(np.int32)
        table = np.zeros(16, np.int32)
        table[:8] = np.arange(1, 9)
        outs = {}
        for key, r in (("int4", r4), ("oracle", rd)):
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
            outs[key] = toks
        # bf16 rounding of the dequantized weights vs the kernel's f32
        # dequant can flip a near-tie; demand near-total agreement.
        same = sum(a == b for a, b in zip(outs["int4"], outs["oracle"]))
        assert same >= len(outs["oracle"]) - 1, outs

    @pytest.mark.parametrize("group", [256, 128])
    def test_batched_prefill_skips_padding_and_matches_lone_prefills(
            self, group, monkeypatch):
        """Three rows of unlike lengths, padded to four, through the
        kernel with its block map: the tokens and the KV pages three lone
        prefills give (prefill_chunk_batch's promise), with eight row
        blocks run and eight skipped; the decode step after it is the
        program it was. At group 128 the MLP is widened to 256, so that
        w_down's contraction carries two scale rows into the same
        launches (tiny-test's own contractions are one group either
        way)."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        from dynamo_tpu.engine.model_runner import (
            ModelRunner,
            PrefillRow,
            RunnerConfig,
        )
        from dynamo_tpu.models.transformer import forward
        from dynamo_tpu.parallel import MeshConfig, make_mesh

        monkeypatch.setenv("DYNT_Q4_MATMUL", "pallas")  # the interpreter
        monkeypatch.setenv("DYNT_Q4_GROUP", str(group))

        config = get_config("tiny-test")
        if group == 128:
            config = dataclasses.replace(config, mlp_hidden=256)

        def runner():
            return ModelRunner(
                config,
                RunnerConfig(page_size=16, num_pages=128, max_batch=4,
                             max_pages_per_seq=64,
                             prefill_buckets=(64, 1024),
                             weight_dtype="int4"),
                make_mesh(MeshConfig()), seed=0)

        batched, lone = runner(), runner()
        assert batched.kernel_paths()["weight_matmul"] == "interpret"
        assert batched.params["layers"][0]["w_down"]["qs4"].shape[0] == \
            256 // group  # 128 rows in one group; 256 rows in two
        rng = np.random.default_rng(7)
        lengths = [600, 40, 1024]
        prompts = [rng.integers(1, 500, n).astype(np.int32)
                   for n in lengths]
        tables, first = [], 1
        for n in lengths:
            pages = -(-n // 16)
            table = np.zeros(64, np.int32)
            table[:pages] = np.arange(first, first + pages)
            tables.append(table)
            first += pages
        rows = [PrefillRow(p, 0, t, len(p), (0.0, 1.0, 0, i))
                for i, (p, t) in enumerate(zip(prompts, tables))]
        tokens = np.asarray(batched.prefill_chunk_batch(rows))[:3]
        assert batched.prefill_positions == 4 * 1024
        assert batched.prefill_row_blocks == {"live": 8, "skipped": 8}
        alone = [lone.prefill_chunk(*row[:5]) for row in rows]
        assert tokens.tolist() == alone
        # page 0 is the scratch sink padded positions write to
        assert np.array_equal(np.asarray(batched.cache[0][0])[:, :, 1:],
                              np.asarray(lone.cache[0][0])[:, :, 1:])
        # alone, 600 of 1024 skips one block; 40 of 64 takes no map
        assert lone.prefill_row_blocks == {"live": 3 + 1 + 4, "skipped": 1}

        def decode(r, tok, table, pos):
            return int(r.decode(
                np.array([tok], np.int32), np.array([pos], np.int32),
                table[None, :], np.array([pos + 1], np.int32),
                np.array([True]), np.zeros(1, np.float32),
                np.ones(1, np.float32), np.zeros(1, np.int32),
                np.zeros(1, np.uint32), np.array([0], np.int32))[0])

        assert decode(batched, int(tokens[0]), tables[0], 600) == \
            decode(lone, alone[0], tables[0], 600)
        assert batched._decode_fn._cache_size() == 1
        # and no decode-shaped call of forward carries a prefetch operand
        cfg = batched.model_config
        step = jnp.zeros((4, 1), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda p, kv, valid: forward(
                p, cfg, step, step, kv, jnp.zeros((4, 64), jnp.int32),
                jnp.ones(4, jnp.int32), valid=valid))(
            batched.params, *batched.cache[0], jnp.ones((4, 1), bool))
        counts = _prefetch_operands(jaxpr.jaxpr)
        assert counts and not any(counts)
        # while the launch above hands every projection its map
        wide = jnp.zeros((4, 1024), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda p, kv, valid: forward(
                p, cfg, wide, wide, kv, jnp.zeros((4, 64), jnp.int32),
                jnp.ones(4, jnp.int32), valid=valid))(
            batched.params, *batched.cache[0], jnp.ones((4, 1024), bool))
        assert _prefetch_operands(jaxpr.jaxpr) == [1] * 7 * cfg.n_layers

    def test_quantized_leaf_structure(self):
        r = self._runner("int4")
        layer = r.params["layers"][0]
        for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
            assert isinstance(layer[name], dict), name
            assert layer[name]["q4"].dtype == np.uint8
            assert layer[name]["qs4"].ndim == 2
        # wo flattens (pack blocks span heads); head projections keep
        # their out axes for the einsum reshape.
        assert layer["wo"].get("q4").ndim == 2
        assert layer["wq"]["q4"].ndim == 3
        assert not isinstance(layer["attn_norm"], dict)
        assert not isinstance(r.params["embed"], dict)

    def test_int4_rejects_non_dense_families(self):
        from dynamo_tpu.models.quantize import check_quantizable

        with pytest.raises(ValueError, match="int4"):
            check_quantizable(get_config("tiny-mla-test"), dtype="int4")
        with pytest.raises(ValueError, match="single-device"):
            check_quantizable(get_config("tiny-test"), tp=2,
                              dtype="int4")


class TestRunnerPackedTree:
    """A tree that arrives already packed (a checkpoint, the weight
    service, a peer's stream) is placed as it comes, as an int8 tree is,
    and refused when a packed leaf is not what this program packs."""

    def _runner(self, params=None):
        from dynamo_tpu.engine.model_runner import ModelRunner, RunnerConfig
        from dynamo_tpu.models.config import ModelConfig
        from dynamo_tpu.parallel import MeshConfig, make_mesh

        # Every contraction (512 = hidden = qh*hd = mlp) holds two
        # groups; tiny everywhere else.
        config = ModelConfig(
            name="tiny-q4-test", vocab_size=512, hidden=512,
            n_layers=1, n_q_heads=4, n_kv_heads=2, head_dim=128,
            mlp_hidden=512, max_context=2048)
        return ModelRunner(
            config,
            RunnerConfig(page_size=4, num_pages=64, max_batch=2,
                         max_pages_per_seq=16, prefill_buckets=(16,),
                         weight_dtype="int4"),
            make_mesh(MeshConfig()),
            params=params,
            seed=0,
        )

    def _host_tree(self, runner):
        return {
            "embed": np.asarray(runner.params["embed"]),
            "final_norm": np.asarray(runner.params["final_norm"]),
            "layers": [{
                name: ({k: np.asarray(v) for k, v in leaf.items()}
                       if isinstance(leaf, dict) else np.asarray(leaf))
                for name, leaf in runner.params["layers"][0].items()
            }],
        }

    def _greedy(self, runner, prompt, steps=4):
        table = np.zeros(16, np.int32)
        table[:8] = np.arange(1, 9)
        tok = runner.prefill_chunk(prompt, 0, table, len(prompt),
                                   (0.0, 1.0, 0, 0))
        toks = [tok]
        for i in range(steps):
            pos = len(prompt) + i
            nxt = runner.decode(
                np.array([tok], np.int32), np.array([pos], np.int32),
                table[None, :], np.array([pos + 1], np.int32),
                np.array([True]), np.zeros(1, np.float32),
                np.ones(1, np.float32), np.zeros(1, np.int32),
                np.zeros(1, np.uint32), np.array([i], np.int32))
            tok = int(nxt[0])
            toks.append(tok)
        return toks

    def test_a_quantized_tree_is_placed_as_it_comes(self):
        first = self._runner()
        host = self._host_tree(first)
        second = self._runner(params=host)
        for name in ("wq", "wo", "w_down"):
            for part in ("q4", "qs4", "qz4"):
                np.testing.assert_array_equal(
                    np.asarray(second.params["layers"][0][name][part]),
                    host["layers"][0][name][part])
        rng = np.random.default_rng(6)
        prompt = rng.integers(1, 500, 12).astype(np.int32)
        assert self._greedy(first, prompt) == self._greedy(second, prompt)

    def test_a_tree_with_a_leaf_that_is_not_uint8_is_refused(self):
        host = self._host_tree(self._runner())
        wo = host["layers"][0]["wo"]
        wo["q4"] = wo["q4"].view(np.int8)
        with pytest.raises(ValueError, match=r"layers.*0.*wo.*uint8.*int8"):
            self._runner(params=host)
