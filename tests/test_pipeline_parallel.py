"""Pipeline parallelism (GPipe over the pp mesh axis): schedule
correctness on the virtual mesh and equivalence with the dense forward
(ref surface: SURVEY §2.5 PP — the reference delegates to vLLM multi-node;
we own the pipeline)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.models import forward, get_config, init_params, make_kv_cache
from dynamo_tpu.models.transformer import make_pp_prefill
from dynamo_tpu.parallel import MeshConfig, make_mesh

# The whole pp plane is built on jax.shard_map (the gpipe loop shards
# microbatches over the pp mesh axis).


def _inputs(m=2, mb=2, t=8, vocab=512, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(1, vocab, (m, mb, t)).astype(np.int32)
    positions = np.broadcast_to(np.arange(t, dtype=np.int32),
                                (m, mb, t)).copy()
    valid = np.ones((m, mb, t), bool)
    valid[0, 0, t - 2:] = False  # one ragged microbatch
    return tokens, positions, valid


class TestGpipeLoop:
    def test_plain_loop_identity_stage(self):
        """With an identity-ish stage, the pipeline must deliver every
        microbatch unchanged in order regardless of pp."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from dynamo_tpu.ops.pipeline import gpipe_stage_loop

        mesh = make_mesh(MeshConfig(pp=4))
        micro = jnp.arange(4 * 3 * 2, dtype=jnp.float32).reshape(4, 3, 2)
        weights = jnp.ones((4, 1), jnp.float32) * 2.0  # one layer per stage

        def stage(w, act):
            return act * w[0]

        out = shard_map(
            lambda w, x: gpipe_stage_loop(stage, w, x, axis_name="pp"),
            mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
        )(weights, micro)
        # 4 stages each multiply by 2 -> x * 16
        np.testing.assert_allclose(np.asarray(out), np.asarray(micro) * 16)


class TestPpPrefill:
    @pytest.mark.parametrize("pp", [1, 2])
    def test_pp_matches_dense_forward(self, pp):
        """Pipeline prefill logits and K/V must match the unified forward
        (paged path) for every microbatch — pp=1 validates the math, pp=2
        validates the schedule. float32 so XLA's scan-vs-loop fusion
        reordering cannot blur the comparison (bf16 rounding differs
        between compiled scan and eager layer loops)."""
        import dataclasses as dc

        config = dc.replace(get_config("tiny-test"), dtype="float32")
        mesh = make_mesh(MeshConfig(pp=pp))
        params = jax.device_put(
            init_params(jax.random.PRNGKey(0), config))
        m, mb, t = 2, 2, 8
        tokens, positions, valid = _inputs(m=m, mb=mb, t=t)
        fn = make_pp_prefill(config, mesh, n_micro=m)
        logits, ks, vs = fn(params, jnp.asarray(tokens),
                            jnp.asarray(positions), jnp.asarray(valid))
        assert logits.shape == (m, mb, t, config.vocab_size)
        assert ks.shape == (config.n_layers, m, mb, t,
                            config.n_kv_heads, config.head_dim)

        # dense reference per microbatch via the paged forward
        for mi in range(m):
            kv = make_kv_cache(config, 64, 4)
            tables = np.zeros((mb, 16), np.int32)
            for b in range(mb):
                tables[b, :2] = [1 + 2 * b, 2 + 2 * b]
            kv_lens = np.asarray(valid[mi].sum(axis=1), np.int32)
            kv2, ref_logits = forward(
                params, config, jnp.asarray(tokens[mi]),
                jnp.asarray(positions[mi]), kv, jnp.asarray(tables),
                jnp.asarray(kv_lens), valid=jnp.asarray(valid[mi]))
            got = np.asarray(logits[mi])
            want = np.asarray(ref_logits)
            vmask = valid[mi]
            np.testing.assert_allclose(got[vmask], want[vmask],
                                       rtol=1e-4, atol=1e-4)
            # greedy decisions identical at every valid position
            np.testing.assert_array_equal(
                np.argmax(got[vmask], -1), np.argmax(want[vmask], -1))

    def test_pp_with_tp_combined(self):
        """pp x tp mesh: REAL tp sharding inside stages (local heads +
        psum) must agree with pp-only up to f32 reduction reordering, and
        the per-rank KV stacks must reassemble to the full head set."""
        import dataclasses as dc

        config = dc.replace(get_config("tiny-test"), dtype="float32")
        params = jax.device_put(init_params(jax.random.PRNGKey(0), config))
        tokens, positions, valid = _inputs()
        fn_a = make_pp_prefill(config, make_mesh(MeshConfig(pp=2)), 2)
        fn_b = make_pp_prefill(config, make_mesh(MeshConfig(pp=2, tp=2)), 2)
        la, ka, va = fn_a(params, jnp.asarray(tokens),
                          jnp.asarray(positions), jnp.asarray(valid))
        lb, kb, vb = fn_b(params, jnp.asarray(tokens),
                          jnp.asarray(positions), jnp.asarray(valid))
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_array_equal(np.argmax(np.asarray(la), -1),
                                      np.argmax(np.asarray(lb), -1))
        assert kb.shape == ka.shape  # tp shards reassemble to full heads
        np.testing.assert_allclose(np.asarray(ka), np.asarray(kb),
                                   rtol=1e-3, atol=1e-3)

    def test_rejects_unsupported_families(self):
        mesh = make_mesh(MeshConfig(pp=2))
        with pytest.raises(AssertionError, match="dense-GQA"):
            make_pp_prefill(get_config("tiny-moe-test"), mesh, 2)
        with pytest.raises(AssertionError, match="divide"):
            import dataclasses as dc

            odd = dc.replace(get_config("tiny-test"), n_layers=3)
            make_pp_prefill(odd, mesh, 2)(
                init_params(jax.random.PRNGKey(0), odd),
                jnp.zeros((1, 1, 8), jnp.int32),
                jnp.zeros((1, 1, 8), jnp.int32),
                jnp.ones((1, 1, 8), bool))
