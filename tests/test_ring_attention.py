"""Ring attention (sequence parallelism over sp) vs the full-sequence oracle,
on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from dynamo_tpu.ops.ring_attention import (
    ring_attention,
    ring_attention_reference,
)


def _mesh(sp):
    return Mesh(np.array(jax.devices()[:sp]).reshape(sp), ("sp",))


def _rand_qkv(key, b, t, qh, kh, hd):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, qh, hd), jnp.float32)
    k = jax.random.normal(kk, (b, t, kh, hd), jnp.float32)
    v = jax.random.normal(kv, (b, t, kh, hd), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("sp", [2, 4, 8])
@pytest.mark.parametrize("qh,kh", [(4, 4), (8, 2)])
def test_matches_full_attention(sp, qh, kh):
    b, t, hd = 2, 32, 16  # t is the FULL sequence; each shard gets t/sp
    assert t % sp == 0
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), b, t, qh, kh, hd)
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))

    want = ring_attention_reference(q, k, v, pos, pos)

    mesh = _mesh(sp)
    shard = P(None, "sp")
    fn = shard_map(
        lambda *a: ring_attention(*a, axis_name="sp"),
        mesh=mesh,
        in_specs=(P(None, "sp", None, None), P(None, "sp", None, None),
                  P(None, "sp", None, None), shard, shard),
        out_specs=P(None, "sp", None, None),
    )
    got = fn(q, k, v, pos, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_padding_keys_are_masked():
    sp, b, t, qh, kh, hd = 4, 1, 16, 4, 4, 8
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), b, t, qh, kh, hd)
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    valid = pos < 10  # last 6 tokens are padding

    want = ring_attention_reference(q, k, v, pos, pos, valid)

    mesh = _mesh(sp)
    s2, s4 = P(None, "sp"), P(None, "sp", None, None)
    fn = shard_map(
        lambda *a: ring_attention(*a, axis_name="sp"),
        mesh=mesh,
        in_specs=(s4, s4, s4, s2, s2, s2),
        out_specs=s4,
    )
    got = fn(q, k, v, pos, pos, valid)
    # Compare only valid query rows (padding queries attend to nothing
    # meaningful; engines never read them).
    gv = np.asarray(got)[:, :10]
    wv = np.asarray(want)[:, :10]
    np.testing.assert_allclose(gv, wv, rtol=2e-5, atol=2e-5)


def test_arbitrary_position_split():
    """Causality must follow GLOBAL positions even if shards hold
    non-contiguous position ranges (e.g. striped layouts)."""
    sp, b, t, qh, kh, hd = 2, 1, 8, 2, 2, 8
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), b, t, qh, kh, hd)
    # striped: shard0 holds even positions, shard1 odd.
    perm = jnp.concatenate([jnp.arange(0, t, 2), jnp.arange(1, t, 2)])
    pos = jnp.broadcast_to(perm, (b, t))

    want = ring_attention_reference(q, k, v, pos, pos)

    mesh = _mesh(sp)
    s2, s4 = P(None, "sp"), P(None, "sp", None, None)
    fn = shard_map(
        lambda *a: ring_attention(*a, axis_name="sp"),
        mesh=mesh,
        in_specs=(s4, s4, s4, s2, s2),
        out_specs=s4,
    )
    got = fn(q, k, v, pos, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
