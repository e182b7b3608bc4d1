"""`ops/grouped_matmul.dropless_experts` against a loop over tokens and
experts in numpy float32: the sort, the histogram and the combine, with
experts held elsewhere, rows that route nowhere and one expert every
token chose. On the CPU (`path="xla"`: `jax.lax.ragged_dot`); what the
TPU's compiler makes of the combine is `tests/test_tpu_compile.py`'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import grouped_matmul
from dynamo_tpu.ops.grouped_matmul import dropless_experts

T, H, M, EXPERTS, HELD, EVERYONES = 24, 32, 16, 12, (3, 9), 5


def _relu2(u):
    return jnp.square(jax.nn.relu(u))


def _case(k, seed=0):
    """Inputs in float32: every token's first choice is expert
    `EVERYONES` (held), the rest of its top-k are distinct others, half
    of them held by another chip; every fifth row is padding."""
    rng = np.random.default_rng(seed + k)
    others = np.array([e for e in range(EXPERTS) if e != EVERYONES])
    topi = np.stack([
        np.concatenate([[EVERYONES], rng.permutation(others)[:k - 1]])
        for _ in range(T)]).astype(np.int32)
    for row in topi:  # the router's order is by weight, not by id
        rng.shuffle(row)
    weights = rng.uniform(0.05, 1.0, (T, k)).astype(np.float32)
    valid = np.arange(T) % 5 != 4
    lo, hi = HELD
    x = rng.standard_normal((T, H)).astype(np.float32)
    w_up = rng.standard_normal((hi - lo, M, H)).astype(np.float32) / 4
    w_down = rng.standard_normal((hi - lo, M, H)).astype(np.float32) / 4
    return x, weights, topi, valid, w_up, w_down


def _loop(x, weights, topi, valid, w_up, w_down):
    lo, hi = HELD
    out = np.zeros((T, H), np.float32)
    counts = np.zeros(hi - lo, np.int32)
    for t in range(T):
        if not valid[t]:
            continue
        for j in range(topi.shape[1]):
            e = topi[t, j] - lo
            if not 0 <= e < hi - lo:
                continue
            counts[e] += 1
            mid = np.square(np.maximum(w_up[e] @ x[t], 0), dtype=np.float32)
            out[t] += weights[t, j] * (mid @ w_down[e])
    return out, counts


def _run(case):
    return jax.jit(lambda *a: dropless_experts(
        *a, HELD, _relu2, path="xla"))(*map(jnp.asarray, case))


@pytest.mark.parametrize("k", [4, 6, 8, 10])
def test_the_experts_agree_with_a_loop_over_tokens_and_experts(k):
    case = _case(k)
    out, counts, dropped = _run(case)
    want, want_counts = _loop(*case)
    assert out.dtype == jnp.float32 and out.shape == (T, H)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    valid = case[3]
    assert want_counts[EVERYONES - HELD[0]] == valid.sum()  # every token
    assert want_counts.sum() < valid.sum() * k  # some went elsewhere
    assert int(dropped) == 0
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)
    assert not np.asarray(out)[~valid].any()  # padding adds nothing


@pytest.mark.parametrize("k", [4, 10])
def test_rows_past_the_groups_are_selected_away_not_weighed_by_zero(
        monkeypatch, k):
    """`expert_gmm` leaves the rows past `sum(group_sizes)` undefined;
    filled with NaN here, nothing of them may reach the sum (0 x NaN is
    NaN: a zero weight would not do)."""
    real = grouped_matmul.expert_gmm

    def poisoned(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        past = jnp.arange(out.shape[0]) >= jnp.sum(group_sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(grouped_matmul, "expert_gmm", poisoned)
    case = _case(k, seed=7)
    out, counts, dropped = _run(case)
    want, want_counts = _loop(*case)
    assert want_counts.sum() < T * k  # there ARE rows past the groups
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    assert int(dropped) == 0
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)


def _bf16(a):
    """`a` rounded to bfloat16, as float32."""
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))


def test_the_sum_is_float32_and_rounded_to_the_inputs_type_once():
    """bf16 in: the down-projection's rows stay float32 through the
    weighted sum and the result is cast once, so it is the float32
    result of the same bf16-rounded inputs, to a bf16 rounding. (`mid`
    is rounded to bf16 between the matmuls, upstream of the combine, as
    before: the loop does the same.)"""
    x, weights, topi, valid, w_up, w_down = _case(10, seed=3)
    x, w_up, w_down = _bf16(x), _bf16(w_up), _bf16(w_down)
    out, _, _ = jax.jit(lambda *a: dropless_experts(
        *a, HELD, _relu2, path="xla"))(
            jnp.asarray(x, jnp.bfloat16), jnp.asarray(weights),
            jnp.asarray(topi), jnp.asarray(valid),
            jnp.asarray(w_up, jnp.bfloat16), jnp.asarray(w_down, jnp.bfloat16))
    assert out.dtype == jnp.bfloat16
    lo, hi = HELD
    want = np.zeros((T, H), np.float32)
    for t in range(T):
        for j in range(topi.shape[1]):
            e = topi[t, j] - lo
            if valid[t] and 0 <= e < hi - lo:
                mid = _bf16(np.square(np.maximum(w_up[e] @ x[t], 0)))
                want[t] += weights[t, j] * (mid @ w_down[e])
    # one bf16 rounding of the sum (2^-8 relative) and the ulp or two by
    # which a float32 product lands on the other side of mid's rounding
    assert (np.abs(np.asarray(out.astype(jnp.float32)) - want).max()
            <= np.abs(want).max() * 2.0 ** -7)
