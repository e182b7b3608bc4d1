"""The Pallas chunked-scan kernel (`ops/ssm.ssm_chunk_scan_kernel`) under
the interpreter on the CPU, against the XLA form it replaces on the chip
(`ssm_chunk_scan`) and against the recurrence a position at a time, at
the two cells' geometry scaled down in heads only: head_dim 64 (two
heads a 128-lane tile), state 128, chunks of 128; all heads in ONE group
(granite) or 8 heads a group (nemotron).

One parametrised test: every case counts. Tolerances on values of spread
~10-100: the kernel and the XLA form round the same operands at the same
places (float32 here), so they agree to float32 sums in another order.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import get_config
from dynamo_tpu.ops.ssm import (
    expand_groups,
    scan_kernel_tiles,
    ssm_chunk_scan,
    ssm_chunk_scan_kernel,
    ssm_state_update_xla,
)

P, N, CHUNK = 64, 128, 128
# heads, groups: all heads share one C B^T | 8 heads a group | two grid
# steps of 16 heads over one group
GEOMETRY = {"granite": (16, 1), "nemotron": (16, 2), "two-blocks": (32, 1)}


def operands(rows, t, heads, groups, dtype=jnp.float32, seed=0):
    """(state, dt, a, xbc, valid): a non-zero state going in; row 0 full,
    row 1 padded from position t - 91 on, row 2 all padding, row 3 padded
    from position 5 on (dt = 0 there, as `mamba_prefill` hands it)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    state = jax.random.normal(ks[0], (rows, heads, P, N))
    n_valid = jnp.asarray([t, t - 91, 0, 5][:rows])
    valid = jnp.arange(t)[None] < n_valid[:, None]
    dt = jnp.where(valid[..., None], jax.nn.softplus(
        jax.random.normal(ks[1], (rows, t, heads)) - 2), 0.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=-1.0,
                                    maxval=2.0))
    xbc = jax.random.normal(
        ks[3], (rows, t, heads * P + 2 * groups * N)).astype(dtype)
    return state, dt, a, xbc, n_valid


def split(xbc, heads, groups):
    rows, t, _ = xbc.shape
    inner, gn = heads * P, groups * N
    return (xbc[..., :inner].reshape(rows, t, heads, P),
            xbc[..., inner:inner + gn].reshape(rows, t, groups, N),
            xbc[..., inner + gn:].reshape(rows, t, groups, N))


def kernel(state, dt, a, xbc, heads, groups):
    block = scan_kernel_tiles(xbc.shape[1], heads, P, groups, N, CHUNK)
    assert block in (8, 16) and (heads // groups) % block == 0
    return ssm_chunk_scan_kernel(state, dt, a, xbc, chunk=CHUNK,
                                 heads_per_block=block, interpret=True)


def xla_form(state, dt, a, xbc, heads, groups):
    final, y = ssm_chunk_scan(state, dt, a, *split(xbc, heads, groups),
                              chunk=CHUNK)
    return final, y.reshape(*xbc.shape[:2], heads * P)


def recurrence(state, dt, a, xbc, heads, groups, n_valid):
    """The decode update a position at a time; a row stops at its last
    real position: (its state there, y [rows, T, H*P])."""
    x, b, c = split(xbc.astype(jnp.float32), heads, groups)

    def step(s, inputs):
        i, dt_i, x_i, b_i, c_i = inputs
        return ssm_state_update_xla(
            s, dt_i, a, x_i, expand_groups(b_i, heads),
            expand_groups(c_i, heads), i < n_valid)

    final, y = jax.lax.scan(step, state, (
        jnp.arange(xbc.shape[1]), *(jnp.moveaxis(v, 1, 0)
                                    for v in (dt, x, b, c))))
    return final, jnp.moveaxis(y, 0, 1).reshape(*xbc.shape[:2], heads * P)


def same(case):
    """Kernel == XLA form == recurrence: carried state, padded rows."""
    geometry, rows, t = case
    heads, groups = GEOMETRY[geometry]
    state, dt, a, xbc, n_valid = operands(rows, t, heads, groups)
    got_s, got_y = kernel(state, dt, a, xbc, heads, groups)
    assert got_y.dtype == jnp.float32 and got_s.dtype == state.dtype
    want_s, want_y = xla_form(state, dt, a, xbc, heads, groups)
    np.testing.assert_allclose(got_s, want_s, atol=1e-4)
    np.testing.assert_allclose(got_y, want_y, atol=2e-3)
    # a row padded from position k on ends in its state after k positions
    # (the all-padding row in the state it came with), and reads the
    # recurrence's y at every real position
    step_s, step_y = recurrence(state, dt, a, xbc, heads, groups, n_valid)
    np.testing.assert_allclose(got_s, step_s, atol=2e-3)
    real = (jnp.arange(t)[None] < n_valid[:, None])[..., None]
    np.testing.assert_allclose(jnp.where(real, got_y - step_y, 0.0), 0.0,
                               atol=2e-2)
    if rows > 2:
        np.testing.assert_array_equal(got_s[2], state[2])


def bf16(case):
    """Activations as served: the kernel rounds the [L, L] weights and
    `to_end x` to bfloat16 where the XLA form does, nowhere else."""
    geometry, rows, t = case
    heads, groups = GEOMETRY[geometry]
    state, dt, a, xbc, _ = operands(rows, t, heads, groups, jnp.bfloat16)
    got_s, got_y = kernel(state, dt, a, xbc, heads, groups)
    want_s, want_y = xla_form(state, dt, a, xbc, heads, groups)
    np.testing.assert_allclose(got_s, want_s, atol=1e-4)
    np.testing.assert_allclose(got_y, want_y, atol=2e-3)


def two_launches(case):
    """A prompt prefilled in two launches (the state carried between
    them) equals one launch of both, at the kernel."""
    geometry, rows, t = case
    heads, groups = GEOMETRY[geometry]
    state, dt, a, xbc, _ = operands(rows, t, heads, groups)
    dt = jnp.abs(dt) + 0.01  # no padding inside a prompt
    whole_s, whole_y = kernel(state, dt, a, xbc, heads, groups)
    cut = CHUNK
    mid, first_y = kernel(state, dt[:, :cut], a, xbc[:, :cut], heads, groups)
    got_s, rest_y = kernel(mid, dt[:, cut:], a, xbc[:, cut:], heads, groups)
    np.testing.assert_allclose(got_s, whole_s, atol=1e-4)
    np.testing.assert_allclose(
        jnp.concatenate([first_y, rest_y], axis=1), whole_y, atol=2e-3)


def refused(case):
    """Shapes the kernel has no tiling for: `scan_kernel_tiles` says
    None, and `mamba_prefill` told to take the kernel's path then runs
    the XLA form (the tiny presets' shapes are such: same outputs)."""
    t, heads, head_dim, groups, state, chunk = case
    assert scan_kernel_tiles(t, heads, head_dim, groups, state,
                             chunk) is None
    from dynamo_tpu.models.hybrid import mamba_prefill
    from dynamo_tpu.models.transformer import init_layer_params

    config = dataclasses.replace(
        get_config("tiny-hybrid-test"), dtype="float32", mamba_heads=heads,
        mamba_head_dim=head_dim, ssm_groups=groups, ssm_state=state,
        ssm_chunk=chunk)
    lp = init_layer_params(jax.random.PRNGKey(0), config, 0)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    args = (jax.random.normal(ks[0], (2, t, config.hidden)), lp, config,
            jax.random.normal(ks[1], (2, config.conv_kernel - 1,
                                      config.mamba_conv_dim)),
            jax.random.normal(ks[2], (2, heads, head_dim, state)),
            jnp.arange(t)[None] < jnp.asarray([[t], [t // 2]]))
    for got, want in zip(mamba_prefill(*args, ssm_path="interpret"),
                         mamba_prefill(*args, ssm_path="xla")):
        np.testing.assert_array_equal(got, want)


def in_the_layer(case):
    """`mamba_prefill` on the kernel's path (a cut of the layer at the
    cell's head_dim, state and chunk) against the XLA form's: the out
    projection's output, the conv's carry and the state, rows padded."""
    heads, groups, rows, t = case
    from dynamo_tpu.models.hybrid import mamba_prefill
    from dynamo_tpu.models.transformer import init_layer_params

    config = dataclasses.replace(
        get_config("tiny-hybrid-test"), dtype="float32", mamba_heads=heads,
        mamba_head_dim=P, ssm_groups=groups, ssm_state=N, ssm_chunk=CHUNK)
    assert scan_kernel_tiles(t, heads, P, groups, N, CHUNK) is not None
    lp = init_layer_params(jax.random.PRNGKey(0), config, 0)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    n_valid = jnp.asarray([t, t - 91, 0, 5][:rows])
    args = (jax.random.normal(ks[0], (rows, t, config.hidden)), lp, config,
            jax.random.normal(ks[1], (rows, config.conv_kernel - 1,
                                      config.mamba_conv_dim)),
            jax.random.normal(ks[2], (rows, heads, P, N)),
            jnp.arange(t)[None] < n_valid[:, None])
    got = mamba_prefill(*args, ssm_path="interpret")
    want = mamba_prefill(*args, ssm_path="xla")
    assert float(jnp.std(want[0])) > 0.1
    np.testing.assert_allclose(got[0], want[0], atol=1e-4)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_allclose(got[2], want[2], atol=1e-4)


CASES = {
    # kernel == XLA form == recurrence: geometry x rows x positions
    "same-granite-1x128": (same, ("granite", 1, 128)),
    "same-granite-2x384": (same, ("granite", 2, 384)),
    "same-granite-4x256": (same, ("granite", 4, 256)),
    "same-nemotron-1x384": (same, ("nemotron", 1, 384)),
    "same-nemotron-2x128": (same, ("nemotron", 2, 128)),
    "same-nemotron-4x256": (same, ("nemotron", 4, 256)),
    "same-two-blocks-2x256": (same, ("two-blocks", 2, 256)),
    "bf16-granite-2x256": (bf16, ("granite", 2, 256)),
    "bf16-nemotron-1x256": (bf16, ("nemotron", 1, 256)),
    "two-launches-granite": (two_launches, ("granite", 2, 384)),
    "two-launches-nemotron": (two_launches, ("nemotron", 1, 256)),
    # (t, heads, head_dim, groups, state, chunk) without a tiling
    "refused-head-dim-16": (refused, (32, 4, 16, 2, 32, 16)),
    "refused-head-dim-128": (refused, (128, 8, 128, 1, 128, 128)),
    "refused-4-heads-a-group": (refused, (128, 8, 64, 2, 128, 128)),
    "refused-12-heads-a-group": (refused, (128, 12, 64, 1, 128, 128)),
    "refused-state-64": (refused, (128, 8, 64, 1, 64, 128)),
    "refused-chunk-64": (refused, (128, 8, 64, 1, 128, 64)),
    "refused-launch-under-a-chunk": (refused, (64, 8, 64, 1, 128, 128)),
    # (heads, groups, rows, t) through `mamba_prefill`
    "layer-granite-2x256": (in_the_layer, (16, 1, 2, 256)),
    "layer-nemotron-4x128": (in_the_layer, (16, 2, 4, 128)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_scan_kernel(case):
    check, args = CASES[case]
    check(args)


def test_the_runner_counts_which_path_its_prefill_scans_took(monkeypatch):
    """`ModelRunner` counts a launch under `kernel` where DYNT_SSM names
    the kernel and `scan_kernel_tiles` admits the bucket, else `xla`
    (dynamo_ssm_scan_launches_total), states the slot at start-up
    (`ssm_scan` beside `ssm_update`) and serves the XLA path's token."""
    from dynamo_tpu.engine import ModelRunner, RunnerConfig
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    config = dataclasses.replace(
        get_config("tiny-hybrid-test"), dtype="float32", layer_pattern="M*E",
        n_layers=3, mamba_heads=8, mamba_head_dim=P, ssm_groups=1,
        ssm_state=N, ssm_chunk=CHUNK, max_context=512)

    def runner(buckets=(128,)):
        return ModelRunner(
            config, RunnerConfig(page_size=16, num_pages=40, max_batch=2,
                                 max_pages_per_seq=16,
                                 prefill_buckets=buckets),
            make_mesh(MeshConfig()), seed=0)

    def prefill(r, n):
        prompt = np.random.default_rng(n).integers(0, 512, n)
        table = np.zeros(16, np.int32)
        table[:12] = 1 + np.arange(12)
        return r.prefill_chunk(prompt.astype(np.int32), 0, table, n,
                               (0.0, 1.0, 0, 0), slot=1)

    xla = runner()
    assert xla.kernel_paths()["ssm_scan"] == "xla"
    want = [prefill(xla, 100), prefill(xla, 40)]
    assert xla.ssm_scan_launches == {"kernel": 0, "xla": 2}
    monkeypatch.setenv("DYNT_SSM", "pallas")
    kernel_runner = runner()
    assert kernel_runner.kernel_paths()["ssm_scan"] == "interpret"
    assert kernel_runner.ssm_scan_tiles(128) == 8
    assert [prefill(kernel_runner, 100), prefill(kernel_runner, 40)] == want
    assert kernel_runner.ssm_scan_launches == {"kernel": 2, "xla": 0}
    # a launch under one chunk (a runner whose only bucket is): the XLA
    # form, and counted so
    short = runner(buckets=(64,))
    assert short.ssm_scan_tiles(64) is None
    prefill(short, 40)
    assert short.ssm_scan_launches == {"kernel": 0, "xla": 1}
