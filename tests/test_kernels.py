"""Per-kernel validation: hypothesis sweeps over shapes/dtypes, allclose
against the pure-jnp oracle in kernels/ref.py (interpret=True on CPU).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep; see requirements-dev.txt
from hypothesis import given, settings, strategies as st

import _oracles
from repro.kernels import ops, ref


@pytest.mark.slow
@settings(deadline=None, max_examples=30)
@given(
    d=st.integers(1, 5000),
    h=st.floats(1e-4, 10.0),
    seed=st.integers(0, 2**30),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_fsvrg_update_matches_ref(d, h, seed, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    w = jax.random.normal(ks[0], (d,), dtype)
    s = jnp.abs(jax.random.normal(ks[1], (d,), dtype)) + 0.1
    gn = jax.random.normal(ks[2], (d,), dtype)
    go = jax.random.normal(ks[3], (d,), dtype)
    gb = jax.random.normal(ks[4], (d,), dtype)
    out_k = ops.fsvrg_update(w, s, gn, go, gb, h)
    out_r = ref.fsvrg_update_ref(w, s, gn, go, gb, h)
    assert out_k.dtype == w.dtype
    tol = 1e-6 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol * (1.0 + 10 * h))


@pytest.mark.slow
@settings(deadline=None, max_examples=20)
@given(
    K=st.integers(1, 24),
    d=st.integers(1, 3000),
    seed=st.integers(0, 2**30),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_scaled_aggregate_matches_ref(K, d, seed, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    wt = jax.random.normal(ks[0], (d,), dtype)
    wks = jax.random.normal(ks[1], (K, d), dtype)
    wts = jax.nn.softmax(jax.random.normal(ks[2], (K,)))
    a = jnp.abs(jax.random.normal(ks[3], (d,))) + 0.5
    out_k = ops.scaled_aggregate(wt, wks, wts, a)
    out_r = ref.scaled_aggregate_ref(wt, wks, wts, a)
    tol = 1e-5 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("block_rows", [8, 64, 256])
def test_fsvrg_update_block_shapes(block_rows):
    d = 1000
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    args = [jax.random.normal(k, (d,)) for k in ks]
    out = ops.fsvrg_update(*args, 0.3, block_rows=block_rows)
    expect = ref.fsvrg_update_ref(*args, 0.3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=5e-5, atol=1e-6)


@pytest.mark.parametrize("k_block,d_block", [(2, 128), (8, 512), (16, 1024)])
def test_scaled_aggregate_block_shapes(k_block, d_block):
    K, d = 10, 999
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    wt = jax.random.normal(ks[0], (d,))
    wks = jax.random.normal(ks[1], (K, d))
    wts = jnp.full((K,), 1.0 / K)
    a = jnp.ones((d,))
    out = ops.scaled_aggregate(wt, wks, wts, a, k_block=k_block, d_block=d_block)
    expect = ref.scaled_aggregate_ref(wt, wks, wts, a)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.slow
@settings(deadline=None, max_examples=20)
@given(
    K=st.integers(1, 24),
    d=st.integers(1, 3000),
    seed=st.integers(0, 2**30),
    mode=st.sampled_from(["trimmed_mean", "median"]),
    trim=st.floats(0.0, 0.49),
)
def test_robust_aggregate_matches_ref(K, d, seed, mode, trim):
    from repro.core.engine import robust_aggregate

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    wt = jax.random.normal(ks[0], (d,))
    deltas = jax.random.normal(ks[1], (K, d))
    valid = jax.random.bernoulli(ks[2], 0.7, (K,))
    a = jnp.abs(jax.random.normal(ks[3], (d,))) + 0.5
    out = robust_aggregate(wt, deltas, valid, a, trim, mode)
    expect = _oracles.order_stat_aggregate_np(wt, deltas, valid, a, trim,
                                              mode)
    np.testing.assert_allclose(np.asarray(out), expect,
                               rtol=1e-5, atol=1e-5)


def test_kernel_equals_fsvrg_inner_loop_semantics():
    """The fused kernel is exactly Alg. 4 line 8 for one step."""
    d = 257
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    w, s, gn, go, gb = [jax.random.normal(k, (d,)) for k in ks]
    h = 0.7
    manual = w - h * (s * (gn - go) + gb)
    out = ops.fsvrg_update(w, s, gn, go, gb, h)
    np.testing.assert_allclose(np.asarray(out), np.asarray(manual), rtol=1e-5)
