"""robust_aggregate (the order-statistic aggregator-guard update) vs
pure-numpy order statistics.

Deterministic sweeps only — unlike test_kernels.py this module must run
without hypothesis (the aggregator guard is load-bearing for the fault-
tolerance contract, so its coverage can't hinge on an optional dev
dependency); the hypothesis shape/seed sweep lives in test_kernels.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _oracles
from repro.core.engine import robust_aggregate


def test_robust_aggregate_matches_numpy_order_stats():
    """The median / trimmed mean are taken over exactly the valid rows (odd
    count, so the median is literally the middle element), scaled by
    a_diag."""
    rng = np.random.default_rng(0)
    K, d = 9, 300
    deltas = rng.normal(size=(K, d)).astype(np.float32)
    valid = np.array([1, 1, 1, 0, 1, 1, 0, 1, 1], np.int32)   # m = 7
    w = rng.normal(size=d).astype(np.float32)
    a = (np.abs(rng.normal(size=d)) + 0.5).astype(np.float32)
    rows = deltas[valid > 0]
    expect_med = w + a * np.median(rows, axis=0)
    # trim=0.2, m=7: lo = floor(0.2*7) = 1, hi = 7-1 = 6 -> mean of ranks 1..5
    expect_tm = w + a * np.sort(rows, axis=0)[1:6].mean(axis=0)
    for mode, expect in (("median", expect_med), ("trimmed_mean", expect_tm)):
        out = robust_aggregate(jnp.asarray(w), jnp.asarray(deltas),
                               jnp.asarray(valid), jnp.asarray(a), 0.2, mode)
        np.testing.assert_allclose(np.asarray(out), expect,
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("K,d,trim,rate", [
    (1, 1, 0.0, 1.0), (2, 127, 0.1, 0.5), (16, 128, 0.25, 0.7),
    (24, 1000, 0.49, 0.3), (7, 4097, 0.1, 1.0),
])
def test_robust_aggregate_matches_ref_across_shapes(K, d, trim, rate):
    """Degenerate valid counts (none, one, all) and odd/even counts agree
    with the per-coordinate numpy order statistics."""
    ks = jax.random.split(jax.random.PRNGKey(K * 7919 + d), 4)
    wt = jax.random.normal(ks[0], (d,))
    deltas = jax.random.normal(ks[1], (K, d))
    valid = jax.random.bernoulli(ks[2], rate, (K,))
    a = jnp.abs(jax.random.normal(ks[3], (d,))) + 0.5
    for mode in ("trimmed_mean", "median"):
        out = robust_aggregate(wt, deltas, valid, a, trim, mode)
        expect = _oracles.order_stat_aggregate_np(wt, deltas, valid, a,
                                                  trim, mode)
        np.testing.assert_allclose(np.asarray(out), expect,
                                   rtol=1e-5, atol=1e-5)


def test_robust_aggregate_all_invalid_is_identity():
    """No surviving client -> zero update (not NaN from an empty mean)."""
    d = 257
    w = jax.random.normal(jax.random.PRNGKey(3), (d,))
    deltas = jnp.full((4, d), jnp.nan)
    valid = jnp.zeros((4,), jnp.int32)
    for mode in ("trimmed_mean", "median"):
        out = robust_aggregate(w, deltas, valid, jnp.ones((d,)), 0.1, mode)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(w))


def test_robust_aggregate_bounds_poisoned_update():
    """The point of the guard: with a minority of rows driven to huge
    (but finite, so no engine pre-exclusion) values and marked valid, the
    trimmed mean stays within the honest rows' coordinate-wise range —
    the sort itself must bury the outliers outside the rank window."""
    rng = np.random.default_rng(1)
    K, d = 11, 200
    deltas = rng.normal(size=(K, d)).astype(np.float32)
    deltas[0] = 1e30
    deltas[1] = -1e30
    w = np.zeros(d, np.float32)
    a = np.ones(d, np.float32)
    out = np.asarray(robust_aggregate(
        jnp.asarray(w), jnp.asarray(deltas), jnp.ones((K,), jnp.int32),
        jnp.asarray(a), 0.2, "trimmed_mean"))
    honest = deltas[2:]
    assert (out >= honest.min(axis=0) - 1e-5).all()
    assert (out <= honest.max(axis=0) + 1e-5).all()


def test_robust_aggregate_validation():
    w = jnp.zeros(8)
    deltas = jnp.zeros((2, 8))
    valid = jnp.ones((2,), jnp.int32)
    with pytest.raises(ValueError, match="mode"):
        robust_aggregate(w, deltas, valid, jnp.ones(8), 0.1, "mean")
    with pytest.raises(ValueError, match="trim"):
        robust_aggregate(w, deltas, valid, jnp.ones(8), 0.5, "trimmed_mean")
