"""Engine-ported DANE / CoCoA+ / Appendix-A methods pinned against the
pre-port list-based implementations (tests/_oracles.py), plus jnp-vs-Pallas
kernel-path parity for the two new fused local-step kernels.

The dense-ridge pins run under f64 so "the same math, reassociated by the
engine's weighted aggregation" is distinguishable from a real drift: the
tolerances are at the f64 noise floor, orders of magnitude below any
algorithmic difference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _oracles
from repro.core import (CoCoAConfig, CoCoAPlus, DANE, DANEConfig, DANERidge,
                        DualMethod, PrimalMethod, build_dense_problem)
from repro.core.cocoa import dual_to_primal


@pytest.fixture()
def x64():
    """f64 for the dense-ridge machine-precision pins (function-scoped so the
    f32 sparse-problem tests and session fixtures are unaffected)."""
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


def _ridge_data(K=4, m=12, d=8, seed=0):
    rng = np.random.default_rng(seed)
    Xs = [jnp.asarray(rng.standard_normal((d, m))) for _ in range(K)]
    ys = [jnp.asarray(rng.standard_normal(m)) for _ in range(K)]
    return Xs, ys


# --------------------------------------------------------------------- #
# DANE
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("eta,mu", [(1.0, 0.0), (0.7, 0.5)])
def test_dane_ridge_engine_pins_list_oracle(x64, eta, mu):
    """3 rounds of engine DANERidge == the pre-port dane_round_ridge loop,
    at the f64 noise floor."""
    Xs, ys = _ridge_data()
    lam = 0.1
    solver = DANERidge(build_dense_problem(Xs, ys, lam), eta=eta, mu=mu)
    w_ref = jnp.asarray(np.random.default_rng(1).standard_normal(8))
    state = solver.init(w_ref)
    for _ in range(3):
        state = solver.round(state, jax.random.PRNGKey(0))
        w_ref = _oracles.dane_round_ridge(Xs, ys, w_ref, lam, eta=eta, mu=mu)
        np.testing.assert_allclose(np.asarray(state.w), np.asarray(w_ref),
                                   rtol=1e-12, atol=1e-13)


def test_dane_gd_engine_pins_list_oracle(tiny_problem):
    """Engine DANE (GD local solver) == the pre-port hand-rolled loop on the
    sparse bucketed problem, over 2 chained rounds (f32 tolerance — the
    engine reassociates the uniform average as w + Σ(w_k − w)/K)."""
    prob = tiny_problem
    cfg = DANEConfig(eta=1.0, mu=0.3, local_steps=10, local_lr=0.3)
    solver = DANE(prob, cfg)
    state = solver.init()
    w_ref = jnp.zeros(prob.d)
    key = jax.random.PRNGKey(0)
    for r in range(2):
        kr = jax.random.fold_in(key, r)
        state = solver.round(state, kr)
        w_ref = _oracles.dane_round_logreg_gd(
            prob, w_ref, eta=cfg.eta, mu=cfg.mu, local_steps=cfg.local_steps,
            local_lr=cfg.local_lr)
        np.testing.assert_allclose(np.asarray(state.w), np.asarray(w_ref),
                                   rtol=2e-5, atol=2e-6)


def test_dane_gd_kernel_path_matches_jnp(tiny_problem):
    """use_kernel=True (fused Pallas dane_update, interpret on CPU) and the
    inline jnp expression produce the same round (to f32 tolerance — the
    jnp path folds the Python-float scalar prefactors in double precision,
    the kernel chains them in f32)."""
    prob = tiny_problem
    w0 = jnp.zeros(prob.d)
    key = jax.random.PRNGKey(5)
    cfg = dict(eta=1.0, mu=0.3, local_steps=5, local_lr=0.3)
    s_j = DANE(prob, DANEConfig(use_kernel=False, **cfg))
    s_k = DANE(prob, DANEConfig(use_kernel=True, **cfg))
    w_j = s_j.round(s_j.init(w0), key).w
    w_k = s_k.round(s_k.init(w0), key).w
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_j),
                               rtol=1e-4, atol=1e-6)


def test_dane_config_validation():
    with pytest.raises(ValueError):
        DANEConfig(local_solver="newton")


# --------------------------------------------------------------------- #
# CoCoA+
# --------------------------------------------------------------------- #


def test_cocoa_engine_pins_list_oracle(tiny_problem):
    """3 rounds of engine CoCoA+ == the pre-port list-based loop: iterates
    AND dual blocks (the round_with_state plumbing must not touch α_k
    beyond the pass's own update)."""
    prob = tiny_problem
    solver = CoCoAPlus(prob)
    state = solver.init()
    w_ref = jnp.zeros(prob.d)
    alphas_ref = [jnp.zeros((b.num_clients, b.m_pad)) for b in prob.buckets]
    for r in range(3):
        key = jax.random.PRNGKey(r)
        state = solver.round(state, key)
        w_ref, alphas_ref = _oracles.cocoa_round_list(prob, w_ref, alphas_ref,
                                                      key, solver.sigma)
        np.testing.assert_allclose(np.asarray(state.w), np.asarray(w_ref),
                                   rtol=1e-5, atol=1e-7)
        for a_eng, a_ref in zip(state.aux, alphas_ref):
            np.testing.assert_allclose(np.asarray(a_eng), np.asarray(a_ref),
                                       rtol=1e-5, atol=1e-7)


def test_cocoa_kernel_path_matches_jnp(tiny_problem):
    """use_kernel=True (fused Pallas cocoa_sdca Newton solve, interpret on
    CPU) matches the inline jnp recursion."""
    prob = tiny_problem
    c_j = CoCoAPlus(prob, cfg=CoCoAConfig(use_kernel=False))
    c_k = CoCoAPlus(prob, cfg=CoCoAConfig(use_kernel=True))
    st_j, st_k = c_j.init(), c_k.init()
    for r in range(2):
        st_j = c_j.round(st_j, jax.random.PRNGKey(r))
        st_k = c_k.round(st_k, jax.random.PRNGKey(r))
    np.testing.assert_allclose(np.asarray(st_k.w), np.asarray(st_j.w),
                               rtol=1e-6, atol=1e-7)


def test_cocoa_partial_participation_freezes_left_out_duals(tiny_problem):
    """Under participation<1, exactly the clients the engine's Bernoulli
    draw left out keep their dual blocks, and the primal iterate keeps
    tracking the dual blocks — w = (1/λn) Σ_k X_k α_k — because the "sum"
    weighting takes the plain partial sum (no unbiasedness reweighting)."""
    prob = tiny_problem
    solver = CoCoAPlus(prob, cfg=CoCoAConfig(participation=0.5))
    key = jax.random.PRNGKey(3)
    state0 = solver.init()
    state = solver.round(state0, key)
    wi = 0
    num_frozen = 0
    for bi, b in enumerate(prob.buckets):
        kb = jax.random.fold_in(key, wi)
        sel = np.asarray(solver.engine.participation_mask(kb, b.num_clients))
        changed = np.abs(np.asarray(state.aux[bi])
                         - np.asarray(state0.aux[bi])).max(axis=1) > 0
        # left-out clients must be frozen; participants (with data) update
        assert not changed[sel == 0.0].any()
        num_frozen += int((sel == 0.0).sum())
        wi += b.num_clients
    assert num_frozen > 0  # the draw actually left someone out

    state = solver.round(state, jax.random.PRNGKey(4))
    state = solver.round(state, jax.random.PRNGKey(5))
    lam, n = prob.flat.lam, prob.flat.n
    xa = jnp.zeros(prob.d)
    for b, a in zip(prob.buckets, state.aux):
        xa = xa.at[b.idx].add(a[:, :, None] * b.val)
    np.testing.assert_allclose(np.asarray(state.w),
                               np.asarray(xa / (lam * n)),
                               rtol=1e-5, atol=1e-6)


def test_cocoa_pallas_aggregator_matches_dense(tiny_problem):
    """CoCoA+'s sum-weighted deltas through aggregator='pallas'
    (scaled_aggregate) == the dense path."""
    prob = tiny_problem
    c_d = CoCoAPlus(prob, cfg=CoCoAConfig(aggregator="dense"))
    c_p = CoCoAPlus(prob, cfg=CoCoAConfig(aggregator="pallas"))
    st_d, st_p = c_d.init(), c_p.init()
    for r in range(2):
        st_d = c_d.round(st_d, jax.random.PRNGKey(r))
        st_p = c_p.round(st_p, jax.random.PRNGKey(r))
    np.testing.assert_allclose(np.asarray(st_p.w), np.asarray(st_d.w),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------- #
# Appendix-A primal/dual methods
# --------------------------------------------------------------------- #


def test_primal_method_engine_pins_list_oracle(x64):
    Xs, ys = _ridge_data(seed=4)
    lam, sigma = 0.1, 2.0
    rng = np.random.default_rng(5)
    alphas0 = [jnp.asarray(rng.standard_normal(12)) for _ in range(4)]
    solver = PrimalMethod(build_dense_problem(Xs, ys, lam), sigma=sigma,
                          alphas0=alphas0)
    state = solver.init()
    w, gs, eta, mu = _oracles.primal_method_init(Xs, alphas0, lam, sigma)
    np.testing.assert_allclose(np.asarray(state.w), np.asarray(w),
                               rtol=1e-12, atol=1e-13)
    for _ in range(4):
        state = solver.round(state, jax.random.PRNGKey(0))
        w, gs = _oracles.primal_method_round(Xs, ys, w, gs, lam, eta, mu)
        np.testing.assert_allclose(np.asarray(state.w), np.asarray(w),
                                   rtol=1e-11, atol=1e-12)
        np.testing.assert_allclose(np.asarray(state.aux[0]),
                                   np.asarray(jnp.stack(gs)),
                                   rtol=1e-11, atol=1e-12)


def test_dual_method_engine_pins_list_oracle(x64):
    Xs, ys = _ridge_data(seed=6)
    lam, sigma = 0.1, 4.0
    rng = np.random.default_rng(7)
    alphas0 = [jnp.asarray(rng.standard_normal(12)) for _ in range(4)]
    solver = DualMethod(build_dense_problem(Xs, ys, lam), sigma=sigma,
                        alphas0=alphas0)
    state = solver.init()
    alphas = list(alphas0)
    for _ in range(4):
        state = solver.round(state, jax.random.PRNGKey(0))
        alphas = _oracles.dual_method_round(Xs, ys, alphas, lam, sigma)
        np.testing.assert_allclose(
            np.asarray(state.aux[0]), np.asarray(jnp.stack(alphas)),
            rtol=1e-11, atol=1e-12)
        # the engine's incremental w tracks (1/λn) X α exactly
        np.testing.assert_allclose(
            np.asarray(state.w), np.asarray(dual_to_primal(Xs, alphas, lam)),
            rtol=1e-11, atol=1e-12)


def test_appendix_a_rejects_unequal_sizes(x64):
    rng = np.random.default_rng(8)
    Xs = [jnp.asarray(rng.standard_normal((5, m))) for m in (6, 9)]
    ys = [jnp.asarray(rng.standard_normal(m)) for m in (6, 9)]
    alphas0 = [jnp.asarray(rng.standard_normal(m)) for m in (6, 9)]
    with pytest.raises(ValueError):
        PrimalMethod(build_dense_problem(Xs, ys, 0.1), sigma=2.0,
                     alphas0=alphas0)


def test_cocoa_dual_objective_ascends_and_bounds_the_primal(tiny_problem):
    """CoCoA+ with the safe σ′ = K raises its dual D(α) every round (D = 0
    at α = 0), and weak duality holds: D(α) ≤ f(w(α)).  The primal itself
    need not fall below f(0) = log 2 in early rounds."""
    solver = CoCoAPlus(tiny_problem)
    state = solver.init()
    dual = [float(solver.dual_objective(state))]
    assert dual[0] == 0.0
    for r in range(3):
        state = solver.round(state, jax.random.PRNGKey(r))
        dual.append(float(solver.dual_objective(state)))
        assert dual[-1] <= float(tiny_problem.flat.loss(state.w)) + 1e-6
    assert all(b > a for a, b in zip(dual, dual[1:])), dual
