"""Pre-port list-based DANE / CoCoA+ / Appendix-A implementations.

These are the standalone (Python-lists-of-per-client-arrays, hand-rolled
round loop) code paths that the engine ports in ``repro.core.dane`` /
``repro.core.cocoa`` replaced.  They are kept verbatim here as *oracles*:
tests/test_dane_cocoa_engine.py pins each engine port against its oracle
round-by-round.  The only deliberate deviation is ``cocoa_round_list``,
whose per-bucket key is ``fold_in(key, wi)`` (wi = the bucket's first
client index) to match the RoundEngine key contract — the pre-port class
used the bucket's position, which pins nothing but its own loop.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp

from repro.core.dane import ridge_grad
from repro.core.problem import FederatedLogReg


# --------------------------------------------------------------------- #
# exact DANE for ridge regression (dense per-client data)
# --------------------------------------------------------------------- #


def dane_round_ridge(Xs: Sequence[jax.Array], ys: Sequence[jax.Array], w, lam,
                     eta: float = 1.0, mu: float = 0.0):
    """One exact DANE round on ridge. Xs[k]: (d, n_k)."""
    K = len(Xs)
    n = sum(int(y.shape[0]) for y in ys)
    # ∇f(w^t) = Σ (n_k/n) ∇F_k(w^t)
    full_grad = sum((ys[k].shape[0] / n) * ridge_grad(Xs[k], ys[k], w, lam)
                    for k in range(K))
    d = w.shape[0]
    w_next = jnp.zeros_like(w)
    for k in range(K):
        X, y = Xs[k], ys[k]
        m = y.shape[0]
        a_k = ridge_grad(X, y, w, lam) - eta * full_grad
        # (H_k + µI) w = c_k + a_k + µ w^t,  H_k = XXᵀ/m + λI, c_k = Xy/m
        H = X @ X.T / m + (lam + mu) * jnp.eye(d)
        rhs = X @ y / m + a_k + mu * w
        w_next = w_next + jnp.linalg.solve(H, rhs) / K
    return w_next


# --------------------------------------------------------------------- #
# inexact DANE for logistic regression (GD local solver)
# --------------------------------------------------------------------- #


def dane_round_logreg_gd(problem: FederatedLogReg, w, *, eta: float = 1.0,
                         mu: float = 0.0, local_steps: int = 50,
                         local_lr: float = 1.0):
    """DANE with a GD local solver, on the bucketed sparse problem."""
    flat = problem.flat
    full_grad = flat.grad(w)
    lam = flat.lam
    agg = jnp.zeros_like(w)
    wi = 0
    for b in problem.buckets:

        def one_client(idx, val, y, n_k):
            d = w.shape[0]
            nkf = jnp.maximum(n_k.astype(jnp.float32), 1.0)
            valid = (jnp.arange(y.shape[0]) < n_k).astype(jnp.float32)

            def Fk_grad(wk):
                z = y * (val * wk[idx]).sum(axis=1)
                gs = -y * jax.nn.sigmoid(-y * z) * valid / nkf
                return jnp.zeros((d,)).at[idx].add(gs[:, None] * val) + lam * wk

            a_k = Fk_grad(w) - eta * full_grad

            def gd_step(wk, _):
                g = Fk_grad(wk) - a_k + mu * (wk - w)
                return wk - local_lr * g, None

            wk, _ = jax.lax.scan(gd_step, w, None, length=local_steps)
            return wk

        wks = jax.vmap(one_client)(b.idx, b.val, b.y, b.n_k)   # (Kb, d)
        agg = agg + wks.sum(axis=0)
        wi += b.num_clients
    return agg / problem.num_clients


# --------------------------------------------------------------------- #
# CoCoA+ (list-based alphas, hand-rolled round loop, per-client SDCA scan)
# --------------------------------------------------------------------- #


def _sdca_local_pass_list(w, alpha_b, bucket, lam, n, sigma, key):
    """The pre-rewrite SDCA local pass: vmap over clients, each running its
    own sequential scan with a *scalar* Newton solve per coordinate —
    verbatim from the pre-port CoCoAPlus.  Kept independent of
    ``repro.core.cocoa._sdca_local_pass`` so the lockstep bucket-scan
    rewrite there is pinned against genuinely separate code."""

    def one_client(idx, val, y, n_k, alpha_k, ck):
        d = w.shape[0]
        m_pad = y.shape[0]
        perm = jax.random.permutation(ck, m_pad)

        def newton_beta(beta0, mcoef, ccoef):
            def it(b, _):
                gb = mcoef + 2.0 * ccoef * (b - beta0) + jnp.log(b / (1.0 - b))
                hb = 2.0 * ccoef + 1.0 / (b * (1.0 - b))
                return jnp.clip(b - gb / hb, 1e-6, 1.0 - 1e-6), None
            b0 = jnp.clip(jax.nn.sigmoid(-mcoef), 1e-6, 1.0 - 1e-6)
            b, _ = jax.lax.scan(it, b0, None, length=12)
            return b

        def step(carry, t):
            u, r = carry
            i = perm[t]
            xi, vi, yi = idx[i], val[i], y[i]
            valid = (i < n_k).astype(jnp.float32)
            beta_old = yi * alpha_k[i]
            beta_old = jnp.clip(beta_old, 1e-6, 1.0 - 1e-6)
            xn2 = (vi * vi).sum()
            mcoef = yi * ((vi * w[xi]).sum() + (sigma / (lam * n)) * (vi * r[xi]).sum())
            ccoef = sigma * xn2 / (2.0 * lam * n)
            beta = newton_beta(beta_old, mcoef, ccoef)
            du = valid * yi * (beta - beta_old)
            u = u.at[i].add(du)
            r = r.at[xi].add(du * vi)
            return (u, r), None

        u0 = jnp.zeros((m_pad,))
        r0 = jnp.zeros((d,))
        (u, r), _ = jax.lax.scan(step, (u0, r0), jnp.arange(m_pad))
        return u, r

    keys = jax.random.split(key, bucket.num_clients)
    return jax.vmap(one_client)(bucket.idx, bucket.val, bucket.y,
                                bucket.n_k, alpha_b, keys)


def cocoa_round_list(problem: FederatedLogReg, w, alphas: List[jax.Array],
                     key, sigma: float):
    """The pre-port CoCoAPlus.round body: per-bucket SDCA pass (the
    pre-rewrite per-client scan above), list alphas, dw accumulated by
    hand, w ← w + dw/(λn)."""
    lam, n = problem.flat.lam, problem.flat.n
    dw = jnp.zeros_like(w)
    new_alphas = []
    wi = 0
    for bi, b in enumerate(problem.buckets):
        u, r = _sdca_local_pass_list(w, alphas[bi], b, lam, n, sigma,
                                     jax.random.fold_in(key, wi))
        new_alphas.append(alphas[bi] + u)
        dw = dw + r.sum(axis=0)
        wi += b.num_clients
    return w + dw / (lam * n), new_alphas


# --------------------------------------------------------------------- #
# Appendix A, ridge regression, dense per-client data  X_k: (d, m)
# --------------------------------------------------------------------- #


def _Fk_grad_ridge(X, y, w, lam, n, K):
    """F_k(w) = (K/2n)||X^T w − y||² + (λ/2)||w||²  (eq. 12 normalization)."""
    return (K / n) * (X @ (X.T @ w - y)) + lam * w


def primal_method_init(Xs: Sequence[jax.Array], alphas0: Sequence[jax.Array],
                       lam: float, sigma: float):
    """Steps 3–5 of Algorithm 5. Returns (w0, g0 list, eta, mu)."""
    K = len(Xs)
    n = sum(int(a.shape[0]) for a in alphas0)
    eta = K / sigma
    mu = lam * (eta - 1.0)
    w0 = sum(X @ a for X, a in zip(Xs, alphas0)) / (lam * n)
    g0 = [eta * ((K / n) * (X @ a) - lam * w0) for X, a in zip(Xs, alphas0)]
    return w0, g0, eta, mu


def primal_method_round(Xs, ys, w, gs: List[jax.Array], lam, eta, mu):
    """One round of Algorithm 5 (exact local solves; ridge)."""
    K = len(Xs)
    n = sum(int(y.shape[0]) for y in ys)
    d = w.shape[0]
    w_ks = []
    for k in range(K):
        X, y = Xs[k], ys[k]
        # argmin F_k(w') − (∇F_k(w^t) − (η∇F_k(w^t) + g_k))ᵀ w' + µ/2||w'−w^t||²
        b_k = (1.0 - eta) * _Fk_grad_ridge(X, y, w, lam, n, K) - gs[k]
        # ∇F_k(w') = (K/n) X Xᵀ w' − (K/n) X y + λ w'
        H = (K / n) * (X @ X.T) + (lam + mu) * jnp.eye(d)
        rhs = (K / n) * (X @ y) + b_k + mu * w
        w_ks.append(jnp.linalg.solve(H, rhs))
    w_next = sum(w_ks) / K
    gs_next = [gs[k] + lam * eta * (w_ks[k] - w_next) for k in range(K)]
    return w_next, gs_next


def dual_method_round(Xs, ys, alphas: List[jax.Array], lam, sigma):
    """One round of Algorithm 6 (exact block solves; ridge φ_i(t)=½(t−y_i)²).

    Block subproblem (19): h_k = argmin (σ/2λn)||X_k h||² + ½||h||²
                                        − (y_k − X_kᵀw^t − α_k)ᵀ h
    """
    K = len(Xs)
    n = sum(int(a.shape[0]) for a in alphas)
    w = sum(X @ a for X, a in zip(Xs, alphas)) / (lam * n)
    new_alphas = []
    for k in range(K):
        X, y, a = Xs[k], ys[k], alphas[k]
        m = a.shape[0]
        c = y - X.T @ w - a
        M = (sigma / (lam * n)) * (X.T @ X) + jnp.eye(m)
        h = jnp.linalg.solve(M, c)
        new_alphas.append(a + h)
    return new_alphas


# --------------------------------------------------------------------- #
# pre-redesign fig2 round loops (Trainer pinning oracles)
# --------------------------------------------------------------------- #
#
# Before the FederatedSolver/Trainer redesign, benchmarks/fig2_convergence.py
# hand-rolled one round loop per algorithm: construct the solver, then
# ``for r: w = round(w, fold_in(PRNGKey(seed), r)); hist.append(eval(w))``
# (CoCoA+ additionally threaded its mutable dual blocks).  These functions
# keep those loop bodies verbatim — inlining each pre-redesign ``round``
# implementation at the engine level — parametrized on the seed, so
# tests/test_trainer.py can pin ``Trainer.fit`` against them bit-for-bit.
# The vmapped client passes are shared with the live solvers on purpose:
# the passes themselves are pinned against the fully independent list-based
# oracles above; what these loops pin is the *driver* — key schedule, round
# ordering, state threading, and history capture.


def _round_key(seed: int, r: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed), r)


def fig2_fsvrg_loop(problem: FederatedLogReg, h: float, rounds: int,
                    seed: int, eval_fn):
    """The pre-redesign fig2 FSVRG curve: fresh solver, hand-rolled loop."""
    from repro.core import FSVRG, FSVRGConfig

    solver = FSVRG(problem, FSVRGConfig(stepsize=h))
    w = jnp.zeros(problem.d)
    hist = []
    for r in range(rounds):
        # verbatim pre-redesign FSVRG.round(w, key) body
        full_grad = problem.flat.grad(w)

        def fsvrg_pass(w_, bi, bucket, kb, fg=full_grad):
            return solver._pass(w_, fg, bucket, phi=solver.phi, key=kb)

        w = solver.engine.round(w, _round_key(seed, r), fsvrg_pass)
        hist.append(eval_fn(w))
    return w, hist


def fig2_fedavg_loop(problem: FederatedLogReg, h: float, local_epochs: int,
                     rounds: int, seed: int, eval_fn):
    """The pre-redesign fig2 FedAvg curve."""
    from repro.core import FedAvg, FedAvgConfig

    solver = FedAvg(problem, FedAvgConfig(stepsize=h,
                                          local_epochs=local_epochs))
    w = jnp.zeros(problem.d)
    hist = []
    for r in range(rounds):
        # verbatim pre-redesign FedAvg.round(w, key) body
        w = solver.engine.round(
            w, _round_key(seed, r),
            lambda w_, bi, bucket, kb: solver._pass(w_, bucket, key=kb))
        hist.append(eval_fn(w))
    return w, hist


def fig2_dane_loop(problem: FederatedLogReg, rounds: int, seed: int, eval_fn,
                   **dane_kw):
    """The pre-redesign fig2 DANE curve (GD local solver)."""
    from repro.core import DANE, DANEConfig

    solver = DANE(problem, DANEConfig(**dane_kw))
    w = jnp.zeros(problem.d)
    hist = []
    for r in range(rounds):
        # verbatim pre-redesign DANE.round(w, key) body
        full_grad = problem.flat.grad(w)

        def dane_pass(w_, bi, bucket, kb, fg=full_grad):
            return solver._pass(w_, fg, bucket, key=kb)

        w = solver.engine.round(w, _round_key(seed, r), dane_pass)
        hist.append(eval_fn(w))
    return w, hist


def fig2_cocoa_loop(problem: FederatedLogReg, rounds: int, seed: int,
                    eval_fn, sigma=None):
    """The pre-redesign fig2 CoCoA+ curve: the mutable-class round body
    (dual blocks threaded by hand through round_with_state)."""
    from repro.core.cocoa import CoCoAPlus

    solver = CoCoAPlus(problem, sigma=sigma)
    w = jnp.zeros(problem.d)
    alphas = [jnp.zeros((b.num_clients, b.m_pad)) for b in problem.buckets]
    hist = []
    for r in range(rounds):
        # verbatim pre-redesign CoCoAPlus.round(key) body, de-mutabilized
        def cocoa_pass(w_, bi, bucket, alpha_b, kb):
            u, dr = solver._pass(w_, alpha_b, bucket, kb)
            return dr * solver._scale, alpha_b + u

        w, alphas = solver.engine.round_with_state(
            w, alphas, _round_key(seed, r), cocoa_pass)
        hist.append(eval_fn(w))
    return w, alphas, hist


# --------------------------------------------------------------------- #
# order-statistic aggregation, one coordinate at a time in numpy
# --------------------------------------------------------------------- #


def order_stat_aggregate_np(w, deltas, valid, a_diag, trim, mode):
    """w + a ⊙ (trimmed mean | median of the valid rows), per coordinate
    in numpy: the plain reading of ``EngineConfig.aggregator_guard``.  The
    trim rank is ``floor(trim·m)`` in f32, as the engine states it."""
    import numpy as np

    rows = np.asarray(deltas, np.float32)[np.asarray(valid) > 0]
    m = rows.shape[0]
    w = np.asarray(w, np.float32)
    if m == 0:
        return w
    if mode == "median":
        agg = np.median(rows, axis=0)
    else:
        lo = int(np.floor(np.float32(trim) * np.float32(m)))
        agg = np.sort(rows, axis=0)[lo:m - lo].mean(axis=0)
    return w + np.asarray(a_diag, np.float32) * agg
