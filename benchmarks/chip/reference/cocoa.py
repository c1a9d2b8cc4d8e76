"""Plain reference of one CoCoA+ client pass (arXiv:1502.03508, γ = 1,
local SDCA), for the §4 logistic regression with λ = 1/n.

Each client k holds a dual block α_k, one entry per row slot, carried from
round to round (``init_state``: α = 0, so w = 0).  In a round from iterate
w, the client visits its rows once, in the order of
``permutation(client_key, m_pad)`` (slots past its n_k rows are skipped),
one row after another.  With β = y_i α_i ∈ (0, 1) and r = X_k Δα_k the
client's own dual change so far, row i solves the scalar subproblem of
eq. (15),

    min over β in [ε, 1 − ε] of  m (β − β₀) + c (β − β₀)² + H(β),
    m = y_i x_iᵀ (w + (σ′/λn) r),   c = σ′ ‖x_i‖² / (2λn),
    H(β) = β log β + (1 − β) log(1 − β),   β₀ = clip(y_i α_i, ε, 1 − ε),

with σ′ = γK (the safe choice, unless the configuration sets ``sigma``)
and ε = 1e-6, then steps Δα_i = y_i (β − β₀).  The client's delta is
Δw_k = X_k Δα_k / (λn), its new block α_k + Δα_k; the server adds the
participants' deltas unweighted (``WEIGHTING = "sum"``), and a client that
takes no part keeps its block.

The scalar solve is bisection on the derivative
g(β) = m + 2c (β − β₀) + log(β / (1 − β)), which rises strictly on
(0, 1): ``BISECTIONS`` halvings of [ε, 1 − ε] keep the sign change of g
bracketed, and end on the minimizer of the subproblem over [ε, 1 − ε] (a
root below ε or above 1 − ε ends on that end).  48 halvings narrow the
bracket to 2^-48, below the float32 spacing at ε (2^-43), so wherever the
root lies the bracket closes on neighbouring floats: the solve is exact to
the working precision, with no starting point or step rule to tune.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: β is kept in [EPS, 1 − EPS], where log(β / (1 − β)) is finite
EPS = 1e-6
#: halvings of the scalar solve's bracket (see the module docstring)
BISECTIONS = 48
WEIGHTING = "sum"


def prepare(data, flat, layout, params, dtype):
    sigma = params.get("sigma")
    return {"lam_n": data.lam * data.n,
            "sigma": float(len(data.sizes) if sigma is None else sigma)}


def server_diag(static):
    return None


def prelude(flat, w, static, params, dtype):
    return None


def init_state(data, layout, static, params, dtype):
    """α = 0: a (members, m_pad) block per group."""
    return [jnp.zeros((len(g.members), g.m_pad), dtype) for g in layout]


def deltas(w, idx, val, y, nk, keys, ctx, static, params, dtype, *, state):
    return _deltas(w, state, idx, val, y, nk, keys,
                   jnp.asarray(static["sigma"], dtype),
                   jnp.asarray(static["lam_n"], dtype))


def _solve(b0, m, c):
    """The minimizer over [EPS, 1 − EPS] of m (β − b0) + c (β − b0)² + H(β),
    by bisection on its derivative."""
    def g(b):
        return m + 2 * c * (b - b0) + jnp.log(b / (1 - b))

    def halve(_, bracket):
        lo, hi = bracket
        mid = (lo + hi) / 2
        below = g(mid) < 0
        return jnp.where(below, mid, lo), jnp.where(below, hi, mid)

    lo, hi = jax.lax.fori_loop(
        0, BISECTIONS, halve,
        (jnp.asarray(EPS, b0.dtype), jnp.asarray(1 - EPS, b0.dtype)))
    return (lo + hi) / 2


def _one(w, alpha, idx, val, y, n_k, ck, sigma, lam_n):
    dt = w.dtype
    order = jax.random.permutation(ck, y.shape[0])

    def step(carry, i):
        a, r = carry
        x, v, yy = idx[i], val[i], y[i]
        b0 = jnp.clip(yy * a[i], EPS, 1 - EPS)
        m = yy * ((v * w[x]).sum() + sigma / lam_n * (v * r[x]).sum())
        c = sigma * (v * v).sum() / (2 * lam_n)
        du = jnp.where(i < n_k, yy * (_solve(b0, m, c) - b0), 0).astype(dt)
        return (a.at[i].add(du), r.at[x].add(du * v)), None

    (a, r), _ = jax.lax.scan(step, (alpha, jnp.zeros_like(w)), order)
    return r / lam_n, a


@jax.jit
def _deltas(w, alpha, idx, val, y, nk, keys, sigma, lam_n):
    """Each client of the block on its own (``jax.vmap`` of one client's
    pass: no client sees another's rows or state)."""
    return jax.vmap(
        lambda a, i, v, yy, n, k: _one(w, a, i, v, yy, n, k, sigma, lam_n)
    )(alpha, idx, val, y, nk, keys)
