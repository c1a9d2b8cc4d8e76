"""Plain reference of one FedAvg client pass (arXiv:1602.05629, B = ∞).

Each client k, from w_k = w, runs E epochs; epoch e visits its rows in the
order of ``permutation(split(client_key, E)[e], m_pad)`` (slots past its
n_k rows are skipped) and steps

    w_k ← (1 − hλ) w_k − h ∇f_i(w_k),     λ = 1/n,

with ∇f_i the logistic gradient of row i.  The server adds the n_k/n
weighted mean of the deltas, unscaled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def prepare(data, flat, layout, params, dtype):
    return {"lam": data.lam}


def server_diag(static):
    return None


def prelude(flat, w, static, params, dtype):
    return None


def deltas(w, idx, val, y, nk, keys, ctx, static, params, dtype):
    return _deltas(w, idx, val, y, nk, keys,
                   jnp.asarray(params["stepsize"], dtype),
                   jnp.asarray(static["lam"], dtype),
                   epochs=int(params["local_epochs"]))


def _one(w0, idx, val, y, n_k, ck, h, lam, epochs):
    d = w0.shape[0]
    dt = w0.dtype

    def epoch(wk, ek):
        order = jax.random.permutation(ek, y.shape[0])

        def step(wk, i):
            x, v, yy = idx[i], val[i], y[i]
            g_scalar = -yy * jax.nn.sigmoid(-yy * (v * wk[x]).sum())
            g = jnp.zeros((d,), dt).at[x].add(g_scalar * v)
            stepped = (1 - h * lam) * wk - h * g
            return jnp.where(i < n_k, stepped, wk), None

        wk, _ = jax.lax.scan(step, wk, order)
        return wk, None

    wk, _ = jax.lax.scan(epoch, w0, jax.random.split(ck, epochs))
    return wk - w0


@functools.partial(jax.jit, static_argnames=("epochs",))
def _deltas(w0, idx, val, y, nk, keys, h, lam, *, epochs):
    return jax.vmap(
        lambda i, v, yy, n, k: _one(w0, i, v, yy, n, k, h, lam, epochs)
    )(idx, val, y, nk, keys)
