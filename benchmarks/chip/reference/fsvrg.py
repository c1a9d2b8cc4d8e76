"""Plain reference of one FSVRG client pass (arXiv:1610.02527 Alg. 4).

Round t from iterate w: the server computes the full gradient ∇f(w); each
client k, from w_k = w with stepsize h_k = h / n_k, visits its rows in the
order of ``permutation(client_key, m_pad)`` (slots past its n_k rows are
skipped) and steps

    w_k ← w_k − h_k ( S_k [∇f_i(w_k) − ∇f_i(w)] + ∇f(w) ),

where ∇f_i includes the λ w term, S_k = Diag(φ^j / φ_k^j) (1 where the
client lacks feature j), φ^j the share of all rows holding j and φ_k^j the
share of the client's rows.  The server scales the weighted sum of the
deltas by A = Diag(K / ω^j), ω^j the number of clients holding j (1 where
none does).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def prepare(data, flat, layout, params, dtype):
    counts = flat.feature_counts()
    phi = (counts / data.n).astype(dtype)
    omega = _omega(flat.idx, flat.val,
                   jnp.asarray(_blocked_client_of(data, flat)),
                   len(data.sizes), data.num_features)
    K = len(data.sizes)
    a = jnp.where(omega > 0, K / jnp.maximum(omega, 1.0), 1.0)
    return {"phi": phi, "a": a.astype(jnp.float32), "lam": data.lam}


def _blocked_client_of(data, flat):
    n, blocks, rows = data.n, flat.idx.shape[0], flat.idx.shape[1]
    cof = np.full(blocks * rows, len(data.sizes), np.int32)
    cof[:n] = data.client_of
    return cof.reshape(blocks, rows)


@functools.partial(jax.jit, static_argnames=("K", "d"))
def _omega(idx, val, client_of, K, d):
    """ω^j: clients with at least one row holding feature j.  Pad rows carry
    client K, out of range, and are dropped."""
    def body(pres, b):
        i, v, c = b
        col = jnp.where(v != 0, i, d)
        return pres.at[c[:, None], col].set(1, mode="drop"), None

    pres, _ = jax.lax.scan(body, jnp.zeros((K, d), jnp.int32),
                           (idx, val, client_of))
    return pres.astype(jnp.float32).sum(0)


def server_diag(static):
    return static["a"]


def prelude(flat, w, static, params, dtype):
    return flat.grad(w)


def deltas(w, idx, val, y, nk, keys, full_grad, static, params, dtype):
    return _deltas(w, idx, val, y, nk, keys, full_grad, static["phi"],
                   jnp.asarray(params["stepsize"], dtype),
                   jnp.asarray(static["lam"], dtype))


@jax.jit
def _deltas(w0, idx, val, y, nk, keys, full_grad, phi, h, lam):
    d = w0.shape[0]
    dt = w0.dtype

    def one(idx, val, y, n_k, ck):
        cnt = jnp.zeros((d,), dt).at[idx].add((val != 0).astype(dt))
        phi_k = cnt / jnp.maximum(n_k, 1).astype(dt)
        s = jnp.where(cnt > 0, phi / jnp.maximum(phi_k, 1e-12), 1.0).astype(dt)
        h_k = h / jnp.maximum(n_k, 1).astype(dt)
        order = jax.random.permutation(ck, y.shape[0])

        def step(wk, i):
            x, v, yy = idx[i], val[i], y[i]
            g_new = -yy * jax.nn.sigmoid(-yy * (v * wk[x]).sum())
            g_old = -yy * jax.nn.sigmoid(-yy * (v * w0[x]).sum())
            diff = jnp.zeros((d,), dt).at[x].add((g_new - g_old) * v)
            diff = diff + lam * (wk - w0)
            stepped = wk - h_k * (s * diff + full_grad)
            return jnp.where(i < n_k, stepped, wk), None

        wk, _ = jax.lax.scan(step, w0, order)
        return wk - w0

    return jax.vmap(one)(idx, val, y, nk, keys)
