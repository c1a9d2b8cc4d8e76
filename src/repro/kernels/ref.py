"""Pure-jnp oracles for the Pallas kernels (the allclose reference)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fsvrg_update_ref(w, s, g_new, g_old, g_bar, h):
    """w − h (S ⊙ (g_new − g_old) + ḡ), computed in f32, cast back."""
    upd = (s.astype(jnp.float32)
           * (g_new.astype(jnp.float32) - g_old.astype(jnp.float32))
           + g_bar.astype(jnp.float32))
    return (w.astype(jnp.float32) - jnp.asarray(h, jnp.float32) * upd).astype(w.dtype)


def fedavg_update_ref(w, g, h, lam):
    """(1 − h·λ)·w − h·g, computed in f32, cast back."""
    h = jnp.asarray(h, jnp.float32)
    lam = jnp.asarray(lam, jnp.float32)
    out = (1.0 - h * lam) * w.astype(jnp.float32) - h * g.astype(jnp.float32)
    return out.astype(w.dtype)


def dane_update_ref(w, g, a, w_t, lr, lam, mu):
    """(1 − lr(λ+µ))·w − lr·g + lr·a + lr·µ·w_t, computed in f32, cast back."""
    lr = jnp.asarray(lr, jnp.float32)
    lam = jnp.asarray(lam, jnp.float32)
    mu = jnp.asarray(mu, jnp.float32)
    out = ((1.0 - lr * (lam + mu)) * w.astype(jnp.float32)
           - lr * g.astype(jnp.float32) + lr * a.astype(jnp.float32)
           + lr * mu * w_t.astype(jnp.float32))
    return out.astype(w.dtype)


def cocoa_sdca_update_ref(beta0, mcoef, ccoef, newton_iters: int = 12):
    """Clipped-Newton solve of the per-coordinate SDCA dual subproblem
    min_β m(β−β₀) + c(β−β₀)² + β log β + (1−β)log(1−β), in f32.

    Also the jnp fallback path of ``repro.core.cocoa._sdca_local_pass``;
    the Newton recursion is a rolled ``fori_loop`` on purpose — a
    Python-unrolled loop embedded in the SDCA scan body blows XLA CPU
    compile time up by two orders of magnitude."""
    eps = 1e-6
    b0 = beta0.astype(jnp.float32)
    m = mcoef.astype(jnp.float32)
    c = ccoef.astype(jnp.float32)

    def it(_, b):
        gb = m + 2.0 * c * (b - b0) + jnp.log(b / (1.0 - b))
        hb = 2.0 * c + 1.0 / (b * (1.0 - b))
        return jnp.clip(b - gb / hb, eps, 1.0 - eps)

    b = jnp.clip(jax.nn.sigmoid(-m), eps, 1.0 - eps)
    b = jax.lax.fori_loop(0, newton_iters, it, b)
    return b.astype(beta0.dtype)


def scaled_aggregate_ref(w_t, w_ks, weights, a_diag):
    """w^t + A ⊙ Σ_k weights_k (w_k − w^t), in f32 — the iterate-consuming
    oracle (the pre-delta-native kernel's entry-point semantics)."""
    wt = w_t.astype(jnp.float32)
    delta = ((w_ks.astype(jnp.float32) - wt[None, :])
             * weights.astype(jnp.float32)[:, None]).sum(axis=0)
    return wt + a_diag.astype(jnp.float32) * delta


def fused_aggregate_ref(w_t, deltas, weights, a_diag, scale=1.0):
    """w^t + A ⊙ (scale · Σ_k weights_k δ_k), in f32 — the delta-native
    oracle, with the participation-reweight scalar in the epilogue."""
    agg = (deltas.astype(jnp.float32)
           * weights.astype(jnp.float32)[:, None]).sum(axis=0)
    return (w_t.astype(jnp.float32)
            + a_diag.astype(jnp.float32) * (jnp.asarray(scale, jnp.float32)
                                            * agg))


def fused_accumulate_ref(acc, deltas, weights):
    """acc + Σ_k weights_k δ_k, in f32 — the chunk-accumulating phase of
    :func:`fused_aggregate_ref` with an identity epilogue.  The streamed
    round (``EngineConfig.client_chunk``) folds each (chunk, d) delta block
    through this so the full (K, d) stack is never materialized."""
    return (acc.astype(jnp.float32)
            + (deltas.astype(jnp.float32)
               * weights.astype(jnp.float32)[:, None]).sum(axis=0))


def fused_epilogue_ref(w_t, acc, a_diag, scale=1.0):
    """w^t + A ⊙ (scale · acc), in f32 — the epilogue-only phase applied to
    a streamed delta-sum accumulator."""
    return (w_t.astype(jnp.float32)
            + a_diag.astype(jnp.float32) * (jnp.asarray(scale, jnp.float32)
                                            * acc.astype(jnp.float32)))


def wkv6_ref(r, k, v, w, u):
    """Token-by-token WKV-6 recurrence in f32 — the oracle for the
    chunk-parallel ``kernels/wkv6.wkv6``.  Per (batch·head) pair with
    state S ∈ R^{D×D} starting at zero:

        out_t = r_t S + (Σ_i r_ti u_i k_ti) v_t
        S    ← diag(w_t) S + k_t^T v_t

    which is the kernel's chunk math at L = 1 (c = w_t, strict intra
    mask empty).  Shapes match the kernel: r,k,v,w (BH, S, D), u (BH, D);
    returns out (BH, S, D) in r.dtype and the final state (BH, D, D) in
    f32."""
    def one_pair(r, k, v, w, u):
        def step(s, x):
            rt, kt, vt, wt = x
            out = rt @ s + (rt * u * kt).sum() * vt
            return wt[:, None] * s + kt[:, None] * vt[None, :], out
        s0 = jnp.zeros((r.shape[-1], r.shape[-1]), jnp.float32)
        s_fin, out = jax.lax.scan(step, s0, (r, k, v, w))
        return out, s_fin

    out, state = jax.vmap(one_pair)(
        r.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        w.astype(jnp.float32), u.astype(jnp.float32))
    return out.astype(r.dtype), state
