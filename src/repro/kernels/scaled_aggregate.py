"""Pallas TPU kernel: delta-native fused server aggregation (Alg. 4 line 11).

    w ← w^t + A ⊙ (s · Σ_k weights_k · δ_k),      δ_k = w_k − w^t

The kernel consumes the stacked client-delta matrix Δ: (K, d) *directly* —
exactly what every engine client pass produces — so the server update is one
true HBM pass over Δ.  The pre-delta-native kernel consumed the iterate
matrix W = w^t + Δ instead, which forced the caller to materialize a full
(K, d) add (an extra HBM round-trip) only so the kernel could subtract
(Σ weights)·w^t back out.  The unbiased-participation reweight scalar ``s``
and the per-coordinate A-diagonal epilogue are folded into the same pass:
weighting, reweighting, scaling, and the server update all happen while each
output tile is VMEM-resident.

Grid: (d_blocks, k_blocks) — k is the *inner* (minor) dimension so each
output tile stays resident in VMEM across the whole client reduction
(revisiting-output accumulation pattern), accumulating in f32.

Every operand is at least 2-D, as the TPU lowering wants: the (d,) vectors
travel as (1, d) rows in (1, d_block) tiles, and the weights as a (K, 1)
column whose (k_block, 1) tile follows the k grid index — each program
sees exactly its own clients' weights, with no in-kernel slicing.

:func:`scaled_aggregate` (the iterate-consuming entry point) survives as a
thin compatibility wrapper; its pure-jnp oracle stays in ``kernels/ref.py``
alongside the new :func:`~repro.kernels.ref.fused_aggregate_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
K_BLOCK = 8
D_BLOCK = 512


def _fused_kernel(wt_ref, dk_ref, wts_ref, s_ref, a_ref, out_ref):
    kb = pl.program_id(1)
    # (k_block, d_block) deltas × (k_block, 1) weights, summed over the
    # client (sublane) axis in f32
    partial = (dk_ref[...].astype(jnp.float32)
               * wts_ref[...].astype(jnp.float32)).sum(axis=0, keepdims=True)

    @pl.when(kb == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(kb > 0)
    def _acc():
        out_ref[...] = out_ref[...] + partial

    @pl.when(kb == pl.num_programs(1) - 1)
    def _final():
        # out_ref holds Σ_k weights_k·δ_k; the whole epilogue — reweight
        # scalar s, A diagonal, and the +w^t server update — lands here while
        # the tile is still VMEM-resident.
        s = s_ref[0, 0].astype(jnp.float32)
        out_ref[...] = (wt_ref[...].astype(jnp.float32)
                        + a_ref[...].astype(jnp.float32) * (s * out_ref[...]))


@functools.partial(jax.jit, static_argnames=("k_block", "d_block", "interpret"))
def fused_aggregate(w_t, deltas, weights, a_diag, scale=1.0, *,
                    k_block: int = K_BLOCK, d_block: int = D_BLOCK,
                    interpret: bool = False):
    """w_t, a_diag: (d,); deltas: (K, d) client deltas w_k − w^t;
    weights: (K,); scale: scalar reweight (1.0 under full participation)."""
    K, d = deltas.shape
    k_block = min(k_block, K)
    d_pad = -(-d // d_block) * d_block
    K_pad = -(-K // k_block) * k_block

    def row(x):
        return jnp.pad(x, (0, d_pad - d)).reshape(1, d_pad)

    dk2 = jnp.pad(deltas, ((0, K_pad - K), (0, d_pad - d)))
    wts2 = jnp.pad(weights, (0, K_pad - K)).reshape(K_pad, 1)
    s2 = jnp.asarray(scale, jnp.float32).reshape(1, 1)

    grid = (d_pad // d_block, K_pad // k_block)
    vec = pl.BlockSpec((1, d_block), lambda i, k: (0, i))
    out = pl.pallas_call(
        _fused_kernel,
        grid=grid,
        in_specs=[
            vec,                                                    # w_t
            pl.BlockSpec((k_block, d_block), lambda i, k: (k, i)),  # deltas
            pl.BlockSpec((k_block, 1), lambda i, k: (k, 0)),        # weights
            pl.BlockSpec((1, 1), lambda i, k: (0, 0)),              # reweight s
            vec,                                                    # a_diag
        ],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
        interpret=interpret,
    )(row(w_t), dk2, wts2, s2, row(a_diag))
    return out[0, :d]


def fused_accumulate(acc, deltas, weights, **kw):
    """Chunk-accumulating entry: acc + Σ_k weights_k·δ_k over one client
    chunk.

    Reuses the kernel's init/acc/epilogue split with an *identity* epilogue
    (w^t = acc, A = 1, s = 1): the streamed round
    (``EngineConfig.client_chunk``) feeds each (chunk, d) delta block through
    this entry, so peak delta memory is O(chunk·d) instead of O(K·d)."""
    return fused_aggregate(acc, deltas, weights, jnp.ones_like(acc), 1.0, **kw)


def fused_epilogue(w_t, acc, a_diag, scale=1.0, **kw):
    """Epilogue-only entry: w^t + A ⊙ (s · acc), with ``acc`` the streamed
    weighted delta sum — the kernel's final grid step applied to a single
    pre-reduced (d,) row."""
    return fused_aggregate(w_t, acc[None, :], jnp.ones((1,), jnp.float32),
                           a_diag, scale, **kw)


def scaled_aggregate(w_t, w_ks, weights, a_diag, **kw):
    """Iterate-consuming compatibility entry: w^t + A ⊙ Σ_k weights_k (w_k − w^t).

    Materializes the (K, d) delta matrix from the stacked iterates and defers
    to :func:`fused_aggregate` — callers with deltas in hand (the engine)
    should call the delta-native kernel directly and skip the subtraction.
    """
    return fused_aggregate(w_t, w_ks - w_t[None, :], weights, a_diag, 1.0, **kw)
