"""Public jit'd wrappers for the Pallas kernels.

On CPU (this container) the kernels execute with ``interpret=True`` — the
kernel body runs as traced JAX ops, validating indexing/accumulation logic
against ``ref.py``.  On TPU backends the same call sites compile to Mosaic.
"""
from __future__ import annotations

import jax

from repro.kernels.cocoa_sdca import cocoa_sdca_update as _cocoa_sdca_update
from repro.kernels.dane_update import dane_update as _dane_update
from repro.kernels.fedavg_update import fedavg_update as _fedavg_update
from repro.kernels.fsvrg_update import fsvrg_update as _fsvrg_update
from repro.kernels.scaled_aggregate import fused_accumulate as _fused_accumulate
from repro.kernels.scaled_aggregate import fused_aggregate as _fused_aggregate
from repro.kernels.scaled_aggregate import fused_epilogue as _fused_epilogue
from repro.kernels.scaled_aggregate import scaled_aggregate as _scaled_aggregate
from repro.kernels.wkv6 import wkv6 as _wkv6


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fsvrg_update(w, s, g_new, g_old, g_bar, h, **kw):
    kw.setdefault("interpret", not _on_tpu())
    return _fsvrg_update(w, s, g_new, g_old, g_bar, h, **kw)


def fedavg_update(w, g, h, lam, **kw):
    kw.setdefault("interpret", not _on_tpu())
    return _fedavg_update(w, g, h, lam, **kw)


def dane_update(w, g, a, w_t, lr, lam, mu, **kw):
    kw.setdefault("interpret", not _on_tpu())
    return _dane_update(w, g, a, w_t, lr, lam, mu, **kw)


def cocoa_sdca_update(beta0, mcoef, ccoef, **kw):
    kw.setdefault("interpret", not _on_tpu())
    return _cocoa_sdca_update(beta0, mcoef, ccoef, **kw)


def scaled_aggregate(w_t, w_ks, weights, a_diag, **kw):
    kw.setdefault("interpret", not _on_tpu())
    return _scaled_aggregate(w_t, w_ks, weights, a_diag, **kw)


def fused_aggregate(w_t, deltas, weights, a_diag, scale=1.0, **kw):
    kw.setdefault("interpret", not _on_tpu())
    return _fused_aggregate(w_t, deltas, weights, a_diag, scale, **kw)


def fused_accumulate(acc, deltas, weights, **kw):
    kw.setdefault("interpret", not _on_tpu())
    return _fused_accumulate(acc, deltas, weights, **kw)


def fused_epilogue(w_t, acc, a_diag, scale=1.0, **kw):
    kw.setdefault("interpret", not _on_tpu())
    return _fused_epilogue(w_t, acc, a_diag, scale, **kw)


def wkv6(r, k, v, w, u, **kw):
    kw.setdefault("interpret", not _on_tpu())
    return _wkv6(r, k, v, w, u, **kw)
