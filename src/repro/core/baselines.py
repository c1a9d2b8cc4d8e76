"""Baseline algorithms the paper compares against (§2, §4, Fig. 2).

  * Distributed GD — the "trivial benchmark" (teal diamonds in Fig. 2).
  * One-shot averaging [107] — each node fully optimizes locally, average
    once; the paper cites [91, App. A] showing it cannot beat a single
    machine in general.  We include it because it is the extreme point of
    the communication-efficiency spectrum.
  * FedAvg-style local SGD [62] — local epochs + n_k/n-weighted averaging;
    the full subsystem lives in :mod:`repro.core.fedavg`, the wrappers here
    keep the original one-call entry points.

All round-based baselines run on the shared
:class:`~repro.core.engine.RoundEngine`: distributed GD is the degenerate
client pass ``delta_k = −h (∇f_k(w) + λw)``, whose n_k/n-weighted aggregate
is exactly ``−h ∇f(w)`` (Σ_k n_k/n = 1).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.engine import EngineConfig, RoundEngine
from repro.core.fedavg import FedAvg, FedAvgConfig
from repro.core.problem import FederatedLogReg, map_clients
from repro.core.registry import register
from repro.core.solver import FederatedSolver, SolverState


def gd_round(problem: FederatedLogReg, w: jax.Array, stepsize: float) -> jax.Array:
    """One round of distributed gradient descent (1 communication), computed
    on the flat view — the cheap reference for :class:`DistributedGD`."""
    return w - stepsize * problem.flat.grad(w)


def _gd_client_pass(w, bucket, lam, stepsize):
    """vmapped over clients: delta_k = −h (mean data grad on P_k + λw)."""

    def one_client(idx, val, y, n_k):
        nkf = jnp.maximum(n_k.astype(jnp.float32), 1.0)
        z = (val * w[idx]).sum(axis=1)                       # (m_pad,)
        g_sc = -y * jax.nn.sigmoid(-y * z) / nkf             # padded rows: val==0
        g = jnp.zeros_like(w).at[idx].add(g_sc[:, None] * val)
        return -stepsize * (g + lam * w)

    return map_clients(jax.vmap(one_client),
                       (bucket.idx, bucket.val, bucket.y, bucket.n_k),
                       w.shape[0])


class DistributedGD(FederatedSolver):
    """Distributed GD expressed on the RoundEngine (client pass = exact local
    gradient, n_k/n aggregation).  Deterministic — the round key is unused."""

    name = "gd"

    def __init__(self, problem: FederatedLogReg, stepsize: float = 2.0,
                 aggregator: str = "dense",
                 client_chunk: Optional[int] = None,
                 participation: float = 1.0,
                 cohort: Optional[int] = None,
                 virtual_data: bool = False,
                 participation_model=None,
                 fault_model=None,
                 aggregator_guard: Optional[str] = None,
                 guard_clip_norm: Optional[float] = None,
                 guard_trim: float = 0.1):
        self.problem = problem
        self.stepsize = stepsize
        virtual = virtual_data or problem.virtual is not None
        self.engine = RoundEngine(problem,
                                  EngineConfig(aggregator=aggregator,
                                               client_chunk=client_chunk,
                                               participation=participation,
                                               cohort=cohort,
                                               virtual_data=virtual,
                                               aggregator_guard=aggregator_guard,
                                               guard_clip_norm=guard_clip_norm,
                                               guard_trim=guard_trim),
                                  participation_model=participation_model,
                                  fault_model=fault_model)
        self._pass = jax.jit(functools.partial(
            _gd_client_pass, lam=problem.flat.lam, stepsize=stepsize))
        gd_pass = lambda w, bi, b, kb: self._pass(w, b)
        # deterministic pass: the per-client keys of the streamed contract
        # are simply unused
        gd_chunk_pass = lambda w, bi, cb, keys: _gd_client_pass(
            w, cb, problem.flat.lam, stepsize)
        self._round_fast = self.engine.compile(gd_pass,
                                               chunk_pass=gd_chunk_pass)
        self._round_ref = self.engine.reference(gd_pass,
                                                chunk_pass=gd_chunk_pass)

    @property
    def hyperparams(self):
        return {"stepsize": self.stepsize}

    def round(self, state: SolverState, key: jax.Array) -> SolverState:
        return state.replace(w=self._round_fast(state.w, key,
                                                round_index=state.round),
                             round=state.round + 1)


def run_gd(problem, w0, rounds: int, stepsize: float, callback=None):
    """Round loop on the flat view — one jitted O(d) gradient per round.
    Mathematically identical to :class:`DistributedGD` (see
    tests/test_engine.py), which materializes per-client deltas and is kept
    for engine parity, not for the hot path."""
    w = w0
    hist = []
    g = jax.jit(problem.flat.grad)
    for r in range(rounds):
        w = w - stepsize * g(w)
        if callback:
            hist.append(callback(w, r))
    return w, hist


def _gd_defaults():
    from repro.configs import get_gd_config
    return {"stepsize": get_gd_config().stepsize}


@register("gd", defaults=_gd_defaults,
          description="distributed gradient descent (the trivial benchmark)")
def _make_gd(problem: FederatedLogReg, **kw) -> DistributedGD:
    return DistributedGD(problem, **kw)


def fedavg_round(problem: FederatedLogReg, w, key, stepsize: float, epochs: int = 1):
    """Local SGD + n_k/n-weighted averaging (FedAvg, [62])."""
    cfg = FedAvgConfig(stepsize=stepsize, local_epochs=epochs)
    solver = FedAvg(problem, cfg)
    return solver.round(solver.init(w), key).w


def one_shot_average(problem: FederatedLogReg, w0, key, stepsize: float,
                     epochs: int = 50):
    """[107]: clients optimize to (near-)completion locally; average once."""
    return fedavg_round(problem, w0, key, stepsize, epochs=epochs)


def majority_baseline_error(train_y, train_client_of, test_y, test_client_of):
    """Per-client majority-vote error (the paper's 17.14% analogue)."""
    import numpy as np
    K = int(max(train_client_of.max(), test_client_of.max())) + 1
    maj = np.zeros(K, np.float32)
    for k in range(K):
        yk = train_y[train_client_of == k]
        maj[k] = 1.0 if (yk > 0).mean() >= 0.5 else -1.0
    pred = maj[test_client_of]
    return float((pred != test_y).mean())
