"""Federated Averaging (McMahan et al., arXiv:1602.05629) on the RoundEngine.

The companion algorithm to the paper's FSVRG: each participating client runs
``local_epochs`` permutation passes of plain SGD on its own data, the server
n_k/n-averages the resulting deltas.  In the 1602.05629 notation this is
B=∞ (full sequential pass per epoch), E=``local_epochs``,
C=``participation``.

One local step on the L2-regularized logistic objective is

    w ← w − h (∇f_i(w) + λ w)  =  (1 − hλ)·w − h·∇f_i(w)

— the compute hot spot, executed n_k·E times per client per round.  On TPU
the dense part (weight-decay multiply + gradient axpy over all d
coordinates) runs as the fused Pallas kernel
:func:`repro.kernels.fedavg_update.fedavg_update` (one VMEM pass, same
(rows, 128) tiling as ``fsvrg_update``); elsewhere it runs as the identical
jnp expression.  Padded permutation slots fold into the kernel's stepsize
(h_eff = 0 ⇒ exact no-op), so validity masking costs nothing extra.

Round scheduling (client sampling, n_k/n vs uniform weighting, partial-
participation reweighting) is entirely the engine's: FedAvg only supplies
the local-SGD client pass.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.engine import EngineConfig, RoundEngine
from repro.core.problem import ClientBucket, FederatedLogReg, map_clients
from repro.core.registry import register
from repro.core.solver import FederatedSolver, SolverState


@dataclasses.dataclass(frozen=True)
class FedAvgConfig:
    stepsize: float = 0.1          # h, the raw per-step local stepsize
    local_epochs: int = 1          # E: permutation passes per client per round
    participation: float = 1.0     # C: i.i.d. client fraction per round
    use_weighted_agg: bool = True  # n_k/n (True) vs uniform 1/K averaging
    # None -> auto: fused Pallas kernel on TPU, plain jnp elsewhere.
    use_kernel: Optional[bool] = None
    aggregator: str = "dense"      # engine aggregator: "dense" | "pallas"
    # None -> materialize each bucket's (Kb, d) delta stack; an int streams
    # the client axis in chunks of this size (paper-scale K on bounded
    # memory; see EngineConfig.client_chunk)
    client_chunk: Optional[int] = None
    # under partial participation, compute only the sampled cohort (padded
    # to this per-bucket capacity; see EngineConfig.cohort / cohort_capacity)
    cohort: Optional[int] = None
    # run on a build_virtual_problem layout: rows regenerate on demand
    # inside the round (see EngineConfig.virtual_data; auto-detected)
    virtual_data: bool = False
    # replace the Bernoulli draw with a repro.fleet participation model
    # (trace-driven availability/stragglers); `participation` then serves
    # as the model's upper-bound rate for cohort capacity sizing
    participation_model: Optional[Any] = None
    # corrupt returned deltas through a repro.fleet.faults fault model
    # (NaN poisoning, sign flips, scaling attacks, stale replay)
    fault_model: Optional[Any] = None
    # robust server aggregation: None | "clip" | "trimmed_mean" | "median"
    # (see EngineConfig.aggregator_guard for the composition rules)
    aggregator_guard: Optional[str] = None
    guard_clip_norm: Optional[float] = None
    guard_trim: float = 0.1


def _local_sgd_pass(w0, bucket: ClientBucket, lam, cfg: FedAvgConfig,
                    use_kernel: bool, key):
    """vmapped over clients in a bucket: E epochs of permutation-order SGD.
    Returns (Kb, d) client deltas w_k - w0."""
    keys = jax.random.split(key, bucket.num_clients)
    return _local_sgd_pass_keyed(w0, bucket, lam, cfg, use_kernel, keys)


def _local_sgd_pass_keyed(w0, bucket: ClientBucket, lam, cfg: FedAvgConfig,
                          use_kernel: bool, keys):
    """:func:`_local_sgd_pass` over explicit per-client keys — the engine's
    streamed (``client_chunk``) path hands in chunk-sized bucket slices with
    the matching slice of the bucket's key split."""

    h = cfg.stepsize

    def one_client(idx, val, y, n_k, ck):
        d = w0.shape[0]
        m_pad = y.shape[0]

        def epoch(wk, ek):
            perm = jax.random.permutation(ek, m_pad)

            def step(wk, i):
                xi, vi, yi = idx[i], val[i], y[i]
                valid = (i < n_k).astype(jnp.float32)
                z = (vi * wk[xi]).sum()
                g_sc = -yi * jax.nn.sigmoid(-yi * z)
                g = jnp.zeros((d,)).at[xi].add(g_sc * vi)
                h_eff = valid * h                  # padded slot -> exact no-op
                if use_kernel:
                    from repro.kernels import ops
                    return ops.fedavg_update(wk, g, h_eff, lam), None
                return (1.0 - h_eff * lam) * wk - h_eff * g, None

            wk, _ = jax.lax.scan(step, wk, perm)
            return wk, None

        wk, _ = jax.lax.scan(epoch, w0, jax.random.split(ck, cfg.local_epochs))
        return wk - w0

    return map_clients(jax.vmap(one_client),
                       (bucket.idx, bucket.val, bucket.y, bucket.n_k, keys),
                       w0.shape[0])


class FedAvg(FederatedSolver):
    """:class:`~repro.core.solver.FederatedSolver` mirroring
    :class:`repro.core.fsvrg.FSVRG`."""

    name = "fedavg"

    def __init__(self, problem: FederatedLogReg, cfg: FedAvgConfig = FedAvgConfig()):
        self.problem = problem
        self.cfg = cfg
        use_kernel = cfg.use_kernel
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        virtual = cfg.virtual_data or problem.virtual is not None
        self._pass = jax.jit(functools.partial(
            _local_sgd_pass, lam=problem.flat.lam, cfg=cfg,
            use_kernel=use_kernel))
        self.engine = RoundEngine(
            problem,
            EngineConfig(
                participation=cfg.participation,
                weighting="nk" if cfg.use_weighted_agg else "uniform",
                aggregator=cfg.aggregator,
                client_chunk=cfg.client_chunk,
                cohort=cfg.cohort,
                virtual_data=virtual,
                aggregator_guard=cfg.aggregator_guard,
                guard_clip_norm=cfg.guard_clip_norm,
                guard_trim=cfg.guard_trim,
            ),
            participation_model=cfg.participation_model,
            fault_model=cfg.fault_model,
        )

        def fedavg_pass(w, bi, bucket, kb):
            return self._pass(w, bucket, key=kb)

        def fedavg_chunk_pass(w, bi, chunk_bucket, keys):
            return _local_sgd_pass_keyed(w, chunk_bucket, problem.flat.lam,
                                         cfg, use_kernel, keys)

        self._round_fast = self.engine.compile(fedavg_pass,
                                               chunk_pass=fedavg_chunk_pass)
        self._round_ref = self.engine.reference(fedavg_pass,
                                                chunk_pass=fedavg_chunk_pass)

    def round(self, state: SolverState, key: jax.Array) -> SolverState:
        return state.replace(w=self._round_fast(state.w, key,
                                                round_index=state.round),
                             round=state.round + 1)


def _fedavg_defaults():
    from repro.configs import get_fedavg_config
    c = get_fedavg_config()
    return {"stepsize": c.stepsize, "local_epochs": c.local_epochs,
            "participation": c.participation}


@register("fedavg", defaults=_fedavg_defaults,
          description="Federated Averaging (arXiv:1602.05629, B=∞)")
def _make_fedavg(problem: FederatedLogReg, **kw) -> FedAvg:
    return FedAvg(problem, FedAvgConfig(**kw))
