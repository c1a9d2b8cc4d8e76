"""Federated SVRG — the paper's Algorithm 4 (and the naive Algorithm 3).

One round (Algorithm 4):
  1. server: compute ∇f(w^t) over all data      — 1 round of communication
  2. each client k, in parallel:
       w_k = w^t;  h_k = h / n_k
       for t over a random permutation of P_k:
         w_k ← w_k − h_k ( S_k [∇f_i(w_k) − ∇f_i(w^t)] + ∇f(w^t) )
  3. server: w ← w^t + A Σ_k (n_k/n)(w_k − w^t)

The four FSVRG modifications vs naive distributed SVRG (§3.6.2):
  (1) local stepsize h_k = h/n_k, (2) n_k/n-weighted aggregation,
  (3) per-coordinate stochastic-gradient scaling S_k,
  (4) per-coordinate aggregation scaling A.

Clients run as vmap-over-bucket × scan-over-permutation; padded permutation
slots are masked no-ops, so every real example is visited exactly once per
round (the paper uses permutation sampling, line 6 of Alg. 4).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import scaling
from repro.core.engine import EngineConfig, RoundEngine
from repro.core.problem import ClientBucket, FederatedLogReg, map_clients
from repro.core.registry import register
from repro.core.solver import FederatedSolver, SolverState


@dataclasses.dataclass(frozen=True)
class FSVRGConfig:
    stepsize: float = 1.0          # h; h_k = h/n_k per client
    naive: bool = False            # Algorithm 3: S=I, A=I, h_k=h, uniform agg
    naive_steps: int = 0           # m for Algorithm 3 (0 -> one pass, m=n_k)
    use_S: bool = True             # ablation switches
    use_A: bool = True
    use_local_stepsize: bool = True
    use_weighted_agg: bool = True
    # partial participation (beyond-paper, the deployment reality the paper
    # motivates in §1.2: devices only participate when charging/on-wifi).
    # Each round samples clients i.i.d. with this probability; aggregation
    # reweights by the realized participating mass so the update direction
    # stays unbiased.
    participation: float = 1.0
    # engine aggregator: "dense" (eager jnp reference) | "pallas" (the
    # delta-native fused_aggregate kernel — one HBM pass over the deltas)
    aggregator: str = "dense"
    # None -> materialize each bucket's (Kb, d) delta stack; an int streams
    # the client axis in chunks of this size (paper-scale K on bounded
    # memory; see EngineConfig.client_chunk)
    client_chunk: Optional[int] = None
    # under partial participation, compute only the sampled cohort (padded
    # to this per-bucket capacity; see EngineConfig.cohort / cohort_capacity)
    cohort: Optional[int] = None
    # run on a build_virtual_problem layout: rows regenerate on demand
    # inside the round (see EngineConfig.virtual_data).  Auto-detected from
    # the problem, so passing a virtual problem is enough.
    virtual_data: bool = False
    # replace the Bernoulli draw with a repro.fleet participation model
    # (trace-driven availability/stragglers); `participation` then serves
    # as the model's upper-bound rate for cohort capacity sizing
    participation_model: Optional[Any] = None
    # corrupt returned deltas through a repro.fleet.faults fault model
    fault_model: Optional[Any] = None
    # robust server aggregation: None | "clip" | "trimmed_mean" | "median"
    # (see EngineConfig.aggregator_guard for the composition rules)
    aggregator_guard: Optional[str] = None
    guard_clip_norm: Optional[float] = None
    guard_trim: float = 0.1


def _client_pass(w0, full_grad, bucket: ClientBucket, lam, phi, cfg: FSVRGConfig, key):
    """vmapped over clients in a bucket. Returns (Kb, d) client deltas w_k - w0."""
    keys = jax.random.split(key, bucket.num_clients)
    return _client_pass_keyed(w0, full_grad, bucket, lam, phi, cfg, keys)


def _client_pass_keyed(w0, full_grad, bucket: ClientBucket, lam, phi,
                       cfg: FSVRGConfig, keys):
    """:func:`_client_pass` over explicit per-client keys — the engine's
    streamed (``client_chunk``) path hands in chunk-sized bucket slices with
    the matching slice of the bucket's key split, so chunked and unchunked
    clients consume identical randomness."""

    def one_client(idx, val, y, n_k, ck):
        d = w0.shape[0]
        nkf = jnp.maximum(n_k.astype(jnp.float32), 1.0)
        if cfg.naive or not cfg.use_S:
            s_diag = jnp.ones((d,))
        else:
            s_diag = scaling.s_k_diag(phi, idx, val, n_k)
        if cfg.naive or not cfg.use_local_stepsize:
            h_k = cfg.stepsize                      # Alg. 3: fixed h
        else:
            h_k = cfg.stepsize / nkf                # Alg. 4: h/n_k

        m_pad = y.shape[0]
        if cfg.naive:
            # Alg. 3 line 7: m uniform samples with replacement from P_k
            m = cfg.naive_steps if cfg.naive_steps > 0 else m_pad
            samples = jax.random.randint(ck, (m,), 0, jnp.maximum(n_k, 1))
            valid_fn = lambda i: jnp.float32(1.0)
        else:
            # Alg. 4 line 6: one pass over a random permutation of P_k
            samples = jax.random.permutation(ck, m_pad)
            valid_fn = lambda i: (i < n_k).astype(jnp.float32)

        # margins at the anchor w^t are recomputed per step (O(nnz));
        # the anchor per-example gradient scalar needs only x·w0.
        def step(wk, i):
            xi, vi, yi = idx[i], val[i], y[i]
            valid = valid_fn(i)
            zi_new = (vi * wk[xi]).sum()
            zi_old = (vi * w0[xi]).sum()
            g_new = -yi * jax.nn.sigmoid(-yi * zi_new)
            g_old = -yi * jax.nn.sigmoid(-yi * zi_old)
            # sparse part of ∇f_i(w_k) − ∇f_i(w^t)
            diff = jnp.zeros((d,)).at[xi].add((g_new - g_old) * vi)
            diff = diff + lam * (wk - w0)          # L2 part of the difference
            upd = h_k * (s_diag * diff + full_grad)
            return wk - valid * upd, None

        wk, _ = jax.lax.scan(step, w0, samples)
        return wk - w0

    return map_clients(jax.vmap(one_client),
                       (bucket.idx, bucket.val, bucket.y, bucket.n_k, keys),
                       w0.shape[0])


class FSVRG(FederatedSolver):
    """:class:`~repro.core.solver.FederatedSolver` for Algorithms 3 & 4:
    precomputes φ and A once, then runs rounds on the shared
    :class:`~repro.core.engine.RoundEngine` (which owns client sampling,
    weighting, and aggregation — mods. 2 & 4 map onto its ``weighting`` /
    ``server_scaling`` knobs)."""

    def __init__(self, problem: FederatedLogReg, cfg: FSVRGConfig = FSVRGConfig()):
        self.problem = problem
        self.cfg = cfg
        self.name = "svrg_naive" if cfg.naive else "fsvrg"
        flat = problem.flat
        n = flat.n
        virtual = cfg.virtual_data or problem.virtual is not None
        self.phi = scaling.global_feature_counts(flat) / n
        self.a_diag = scaling.aggregation_diag(problem) if cfg.use_A else jnp.ones((problem.d,))
        # one jitted pass; the bucket is an argument (its rows are never
        # closed over, so no compiled program embeds them)
        self._pass = jax.jit(functools.partial(_client_pass, lam=flat.lam,
                                               cfg=cfg))
        plain = cfg.naive  # Alg. 3: uniform aggregation, no A scaling
        self.engine = RoundEngine(
            problem,
            EngineConfig(
                participation=cfg.participation,
                weighting="uniform" if (plain or not cfg.use_weighted_agg) else "nk",
                server_scaling="diag" if (cfg.use_A and not plain) else "none",
                aggregator=cfg.aggregator,
                client_chunk=cfg.client_chunk,
                cohort=cfg.cohort,
                virtual_data=virtual,
                aggregator_guard=cfg.aggregator_guard,
                guard_clip_norm=cfg.guard_clip_norm,
                guard_trim=cfg.guard_trim,
            ),
            a_diag=self.a_diag,
            participation_model=cfg.participation_model,
            fault_model=cfg.fault_model,
        )
        # The full gradient is the round's own communication (Alg. 4 line 3),
        # so it is the eager prelude; everything after it is one compiled
        # dispatch.  The eager reference twin backs the pin tests and the
        # round-latency benchmark's baseline.
        def fsvrg_pass(w, bi, bucket, kb, full_grad):
            return self._pass(w, full_grad, bucket, phi=self.phi, key=kb)

        def fsvrg_chunk_pass(w, bi, chunk_bucket, keys, full_grad):
            return _client_pass_keyed(w, full_grad, chunk_bucket, flat.lam,
                                      self.phi, cfg, keys)

        prelude = lambda w: (self.problem.flat.grad(w),)
        self._round_fast = self.engine.compile(fsvrg_pass, prelude=prelude,
                                               chunk_pass=fsvrg_chunk_pass)
        self._round_ref = self.engine.reference(fsvrg_pass, prelude=prelude,
                                                chunk_pass=fsvrg_chunk_pass)

    def round(self, state: SolverState, key: jax.Array) -> SolverState:
        return state.replace(w=self._round_fast(state.w, key,
                                                round_index=state.round),
                             round=state.round + 1)


def naive_fsvrg_round(problem: FederatedLogReg, w, key, stepsize: float, m: Optional[int] = None):
    """Algorithm 3: S=I, A=I, h_k=h, m uniform samples, (1/K)-average agg."""
    cfg = FSVRGConfig(stepsize=stepsize, naive=True, naive_steps=m or 0)
    solver = FSVRG(problem, cfg)
    return solver.round(solver.init(w), key).w


def _fsvrg_defaults():
    from repro.configs import get_fsvrg_config
    c = get_fsvrg_config()
    return {"stepsize": c.stepsize}


@register("fsvrg", defaults=_fsvrg_defaults,
          description="Federated SVRG (Algorithm 4, all four modifications)")
def _make_fsvrg(problem: FederatedLogReg, **kw) -> FSVRG:
    return FSVRG(problem, FSVRGConfig(**kw))


@register("svrg_naive",
          defaults=lambda: {"stepsize": 0.01, "naive_steps": 50},
          description="naive distributed SVRG (Algorithm 3: S=I, A=I, "
                      "fixed h, uniform averaging)")
def _make_svrg_naive(problem: FederatedLogReg, **kw) -> FSVRG:
    return FSVRG(problem, FSVRGConfig(naive=True, **kw))
