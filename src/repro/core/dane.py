"""DANE — Distributed Approximate Newton (Algorithm 2) on the RoundEngine.

Local subproblem (eq. 10):

    w_k = argmin_w F_k(w) − (∇F_k(w^t) − η∇f(w^t))ᵀ w + (µ/2)||w − w^t||²

Every variant is expressed as a :data:`~repro.core.engine.ClientPassFn`
returning per-client deltas ``w_k − w^t``; the shared
:class:`~repro.core.engine.RoundEngine` owns client sampling and the
(uniform, per the paper's "averages the solutions" step) aggregation:

  * :class:`DANE` — sparse L2-logistic regression (the Fig.-2 problem), with
    two inexact local solvers: ``local_solver="gd"`` runs ``local_steps``
    gradient steps on the subproblem (each step the fused Pallas
    :func:`repro.kernels.dane_update.dane_update` on TPU, the identical jnp
    expression elsewhere); ``local_solver="svrg"`` is the Proposition-1
    construction — one epoch of generic SVRG on the *explicitly
    materialized* subproblem (linear perturbation and all), whose iterates
    Prop. 1 proves identical to naive Federated SVRG (Algorithm 3) given
    the same sample sequence.  tests/test_equivalence.py checks this to
    float tolerance against the independently coded Algorithm 3.
  * :class:`DANERidge` — the exact solver for ridge regression (per-client
    d×d linear solves, vmapped over each bucket of a
    :func:`~repro.core.problem.build_dense_problem` layout); used for the
    §3.4 property tests (one-round solve on identical data, Property A
    fixed point) and pinned against the pre-port list implementation in
    tests/test_dane_cocoa_engine.py.

:func:`dane_svrg_round` keeps the original one-call entry point for the
Prop.-1 equivalence test.
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

_SOLVERS = ("gd", "svrg")


def ridge_grad(X, y, w, lam):
    """F(w) = 1/(2m) ||X^T w - y||^2 + lam/2 ||w||^2 with X: (d, m)."""
    m = y.shape[0]
    return X @ (X.T @ w - y) / m + lam * w


@dataclasses.dataclass(frozen=True)
class DANEConfig:
    """Knobs of Algorithm 2 and its local solvers."""

    eta: float = 1.0               # η: full-gradient weight in a_k (eq. 10)
    mu: float = 0.0                # µ: prox coefficient (eq. 10)
    local_solver: str = "gd"       # "gd" | "svrg" (the Prop.-1 construction)
    local_steps: int = 50          # GD solver: iterations on the subproblem
    local_lr: float = 1.0          # GD solver: stepsize
    svrg_stepsize: float = 0.05    # SVRG solver: stepsize h
    svrg_steps: int = 25           # SVRG solver: samples m per epoch
    participation: float = 1.0     # i.i.d. per-round client participation
    # None -> auto: fused Pallas dane_update kernel on TPU, jnp elsewhere.
    use_kernel: Optional[bool] = None
    aggregator: str = "dense"      # engine aggregator: "dense" | "pallas"
    # None -> materialize each bucket's (Kb, d) delta stack; an int streams
    # the client axis in chunks of this size (see EngineConfig.client_chunk)
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
    fault_model: Optional[Any] = None
    # robust server aggregation: None | "clip" | "trimmed_mean" | "median"
    # (see EngineConfig.aggregator_guard for the composition rules)
    aggregator_guard: Optional[str] = None
    guard_clip_norm: Optional[float] = None
    guard_trim: float = 0.1

    def __post_init__(self):
        if self.local_solver not in _SOLVERS:
            raise ValueError(f"local_solver must be one of {_SOLVERS}")


def _dane_gd_pass(w0, full_grad, bucket: ClientBucket, lam, cfg: DANEConfig,
                  use_kernel: bool, key):
    """vmapped over clients: ``local_steps`` GD steps on subproblem (10).
    Deterministic — ``key`` is part of the ClientPassFn signature only.
    Returns (Kb, d) client deltas w_k − w0."""
    del key
    lr, eta, mu = cfg.local_lr, cfg.eta, cfg.mu

    def one_client(idx, val, y, n_k):
        d = w0.shape[0]
        nkf = jnp.maximum(n_k.astype(jnp.float32), 1.0)
        valid = (jnp.arange(y.shape[0]) < n_k).astype(jnp.float32)

        def data_grad(wk):
            """Sparse data part of ∇F_k; the dense λ·wk part is fused into
            the update step."""
            z = y * (val * wk[idx]).sum(axis=1)
            gs = -y * jax.nn.sigmoid(-y * z) * valid / nkf
            return jnp.zeros((d,)).at[idx].add(gs[:, None] * val)

        a_k = data_grad(w0) + lam * w0 - eta * full_grad   # ∇F_k(w^t) − η∇f(w^t)

        def gd_step(wk, _):
            g = data_grad(wk)
            if use_kernel:
                from repro.kernels import ops
                wk = ops.dane_update(wk, g, a_k, w0, lr, lam, mu)
            else:
                wk = ((1.0 - lr * (lam + mu)) * wk - lr * g + lr * a_k
                      + lr * mu * w0)
            return wk, None

        wk, _ = jax.lax.scan(gd_step, w0, None, length=cfg.local_steps)
        return wk - w0

    return map_clients(jax.vmap(one_client),
                       (bucket.idx, bucket.val, bucket.y, bucket.n_k),
                       w0.shape[0])


def _dane_svrg_pass(w0, full_grad, bucket: ClientBucket, lam, cfg: DANEConfig,
                    key):
    keys = jax.random.split(key, bucket.num_clients)
    return _dane_svrg_pass_keyed(w0, full_grad, bucket, lam, cfg, keys)


def _dane_svrg_pass_keyed(w0, full_grad, bucket: ClientBucket, lam,
                          cfg: DANEConfig, keys):
    """Proposition 1: solve subproblem (10) *as a subproblem* (η=1, µ=0)
    with one epoch of generic SVRG.  Returns (Kb, d) deltas w_k − w0.
    Takes explicit per-client keys so the engine's streamed path can hand
    chunk-sized slices of the bucket's key split.

    The SVRG epoch on G_k(w') = F_k(w') − a_kᵀw' starting at w^t:
      full gradient of G_k at anchor w^t is ∇F_k(w^t) − a_k = ∇f(w^t)
      (no extra pass needed — exactly the observation in §3.5);
      stochastic update uses ∇g_i(w') − ∇g_i(w^t) + ∇G_k(w^t), where
      g_i(w') = f_i(w') − a_kᵀw' so the linear term cancels in the
      difference.  The code below nevertheless *materializes a_k and the
      linear term explicitly* so the equivalence with Algorithm 3 is a real
      test, not a tautology.
    """
    stepsize, m = cfg.svrg_stepsize, cfg.svrg_steps

    def one_client(idx, val, y, n_k, ck):
        d = w0.shape[0]
        nkf = jnp.maximum(n_k.astype(jnp.float32), 1.0)
        valid_rows = (jnp.arange(y.shape[0]) < n_k).astype(jnp.float32)

        def Fk_grad(wk):
            z = y * (val * wk[idx]).sum(axis=1)
            gs = -y * jax.nn.sigmoid(-y * z) * valid_rows / nkf
            return jnp.zeros((d,)).at[idx].add(gs[:, None] * val) + lam * wk

        a_k = Fk_grad(w0) - full_grad          # η = 1
        G_anchor_grad = Fk_grad(w0) - a_k      # = ∇f(w^t), materialized

        def fi_grad(wk, i):
            xi, vi, yi = idx[i], val[i], y[i]
            z = (vi * wk[xi]).sum()
            gs = -yi * jax.nn.sigmoid(-yi * z)
            return jnp.zeros((d,)).at[xi].add(gs * vi) + lam * wk

        samples = jax.random.randint(ck, (m,), 0, jnp.maximum(n_k, 1))

        def step(wk, i):
            gi_new = fi_grad(wk, i) - a_k      # ∇g_i(w')
            gi_old = fi_grad(w0, i) - a_k      # ∇g_i(w^t)
            wk = wk - stepsize * (gi_new - gi_old + G_anchor_grad)
            return wk, None

        wk, _ = jax.lax.scan(step, w0, samples)
        return wk - w0

    return map_clients(jax.vmap(one_client),
                       (bucket.idx, bucket.val, bucket.y, bucket.n_k, keys),
                       w0.shape[0])


class DANE(FederatedSolver):
    """:class:`~repro.core.solver.FederatedSolver` for Algorithm 2: per-round
    full gradient (1 extra communication, as in Alg. 2 step 1) closed over
    the client pass; sampling/aggregation on the shared engine with uniform
    1/K weighting (Alg. 2 step 3: "averages the solutions")."""

    name = "dane"

    def __init__(self, problem: FederatedLogReg, cfg: DANEConfig = DANEConfig()):
        self.problem = problem
        self.cfg = cfg
        use_kernel = cfg.use_kernel
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        lam = problem.flat.lam
        virtual = cfg.virtual_data or problem.virtual is not None
        if cfg.local_solver == "gd":
            self._pass = jax.jit(functools.partial(
                _dane_gd_pass, lam=lam, cfg=cfg, use_kernel=use_kernel))
        else:
            self._pass = jax.jit(functools.partial(_dane_svrg_pass, lam=lam,
                                                   cfg=cfg))
        self.engine = RoundEngine(
            problem,
            EngineConfig(participation=cfg.participation, weighting="uniform",
                         aggregator=cfg.aggregator,
                         client_chunk=cfg.client_chunk,
                         cohort=cfg.cohort,
                         virtual_data=virtual,
                         aggregator_guard=cfg.aggregator_guard,
                         guard_clip_norm=cfg.guard_clip_norm,
                         guard_trim=cfg.guard_trim),
            participation_model=cfg.participation_model,
            fault_model=cfg.fault_model,
        )

        # Alg. 2 step 1's full gradient is the eager prelude (its own round
        # of communication); the rest of the round is one compiled dispatch.
        def dane_pass(w, bi, bucket, kb, full_grad):
            return self._pass(w, full_grad, bucket, key=kb)

        if cfg.local_solver == "gd":
            def dane_chunk_pass(w, bi, chunk_bucket, keys, full_grad):
                return _dane_gd_pass(w, full_grad, chunk_bucket, lam, cfg,
                                     use_kernel, key=None)
        else:
            def dane_chunk_pass(w, bi, chunk_bucket, keys, full_grad):
                return _dane_svrg_pass_keyed(w, full_grad, chunk_bucket, lam,
                                             cfg, keys)

        prelude = lambda w: (self.problem.flat.grad(w),)
        self._round_fast = self.engine.compile(dane_pass, prelude=prelude,
                                               chunk_pass=dane_chunk_pass)
        self._round_ref = self.engine.reference(dane_pass, prelude=prelude,
                                                chunk_pass=dane_chunk_pass)

    def round(self, state: SolverState, key: jax.Array) -> SolverState:
        return state.replace(w=self._round_fast(state.w, key,
                                                round_index=state.round),
                             round=state.round + 1)


def dane_svrg_round(problem: FederatedLogReg, w, key, stepsize: float, m: int):
    """One Prop.-1 round (DANE η=1, µ=0, one SVRG epoch as local solver) —
    the original entry point, now a thin wrapper over the engine port."""
    cfg = DANEConfig(eta=1.0, mu=0.0, local_solver="svrg",
                     svrg_stepsize=stepsize, svrg_steps=m)
    solver = DANE(problem, cfg)
    return solver.round(solver.init(w), key).w


class DANERidge(FederatedSolver):
    """Exact DANE for ridge regression (d×d local solves) on the engine.

    F_k(w) = 1/(2 n_k)||X_kᵀw − y_k||² + (λ/2)||w||²; subproblem (10) is the
    linear system (H_k + µI) w = c_k + a_k + µw^t with H_k = X_kX_kᵀ/n_k + λI
    and c_k = X_k y_k / n_k, solved exactly per client (vmapped over each
    bucket) and uniformly averaged by the engine.  ``problem`` must be a
    :func:`~repro.core.problem.build_dense_problem` layout; λ is read from
    ``problem.flat.lam``."""

    name = "dane_ridge"

    def __init__(self, problem: FederatedLogReg, *, eta: float = 1.0,
                 mu: float = 0.0, aggregator: str = "dense"):
        self.problem = problem
        self.lam = float(problem.flat.lam)
        self.eta, self.mu = float(eta), float(mu)
        self.engine = RoundEngine(self.problem,
                                  EngineConfig(weighting="uniform",
                                               aggregator=aggregator))
        self._round_fast = self.engine.compile(self._ridge_pass,
                                               prelude=self._prelude)
        self._round_ref = self.engine.reference(self._ridge_pass,
                                                prelude=self._prelude)

    @property
    def hyperparams(self):
        return {"eta": self.eta, "mu": self.mu}

    def full_grad(self, w: jax.Array) -> jax.Array:
        """∇f(w) = (1/n) Σ_k X_k (X_kᵀ w − y_k) + λw, from the buckets."""
        n = self.problem.flat.n
        g = self.lam * w
        for b in self.problem.buckets:
            resid = jnp.einsum("kmd,d->km", b.val, w) - b.y
            g = g + jnp.einsum("kmd,km->d", b.val, resid) / n
        return g

    def _prelude(self, w):
        return (self.full_grad(w),)

    def _ridge_pass(self, w, bi, bucket, kb, fg):
        lam, eta, mu = self.lam, self.eta, self.mu

        def one_client(val, y, n_k):
            d = w.shape[0]
            X = val.T                                  # (d, m)
            m = jnp.maximum(n_k, 1).astype(val.dtype)
            grad_k = X @ (X.T @ w - y) / m + lam * w
            a_k = grad_k - eta * fg
            H = X @ X.T / m + (lam + mu) * jnp.eye(d, dtype=val.dtype)
            rhs = X @ y / m + a_k + mu * w
            return jnp.linalg.solve(H, rhs) - w

        return jax.vmap(one_client)(bucket.val, bucket.y, bucket.n_k)

    def round(self, state: SolverState, key: jax.Array) -> SolverState:
        return state.replace(w=self._round_fast(state.w, key),
                             round=state.round + 1)


def _dane_defaults():
    from repro.configs import get_dane_config
    c = get_dane_config()
    return {"eta": c.eta, "mu": c.mu, "local_steps": c.local_steps,
            "local_lr": c.local_lr}


@register("dane", defaults=_dane_defaults,
          description="DANE (Algorithm 2) with inexact GD/SVRG local solvers")
def _make_dane(problem: FederatedLogReg, **kw) -> DANE:
    return DANE(problem, DANEConfig(**kw))


@register("dane_ridge", layout="dense",
          description="exact DANE for ridge regression (d×d local solves)")
def _make_dane_ridge(problem: FederatedLogReg, **kw) -> DANERidge:
    return DANERidge(problem, **kw)
