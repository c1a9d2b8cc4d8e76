"""CoCoA+ and the Appendix-A primal/dual methods, on the RoundEngine.

All three algorithms carry *per-client state across rounds* — exactly the
case the engine's :meth:`~repro.core.engine.RoundEngine.round_with_state`
hook exists for: the client pass receives and returns its bucket's state,
and the primal deltas flow through the ordinary aggregation path with
``weighting="sum"`` (each delta already carries its 1/(λn) normalization,
so the server update is the plain Σ_k of Algorithm 6 / CoCoA+).

  * :class:`CoCoAPlus` — CoCoA+ [arXiv:1502.03508] with γ=1 (adding) and the
    safe σ′ = γK, on the general bucketed sparse logreg problem.  State is
    the dual block α_k (Kb, m_pad) per bucket; the local solver is one
    permutation pass of SDCA whose per-coordinate subproblem (from eq. 15)
    is solved by clipped Newton — fused across the vmapped client batch by
    the Pallas kernel :func:`repro.kernels.cocoa_sdca.cocoa_sdca_update` on
    TPU, the identical jnp recursion elsewhere.  The paper's Fig. 2 shows it
    converging slowly on sparse non-IID data because σ′ scales with K.
  * :class:`PrimalMethod` — Algorithm 5: quadratic-perturbation method with
    perturbation vectors a_k^t = ∇F_k(w^t) − (η∇F_k(w^t) + g_k^t); state is
    g_k, updated from the aggregated w^{t+1} after the round.
  * :class:`DualMethod` — Algorithm 6: dual block proximal gradient ascent
    with exact block solves (eq. 19); state is α_k, and the iterate tracks
    w^t = (1/λn) X α^t incrementally through the sum-weighted deltas.
  * Theorem 5: for ridge regression Algorithms 5 and 6 generate identical
    iterates under w^t = (1/λn) X α^t — checked on the engine ports in
    tests/test_equivalence.py (both classes assume equal n_k, as the paper
    does "for simplicity", on a :func:`build_dense_problem` layout).

The pre-port list-based implementations survive verbatim in
tests/_oracles.py and pin these ports round-by-round.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.scipy.special import xlogy

from repro.core.engine import EngineConfig, RoundEngine
from repro.core.problem import ClientBucket, FederatedLogReg, map_clients
from repro.core.registry import register
from repro.core.solver import FederatedSolver, SolverState


def dual_to_primal(Xs, alphas, lam):
    """w = (1/λn) Σ_k X_k α_k for list-of-arrays dual blocks."""
    n = sum(int(a.shape[0]) for a in alphas)
    return sum(X @ a for X, a in zip(Xs, alphas)) / (lam * n)


# --------------------------------------------------------------------- #
# CoCoA+ for sparse logistic regression (local SDCA)
# --------------------------------------------------------------------- #


def _sdca_local_pass(w, alpha_b, bucket: ClientBucket, lam, n, sigma,
                     use_kernel, key):
    """One permutation pass of SDCA on each client's local dual subproblem.

    For logistic loss with y∈{−1,1} we parametrize β_i = y_i α_i ∈ (0,1);
    the scalar subproblem for coordinate i (from eq. (15)) is

        min_{β∈(0,1)}  m_i (β − β_old) + c_i (β − β_old)² + H(β),
        m_i = y_i x_iᵀ(w + (σ/λn) r),  c_i = σ||x_i||²/(2λn),
        H(β) = β log β + (1−β) log(1−β).

    r = X_k u tracks each client's own updates within the round (the cross
    terms of the local block).  The scan runs at the *bucket* level: at step
    t every client processes the t-th coordinate of its own permutation
    (clients are independent, so lockstep order is exactly the per-client
    sequential order), which turns the clipped-Newton β-solve into ONE
    vector call per step over a batch of clients — the fused Pallas kernel
    when ``use_kernel``, the identical jnp recursion elsewhere.
    """
    keys = jax.random.split(key, bucket.num_clients)
    return _sdca_local_pass_keyed(w, alpha_b, bucket, lam, n, sigma,
                                  use_kernel, keys)


def _sdca_local_pass_keyed(w, alpha_b, bucket: ClientBucket, lam, n, sigma,
                           use_kernel, keys):
    """:func:`_sdca_local_pass` over explicit per-client keys — the engine's
    streamed (``client_chunk``) path hands in chunk-sized bucket/state
    slices with the matching slice of the bucket's key split.  Clients are
    independent, so the lockstep pass runs batch by batch
    (:func:`~repro.core.problem.map_clients`)."""
    return map_clients(
        lambda a, idx, val, y, n_k, ks: _sdca_lockstep(
            w, a, ClientBucket(idx, val, y, n_k), lam, n, sigma, use_kernel,
            ks),
        (alpha_b, bucket.idx, bucket.val, bucket.y, bucket.n_k, keys),
        w.shape[0])


def _sdca_lockstep(w, alpha_b, bucket: ClientBucket, lam, n, sigma,
                   use_kernel, keys):
    """The SDCA pass of a batch of clients in lockstep (see
    :func:`_sdca_local_pass`); returns the dual steps u (Kb, m_pad) and the
    primal sums r = X_k u (Kb, d)."""
    Kb = bucket.num_clients
    m_pad = bucket.m_pad
    d = w.shape[0]
    perms = jax.vmap(lambda ck: jax.random.permutation(ck, m_pad))(keys)

    def coeffs_one(idx, val, y, alpha_k, r, i):
        xi, vi, yi = idx[i], val[i], y[i]
        beta_old = jnp.clip(yi * alpha_k[i], 1e-6, 1.0 - 1e-6)
        xn2 = (vi * vi).sum()
        mcoef = yi * ((vi * w[xi]).sum() + (sigma / (lam * n)) * (vi * r[xi]).sum())
        ccoef = sigma * xn2 / (2.0 * lam * n)
        return beta_old, mcoef, ccoef

    def apply_one(idx, val, y, n_k, u, r, i, beta_old, beta):
        xi, vi, yi = idx[i], val[i], y[i]
        valid = (i < n_k).astype(jnp.float32)
        du = valid * yi * (beta - beta_old)
        return u.at[i].add(du), r.at[xi].add(du * vi)

    def newton_batch(beta0, mcoef, ccoef):          # all (Kb,)
        if use_kernel:
            from repro.kernels import ops
            return ops.cocoa_sdca_update(beta0, mcoef, ccoef)
        from repro.kernels import ref
        return ref.cocoa_sdca_update_ref(beta0, mcoef, ccoef)

    def step(carry, t):
        u, r = carry                               # (Kb, m_pad), (Kb, d)
        i = perms[:, t]                            # (Kb,)
        beta_old, mcoef, ccoef = jax.vmap(coeffs_one)(
            bucket.idx, bucket.val, bucket.y, alpha_b, r, i)
        beta = newton_batch(beta_old, mcoef, ccoef)
        u, r = jax.vmap(apply_one)(bucket.idx, bucket.val, bucket.y,
                                   bucket.n_k, u, r, i, beta_old, beta)
        return (u, r), None

    u0 = jnp.zeros((Kb, m_pad))
    r0 = jnp.zeros((Kb, d))
    (u, r), _ = jax.lax.scan(step, (u0, r0), jnp.arange(m_pad))
    return u, r


@dataclasses.dataclass(frozen=True)
class CoCoAConfig:
    """CoCoA+ knobs (γ is fixed at 1, the "adding" variant)."""

    sigma: Optional[float] = None  # σ': None -> the safe γK
    participation: float = 1.0     # i.i.d. per-round client participation
    aggregator: str = "dense"      # engine aggregator: "dense" | "pallas"
    # None -> auto: fused Pallas cocoa_sdca kernel on TPU, jnp elsewhere.
    use_kernel: Optional[bool] = None
    # None -> materialize each bucket's (Kb, d) delta stack; an int streams
    # the client axis in chunks of this size (see EngineConfig.client_chunk)
    client_chunk: Optional[int] = None
    # under partial participation, compute only the sampled cohort (padded
    # to this per-bucket capacity; see EngineConfig.cohort / cohort_capacity)
    cohort: Optional[int] = None
    # run on a build_virtual_problem layout: rows regenerate on demand
    # inside the round (see EngineConfig.virtual_data; auto-detected).  The
    # dual blocks α_k stay materialized — they are the algorithm's own
    # state, not the dataset's.
    virtual_data: bool = False
    # replace the Bernoulli draw with a repro.fleet participation model
    # (trace-driven availability/stragglers); `participation` then serves
    # as the model's upper-bound rate for cohort capacity sizing
    participation_model: Optional[Any] = None
    # corrupt returned deltas through a repro.fleet.faults fault model
    # (corruption hits the wire — the primal contribution — never the
    # dual blocks, which stay whatever the honest pass computed)
    fault_model: Optional[Any] = None
    # robust server aggregation.  Dual methods aggregate with
    # weighting="sum", so only "clip" composes (order-statistic guards
    # would break the w = (1/λn)Xα invariant and are a config error).
    aggregator_guard: Optional[str] = None
    guard_clip_norm: Optional[float] = None
    guard_trim: float = 0.1


class CoCoAPlus(FederatedSolver):
    """CoCoA+ with γ=1 and safe σ′ = γK by default, on the engine.

    Purely functional: the dual blocks α_k (one (Kb, m_pad) array per
    bucket) ride in ``state.aux`` and travel through
    :meth:`RoundEngine.round_with_state`; the per-client primal
    contributions X_k u_k / (λn) are the deltas, summed by the engine
    (``weighting="sum"``) into w^{t+1} = w^t + (γ/λn) Σ_k X_k u_k.  Under
    partial participation the engine freezes the dual blocks of the
    clients its Bernoulli draw left out.

    ``init()`` starts at α = 0 ⇒ w = 0; a nonzero ``w0`` would break the
    dual-primal invariant w = (1/λn) X α and is rejected."""

    name = "cocoa"

    def __init__(self, problem: FederatedLogReg, sigma: Optional[float] = None,
                 cfg: CoCoAConfig = CoCoAConfig()):
        if sigma is not None:
            cfg = dataclasses.replace(cfg, sigma=sigma)
        self.problem = problem
        self.cfg = cfg
        self.sigma = float(cfg.sigma if cfg.sigma is not None
                           else problem.num_clients)
        use_kernel = cfg.use_kernel
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        n = problem.flat.n
        lam = problem.flat.lam
        self._scale = 1.0 / (lam * n)
        virtual = cfg.virtual_data or problem.virtual is not None
        self._pass = jax.jit(lambda w, a, b, key: _sdca_local_pass(
            w, a, b, lam, n, self.sigma, use_kernel, key))
        self.engine = RoundEngine(
            problem,
            EngineConfig(weighting="sum", participation=cfg.participation,
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

        def cocoa_pass(w, bi, bucket, alpha_b, kb):
            u, r = self._pass(w, alpha_b, bucket, kb)
            return r * self._scale, alpha_b + u

        def cocoa_chunk_pass(w, bi, chunk_bucket, alpha_c, keys):
            u, r = _sdca_local_pass_keyed(w, alpha_c, chunk_bucket, lam, n,
                                          self.sigma, use_kernel, keys)
            return r * self._scale, alpha_c + u

        self._round_fast = self.engine.compile_with_state(
            cocoa_pass, chunk_pass=cocoa_chunk_pass)
        self._round_ref = self.engine.reference_with_state(
            cocoa_pass, chunk_pass=cocoa_chunk_pass)

    def init(self, w0: Optional[jax.Array] = None) -> SolverState:
        if w0 is not None and bool(jnp.any(w0 != 0)):
            raise ValueError("CoCoA+ starts at alpha=0 => w=0; a custom w0 "
                             "would break w = (1/lambda n) X alpha")
        return SolverState(
            w=jnp.zeros((self.problem.d,)),
            aux=tuple(jnp.zeros((b.num_clients, b.m_pad))
                      for b in self.problem.buckets),
            round=jnp.asarray(0, jnp.int32))

    def round(self, state: SolverState, key: jax.Array) -> SolverState:
        w, alphas = self._round_fast(state.w, state.aux, key,
                                     round_index=state.round)
        return SolverState(w=w, aux=alphas, round=state.round + 1)

    def dual_objective(self, state: SolverState) -> jax.Array:
        """D(α) = (1/n) Σ_i H(β_i) − (λ/2)||w||², with β_i = y_i α_i and H
        the binary entropy (−ℓ*(−α_i) for the logistic loss ℓ).

        This is what CoCoA+'s rounds ascend — monotonically for the safe
        σ′ = γK — and D(α) ≤ f(w*) ≤ f(w(α)).  The primal f(w(α)) carries
        no such guarantee: from α = 0 it can rise above f(0) = log 2 for
        rounds before it falls."""
        flat = self.problem.flat
        ent = jnp.zeros(())
        for b, a in zip(self.problem.buckets, state.aux):
            if self.problem.virtual is not None:
                b = self.problem.virtual.realize(b)
            beta = jnp.clip(b.y * a, 0.0, 1.0)
            h = -(xlogy(beta, beta) + xlogy(1.0 - beta, 1.0 - beta))
            valid = jnp.arange(b.m_pad)[None, :] < b.n_k[:, None]
            ent = ent + jnp.where(valid, h, 0.0).sum()
        return ent / flat.n - 0.5 * flat.lam * jnp.dot(state.w, state.w)

    @property
    def hyperparams(self):
        hp = dataclasses.asdict(self.cfg)
        hp["sigma"] = self.sigma          # the resolved σ′, not the None default
        return hp


# --------------------------------------------------------------------- #
# Appendix A, ridge regression, engine-ported (equal n_k, dense buckets)
# --------------------------------------------------------------------- #


def _check_equal_sizes(problem: FederatedLogReg):
    for b in problem.buckets:
        if int(b.n_k.min()) != int(b.n_k.max()):
            raise ValueError("Appendix-A methods assume equal n_k")
    if len(problem.buckets) != 1:
        raise ValueError("Appendix-A methods assume equal n_k (one bucket)")


def _stack_alphas0(problem: FederatedLogReg,
                   alphas0: Optional[Sequence[jax.Array]]) -> jax.Array:
    """(K, m) initial dual blocks from a per-client list (zeros default)."""
    b = problem.buckets[0]
    if alphas0 is None:
        return jnp.zeros((b.num_clients, b.m_pad), b.val.dtype)
    return jnp.stack([jnp.asarray(a) for a in alphas0])


class PrimalMethod(FederatedSolver):
    """Algorithm 5 (Primal Method) with exact local solves, on the engine.

    Per-client state g_k (steps 4/9) rides through ``round_with_state``:
    the pass returns each exact subproblem solution w_k as the bucket state,
    the engine's uniform weighting forms w^{t+1} = (1/K) Σ w_k, and step 9
    (g_k ← g_k + λη(w_k − w^{t+1})) closes the round with the aggregate.

    ``problem`` must be a :func:`~repro.core.problem.build_dense_problem`
    layout with equal n_k.  ``init()`` runs steps 3–5 (w⁰ and g⁰ follow
    from ``alphas0``), so a custom ``w0`` is rejected."""

    name = "primal"

    def __init__(self, problem: FederatedLogReg, *,
                 sigma: Optional[float] = None, alphas0=None):
        _check_equal_sizes(problem)
        self.problem = problem
        K = problem.num_clients
        self.lam = float(problem.flat.lam)
        self.sigma = float(K if sigma is None else sigma)
        self.eta = K / self.sigma
        self.mu = self.lam * (self.eta - 1.0)
        self._alpha0 = _stack_alphas0(problem, alphas0)
        self.engine = RoundEngine(problem, EngineConfig(weighting="uniform"))
        # donate=False: step 9's epilogue re-reads state.aux *after* the
        # compiled dispatch, so the state buffers must survive the call.
        self._round_fast = self.engine.compile_with_state(self._primal_pass,
                                                          donate=False)

    @property
    def hyperparams(self):
        return {"sigma": self.sigma, "eta": self.eta, "mu": self.mu}

    def init(self, w0: Optional[jax.Array] = None) -> SolverState:
        if w0 is not None:
            raise ValueError("PrimalMethod's w0 is determined by alphas0 "
                             "(steps 3-5 of Algorithm 5)")
        b = self.problem.buckets[0]
        n = self.problem.flat.n
        K = self.problem.num_clients
        # steps 3-5: w^0 = (1/λn) Σ X_k α_k;  g_k^0 = η((K/n) X_k α_k − λw^0)
        xa = jnp.einsum("kmd,km->kd", b.val, self._alpha0)       # X_k α_k
        w = xa.sum(axis=0) / (self.lam * n)
        gs = self.eta * ((K / n) * xa - self.lam * w)
        return SolverState(w=w, aux=(gs,), round=jnp.asarray(0, jnp.int32))

    def _primal_pass(self, w, bi, bucket, gs_b, kb):
        lam, eta, mu = self.lam, self.eta, self.mu
        K, n = self.problem.num_clients, self.problem.flat.n

        def one_client(val, y, g_k):
            d = w.shape[0]
            X = val.T
            # argmin F_k(w') − (∇F_k(w^t) − (η∇F_k(w^t) + g_k))ᵀw'
            #        + µ/2||w'−w^t||²,  F_k as in eq. 12 ((K/n)-normalized)
            Fk = (K / n) * (X @ (X.T @ w - y)) + lam * w
            b_k = (1.0 - eta) * Fk - g_k
            H = (K / n) * (X @ X.T) + (lam + mu) * jnp.eye(d, dtype=val.dtype)
            rhs = (K / n) * (X @ y) + b_k + mu * w
            wk = jnp.linalg.solve(H, rhs)
            return wk - w, wk

        return jax.vmap(one_client)(bucket.val, bucket.y, gs_b)

    def round(self, state: SolverState, key: jax.Array) -> SolverState:
        # step 9's g update needs the aggregated w^{t+1}, so it closes the
        # round eagerly after the compiled engine dispatch.
        w_next, wks = self._round_fast(state.w, state.aux, key)
        gs = tuple(g + self.lam * self.eta * (wk - w_next)
                   for g, wk in zip(state.aux, wks))
        return SolverState(w=w_next, aux=gs, round=state.round + 1)


class DualMethod(FederatedSolver):
    """Algorithm 6 (Dual Method) with exact block solves, on the engine.

    Block subproblem (19): h_k = argmin (σ/2λn)||X_k h||² + ½||h||²
                                        − (y_k − X_kᵀw^t − α_k)ᵀ h
    State is the dual block α_k in ``state.aux``; the pass returns
    X_k h_k/(λn) as the delta, so the engine's plain sum tracks
    w^{t+1} = (1/λn) X α^{t+1} exactly.  ``init()`` derives w⁰ from
    ``alphas0``, so a custom ``w0`` is rejected."""

    name = "dual"

    def __init__(self, problem: FederatedLogReg, *,
                 sigma: Optional[float] = None, alphas0=None):
        _check_equal_sizes(problem)
        self.problem = problem
        self.lam = float(problem.flat.lam)
        self.sigma = float(problem.num_clients if sigma is None else sigma)
        self._alpha0 = _stack_alphas0(problem, alphas0)
        self.engine = RoundEngine(problem, EngineConfig(weighting="sum"))
        self._round_fast = self.engine.compile_with_state(self._dual_pass)

    @property
    def hyperparams(self):
        return {"sigma": self.sigma}

    def init(self, w0: Optional[jax.Array] = None) -> SolverState:
        if w0 is not None:
            raise ValueError("DualMethod's w0 is determined by alphas0 "
                             "(w = (1/lambda n) X alpha)")
        b = self.problem.buckets[0]
        n = self.problem.flat.n
        w = jnp.einsum("kmd,km->d", b.val, self._alpha0) / (self.lam * n)
        # hand out a copy: round 1's compiled dispatch donates the state
        # buffers off-CPU, and the cached template must survive re-inits
        return SolverState(w=w, aux=(jnp.array(self._alpha0),),
                           round=jnp.asarray(0, jnp.int32))

    def _dual_pass(self, w, bi, bucket, alpha_b, kb):
        lam, sigma = self.lam, self.sigma
        n = self.problem.flat.n

        def one_client(val, y, a):
            X = val.T
            m = a.shape[0]
            c = y - X.T @ w - a
            M = (sigma / (lam * n)) * (X.T @ X) + jnp.eye(m, dtype=val.dtype)
            h = jnp.linalg.solve(M, c)
            return (X @ h) / (lam * n), a + h

        return jax.vmap(one_client)(bucket.val, bucket.y, alpha_b)

    def round(self, state: SolverState, key: jax.Array) -> SolverState:
        w, alphas = self._round_fast(state.w, state.aux, key)
        return SolverState(w=w, aux=alphas, round=state.round + 1)


def _cocoa_defaults():
    from repro.configs import get_cocoa_config
    return {"sigma": get_cocoa_config().sigma}


@register("cocoa", defaults=_cocoa_defaults,
          description="CoCoA+ (arXiv:1502.03508, γ=1, local SDCA)")
def _make_cocoa(problem: FederatedLogReg, sigma=None, **kw) -> CoCoAPlus:
    return CoCoAPlus(problem, sigma=sigma, cfg=CoCoAConfig(**kw))


@register("primal", layout="dense",
          description="Appendix-A Algorithm 5 (Primal Method, exact solves)")
def _make_primal(problem: FederatedLogReg, **kw) -> PrimalMethod:
    return PrimalMethod(problem, **kw)


@register("dual", layout="dense",
          description="Appendix-A Algorithm 6 (Dual Method, exact solves)")
def _make_dual(problem: FederatedLogReg, **kw) -> DualMethod:
    return DualMethod(problem, **kw)
