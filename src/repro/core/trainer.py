"""`Trainer` — the one round-loop driver every solver shares.

Before this module each benchmark/example hand-rolled its own loop, seed
schedule, and stepsize sweep per algorithm (~30 lines each in
``benchmarks/fig2_convergence.py``).  The Trainer owns all of it:

  * **Key schedule** — round r uses ``fold_in(PRNGKey(seed), r)`` with r the
    *absolute* round index from ``state.round``, so a restored checkpoint
    resumes the exact same key sequence it would have seen uninterrupted.
  * **Eval / history** — ``eval_fn(w) -> dict`` of scalars, recorded as
    Python floats every ``eval_every`` rounds (default every round; the
    final round is always evaluated, so ``history[-1]`` keeps meaning
    "final objective" for :func:`sweep` at any cadence);
    ``callback(state, r)`` for side effects.
  * **Scan fast path** — with ``scan=True`` the whole loop runs as one
    ``jit(lax.scan)`` over rounds.  Valid whenever the solver state is a
    pure pytree and ``round`` is traceable (every solver in this repo) and
    ``eval_fn`` is jax-traceable; ``callback`` and mid-run checkpointing
    are Python-side and therefore excluded.  Numerics: XLA may fuse the
    round body differently than the eager per-round path, so scan
    trajectories agree to float tolerance, not bit-for-bit — the pinning
    tests run the loop path.
  * **Checkpointing** — ``checkpoint_dir`` + ``checkpoint_every`` save the
    state pytree through :mod:`repro.checkpoint`; ``Trainer.restore``
    rebuilds a :class:`~repro.core.solver.SolverState` and ``fit(state=...)``
    resumes from it.

:func:`sweep` is the paper's retrospective stepsize-sweep protocol (run
every candidate for the full round budget, keep the best final objective),
previously a private helper inside the fig2 benchmark.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.solver import FederatedSolver, SolverState
from repro.utils import obs

EvalFn = Callable[[jax.Array], Dict[str, Any]]


class NonFiniteIterateError(RuntimeError):
    """The iterate went NaN/Inf mid-run.  Carries which solver and which
    round, so a campaign guard-rail can quarantine exactly that round
    instead of letting the poison silently propagate to the final
    checkpoint."""

    def __init__(self, solver_name: str, round_index: int):
        super().__init__(
            f"non-finite iterate after round {round_index} of solver "
            f"'{solver_name}' — a diverging stepsize or an unguarded "
            "fault-injected delta (see EngineConfig.aggregator_guard)")
        self.solver_name = solver_name
        self.round_index = int(round_index)


@dataclasses.dataclass
class FitResult:
    """What a training run produced: final state + per-round eval history
    (plus the solver that produced it, for hyperparam introspection)."""

    state: SolverState
    history: List[Dict[str, float]]
    solver: Optional[FederatedSolver] = None

    @property
    def w(self) -> jax.Array:
        return self.state.w


def _tuplify(node):
    """Rebuild tuples from the lists `repro.checkpoint.restore` returns."""
    if isinstance(node, (list, tuple)):
        return tuple(_tuplify(x) for x in node)
    if isinstance(node, dict):
        return {k: _tuplify(v) for k, v in node.items()}
    return node


class Trainer:
    """Drives ``solver.round`` for a fixed number of rounds.

    The per-round key is ``fold_in(PRNGKey(seed), r)`` — the single schedule
    every curve in the fig2 benchmark now derives from its ``--seed``.
    """

    def __init__(self, solver: FederatedSolver, *, rounds: int, seed: int = 0,
                 eval_fn: Optional[EvalFn] = None,
                 callback: Optional[Callable[[SolverState, int], None]] = None,
                 scan: bool = False,
                 eval_every: int = 1,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0,
                 fail_fast: bool = True):
        if scan and callback is not None:
            raise ValueError("scan=True runs the loop inside jit; Python "
                             "callbacks need the eager path")
        if scan and checkpoint_every:
            raise ValueError("scan=True runs the loop inside jit; periodic "
                             "checkpointing needs the eager path (the final "
                             "state is still saved to checkpoint_dir)")
        if checkpoint_every and not checkpoint_dir:
            raise ValueError("checkpoint_every requires a checkpoint_dir")
        if int(eval_every) < 1:
            raise ValueError("eval_every must be >= 1")
        self.solver = solver
        self.rounds = int(rounds)
        self.seed = int(seed)
        self.eval_fn = eval_fn
        self.callback = callback
        self.scan = scan
        self.eval_every = int(eval_every)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = int(checkpoint_every)
        # raise NonFiniteIterateError the round the iterate goes NaN/Inf
        # instead of silently training on garbage.  sweep() turns this off:
        # its divergent stepsize candidates are expected and discarded.
        # The scan path checks the final iterate only (the loop is one jit).
        self.fail_fast = bool(fail_fast)

    def _check_finite(self, state: SolverState, r: int) -> None:
        if self.fail_fast and not bool(jnp.isfinite(state.w).all()):
            raise NonFiniteIterateError(self.solver.name, r)

    def _is_eval_round(self, r: int) -> bool:
        """Rounds whose metrics land in history: every ``eval_every``-th
        round plus, unconditionally, the final one."""
        return (r + 1) % self.eval_every == 0 or r == self.rounds - 1

    # -- checkpointing ----------------------------------------------------- #

    def save(self, state: SolverState, path: Optional[str] = None) -> None:
        from repro import checkpoint
        path = path or self.checkpoint_dir
        checkpoint.save(path, {"w": state.w, "aux": state.aux,
                               "round": state.round},
                        step=int(state.round),
                        metadata={"solver": self.solver.name,
                                  "seed": self.seed})

    @staticmethod
    def restore(path: str) -> SolverState:
        from repro import checkpoint
        tree, info = checkpoint.restore(path)
        return SolverState(w=tree["w"], aux=_tuplify(tree.get("aux", ())),
                           round=jnp.asarray(tree.get("round", info["step"]),
                                             jnp.int32))

    # -- the round loop ---------------------------------------------------- #

    def fit(self, w0: Optional[jax.Array] = None,
            state: Optional[SolverState] = None) -> FitResult:
        """Run rounds ``state.round .. rounds-1``; fresh ``init(w0)`` state
        unless an explicit (e.g. restored) ``state`` is given."""
        if state is None:
            state = self.solver.init(w0)
        elif w0 is not None:
            raise ValueError("pass w0 or state, not both")
        start = int(state.round)
        if start >= self.rounds:
            # the "saved checkpoint never lags the returned result"
            # invariant must hold for the degenerate run too: a restored
            # state handed to a past-budget fit would otherwise return
            # without ever touching the checkpoint directory
            if self.checkpoint_dir:
                self.save(state)
            return FitResult(state=state, history=[], solver=self.solver)
        if self.scan:
            return self._fit_scan(state, start)

        base = jax.random.PRNGKey(self.seed)
        history: List[Dict[str, float]] = []
        saved_at = -1
        for r in range(start, self.rounds):
            with obs.round_span(r):
                state = self.solver.round(state, jax.random.fold_in(base, r))
                with obs.span("fl.check_finite"):
                    self._check_finite(state, r)
                if self.eval_fn is not None and self._is_eval_round(r):
                    with obs.span("fl.eval"):
                        history.append({k: float(v) for k, v in
                                        self.eval_fn(state.w).items()})
                if self.callback is not None:
                    with obs.span("fl.callback"):
                        self.callback(state, r)
                if (self.checkpoint_every
                        and (r + 1) % self.checkpoint_every == 0):
                    with obs.span("fl.checkpoint"):
                        self.save(state)
                    saved_at = r + 1
        # the saved checkpoint must never lag the returned result
        if self.checkpoint_dir and saved_at != self.rounds:
            with obs.span("fl.checkpoint"):
                self.save(state)
        return FitResult(state=state, history=history, solver=self.solver)

    def _fit_scan(self, state: SolverState, start: int) -> FitResult:
        base = jax.random.PRNGKey(self.seed)
        rs = jnp.arange(start, self.rounds)
        keys = jax.vmap(lambda r: jax.random.fold_in(base, r))(rs)
        sparse_eval = self.eval_fn is not None and self.eval_every != 1
        if sparse_eval:
            # eval_fn runs under lax.cond on eval rounds only; off rounds
            # emit same-shaped placeholders that are discarded below
            shapes = jax.eval_shape(self.eval_fn, state.w)

            def maybe_eval(w, r):
                pred = ((r + 1) % self.eval_every == 0) | (r == self.rounds - 1)
                return jax.lax.cond(
                    pred, self.eval_fn,
                    lambda _: jax.tree.map(
                        lambda s: jnp.zeros(s.shape, s.dtype), shapes), w)

        def body(s, rk):
            r, key = rk
            s = self.solver.round(s, key)
            if sparse_eval:
                metrics = maybe_eval(s.w, r)
            else:
                metrics = self.eval_fn(s.w) if self.eval_fn is not None else {}
            return s, metrics

        with obs.span("fl.scan"):
            final, stacked = jax.jit(
                lambda s, xs: jax.lax.scan(body, s, xs))(state, (rs, keys))
        self._check_finite(final, self.rounds - 1)
        if self.eval_fn is None:
            history: List[Dict[str, float]] = []
        else:
            recorded = [i for i, r in enumerate(range(start, self.rounds))
                        if self._is_eval_round(r)]
            history = [{k: float(v[i]) for k, v in stacked.items()}
                       for i in recorded]
        if self.checkpoint_dir:
            self.save(final)
        return FitResult(state=final, history=history, solver=self.solver)


def sweep(build_solver: Callable[[Any], FederatedSolver],
          candidates: Sequence[Any], *, rounds: int, seed: int = 0,
          eval_fn: EvalFn, objective: str = "f",
          **trainer_kw) -> Tuple[Optional[FitResult], Optional[Any]]:
    """Retrospective hyperparameter sweep (the paper's protocol).

    Runs ``build_solver(v)`` for the full round budget for every candidate
    ``v`` and keeps the run whose *final* ``history[-1][objective]`` is
    lowest (non-finite runs are discarded).  Returns
    ``(best_result, best_value)`` — ``(None, None)`` if every run diverged.
    """
    best_res, best_v, best_f = None, None, np.inf
    for v in candidates:
        # fail_fast off: a divergent candidate is part of the protocol —
        # it just loses the sweep — unless the caller opts back in
        trainer_kw.setdefault("fail_fast", False)
        res = Trainer(build_solver(v), rounds=rounds, seed=seed,
                      eval_fn=eval_fn, **trainer_kw).fit()
        if not res.history:        # degenerate budget (rounds <= start)
            continue
        f = res.history[-1][objective]
        if np.isfinite(f) and f < best_f:
            best_res, best_v, best_f = res, v, f
    return best_res, best_v
