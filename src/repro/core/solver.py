"""The `FederatedSolver` protocol — one front door for every round-based
algorithm in this repo.

The paper's central object is a *round of communication* (§1, §3); before
this module each algorithm exposed a different one (functional
``round(w, key)`` here, a mutating ``round(key)`` there, bespoke ``run``
loops everywhere).  Now every algorithm is a :class:`FederatedSolver`:

  * ``init(w0) -> SolverState`` — build the solver's full state: the
    iterate ``w``, per-client auxiliary state ``aux`` (CoCoA+'s dual blocks
    α_k, the Primal Method's perturbation vectors g_k — an empty tuple for
    stateless algorithms), and the ``round`` counter.
  * ``round(state, key) -> SolverState`` — one round of communication,
    *purely functional*: no hidden ``self.w``.  ``key`` is the round's PRNG
    key; deterministic solvers simply ignore it.
  * ``name`` / ``hyperparams`` — the string the solver registers under
    (:mod:`repro.core.registry`) and the knobs it was built with.
  * ``fit(rounds, ...)`` — convenience wrapper over
    :class:`repro.core.trainer.Trainer`, which owns the key schedule,
    eval/history, checkpointing, and the scan fast path.

:class:`SolverState` is a registered pytree, so whole states jit, scan,
and checkpoint like any other JAX value.  The contract every solver keeps:

  * ``aux`` is a (possibly empty) tuple with one entry per problem bucket,
    each a pytree of arrays with leading client axis ``(Kb, ...)`` — the
    exact shape :meth:`RoundEngine.round_with_state` threads.
  * ``round`` must not depend on Python-level mutable state, so
    ``lax.scan`` over rounds (the Trainer's fast path) and a hand-rolled
    Python loop produce the same trajectory.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.problem import FederatedLogReg


@dataclasses.dataclass(frozen=True)
class SolverState:
    """Everything a solver carries between rounds, as one pytree.

    w     : (d,) the server iterate.
    aux   : per-client auxiliary state — a tuple with one pytree per
            problem bucket (leading axis = that bucket's client axis), or
            the empty tuple for stateless algorithms.
    round : int32 scalar round counter; the Trainer derives round r's key
            as ``fold_in(PRNGKey(seed), r)``, so a restored state resumes
            the exact key schedule.
    """

    w: jax.Array
    aux: Any = ()
    round: Any = 0

    def replace(self, **kw) -> "SolverState":
        return dataclasses.replace(self, **kw)


jax.tree_util.register_dataclass(
    SolverState, data_fields=["w", "aux", "round"], meta_fields=[])


class FederatedSolver:
    """Base class / protocol for round-based federated algorithms.

    Subclasses set ``name``, implement :meth:`round`, and override
    :meth:`_init_aux` if their clients carry state across rounds.
    Constructors take the problem first: ``Solver(problem, ...)`` — the
    registry's ``make_solver(name, problem, **overrides)`` relies on it.
    """

    name: str = "solver"
    problem: FederatedLogReg

    # -- state ------------------------------------------------------------ #

    def init(self, w0: Optional[jax.Array] = None) -> SolverState:
        """Fresh solver state at iterate ``w0`` (zeros by default).

        Dual methods whose iterate is a function of the dual state
        (Appendix-A Primal/Dual) override this and reject a custom ``w0``.

        The state owns a copy of ``w0``: compiled rounds donate the
        iterate buffer on accelerators, so a state built on the caller's
        array would delete it for every later user (a second curve from
        the same start point, a reference round from the same state).
        """
        w0 = jnp.zeros((self.problem.d,)) if w0 is None else jnp.array(w0)
        return SolverState(w=w0, aux=self._init_aux(w0),
                           round=jnp.asarray(0, jnp.int32))

    def _init_aux(self, w0: jax.Array) -> Any:
        return ()

    # -- one round of communication --------------------------------------- #

    def round(self, state: SolverState, key: jax.Array) -> SolverState:
        raise NotImplementedError

    def lower_round(self, state: SolverState, key: jax.Array):
        """The ``jax.stages.Lowered`` program that :meth:`round` dispatches
        from ``state`` — traced, not compiled or run.  Its ``as_text()``
        shows what the compiler is handed (e.g. which Pallas kernels a
        round calls); ``compile()`` compiles it — with JAX's persistent
        cache on, that is the compile the first :meth:`round` loads."""
        args = (state.w, state.aux) if state.aux else (state.w,)
        return self._round_fast.lower(*args, key, round_index=state.round)

    # -- introspection ----------------------------------------------------- #

    @property
    def hyperparams(self) -> Dict[str, Any]:
        """The knobs this solver was constructed with (JSON-friendly)."""
        cfg = getattr(self, "cfg", None)
        if dataclasses.is_dataclass(cfg):
            return dataclasses.asdict(cfg)
        return {}

    # -- convenience ------------------------------------------------------- #

    def fit(self, rounds: int, *, seed: int = 0, w0=None, state=None,
            eval_fn=None, **trainer_kw):
        """Run ``rounds`` rounds through the shared Trainer driver."""
        from repro.core.trainer import Trainer
        return Trainer(self, rounds=rounds, seed=seed, eval_fn=eval_fn,
                       **trainer_kw).fit(w0=w0, state=state)

    def __repr__(self) -> str:
        hp = ", ".join(f"{k}={v!r}" for k, v in self.hyperparams.items())
        return f"{type(self).__name__}({self.name}: {hp})"
