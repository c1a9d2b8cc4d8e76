"""FederatedSolver protocol, registry, and Trainer driver.

Pins ``Trainer.fit`` against the pre-redesign hand-rolled fig2 round loops
(kept verbatim in tests/_oracles.py) for FSVRG, FedAvg, DANE, and CoCoA+ —
the loop structure, key schedule, state threading, and history capture
must all survive the API redesign.  The oracles drive the *eager*
reference round while ``Trainer`` drives each solver's compiled closure,
so the iterate/history pins are a tight float tolerance (the whole-round
jit may re-associate the cross-bucket aggregation sum — see
test_fused_round.py); per-client dual blocks stay bit-for-bit.  Also
covers the registry round-trip (every registered name constructs, runs 2
rounds, and yields a valid SolverState pytree), the jit+lax.scan fast
path, the checkpoint save/resume cycle, and the retrospective sweep
protocol.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _oracles
from repro.core import (NonFiniteIterateError, SolverState, Trainer,
                        available, build_dense_problem, get_spec, make_solver,
                        sweep)


def _eval(prob):
    """jax-traceable eval (works in both the loop and the scan path); the
    Trainer converts the recorded values to Python floats."""
    def eval_fn(w):
        return {"f": prob.flat.loss(w)}
    return eval_fn


def _eval_floats(prob):
    """What the pre-redesign fig2 loops recorded: eager Python floats."""
    ev = _eval(prob)
    return lambda w: {k: float(v) for k, v in ev(w).items()}


# --------------------------------------------------------------------- #
# Trainer vs the pre-redesign fig2 loops
# --------------------------------------------------------------------- #


def _assert_history_close(hist, hist_ref):
    assert len(hist) == len(hist_ref)
    for rec, rec_ref in zip(hist, hist_ref):
        assert rec.keys() == rec_ref.keys()
        for k in rec:
            np.testing.assert_allclose(rec[k], rec_ref[k],
                                       rtol=1e-5, atol=1e-8)


def test_trainer_pins_fig2_fsvrg_loop(tiny_problem):
    prob = tiny_problem
    ev = _eval(prob)
    w_ref, hist_ref = _oracles.fig2_fsvrg_loop(prob, 1.0, 3, seed=1,
                                               eval_fn=_eval_floats(prob))
    res = Trainer(make_solver("fsvrg", prob, stepsize=1.0), rounds=3, seed=1,
                  eval_fn=ev).fit()
    np.testing.assert_allclose(np.asarray(res.w), np.asarray(w_ref),
                               rtol=1e-5, atol=1e-8)
    _assert_history_close(res.history, hist_ref)


def test_trainer_pins_fig2_fedavg_loop(tiny_problem):
    prob = tiny_problem
    ev = _eval(prob)
    w_ref, hist_ref = _oracles.fig2_fedavg_loop(prob, 0.5, 2, 3, seed=2,
                                                eval_fn=_eval_floats(prob))
    res = Trainer(make_solver("fedavg", prob, stepsize=0.5, local_epochs=2),
                  rounds=3, seed=2, eval_fn=ev).fit()
    # atol is one f32 ulp at the iterate's scale (max|w| = 1.23, ulp
    # 1.19e-7): the compiled round re-associates the cross-bucket sum, and
    # under jax 0.9 that leaves a 1.49e-8 (2^-26) residue on a coordinate
    # of magnitude 4e-5 — one ulp of the [0.125, 0.25) partial sums it
    # cancels from, far below what rtol covers at that magnitude
    np.testing.assert_allclose(np.asarray(res.w), np.asarray(w_ref),
                               rtol=1e-5, atol=1.2e-7)
    _assert_history_close(res.history, hist_ref)


def test_trainer_pins_fig2_dane_loop(tiny_problem):
    prob = tiny_problem
    ev = _eval(prob)
    kw = dict(eta=1.0, mu=3.0, local_steps=5, local_lr=0.3)
    w_ref, hist_ref = _oracles.fig2_dane_loop(prob, 3, seed=4,
                                              eval_fn=_eval_floats(prob), **kw)
    res = Trainer(make_solver("dane", prob, **kw), rounds=3, seed=4,
                  eval_fn=ev).fit()
    np.testing.assert_allclose(np.asarray(res.w), np.asarray(w_ref),
                               rtol=1e-5, atol=1e-8)
    _assert_history_close(res.history, hist_ref)


def test_trainer_pins_fig2_cocoa_loop(tiny_problem):
    """Iterates AND final dual blocks: the functional SolverState threading
    must reproduce the pre-redesign mutable-class trajectory (dual blocks
    exactly — per-client state never crosses the aggregation sum)."""
    prob = tiny_problem
    ev = _eval(prob)
    w_ref, alphas_ref, hist_ref = _oracles.fig2_cocoa_loop(
        prob, 3, seed=0, eval_fn=_eval_floats(prob))
    res = Trainer(make_solver("cocoa", prob), rounds=3, seed=0,
                  eval_fn=ev).fit()
    np.testing.assert_allclose(np.asarray(res.w), np.asarray(w_ref),
                               rtol=1e-5, atol=1e-8)
    _assert_history_close(res.history, hist_ref)
    assert len(res.state.aux) == len(alphas_ref)
    for a_eng, a_ref in zip(res.state.aux, alphas_ref):
        np.testing.assert_array_equal(np.asarray(a_eng), np.asarray(a_ref))


# --------------------------------------------------------------------- #
# registry round-trip
# --------------------------------------------------------------------- #


def _dense_ridge_problem(K=3, m=8, d=5, lam=0.1, seed=0):
    rng = np.random.default_rng(seed)
    Xs = [jnp.asarray(rng.standard_normal((d, m)), jnp.float32)
          for _ in range(K)]
    ys = [jnp.asarray(rng.standard_normal(m), jnp.float32) for _ in range(K)]
    return build_dense_problem(Xs, ys, lam)


def test_registry_round_trip(tiny_problem):
    """Every registered name constructs with its config defaults, runs 2
    rounds through the Trainer, and produces a valid, finite SolverState
    pytree with the round counter advanced."""
    names = available()
    assert len(names) >= 8, names
    dense = _dense_ridge_problem()
    for name in names:
        spec = get_spec(name)
        problem = tiny_problem if spec.layout == "sparse" else dense
        solver = make_solver(name, problem)
        assert solver.name == name
        assert isinstance(solver.hyperparams, dict)
        res = solver.fit(2, seed=0)
        state = res.state
        assert isinstance(state, SolverState)
        assert int(state.round) == 2, name
        assert state.w.shape == (problem.d,)
        # a valid pytree: flatten/unflatten round-trips, all leaves finite
        leaves, treedef = jax.tree_util.tree_flatten(state)
        assert all(np.isfinite(np.asarray(l)).all() for l in leaves), name
        state2 = jax.tree_util.tree_unflatten(treedef, leaves)
        np.testing.assert_array_equal(np.asarray(state2.w),
                                      np.asarray(state.w))


def test_registry_unknown_name_and_overrides(tiny_problem):
    with pytest.raises(KeyError):
        make_solver("bogus", tiny_problem)
    solver = make_solver("fedavg", tiny_problem, stepsize=0.7)
    assert solver.hyperparams["stepsize"] == 0.7
    # defaults still come from the config for keys not overridden
    from repro.configs import get_fedavg_config
    assert solver.hyperparams["local_epochs"] == get_fedavg_config().local_epochs


def test_cocoa_rejects_nonzero_w0(tiny_problem):
    solver = make_solver("cocoa", tiny_problem)
    with pytest.raises(ValueError):
        solver.init(jnp.ones(tiny_problem.d))


# --------------------------------------------------------------------- #
# scan fast path
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("name", ["fedavg", "cocoa"])
def test_scan_fast_path_matches_loop(tiny_problem, name):
    """jit + lax.scan over rounds == the eager per-round loop, for a
    stateless and a dual-state solver (float tolerance: XLA may fuse the
    scanned round body differently)."""
    prob = tiny_problem
    ev = _eval(prob)
    loop = Trainer(make_solver(name, prob), rounds=3, seed=0,
                   eval_fn=ev).fit()
    scan = Trainer(make_solver(name, prob), rounds=3, seed=0, eval_fn=ev,
                   scan=True).fit()
    np.testing.assert_allclose(np.asarray(scan.w), np.asarray(loop.w),
                               rtol=1e-6, atol=1e-7)
    assert int(scan.state.round) == int(loop.state.round) == 3
    assert len(scan.history) == len(loop.history)
    for a, b in zip(scan.history, loop.history):
        np.testing.assert_allclose(a["f"], b["f"], rtol=1e-6)


def test_scan_rejects_python_callback(tiny_problem):
    with pytest.raises(ValueError):
        Trainer(make_solver("fedavg", tiny_problem), rounds=2, scan=True,
                callback=lambda s, r: None)


# --------------------------------------------------------------------- #
# checkpoint save / restore / resume
# --------------------------------------------------------------------- #


def test_checkpoint_resume_is_bit_identical(tiny_problem, tmp_path):
    """fit 2 rounds + save, restore, fit to 4 == one uninterrupted 4-round
    run — the absolute-round key schedule makes resumption exact (dual
    state included)."""
    prob = tiny_problem
    ckpt = str(tmp_path / "cocoa")
    solver = make_solver("cocoa", prob)
    Trainer(solver, rounds=2, seed=0, checkpoint_dir=ckpt).fit()

    restored = Trainer.restore(ckpt)
    assert int(restored.round) == 2
    resumed = Trainer(solver, rounds=4, seed=0).fit(state=restored)
    straight = Trainer(make_solver("cocoa", prob), rounds=4, seed=0).fit()
    for a, b in zip(jax.tree_util.tree_leaves(resumed.state),
                    jax.tree_util.tree_leaves(straight.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_final_checkpoint_never_lags_result(tiny_problem, tmp_path):
    """checkpoint_every that does not divide rounds must still leave the
    *final* state on disk, not the last periodic save."""
    ckpt = str(tmp_path / "gd")
    res = Trainer(make_solver("gd", tiny_problem), rounds=3, seed=0,
                  checkpoint_dir=ckpt, checkpoint_every=2).fit()
    restored = Trainer.restore(ckpt)
    assert int(restored.round) == 3
    np.testing.assert_array_equal(np.asarray(restored.w), np.asarray(res.w))


def test_scan_rejects_periodic_checkpointing(tiny_problem, tmp_path):
    with pytest.raises(ValueError):
        Trainer(make_solver("gd", tiny_problem), rounds=4, scan=True,
                checkpoint_dir=str(tmp_path / "x"), checkpoint_every=2)


def test_fit_past_round_budget_is_noop(tiny_problem):
    solver = make_solver("gd", tiny_problem)
    res = Trainer(solver, rounds=2, seed=0).fit()
    again = Trainer(solver, rounds=2, seed=0).fit(state=res.state)
    assert again.history == []
    np.testing.assert_array_equal(np.asarray(again.w), np.asarray(res.w))


def test_fit_past_round_budget_still_checkpoints(tiny_problem, tmp_path):
    """The degenerate start >= rounds return must uphold the "saved
    checkpoint never lags the returned result" invariant: a restored state
    handed to a past-budget fit with checkpoint_dir set used to return
    without ever writing the directory."""
    solver = make_solver("gd", tiny_problem)
    res = Trainer(solver, rounds=2, seed=0).fit()
    ckpt = str(tmp_path / "late")
    again = Trainer(solver, rounds=2, seed=0,
                    checkpoint_dir=ckpt).fit(state=res.state)
    restored = Trainer.restore(ckpt)
    assert int(restored.round) == int(again.state.round) == 2
    np.testing.assert_array_equal(np.asarray(restored.w), np.asarray(again.w))


# --------------------------------------------------------------------- #
# retrospective sweep
# --------------------------------------------------------------------- #


def test_sweep_picks_best_final_objective(tiny_problem):
    prob = tiny_problem
    ev = _eval(prob)
    candidates = (0.3, 1.0)
    res, best = sweep(lambda h: make_solver("fsvrg", prob, stepsize=h),
                      candidates, rounds=2, seed=0, eval_fn=ev)
    finals = {
        h: Trainer(make_solver("fsvrg", prob, stepsize=h), rounds=2, seed=0,
                   eval_fn=ev).fit().history[-1]["f"]
        for h in candidates
    }
    assert best == min(finals, key=finals.get)
    assert res.history[-1]["f"] == finals[best]


# --------------------------------------------------------------------- #
# eval cadence (eval_every)
# --------------------------------------------------------------------- #


def test_eval_every_records_subset_of_dense_history(tiny_problem):
    """eval_every=k keeps exactly the rounds (r+1) % k == 0 plus the final
    round, with values identical to the every-round history's entries."""
    prob = tiny_problem
    ev = _eval(prob)
    dense = Trainer(make_solver("gd", prob), rounds=7, seed=0,
                    eval_fn=ev).fit()
    sparse = Trainer(make_solver("gd", prob), rounds=7, seed=0,
                     eval_fn=ev, eval_every=3).fit()
    # rounds 2, 5 (cadence) + 6 (final)
    assert len(sparse.history) == 3
    expect = [dense.history[2], dense.history[5], dense.history[6]]
    for rec, rec_ref in zip(sparse.history, expect):
        assert rec == rec_ref
    np.testing.assert_array_equal(np.asarray(sparse.w), np.asarray(dense.w))


def test_eval_every_final_round_always_recorded(tiny_problem):
    """A cadence that never divides the budget still records the final
    round — history[-1] keeps meaning 'final objective' (the sweep
    contract)."""
    prob = tiny_problem
    res = Trainer(make_solver("gd", prob), rounds=4, seed=0,
                  eval_fn=_eval(prob), eval_every=10).fit()
    assert len(res.history) == 1
    ref = Trainer(make_solver("gd", prob), rounds=4, seed=0,
                  eval_fn=_eval(prob)).fit()
    assert res.history[0] == ref.history[-1]


@pytest.mark.parametrize("eval_every", [2, 5])
def test_eval_every_scan_matches_loop(tiny_problem, eval_every):
    prob = tiny_problem
    ev = _eval(prob)
    loop = Trainer(make_solver("gd", prob), rounds=6, seed=0,
                   eval_fn=ev, eval_every=eval_every).fit()
    scan = Trainer(make_solver("gd", prob), rounds=6, seed=0,
                   eval_fn=ev, eval_every=eval_every, scan=True).fit()
    assert len(scan.history) == len(loop.history)
    for rec, rec_ref in zip(scan.history, loop.history):
        np.testing.assert_allclose(rec["f"], rec_ref["f"],
                                   rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(np.asarray(scan.w), np.asarray(loop.w),
                               rtol=1e-5, atol=1e-8)


def test_eval_every_validation(tiny_problem):
    with pytest.raises(ValueError):
        Trainer(make_solver("gd", tiny_problem), rounds=2, eval_every=0)


def test_eval_every_sweep_still_picks_best(tiny_problem):
    """The sweep keys off history[-1], which eval_every preserves."""
    prob = tiny_problem
    ev = _eval(prob)
    res_d, best_d = sweep(lambda h: make_solver("gd", prob, stepsize=h),
                          (0.5, 2.0), rounds=3, seed=0, eval_fn=ev)
    res_s, best_s = sweep(lambda h: make_solver("gd", prob, stepsize=h),
                          (0.5, 2.0), rounds=3, seed=0, eval_fn=ev,
                          eval_every=2)
    assert best_s == best_d
    assert res_s.history[-1] == res_d.history[-1]


# --------------------------------------------------------------------- #
# fail-fast on non-finite iterates
# --------------------------------------------------------------------- #


class _DivergeAt:
    """Protocol-minimal solver whose iterate goes NaN at a given round —
    traceable, so it drives both the eager loop and the scan path."""

    name = "diverge-stub"
    hyperparams = {}

    def __init__(self, bad_round):
        self.bad_round = bad_round

    def init(self, w0=None):
        w = jnp.zeros(3) if w0 is None else w0
        return SolverState(w=w, aux=(), round=jnp.asarray(0, jnp.int32))

    def round(self, state, key):
        bad = state.round == self.bad_round
        w = jnp.where(bad, jnp.full_like(state.w, jnp.nan), state.w + 1.0)
        return SolverState(w=w, aux=(), round=state.round + 1)


def test_fail_fast_raises_the_round_the_iterate_goes_nan():
    """The error names the solver and the exact round — what the campaign
    guard-rail quarantines."""
    with pytest.raises(NonFiniteIterateError) as ei:
        Trainer(_DivergeAt(2), rounds=5, seed=0).fit()
    assert ei.value.solver_name == "diverge-stub"
    assert ei.value.round_index == 2


def test_fail_fast_off_lets_the_run_finish():
    res = Trainer(_DivergeAt(2), rounds=5, seed=0, fail_fast=False).fit()
    assert int(res.state.round) == 5
    assert not bool(jnp.isfinite(res.w).all())


def test_fail_fast_scan_path_checks_final_iterate():
    with pytest.raises(NonFiniteIterateError):
        Trainer(_DivergeAt(3), rounds=5, seed=0, scan=True).fit()
