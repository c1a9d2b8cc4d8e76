"""Per-client state in the plain reference (``reference/common.py``): the
hook leaves the stateless references as they were, and the CoCoA+
reference (``reference/cocoa.py``), which carries a dual block per client,
agrees with the program's ``make_solver("cocoa")`` over the checked rounds
while copies that lose the state's rules do not."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import catalog
import compare
import run
from bench_cases import COHORTS
from reference import common
from repro.core import make_solver

SEED = 2 ** 31 + 23
#: the CoCoA+ reference against the program: both solve each row's scalar
#: subproblem to float32 (the program by 12 clipped Newton steps, the
#: reference by bisection), so they part by rounding over a few hundred
#: sequential steps a client: 1.6e-7 at most on this seed, 60 times under
#: this; a state reset or stepped out of turn reads 5e-2 or more
TOLERANCE = 1e-5


def _module(solver: str, **overrides):
    """A copy of the solver's reference module, with some of it replaced."""
    mod = catalog.load_module(catalog.HERE / "reference" / f"{solver}.py",
                              "reference")
    copy = types.SimpleNamespace(**{k: getattr(mod, k) for k in dir(mod)
                                    if not k.startswith("__")})
    copy.__name__ = mod.__name__
    for k, v in overrides.items():
        setattr(copy, k, v)
    return copy


@pytest.fixture(scope="module")
def tiny_data(tiny_cell):
    """The rows of a cohort cell at the tests' size, by configuration."""
    out = {}

    def get(name):
        if name not in out:
            cell = tiny_cell(name)
            spec, *rows = run.dataset(cell, SEED)
            out[name] = cell, spec, rows, common.Data(
                *rows, spec.train_sizes, spec.num_features)
        return out[name]

    return get


@pytest.mark.parametrize("name", COHORTS + ("fsvrg-gplus.full",))
def test_state_hook_leaves_a_stateless_reference_bit_identical(
        tiny_data, name):
    """The reference of a solver without per-client state, run through
    the state hook with a state it carries and never changes, gives the
    same iterates to the bit as without the hook."""
    cell, _, _, data = tiny_data(name)
    plain = cell.reference()

    def init_state(data, layout, static, params, dtype):
        return [jnp.zeros((len(g.members), 1), dtype) for g in layout]

    def deltas(*args, state):
        return plain.deltas(*args), state

    hooked = _module(cell.solver, init_state=init_state, deltas=deltas)
    p = float(cell.solver_kwargs()["participation"])
    params = cell.config["solver_kwargs"]
    want = common.run_rounds(data, plain, params, p, SEED,
                             run.CHECKED_ROUNDS)
    got = common.run_rounds(data, hooked, params, p, SEED,
                            run.CHECKED_ROUNDS)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def _cocoa_rounds(data, module, p, rounds=run.CHECKED_ROUNDS):
    return common.run_rounds(data, module, {"sigma": None}, p, SEED, rounds)


def _program_rounds(spec, rows, p, rounds=run.CHECKED_ROUNDS):
    solver = make_solver("cocoa", run.build(spec, *rows), participation=p)
    state, ws = solver.init(), []
    for r in range(rounds):
        state = solver.round(state, jax.random.fold_in(
            jax.random.PRNGKey(SEED), r))
        ws.append(np.asarray(state.w))
    return ws


@pytest.mark.parametrize("p", [1.0, 0.5])
def test_cocoa_reference_agrees_with_the_program(tiny_data, p):
    _, spec, rows, data = tiny_data("fedavg-gplus.cohort10")
    ref = _cocoa_rounds(data, _module("cocoa"), p)
    ws = _program_rounds(spec, rows, p)
    flat = common.Flat(data)
    got = compare.numbers(ws, [flat.loss(jnp.asarray(w)) for w in ws], ref,
                          [flat.loss(jnp.asarray(w)) for w in ref])
    assert max(got.values()) < TOLERANCE, got
    # round 2 starts from round 1's dual blocks: the state was carried
    assert np.abs(ref[1]).max() > np.abs(ref[0]).max()


def _reset_each_round():
    mod = _module("cocoa")
    return _module("cocoa", deltas=lambda *args, state: mod.deltas(
        *args, state=jnp.zeros_like(state)))


def _steps_everyone(monkeypatch):
    """Every member's state moves, the participants' deltas alone are
    summed: non-participants are stepped instead of frozen."""
    real = common._group_sum

    def group_sum(solver, g_rows, chosen, keys, *args):
        acc, _ = real(solver, g_rows, chosen, keys, *args)
        _, state = real(solver, g_rows, np.arange(len(keys)), keys, *args)
        return acc, state

    monkeypatch.setattr(common, "_group_sum", group_sum)
    return _module("cocoa")


@pytest.mark.parametrize("fault", ["reset_each_round", "steps_everyone"])
def test_a_reference_that_breaks_the_state_rules_fails_at_round_two(
        tiny_data, monkeypatch, fault):
    _, _, _, data = tiny_data("fedavg-gplus.cohort10")
    sound = _cocoa_rounds(data, _module("cocoa"), 0.5)
    broken = _cocoa_rounds(
        data, _reset_each_round() if fault == "reset_each_round"
        else _steps_everyone(monkeypatch), 0.5)
    gap = [float(np.abs(b - s).max() / np.abs(s).max())
           for b, s in zip(broken, sound)]
    # round 1 starts from α = 0 either way; round 2 is where the rule bites
    assert gap[0] == 0.0 and gap[1] > 100 * TOLERANCE, gap


@pytest.mark.parametrize("fault", [None, "half_cohort"])
def test_summed_weighting_adds_deltas_unweighted_and_unscaled(tiny_data,
                                                              fault):
    """``WEIGHTING = "sum"``: the server adds each participant's delta
    with weight 1 and no reweighting by the realized mass; under the
    ``half_cohort`` fault, the plain sum over every other participant."""
    _, _, _, data = tiny_data("fedavg-gplus.cohort10")
    d = data.num_features
    layout = common.groups(data.sizes)
    p = 0.5

    def ones(w, idx, val, y, nk, keys, ctx, static, params, dtype, *,
             state):
        return (jnp.where(nk[:, None] > 0, 1.0, 0.0) * jnp.ones((1, d)),
                state + 1)

    mod = _module("cocoa", deltas=ones)
    w1 = common.run_rounds(data, mod, {"sigma": None}, p, SEED, 1,
                           fault=fault)[0]
    chosen = [np.flatnonzero(common.takes_part(common.client_keys(
        SEED, 0, g.offset, len(g.members))[0], len(g.members), p))
        for g in layout]
    assert 0 < sum(map(len, chosen)) < len(data.sizes)
    if fault == "half_cohort":
        chosen = [c[::2] for c in chosen]
    takers = sum(map(len, chosen))
    np.testing.assert_array_equal(w1, np.full(d, takers, np.float32))

