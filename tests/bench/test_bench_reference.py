"""Each solver's plain reference round (reference/) agrees with the
program's ``make_solver(...).round`` under both traffic mixes, and its
bfloat16 control does not."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compare
import run
from bench_cases import CELLS, MIXES
from reference import common
from repro.core import make_solver

SEED = 2 ** 31 + 17


def _program(cell, seed):
    """The program's first rounds through ``solver.round``, the rows they
    ran on, and f after each."""
    spec, idx, val, y, client_of = run.dataset(cell, seed)
    prob = run.build(spec, idx, val, y, client_of)
    solver = make_solver(cell.solver, prob, **cell.solver_kwargs())
    state, ws = solver.init(), []
    base = jax.random.PRNGKey(seed)
    for r in range(run.CHECKED_ROUNDS):
        state = solver.round(state, jax.random.fold_in(base, r))
        ws.append(np.asarray(state.w))
    data = common.Data(idx, val, y, client_of, spec.train_sizes,
                       spec.num_features)
    return ws, data, [prob.flat.loss(w) for w in ws]


def _reference(cell, data, seed, **kw):
    p = float(cell.solver_kwargs().get("participation", 1.0))
    ref = common.run_rounds(data, cell.reference(),
                            cell.config["solver_kwargs"], p, seed,
                            run.CHECKED_ROUNDS, **kw)
    flat = common.Flat(data)
    return ref, [flat.loss(jnp.asarray(w)) for w in ref]


@pytest.fixture(scope="module")
def runs(tiny_cell):
    """Per cell: the program's checked rounds, the rows, and the float32
    reference's rounds, made once for both tests."""
    out = {}

    def get(name):
        if name not in out:
            cell = tiny_cell(name)
            ws, data, fs = _program(cell, SEED)
            out[name] = (cell, ws, fs, data, *_reference(cell, data, SEED))
        return out[name]

    return get


@pytest.mark.parametrize("name", MIXES)
def test_reference_round_agrees_with_the_program(runs, name):
    cell, ws, fs, data, ref, f_ref = runs(name)
    got = compare.numbers(ws, fs, ref, f_ref)
    # float32 rounding over a few thousand sequential steps
    assert max(got.values()) < 1e-5, got
    if name in CELLS:
        assert compare.holds({k: {"value": v, "limit": cell.limits[k]}
                              for k, v in got.items()})


@pytest.mark.parametrize("name", CELLS)
def test_bfloat16_control_is_not_correct(runs, name):
    """The control — the reference in bfloat16, the precision below the
    configuration's float32, in the program's place — fails the cell's
    limits."""
    cell, _, _, data, ref, f_ref = runs(name)
    ctl, f_ctl = _reference(cell, data, SEED, dtype=jnp.bfloat16)
    got = compare.numbers(ctl, f_ctl, ref, f_ref)
    assert not compare.holds({k: {"value": v, "limit": cell.limits[k]}
                              for k, v in got.items()}), got
