"""A whole run, past the look for a chip, at a size a test holds: sound, it
reads ``correct``; with the timed path broken underneath the harness, it
does not.  The faults a federated round can have on one chip: a round that
returns its state unchanged, and half of the cohort left out with the
weighted mean taken over the rest."""
import jax.numpy as jnp
import pytest

import run
from bench_cases import CELLS


def unchanged(solver):
    solver.round = lambda state, key: state.replace(round=state.round + 1)


def half_cohort(solver):
    engine = solver.engine
    weights = engine.bucket_weights

    def kept_half(wi, n):
        w = weights(wi, n)
        kept = jnp.where(jnp.arange(n) % 2 == 0, w, 0.0)
        return kept * (w.sum() / jnp.maximum(kept.sum(), 1e-30))

    engine.bucket_weights = kept_half


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [None, unchanged, half_cohort],
                         ids=["sound", "unchanged", "half_cohort"])
def test_run_reads_correct_only_when_sound(tiny_cell, tmp_path, name, fault):
    rec = run.run_cell(tiny_cell(name), 2 ** 31 + 101, 0.2, False,
                       out_dir=tmp_path, break_round=fault)
    assert rec["correct"] is (fault is None), rec["checks"]
    assert list(rec)[-1] == "checks"
    assert {"setup_s", "round_s"} <= set(rec["metrics"])
