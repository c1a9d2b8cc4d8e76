"""The benchmark's copy of the §4 generator makes the program's data."""
import dataclasses

import numpy as np
import pytest

import generator
from repro.configs import get_logreg_config
from repro.data.synthetic import generate

FIELDS = ("idx", "val", "y", "client_of", "client_sizes", "test_idx",
          "test_val", "test_y", "test_client_of")


@pytest.mark.parametrize("scale,seed", [(0.001, 3), (0.002, 0),
                                        (0.002, 2 ** 31 + 5)])
def test_copy_equals_program_generate_bit_for_bit(scale, seed):
    cfg = get_logreg_config().scaled(scale)
    want = generate(cfg, seed=seed)
    got = generator.generate(dataclasses.asdict(cfg), seed)
    for f in FIELDS:
        a, b = getattr(want, f), got[f]
        assert a.dtype == b.dtype and np.array_equal(a, b), f
    assert got["num_features"] == want.num_features


def test_fixed_sizes_keep_the_work_and_change_the_rows():
    """With a sizes seed every run seed has the same client sizes (so the
    same program shapes and weights) and other rows."""
    problem = dataclasses.asdict(get_logreg_config().scaled(0.002))
    a = generator.draw_spec(problem, 11, sizes_seed=0)
    b = generator.draw_spec(problem, 12, sizes_seed=0)
    assert np.array_equal(a.full_sizes, b.full_sizes)
    assert int(a.full_sizes.sum()) == problem["num_examples"]
    ra = generator.rows(a, a.train_sizes)
    rb = generator.rows(b, b.train_sizes)
    assert ra[0].shape == rb[0].shape
    assert not np.array_equal(ra[0], rb[0])


def test_fixed_pattern_keeps_the_features_and_changes_the_labels():
    """With a pattern seed every run seed has the same features in every
    row (so the same per-feature counts) and other labels; one run seed
    makes the same rows twice."""
    problem = dataclasses.asdict(get_logreg_config().scaled(0.002))
    a = generator.draw_spec(problem, 11, sizes_seed=0, pattern_seed=0)
    b = generator.draw_spec(problem, 2 ** 31 + 12, sizes_seed=0,
                            pattern_seed=0)
    ra = generator.rows(a, a.train_sizes)
    rb = generator.rows(b, b.train_sizes)
    assert np.array_equal(ra[0], rb[0]) and np.array_equal(ra[1], rb[1])
    assert np.array_equal(ra[3], rb[3])
    assert not np.array_equal(ra[2], rb[2])
    again = generator.rows(a, a.train_sizes)
    assert all(np.array_equal(x, z) for x, z in zip(ra, again))
