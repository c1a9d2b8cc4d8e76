"""The work counts (work/) against hand counts at tiny shapes."""
import numpy as np

import catalog
import peaks

SHAPES = {"n": 10, "d": 7, "entries": 3, "clients": 4}


def _work(stem):
    return catalog.load_module(catalog.HERE / "work" / f"{stem}.py", "work")


def test_fsvrg_round_work_by_hand():
    # clients of 2 and 3 rows take part; rows are 8·3 + 4 = 28 bytes
    rnd = {"participant_sizes": [np.array([2]), np.array([3])],
           "evaluates": True}
    w = _work("fsvrg").round_work(SHAPES, {}, rnd)
    full_grad = 10 * 28 + 2 * 4 * 7
    passes = 5 * 28 + 2 * 4 * 7
    update = 4 * 4 * 7
    evaluation = 10 * 28 + 4 * 7
    assert w["bytes"] == full_grad + passes + update + evaluation
    assert w["flops"] == (10 * (4 * 3 + 8) + 5 * (6 * 3 + 16) + 2 * 2 * 7
                          + 3 * 7 + 10 * (2 * 3 + 8))


def test_fedavg_round_work_by_hand():
    rnd = {"participant_sizes": [np.array([2, 4])], "evaluates": False}
    w = _work("fedavg").round_work(SHAPES, {"local_epochs": 2}, rnd)
    assert w["bytes"] == 2 * 6 * 28 + 2 * 4 * 7 + 3 * 4 * 7
    assert w["flops"] == 2 * 6 * (4 * 3 + 12) + 2 * 2 * 7 + 2 * 7


def test_kernel_work_by_hand():
    rnd = {"participant_sizes": [np.array([2]), np.array([3, 1])],
           "evaluates": False}
    for stem in ("fsvrg.fused_aggregate", "fedavg.fused_aggregate"):
        k = _work(stem).kernel_work(SHAPES, {}, rnd)
        assert k == {"flops": 2 * 3 * 7 + 3 * 7,
                     "bytes": 4 * 3 * 7 + 4 * 3 + 3 * 4 * 7}


def test_least_time_takes_the_binding_bound():
    peak = peaks.lookup("TPU v5 lite")
    t, bound = peaks.least_time_s({"flops": 197e12, "bytes": 1.0}, peak)
    assert (t, bound) == (1.0, "flops")
    t, bound = peaks.least_time_s({"flops": 1.0, "bytes": 1638e9}, peak)
    assert bound == "bytes" and abs(t - 2.0) < 1e-12


def test_unknown_device_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")
