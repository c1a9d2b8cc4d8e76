"""The trace reducer (trace_reduce.py): busy and idle time, module and kernel
time, and the breakdown, on hand-made events and on a small trace recorded
on a v5e chip."""
import pathlib

import pytest

import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _reduced():
    # one device; a round span 0..100 ns, an eval_f span 60..80 ns
    ops = [(10, 30, "%fusion.1"), (20, 45, "%fused_aggregate.3"),
           (50, 55, "%custom-call.2"), (90, 120, "%fused_aggregate")]
    kernels = [ops[1], ops[2], ops[3]]
    modules = [(10, 40, "jit__body(3)"), (50, 55, "jit_loss(4)"),
               (90, 120, "jit__body(3)")]
    spans = [(0, 100, "round"), (60, 80, "eval_f"), (85, 95, "callback")]
    return trace_reduce.Reduced(
        [{"ops": ops, "kernels": kernels, "modules": modules}], spans)


def test_busy_idle_module_and_kernel_time_by_hand():
    t = _reduced()
    assert t.window_s == pytest.approx(100e-9)
    # ops cover 10..45, 50..55, 90..100 inside the window
    assert t.busy_s == pytest.approx(50e-9)
    assert t.module_s("jit__body") == pytest.approx(40e-9)
    assert t.other_modules_s("jit__body") == pytest.approx(5e-9)
    assert t.kernel_s("fused_aggregate") == pytest.approx(35e-9)
    assert t.kernel_s("fused") == 0.0


def test_breakdown_names_gaps_by_the_innermost_span():
    b = _reduced().breakdown()
    # outermost ops: the kernel at 20..45 runs past fusion.1 (10..30), so
    # it counts whole; the window clips the last kernel to 90..100
    assert b["device_ops"] == [
        ["%fused_aggregate.3", pytest.approx(25e-9)],
        ["%fusion.1", pytest.approx(20e-9)],
        ["%fused_aggregate", pytest.approx(10e-9)],
        ["%custom-call.2", pytest.approx(5e-9)]]
    # 55..90 (mid 72.5, in eval_f), 0..10 and 45..50 (round)
    assert b["idle_gaps"] == [["eval_f", pytest.approx(35e-9)],
                              ["round", pytest.approx(10e-9)],
                              ["round", pytest.approx(5e-9)]]


def test_a_whole_trace_is_not_cut():
    assert _reduced().cut() == ""


def _long_round(module_ops):
    """Two round spans of 4 ms and a tail, and a 3 ms round program in the
    first whose operations run for ``module_ops`` ns of it."""
    ms = 1_000_000
    ops = [(ms, ms + module_ops, "%while.1"), (7 * ms, 7 * ms + 10, "%f.2")]
    modules = [(ms, 4 * ms, "jit__body(3)"), (7 * ms, 7 * ms + 10, "jit_f(4)")]
    spans = [(0, 4 * ms, "round"), (4 * ms, 8 * ms, "round"),
             (8 * ms, 8 * ms + 50, "round")]
    return ops, modules, spans


def test_a_trace_is_cut_where_a_long_module_has_no_operations():
    ops, modules, spans = _long_round(2_000_000)
    assert trace_reduce.Reduced(
        [{"ops": ops, "kernels": [], "modules": modules}], spans).cut() == ""
    ops, modules, spans = _long_round(900_000)
    cut = trace_reduce.Reduced(
        [{"ops": ops, "kernels": [], "modules": modules}], spans).cut()
    assert "jit__body(3)" in cut


def test_a_trace_is_cut_where_a_round_holds_no_module():
    ops, modules, spans = _long_round(2_000_000)
    cut = trace_reduce.Reduced(
        [{"ops": ops[:1], "kernels": [], "modules": modules[:1]}],
        spans).cut()
    assert "holds no module execution" in cut


def test_a_trace_is_cut_where_the_device_goes_quiet_before_the_end():
    """The profiler dropped the events after 7 ms: the second round's span
    runs on to 20 ms and holds only an eager module at its start."""
    ops, modules, spans = _long_round(2_000_000)
    ms = 1_000_000
    spans[1] = (4 * ms, 20 * ms, "round")
    spans[2] = (20 * ms, 20 * ms + 50, "round")
    cut = trace_reduce.Reduced(
        [{"ops": ops, "kernels": [], "modules": modules}], spans).cut()
    assert "before the end of the last round" in cut


def test_an_empty_trace_reads_nothing():
    t = trace_reduce.Reduced([], [])
    assert t.busy_s == 0.0 and t.window_s == 0.0
    assert t.breakdown() == {"device_ops": [], "idle_gaps": []}


@pytest.fixture(scope="module")
def chip_trace():
    """One FedAvg round at a tiny size (10 clients, d = 200), traced on a
    TPU v5e with the benchmark's spans and profiler options."""
    import gzip

    import jax

    raw = gzip.open(DATA / "tiny_fedavg_full.xplane.pb.gz").read()
    return jax.profiler.ProfileData.from_serialized_xspace(raw)


def _events(data, plane, line):
    p = data.find_plane_with_name(plane)
    return [e for ln in p.lines if ln.name == line for e in ln.events]


def test_chip_trace_reads_its_known_times(chip_trace):
    import numpy as np

    t = trace_reduce.from_xspace(chip_trace)
    spans = [e for e in _events(chip_trace, "/host:CPU", "python")
             if e.name == "round"]
    lo = min(e.start_ns for e in spans)
    hi = max(e.end_ns for e in spans)
    assert t.window_s == pytest.approx(15.724989e-3, rel=1e-12)
    assert t.window_s == pytest.approx((hi - lo) * 1e-9, rel=1e-12)

    # busy time, counted on a 1 ns grid over the window
    grid = np.zeros(int(hi - lo), bool)
    for e in _events(chip_trace, "/device:TPU:0", "XLA Ops"):
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            grid[int(a - lo):int(b - lo)] = True
    assert t.busy_s == pytest.approx(grid.sum() * 1e-9, rel=1e-6)
    assert t.busy_s == pytest.approx(6.779577e-3, rel=1e-6)

    body = sum(e.duration_ns for e in _events(
        chip_trace, "/device:TPU:0", "XLA Modules")
        if e.name.startswith("jit__body("))
    assert t.module_s("jit__body") == pytest.approx(body * 1e-9, rel=1e-12)
    assert t.module_s("jit__body") == pytest.approx(6.512097e-3, rel=1e-9)
    assert t.other_modules_s("jit__body") == pytest.approx(0.290341e-3,
                                                           rel=1e-9)

    kernel = sum(e.duration_ns for e in _events(
        chip_trace, "/device:TPU:0", "XLA Ops")
        if e.name.startswith("%fedavg_update.") and " custom-call(" in e.name)
    assert t.kernel_s("fedavg_update") == pytest.approx(kernel * 1e-9,
                                                        rel=1e-12)
    assert t.kernel_s("fedavg_update") == pytest.approx(108.638e-6, rel=1e-9)
    assert t.kernel_s("fused_aggregate") == pytest.approx(0.637e-6, rel=1e-9)


def test_chip_trace_is_whole(chip_trace):
    t = trace_reduce.from_xspace(chip_trace)
    assert t.events == sum(
        1 for ln in chip_trace.find_plane_with_name("/device:TPU:0").lines
        for _ in ln.events)
    assert t.cut() == ""


def test_chip_trace_breakdown(chip_trace):
    b = trace_reduce.from_xspace(chip_trace).breakdown()
    assert b["device_ops"][0] == ["%while.96", pytest.approx(5.361555e-3)]
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == 10
    assert b["idle_gaps"][0] == ["eval_f", pytest.approx(1.516035e-3)]
    assert all(label in ("round", "eval_f", "callback")
               for label, _ in b["idle_gaps"])
