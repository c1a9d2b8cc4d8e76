"""What the CoCoA+ cell adds to the benchmark: the program's ``fl.state``
scope, present in a round that carries per-client state and absent from
every other; the readers of the dual state's time and of the
``cocoa_sdca`` kernel on hand-made traces; and the CoCoA+ work counts
(work/cocoa*.py) against hand counts on a 3-client problem."""
import jax
import numpy as np
import pytest

import catalog
import program_trace
import run
import trace_reduce
from bench_cases import MIXES
from repro.core import make_solver

#: 3 clients of 2, 3 and 4 rows take part; rows of 3 entries (28 bytes)
SHAPES = {"n": 9, "d": 7, "entries": 3, "clients": 3}
ROUND = {"participant_sizes": [np.array([2]), np.array([3, 4])],
         "evaluates": True}


def _read(name, ctx):
    return catalog.load_module(catalog.HERE / "metrics" / f"{name}.py",
                               "metric").read(ctx)


def _work(stem):
    return catalog.load_module(catalog.HERE / "work" / f"{stem}.py", "work")


@pytest.mark.parametrize("name", MIXES)
def test_only_a_stateful_round_carries_the_state_scope(tiny_cell, name):
    """The cell's round as the harness lowers it, compiled: ``fl.state``
    is among its scopes exactly where the solver carries per-client
    state.  A streamed cell runs 7-client chunks here, so that the tiny
    buckets pad and chunk their clients as the §4 buckets do, and the
    state's padding and write-back are operations of the round."""
    cell = tiny_cell(name)
    spec, *rows = run.dataset(cell, 2 ** 31 + 5)
    kwargs = cell.solver_kwargs()
    if "client_chunk" in kwargs:
        kwargs["client_chunk"] = 7
    solver = make_solver(cell.solver, run.build(spec, *rows), **kwargs)
    hlo = solver.lower_round(solver.init(),
                             jax.random.PRNGKey(0)).compile().as_text()
    scopes = set(program_trace.scopes(hlo).values())
    assert {"fl.client_pass", "fl.aggregate"} <= scopes
    assert ("fl.state" in scopes) is (cell.solver == "cocoa"), scopes


def _program(ops, hlo, kernels=()):
    modules = [(0, 100, "jit__body(1)", "fl.dispatch")]
    reduced = trace_reduce.Reduced(
        [{"ops": ops, "kernels": list(kernels),
          "modules": [m[:3] for m in modules]}], [(0, 100, "round")])
    return program_trace.Program(reduced, [], [modules], hlo, "jit__body")


STATE_HLO = """HloModule jit__body, is_scheduled=true

ENTRY %main.9 (x.1: f32[8]) -> f32[] {
  %x.1 = f32[8]{0} parameter(0)
  %concatenate.2 = f32[16]{0} concatenate(%x.1, %x.1), metadata={op_name="jit(_body)/fl.client_pass/fl.state/concatenate"}
  %copy.3 = f32[16]{0} copy(%concatenate.2)
  %while.4 = f32[16]{0} while(%copy.3), metadata={op_name="jit(_body)/fl.client_pass/while"}
  ROOT %slice.5 = f32[8]{0} slice(%while.4), metadata={op_name="jit(_body)/fl.client_pass/fl.state/slice"}
}
"""


def test_state_ms_reads_the_state_scope_and_what_inherits_it():
    # the copy the compiler put after the padding inherits fl.state
    ops = [(0, 10, "%concatenate.2"), (10, 15, "%copy.3"),
           (15, 90, "%while.4"), (90, 100, "%slice.5")]
    prog = _program(ops, STATE_HLO)
    ctx = {"program": prog, "window": {"rounds": 1}}
    assert _read("state_ms", ctx) == pytest.approx(25e-6)
    assert _read("client_pass_ms", ctx) == pytest.approx(75e-6)
    assert prog.scope_s("fl.state") == pytest.approx(20e-9)


def test_state_ms_is_none_without_state_or_trace():
    hlo = STATE_HLO.replace("fl.client_pass/fl.state/", "fl.client_pass/")
    prog = _program([(0, 100, "%while.4")], hlo)
    assert _read("state_ms", {"program": prog,
                              "window": {"rounds": 1}}) is None
    assert _read("state_ms", {"program": None,
                              "window": {"rounds": 1}}) is None


def _kernel_ctx(cell, kernels):
    reduced = trace_reduce.Reduced(
        [{"ops": list(kernels), "kernels": list(kernels),
          "modules": [(0, 10_000, "jit__body(1)")]}], [(0, 10_000, "round")])
    return {"trace": reduced, "window": {"rounds": 2}, "cell": cell,
            "shapes": SHAPES,
            "traced_rounds": [ROUND, dict(ROUND, evaluates=False)],
            "peaks": {"flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}}


def test_the_sdca_kernel_readers_take_its_custom_calls_only(tiny_cell):
    ctx = _kernel_ctx(tiny_cell("cocoa-gplus.full"),
                      [(0, 1000, "%cocoa_sdca_update.6"),
                       (2000, 2500, "%cocoa_sdca_update"),
                       (3000, 9000, "%fused_aggregate.2")])
    assert _read("kernel_ms.cocoa_sdca", ctx) == pytest.approx(1500e-6 / 2)
    # 9 rows a round, 16 bytes and 198 FLOPs each: FLOPs bind at 1 GFLOP/s
    least = 2 * 9 * 198 / 1e9
    assert _read("roofline.cocoa_sdca", ctx) == pytest.approx(
        100 * least / 1500e-9)


@pytest.mark.parametrize("name", ["cocoa-gplus.full", "fedavg-gplus.full"])
def test_the_sdca_kernel_readers_give_none_where_it_did_not_run(tiny_cell,
                                                                name):
    ctx = _kernel_ctx(tiny_cell(name), [(0, 1000, "%fused_aggregate.2")])
    assert _read("kernel_ms.cocoa_sdca", ctx) is None
    assert _read("roofline.cocoa_sdca", ctx) is None
    assert _read("kernel_ms.cocoa_sdca", dict(ctx, trace=None)) is None
    assert _read("roofline.cocoa_sdca", dict(ctx, trace=None)) is None


def test_cocoa_round_work_by_hand():
    w = _work("cocoa").round_work(SHAPES, {}, ROUND)
    rows, row, d = 9, 8 * 3 + 4, 7
    passes = rows * (row + 8) + 3 * 4 * d
    update = 3 * 4 * d
    evaluation = 9 * row + 4 * d
    assert w["bytes"] == passes + update + evaluation
    solve = 6 + 12 * 16
    assert w["flops"] == (rows * (8 * 3 + solve + 10) + 2 * 3 * d + 2 * d
                          + 9 * (2 * 3 + 8))
    quiet = _work("cocoa").round_work(SHAPES, {}, dict(ROUND,
                                                       evaluates=False))
    assert quiet["bytes"] == passes + update


def test_cocoa_kernel_work_by_hand():
    agg = _work("cocoa.fused_aggregate").kernel_work(SHAPES, {}, ROUND)
    assert agg == {"flops": 2 * 3 * 7 + 3 * 7,
                   "bytes": 4 * 3 * 7 + 4 * 3 + 3 * 4 * 7}
    sdca = _work("cocoa.cocoa_sdca").kernel_work(SHAPES, {}, ROUND)
    assert sdca == {"flops": 9 * (6 + 12 * 16), "bytes": 9 * 16}
