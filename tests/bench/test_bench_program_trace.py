"""The program's spans and scopes read from a trace (program_trace.py): the
HLO scope map, self time of nested operations, the join of module
executions to their dispatch, on hand-made events, on the one-round
FedAvg chip trace (the benchmark's spans only) and on a small FSVRG trace
recorded on a v5e with the program's spans and scopes."""
import gzip
import pathlib

import pytest

import program_trace
import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "data"

HLO = """HloModule jit__body, is_scheduled=true

%body (p: f32[]) -> f32[] {
  %p = f32[] parameter(0)
  ROOT %add.2 = f32[] add(%p, %p), metadata={op_name="jit(_body)/fl.client_pass/while/body/closed_call/fl.aggregate/add"}
}

ENTRY %main.9 (x.1: f32[8]) -> f32[] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %while.3 = (s32[], f32[]) while(%t), condition=%c, body=%body, metadata={op_name="jit(_body)/fl.client_pass/while" source_file="e.py" source_line=3}
  %copy-start.1 = f32[8]{0} copy-start(%x.1)
  %fusion.4 = f32[8]{0} fusion(%x.1, %while.3), kind=kCustom, calls=%fc
  ROOT %fused_aggregate.7 = f32[] custom-call(%while.3), metadata={op_name="jit(_body)/fl.aggregate/jit(fused_aggregate)/pallas_call"}
}
"""


def test_scopes_name_each_instruction_by_its_innermost_scope():
    assert program_trace.scopes(HLO) == {
        "%p": None, "%add.2": "fl.aggregate", "%x.1": None,
        "%while.3": "fl.client_pass", "%copy-start.1": None,
        "%fusion.4": None, "%fused_aggregate.7": "fl.aggregate"}


def test_an_instruction_without_a_scope_inherits_its_source():
    # a fusion the compiler made takes its operand's scope; the loop body's
    # parameter takes that of the loop that calls the body
    assert program_trace.inherited(HLO) == {
        "%p": "fl.client_pass", "%add.2": "fl.aggregate", "%x.1": None,
        "%while.3": "fl.client_pass", "%copy-start.1": None,
        "%fusion.4": "fl.client_pass", "%fused_aggregate.7": "fl.aggregate"}


def test_self_time_goes_to_the_innermost_running_operation():
    # a loop 0..100 with body ops 10..30 and 40..60, one of them holding
    # an op 45..50; then an op that starts as the loop ends
    ops = [(0, 100, "loop"), (10, 30, "a"), (40, 60, "b"), (45, 50, "c"),
           (100, 110, "d")]
    assert program_trace.self_times(ops, lambda n: n) == {
        "loop": 60, "a": 20, "b": 15, "c": 5, "d": 10}
    # a label shared by several operations sums them
    assert program_trace.self_times(
        ops, lambda n: "loop" if n == "loop" else "body") == {
            "loop": 60, "body": 50}


@pytest.fixture(scope="module")
def fedavg_trace():
    """The one-round FedAvg chip trace of test_bench_trace.py, recorded
    before the program had spans: the benchmark's spans only."""
    return program_trace.read(DATA / "tiny_fedavg_full.xplane.pb.gz",
                              "/dev/null", "jit__body")


def test_every_module_of_the_fedavg_trace_is_joined_to_a_dispatch(
        fedavg_trace):
    t = fedavg_trace.reduced
    assert fedavg_trace.unjoined == 0
    assert all(m[3] is not None for m in fedavg_trace.modules[0])
    # no program spans: every dispatch is outside them, and the span table
    # holds every module execution in the window
    assert fedavg_trace.span_table() == {
        program_trace.OUTSIDE: pytest.approx(
            t.module_s("jit__body") + t.other_modules_s("jit__body"),
            rel=1e-12)}
    # without program spans the gaps keep the benchmark's labels
    gaps = t.breakdown()["idle_gaps"]
    assert gaps and all(label in trace_reduce.SPANS for label, _ in gaps)


@pytest.fixture(scope="module")
def fsvrg_trace():
    """Two FSVRG rounds at a tiny size (60 clients, d = 200), traced on a
    TPU v5e by ``program_trace.py --tiny --out``, with the round's
    compiled HLO text."""
    return program_trace.read(DATA / "tiny_fsvrg_full.xplane.pb.gz",
                              DATA / "tiny_fsvrg_full.hlo.txt.gz",
                              "jit__body")


def test_chip_trace_joins_every_module_in_its_window(fsvrg_trace):
    assert fsvrg_trace.unjoined == 0
    assert fsvrg_trace.reduced.cut() == ""
    spans = {n for _, _, n in fsvrg_trace.spans}
    assert {"fl.round", "fl.prelude", "fl.dispatch", "fl.check_finite",
            "fl.eval", "fl.callback"} <= spans
    # the round's module is dispatched under fl.dispatch, nothing else is
    table = fsvrg_trace.span_table()
    t = fsvrg_trace.reduced
    assert table["fl.dispatch"] == pytest.approx(t.module_s("jit__body"),
                                                 rel=1e-12)
    assert sum(table.values()) == pytest.approx(
        t.module_s("jit__body") + t.other_modules_s("jit__body"), rel=1e-12)


def test_chip_trace_scopes_sum_to_the_round_program(fsvrg_trace):
    scoped = fsvrg_trace.scope_table()
    program = fsvrg_trace.reduced.module_s("jit__body")
    assert "unknown" not in scoped
    assert fsvrg_trace.scope_s("fl.client_pass") > 0
    assert fsvrg_trace.scope_s("fl.aggregate") > 0
    assert sum(scoped.values()) == pytest.approx(program, rel=0.01)


def test_chip_trace_phases_sum_to_the_eager_work(fsvrg_trace):
    r = fsvrg_trace.readings(2)
    assert r["full_grad_ms"] > 0 and r["eval_ms"] > 0 and r["check_ms"] > 0
    assert r["full_grad_ms"] + r["eval_ms"] + r["check_ms"] == pytest.approx(
        r["eager_ms"], rel=0.02)
    assert r["client_pass_ms"] + r["aggregate_ms"] <= r["round_program_ms"]


def test_chip_trace_names_the_unscoped_operations(fsvrg_trace):
    rows = fsvrg_trace.unscoped_ops()
    unscoped = fsvrg_trace.scope_s(None)
    assert rows and sum(v for _, v, _, _ in rows) <= unscoped * (1 + 1e-12)
    # the longest is the compiler's fusion of the client pass's scatter
    name, _, what, source = rows[0]
    assert what.startswith("fusion f32[") and source == "fl.client_pass"
    assert fsvrg_trace.scope_table(inherit=True).get(None, 0.0) < (
        0.01 * unscoped)


def test_chip_trace_names_idle_gaps_by_program_spans(fsvrg_trace):
    # the breakdown the ledger keeps; one gap falls after the last round's
    # spans close, in the benchmark's tail round span
    assert [label for label, _ in fsvrg_trace.reduced.breakdown()[
        "idle_gaps"]] == [
        "fl.eval", "fl.eval", "fl.check_finite", "fl.check_finite",
        "fl.round", "round", "fl.round", "fl.prelude", "fl.prelude",
        "fl.prelude"]


def test_the_hlo_text_is_the_round_module():
    text = gzip.open(DATA / "tiny_fsvrg_full.hlo.txt.gz").read().decode()
    assert text.startswith("HloModule jit__body,")
