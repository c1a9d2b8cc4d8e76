"""The per-layer readers of the program's own instruments (``metrics/``):
the client pass, aggregation, full gradient, eval of f and cohort gather
from the ``fl.*`` spans and scopes of a trace, and compile time from the
program's counters; and what a run's record holds with and without
``--trace``."""
import gzip
import json
import pathlib

import pytest

import catalog
import peaks
import program_trace
import run
import trace_reduce
from bench_cases import ROOT

DATA = pathlib.Path(__file__).resolve().parent / "data"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
#: the readers of the program's instruments, and what each reads
SCOPED = {"client_pass_ms": ("fl.client_pass",),
          "aggregate_ms": ("fl.aggregate",),
          "gather_ms": ("fl.sample", "fl.gather")}
SPANNED = {"full_grad_ms": "fl.prelude", "eval_ms": "fl.eval"}
ROUNDS = 2


def _read(name, ctx):
    return catalog.load_module(catalog.HERE / "metrics" / f"{name}.py",
                               "metric").read(ctx)


@pytest.fixture(scope="module")
def fsvrg_ctx():
    """What a traced run hands the readers, from the two FSVRG rounds
    traced on a v5e at a tiny size, with the round's compiled HLO."""
    prog = program_trace.read(DATA / "tiny_fsvrg_full.xplane.pb.gz",
                              DATA / "tiny_fsvrg_full.hlo.txt.gz",
                              "jit__body")
    return {"program": prog, "trace": prog.reduced,
            "window": {"rounds": ROUNDS}, "round_module": "jit__body"}


def test_every_reader_of_the_program_is_a_per_layer_metric():
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in (*SCOPED, *SPANNED, "compile_s"):
        assert name in per_layer and per_layer[name]["workloads"]
    assert per_layer["compile_s"]["moves"] == "setup_s"
    assert per_layer["full_grad_ms"]["workloads"] == ["fsvrg-gplus.full"]
    assert per_layer["gather_ms"]["workloads"] == ["fedavg-gplus.cohort10"]


@pytest.mark.parametrize("name", sorted(SCOPED))
def test_scope_readers_return_what_program_trace_gives(fsvrg_ctx, name):
    prog = fsvrg_ctx["program"]
    want = sum(prog.scope_s(k, inherit=True) for k in SCOPED[name])
    got = _read(name, fsvrg_ctx)
    if want == 0:
        # a full round has no cohort gather: nothing to read, no 0
        assert name == "gather_ms" and got is None
    else:
        assert got == pytest.approx(1e3 * want / ROUNDS, rel=1e-12)


def test_the_client_pass_counts_the_scatter_fusions_it_left(fsvrg_ctx):
    prog = fsvrg_ctx["program"]
    own = prog.scope_s("fl.client_pass")
    got = _read("client_pass_ms", fsvrg_ctx)
    assert got > 1e3 * own / ROUNDS
    assert prog.scope_table(inherit=True).get(None, 0.0) < 0.01 * own


@pytest.mark.parametrize("name", sorted(SPANNED))
def test_span_readers_return_what_program_trace_gives(fsvrg_ctx, name):
    got = _read(name, fsvrg_ctx)
    assert got > 0
    assert got == pytest.approx(
        fsvrg_ctx["program"].readings(ROUNDS)[name], rel=1e-12)


def test_the_readers_sum_to_the_round_program_and_eager_work(fsvrg_ctx):
    """The scopes split the round's module, the spans the other modules;
    the sums are the checks ``PERF.md`` gives for the chip runs."""
    prog, t = fsvrg_ctx["program"], fsvrg_ctx["trace"]
    ms = 1e3 / ROUNDS
    program = _read("round_program_ms", fsvrg_ctx)
    rest = sum(v for k, v in prog.scope_table(inherit=True).items()
               if k not in ("fl.client_pass", "fl.aggregate")) * ms
    assert _read("client_pass_ms", fsvrg_ctx) + _read(
        "aggregate_ms", fsvrg_ctx) + rest == pytest.approx(program, rel=0.01)
    check = prog.span_modules_s("fl.check_finite") * ms
    assert _read("full_grad_ms", fsvrg_ctx) + _read(
        "eval_ms", fsvrg_ctx) + check == pytest.approx(
            1e3 * t.other_modules_s("jit__body") / ROUNDS, rel=0.02)


def test_the_gather_reader_sums_sample_and_gather_scopes():
    hlo = """HloModule jit__body, is_scheduled=true

ENTRY %main.9 (x.1: f32[8]) -> f32[] {
  %x.1 = f32[8]{0} parameter(0)
  %rng.2 = f32[8]{0} rng(%x.1), metadata={op_name="jit(_body)/fl.sample/uniform"}
  %gather.3 = f32[8]{0} gather(%x.1, %rng.2), metadata={op_name="jit(_body)/fl.gather/take"}
  %while.4 = f32[8]{0} while(%gather.3), metadata={op_name="jit(_body)/fl.client_pass/while"}
  ROOT %scatter.5 = f32[8]{0} scatter(%while.4), metadata={op_name="jit(_body)/fl.gather/scatter"}
}
"""
    ops = [(0, 10, "%rng.2"), (10, 30, "%gather.3"), (30, 90, "%while.4"),
           (90, 100, "%scatter.5")]
    modules = [(0, 100, "jit__body(1)", "fl.dispatch")]
    reduced = trace_reduce.Reduced(
        [{"ops": ops, "kernels": [], "modules": [m[:3] for m in modules]}],
        [(0, 100, "round")])
    prog = program_trace.Program(reduced, [], [modules], hlo, "jit__body")
    ctx = {"program": prog, "window": {"rounds": 1}}
    assert _read("gather_ms", ctx) == pytest.approx(40e-6)
    assert _read("client_pass_ms", ctx) == pytest.approx(60e-6)
    assert _read("aggregate_ms", ctx) is None


@pytest.mark.parametrize("name", [*SCOPED, *SPANNED])
def test_an_untraced_run_gives_the_program_readers_nothing(name):
    assert _read(name, {"program": None, "window": {"rounds": 3}}) is None


def test_compile_s_reads_the_counter_at_the_window_start():
    start = {"compiles": 7, "compile_s": 12.5, "cache_hits": 7,
             "cache_misses": 0}
    end = dict(start, compiles=9, compile_s=14.0)
    assert _read("compile_s", {"counters": {"start": start,
                                            "end": end}}) == 12.5
    assert _read("compile_s", {"counters": {"start": dict(
        start, compile_s=0.0), "end": end}}) is None
    assert _read("compile_s", {"counters": None}) is None


def test_an_idle_gap_is_named_by_the_program_span_first():
    ops = [(10, 20, "%a")]
    reduced = trace_reduce.Reduced(
        [{"ops": ops, "kernels": [], "modules": [(10, 20, "jit__body(1)")]}],
        [(0, 100, "round"), (60, 80, "eval_f"), (0, 100, "fl.round"),
         (50, 90, "fl.eval"), (85, 95, "callback")])
    # 20..100 (mid 60: fl.eval inside eval_f), 0..10 (fl.round)
    assert reduced.breakdown()["idle_gaps"] == [
        ["fl.eval", pytest.approx(80e-9)], ["fl.round", pytest.approx(10e-9)]]
    assert reduced._label(92) == "fl.round"
    # without the program's spans the benchmark's name the gap
    reduced.spans = [s for s in reduced.spans if not s[2].startswith("fl.")]
    assert reduced._label(70) == "eval_f" and reduced._label(92) == "callback"


@pytest.mark.parametrize("trace", [0, 1])
def test_a_record_holds_end_to_end_or_per_layer_metrics(
        tiny_cell, tmp_path, monkeypatch, trace):
    """``--trace 0``: the end-to-end metrics and no per-layer key;
    ``--trace 1``: per-layer metrics only, the program's readings and the
    breakdown, ``checks`` last either way.  On the CPU the trace has no
    device, so only readers that need none read a number."""
    cell = tiny_cell("fedavg-gplus.cohort10")
    lookup = peaks.lookup
    monkeypatch.setattr(peaks, "lookup", lambda kind: lookup("TPU v5 lite"))
    keep = tmp_path / "keep"
    rec = run.run_cell(cell, 2 ** 31 + 211, 0.2, bool(trace),
                       out_dir=tmp_path / "out", keep=keep)
    assert rec["correct"] and list(rec)[-1] == "checks"
    end_to_end = {m["name"] for m in cell.end_to_end}
    per_layer = {m["name"] for m in cell.per_layer}
    if trace:
        assert set(rec["metrics"]) <= per_layer
        assert "compile_s" in rec["metrics"]
        assert {"program", "breakdown"} <= set(rec)
        assert rec["program"]["unjoined"] == 0
        # the trace and the round's HLO text, kept as the test fixtures are
        hlo = gzip.open(keep / f"{cell.name}.hlo.txt.gz").read().decode()
        assert hlo.startswith("HloModule jit__body,")
        assert (keep / f"{cell.name}.xplane.pb.gz").stat().st_size > 0
    else:
        assert set(rec["metrics"]) == end_to_end - {"peak_hbm_gib"}
        assert not {"program", "breakdown"} & set(rec)
        assert not per_layer & set(rec["metrics"])
        assert not keep.exists()
    assert rec["compiles_in_window"] == 0
