"""The program's own spans, scopes and compile counters (repro.utils.obs):
every round path's compiled HLO carries its device scopes and nothing else
changes; the compile counters move on a compile only; a fit under the
profiler writes its round and phase spans."""
import contextlib
import glob
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core import make_solver
from repro.fleet.faults import DeltaFaults
from repro.utils import obs

ROOT = pathlib.Path(__file__).resolve().parents[1]
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_METADATA = re.compile(r", metadata=\{[^}]*\}")


def _instructions(hlo_text):
    """The HLO text without op metadata and the source locations after the
    computations (the scopes' wrappers add stack frames)."""
    return _METADATA.sub("", hlo_text.split("\nFileNames\n", 1)[0])


#: (solver, arguments, problem fixture, the scopes its round must carry)
PATHS = {
    "plain": ("fsvrg", {}, "tiny_problem",
              {"fl.client_pass", "fl.aggregate"}),
    "with_state": ("cocoa", {}, "tiny_problem",
                   {"fl.client_pass", "fl.aggregate"}),
    # per-client state moves (frozen, chunked, written back) under fl.state
    "with_state_partial": ("cocoa", {"participation": 0.5}, "tiny_problem",
                           {"fl.sample", "fl.client_pass", "fl.state",
                            "fl.aggregate"}),
    "with_state_streamed": ("cocoa", {"client_chunk": 2}, "tiny_problem",
                            {"fl.client_pass", "fl.state", "fl.aggregate"}),
    "with_state_cohort": ("cocoa", {"participation": 0.5, "cohort": 2,
                                    "client_chunk": 2}, "tiny_problem",
                          {"fl.sample", "fl.gather", "fl.client_pass",
                           "fl.state", "fl.aggregate"}),
    "streamed": ("fedavg", {"client_chunk": 2}, "tiny_problem",
                 {"fl.client_pass", "fl.aggregate"}),
    "cohort": ("fedavg", {"participation": 0.5, "cohort": 2},
               "tiny_problem",
               {"fl.sample", "fl.gather", "fl.client_pass", "fl.aggregate"}),
    "cohort_robust": ("fsvrg", {"participation": 0.5, "cohort": 2,
                                "aggregator_guard": "trimmed_mean"},
                      "tiny_problem",
                      {"fl.sample", "fl.gather", "fl.client_pass",
                       "fl.guard", "fl.aggregate"}),
    "virtual": ("fedavg", {"virtual_data": True, "client_chunk": 4},
                "small_virtual_problem", {"fl.client_pass", "fl.aggregate"}),
    "faulted": ("fedavg", {"fault_model": DeltaFaults(seed=1, sign_rate=0.3),
                           "aggregator_guard": "clip"},
                "tiny_problem",
                {"fl.client_pass", "fl.fault", "fl.guard", "fl.aggregate"}),
}


def _compiled_round(name, kwargs, problem):
    solver = make_solver(name, problem, **kwargs)
    lowered = solver.lower_round(solver.init(), jax.random.PRNGKey(0))
    module = lowered.as_text(dialect="hlo").split("\n", 1)[0].split()[1]
    return module.rstrip(","), lowered.compile().as_text()


def _scopes(hlo_text):
    return {s for op in _OP_NAME.findall(hlo_text)
            for s in re.findall(r"fl\.\w+", op)}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_round_paths_carry_their_scopes_and_nothing_else(
        path, request, monkeypatch):
    name, kwargs, fixture, want = PATHS[path]
    problem = request.getfixturevalue(fixture)
    module, hlo = _compiled_round(name, kwargs, problem)
    assert module == "jit__body"
    assert _scopes(hlo) == want

    # the same round with every scope of the program turned off: the same
    # module and instructions, only the op_name metadata differs
    monkeypatch.setattr(jax, "named_scope",
                        lambda _: contextlib.nullcontext())
    bare_module, bare = _compiled_round(name, kwargs, problem)
    assert bare_module == module
    assert not _scopes(bare)
    assert _instructions(bare) == _instructions(hlo)


def test_compile_counter_moves_on_a_compile_only():
    x = jnp.arange(5.0)
    f = jax.jit(lambda v: v * 3.0 + 1.0)
    before = obs.counters()
    f(x).block_until_ready()
    after = obs.counters()
    assert after["compiles"] == before["compiles"] + 1
    assert after["compile_s"] > before["compile_s"]
    f(x).block_until_ready()
    assert obs.counters() == after


def test_compile_seconds_count_nested_intervals_once():
    c = obs._Compiles()
    # an inner jit's trace reported inside its caller's, then the caller's
    # trace, its lowering and its build
    c.on_span(obs.TRACE_EVENT, 2.0, 3.0)
    c.on_span(obs.TRACE_EVENT, 4.0, 5.0)
    c.on_span(obs.TRACE_EVENT, 1.0, 6.0)
    c.on_span(obs.LOWER_EVENT, 6.0, 7.0)
    c.on_span(obs.COMPILE_EVENT, 8.0, 10.0)
    c.on_span("/jax/other", 0.0, 100.0)
    c.on_event(obs.CACHE_MISS)
    assert c.snapshot() == {"compiles": 1, "compile_s": 8.0,
                            "cache_hits": 0, "cache_misses": 1}


def _host_events(trace_dir):
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    data = jax.profiler.ProfileData.from_serialized_xspace(
        pathlib.Path(path).read_bytes())
    return [(e.start_ns, e.end_ns, e.name, e)
            for p in data.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]


def test_fit_under_the_profiler_writes_round_and_phase_spans(
        tiny_problem, tmp_path):
    solver = make_solver("fsvrg", tiny_problem)
    flat = tiny_problem.flat
    with jax.profiler.trace(str(tmp_path)):
        solver.fit(2, eval_fn=lambda w: {"f": flat.loss(w)},
                   callback=lambda st, r: None)
    events = _host_events(tmp_path)
    rounds = sorted((e for e in events if e[2] == "fl.round"),
                    key=lambda e: e[0])
    assert [dict(e[3].stats)["step_num"] for e in rounds] == [0, 1]
    for s, e, _, _ in rounds:
        inside = {n for a, b, n, _ in events
                  if n.startswith("fl.") and s < a and b <= e}
        assert inside == {"fl.prelude", "fl.dispatch", "fl.check_finite",
                          "fl.eval", "fl.callback"}


def test_no_program_span_takes_a_benchmark_span_name():
    sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))
    try:
        import trace_reduce
    finally:
        sys.path.pop(0)
    names = {"fl.round"} | {
        n for f in (ROOT / "src" / "repro").rglob("*.py")
        for n in re.findall(r'obs\.span\("([^"]+)"\)', f.read_text())}
    assert {"fl.prelude", "fl.dispatch", "fl.check_finite", "fl.eval",
            "fl.callback", "fl.checkpoint", "fl.scan"} <= names
    assert all(n.startswith("fl.") for n in names)
    assert not names & (set(trace_reduce.SPANS) | set(trace_reduce.SPAN_ORDER))
