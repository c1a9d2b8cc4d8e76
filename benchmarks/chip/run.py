"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

A cell is a configuration (``configs/``: a solver and its arguments on the
§4 problem) under a traffic mix (``traffic/``: participation and the eval
cadence), both named in ``BENCHMARK.json``.  A run:

1. set-up, timed as ``setup_s``: makes the §4 rows from ``--seed`` with
   the benchmark's own generator (``generate_s``), builds the problem with
   the program's ``build_problem`` (``build_s``), makes the solver with
   ``make_solver`` and lowers its round for the module's name
   (``solver_s``), and drives the solver through its first
   ``CHECKED_ROUNDS`` rounds with ``solver.fit`` — the window's own call,
   whose first round loads the round program from the cache or compiles
   it (``warm_s``, of which ``first_round_s``);
2. the window: ``solver.fit`` from that state for as many whole rounds as
   fill ``--seconds`` at the last warm round's pace, back to back; with
   ``--trace 1`` the same window under the profiler, at least two rounds
   or the configuration's ``traced_rounds``;
3. reads the device's peak memory; with ``--trace 1`` then compiles the
   round from the cache for its HLO text and reads the trace with it
   (``program_trace``: the program's ``fl.*`` spans and scopes); frees the
   program's state, and runs the plain reference (``reference/``) over the
   checked rounds from the same rows and keys; ``correct`` holds when
   every number compared is within its limit (``limits/``).

Every run reads the program's compile counters (``repro.utils.obs``) at
the window's start and end.

The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed`` (rounds), ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``, each
read by ``metrics/<name>.py``), ``device``, with ``--trace 1``
``breakdown`` and ``program`` (the spans' and scopes' readings a round),
and last ``checks``: each number compared with its limit.
The run exits nonzero without that line when JAX finds no TPU or fewer
chips than the cell asks for, or when the checkout lacks the program.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import catalog  # noqa: E402
import compare  # noqa: E402

#: rounds from w = 0 that set-up runs and the reference checks
CHECKED_ROUNDS = 2
#: fewest rounds in a traced window, unless the configuration's file sets
#: ``traced_rounds``: the profiler keeps about 4.2M device events a trace
#: (2**22), and one FedAvg round at the §4 widths makes 3.7M
TRACED_ROUNDS = 2
#: seeds are taken modulo 2**32: JAX keys hold 32 bits of a seed
SEED_MOD = 2 ** 32


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str, code: int) -> int:
    print(f"benchmarks/chip/run.py: {msg}", file=sys.stderr)
    return code


def use_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    the environment names one; every program is kept, however fast it
    compiled, so a second run of a cell loads all of them."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Clock:
    """Set-up phases on the host clock."""

    def __init__(self, start: float):
        self.parts: dict = {}
        self._t = start

    def lap(self, name: str) -> None:
        now = time.perf_counter()
        self.parts[name] = now - self._t
        self._t = now


def dataset(cell, seed: int):
    """The cell's train rows from ``seed``: (spec, idx, val, y, client_of).
    The configuration may fix the client sizes (``sizes_seed``) and the
    features of every row (``pattern_seed``); the rest comes from
    ``seed``."""
    import generator
    problem = cell.config["problem"]
    spec = generator.draw_spec(problem, seed, cell.config["sizes_seed"],
                               cell.config.get("pattern_seed"))
    idx, val, y, client_of = generator.rows(spec, spec.train_sizes)
    return spec, idx, val, y, client_of


def build(spec, idx, val, y, client_of):
    """The program's problem over these rows (no test split: the cells
    score the train objective)."""
    import numpy as np
    from repro.core import build_problem
    from repro.data.synthetic import FederatedDataset
    none_i = np.zeros((0, idx.shape[1]), np.int32)
    ds = FederatedDataset(
        idx=idx, val=val, y=y, client_of=client_of,
        client_sizes=spec.train_sizes.astype(np.int32),
        num_features=spec.num_features, test_idx=none_i,
        test_val=none_i.astype(np.float32), test_y=np.zeros(0, np.float32),
        test_client_of=np.zeros(0, np.int32))
    return build_problem(ds)


def module_name(lowered) -> str:
    """The HLO module name of a lowered program, as the trace shows it."""
    first = lowered.as_text(dialect="hlo").split("\n", 1)[0]
    return first.split()[1].rstrip(",")


class Spans:
    """Host spans around the calls into each layer: ``round`` runs from one
    callback to the next, ``eval_f`` wraps the eval of f, ``callback`` the
    callback.  They land in the profiler's trace when it runs."""

    def __init__(self):
        import jax
        self._jax = jax
        self._round = None

    def open_round(self):
        self._round = self._jax.profiler.TraceAnnotation("round")
        self._round.__enter__()

    def close_round(self):
        if self._round is not None:
            self._round.__exit__(None, None, None)
            self._round = None

    def span(self, name):
        return self._jax.profiler.TraceAnnotation(name)


def prepare(cell, seed: int, clock: Clock, break_round=None):
    """Set-up, the same for a run and for ``calibrate.py``: the rows, the
    program's problem and solver, the round's module name, and the first
    ``CHECKED_ROUNDS`` rounds from w = 0 through ``solver.fit`` — the
    window's own call on the solver the window then drives.  Laps each
    phase on ``clock``.

    ``break_round(solver)``, for the tests only, breaks the timed path
    underneath the harness after the solver is made."""
    import types

    import jax
    import numpy as np

    from repro.core import make_solver

    spec, *rows = dataset(cell, seed)
    clock.lap("generate_s")
    prob = build(spec, *rows)
    jax.block_until_ready(prob.buckets[-1].idx)
    clock.lap("build_s")

    solver = make_solver(cell.solver, prob, **cell.solver_kwargs())
    if break_round is not None:
        break_round(solver)
    lowered = solver.lower_round(solver.init(), jax.random.fold_in(
        jax.random.PRNGKey(seed), 0))
    round_module = module_name(lowered)
    del lowered
    clock.lap("solver_s")

    spans = Spans()
    flat = prob.flat

    def eval_f(w):
        with spans.span("eval_f"):
            return {"f": flat.loss(w)}

    checked, stamps = [], [time.perf_counter()]

    def keep(st, r):
        checked.append(np.asarray(st.w, np.float32))
        stamps.append(time.perf_counter())

    res = solver.fit(CHECKED_ROUNDS, seed=seed, eval_fn=eval_f,
                     callback=keep)
    clock.lap("warm_s")
    # the first round loads the round program from the cache, or compiles it
    clock.parts["first_round_s"] = stamps[1] - stamps[0]
    return types.SimpleNamespace(
        spec=spec, rows=rows, prob=prob, solver=solver, res=res,
        round_module=round_module, spans=spans, eval_f=eval_f,
        checked=checked, f_checked=[h["f"] for h in res.history],
        pace=stamps[-1] - stamps[-2])


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             out_dir: pathlib.Path, break_round=None, keep=None) -> dict:
    """Everything after the device check: returns the result record.

    ``break_round(solver)``, for the tests only, breaks the timed path
    underneath the harness after the solver is made.  ``keep``, a
    directory, keeps a traced run's trace and the round's HLO text there,
    gzipped."""
    import jax
    import numpy as np

    from repro.utils import obs

    clock = Clock(T_START)
    s = prepare(cell, seed, clock, break_round)
    spec, rows, spans = s.spec, s.rows, s.spans
    rounds = max(1, math.ceil(seconds / s.pace))
    if trace:
        rounds = cell.config.get("traced_rounds", max(TRACED_ROUNDS, rounds))
    first = CHECKED_ROUNDS
    last_only = cell.traffic["eval"] == "last_round"
    eval_every = (first + rounds) if last_only else 1

    def tick(st, r):
        with spans.span("callback"):
            spans.close_round()
            spans.open_round()

    trace_dir = out_dir / "trace"
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    at_start = obs.counters()
    setup_s = time.perf_counter() - T_START
    t0 = time.perf_counter()
    spans.open_round()
    res = s.solver.fit(first + rounds, seed=seed, state=s.res.state,
                       eval_fn=s.eval_f, eval_every=eval_every,
                       callback=tick)
    spans.close_round()
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    at_end = obs.counters()
    finite = bool(np.isfinite(np.asarray(res.w)).all())

    dev = jax.devices()[0]
    memory = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(memory.get("peak_bytes_in_use", 0))}

    ctx = {
        "cell": cell, "setup": {"setup_s": setup_s, **clock.parts},
        "window": {"seconds": window_s, "rounds": rounds},
        "memory": memory, "round_module": s.round_module, "trace": None,
        "program": None, "counters": {"start": at_start, "end": at_end},
    }
    if trace:
        import peaks
        ctx["program"] = read_program(trace_dir, s.solver, seed,
                                      s.round_module,
                                      None if keep is None
                                      else pathlib.Path(keep) / cell.name)
        ctx["trace"] = ctx["program"].reduced
        shutil.rmtree(out_dir, ignore_errors=True)
        cut = ctx["trace"].cut()
        if cut:
            raise RuntimeError(f"the profiler's trace was cut: {cut}")
        ctx["peaks"] = peaks.lookup(dev.device_kind)
        ctx["shapes"] = {"n": int(spec.train_sizes.sum()),
                         "d": spec.num_features, "entries": spec.nnz + 2,
                         "clients": spec.num_clients}
        ctx["traced_rounds"] = traced_rounds(cell, spec, seed, first, rounds)
    checked, f_checked = s.checked, s.f_checked
    # the program's state goes before the reference runs on the chip
    del s, res
    gc.collect()

    t_check = time.perf_counter()
    data, flat = reference_inputs(spec, rows)
    ref, f_ref = reference(cell, data, flat, seed)
    checks = {k: {"value": v, "limit": cell.limits[k]} for k, v in
              compare.numbers(checked, f_checked, ref, f_ref).items()}
    check_s = time.perf_counter() - t_check
    ok = finite and compare.holds(checks)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    record = {"correct": ok, "attempted": rounds,
              "failed": 0 if finite else rounds, "metrics": metrics,
              "device": device, "setup": ctx["setup"], "check_s": check_s,
              # 0 where every program came from set-up or the cache
              "compiles_in_window": at_end["compiles"] - at_start["compiles"]}
    if trace:
        t = ctx["trace"]
        record["device"].update(busy_s=t.busy_s, window_s=t.window_s)
        record["trace_events"] = t.events
        record["breakdown"] = t.breakdown()
        record["program"] = ctx["program"].readings(rounds)
    record["checks"] = checks
    return record


def read_program(trace_dir, solver, seed: int, round_module: str,
                 keep=None):
    """The window's trace with the program's spans and scopes
    (``program_trace.Program``): the round is compiled again for its HLO
    text, which loads it from the cache, after the window and the memory
    reading.  ``keep``, a path stem, keeps the trace and the HLO text as
    ``<stem>.xplane.pb.gz`` and ``<stem>.hlo.txt.gz``."""
    import gzip

    import jax

    import program_trace
    hlo = solver.lower_round(solver.init(), jax.random.fold_in(
        jax.random.PRNGKey(seed), 0)).compile().as_text()
    files = sorted(trace_dir.glob("**/*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    raw = files[-1].read_bytes()
    if keep is not None:
        keep.parent.mkdir(parents=True, exist_ok=True)
        for suffix, data in ((".xplane.pb.gz", raw),
                             (".hlo.txt.gz", hlo.encode())):
            with gzip.open(keep.with_name(keep.name + suffix), "wb") as f:
                f.write(data)
    return program_trace.from_xspace(
        jax.profiler.ProfileData.from_serialized_xspace(raw), hlo,
        round_module)


def traced_rounds(cell, spec, seed, first, rounds):
    """Who took part in each traced round, drawn by the benchmark's own
    copy of the round's schedule, and whether the round evaluated f: what
    the work counts (work/) need besides the problem's shapes."""
    from reference import common
    layout = common.groups(spec.train_sizes)
    p = float(cell.solver_kwargs().get("participation", 1.0))
    every = cell.traffic["eval"] != "last_round"
    out = []
    for i, r in enumerate(range(first, first + rounds)):
        sizes = []
        for g in layout:
            kb, _ = common.client_keys(seed, r, g.offset, len(g.members))
            part = (common.takes_part(kb, len(g.members), p) if p < 1.0
                    else slice(None))
            sizes.append(spec.train_sizes[g.members][part])
        out.append({"participant_sizes": sizes,
                    "evaluates": every or i == rounds - 1})
    return out


def reference_inputs(spec, rows):
    """The reference's view of the rows, and its objective over them."""
    from reference import common
    data = common.Data(*rows, spec.train_sizes, spec.num_features)
    return data, common.Flat(data)


def reference(cell, data, flat, seed: int, **kw):
    """The plain reference's ``CHECKED_ROUNDS`` rounds from w = 0 over the
    same rows and keys, and f after each; ``kw`` computes it in another
    precision or with a fault (``common.run_rounds``)."""
    from reference import common
    p = float(cell.solver_kwargs().get("participation", 1.0))
    ws = common.run_rounds(data, cell.reference(),
                           cell.config["solver_kwargs"], p, seed,
                           CHECKED_ROUNDS, **kw)
    return ws, [flat.loss(w) for w in ws]


def main(argv=None, *, root: pathlib.Path = ROOT) -> int:
    args = parse(argv)
    if not (root / "src" / "repro").is_dir():
        return fail("the program (src/repro) is not in this checkout", 2)
    try:
        cell = catalog.Cell(catalog.load(root), args.workload, root)
    except catalog.CatalogError as e:
        return fail(str(e), 2)
    sys.path.insert(0, str(root / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        return fail(f"needs {cell.chips} TPU chip(s); JAX found "
                    f"{len(devices)} {devices[0].platform} device(s)", 3)
    use_compile_cache(root)
    seed = args.seed % SEED_MOD
    out_dir = HERE / "out" / f"{cell.name}.{seed}.{os.getpid()}"
    try:
        record = run_cell(cell, seed, args.seconds, bool(args.trace),
                          out_dir=out_dir)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    for name, c in record["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
