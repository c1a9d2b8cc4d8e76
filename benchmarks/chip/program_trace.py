"""The program's own spans and scopes in a traced window.

    python3 benchmarks/chip/program_trace.py --workload <name> --seed <n> \
        [--tiny] [--out DIR]

The program names its phases (``repro.utils.obs``): host spans on the
thread that drives the rounds (``fl.round`` per round; inside it
``fl.prelude``, ``fl.dispatch``, ``fl.check_finite``, ``fl.eval``,
``fl.callback``), and device scopes in the HLO ``op_name`` of the round's
instructions (``fl.sample``, ``fl.gather``, ``fl.client_pass``,
``fl.fault``, ``fl.guard``, ``fl.aggregate``).  This module reads them
from a profiler trace of the window and the round's compiled HLO text:

* :func:`scopes` maps each instruction of the HLO text to the innermost
  ``fl.*`` scope of its ``op_name`` (None where it has none);
* :meth:`Program.scope_s` is the device time in which the innermost
  running operation of the round's module carries a scope: self time on
  the nested ``XLA Ops`` line, clipped to the window;
* :meth:`Program.span_modules_s` is the device time of the module
  executions dispatched under a host span (the innermost ``fl.*`` span
  around the dispatch);
* :meth:`Program.readings` turns them into the per-layer metrics a round
  that the readers under ``metrics/`` return.

A module execution is joined to its dispatch through the trace's flows.
Its ``XLA Modules`` event consumes a flow (``_c``) that the runtime's
``DoEnqueueProgram`` event produces (``_p``, with the same ``run_id``).
From an event, the next hop is the nearest event on its line, itself or
one around it, that consumes a flow, and on to the event that produced
it: ``DoEnqueueProgram`` → ``tpu::System::Execute=>IssueSequencedEvent``
→ ``tpu::System::Execute`` → ``PJRT_LoadedExecutable_Execute`` → the
``PJRT_LoadedExecutable_Execute linkage`` event on the line that holds the
spans, the Python thread.  That event's start is the dispatch; the
enqueue itself may run later, on a runtime thread.

Run as a script, it makes one ``run.py --trace 1`` run of a cell
(``run.run_cell``) and prints its readings a round (``Program.readings``)
and the idle gaps of its breakdown, as one JSON line.  ``--tiny`` cuts the
problem to a test size; ``--out`` keeps the trace and the round's HLO
text, gzipped: the test fixtures under ``tests/bench/data``.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import gzip
import json
import pathlib
import re
import shutil
import sys

HERE = pathlib.Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import trace_reduce  # noqa: E402

#: the program's span and scope names start with this
PREFIX = "fl."
_SCOPE = re.compile(r"(?<![\w.])fl\.\w+")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NAME = re.compile(r"%[\w.\-]+")
#: an instruction's shape and opcode: ``= f32[8]{0} fusion(``
_WHAT = re.compile(r"=\s*(.*?)\s([\w\-]+)\(")
_CALLS = re.compile(r"(?:calls|body|condition|to_apply|branch_computations|"
                    r"true_computation|false_computation)=(\{[^}]*\}|\S+)")
#: most hops from a module execution back to the spans' line
MAX_HOPS = 8
#: the span that dispatched no module: outside every fl.* span
OUTSIDE = "outside spans"
#: the problem size of the chip benchmark's CPU tests (bench_cases.py)
TINY_PROBLEM = {"num_clients": 60, "num_features": 200, "num_examples": 6000,
                "min_client_examples": 30, "max_client_examples": 400,
                "nnz_per_example": 12}


def _instructions(hlo_text: str):
    """(name, computation, the instruction's text) of each instruction,
    names as the trace gives them (``%while.3``)."""
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and line[:1].isspace():
            name = m.group(1)
            yield (name if name.startswith("%") else "%" + name), comp, line
        elif line.rstrip().endswith("{"):
            head = line.split()
            comp = head[1] if head[0] == "ENTRY" else head[0]


def _scope(line: str):
    op = _OP_NAME.search(line)
    found = _SCOPE.findall(op.group(1)) if op else []
    return found[-1] if found else None


def scopes(hlo_text: str) -> dict:
    """{instruction: the innermost ``fl.*`` scope of its ``op_name``, or
    None}."""
    return {name: _scope(line) for name, _, line in _instructions(hlo_text)}


def inherited(hlo_text: str) -> dict:
    """:func:`scopes`, where each instruction without a scope takes its
    operands' (the first that has one), else that of the instruction that
    calls its computation.  The compiler's own instructions carry no
    ``op_name`` (a scatter rewritten to a fusion, a copy): this names the
    work they came from, so that the scopes' metrics
    (:meth:`Program.readings`) partition the round's module."""
    scope, comp_of, operands, calls = {}, {}, {}, collections.defaultdict(list)
    for name, comp, line in _instructions(hlo_text):
        scope[name], comp_of[name] = _scope(line), comp
        m = _WHAT.search(line)
        operands[name] = _NAME.findall(line[m.end():].split(")")[0]) if m \
            else []
        for group in _CALLS.findall(line.split("metadata=")[0]):
            for callee in _NAME.findall(group):
                calls[callee].append(name)
    changed = True
    while changed:      # each pass names at least one more, or stops
        changed = False
        for name, own in scope.items():
            if own is None:
                found = next((scope[o] for o in operands[name]
                              if scope.get(o)), None) or next(
                    (scope[c] for c in calls.get(comp_of[name], ())
                     if scope[c]), None)
                if found:
                    scope[name], changed = found, True
    return scope


def self_times(ops, label) -> collections.Counter:
    """Time per ``label(name)`` of the innermost running operation, over
    (start, end, name) events that nest (a loop's body inside the loop):
    at each instant, the last started operation still running."""
    out = collections.Counter()
    stack, t = [], None
    for s, e, n in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            end, key = stack.pop()
            if end > t:
                out[key] += end - t
                t = end
        if stack and s > t:
            out[stack[-1][1]] += s - t
        t = s if t is None else max(t, s)
        stack.append((e, label(n)))
    while stack:
        end, key = stack.pop()
        if end > t:
            out[key] += end - t
            t = end
    return out


def _innermost(spans, t):
    """The name of the innermost span of ``spans`` (start, end, name)
    around ``t``, or None."""
    best = None
    for s, e, n in spans:
        if s <= t <= e and (best is None or s >= best[0]):
            best = (s, n)
    return None if best is None else best[1]


class _Line:
    """A host line's events, sorted, with each one's enclosing event."""

    def __init__(self, events):
        # events: (start, end, name, stats)
        self.events = sorted(events, key=lambda ev: (ev[0], -ev[1]))
        self.parent = []
        stack = []
        for i, (s, e, _, _) in enumerate(self.events):
            while stack and self.events[stack[-1]][1] < e:
                stack.pop()
            self.parent.append(stack[-1] if stack else None)
            stack.append(i)

    def enclosing(self, i):
        """Event ``i``, then the events around it, innermost first."""
        while i is not None:
            yield i
            i = self.parent[i]


class Program:
    """The program's spans and the device time they and its scopes hold,
    over the window of a :class:`trace_reduce.Reduced`."""

    def __init__(self, reduced, spans, modules, hlo_text, round_module,
                 unjoined=0):
        self.reduced = reduced
        self.spans = spans            # [(start, end, name)] fl.* host spans
        # per device: [(start, end, module name, dispatching span)], the
        # span None where the join found no dispatch
        self.modules = modules
        self.hlo_text = hlo_text      # the round's compiled HLO
        self.scope_of = scopes(hlo_text)
        self.inherited = inherited(hlo_text)
        self.round_module = round_module
        self.unjoined = unjoined      # module executions in the window
        self._ops = None
        self._readings = {}

    def _devices(self):
        return len(self.reduced.devices) or 1

    def op_table(self) -> dict:
        """{instruction: device seconds in which it is the innermost
        running operation} inside the round's module executions."""
        if self._ops is None:
            t = self.reduced
            total = collections.Counter()
            for d, mods in zip(t.devices, self.modules):
                ops = sorted(t._clip(d["ops"]))
                starts = [o[0] for o in ops]
                for s, e, n in t._clip([m[:3] for m in mods]):
                    if trace_reduce._ID.sub("", n) != self.round_module:
                        continue
                    total.update(self_times(
                        [(max(a, s), min(b, e), name) for a, b, name in
                         ops[bisect.bisect_left(starts, s):
                             bisect.bisect_left(starts, e)]],
                        lambda n: n))
            self._ops = {k: v * 1e-9 / self._devices()
                         for k, v in total.items()}
        return self._ops

    def scope_table(self, inherit: bool = False) -> dict:
        """{scope: device seconds} of :meth:`op_table`, by each
        instruction's scope (:func:`scopes`, or :func:`inherited`): ``None``
        for an instruction without one, ``"unknown"`` for one the HLO text
        lacks."""
        scope_of = self.inherited if inherit else self.scope_of
        out = collections.Counter()
        for n, v in self.op_table().items():
            out[scope_of.get(n, "unknown")] += v
        return dict(out)

    def unscoped_ops(self, top: int = trace_reduce.TOP):
        """The instructions without a scope that hold most of the round's
        time: [instruction, seconds, its opcode and shape, the scope it
        inherits (:func:`inherited`)]."""
        what = {}
        for n, _, line in _instructions(self.hlo_text):
            m = _WHAT.search(line)
            what[n] = f"{m.group(2)} {m.group(1)}" if m else ""
        rows = sorted(((v, n) for n, v in self.op_table().items()
                       if self.scope_of.get(n, "unknown") is None),
                      reverse=True)[:top]
        return [[n, v, what[n], self.inherited.get(n)] for v, n in rows]

    def scope_s(self, scope: str, inherit: bool = False) -> float:
        """Device seconds in which the innermost running operation of the
        round's module carries ``scope`` (or, with ``inherit``, carries or
        inherits it: :func:`inherited`)."""
        return self.scope_table(inherit).get(scope, 0.0)

    def span_table(self) -> dict:
        """{span: device seconds} of the module executions in the window,
        by the innermost ``fl.*`` span around their dispatch; ``OUTSIDE``
        where no such span was open, None where no dispatch was found."""
        total = collections.Counter()
        for mods in self.modules:
            for s, e, _, span in mods:
                s, e = max(s, self.reduced.lo), min(e, self.reduced.hi)
                if e > s:
                    total[span] += e - s
        return {k: v * 1e-9 / self._devices() for k, v in total.items()}

    def span_modules_s(self, span: str) -> float:
        """Device seconds of module executions dispatched under ``span``."""
        return self.span_table().get(span, 0.0)

    def readings(self, rounds: int) -> dict:
        """Per round, in ms: the metrics the program's spans and scopes
        give (the readers under ``metrics/`` return them), and the sums
        that check them against trace_reduce's.  A scope's metric counts
        the unscoped instructions that inherit it (:func:`inherited`), so
        the scopes leave no part of the round's module out; ``scopes_ms``
        keeps each scope's own."""
        if rounds not in self._readings:
            self._readings[rounds] = self._read(rounds)
        return self._readings[rounds]

    def _read(self, rounds: int) -> dict:
        ms = 1e3 / rounds
        t = self.reduced
        scoped = self.scope_table()
        by_flow = self.scope_table(inherit=True)
        spans = self.span_table()
        program = t.module_s(self.round_module)
        eager = t.other_modules_s(self.round_module)
        phases = ("fl.prelude", "fl.eval", "fl.check_finite")
        return {
            "client_pass_ms": by_flow.get("fl.client_pass", 0.0) * ms,
            "aggregate_ms": by_flow.get("fl.aggregate", 0.0) * ms,
            "gather_ms": (by_flow.get("fl.sample", 0.0)
                          + by_flow.get("fl.gather", 0.0)) * ms,
            "full_grad_ms": self.span_modules_s("fl.prelude") * ms,
            "eval_ms": self.span_modules_s("fl.eval") * ms,
            "check_ms": self.span_modules_s("fl.check_finite") * ms,
            "round_program_ms": program * ms,
            "eager_ms": eager * ms,
            "scopes_ms": {str(k): v * ms for k, v in sorted(
                scoped.items(), key=lambda kv: -kv[1])},
            "scopes_inherited_ms": {str(k): v * ms for k, v in sorted(
                by_flow.items(), key=lambda kv: -kv[1])},
            "unscoped_ops": [[n, v * ms, what, kin] for n, v, what, kin in
                             self.unscoped_ops()],
            "spans_ms": {str(k): v * ms for k, v in sorted(
                spans.items(), key=lambda kv: -kv[1])},
            # operations of the round's module over the module's time
            "scopes_over_round_program": (sum(scoped.values()) / program
                                          if program else None),
            "unscoped_share": (scoped.get(None, 0.0) / program
                               if program else None),
            # the prelude, the eval of f and the check over eager_ms
            "phases_over_eager": (sum(spans.get(p, 0.0) for p in phases)
                                  / eager if eager else None),
            "unjoined": self.unjoined,
        }


def _stats(e) -> dict:
    return {k: v for k, v in e.stats}


def from_xspace(data, hlo_text: str, round_module: str) -> Program:
    """The program's spans and scopes in a profiler trace (``ProfileData``)
    of the window, with the round's compiled HLO text."""
    reduced = trace_reduce.from_xspace(data)
    lines, producer = [], {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            ln = _Line([(e.start_ns, e.end_ns, e.name, _stats(e))
                        for e in line.events])
            for i, ev in enumerate(ln.events):
                if "_p" in ev[3]:
                    producer[ev[3]["_p"]] = (len(lines), i)
            lines.append(ln)
    # the spans' line: the one that holds the round spans
    home = next((k for k, ln in enumerate(lines) if any(
        ev[2] in trace_reduce.SPANS or ev[2].startswith(PREFIX)
        for ev in ln.events)), None)
    spans = [] if home is None else [
        (s, e, n) for s, e, n, _ in lines[home].events
        if n.startswith(PREFIX)]

    def dispatch(flow):
        """The time on the spans' line of the call that led to ``flow``."""
        for _ in range(MAX_HOPS):
            if flow not in producer:
                return None
            k, i = producer[flow]
            if k == home:
                return lines[k].events[i][0]
            flow = next((lines[k].events[j][3]["_c"]
                         for j in lines[k].enclosing(i)
                         if "_c" in lines[k].events[j][3]), None)
        return None

    modules, unjoined = [], 0
    for plane in data.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        mods = []
        for line in plane.lines:
            if line.name != trace_reduce.MODULES_LINE:
                continue
            for e in line.events:
                t = dispatch(_stats(e).get("_c"))
                span = None if t is None else (_innermost(spans, t)
                                               or OUTSIDE)
                if (span is None and e.end_ns > reduced.lo
                        and e.start_ns < reduced.hi):
                    unjoined += 1
                mods.append((e.start_ns, e.end_ns, e.name, span))
        modules.append(mods)
    return Program(reduced, spans, modules, hlo_text, round_module, unjoined)


def read(trace_path, hlo_path, round_module: str) -> Program:
    """A :class:`Program` from a (gzipped) ``.xplane.pb`` and HLO text."""
    import jax

    def raw(p):
        p = pathlib.Path(p)
        return (gzip.open(p) if p.suffix == ".gz" else open(p, "rb")).read()

    return from_xspace(jax.profiler.ProfileData.from_serialized_xspace(
        raw(trace_path)), raw(hlo_path).decode(), round_module)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--tiny", action="store_true",
                    help="cut the problem to a test size")
    ap.add_argument("--out", type=pathlib.Path,
                    help="keep the trace and the HLO text here, gzipped")
    args = ap.parse_args(argv)

    import catalog
    import run
    root = HERE.parents[1]
    sys.path.insert(0, str(root / "src"))
    import jax
    cell = catalog.Cell(catalog.load(root), args.workload, root)
    if args.tiny:
        cell.config["problem"].update(TINY_PROBLEM)
    if jax.devices()[0].platform != "tpu":
        print("program_trace.py: needs a TPU", file=sys.stderr)
        return 3
    run.use_compile_cache(root)
    seed = args.seed % run.SEED_MOD
    out_dir = HERE / "out" / f"program.{cell.name}.{seed}"
    try:
        rec = run.run_cell(cell, seed, 0.0, True, out_dir=out_dir,
                           keep=args.out)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    print(json.dumps({"workload": cell.name, "seed": seed,
                      "tiny": args.tiny, "correct": rec["correct"],
                      "rounds": rec["attempted"], **rec["program"],
                      "idle_gaps": rec["breakdown"]["idle_gaps"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
