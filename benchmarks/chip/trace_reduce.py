"""Reduce a profiler trace of the window to what the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
planes are those named ``/device:TPU:<i>``; on each, the ``XLA Modules``
line holds one event per program execution (named ``<module>(<id>)``) and
the ``XLA Ops`` line one event per operation, named by its HLO text
(``%<name> = <shape> <opcode>(...)``), a loop's body ops nested inside the
loop's event.  A Pallas kernel is a ``custom-call`` named after the jitted
function that calls ``pallas_call`` (``%fused_aggregate.7``).  Host planes
hold the benchmark's own spans (``round``, ``eval_f``, ``callback``) and
the program's (``fl.*``, ``repro.utils.obs``), on the same clock.

The window runs from the start of the first ``round`` span to the end of
the last.  Busy time is the union of the operation intervals inside it,
averaged over the device planes; every other reading is clipped to the
window too.

The profiler keeps the first few million device events of a trace and
drops the rest without a word; a cut trace reads idle time where the
device was busy.  :meth:`Reduced.cut` says why a trace looks cut, and a
run reads no metric from such a trace.
"""
from __future__ import annotations

import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPANS = ("round", "eval_f", "callback")
#: the program's own spans start with this
PROGRAM_PREFIX = "fl."
#: which of the benchmark's spans names an idle gap, innermost first; the
#: innermost program span around the gap comes before them
SPAN_ORDER = ("eval_f", "callback", "round")
TOP = 10
#: a module execution this long (ns) has to be covered by its operations
LONG_MODULE_NS = 1_000_000
_ID = re.compile(r"\(\d+\)$")
_SUFFIX = re.compile(r"\.\d+$")


def _union(intervals):
    """Total length of the union of (start, end) intervals."""
    total, end = 0.0, None
    start = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                total += end - start
            start, end = s, e
        elif e > end:
            end = e
    if end is not None:
        total += end - start
    return total


def _gaps(intervals, lo, hi):
    """The (start, end) stretches of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _outermost(events):
    """The events that no earlier event on the line contains."""
    out, end = [], None
    for ev in sorted(events):
        if end is None or ev[1] > end:
            out.append(ev)
            end = ev[1] if end is None else max(end, ev[1])
    return out


class Reduced:
    """A traced window: device operations and modules per device, and the
    host's spans, in nanoseconds on the trace's clock."""

    def __init__(self, devices, spans, events: int = 0):
        # devices: list of {"ops": [(start, end, name)],
        #                   "kernels": [(start, end, name)],
        #                   "modules": [(start, end, name)]}; op names are
        # the HLO instruction names, kernels the custom calls among them
        self.devices = devices
        self.spans = spans          # [(start, end, name)], both kinds
        self.events = events        # device events the trace holds
        rounds = [(s, e) for s, e, n in spans if n == "round"]
        if rounds and devices:
            self.lo = min(s for s, _ in rounds)
            self.hi = max(e for _, e in rounds)
        else:
            self.lo = self.hi = 0.0

    def _clip(self, events):
        return [(max(s, self.lo), min(e, self.hi), n) for s, e, n in events
                if e > self.lo and s < self.hi]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return 1e-9 * sum(
            _union([(s, e) for s, e, _ in self._clip(d["ops"])])
            for d in self.devices) / len(self.devices)

    def _module_total(self, keep) -> float:
        if not self.devices:
            return 0.0
        return 1e-9 * sum(
            sum(e - s for s, e, n in self._clip(d["modules"])
                if keep(_ID.sub("", n)))
            for d in self.devices) / len(self.devices)

    def module_s(self, name: str) -> float:
        """Device seconds in executions of module ``name``."""
        return self._module_total(lambda n: n == name)

    def other_modules_s(self, name: str) -> float:
        """Device seconds in executions of every module but ``name``."""
        return self._module_total(lambda n: n != name)

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of the custom calls named ``%<kernel>`` or
        ``%<kernel>.<n>``."""
        if not self.devices:
            return 0.0
        name = "%" + kernel
        return 1e-9 * sum(
            sum(e - s for s, e, n in self._clip(d["kernels"])
                if _SUFFIX.sub("", n) == name)
            for d in self.devices) / len(self.devices)

    def cut(self) -> str:
        """Why the trace looks cut, or "" where it looks whole.  The
        profiler keeps a trace's first events, so a cut trace goes quiet
        before its end: a module execution of a millisecond or more inside
        the window is less than half covered by operations; or a round
        span, but the last (the tail after the last callback), holds no
        module execution's start; or the device's operations end before
        the second half of the last such round (a round ends with the
        finiteness check and the eval of f on the device)."""
        rounds = sorted((s, e) for s, e, n in self.spans if n == "round")
        for d in self.devices:
            ops = [(s, e) for s, e, _ in d["ops"]]
            for s, e, n in self._clip(d["modules"]):
                if e - s < LONG_MODULE_NS:
                    continue
                cover = _union([(max(a, s), min(b, e)) for a, b in ops
                                if b > s and a < e])
                if cover < 0.5 * (e - s):
                    return (f"{n} runs {(e - s) * 1e-9:.3f} s with "
                            f"operations for {cover * 1e-9:.3f} s of it")
            starts = [s for s, _, _ in d["modules"]]
            for s, e in rounds[:-1]:
                if not any(s <= t < e for t in starts):
                    return (f"the round span at {(s - self.lo) * 1e-9:.3f} s "
                            "of the window holds no module execution")
            if len(rounds) > 1:
                s, e = rounds[-2]
                last = max((b for _, b, _ in d["ops"] if b <= e), default=s)
                if last < (s + e) / 2:
                    return (f"the device's operations end "
                            f"{(e - last) * 1e-9:.3f} s before the end of "
                            "the last round")
        return ""

    def breakdown(self) -> dict:
        """The outermost device operations that took most time (a loop
        counts with its body), and the longest idle gaps, each named by the
        innermost host span around it."""
        by_op = collections.Counter()
        gaps = []
        for d in self.devices:
            ops = self._clip(d["ops"])
            for s, e, n in _outermost(ops):
                by_op[n] += (e - s) * 1e-9 / len(self.devices)
            gaps.extend((e - s, (s + e) / 2) for s, e in
                        _gaps([(s, e) for s, e, _ in ops], self.lo, self.hi))
        gaps.sort(reverse=True)
        return {"device_ops": [[n, t] for n, t in by_op.most_common(TOP)],
                "idle_gaps": [[self._label(mid), g * 1e-9]
                              for g, mid in gaps[:TOP]]}

    def _label(self, t) -> str:
        """The innermost program span (``fl.*``) around ``t``, else the
        first of the benchmark's spans there in ``SPAN_ORDER``."""
        program = [(s, n) for s, e, n in self.spans
                   if s <= t <= e and n.startswith(PROGRAM_PREFIX)]
        if program:
            return max(program)[1]
        live = {n for s, e, n in self.spans if s <= t <= e}
        for name in SPAN_ORDER:
            if name in live:
                return name
        return "outside spans"


def from_xspace(data) -> Reduced:
    devices, spans, events = [], [], 0
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            d = {"ops": [], "kernels": [], "modules": []}
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    d["modules"] = [(e.start_ns, e.end_ns, e.name)
                                    for e in line.events]
                elif line.name == OPS_LINE:
                    for e in line.events:
                        text = e.name
                        ev = (e.start_ns, e.end_ns, text.split(" = ", 1)[0])
                        d["ops"].append(ev)
                        if " custom-call(" in text:
                            d["kernels"].append(ev)
                else:
                    events += sum(1 for _ in line.events)
            events += len(d["modules"]) + len(d["ops"])
            devices.append(d)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.end_ns, e.name)
                             for e in line.events if e.name in SPANS
                             or e.name.startswith(PROGRAM_PREFIX))
    return Reduced(devices, spans, events)
