"""What the program records of its own time: host spans, device scopes and
compile counters.

* :func:`span` and :func:`round_span` are host spans
  (``jax.profiler.TraceAnnotation`` / ``StepTraceAnnotation``): a check of
  one flag each while no profiler session runs, an event on the calling
  thread's line of the trace while one does.
* :func:`scope` is ``jax.named_scope`` (:func:`scoped` its decorator):
  the ops traced inside it carry the name in their HLO ``op_name``
  metadata (``jit(_body)/fl.aggregate/...``).  It changes no instruction
  and no module name.
* :func:`counters` reads the compile counters, kept by ``jax.monitoring``
  listeners registered once, at import.

Every name starts with ``fl.``.
"""
from __future__ import annotations

import functools
import threading

import jax

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
#: one per executable built: a backend compile, or a load from the
#: persistent cache (the cache's read is timed inside this event)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


def span(name: str):
    """A host span named ``name`` around the calls made inside it."""
    return jax.profiler.TraceAnnotation(name)


def round_span(r: int):
    """The host span of round ``r``: ``fl.round`` with ``step_num`` r."""
    return jax.profiler.StepTraceAnnotation("fl.round", step_num=int(r))


def scope(name: str):
    """A device scope: ops traced inside it carry ``name`` in their
    ``op_name``."""
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: the function's ops traced under :func:`scope` ``name``,
    a fresh scope per call."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


class _Compiles:
    """Compile counters.  ``compile_s`` is the union of the intervals of
    tracing, lowering and building executables: a jit traced inside
    another's trace, or an eager op compiled while tracing, is counted
    once."""

    def __init__(self):
        self._lock = threading.Lock()
        self.compiles = self.cache_hits = self.cache_misses = 0
        self._merged = []       # disjoint (start, end), in order of end
        self._total = 0.0       # their summed length

    def on_span(self, event, start, end, **_):
        if event not in (TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT):
            return
        with self._lock:
            if event == COMPILE_EVENT:
                self.compiles += 1
            # listeners fire at each interval's end, so an interval that
            # holds earlier ones (an outer trace) swallows them here
            merged = self._merged
            while merged and merged[-1][1] >= start:
                s, e = merged.pop()
                self._total -= e - s
                start, end = min(s, start), max(e, end)
            merged.append((start, end))
            self._total += end - start

    def on_event(self, event, **_):
        if event in (CACHE_HIT, CACHE_MISS):
            with self._lock:
                if event == CACHE_HIT:
                    self.cache_hits += 1
                else:
                    self.cache_misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles, "compile_s": self._total,
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses}


_COMPILES = _Compiles()
jax.monitoring.register_event_time_span_listener(_COMPILES.on_span)
jax.monitoring.register_event_listener(_COMPILES.on_event)


def counters() -> dict:
    """The compile counters since import: ``compiles`` (executables built
    or loaded from the persistent cache), ``compile_s`` (seconds spent
    tracing, lowering and building them), ``cache_hits`` and
    ``cache_misses`` (of the persistent cache)."""
    return _COMPILES.snapshot()
