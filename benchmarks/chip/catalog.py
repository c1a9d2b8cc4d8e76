"""Find everything a cell needs by the names in ``BENCHMARK.json``.

Each configuration, traffic mix, metric reader, work count, plain
reference and comparison limit is a file of its own, found by name under
this directory:

    configs/<config>.json        the file BENCHMARK.json names for it
    traffic/<traffic>.json       solver arguments and eval cadence
    metrics/<metric>.py          ``read(ctx) -> number or None``; ``ctx``
                                 holds the window, the set-up's laps, the
                                 program's counters, and with ``--trace 1``
                                 the trace and the program's spans and
                                 scopes in it (``run.run_cell``)
    reference/<solver>.py        the solver's plain reference pass; it may
                                 carry per-client state (``init_state``)
                                 and declare ``WEIGHTING = "sum"``
                                 (``reference/common.py``)
    work/<solver>.py             the round's required work
    work/<solver>.<kernel>.py    a kernel's required work in that round
    limits/<workload>.json       the limit of each number compared

A later cell, mix or metric is added by adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parent


class CatalogError(ValueError):
    """A name that BENCHMARK.json uses has no file, or a file is malformed."""


def load_module(path: pathlib.Path, tag: str):
    """Import the file ``path`` as a module of its own."""
    if not path.is_file():
        raise CatalogError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchchip_{tag}_{re.sub(r'[^A-Za-z0-9_]', '_', path.stem)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise CatalogError(f"no file {path}")
    return json.loads(path.read_text())


class Cell:
    """One workload of BENCHMARK.json with the files its names lead to."""

    def __init__(self, bench: dict, name: str, root: pathlib.Path,
                 here: pathlib.Path = HERE):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise CatalogError(f"no workload {name!r}; BENCHMARK.json has "
                               f"{sorted(cells)}")
        self.here = here
        self.workload = cells[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        entry = configs.get(self.workload["config"])
        if entry is None:
            raise CatalogError(f"workload {name!r} names config "
                               f"{self.workload['config']!r}, which "
                               "BENCHMARK.json does not list")
        self.config = _json(root / entry["file"])
        self.traffic = _json(here / "traffic" / f"{self.workload['traffic']}.json")
        self.chips = int(self.workload["chips"])
        self.end_to_end = [m for m in bench["end_to_end"] if self._has(m)]
        self.per_layer = [m for m in bench["per_layer"] if self._has(m)]

    @property
    def limits(self) -> dict:
        return _json(self.here / "limits" / f"{self.name}.json")

    def _has(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    @property
    def solver(self) -> str:
        return self.config["solver"]

    def solver_kwargs(self) -> dict:
        return {**self.config["solver_kwargs"],
                **self.traffic["solver_kwargs"]}

    def reader(self, metric: str):
        return load_module(self.here / "metrics" / f"{metric}.py", "metric")

    def reference(self):
        return load_module(self.here / "reference" / f"{self.solver}.py",
                           "reference")

    def work(self, kernel: str | None = None):
        """The solver's round work, or a kernel's work in its round (None
        where the round does not call that kernel)."""
        stem = self.solver if kernel is None else f"{self.solver}.{kernel}"
        path = self.here / "work" / f"{stem}.py"
        if kernel is not None and not path.is_file():
            return None
        return load_module(path, "work")


def load(root: pathlib.Path) -> dict:
    return _json(root / "BENCHMARK.json")
