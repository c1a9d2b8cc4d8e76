"""The chip benchmark's CPU tests: small sizes, no chip.

``benchmarks/chip`` goes on the import path, as ``run.py`` puts it there.
"""
import json
import sys

import pytest

from bench_cases import CHIP, COHORTS, ROOT, TINY_PROBLEM

if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A root whose BENCHMARK.json is the repository's, with every
    configuration's problem cut to ``TINY_PROBLEM``."""
    root = tmp_path_factory.mktemp("tiny_root")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # the cohort mix on both configurations, for the tests of the
    # reference and the harness, where BENCHMARK.json has no such cell
    names = {w["name"] for w in bench["workloads"]}
    for name in COHORTS:
        if name not in names:
            bench["workloads"].append({"name": name,
                                       "config": name.split(".")[0],
                                       "traffic": "cohort10", "chips": 1,
                                       "why": "tests only"})
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["problem"].update(TINY_PROBLEM)
        c["file"] = f"{c['name']}.json"
        (root / c["file"]).write_text(json.dumps(cfg))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="session")
def tiny_cell(tiny_root):
    import catalog

    bench = catalog.load(tiny_root)
    return lambda name: catalog.Cell(bench, name, tiny_root)
