"""What the chip benchmark's CPU tests share: where things are, the cells,
and the §4 problem's shape at a size a test run holds."""
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
CELLS = tuple(w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"])
#: the cohort mix on both configurations, which the tests run at their
#: size (``tiny_root``) whether or not BENCHMARK.json has the cell
COHORTS = ("fedavg-gplus.cohort10", "fsvrg-gplus.cohort10")
#: the cells, and the cohort mixes that are not cells
MIXES = CELLS + tuple(c for c in COHORTS if c not in CELLS)

#: 60 clients, 200 features, 12 nonzeros a row
TINY_PROBLEM = {"num_clients": 60, "num_features": 200, "num_examples": 6000,
                "min_client_examples": 30, "max_client_examples": 400,
                "nnz_per_example": 12}
