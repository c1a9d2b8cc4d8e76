"""BENCHMARK.json's shape, discovery by name, and the harness's refusals."""
import json
import re
import shutil
import subprocess
import sys

import pytest

import catalog
import run
from bench_cases import CHIP, ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "benchmarks/chip/run.py"]
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert (ROOT / p).is_dir() and ".." not in p
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_lines_use_only_the_allowed_characters():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["why"])
        assert LINE.match(c["source"]) and c["reduced"] == []
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and LINE.match(w["why"])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and LINE.match(m["layer"])
        assert set(m["workloads"]) <= cells


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_name_leads_to_its_files(name):
    cell = catalog.Cell(BENCH, name, ROOT)
    assert cell.config["name"] == cell.workload["config"]
    assert cell.reference().deltas and cell.work().round_work
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
    assert set(cell.limits) == {"loss_gap", "update_norm_gap",
                                "change_norm_gap", "iterate_gap"}


def test_every_cell_has_limits_and_every_reference_its_hooks():
    """Each cell's limits are a file of their own; each solver's reference
    module has the four functions ``reference/common.py`` calls, and a
    weighting it knows."""
    for w in BENCH["workloads"]:
        assert (CHIP / "limits" / f"{w['name']}.json").is_file(), w["name"]
    stems = set()
    for path in sorted((CHIP / "reference").glob("*.py")):
        if path.stem == "common":
            continue
        stems.add(path.stem)
        mod = catalog.load_module(path, "reference")
        for fn in ("prepare", "prelude", "deltas", "server_diag"):
            assert callable(getattr(mod, fn)), (path.stem, fn)
        assert getattr(mod, "WEIGHTING", "nk") in ("nk", "sum")
    assert {"fsvrg", "fedavg", "cocoa"} <= stems
    assert callable(catalog.load_module(
        CHIP / "reference" / "cocoa.py", "reference").init_state)


def test_a_new_mix_metric_and_cell_are_found_by_adding_files(tmp_path):
    """A later cell brings its traffic, limits and metric reader as new
    files and entries; no file that is there changes."""
    here = tmp_path / "chip"
    shutil.copytree(CHIP, here, ignore=shutil.ignore_patterns("out"))
    (here / "traffic" / "cohort1.json").write_text(json.dumps(
        {"solver_kwargs": {"participation": 0.01, "cohort": 10000},
         "eval": "last_round"}))
    (here / "limits" / "fedavg-gplus.cohort1.json").write_text(
        (here / "limits" / "fedavg-gplus.full.json").read_text())
    (here / "metrics" / "rounds_done.py").write_text(
        "def read(ctx):\n    return ctx['window']['rounds']\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "fedavg-gplus.cohort1",
                               "config": "fedavg-gplus", "traffic": "cohort1",
                               "chips": 1, "why": "p = 0.01"})
    bench["per_layer"].append({"name": "rounds_done", "unit": "rounds",
                               "better": "higher", "source": "host_clock",
                               "layer": "host loop", "moves": "round_s",
                               "workloads": ["fedavg-gplus.cohort1"]})
    cell = catalog.Cell(bench, "fedavg-gplus.cohort1", ROOT, here=here)
    assert cell.solver_kwargs()["participation"] == 0.01
    assert [m["name"] for m in cell.per_layer] == ["rounds_done"]
    assert cell.reader("rounds_done").read({"window": {"rounds": 7}}) == 7
    assert set(cell.limits) == set(catalog.Cell(BENCH, "fedavg-gplus.full",
                                                ROOT).limits)


def test_an_unknown_workload_is_refused(capsys):
    assert run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"],
                    ) == 2
    assert capsys.readouterr().out == ""


def test_no_tpu_means_no_result(capsys):
    """On a machine whose JAX finds no TPU the run exits nonzero and
    prints no metric."""
    code = run.main(["--workload", "fsvrg-gplus.full", "--seed", "1",
                     "--seconds", "1"])
    assert code == 3
    assert capsys.readouterr().out == ""


def test_a_checkout_of_only_the_benchmark_gives_no_result(tmp_path):
    """With only BENCHMARK.json and the benchmark's own files, the program
    is missing: the run exits nonzero and prints nothing."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("out", "__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "fsvrg-gplus.full", "--seed", "3", "--seconds", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""
