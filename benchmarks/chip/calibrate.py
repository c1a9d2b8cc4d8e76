"""The readings that set each limit in ``limits/``, taken on the chip.

    python3 benchmarks/chip/calibrate.py --workload NAME --seeds S1 S2 ... \
        [--broken N]

In one process (every seed runs the same program shapes, so the round
compiles once), for each seed:

* ``program``: a run's own set-up (``run.prepare``: the cell's rows, the
  program's problem and solver, and ``fit`` through the checked rounds)
  against the plain float32 reference: the sound readings, whose largest
  is a number's lower reading;

and for the first ``--broken`` seeds, each against the float32 reference:

* ``control``: the reference computed in bfloat16, the precision below
  the configuration's float32, put in the program's place;
* ``half_cohort``: the reference with every other participating client
  left out and the weighted mean taken over the rest (the plain sum, for
  a module that declares ``WEIGHTING = "sum"``).

and, with no run, ``unchanged``: rounds that leave the iterate at w = 0,
which read 1 on ``update_norm_gap``, ``change_norm_gap`` and
``iterate_gap`` by their definition.  Prints one JSON line per seed and
reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402


def readings(cell, seed: int, broken: bool) -> list:
    import time

    import jax.numpy as jnp
    import numpy as np

    import compare

    s = run.prepare(cell, seed, run.Clock(time.perf_counter()))
    spec, rows, ws, fs = s.spec, s.rows, s.checked, s.f_checked
    del s
    gc.collect()

    data, flat = run.reference_inputs(spec, rows)
    ref, f_ref = run.reference(cell, data, flat, seed)
    out = [{"seed": seed, "reading": "program",
            **compare.numbers(ws, fs, ref, f_ref)}]
    zero = np.zeros_like(ref[0])
    out.append({"seed": seed, "reading": "unchanged", **compare.numbers(
        [zero] * len(ref), [flat.loss(jnp.asarray(zero))] * len(ref), ref,
        f_ref)})
    if broken:
        for name, kw in (("control", {"dtype": jnp.bfloat16}),
                         ("half_cohort", {"fault": "half_cohort"})):
            other, f_other = run.reference(cell, data, flat, seed, **kw)
            out.append({"seed": seed, "reading": name,
                        **compare.numbers(other, f_other, ref, f_ref)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--broken", type=int, default=3)
    args = ap.parse_args(argv)
    import catalog
    cell = catalog.Cell(catalog.load(run.ROOT), args.workload, run.ROOT)
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 3
    run.use_compile_cache(run.ROOT)
    for i, seed in enumerate(args.seeds):
        for rec in readings(cell, seed % run.SEED_MOD, i < args.broken):
            print(json.dumps({"workload": cell.name, **rec}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
