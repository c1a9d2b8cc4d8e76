"""Compile each cell's round program for a described TPU v5e, without one.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse.py [--workload NAME]...

For each cell of BENCHMARK.json (or those named), builds the program's
problem and solver over *shapes* — the §4 buckets the cell's sizes make,
as abstract arrays on one described v5e chip — lowers the round that
``solver.round`` dispatches, and compiles it with the TPU compiler.
Nothing runs and no row is made, so this says nothing of results or
times; it shows what the chip's compiler refuses (an unsupported op in a
kernel, a block shape, too much memory) before any chip time is spent,
and prints each program's memory analysis and Pallas call sites.

The program asks ``jax.default_backend()`` to pick its kernels; that
question is answered "tpu" here, in this script only, so the round takes
the path it takes on the chip.  The eager full gradient is not a compiled
program and is left out.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


class ShapeFlat:
    """The flat view's surface a solver reads while it is made, over
    shapes: n, λ, d, and feature counts (ones: no value reaches a shape)."""

    def __init__(self, n: int, d: int):
        import jax.numpy as jnp
        self.n, self.num_features, self.lam = n, d, 1.0 / n
        self._ones = jnp.ones((d,), jnp.float32)

    def feature_counts(self):
        return self._ones


def shape_problem(cell, chip):
    """The program's FederatedLogReg over abstract bucket rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import generator
    from reference import common
    from repro.core.problem import ClientBucket, FederatedLogReg, row_width

    spec = generator.draw_spec(cell.config["problem"], 0,
                               cell.config["sizes_seed"])
    sizes = spec.train_sizes
    width = row_width(spec.nnz + 2)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    buckets, weights = [], []
    n = int(sizes.sum())
    for g in common.groups(sizes):
        kb = len(g.members)
        buckets.append(ClientBucket(
            sds((kb, g.m_pad, width), jnp.int32),
            sds((kb, g.m_pad, width), jnp.float32),
            sds((kb, g.m_pad), jnp.float32), sds((kb,), jnp.int32)))
        weights.append(sizes[g.members] / n)
    return FederatedLogReg(
        flat=ShapeFlat(n, spec.num_features), buckets=buckets,
        client_weights=jnp.asarray(np.concatenate(weights).astype(np.float32)),
        num_clients=spec.num_clients)


def round_body(solver):
    """The jitted round behind ``solver.round`` (the compiled closure's
    ``_body``), and the prelude's outputs it takes."""
    fn = solver._round_fast
    cells = dict(zip(fn.__code__.co_freevars, fn.__closure__))
    return cells["_body"].cell_contents


def rehearse(cell, chip) -> dict:
    import jax
    import jax.numpy as jnp

    import repro.core.scaling as scaling
    from repro.core import make_solver

    prob = shape_problem(cell, chip)
    d = prob.d
    scaling.aggregation_diag = lambda problem: jnp.ones((d,), jnp.float32)
    solver = make_solver(cell.solver, prob, **cell.solver_kwargs())
    body = round_body(solver)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    w = sds((d,), jnp.float32)
    ctx = (sds((d,), jnp.float32),) if cell.solver == "fsvrg" else ()
    t = time.perf_counter()
    lowered = body.lower(w, ctx, sds((2,), jnp.uint32), sds((), jnp.int32),
                         tuple(prob.buckets))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    return {
        "workload": cell.name, "compile_s": time.perf_counter() - t,
        "pallas_sites": compiled.as_text().count("tpu_custom_call"),
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "buckets": [[b.num_clients, b.m_pad] for b in prob.buckets],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import catalog

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"
    bench = catalog.load(ROOT)
    names = args.workload or [w["name"] for w in bench["workloads"]]
    for name in names:
        print(json.dumps(rehearse(catalog.Cell(bench, name, ROOT), chip)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
