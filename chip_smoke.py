"""Run the paper's Fig. 2 solvers on one TPU chip at the §4 published widths.

    python chip_smoke.py [--seed 0]

Generates the §4 problem from ``--seed`` (K = 10,000 clients, d = 20,002
features, n ≈ 2.17M examples, n_k in [75, 9,000], 60 nonzeros per example;
``configs/gplus_logreg.py``), then runs FSVRG, FedAvg, GD, DANE and CoCoA+
through the user's entry point, ``make_solver(name, problem).fit(...)``:
Trainer → RoundEngine → Pallas kernels.  Each solver prints one JSON line:

  * ``f0`` and ``f`` — the objective at w₀ = 0 (log 2) and after each round;
    for CoCoA+ also ``dual``, its dual objective D(α) from α = 0 on;
  * ``tpu_custom_calls`` — Pallas kernel call sites in the compiled round;
  * ``kernel_vs_reference`` — the same round from the same state with the
    kernels off (``use_kernel=False`` / ``aggregator="dense"``; for GD, the
    flat-view step ``w − h∇f(w)``), on the same chip;
  * ``smoke_timing`` — compile and round seconds of this one run: a smoke
    reading, not a benchmark metric.

The last line is ``{"ok": true, "device": {...}}``, printed only when every
solver passed: finite iterates; progress on the objective the method
guarantees — f below f(w₀) after the last round for the primal methods,
and for CoCoA+ a dual that rises every round (its primal can rise above
log 2 in early rounds; at K = 10,000 it does); kernel call sites wherever
the configuration selects a kernel; and kernel and reference rounds within
``RTOL`` of each other in the max norm.  The
script exits nonzero, without that line, on any failure and on any device
other than a TPU: there is no CPU fallback.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ROUNDS = 2
#: kernel vs reference round: max|Δw| <= RTOL · max|w_ref| (the tests' rtol)
RTOL = 1e-5

#: (solver, config with kernels, config of the reference round or None for
#: the flat-view GD step, whether the configuration selects a kernel)
SOLVERS = (
    ("fsvrg", {"aggregator": "pallas"}, {"aggregator": "dense"}, True),
    ("fedavg", {"client_chunk": 512, "aggregator": "pallas"},
     {"client_chunk": 512, "aggregator": "dense", "use_kernel": False}, True),
    # the registry's h = 2.0 exceeds 2/L of the §4 objective (L ≈ 1.13 by
    # power iteration on its rows at full d), where a GD step raises f
    ("gd", {"stepsize": 1.0}, None, False),
    ("dane", {}, {"use_kernel": False}, True),
    ("cocoa", {}, {"use_kernel": False}, True),
)


def count_custom_calls(lowered) -> int:
    """Pallas TPU kernel call sites in a lowered program."""
    return lowered.as_text().count("@tpu_custom_call")


def smoke_solver(prob, name, kw, ref_kw, selects_kernel, seed):
    """Fit ``name`` for ROUNDS rounds and check it; returns the JSON
    record, whose ``ok`` says whether every check passed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import make_solver
    from repro.core.baselines import gd_round

    solver = make_solver(name, prob, **kw)
    state0 = solver.init()
    base = jax.random.PRNGKey(seed)
    lowered = solver.lower_round(state0, jax.random.fold_in(base, 0))
    custom_calls = count_custom_calls(lowered)
    t = time.perf_counter()
    lowered.compile()         # lands in the compile cache the first round reads
    compile_s = time.perf_counter() - t

    f0 = float(prob.flat.loss(state0.w))
    dual_fn = getattr(solver, "dual_objective", None)
    dual = [] if dual_fn is None else [float(dual_fn(state0))]
    # a round's time runs from the end of the previous callback to the
    # start of its own: the round and the eval of f (both synchronous)
    starts, ends = [time.perf_counter()], []
    kept = {}

    def callback(state, r):
        ends.append(time.perf_counter())
        if dual_fn is not None:
            dual.append(float(dual_fn(state)))
        if r == ROUNDS - 2:
            # the state the last round starts from, copied before that
            # round donates its buffers
            kept["state"] = jax.tree.map(jnp.array, state)
        starts.append(time.perf_counter())

    res = solver.fit(ROUNDS, seed=seed, callback=callback,
                     eval_fn=lambda w: {"f": prob.flat.loss(w)})
    f = [h["f"] for h in res.history]
    w = np.asarray(res.w)

    # the last round again from the same state, kernels off
    last = jax.random.fold_in(base, ROUNDS - 1)
    start = kept["state"]
    if ref_kw is None:
        w_ref = gd_round(prob, start.w, solver.stepsize)
    else:
        w_ref = make_solver(name, prob, **ref_kw).round(start, last).w
    w_ref = np.asarray(w_ref)
    max_dw = float(np.abs(w - w_ref).max())
    max_w = float(np.abs(w_ref).max())

    checks = {"finite": bool(np.isfinite(w).all() and np.isfinite(f).all())}
    if dual_fn is None:
        checks["f_below_f0"] = f[-1] < f0
    else:
        checks["dual_rises"] = all(b > a for a, b in zip(dual, dual[1:]))
    checks.update({
        "kernel_called": custom_calls > 0 or not selects_kernel,
        "matches_reference": max_dw <= RTOL * max_w,
    })
    return {
        "solver": name, "config": kw, "K": prob.num_clients, "d": prob.d,
        "f0": f0, "f": f, **({"dual": dual} if dual else {}),
        "tpu_custom_calls": custom_calls,
        "kernel_vs_reference": {"reference": ref_kw or "w - h*grad f(w)",
                                "max_abs_dw": max_dw, "max_abs_w": max_w,
                                "rtol": RTOL},
        "smoke_timing": {"compile_s": compile_s,
                         "round_s": [e - s for s, e in zip(starts, ends)],
                         "note": "one smoke run; not a benchmark metric"},
        "checks": checks,
        "ok": all(checks.values()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.utils.compile_cache import use_compile_cache

    cache = use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1

    from repro.configs import get_logreg_config
    from repro.core import build_problem
    from repro.data.synthetic import generate

    cfg = get_logreg_config()
    t = time.perf_counter()
    prob = build_problem(generate(cfg, seed=args.seed))
    print(json.dumps({"problem": cfg.name, "K": prob.num_clients,
                      "d": prob.d, "n": prob.flat.n,
                      "buckets": [[b.num_clients, b.m_pad]
                                  for b in prob.buckets],
                      "setup_s": time.perf_counter() - t,
                      "compile_cache": cache}), flush=True)

    ok = True
    for name, kw, ref_kw, selects_kernel in SOLVERS:
        rec = smoke_solver(prob, name, kw, ref_kw, selects_kernel, args.seed)
        print(json.dumps(rec), flush=True)
        ok = ok and rec["ok"]
    if not ok:
        print("chip_smoke: a solver failed its checks (see its line)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
