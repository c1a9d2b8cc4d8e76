"""Fig. 2 reproduction: rounds of communication vs objective / test error.

Compares (as in the paper): OPT (offline optimum), GD (best stepsize),
CoCoA+, DANE, FSVRG, FSVRGR (same algorithm, randomly reshuffled data), plus
the FedAvg/local-SGD and one-shot baselines.  Every round-based curve is a
row in the data-driven ``CURVES`` table: the solver comes from the registry
(``make_solver``), the round loop and key schedule from the shared
:class:`repro.core.Trainer` (all derived from ``--seed``), and the
retrospective stepsize sweep from :func:`repro.core.sweep` — no
per-algorithm hand-rolled loops.  Adding an algorithm to the comparison is
one table row.

Scale is controlled by --scale (default CI-friendly 0.005 ≈ 50 clients; the
paper's full setting is scale=1.0: K=10,000, n≈2.2M, d=20,002).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (get_dane_config,
                           get_fedavg_config, get_fsvrg_config,
                           get_gd_config, get_logreg_config)
from repro.core import (Trainer, build_problem, build_test_problem,
                        make_solver, sweep)
from repro.core.baselines import majority_baseline_error, one_shot_average
from repro.data.synthetic import generate


@dataclasses.dataclass(frozen=True)
class Curve:
    """One comparison curve: a registry solver + its retrospective sweep."""

    solver: str                                  # registry name
    sweep_param: Optional[str] = None            # hyperparam swept (None: none)
    sweep: Tuple[float, ...] = ()
    reshuffle: bool = False                      # FSVRGR: same algo, shuffled data


def _curves():
    return {
        "fsvrg": Curve("fsvrg", "stepsize", get_fsvrg_config().stepsize_sweep),
        "fsvrgr": Curve("fsvrg", "stepsize", get_fsvrg_config().stepsize_sweep,
                        reshuffle=True),
        "gd": Curve("gd", "stepsize", get_gd_config().stepsize_sweep),
        "dane": Curve("dane", "local_lr", get_dane_config().local_lr_sweep),
        "cocoa": Curve("cocoa"),
        "fedavg": Curve("fedavg", "stepsize", get_fedavg_config().stepsize_sweep),
    }


ALGOS = ("fsvrg", "fsvrgr", "gd", "dane", "cocoa", "fedavg", "oneshot")


def optimum(prob, iters=6000, lr=2.0):
    w = jnp.zeros(prob.d)
    g = jax.jit(prob.flat.grad)
    best, best_f = w, float(prob.flat.loss(w))
    for i in range(iters):
        w = w - lr * g(w)
        if i % 500 == 499:
            f = float(prob.flat.loss(w))
            if f < best_f:
                best, best_f = w, f
    return best


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.005)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0,
                    help="drives the data generator AND every curve's "
                         "per-round key schedule (via the Trainer)")
    ap.add_argument("--opt-iters", type=int, default=6000,
                    help="GD iterations for the offline OPT reference "
                         "(lower it for smoke runs)")
    ap.add_argument("--json", default=None)
    ap.add_argument("--algo", default="all", choices=("all",) + ALGOS,
                    help="run a single comparison curve instead of all of them")
    ap.add_argument("--participation-model", default="none",
                    choices=("none", "bernoulli", "trace"),
                    help="run every curve under partial participation: "
                         "'bernoulli' uses --participation as the i.i.d. "
                         "rate, 'trace' a repro.fleet diurnal availability/"
                         "straggler trace (seeded from --seed)")
    ap.add_argument("--participation", type=float, default=0.3,
                    help="client participation rate for "
                         "--participation-model=bernoulli")
    ap.add_argument("--fault-model", default=None,
                    help="inject deterministic delta corruptions into every "
                         "curve, e.g. 'nan=0.01,sign=0.05,start=3' (knobs: "
                         "nan/sign/scale/replay rates, scale-factor, window, "
                         "start/stop rounds, seed) — repro.fleet.DeltaFaults; "
                         "unguarded NaN-poisoned candidates diverge and lose "
                         "their sweeps, so pair with --aggregator-guard")
    ap.add_argument("--aggregator-guard", default="none",
                    choices=("none", "clip", "trimmed_mean", "median"),
                    help="robust-aggregation guard installed in every "
                         "curve's engine (trimmed_mean/median reject the "
                         "cocoa curve: order-stat guards don't compose with "
                         "its sum-weighted dual aggregation)")
    args = ap.parse_args(argv)

    def want(name):
        return args.algo in ("all", name)

    # extra solver kwargs shared by every curve (merged into make_solver)
    fleet_kw = {}
    if args.participation_model == "bernoulli":
        fleet_kw = {"participation": args.participation}
    elif args.participation_model == "trace":
        from repro.fleet import FleetTrace, TraceParticipation
        trace = FleetTrace(seed=args.seed)
        fleet_kw = {"participation": trace.max_rate(),
                    "participation_model": TraceParticipation(trace)}
    if args.fault_model:
        from repro.fleet import DeltaFaults
        fleet_kw["fault_model"] = DeltaFaults.from_spec(args.fault_model)
    if args.aggregator_guard != "none":
        fleet_kw["aggregator_guard"] = args.aggregator_guard

    cfg = get_logreg_config().scaled(args.scale)
    ds = generate(cfg, seed=args.seed)
    prob = build_problem(ds)
    te = build_test_problem(ds)
    print(f"# K={ds.num_clients} n={ds.num_examples} d={ds.num_features} "
          f"n_k in [{ds.client_sizes.min()},{ds.client_sizes.max()}]")

    w_star = optimum(prob, iters=args.opt_iters)
    f_star = float(prob.flat.loss(w_star))
    err_star = float(te.error_rate(w_star))

    # naive prediction properties (§4.1 analogues)
    err_const = min(float((te.y == 1).mean()), float((te.y == -1).mean()))
    err_majority = majority_baseline_error(ds.y, ds.client_of, ds.test_y,
                                           ds.test_client_of)
    print(f"# OPT f*={f_star:.5f} err*={err_star:.4f} | "
          f"const-pred err={err_const:.4f} | per-author-majority err={err_majority:.4f}")

    results = {"opt": {"f": f_star, "err": err_star},
               "const_err": err_const, "majority_err": err_majority,
               "config": dataclasses.asdict(cfg)}

    # FSVRGR's reshuffled problem (built lazily, derived from --seed too)
    prob_r = None

    def reshuffled():
        nonlocal prob_r
        if prob_r is None:
            rng = np.random.default_rng(args.seed)
            perm = rng.permutation(ds.num_examples)
            ds_r = dataclasses.replace(ds, idx=ds.idx[perm], val=ds.val[perm],
                                       y=ds.y[perm])
            prob_r = build_problem(ds_r)
        return prob_r

    # ---- every round-based curve: one registry-driven sweep ---- #
    for name, c in _curves().items():
        if not want(name):
            continue
        problem = reshuffled() if c.reshuffle else prob

        def eval_w(w, problem=problem):
            return {"f": problem.flat.loss(w), "err": te.error_rate(w)}

        t0 = time.time()
        if c.sweep_param is not None:
            res, best = sweep(
                lambda v: make_solver(c.solver, problem,
                                      **{c.sweep_param: v, **fleet_kw}),
                c.sweep, rounds=args.rounds, seed=args.seed, eval_fn=eval_w)
            if res is None:
                print(f"{name}: every candidate in {c.sweep} diverged")
                continue
            swept = {c.sweep_param: best}
        else:
            res = Trainer(make_solver(c.solver, problem, **fleet_kw),
                          rounds=args.rounds,
                          seed=args.seed, eval_fn=eval_w).fit()
            swept = {}
        hist = res.history
        results[name] = {
            "solver": c.solver, "swept": swept, "hist": hist,
            # JSON-friendly hyperparams of the (best) run
            "hyperparams": {
                k: v for k, v in res.solver.hyperparams.items()
                if isinstance(v, (int, float, str, bool, type(None)))}}
        tag = ",".join(f"{k}={v}" for k, v in swept.items()) or "defaults"
        print(f"{name:7s} ({tag}): " + " ".join(
            f"r{r+1}={p['f']:.4f}"
            for r, p in list(enumerate(hist))[::max(1, args.rounds // 6)])
            + f"  err={hist[-1]['err']:.4f}  [{time.time()-t0:.0f}s]")

    # ---- one-shot averaging (not round-based: single communication) ---- #
    if want("oneshot"):
        key_os = jax.random.fold_in(jax.random.PRNGKey(args.seed), 10_000)
        w_os = one_shot_average(prob, jnp.zeros(prob.d), key_os,
                                stepsize=0.5, epochs=20)
        results["oneshot"] = {"f": float(prob.flat.loss(w_os)),
                              "err": float(te.error_rate(w_os))}
        print(f"oneshot: f={results['oneshot']['f']:.4f} "
              f"err={results['oneshot']['err']:.4f}")

    # rounds-to-within-10%-of-optimal-gap table
    f0 = float(prob.flat.loss(jnp.zeros(prob.d)))
    target = f_star + 0.1 * (f0 - f_star)
    print("\nname,rounds_to_10pct_gap,final_f,final_err")
    for name in ("fsvrg", "fsvrgr", "gd", "dane", "cocoa", "fedavg"):
        if name not in results:
            continue
        hist_n = results[name]["hist"]
        rto = next((r + 1 for r, p in enumerate(hist_n) if p["f"] <= target), None)
        print(f"{name},{rto},{hist_n[-1]['f']:.5f},{hist_n[-1]['err']:.4f}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
