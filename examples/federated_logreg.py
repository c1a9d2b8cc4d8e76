"""The paper's §4 experiment end-to-end, with every compared method and the
four FSVRG-modification ablations (§3.6.2).

Every run is a row in a data-driven table: the solver comes from the
registry (``make_solver(name, prob, **overrides)``), the round loop from
the shared Trainer (``solver.fit``) — no per-algorithm loops.

    PYTHONPATH=src python examples/federated_logreg.py --scale 0.01 --rounds 30
"""
import argparse

from repro.configs import get_logreg_config
from repro.core import build_problem, build_test_problem, make_solver
from repro.core.baselines import majority_baseline_error
from repro.data.synthetic import generate


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.005)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--stepsize", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_logreg_config().scaled(args.scale)
    ds = generate(cfg, seed=args.seed)
    prob = build_problem(ds)
    te = build_test_problem(ds)
    print(f"K={ds.num_clients} n={ds.num_examples} d={ds.num_features}")

    # §4.1 naive prediction properties
    err_const = min(float((te.y == 1).mean()), float((te.y == -1).mean()))
    err_maj = majority_baseline_error(ds.y, ds.client_of, ds.test_y, ds.test_client_of)
    print(f"predict-constant err={err_const:.4f}  per-author-majority err={err_maj:.4f}")

    h = args.stepsize
    runs = (
        ("FSVRG (Algorithm 4, all mods)", "fsvrg", {"stepsize": h}),
        ("  − S_k gradient scaling", "fsvrg", {"stepsize": h, "use_S": False}),
        ("  − A aggregation scaling", "fsvrg", {"stepsize": h, "use_A": False}),
        ("  − local stepsize h/n_k", "fsvrg",
         {"stepsize": h, "use_local_stepsize": False}),
        ("  − n_k/n weighted aggregation", "fsvrg",
         {"stepsize": h, "use_weighted_agg": False}),
        ("naive FSVRG (Algorithm 3)", "svrg_naive",
         {"stepsize": h / 100, "naive_steps": 50}),
        ("GD", "gd", {"stepsize": 2.0}),
        ("FedAvg (registry defaults)", "fedavg", {}),
        ("DANE (registry defaults)", "dane", {}),
        ("CoCoA+ (sigma=K)", "cocoa", {}),
    )

    def evaluate(w):
        return {"f": prob.flat.loss(w), "err": te.error_rate(w)}

    for label, name, overrides in runs:
        res = make_solver(name, prob, **overrides).fit(
            args.rounds, seed=args.seed, eval_fn=evaluate)
        p = res.history[-1]
        print(f"{label:34s} f={p['f']:.5f} err={p['err']:.4f}")


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
