"""Quickstart: federated optimization in 40 lines.

Generates a non-IID, unbalanced, sparse federated dataset (the paper's §4
setting, scaled down), runs FSVRG (Algorithm 4) for 10 rounds of
communication through the shared Trainer driver, and compares against
distributed gradient descent — both constructed by name from the solver
registry.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.configs import get_logreg_config
from repro.core import build_problem, build_test_problem, make_solver
from repro.data.synthetic import generate
from repro.utils.compile_cache import use_compile_cache

use_compile_cache()

# 1. a federated dataset: K clients, power-law sizes, per-client skew
cfg = get_logreg_config().scaled(0.005)
ds = generate(cfg, seed=0)
print(f"K={ds.num_clients} clients, n={ds.num_examples} examples, "
      f"d={ds.num_features} features, n_k in "
      f"[{ds.client_sizes.min()}, {ds.client_sizes.max()}]")

# 2. the optimization problem (eq. 8): f(w) = sum_k (n_k/n) F_k(w)
prob = build_problem(ds)          # lambda = 1/n, the paper's choice
test = build_test_problem(ds)


def evaluate(w):
    return {"f": prob.flat.loss(w), "err": test.error_rate(w)}


# 3. Federated SVRG — one communication round per iteration.  Any solver in
#    the registry works the same way: make_solver(name, prob).fit(rounds).
res = make_solver("fsvrg", prob, stepsize=1.0).fit(10, seed=0,
                                                   eval_fn=evaluate)
for r, p in enumerate(res.history):
    print(f"round {r+1:2d}: objective={p['f']:.5f} test_error={p['err']:.4f}")

# 4. baseline: distributed GD at the same communication budget
res_gd = make_solver("gd", prob, stepsize=2.0).fit(10, seed=0,
                                                   eval_fn=evaluate)
print(f"\nFSVRG objective {res.history[-1]['f']:.5f} vs "
      f"GD {res_gd.history[-1]['f']:.5f} at 10 rounds each")
