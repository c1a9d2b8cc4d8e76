"""Benchmark harness — one entry per paper table/figure + framework benches.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV rows plus the Fig.-2 convergence
summary.  Roofline terms come from the dry-run JSON (see
benchmarks/roofline.py; the dry-run itself needs the 512-device env and is
run separately).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, *args, reps=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6, out


def bench_fig2(quick: bool):
    """Paper Fig. 2 (the paper's only figure-experiment)."""
    from benchmarks import fig2_convergence

    scale = 0.003 if quick else 0.01
    rounds = 15 if quick else 30
    res = fig2_convergence.main(["--scale", str(scale), "--rounds", str(rounds),
                                 "--json", "/root/repo/fig2_results.json"])
    f_fsvrg = res["fsvrg"]["hist"][-1]["f"]
    f_gd = res["gd"]["hist"][-1]["f"]
    f_cocoa = res["cocoa"]["hist"][-1]["f"]
    print(f"fig2_fsvrg_final_f,{f_fsvrg:.6f},opt={res['opt']['f']:.6f}")
    print(f"fig2_ordering_ok,{int(f_fsvrg < f_gd <= f_cocoa * 1.5)},fsvrg<gd(<~cocoa)")


def bench_kernels():
    """Kernel microbenchmarks (interpret mode on CPU — relative only)."""
    from repro.kernels import ops, ref

    d = 20_002  # the paper's dimensionality
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    w, s, gn, go, gb = [jax.random.normal(k, (d,)) for k in ks]
    us, _ = _timeit(lambda: ops.fsvrg_update(w, s, gn, go, gb, 0.1))
    print(f"kernel_fsvrg_update_d{d},{us:.1f},interpret")
    us_ref, _ = _timeit(lambda: ref.fsvrg_update_ref(w, s, gn, go, gb, 0.1))
    print(f"ref_fsvrg_update_d{d},{us_ref:.1f},jnp")

    us, _ = _timeit(lambda: ops.fedavg_update(w, gn, 0.1, 1e-4))
    print(f"kernel_fedavg_update_d{d},{us:.1f},interpret")
    us_ref, _ = _timeit(lambda: ref.fedavg_update_ref(w, gn, 0.1, 1e-4))
    print(f"ref_fedavg_update_d{d},{us_ref:.1f},jnp")

    K = 64
    wks = jax.random.normal(ks[1], (K, d))
    wts = jnp.full((K,), 1.0 / K)
    a = jnp.ones((d,))
    us, _ = _timeit(lambda: ops.scaled_aggregate(w, wks, wts, a))
    print(f"kernel_scaled_aggregate_K{K}_d{d},{us:.1f},interpret")
    us_ref, _ = _timeit(lambda: ref.scaled_aggregate_ref(w, wks, wts, a))
    print(f"ref_scaled_aggregate_K{K}_d{d},{us_ref:.1f},jnp")


def bench_round_cost(quick: bool):
    """Wall-clock of one FSVRG round vs one GD round vs one CoCoA+ round —
    the T_A side of the paper's efficiency paradigm (eq. 3/4)."""
    from repro.configs import get_logreg_config
    from repro.core import FSVRG, FSVRGConfig, build_problem
    from repro.core.cocoa import CoCoAPlus
    from repro.data.synthetic import generate

    cfg = get_logreg_config().scaled(0.002 if quick else 0.005)
    ds = generate(cfg, seed=0)
    prob = build_problem(ds)
    w = jnp.zeros(prob.d)

    solver = FSVRG(prob, FSVRGConfig(stepsize=1.0))
    st = solver.init(w)
    us, _ = _timeit(lambda: solver.round(st, jax.random.PRNGKey(0)).w, reps=3)
    print(f"fsvrg_round_K{ds.num_clients},{us:.0f},1 communication")

    g = jax.jit(prob.flat.grad)
    us, _ = _timeit(lambda: g(w), reps=3)
    print(f"gd_round_K{ds.num_clients},{us:.0f},1 communication")

    cc = CoCoAPlus(prob)
    st_cc = cc.init()
    us, _ = _timeit(lambda: cc.round(st_cc, jax.random.PRNGKey(0)).w, reps=3)
    print(f"cocoa_round_K{ds.num_clients},{us:.0f},1 communication")


def bench_neural_round(quick: bool):
    """Federated LM round on the reduced llama config (framework bench)."""
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.core import neural
    from repro.models import build_model, make_batch

    cfg = get_config("llama3-8b").reduced()
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, InputShape("t", 64, 8, "train"), dtype=jnp.float32)
    cb = neural.make_client_batches(batch, num_clients=4, local_steps=2)
    rnd = jax.jit(neural.make_fsvrg_round(model, neural.FedNeuralConfig(stepsize=0.5,
                                                                        local_steps=2)))
    us, _ = _timeit(lambda: rnd(params, cb)[0], reps=2, warmup=1)
    print(f"neural_fsvrg_round_reduced_llama3,{us:.0f},4 clients x 2 steps")


def bench_properties_table():
    """§3.1 properties as a one-round gap-closure table."""
    import sys
    sys.path.insert(0, "tests")
    from test_properties import _dense_problem_from_clients, _random_clients
    from repro.core import FSVRG, FSVRGConfig

    rng = np.random.default_rng(0)

    def gap_closure(prob):
        w_star = jnp.zeros(prob.d)
        for _ in range(2000):
            w_star = w_star - 0.5 * prob.flat.grad(w_star)
        f_star = float(prob.flat.loss(w_star))
        f0 = float(prob.flat.loss(jnp.zeros(prob.d)))
        # best stepsize retrospectively (the paper's protocol)
        def one_round_f(h):
            solver = FSVRG(prob, FSVRGConfig(stepsize=h))
            st = solver.round(solver.init(), jax.random.PRNGKey(0))
            return float(prob.flat.loss(st.w))

        f1 = min(one_round_f(h) for h in (1.0, 3.0, 10.0))
        return (f0 - f1) / max(f0 - f_star, 1e-12)

    p_b = _dense_problem_from_clients(_random_clients(rng, 1, 256, 16, 8), 16, lam=0.05)
    print(f"propB_one_round_gap_closure,{gap_closure(p_b):.3f},target>0.8")
    clients = []
    for k in range(4):
        pool = np.arange(k * 8, (k + 1) * 8)
        clients += _random_clients(rng, 1, 128, 32, 4, feature_pool=pool)
    p_c = _dense_problem_from_clients(clients, 32, lam=0.05)
    print(f"propC_one_round_gap_closure,{gap_closure(p_c):.3f},target>0.65")
    base = _random_clients(rng, 1, 128, 16, 8)[0]
    p_d = _dense_problem_from_clients([base] * 4, 16, lam=0.05)
    print(f"propD_one_round_gap_closure,{gap_closure(p_d):.3f},target>0.8")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args, _ = ap.parse_known_args()

    print("name,us_per_call,derived")
    bench_kernels()
    bench_round_cost(args.quick)
    bench_properties_table()
    bench_neural_round(args.quick)
    bench_fig2(args.quick)


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
