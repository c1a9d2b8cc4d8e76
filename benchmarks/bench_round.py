"""Round-latency benchmark: the repo's measured perf trajectory.

The paper's premise is that *rounds* are the scarce resource (§1), so the
number this benchmark tracks is the server-side wall-clock latency of one
federated round, per algorithm × aggregation path × problem scale:

  * ``eager_dense``   — the reference round: a Python loop of per-bucket
                        dispatches plus the eager jnp weighted-sum
                        aggregation (``RoundEngine.round``, the pre-compile
                        hot path and the baseline every speedup is against).
  * ``compiled_dense``— the same round as one compiled dispatch
                        (``RoundEngine.compile``), dense aggregation.
  * ``compiled_fused``— the compiled round with the delta-native fused
                        aggregation (one HBM pass over the stacked deltas,
                        reweight + A epilogue folded in: the
                        ``fused_aggregate`` Pallas kernel on TPU, the
                        identical fused jnp expression elsewhere).

``--paper-k`` appends the paper-scale *client axis* entry: the §4
experiment's K = 10,000 clients (d and n_k shrunk so it fits CPU CI,
``configs.gplus_logreg.PAPER_K_CONFIG``), timed over

  * ``eager_dense``            — the unchunked reference round, which
                                 materializes every bucket's (Kb, d) delta
                                 stack: O(K·d) peak delta memory.
  * ``compiled_chunked_dense`` — the streamed round
                                 (``EngineConfig.client_chunk``): the client
                                 axis runs in chunks under one ``jax.jit``,
                                 O(client_chunk·d) peak delta memory.
  * ``compiled_chunked_fused`` — the streamed round accumulating through the
                                 delta-native ``fused_aggregate`` chunk
                                 entry.

``--participation-sweep`` appends the partial-participation family at the
same paper-scale config: for each participation p ∈ {1.0, 0.3, 0.1} it
times the **masked** streamed round (every client's pass runs; the
Bernoulli draw zeroes non-participants' weights) against the **cohort**
round (``EngineConfig.cohort``: only the sampled clients are gathered and
computed, capacity from ``cohort_capacity``).  At p=1.0 the cohort knob is
a compile-time no-op, so that row is the ≈1× sanity anchor; at the paper's
~10% participation the cohort path should win by roughly 1/p.

``--virtual`` appends the bounded-memory *virtual data* sweep: the client
axis pushed to the §1.2 "as many nodes as users" regime, K ∈ {10⁴, 10⁵,
10⁶} on ``configs.get_virtual_k_config`` — no dataset is ever
materialized; each scanned chunk's rows are regenerated inside the
compiled round (``EngineConfig.virtual_data``).  Alongside the round
latency it records the memory columns that make the claim checkable:

  * ``live_buffer_mb``     — Σ nbytes over ``jax.live_arrays()`` after the
                             timed rounds: every device buffer the process
                             retains.  The headline column — it must stay
                             at per-client *metadata* scale (a few B/client)
                             while ``est_materialized_mb`` grows ~50x per
                             K step.
  * ``est_materialized_mb``— what the same dataset's row arrays would
                             occupy if generated materialized.
  * ``rss_mb``             — psutil RSS after the entry's rounds.
  * ``peak_rss_mb``        — ``ru_maxrss``: the *process-lifetime* high
                             water mark, i.e. a monotone upper bound shared
                             by everything that ran before (compiles, other
                             entries); reported for context, not a per-K
                             signal.

Writes ``BENCH_round.json`` at the repo root — ≥ 2 problem scales × ≥ 3
algorithms, median/mean/min round latency per path and the
dense-vs-fused speedups, so every future PR has a trajectory to be judged
against.  ``--smoke`` is the CI guard: a tiny config that exercises every
path end-to-end (run by ``tests/run_tier1.sh`` with a scratch ``--json`` so
the committed trajectory file is not clobbered; ``--smoke --paper-k`` is the
budget-guarded large-K variant, ``--smoke --participation-sweep`` the
budget-guarded cohort variant, and ``--smoke --virtual`` the budget-guarded
K=10⁴ virtual variant — each skips the scale sweep).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import resource
import statistics
import time

import jax

try:
    import psutil
except ImportError:          # pragma: no cover - env-dependent
    psutil = None

from repro.configs import (get_logreg_config, get_paper_k_config,
                           get_virtual_k_config)
from repro.core import (build_problem, build_virtual_problem,
                        cohort_capacity, make_solver)
from repro.data.synthetic import generate, virtual_dataset

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_JSON = os.path.join(REPO_ROOT, "BENCH_round.json")

#: registry solvers benched by default — all stateless-w sparse solvers whose
#: round is a pure (w, key) -> w closure (dual-state solvers carry (Kb, m_pad)
#: blocks whose timing is dominated by the local SDCA scan, not the round
#: template this benchmark measures).
ALGOS = ("gd", "fedavg", "fsvrg", "dane")
PATHS = ("eager_dense", "compiled_dense", "compiled_fused")

#: the paper-scale entry's paths: unchunked reference vs the streamed round
PAPER_K_ALGOS = ("gd", "fedavg", "fsvrg")
PAPER_K_PATHS = ("eager_dense", "compiled_chunked_dense",
                 "compiled_chunked_fused")
PAPER_K_BUCKET_ROWS = 20_000

#: the participation-sweep family: masked streamed round vs cohort round at
#: the paper-scale config, per participation level
SWEEP_PARTICIPATIONS = (1.0, 0.3, 0.1)
SWEEP_PATHS = ("masked_chunked", "cohort_chunked")
SWEEP_ALGO = "fedavg"

#: the aggregator-guard overhead family at the paper-scale config: the
#: streamed round with and without the per-client clip guard, and the plain
#: round with and without coordinate-wise trimmed-mean (order stats need the
#: full delta stacks, so they have no streamed variant to compare)
GUARD_ALGO = "fedavg"
GUARD_PATHS = ("chunked_none", "chunked_clip",
               "plain_none", "plain_trimmed_mean")

#: the virtual-data client-axis sweep (ascending, so each K's numbers land
#: before the next, bigger one runs); gd+fedavg up to 10⁵, gd only at 10⁶
VIRTUAL_KS = (10_000, 100_000, 1_000_000)
VIRTUAL_ALGOS = ("gd", "fedavg")
VIRTUAL_GD_ONLY_ABOVE = 100_000
VIRTUAL_PATH = "compiled_virtual_chunked"


def _virtual_closures(algos, pv, chunk: int):
    """algo -> compiled virtual streamed round on the virtual problem (the
    solver factories detect ``problem.virtual`` and route their keyed chunk
    passes through ``EngineConfig.virtual_data``)."""
    return {algo: make_solver(algo, pv, client_chunk=chunk)._round_fast
            for algo in algos}


def _memory_columns():
    """(rss_mb, peak_rss_mb, live_buffer_mb) right now — see the module
    docstring for what each column can and cannot claim."""
    rss_mb = (psutil.Process().memory_info().rss / 2**20) if psutil else None
    # ru_maxrss is KB on Linux; process-lifetime monotone high-water mark
    peak_rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    live_mb = sum(a.nbytes for a in jax.live_arrays()) / 2**20
    return rss_mb, peak_rss_mb, live_mb


def _est_materialized_mb(cfg) -> float:
    """What generate() would hold for this config: per row, nnz idx (i32) +
    nnz val (f32) + label (f32) + client id (i32), train + test."""
    row_bytes = cfg.nnz_per_example * 8 + 8
    return cfg.num_examples * row_bytes / 2**20


def _round_closures(algo: str, prob):
    """(eager_dense, compiled_dense, compiled_fused) round closures."""
    dense = make_solver(algo, prob)
    fused = make_solver(algo, prob, aggregator="pallas")
    return {
        "eager_dense": dense._round_ref,
        "compiled_dense": dense._round_fast,
        "compiled_fused": fused._round_fast,
    }


def _paper_k_closures(algo: str, prob, chunk: int):
    """Round closures for the large-K entry: the unchunked eager reference
    against the streamed (client_chunk) compiled round, dense and fused."""
    dense = make_solver(algo, prob)
    chunked = make_solver(algo, prob, client_chunk=chunk)
    fused = make_solver(algo, prob, client_chunk=chunk, aggregator="pallas")
    return {
        "eager_dense": dense._round_ref,
        "compiled_chunked_dense": chunked._round_fast,
        "compiled_chunked_fused": fused._round_fast,
    }


def _sweep_closures(algo: str, prob, chunk: int, participation: float):
    """(masked_chunked, cohort_chunked) compiled round closures at one
    participation level, plus the cohort capacity used.  Both paths stream
    with the same client_chunk; the only difference is whether the
    non-participants' passes run at all."""
    masked = make_solver(algo, prob, client_chunk=chunk,
                         participation=participation)
    cap = cohort_capacity(participation,
                          max(b.num_clients for b in prob.buckets)) \
        if participation < 1.0 else None
    kw = dict(client_chunk=chunk, participation=participation)
    if cap is not None:
        kw["cohort"] = cap
    cohort = make_solver(algo, prob, **kw)
    return {
        "masked_chunked": masked._round_fast,
        "cohort_chunked": cohort._round_fast,
    }, cap


def _guard_closures(algo: str, prob, chunk: int):
    """Guard-vs-none compiled round closures: the robust-aggregation cost
    is the *difference* within each (chunked, plain) pair."""
    return {
        "chunked_none": make_solver(algo, prob,
                                    client_chunk=chunk)._round_fast,
        "chunked_clip": make_solver(algo, prob, client_chunk=chunk,
                                    aggregator_guard="clip")._round_fast,
        "plain_none": make_solver(algo, prob)._round_fast,
        "plain_trimmed_mean": make_solver(
            algo, prob, aggregator_guard="trimmed_mean")._round_fast,
    }


def _time_rounds(closures, w0, rounds: int, repeats: int):
    """Per-round wall-clock samples per path (blocking each round).

    Paths are *interleaved at round granularity* — path A's round r runs
    back-to-back with path B's round r — so ambient machine load perturbs
    every path equally instead of biasing whichever path ran during a busy
    window.  Compilation happens in a warmup round outside the clock.
    """
    key = jax.random.PRNGKey(0)
    # every closure gets its own w0 buffer: compiled rounds donate their
    # input iterate on accelerator backends, so paths must never share one
    for fn in closures.values():
        jax.block_until_ready(fn(jax.numpy.array(w0),
                                 jax.random.fold_in(key, 0)))
    samples = {path: [] for path in closures}
    for _ in range(repeats):
        ws = {path: jax.numpy.array(w0) for path in closures}
        for r in range(rounds):
            kr = jax.random.fold_in(key, r)
            for path, fn in closures.items():
                t0 = time.perf_counter()
                w = fn(ws[path], kr)
                jax.block_until_ready(w)
                samples[path].append(time.perf_counter() - t0)
                ws[path] = w
    return samples


def _stats(samples):
    return {
        "median_s": statistics.median(samples),
        "mean_s": statistics.fmean(samples),
        "min_s": min(samples),
        "samples": len(samples),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--scales", default="0.002,0.005",
                    help="comma-separated problem scales (see "
                         "configs.gplus_logreg.scaled); the last one is the "
                         "'largest config' the speedup headline reports")
    ap.add_argument("--algos", default=",".join(ALGOS))
    ap.add_argument("--rounds", type=int, default=4,
                    help="timed rounds per repeat (after a compile warmup)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=DEFAULT_JSON)
    ap.add_argument("--smoke", action="store_true",
                    help="CI guard: tiny config, 2 algorithms, 1 repeat")
    ap.add_argument("--paper-k", action="store_true",
                    help="append the K=10,000 paper-scale client-axis entry "
                         "(streamed rounds); with --smoke, run ONLY it at "
                         "reduced budget")
    ap.add_argument("--paper-chunk", type=int, default=512,
                    help="client_chunk for the --paper-k streamed rounds")
    ap.add_argument("--participation-sweep", action="store_true",
                    help="append the masked-vs-cohort family at the paper-k "
                         "config over --sweep-participations; with --smoke, "
                         "run ONLY it at reduced budget")
    ap.add_argument("--sweep-participations",
                    default=",".join(str(p) for p in SWEEP_PARTICIPATIONS))
    ap.add_argument("--guard-overhead", action="store_true",
                    help="append the aggregator-guard overhead family at "
                         "the paper-k config (guard vs none, streamed clip "
                         "and plain trimmed-mean); with --smoke, run ONLY "
                         "it at reduced budget")
    ap.add_argument("--virtual", action="store_true",
                    help="append the virtual-data client-axis sweep "
                         "(K up to 10^6, rows regenerated on demand); with "
                         "--smoke, run ONLY it at K=10^4")
    ap.add_argument("--virtual-ks",
                    default=",".join(str(k) for k in VIRTUAL_KS))
    ap.add_argument("--virtual-chunk", type=int, default=2048,
                    help="client_chunk for the --virtual streamed rounds")
    args = ap.parse_args(argv)

    if args.smoke:
        scales = [] if (args.paper_k or args.participation_sweep
                        or args.virtual or args.guard_overhead) else [0.001]
        algos = ["gd", "fedavg"]
        rounds, repeats = 2, 1
        pk_algos = ["gd", "fedavg"]
        sweep_ps = [0.1]     # budget guard: the headline level only
        virtual_ks = [10_000]
    else:
        scales = [float(s) for s in args.scales.split(",") if s]
        algos = [a.strip() for a in args.algos.split(",")]
        rounds, repeats = args.rounds, args.repeats
        pk_algos = list(PAPER_K_ALGOS)
        sweep_ps = [float(p) for p in args.sweep_participations.split(",")
                    if p]
        virtual_ks = sorted(int(k) for k in args.virtual_ks.split(",") if k)

    results = {
        "schema": 5,
        "smoke": bool(args.smoke),
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "seed": args.seed,
        "rounds_per_repeat": rounds,
        "repeats": repeats,
        "paths": list(PATHS),
        "configs": [],
    }

    print("scale,algo,path,median_s,mean_s,min_s")
    for scale in scales:
        cfg = get_logreg_config().scaled(scale)
        ds = generate(cfg, seed=args.seed)
        prob = build_problem(ds)
        entry = {
            "scale": scale,
            "clients": int(ds.num_clients),
            "examples": int(ds.num_examples),
            "features": int(ds.num_features),
            "buckets": len(prob.buckets),
            "algos": {},
        }
        for algo in algos:
            closures = _round_closures(algo, prob)
            w0 = jax.numpy.zeros(prob.d)
            all_samples = _time_rounds(closures, w0, rounds, repeats)
            rec = {}
            for path in PATHS:
                rec[path] = _stats(all_samples[path])
                print(f"{scale},{algo},{path},{rec[path]['median_s']:.5f},"
                      f"{rec[path]['mean_s']:.5f},{rec[path]['min_s']:.5f}")
            eager = rec["eager_dense"]["median_s"]
            rec["speedup_compiled_vs_eager"] = \
                eager / rec["compiled_dense"]["median_s"]
            rec["speedup_fused_vs_eager"] = \
                eager / rec["compiled_fused"]["median_s"]
            # Paired estimate: sample i of every path ran back-to-back under
            # the same machine load, so the median of per-round ratios is
            # far more noise-robust than the ratio of medians.
            rec["paired_speedup_fused_vs_eager"] = statistics.median(
                e / f for e, f in zip(all_samples["eager_dense"],
                                      all_samples["compiled_fused"]))
            entry["algos"][algo] = rec

        # One "round of everything": total median latency across the benched
        # algorithms, per path — the headline trajectory number.
        entry["total_median_s"] = {
            path: sum(rec[path]["median_s"] for rec in entry["algos"].values())
            for path in PATHS}
        results["configs"].append(entry)

    if scales:
        largest = results["configs"][-1]
        paired = {a: rec["paired_speedup_fused_vs_eager"]
                  for a, rec in largest["algos"].items()}
        # Headline speedup: geometric mean across algorithms of the *paired*
        # per-round estimates.  Summed raw medians let one compute-heavy
        # algorithm's ambient-load noise (±3% on a shared machine) swamp the
        # real per-algorithm wins; the paired ratios cancel that load, and
        # the geomean is the standard cross-benchmark summary.
        geomean = math.exp(
            statistics.fmean(math.log(s) for s in paired.values()))
        results["largest"] = {
            "scale": largest["scale"],
            "clients": largest["clients"],
            "median_round_latency_s": largest["total_median_s"],
            "per_algo_paired_speedup_fused_vs_eager": paired,
            "speedup_fused_vs_eager": geomean,
            "fused_beats_eager": geomean > 1.0,
        }
        print("# largest config (scale={scale}, K={clients}): total median "
              "round latency {median_round_latency_s}; paired per-algo "
              "{per_algo_paired_speedup_fused_vs_eager} -> fused-vs-eager "
              "speedup (geomean) {speedup_fused_vs_eager:.3f} "
              "(beats eager: {fused_beats_eager})"
              .format(**results["largest"]))

    pk_prob = None
    if args.paper_k or args.participation_sweep or args.guard_overhead:
        pk_cfg = get_paper_k_config()
        ds = generate(pk_cfg, seed=args.seed)
        pk_prob = build_problem(ds, max_bucket_rows=PAPER_K_BUCKET_ROWS)

    if args.paper_k:
        prob = pk_prob
        entry = {
            "scale": "paper-k",
            "clients": int(ds.num_clients),
            "examples": int(ds.num_examples),
            "features": int(ds.num_features),
            "buckets": len(prob.buckets),
            "client_chunk": args.paper_chunk,
            "max_bucket_rows": PAPER_K_BUCKET_ROWS,
            "paths": list(PAPER_K_PATHS),
            "algos": {},
        }
        for algo in pk_algos:
            closures = _paper_k_closures(algo, prob, args.paper_chunk)
            w0 = jax.numpy.zeros(prob.d)
            all_samples = _time_rounds(closures, w0, rounds, repeats)
            rec = {}
            for path in PAPER_K_PATHS:
                rec[path] = _stats(all_samples[path])
                print(f"paper-k,{algo},{path},{rec[path]['median_s']:.5f},"
                      f"{rec[path]['mean_s']:.5f},{rec[path]['min_s']:.5f}")
            rec["paired_speedup_chunked_vs_eager"] = statistics.median(
                e / c for e, c in zip(all_samples["eager_dense"],
                                      all_samples["compiled_chunked_dense"]))
            entry["algos"][algo] = rec
        entry["total_median_s"] = {
            path: sum(rec[path]["median_s"] for rec in entry["algos"].values())
            for path in PAPER_K_PATHS}
        results["configs"].append(entry)
        results["paper_k"] = {
            "clients": entry["clients"],
            "client_chunk": entry["client_chunk"],
            "median_round_latency_s": entry["total_median_s"],
            "per_algo_paired_speedup_chunked_vs_eager": {
                a: rec["paired_speedup_chunked_vs_eager"]
                for a, rec in entry["algos"].items()},
        }
        print("# paper-k (K={clients}, client_chunk={client_chunk}): total "
              "median round latency {median_round_latency_s}; paired "
              "chunked-vs-eager "
              "{per_algo_paired_speedup_chunked_vs_eager}"
              .format(**results["paper_k"]))

    if args.participation_sweep:
        prob = pk_prob
        entry = {
            "scale": "paper-k-participation-sweep",
            "clients": int(ds.num_clients),
            "features": int(ds.num_features),
            "buckets": len(prob.buckets),
            "client_chunk": args.paper_chunk,
            "max_bucket_rows": PAPER_K_BUCKET_ROWS,
            "algo": SWEEP_ALGO,
            "paths": list(SWEEP_PATHS),
            "participations": {},
        }
        for p in sweep_ps:
            closures, cap = _sweep_closures(SWEEP_ALGO, prob,
                                            args.paper_chunk, p)
            w0 = jax.numpy.zeros(prob.d)
            all_samples = _time_rounds(closures, w0, rounds, repeats)
            rec = {"cohort_capacity": cap}
            for path in SWEEP_PATHS:
                rec[path] = _stats(all_samples[path])
                print(f"sweep-p={p},{SWEEP_ALGO},{path},"
                      f"{rec[path]['median_s']:.5f},"
                      f"{rec[path]['mean_s']:.5f},{rec[path]['min_s']:.5f}")
            rec["paired_speedup_cohort_vs_masked"] = statistics.median(
                m / c for m, c in zip(all_samples["masked_chunked"],
                                      all_samples["cohort_chunked"]))
            entry["participations"][str(p)] = rec
        results["configs"].append(entry)
        summary = {
            "algo": SWEEP_ALGO,
            "clients": entry["clients"],
            "client_chunk": entry["client_chunk"],
            "per_participation_paired_speedup_cohort_vs_masked": {
                p_str: rec["paired_speedup_cohort_vs_masked"]
                for p_str, rec in entry["participations"].items()},
        }
        lowest = str(min(sweep_ps))
        if lowest in entry["participations"]:
            s_low = entry["participations"][lowest][
                "paired_speedup_cohort_vs_masked"]
            summary["lowest_participation"] = float(lowest)
            summary["speedup_cohort_vs_masked_at_lowest"] = s_low
            summary["cohort_beats_masked_2x_at_lowest"] = s_low >= 2.0
        results["participation_sweep"] = summary
        print("# participation sweep ({algo}, K={clients}): paired "
              "cohort-vs-masked "
              "{per_participation_paired_speedup_cohort_vs_masked}"
              .format(**summary))

    if args.guard_overhead:
        prob = pk_prob
        entry = {
            "scale": "paper-k-guard-overhead",
            "clients": int(ds.num_clients),
            "features": int(ds.num_features),
            "buckets": len(prob.buckets),
            "client_chunk": args.paper_chunk,
            "max_bucket_rows": PAPER_K_BUCKET_ROWS,
            "algo": GUARD_ALGO,
            "paths": list(GUARD_PATHS),
        }
        closures = _guard_closures(GUARD_ALGO, prob, args.paper_chunk)
        w0 = jax.numpy.zeros(prob.d)
        all_samples = _time_rounds(closures, w0, rounds, repeats)
        for path in GUARD_PATHS:
            entry[path] = _stats(all_samples[path])
            print(f"guard,{GUARD_ALGO},{path},{entry[path]['median_s']:.5f},"
                  f"{entry[path]['mean_s']:.5f},{entry[path]['min_s']:.5f}")
        # paired per-round ratios within each (guard, none) pair — ambient
        # load cancels, leaving the guard's own arithmetic
        entry["paired_overhead_clip_vs_none"] = statistics.median(
            c / n for c, n in zip(all_samples["chunked_clip"],
                                  all_samples["chunked_none"]))
        entry["paired_overhead_trimmed_vs_none"] = statistics.median(
            t / n for t, n in zip(all_samples["plain_trimmed_mean"],
                                  all_samples["plain_none"]))
        results["configs"].append(entry)
        results["guard_overhead"] = {
            "algo": GUARD_ALGO,
            "clients": entry["clients"],
            "client_chunk": entry["client_chunk"],
            "paired_overhead_clip_vs_none":
                entry["paired_overhead_clip_vs_none"],
            "paired_overhead_trimmed_vs_none":
                entry["paired_overhead_trimmed_vs_none"],
        }
        print("# guard overhead ({algo}, K={clients}): clip-vs-none "
              "{paired_overhead_clip_vs_none:.3f}x, trimmed-mean-vs-none "
              "{paired_overhead_trimmed_vs_none:.3f}x"
              .format(**results["guard_overhead"]))

    if args.virtual:
        entry = {
            "scale": "virtual-k-sweep",
            "client_chunk": args.virtual_chunk,
            "path": VIRTUAL_PATH,
            "ks": {},
        }
        for K in virtual_ks:
            vcfg = get_virtual_k_config(K)
            vds = virtual_dataset(vcfg, seed=args.seed)
            pv = build_virtual_problem(vds)
            # 10⁶ is the bounded-memory existence proof, not a latency
            # horse race: one timed gd round is the budget-sane payload
            v_algos = [a for a in VIRTUAL_ALGOS
                       if K <= VIRTUAL_GD_ONLY_ABOVE or a == "gd"]
            v_rounds = rounds if K <= VIRTUAL_GD_ONLY_ABOVE else 1
            v_repeats = repeats if K <= VIRTUAL_GD_ONLY_ABOVE else 1
            closures = _virtual_closures(v_algos, pv, args.virtual_chunk)
            w0 = jax.numpy.zeros(pv.d)
            all_samples = _time_rounds(closures, w0, v_rounds, v_repeats)
            rec = {
                "clients": int(vcfg.num_clients),
                "examples": int(vcfg.num_examples),
                "features": int(vcfg.num_features),
                "buckets": len(pv.buckets),
                "rounds_per_repeat": v_rounds,
                "repeats": v_repeats,
                "algos": {a: _stats(all_samples[a]) for a in v_algos},
            }
            del closures, pv, vds, all_samples, w0
            rss_mb, peak_rss_mb, live_mb = _memory_columns()
            rec["rss_mb"] = rss_mb
            rec["peak_rss_mb"] = peak_rss_mb
            rec["live_buffer_mb"] = live_mb
            rec["est_materialized_mb"] = _est_materialized_mb(vcfg)
            for a in v_algos:
                s = rec["algos"][a]
                print(f"virtual-k={K},{a},{VIRTUAL_PATH},"
                      f"{s['median_s']:.5f},{s['mean_s']:.5f},"
                      f"{s['min_s']:.5f}")
            print(f"# virtual-k={K}: live_buffer={live_mb:.1f}MB vs "
                  f"est_materialized={rec['est_materialized_mb']:.1f}MB "
                  f"(rss={rss_mb if rss_mb is None else round(rss_mb, 1)}MB, "
                  f"peak_rss={peak_rss_mb:.1f}MB)")
            entry["ks"][str(K)] = rec
        results["configs"].append(entry)
        largest_k = str(max(virtual_ks))
        big = entry["ks"][largest_k]
        results["virtual"] = {
            "client_chunk": args.virtual_chunk,
            "largest_k": int(largest_k),
            "largest_k_median_round_s": {
                a: s["median_s"] for a, s in big["algos"].items()},
            "largest_k_live_buffer_mb": big["live_buffer_mb"],
            "largest_k_est_materialized_mb": big["est_materialized_mb"],
            "bounded_memory": big["live_buffer_mb"]
            < 0.25 * big["est_materialized_mb"],
        }
        print("# virtual sweep: K={largest_k} round medians "
              "{largest_k_median_round_s}; live buffers "
              "{largest_k_live_buffer_mb:.1f}MB vs materialized-estimate "
              "{largest_k_est_materialized_mb:.1f}MB "
              "(bounded: {bounded_memory})".format(**results["virtual"]))

    with open(args.json, "w") as f:
        json.dump(results, f, indent=1)
        f.write("\n")
    print(f"# wrote {args.json}")
    return results


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
