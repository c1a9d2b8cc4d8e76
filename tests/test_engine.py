"""RoundEngine: the unified round loop must reproduce the pre-refactor
hand-rolled FSVRG loop bit-for-bit, partial-participation reweighting must
keep the aggregated update unbiased, and the pluggable aggregation paths
(dense jnp vs Pallas scaled_aggregate) must agree."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FSVRG, FSVRGConfig
from repro.core.engine import EngineConfig, RoundEngine
from repro.core.fsvrg import _client_pass


def _prerefactor_fsvrg_round(problem, w, key, cfg, phi, a_diag, passes,
                             apply_fn):
    """Verbatim copy of the seed FSVRG.round body (pre-RoundEngine), kept
    here as the bit-for-bit oracle for the engine refactor."""
    full_grad = problem.flat.grad(w)
    agg = jnp.zeros_like(w)
    wi = 0
    total_mass = jnp.zeros(())
    expected_mass = jnp.zeros(())
    for b, pass_fn in zip(problem.buckets, passes):
        kb = jax.random.fold_in(key, wi)
        deltas = pass_fn(w, full_grad, b, phi=phi, key=kb)
        if cfg.naive or not cfg.use_weighted_agg:
            wts = jnp.full((b.num_clients,), 1.0 / problem.num_clients)
        else:
            wts = problem.client_weights[wi : wi + b.num_clients]
        if cfg.participation < 1.0:
            sel = (jax.random.uniform(jax.random.fold_in(kb, 997),
                                      (b.num_clients,))
                   < cfg.participation).astype(jnp.float32)
            total_mass = total_mass + (wts * sel).sum()
            expected_mass = expected_mass + wts.sum()
            wts = wts * sel
        agg = agg + (wts[:, None] * deltas).sum(axis=0)
        wi += b.num_clients
    if cfg.participation < 1.0:
        agg = agg * (expected_mass / jnp.maximum(total_mass, 1e-9))
    scale = a_diag if (cfg.use_A and not cfg.naive) else 1.0
    return apply_fn(w, agg, scale)


@pytest.mark.parametrize("participation", [1.0, 0.5])
def test_fsvrg_on_engine_matches_prerefactor_trajectory(tiny_problem, participation):
    """3 rounds of engine-backed FSVRG == the seed round loop, bit-for-bit.

    The engine's *eager reference* round is the bit-exact pin surface (the
    refactor must not change a single ulp of the round template); the
    compiled round that ``solver.round`` dispatches is checked against the
    same oracle at tight tolerance — whole-round jit may associate the
    multi-bucket aggregation differently (see test_fused_round.py).
    """
    prob = tiny_problem
    cfg = FSVRGConfig(stepsize=1.0, participation=participation)
    solver = FSVRG(prob, cfg)

    # the bucket rows are an argument of the jitted pass, as in the solver:
    # closed-over rows would be program constants, which XLA may fold with
    # a different rounding than the compiled pass
    pass_fn = jax.jit(functools.partial(_client_pass, lam=prob.flat.lam,
                                        cfg=cfg))
    passes = [pass_fn] * len(prob.buckets)
    apply_fn = jax.jit(lambda w, agg, scale: w + scale * agg)

    state = solver.init()
    w_eager = jnp.zeros(prob.d)
    w_ref = jnp.zeros(prob.d)
    key = jax.random.PRNGKey(0)
    for r in range(3):
        kr = jax.random.fold_in(key, r)
        state = solver.round(state, kr)
        w_eager = solver._round_ref(w_eager, kr)
        w_ref = _prerefactor_fsvrg_round(prob, w_ref, kr, cfg, solver.phi,
                                         solver.a_diag, passes, apply_fn)
        np.testing.assert_array_equal(np.asarray(w_eager), np.asarray(w_ref))
        np.testing.assert_allclose(np.asarray(state.w), np.asarray(w_ref),
                                   rtol=1e-5, atol=1e-8)


def test_partial_participation_reweighting_unbiased(small_problem):
    """With fixed client deltas, the mean over participation draws of the
    reweighted aggregate matches the full-participation aggregate — the
    (expected mass / realized mass) correction keeps the round direction
    unbiased in expectation."""
    prob = small_problem
    w = jnp.zeros(prob.d)
    rng = np.random.default_rng(0)
    deltas = [
        jnp.asarray(rng.standard_normal((b.num_clients, prob.d)), jnp.float32)
        for b in prob.buckets
    ]

    eng_full = RoundEngine(prob, EngineConfig())
    ref_dir = eng_full.aggregate(w, deltas, jax.random.PRNGKey(0)) - w

    eng_p = RoundEngine(prob, EngineConfig(participation=0.75))
    one_draw = jax.jit(lambda key: eng_p.aggregate(w, deltas, key) - w)
    N = 800
    acc = jnp.zeros_like(w)
    base = jax.random.PRNGKey(42)
    for i in range(N):
        acc = acc + one_draw(jax.random.fold_in(base, i))
    mean_dir = acc / N

    rel = float(jnp.linalg.norm(mean_dir - ref_dir)
                / jnp.linalg.norm(ref_dir))
    assert rel < 0.08, rel


def test_pallas_aggregator_matches_dense(small_problem):
    """aggregator='pallas' (scaled_aggregate kernel over the stacked deltas)
    == the dense jnp weighted sum, for both scaling modes."""
    prob = small_problem
    w = jax.random.normal(jax.random.PRNGKey(1), (prob.d,)) * 0.1
    rng = np.random.default_rng(1)
    deltas = [
        jnp.asarray(rng.standard_normal((b.num_clients, prob.d)), jnp.float32)
        for b in prob.buckets
    ]
    a_diag = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (prob.d,))) + 0.5
    key = jax.random.PRNGKey(3)

    for eng_kw in ({}, {"server_scaling": "diag"},
                   {"participation": 0.5},
                   {"weighting": "uniform", "server_scaling": "diag"}):
        dense = RoundEngine(prob, EngineConfig(**eng_kw), a_diag=a_diag)
        pallas = RoundEngine(prob, EngineConfig(aggregator="pallas", **eng_kw),
                             a_diag=a_diag)
        out_d = dense.aggregate(w, deltas, key)
        out_p = pallas.aggregate(w, deltas, key)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_d),
                                   rtol=1e-5, atol=1e-5)


def test_distributed_gd_on_engine_matches_flat_gd(tiny_problem):
    """The engine-ported GD (per-client mean gradients, n_k/n aggregation)
    equals the flat single-gradient round up to f32 association."""
    from repro.core.baselines import DistributedGD, gd_round

    prob = tiny_problem
    w_flat = jnp.zeros(prob.d)
    solver = DistributedGD(prob, stepsize=2.0)
    state = solver.init()
    for _ in range(3):
        w_flat = gd_round(prob, w_flat, 2.0)
        state = solver.round(state, jax.random.PRNGKey(0))
        np.testing.assert_allclose(np.asarray(state.w), np.asarray(w_flat),
                                   rtol=1e-5, atol=1e-6)


def test_sum_weighting_is_plain_delta_sum(small_problem):
    """weighting='sum' (the dual-method aggregation) applies weight 1 per
    client: the round update is exactly Σ_k δ_k."""
    prob = small_problem
    w = jnp.zeros(prob.d)
    rng = np.random.default_rng(2)
    deltas = [
        jnp.asarray(rng.standard_normal((b.num_clients, prob.d)), jnp.float32)
        for b in prob.buckets
    ]
    eng = RoundEngine(prob, EngineConfig(weighting="sum"))
    out = eng.aggregate(w, deltas, jax.random.PRNGKey(0))
    expect = sum(d.sum(axis=0) for d in deltas)
    np.testing.assert_allclose(np.asarray(out - w), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_round_with_state_threads_and_masks_state(small_problem):
    """round_with_state hands each bucket its own state, returns the pass's
    update, and under partial participation freezes exactly the clients the
    aggregation draw zeroes."""
    prob = small_problem
    w = jnp.zeros(prob.d)
    states = [jnp.zeros((b.num_clients, 3)) for b in prob.buckets]

    def pass_fn(w, bi, bucket, state_b, kb):
        deltas = jnp.zeros((bucket.num_clients, prob.d))
        return deltas, state_b + 1.0

    # full participation: every client's state advances
    eng = RoundEngine(prob, EngineConfig())
    _, new_states = eng.round_with_state(w, states, jax.random.PRNGKey(0),
                                         pass_fn)
    for s in new_states:
        np.testing.assert_array_equal(np.asarray(s), 1.0)

    # partial participation: non-participants keep their state, and the
    # frozen set is exactly the complement of the engine's Bernoulli draw
    eng_p = RoundEngine(prob, EngineConfig(participation=0.5))
    key = jax.random.PRNGKey(1)
    _, new_states = eng_p.round_with_state(w, states, key, pass_fn)
    wi = 0
    advanced = frozen = 0
    for b, s in zip(prob.buckets, new_states):
        sel = np.asarray(eng_p.participation_mask(
            jax.random.fold_in(key, wi), b.num_clients))
        np.testing.assert_array_equal(np.asarray(s)[sel == 1.0], 1.0)
        np.testing.assert_array_equal(np.asarray(s)[sel == 0.0], 0.0)
        advanced += int(sel.sum())
        frozen += int((1 - sel).sum())
        wi += b.num_clients
    assert advanced > 0 and frozen > 0


def test_engine_config_validation(tiny_problem):
    with pytest.raises(ValueError):
        EngineConfig(weighting="bogus")
    with pytest.raises(ValueError):
        EngineConfig(participation=0.0)
    with pytest.raises(ValueError):
        RoundEngine(tiny_problem, EngineConfig(server_scaling="diag"))


#: every documented invalid knob combination and the message it must carry —
#: the runtime twin of the FED004 static check (every knob is either
#: threaded through all round paths or rejected here, loudly)
_INVALID_CONFIGS = [
    (dict(weighting="bogus"), "weighting must be one of"),
    (dict(server_scaling="block"), "server_scaling must be one of"),
    (dict(aggregator="sparse"), "aggregator must be one of"),
    (dict(participation=0.0), r"participation must be in \(0, 1\]"),
    (dict(participation=1.5), r"participation must be in \(0, 1\]"),
    (dict(participation=-0.25), r"participation must be in \(0, 1\]"),
    # bool is a subclass of int: client_chunk=True must not mean chunk=1
    (dict(client_chunk=True), "client_chunk must be a positive int"),
    (dict(client_chunk=0), "client_chunk must be a positive int"),
    (dict(client_chunk=-4), "client_chunk must be a positive int"),
    (dict(client_chunk=2.5), "client_chunk must be a positive int"),
    (dict(cohort=True), "cohort must be a positive int"),
    (dict(cohort=0), "cohort must be a positive int"),
    (dict(cohort=-1), "cohort must be a positive int"),
    (dict(virtual_data=1), "virtual_data must be a bool"),
    (dict(virtual_data=None), "virtual_data must be a bool"),
    (dict(aggregator_guard="huber"), "aggregator_guard must be one of"),
    # order-statistic guards need the materialized (K, d) stacks
    (dict(aggregator_guard="trimmed_mean", client_chunk=8), "materialized"),
    (dict(aggregator_guard="median", client_chunk=8), "materialized"),
    (dict(aggregator_guard="trimmed_mean", virtual_data=True), "virtual"),
    (dict(aggregator_guard="median", virtual_data=True), "virtual"),
    # ... and replace the weighted sum dual methods rely on
    (dict(aggregator_guard="trimmed_mean", weighting="sum"),
     "exact plain sum"),
    (dict(aggregator_guard="median", weighting="sum"), "exact plain sum"),
    (dict(guard_trim=-0.1), r"guard_trim must be in \[0, 0.5\)"),
    (dict(guard_trim=0.5), r"guard_trim must be in \[0, 0.5\)"),
    (dict(guard_trim=0.7), r"guard_trim must be in \[0, 0.5\)"),
    (dict(guard_clip_norm=0.0), "guard_clip_norm must be a positive number"),
    (dict(guard_clip_norm=-1.0), "guard_clip_norm must be a positive number"),
    (dict(guard_clip_norm=True), "guard_clip_norm must be a positive number"),
    (dict(guard_clip_norm=1.0), "requires aggregator_guard='clip'"),
    (dict(guard_clip_norm=1.0, aggregator_guard="median"),
     "requires aggregator_guard='clip'"),
]


@pytest.mark.parametrize(
    "kwargs,match", _INVALID_CONFIGS,
    ids=["-".join(f"{k}={v}" for k, v in kw.items())
         for kw, _ in _INVALID_CONFIGS])
def test_engine_config_validation_matrix(kwargs, match):
    with pytest.raises(ValueError, match=match):
        EngineConfig(**kwargs)


@pytest.mark.parametrize("kwargs", [
    dict(),
    dict(participation=0.5, cohort=4),
    dict(client_chunk=8, virtual_data=True),
    dict(aggregator_guard="trimmed_mean", guard_trim=0.2),
    dict(aggregator_guard="median", participation=0.3),
    dict(aggregator_guard="clip", guard_clip_norm=5.0, client_chunk=8),
    dict(aggregator_guard="clip", virtual_data=True),
])
def test_engine_config_valid_combinations(kwargs):
    EngineConfig(**kwargs)  # must not raise
