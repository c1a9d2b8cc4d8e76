"""The fleet simulation layer (repro.fleet): traces + participation models.

Contracts:

1. Trace determinism — any round's masks are a pure function of
   ``(trace.seed, round, client_ids)``: bit-identical across
   regeneration, under jit, and invariant to how the client axis is
   batched (a slice of the fleet's mask == the mask of the slice), which
   is what makes chunk/cohort rounds see the same fleet.
2. BernoulliParticipation is a bit-exact pin of the engine's historical
   draw — installing it changes nothing, down to the last bit.
3. Trace-driven rounds: plain vs streamed (chunk) vs cohort parity under
   a round-dependent model; round-dependent models reject mask requests
   without a round index.
4. Dropout-after-compute — a straggler (available but not returned)
   is indistinguishable from a never-sampled client: replaying the
   trace's ``returned`` mask through FixedParticipation reproduces the
   trace round bit-for-bit, and dual-state freezing covers stragglers.
5. Solver plumbing: registry solvers accept ``participation_model`` and
   thread ``state.round`` into the compiled round (no retrace per round).
6. Distribution drift (repro.data.synthetic.drifted_dataset): epoch 0 is
   the identity, epochs are deterministic, shapes are drift-invariant.
7. Fault injection (repro.fleet.faults): fault draws follow the same
   purity/batch-shape-invariance contract as the masks; every engine path
   corrupts the same clients identically; NaN poisoning breaks an
   unguarded round and every ``aggregator_guard`` restores a finite one.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Trainer, make_solver
from repro.core.engine import EngineConfig, RoundEngine
from repro.fleet import (BernoulliParticipation, DeltaFaults,
                         FixedParticipation, FleetTrace, TraceParticipation,
                         availability_rate, fault_counts, fleet_masks)

TRACE = FleetTrace(seed=5, base=0.5, amplitude=0.3, period=7.0,
                   burst_prob=0.3, burst_frac=0.5, straggler_rate=0.25)


def _keyed_deltas(w, bucket, keys):
    def one(n_k, ck):
        return ((jax.random.uniform(ck, w.shape) - 0.5)
                * (1.0 + 0.1 * n_k.astype(jnp.float32)))
    return jax.vmap(one)(bucket.n_k, keys)


def _passes():
    def client_pass(w, bi, b, kb):
        return _keyed_deltas(w, b, jax.random.split(kb, b.num_clients))

    def chunk_pass(w, bi, cb, keys):
        return _keyed_deltas(w, cb, keys)

    return client_pass, chunk_pass


# --------------------------------------------------------------------- #
# 1. trace determinism
# --------------------------------------------------------------------- #


def test_fleet_masks_bit_identical_across_regeneration():
    ids = jnp.arange(64, dtype=jnp.uint32)
    for r in (0, 3, 11):
        a = fleet_masks(TRACE, r, ids)
        b = fleet_masks(TRACE, r, ids)
        c = jax.jit(lambda rr: fleet_masks(TRACE, rr, ids))(jnp.int32(r))
        np.testing.assert_array_equal(np.asarray(a.available),
                                      np.asarray(b.available))
        np.testing.assert_array_equal(np.asarray(a.returned),
                                      np.asarray(b.returned))
        np.testing.assert_array_equal(np.asarray(a.available),
                                      np.asarray(c.available))
        np.testing.assert_array_equal(np.asarray(a.returned),
                                      np.asarray(c.returned))


def test_fleet_masks_batch_shape_invariant():
    """The mask of a client depends only on its global id — computing the
    fleet whole or in arbitrary slices gives the same bits (the property
    chunk/cohort rounds rely on)."""
    K = 50
    ids = jnp.arange(K, dtype=jnp.uint32)
    whole = fleet_masks(TRACE, 4, ids)
    for lo, hi in ((0, 7), (7, 30), (30, 50), (13, 14)):
        part = fleet_masks(TRACE, 4, ids[lo:hi])
        np.testing.assert_array_equal(np.asarray(whole.available)[lo:hi],
                                      np.asarray(part.available))
        np.testing.assert_array_equal(np.asarray(whole.returned)[lo:hi],
                                      np.asarray(part.returned))


def test_availability_rate_bounds_and_diurnal_variation():
    ids = jnp.arange(200, dtype=jnp.uint32)
    rates = np.stack([np.asarray(availability_rate(TRACE, r, ids))
                      for r in range(14)])
    assert (rates >= 0.0).all() and (rates <= 1.0).all()
    # the sinusoid must actually move the per-client rate across rounds
    assert rates.std(axis=0).max() > 0.05


def test_returned_is_subset_of_available():
    ids = jnp.arange(300, dtype=jnp.uint32)
    m = fleet_masks(TRACE, 2, ids)
    av, ret = np.asarray(m.available), np.asarray(m.returned)
    assert ((ret == 1) <= (av == 1)).all()
    assert (av - ret).sum() > 0  # straggler_rate=0.25: someone straggled
    quiet = dataclasses.replace(TRACE, straggler_rate=0.0)
    m0 = fleet_masks(quiet, 2, ids)
    np.testing.assert_array_equal(np.asarray(m0.available),
                                  np.asarray(m0.returned))


def test_trace_validation():
    with pytest.raises(ValueError):
        FleetTrace(base=0.0)
    with pytest.raises(ValueError):
        FleetTrace(base=0.3, amplitude=0.4)   # rate floor <= 0
    with pytest.raises(ValueError):
        FleetTrace(straggler_rate=1.0)


# --------------------------------------------------------------------- #
# 2. BernoulliParticipation pins the engine draw
# --------------------------------------------------------------------- #


def test_bernoulli_model_bit_identical_to_engine_draw(small_problem):
    prob = small_problem
    p = 0.4
    eng = RoundEngine(prob, EngineConfig(participation=p))
    eng_m = RoundEngine(prob, EngineConfig(participation=p),
                        participation_model=BernoulliParticipation(p))
    client_pass, _ = _passes()
    w = jnp.zeros(prob.d)
    for r in range(3):
        key = jax.random.PRNGKey(30 + r)
        for a, b in zip(eng.participation_masks(key),
                        eng_m.participation_masks(key)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(eng.round(w, key, client_pass)),
            np.asarray(eng_m.round(w, key, client_pass)))


# --------------------------------------------------------------------- #
# 3. trace-driven rounds across engine paths
# --------------------------------------------------------------------- #


def test_round_dependent_model_requires_round_index(small_problem):
    eng = RoundEngine(small_problem, EngineConfig(participation=0.8),
                      participation_model=TraceParticipation(TRACE))
    client_pass, _ = _passes()
    with pytest.raises(ValueError, match="round"):
        eng.round(jnp.zeros(small_problem.d), jax.random.PRNGKey(0),
                  client_pass)


@pytest.mark.parametrize("r", [0, 5])
def test_trace_round_chunk_and_cohort_parity(small_problem, r):
    """One fleet, three engine paths: the plain masked round, the streamed
    (client_chunk) round, and the gathered cohort round all see the same
    trace masks — outputs agree to the same float tolerance as the
    Bernoulli paths (chunked/cohort accumulation reorders the sum)."""
    prob = small_problem
    model = TraceParticipation(TRACE)
    cap = TRACE.max_rate()
    kw = dict(participation=cap)
    eng = RoundEngine(prob, EngineConfig(**kw), participation_model=model)
    eng_ch = RoundEngine(prob, EngineConfig(client_chunk=3, **kw),
                         participation_model=model)
    eng_co = RoundEngine(prob, EngineConfig(cohort=6, **kw),
                         participation_model=model)
    client_pass, chunk_pass = _passes()
    w = jax.random.normal(jax.random.PRNGKey(1), (prob.d,)) * 0.1
    key = jax.random.PRNGKey(40 + r)
    out = eng.round(w, key, client_pass, round_index=r)
    out_ch = eng_ch.round_streamed(w, key, chunk_pass, round_index=r)
    out_co = eng_co.round_cohort(w, key, chunk_pass, round_index=r)
    np.testing.assert_allclose(np.asarray(out_ch), np.asarray(out),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_co), np.asarray(out),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------- #
# 4. dropout-after-compute semantics
# --------------------------------------------------------------------- #


def test_straggler_equals_removed_delta(small_problem):
    """The trace round must equal a round whose participant set is exactly
    the returned clients — a straggler's computed-but-dropped delta leaves
    no trace in the aggregate (zero weight ≡ removed), and differs from
    the availability-only round whenever someone straggled."""
    prob = small_problem
    model = TraceParticipation(TRACE)
    r = 2
    key = jax.random.PRNGKey(50)
    offsets = tuple(int(w) for w in np.cumsum(
        [0] + [b.num_clients for b in prob.buckets])[:-1])
    sizes = tuple(b.num_clients for b in prob.buckets)
    avail, returned = model.mask_components(key, jnp.int32(r), offsets, sizes)
    assert sum(float((a - b).sum()) for a, b in zip(avail, returned)) > 0

    kw = dict(participation=TRACE.max_rate())
    client_pass, _ = _passes()
    w = jnp.zeros(prob.d)
    eng_tr = RoundEngine(prob, EngineConfig(**kw), participation_model=model)
    eng_ret = RoundEngine(prob, EngineConfig(**kw),
                          participation_model=FixedParticipation(
                              tuple(returned)))
    eng_av = RoundEngine(prob, EngineConfig(**kw),
                         participation_model=FixedParticipation(tuple(avail)))
    out_tr = eng_tr.round(w, key, client_pass, round_index=r)
    out_ret = eng_ret.round(w, key, client_pass, round_index=r)
    out_av = eng_av.round(w, key, client_pass, round_index=r)
    np.testing.assert_array_equal(np.asarray(out_tr), np.asarray(out_ret))
    assert (np.asarray(out_tr) != np.asarray(out_av)).any()


def test_straggler_state_frozen(small_problem):
    """Dual-state freezing covers stragglers: every client whose delta
    did not return — never-available AND available-but-straggling — keeps
    its state bit-for-bit."""
    prob = small_problem
    model = TraceParticipation(TRACE)
    eng = RoundEngine(prob, EngineConfig(weighting="sum",
                                         participation=TRACE.max_rate()),
                      participation_model=model)

    def dual_pass(w, bi, b, s_b, kb):
        deltas = _keyed_deltas(w, b, jax.random.split(kb, b.num_clients))
        return deltas, s_b + deltas[:, :3]

    states = [jnp.ones((b.num_clients, 3)) for b in prob.buckets]
    r, key = 2, jax.random.PRNGKey(50)
    offsets = tuple(int(w) for w in np.cumsum(
        [0] + [b.num_clients for b in prob.buckets])[:-1])
    sizes = tuple(b.num_clients for b in prob.buckets)
    _, returned = model.mask_components(key, jnp.int32(r), offsets, sizes)
    _, new_states = eng.round_with_state(jnp.zeros(prob.d), states, key,
                                         dual_pass, round_index=r)
    changed_any = False
    for ret, s_old, s_new in zip(returned, states, new_states):
        gone = np.asarray(ret) <= 0
        np.testing.assert_array_equal(np.asarray(s_new)[gone],
                                      np.asarray(s_old)[gone])
        changed_any |= bool(
            (np.asarray(s_new)[~gone] != np.asarray(s_old)[~gone]).any())
    assert changed_any  # someone returned and their state moved


# --------------------------------------------------------------------- #
# 5. solver plumbing
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("name", ["gd", "fedavg", "fsvrg", "cocoa", "dane"])
def test_solvers_accept_trace_model(small_problem, name):
    """Every Fig.-2 solver runs under a trace model through the Trainer
    (which feeds state.round into the compiled round), and two identical
    fits are bit-identical."""
    model = TraceParticipation(TRACE)
    kw = dict(participation=TRACE.max_rate(), participation_model=model)

    def fit():
        solver = make_solver(name, small_problem, **kw)
        return Trainer(solver, rounds=3, seed=0).fit()

    w1, w2 = fit().w, fit().w
    assert np.isfinite(np.asarray(w1)).all()
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))


def test_trace_model_ignores_solver_seed(small_problem):
    """The fleet is the fleet: masks are a function of (trace.seed, r),
    not of the solver's round key."""
    model = TraceParticipation(TRACE)
    eng = RoundEngine(small_problem,
                      EngineConfig(participation=TRACE.max_rate()),
                      participation_model=model)
    m1 = eng.participation_masks(jax.random.PRNGKey(0), round_index=4)
    m2 = eng.participation_masks(jax.random.PRNGKey(999), round_index=4)
    for a, b in zip(m1, m2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------- #
# 6. distribution drift
# --------------------------------------------------------------------- #


def test_drift_epoch_zero_is_identity(small_virtual_dataset):
    from repro.data.synthetic import drifted_dataset

    vds = small_virtual_dataset
    assert drifted_dataset(vds, 0, w_true_scale=0.5,
                           resample_clients=True) is vds


def test_drift_deterministic_and_shape_invariant(small_virtual_dataset):
    from repro.data.synthetic import drifted_dataset, materialize_dataset

    vds = small_virtual_dataset
    d1 = materialize_dataset(drifted_dataset(vds, 2, w_true_scale=0.8,
                                             resample_clients=True))
    d2 = materialize_dataset(drifted_dataset(vds, 2, w_true_scale=0.8,
                                             resample_clients=True))
    base = materialize_dataset(vds)
    np.testing.assert_array_equal(d1.y, d2.y)
    np.testing.assert_array_equal(np.asarray(d1.val), np.asarray(d2.val))
    # same shapes and client partition, different data
    assert d1.y.shape == base.y.shape and d1.idx.shape == base.idx.shape
    np.testing.assert_array_equal(d1.client_sizes, base.client_sizes)
    assert (d1.y != base.y).any() or (np.asarray(d1.idx)
                                      != np.asarray(base.idx)).any()


def test_drift_epochs_differ(small_virtual_dataset):
    from repro.data.synthetic import drifted_dataset, materialize_dataset

    vds = small_virtual_dataset
    d1 = materialize_dataset(drifted_dataset(vds, 1, resample_clients=True))
    d2 = materialize_dataset(drifted_dataset(vds, 2, resample_clients=True))
    assert (d1.y != d2.y).any() or (np.asarray(d1.idx)
                                    != np.asarray(d2.idx)).any()


def test_drift_w_scale_only_relabels(small_virtual_dataset):
    """Concept drift (w_true rescale) moves labels, not features."""
    from repro.data.synthetic import drifted_dataset, materialize_dataset

    vds = small_virtual_dataset
    base = materialize_dataset(vds)
    dr = materialize_dataset(drifted_dataset(vds, 3, w_true_scale=0.5))
    np.testing.assert_array_equal(np.asarray(base.idx), np.asarray(dr.idx))
    np.testing.assert_array_equal(np.asarray(base.val), np.asarray(dr.val))


# --------------------------------------------------------------------- #
# 7. fault injection
# --------------------------------------------------------------------- #

# finite corruptions only (sign / scale / replay) — rounds stay comparable
# across engine paths; NaN poisoning gets its own tests below
FAULTS = DeltaFaults(seed=9, sign_rate=0.2, scale_rate=0.15,
                     scale_factor=5.0, replay_rate=0.15, replay_window=2)
NAN_FAULTS = DeltaFaults(seed=2, nan_rate=0.3)


def test_fault_kinds_deterministic_and_jit_stable():
    ids = jnp.arange(200, dtype=jnp.uint32)
    for r in (0, 3):
        k1 = FAULTS.kinds(r, ids)
        k2 = jax.jit(FAULTS.kinds)(jnp.int32(r), ids)
        np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))
    # different rounds draw different fault sets
    assert (np.asarray(FAULTS.kinds(0, ids))
            != np.asarray(FAULTS.kinds(3, ids))).any()
    # no nan_rate configured -> the poison kind never fires
    assert 1 not in set(np.unique(np.asarray(FAULTS.kinds(1, ids))))


def test_fault_kinds_batch_shape_invariant():
    """A slice of the fleet's kinds == the kinds of the slice — the same
    invariance the masks have, so chunk/cohort rounds corrupt the same
    clients as the plain round."""
    ids = jnp.arange(120, dtype=jnp.uint32)
    whole = np.asarray(FAULTS.kinds(2, ids))
    for lo, hi in ((0, 7), (7, 64), (64, 120)):
        np.testing.assert_array_equal(
            np.asarray(FAULTS.kinds(2, ids[lo:hi])), whole[lo:hi])


def test_fault_apply_batch_shape_invariant():
    ids = jnp.arange(50, dtype=jnp.uint32)
    deltas = jax.random.normal(jax.random.PRNGKey(0), (50, 33))
    whole = np.asarray(FAULTS.apply(deltas, 4, ids))
    assert (whole != np.asarray(deltas)).any()
    for lo, hi in ((0, 13), (13, 50)):
        np.testing.assert_array_equal(
            np.asarray(FAULTS.apply(deltas[lo:hi], 4, ids[lo:hi])),
            whole[lo:hi])


def test_fault_window_gating():
    f = dataclasses.replace(FAULTS, start_round=3, stop_round=5)
    ids = jnp.arange(100, dtype=jnp.uint32)
    assert not np.asarray(f.kinds(2, ids)).any()
    assert np.asarray(f.kinds(3, ids)).any()
    assert not np.asarray(f.kinds(5, ids)).any()
    # inside the window the draws match the ungated model bit-for-bit
    np.testing.assert_array_equal(np.asarray(f.kinds(4, ids)),
                                  np.asarray(FAULTS.kinds(4, ids)))


def test_fault_validation():
    with pytest.raises(ValueError, match="sum"):
        DeltaFaults(nan_rate=0.6, sign_rate=0.6)
    with pytest.raises(ValueError, match="nan_rate"):
        DeltaFaults(nan_rate=1.5)
    with pytest.raises(ValueError, match="replay_window"):
        DeltaFaults(replay_window=0)
    with pytest.raises(ValueError, match="stop_round"):
        DeltaFaults(start_round=4, stop_round=4)


def test_fault_spec_round_trip():
    f = DeltaFaults.from_spec("nan=0.01,sign=0.05,scale-factor=7,"
                              "start=3,stop=9,seed=2")
    assert f == DeltaFaults(seed=2, nan_rate=0.01, sign_rate=0.05,
                            scale_factor=7.0, start_round=3, stop_round=9)
    with pytest.raises(ValueError, match="knob"):
        DeltaFaults.from_spec("nans=0.1")


def test_fault_counts_matches_kinds():
    """fault_counts is telemetry's recomputable view: it must agree with
    counting the kinds over the returned clients directly, and a client
    that never reports is never counted."""
    f = DeltaFaults(seed=7, nan_rate=0.2, sign_rate=0.2)
    ids = jnp.arange(64, dtype=jnp.uint32)
    mask = (ids % 3 != 0).astype(jnp.float32)    # the returned-weight view
    inj, poi = fault_counts(f, 1, ids, mask)
    k = np.asarray(f.kinds(1, ids))
    live = np.asarray(mask) > 0
    assert int(inj) == int((live & (k != 0)).sum()) > 0
    assert int(poi) == int((live & (k == 1)).sum()) > 0
    inj0, poi0 = fault_counts(None, 1, ids, mask)
    assert int(inj0) == 0 and int(poi0) == 0


@pytest.mark.parametrize("r", [1, 4])
def test_faulted_round_paths_parity(small_problem, r):
    """One fault model, three engine paths: plain, streamed, and cohort
    rounds corrupt the same clients identically (global-id draws, not
    batch positions), to the same tolerance as the honest parity test —
    and the faults demonstrably changed the round."""
    prob = small_problem
    model = TraceParticipation(TRACE)
    kw = dict(participation=TRACE.max_rate())
    eng = RoundEngine(prob, EngineConfig(**kw), participation_model=model,
                      fault_model=FAULTS)
    key = jax.random.PRNGKey(70 + r)
    # the round must corrupt a client that reports, or "the faults changed
    # the round" below asserts nothing (round 0 of this trace and fault
    # seed draws no fault on any of its three returned clients)
    injected = sum(
        int(fault_counts(FAULTS, r, eng._bucket_ids(wi, b.num_clients),
                         m)[0])
        for wi, b, m in zip(eng._offsets, prob.buckets,
                            eng.participation_masks(key, r)))
    assert injected > 0
    eng_ch = RoundEngine(prob, EngineConfig(client_chunk=3, **kw),
                         participation_model=model, fault_model=FAULTS)
    eng_co = RoundEngine(prob, EngineConfig(cohort=6, **kw),
                         participation_model=model, fault_model=FAULTS)
    client_pass, chunk_pass = _passes()
    w = jax.random.normal(jax.random.PRNGKey(1), (prob.d,)) * 0.1
    out = eng.round(w, key, client_pass, round_index=r)
    out_ch = eng_ch.round_streamed(w, key, chunk_pass, round_index=r)
    out_co = eng_co.round_cohort(w, key, chunk_pass, round_index=r)
    np.testing.assert_allclose(np.asarray(out_ch), np.asarray(out),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_co), np.asarray(out),
                               rtol=1e-5, atol=1e-6)
    honest = RoundEngine(prob, EngineConfig(**kw),
                         participation_model=model)
    out_h = honest.round(w, key, client_pass, round_index=r)
    assert (np.asarray(out) != np.asarray(out_h)).any()


def test_zero_rate_fault_model_is_identity(small_problem):
    """Installing an all-zero-rate fault model changes nothing, down to
    the last bit — the no-faults analogue of the Bernoulli pin."""
    prob = small_problem
    client_pass, chunk_pass = _passes()
    w = jnp.zeros(prob.d)
    key = jax.random.PRNGKey(7)
    f0 = DeltaFaults(seed=3)
    eng = RoundEngine(prob, EngineConfig(participation=0.5))
    eng_f = RoundEngine(prob, EngineConfig(participation=0.5),
                        fault_model=f0)
    np.testing.assert_array_equal(
        np.asarray(eng.round(w, key, client_pass, round_index=0)),
        np.asarray(eng_f.round(w, key, client_pass, round_index=0)))
    eng_ch = RoundEngine(prob, EngineConfig(participation=0.5,
                                            client_chunk=3))
    eng_chf = RoundEngine(prob, EngineConfig(participation=0.5,
                                             client_chunk=3),
                          fault_model=f0)
    np.testing.assert_array_equal(
        np.asarray(eng_ch.round_streamed(w, key, chunk_pass,
                                         round_index=0)),
        np.asarray(eng_chf.round_streamed(w, key, chunk_pass,
                                          round_index=0)))


def test_fault_model_requires_round_index(small_problem):
    eng = RoundEngine(small_problem, EngineConfig(participation=0.8),
                      fault_model=FAULTS)
    client_pass, _ = _passes()
    with pytest.raises(ValueError, match="fault"):
        eng.round(jnp.zeros(small_problem.d), jax.random.PRNGKey(0),
                  client_pass)


def test_nan_faults_break_unguarded_round_and_every_guard_recovers(
        small_problem):
    """NaN poisoning propagates through the unguarded weighted sum; each
    aggregator_guard arm ("clip" rejection, trimmed mean, median) yields a
    finite round from the same poisoned deltas, and the streamed clip
    round matches the plain clip round."""
    prob = small_problem
    client_pass, chunk_pass = _passes()
    w = jnp.zeros(prob.d)
    key = jax.random.PRNGKey(11)
    out = RoundEngine(prob, EngineConfig(), fault_model=NAN_FAULTS).round(
        w, key, client_pass, round_index=0)
    assert not bool(jnp.isfinite(out).all())
    guarded = {}
    for g in ("clip", "trimmed_mean", "median"):
        out_g = RoundEngine(prob, EngineConfig(aggregator_guard=g),
                            fault_model=NAN_FAULTS).round(
            w, key, client_pass, round_index=0)
        assert bool(jnp.isfinite(out_g).all()), g
        guarded[g] = np.asarray(out_g)
    out_ch = RoundEngine(prob, EngineConfig(aggregator_guard="clip",
                                            client_chunk=3),
                         fault_model=NAN_FAULTS).round_streamed(
        w, key, chunk_pass, round_index=0)
    np.testing.assert_allclose(np.asarray(out_ch), guarded["clip"],
                               rtol=1e-5, atol=1e-6)


def test_guard_clip_rejects_nonfinite_and_caps_norms(small_problem):
    eng = RoundEngine(small_problem,
                      EngineConfig(aggregator_guard="clip",
                                   guard_clip_norm=0.5))
    d = small_problem.d
    big = jnp.ones((d,))                      # ||big|| = sqrt(d) >> 0.5
    small = jnp.full((d,), 1e-3 / np.sqrt(d))
    deltas = jnp.stack([jnp.full((d,), jnp.nan), big, small])
    safe = np.asarray(eng._guard_clip(deltas))
    np.testing.assert_array_equal(safe[0], np.zeros(d))
    assert np.linalg.norm(safe[1]) == pytest.approx(0.5, rel=1e-5)
    np.testing.assert_allclose(safe[2], np.asarray(small), rtol=1e-6)


def test_guard_config_validation():
    with pytest.raises(ValueError, match="aggregator_guard"):
        EngineConfig(aggregator_guard="mean")
    with pytest.raises(ValueError, match="client_chunk"):
        EngineConfig(aggregator_guard="trimmed_mean", client_chunk=4)
    with pytest.raises(ValueError, match="virtual"):
        EngineConfig(aggregator_guard="median", virtual_data=True)
    with pytest.raises(ValueError, match="sum"):
        EngineConfig(aggregator_guard="median", weighting="sum")
    with pytest.raises(ValueError, match="guard_trim"):
        EngineConfig(aggregator_guard="trimmed_mean", guard_trim=0.5)
    with pytest.raises(ValueError, match="guard_clip_norm"):
        EngineConfig(aggregator_guard="clip", guard_clip_norm=0.0)
    with pytest.raises(ValueError, match="clip"):
        EngineConfig(guard_clip_norm=1.0)
