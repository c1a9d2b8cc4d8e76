"""Unified federated round engine — the paper's round template (§1, §3).

Every algorithm in this repo follows the same communication pattern:

  1. (algorithm) server broadcasts state to clients
  2. clients compute local updates in parallel         — vmap over buckets
  3. server samples/weights the participating clients  — full or i.i.d. partial
  4. server aggregates deltas and applies the update   — uniform / n_k/n /
                                                         A-scaled (Pallas)

Steps 2–4 are algorithm-independent: FSVRG (Alg. 4), naive SVRG (Alg. 3),
FedAvg, and distributed GD differ only in the *client pass* that produces the
per-client deltas ``w_k − w`` and in the weighting/scaling choices.  The
``RoundEngine`` owns steps 2–4 so algorithms supply one function instead of
hand-rolling the loop (the pre-refactor state: four divergent copies).

Aggregation is pluggable:

  * ``weighting``      — ``"nk"`` (n_k/n, the paper's mod. 2), ``"uniform"``
                          (1/K), or ``"sum"`` (weight 1 per client — the plain
                          Σ_k used by dual methods, where each delta already
                          carries its own normalization)
  * ``server_scaling`` — ``"none"`` or ``"diag"`` (A = Diag(K/ω), mod. 4)
  * ``aggregator``     — ``"dense"`` (eager jnp weighted sum, the reference
                          path) or ``"pallas"`` (one HBM pass over the stacked
                          client deltas via ``kernels.scaled_aggregate``)

Algorithms whose clients carry *auxiliary per-client state* across rounds —
CoCoA+'s dual blocks α_k, the Primal Method's perturbation vectors g_k —
use :meth:`RoundEngine.round_with_state`: the client pass receives and
returns the bucket's state alongside the deltas, and under partial
participation the engine freezes the state of exactly the clients whose
aggregation weight the same Bernoulli draw zeroed.

Partial participation samples clients i.i.d. with probability
``participation`` per round and reweights the aggregate by
(expected mass / realized mass) so the update direction stays unbiased —
the deployment reality the paper motivates in §1.2 (devices participate
only when charging / on wi-fi).  ``weighting="sum"`` is exempt from the
reweighting: dual methods need the plain sum of the participants' deltas,
matching their frozen dual blocks exactly.  Each round's Bernoulli masks
are drawn **once** (:meth:`RoundEngine.participation_masks`) and shared by
state freezing and aggregation — one draw, two consumers, bit-identical to
the historical re-derivation by construction (same ``fold_in`` chain).

The single Bernoulli draw is itself pluggable: a **participation model**
(``repro.fleet.participation``) handed to the engine replaces the draw
with arbitrary per-round per-client masks — diurnal availability traces,
correlated dropout bursts, stragglers — while every consumer downstream
(weight zeroing, reweighting, dual-state freezing, the cohort gather) is
unchanged, because they only ever see the mask list.  Round-dependent
models need the round index, so every round entry point (and the compiled
closures) accepts ``round_index``; solvers forward ``state.round``, and
``cfg.participation`` becomes the model's *upper-bound* rate used for
cohort capacity sizing (the model owns the actual draw).

Because rounds are the scarce resource (§1: "minimizing the number of
rounds of communication is the principal goal"), the per-round server work
should be a *constant number of compiled dispatches*, not a Python loop of
per-bucket calls.  :meth:`RoundEngine.compile` /
:meth:`RoundEngine.compile_with_state` return jitted round closures — the
per-bucket ``fold_in`` offsets are precomputed, the client passes and the
aggregation run inside a single ``jax.jit`` (with donated iterate/state
buffers off-CPU), and an optional eager ``prelude`` carries per-round
server state (e.g. FSVRG's full gradient — its own round of communication
in the paper, so it stays outside the jitted body; the compiled round then
tracks the eager reference to tight float tolerance — bit-identically on
single-bucket problems, where the jit has no cross-bucket aggregation sum
to re-associate).  Every solver's ``round``
calls its compiled closure; :meth:`round` / :meth:`round_with_state` stay
as the eager reference implementations the pin tests compare against.

The paper's defining regime is *massively distributed* — §4 runs K=10,000
clients.  Materializing every bucket's (Kb, d) delta stack is O(K·d) peak
memory, which is exactly what breaks first at that K.  With
``EngineConfig.client_chunk`` set, rounds **stream** the client axis
instead (:meth:`round_streamed` / :meth:`round_streamed_with_state`): each
bucket's pass runs over chunk-sized client slices under ``lax.scan``,
accumulating the weighted delta sum (a (d,) vector) chunk by chunk —
O(client_chunk·d) peak delta memory — and ``compile`` traces the streamed
path inside the same single ``jax.jit``.  The per-client key split is
hoisted into the engine (:meth:`client_keys`) so chunked rounds consume
the *same* per-client randomness as the reference and differ only in
summation order (float tolerance, not bit-for-bit).

Streaming fixes the *memory* axis; ``EngineConfig.cohort`` fixes the
*compute* axis.  Under partial participation the masked paths still run
every client's pass and zero the non-participants' weights — at the
paper's ~10% participation that wastes ~90% of round flops.  The cohort
path (:meth:`round_cohort` / :meth:`round_cohort_with_state`) reuses the
round's single Bernoulli draw to *gather* only the sampled clients' rows,
weights, per-client keys, and aux-state slices into a padded
fixed-capacity bucket (static shapes under jit — size the capacity with
:func:`cohort_capacity`), runs passes + aggregation over O(C·K) clients,
and scatters dual state back.  Reweighting still sees the full weight and
mask vectors, so the unbiasedness contract is identical to the masked
reference; a capacity-overflowing draw falls back per-bucket to the
masked pass via ``lax.cond``.  ``compile``/``compile_with_state`` trace
the cohort body whenever ``cohort`` is set and participation < 1.0,
composing with ``client_chunk`` (the gathered cohort is streamed).

Streaming and cohorts bound the *compute* and *delta* memory, but the
bucket rows themselves were still materialized up front — O(n·nnz), the
last axis that breaks at the paper's thesis scale ("as many nodes as
users of the service": K=10⁶).  ``EngineConfig.virtual_data`` removes it:
the problem carries a :class:`~repro.core.problem.VirtualLayout`
(``build_virtual_problem``) whose buckets hold only (client_ids, n_k,
m_pad), and every round path **regenerates the rows it is about to
consume inside the traced body** — the streamed path materializes one
chunk's rows per ``lax.scan`` step (peak data memory
O(client_chunk·m_pad·nnz) regardless of K), the cohort path generates
rows only for the gathered cohort, and the plain paths realize one
bucket at a time.  The per-client seeding contract
(``fold_in(base, k)`` per client, ``fold_in`` per row) makes regenerated
rows bit-for-bit equal to the materialized dataset's, so virtual rounds
match materialized rounds exactly per client and to float tolerance on
iterates (the usual summation-order calibration).

Unreliable devices don't just disappear (the participation layer) — they
also send garbage.  A **fault model** (``repro.fleet.faults``) handed to
the engine corrupts each round path's deltas between the client pass and
aggregation, as a pure function of ``(seed, round_index, client_id)`` —
the wire, not the client: dual state is whatever the honest pass computed.
``EngineConfig.aggregator_guard`` is the server's defense: ``"clip"``
(per-client non-finite rejection + norm capping, folded into every path
including the streamed chunk entries) or coordinate-wise
``"trimmed_mean"`` / ``"median"`` over the materialized delta stacks
(:func:`robust_aggregate`, plain and cohort paths only — the config
rejects combinations whose stacks are never materialized).  With
``fault_model=None`` and ``aggregator_guard=None`` every path is
bit-for-bit the pre-fault engine (no extra scan inputs, no extra traced
ops) — the parity the pin tests hold.
"""
from __future__ import annotations

import copy
import dataclasses
import functools
import math
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.problem import ClientBucket, FederatedLogReg, VirtualBucket
from repro.utils import obs

#: client_pass(w, bucket_index, bucket, key) -> (Kb, d) deltas w_k - w
ClientPassFn = Callable[[jax.Array, int, ClientBucket, jax.Array], jax.Array]

#: dual_pass(w, bucket_index, bucket, state_b, key) -> (deltas, new_state_b);
#: state_b is any pytree of arrays with a leading client axis (Kb, ...)
DualClientPassFn = Callable[
    [jax.Array, int, ClientBucket, Any, jax.Array], Tuple[jax.Array, Any]]

#: chunk_pass(w, bucket_index, chunk_bucket, keys) -> (chunk, d) deltas.
#: The streamed round hands the pass a chunk-sized slice of the bucket and
#: the matching slice of ``split(bucket_key, Kb)`` — the exact per-client
#: keys the unchunked pass derives internally, so chunked and reference
#: rounds differ only in summation order.
ChunkClientPassFn = Callable[
    [jax.Array, int, ClientBucket, jax.Array], jax.Array]

#: dual chunk_pass(w, bucket_index, chunk_bucket, state_chunk, keys)
#: -> (deltas, new_state_chunk)
DualChunkClientPassFn = Callable[
    [jax.Array, int, ClientBucket, Any, jax.Array], Tuple[jax.Array, Any]]

_WEIGHTINGS = ("nk", "uniform", "sum")
_SCALINGS = ("none", "diag")
_AGGREGATORS = ("dense", "pallas")
_GUARDS = ("clip", "trimmed_mean", "median")
_ORDER_STAT_GUARDS = ("trimmed_mean", "median")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Round-scheduling knobs shared by every federated algorithm."""

    participation: float = 1.0     # i.i.d. per-round client participation prob
    weighting: str = "nk"          # "nk" (n_k/n) | "uniform" (1/K) | "sum" (1)
    server_scaling: str = "none"   # "none" | "diag" (apply a_diag coordinatewise)
    aggregator: str = "dense"      # "dense" | "pallas" (scaled_aggregate kernel)
    # None -> materialize each bucket's full (Kb, d) delta stack (the
    # bit-exact reference path).  An int streams the client axis instead:
    # each bucket's pass runs over client chunks of this size via lax.scan,
    # accumulating the weighted delta *sum* (a (d,) vector) chunk by chunk,
    # so peak delta memory is O(client_chunk·d) — the paper-scale K=10,000
    # regime on a CPU box.  Chunked rounds match the reference to float
    # tolerance (summation order), not bit-for-bit.
    client_chunk: Optional[int] = None
    # None -> under partial participation, every client's pass still runs
    # and the Bernoulli draw merely zeroes non-participants' weights.  An
    # int caps the *computed* cohort instead: each bucket gathers only the
    # sampled clients, padded to a fixed per-bucket capacity
    # min(cohort, Kb, cohort_capacity(participation, Kb)) so jit shapes
    # stay static, and runs passes + aggregation over O(participation·K)
    # clients.  Size the ceiling with :func:`cohort_capacity` on the
    # largest bucket; a draw that overflows the capacity falls back to
    # the masked full-bucket pass for that bucket (lax.cond), so results
    # never depend on the capacity.  No-op at participation=1.0.
    cohort: Optional[int] = None
    # False -> buckets carry materialized rows (ClientBucket).  True -> the
    # problem was built by build_virtual_problem: buckets are VirtualBucket
    # specs and every round path regenerates the rows it consumes inside
    # the traced body through problem.virtual — one chunk (or one gathered
    # cohort) at a time, so peak data memory is independent of K.
    virtual_data: bool = False
    # None -> trust every returned delta bit-for-bit (the historical path).
    # "clip" -> per-client robustness folded into every round path: a client
    # whose delta has any non-finite coordinate is rejected (delta zeroed —
    # it counts as "returned no update" while keeping its weight in the
    # realized mass, so the reweight scalar is unchanged), and
    # guard_clip_norm caps each surviving delta's L2 norm.  Both are
    # per-client scalars, so they fold into the streamed fused_accumulate
    # chunk entries at O(chunk·d).  "trimmed_mean" / "median" ->
    # coordinate-wise order statistics over the valid (participating,
    # all-finite) clients via robust_aggregate — a bounded fraction
    # of adversarial deltas cannot move the aggregate arbitrarily.  Order
    # statistics need the materialized (K, d) stacks, so these are rejected
    # with client_chunk / virtual_data (the streamed body only ever holds
    # one chunk and a running sum — a sort cannot be folded chunk-by-chunk)
    # and with weighting="sum" (dual iterates must track the frozen dual
    # blocks through the exact plain sum); they are unweighted by
    # construction and skip participation reweighting.
    aggregator_guard: Optional[str] = None
    # L2 norm cap per client delta; requires aggregator_guard="clip".
    guard_clip_norm: Optional[float] = None
    # per-side trim fraction for aggregator_guard="trimmed_mean".
    guard_trim: float = 0.1

    @staticmethod
    def _check_optional_count(value, name: str):
        # NB: bool is a subclass of int, so isinstance(True, int) is true —
        # reject bools explicitly or cohort=True silently means cohort=1.
        if value is not None and (
                isinstance(value, bool) or not isinstance(value, int)
                or value < 1):
            raise ValueError(f"{name} must be a positive int or None")

    def __post_init__(self):
        if self.weighting not in _WEIGHTINGS:
            raise ValueError(f"weighting must be one of {_WEIGHTINGS}")
        if self.server_scaling not in _SCALINGS:
            raise ValueError(f"server_scaling must be one of {_SCALINGS}")
        if self.aggregator not in _AGGREGATORS:
            raise ValueError(f"aggregator must be one of {_AGGREGATORS}")
        if not 0.0 < self.participation <= 1.0:
            raise ValueError("participation must be in (0, 1]")
        self._check_optional_count(self.client_chunk, "client_chunk")
        self._check_optional_count(self.cohort, "cohort")
        if not isinstance(self.virtual_data, bool):
            raise ValueError("virtual_data must be a bool")
        if (self.aggregator_guard is not None
                and self.aggregator_guard not in _GUARDS):
            raise ValueError(f"aggregator_guard must be one of {_GUARDS} "
                             "or None")
        if self.aggregator_guard in _ORDER_STAT_GUARDS:
            if self.client_chunk is not None:
                raise ValueError(
                    f"aggregator_guard='{self.aggregator_guard}' needs the "
                    "materialized (K, d) delta stacks; the streamed path "
                    "(client_chunk) only ever holds one chunk and a running "
                    "sum, and order statistics cannot be folded "
                    "chunk-by-chunk — use the plain or cohort path, or "
                    "aggregator_guard='clip'")
            if self.virtual_data:
                raise ValueError(
                    f"aggregator_guard='{self.aggregator_guard}' is not "
                    "available with virtual_data (virtual rounds never "
                    "materialize the full delta stacks) — use "
                    "aggregator_guard='clip'")
            if self.weighting == "sum":
                raise ValueError(
                    "order-statistic guards replace the weighted sum with "
                    "an unweighted coordinate-wise statistic; "
                    "weighting='sum' (dual methods tracking frozen dual "
                    "blocks) requires the exact plain sum — use "
                    "aggregator_guard='clip'")
        if not 0.0 <= self.guard_trim < 0.5:
            raise ValueError("guard_trim must be in [0, 0.5)")
        if self.guard_clip_norm is not None:
            if (isinstance(self.guard_clip_norm, bool)
                    or not isinstance(self.guard_clip_norm, (int, float))
                    or self.guard_clip_norm <= 0):
                raise ValueError(
                    "guard_clip_norm must be a positive number or None")
            if self.aggregator_guard != "clip":
                raise ValueError(
                    "guard_clip_norm requires aggregator_guard='clip'")


@functools.partial(jax.jit, static_argnames=("scaled",))
def _apply_server_update(w, agg, a_diag, scaled: bool):
    return w + (a_diag if scaled else 1.0) * agg


def robust_aggregate(w_t, deltas, valid, a_diag, trim=0.1,
                     mode="trimmed_mean"):
    """w^t + A ⊙ robust_agg({δ_k : valid_k}), in f32 — the order-statistic
    server update behind ``EngineConfig.aggregator_guard``.

    ``robust_agg`` is the coordinate-wise trimmed mean (drop the
    ``trim``-fraction smallest and largest per coordinate, average the
    rest) or median over the valid rows.  Invalid rows (non-participants,
    guard-rejected non-finite deltas) are sorted past the rank window via
    a +inf sentinel, so the dynamic valid count ``m`` sets the window and
    one expression serves both modes (the median is the 1- or 2-rank
    trimmed mean):

        trimmed mean:  lo = floor(trim·m),  hi = m − lo
        median:        lo = (m−1)//2,       hi = m//2 + 1

    The sort is XLA's on every backend: Mosaic has no sort lowering, so
    there is no Pallas kernel for this reduction."""
    if mode not in _ORDER_STAT_GUARDS:
        raise ValueError(f"mode must be one of {_ORDER_STAT_GUARDS}")
    if not 0.0 <= trim < 0.5:
        raise ValueError("trim must be in [0, 0.5)")
    x = jnp.where(valid.reshape(-1, 1) > 0, deltas.astype(jnp.float32),
                  jnp.inf)
    xs = jnp.sort(x, axis=0)
    m = valid.astype(jnp.int32).sum()
    if mode == "median":
        lo = (m - 1) // 2
        hi = m // 2 + 1
    else:
        lo = jnp.floor(jnp.asarray(trim, jnp.float32)
                       * m.astype(jnp.float32)).astype(jnp.int32)
        hi = m - lo
    ranks = jnp.arange(xs.shape[0])[:, None]
    inc = (ranks >= lo) & (ranks < hi)
    cnt = jnp.maximum(hi - lo, 1).astype(jnp.float32)
    agg = jnp.where(inc, xs, 0.0).sum(axis=0) / cnt
    agg = jnp.where(m > 0, agg, 0.0)                # empty round: no update
    return w_t.astype(jnp.float32) + a_diag.astype(jnp.float32) * agg


def _prelude(prelude: Optional[Callable], w) -> tuple:
    """The compiled round's eager per-round server state, ``prelude(w)``,
    under the host span ``fl.prelude``; () without a prelude."""
    if prelude is None:
        return ()
    with obs.span("fl.prelude"):
        return tuple(prelude(w))


def _kernel(name: str) -> Callable:
    """Resolve a delta-native aggregation kernel for this backend — the
    Pallas entry on TPU, the identical fused jnp oracle elsewhere (the same
    auto policy as the solvers' ``use_kernel``; interpret-mode emulation is
    for the parity tests, never the hot path)."""
    if jax.default_backend() == "tpu":
        from repro.kernels import ops
        return getattr(ops, name)
    from repro.kernels import ref
    return getattr(ref, name + "_ref")


def cohort_capacity(participation: float, num_clients: int, *,
                    z: float = 6.0) -> int:
    """Static per-bucket cohort capacity for ``EngineConfig.cohort``.

    The realized cohort is Binomial(Kb, participation); a capacity of
    mean + z·σ (+1) covers the draw with overwhelming probability (z=6 ⇒
    overflow odds ~1e-9 per bucket per round), so the lax.cond fallback to
    the masked full-bucket pass is for correctness, not a path that ever
    runs in practice.  Pass the *largest* bucket's client count — the
    engine right-sizes every bucket's gather on its own to
    ``min(cohort, Kb, cohort_capacity(participation, Kb))``, so the knob
    only needs to be a safe ceiling.
    """
    if not 0.0 < participation <= 1.0:
        raise ValueError("participation must be in (0, 1]")
    if num_clients < 1:
        raise ValueError("num_clients must be >= 1")
    mean = participation * num_clients
    sd = math.sqrt(participation * (1.0 - participation) * num_clients)
    return max(1, min(num_clients, int(math.ceil(mean + z * sd)) + 1))


class RoundEngine:
    """Owns client sampling, the vmap-over-bucket client pass, and server
    aggregation.  Algorithms provide a :data:`ClientPassFn`; the engine never
    looks inside the deltas it aggregates."""

    def __init__(self, problem: FederatedLogReg, cfg: EngineConfig = EngineConfig(),
                 *, a_diag: Optional[jax.Array] = None,
                 participation_model: Optional[Any] = None,
                 fault_model: Optional[Any] = None):
        self.problem = problem
        self.cfg = cfg
        if participation_model is not None and not hasattr(
                participation_model, "masks"):
            raise ValueError(
                "participation_model must implement "
                "masks(key, round_index, offsets, sizes) — see "
                "repro.fleet.participation.ParticipationModel")
        self.participation_model = participation_model
        if fault_model is not None and not hasattr(fault_model, "apply"):
            raise ValueError(
                "fault_model must implement "
                "apply(deltas, round_index, client_ids) — see "
                "repro.fleet.faults.FaultModel")
        self.fault_model = fault_model
        if cfg.server_scaling == "diag" and a_diag is None:
            raise ValueError("server_scaling='diag' requires an a_diag")
        layout = getattr(problem, "virtual", None)
        if cfg.virtual_data and layout is None:
            raise ValueError(
                "virtual_data=True requires a problem built by "
                "build_virtual_problem (problem.virtual is the layout)")
        if layout is not None and not cfg.virtual_data:
            raise ValueError(
                "the problem carries a virtual layout (no materialized "
                "rows); set EngineConfig(virtual_data=True) to run rounds "
                "on it")
        self._virtual = layout if cfg.virtual_data else None
        self.a_diag = jnp.ones((problem.d,)) if a_diag is None else a_diag
        # per-bucket first-client index — the fold_in offset of every bucket's
        # round key, precomputed once so compiled rounds close over constants
        wi = 0
        offsets = []
        for b in problem.buckets:
            offsets.append(wi)
            wi += b.num_clients
        self._offsets = tuple(offsets)
        self._sizes = tuple(b.num_clients for b in problem.buckets)

    def _round_index_arg(self, round_index):
        """Normalize the round index the masks are drawn for.  ``None`` is
        the legacy calling convention — fine for the Bernoulli draw and any
        round-invariant model, an error for round-dependent ones (traces),
        whose masks are a function of ``(seed, r)`` by contract."""
        if round_index is None:
            if (self.participation_model is not None and
                    getattr(self.participation_model, "needs_round_index",
                            False)):
                raise ValueError(
                    "this engine's participation model is round-dependent; "
                    "pass round_index (solvers forward state.round)")
            if (self.fault_model is not None and
                    getattr(self.fault_model, "needs_round_index", True)):
                raise ValueError(
                    "this engine has a fault model; fault draws are a "
                    "function of the round by contract — pass round_index "
                    "(solvers forward state.round)")
            return jnp.asarray(0, jnp.int32)
        return jnp.asarray(round_index, jnp.int32)

    def _realize(self, bucket):
        """Materialize a virtual bucket's rows through the problem's
        layout (traceable — this is the call that runs *inside* scan/cond
        bodies so only the about-to-be-consumed rows are ever live).
        No-op on an already-materialized :class:`ClientBucket`."""
        if self._virtual is not None and isinstance(bucket, VirtualBucket):
            return self._virtual.realize(bucket)
        return bucket

    # -- fault injection & per-client guard ------------------------------- #

    def _bucket_ids(self, wi: int, num_clients: int) -> jax.Array:
        """Global client ids for the bucket whose first client is ``wi`` —
        the identity the fault model's draws fold in, so the same clients
        are corrupted identically on every round path."""
        return jnp.uint32(wi) + jnp.arange(num_clients, dtype=jnp.uint32)

    def _fault_round(self, round_index) -> Optional[jax.Array]:
        """The round index fault draws are a function of — ``None`` (and
        zero traced overhead) when no fault model is installed."""
        if self.fault_model is None:
            return None
        return self._round_index_arg(round_index)

    @obs.scoped("fl.fault")
    def _faulted(self, deltas, r, ids, live):
        """Corrupt the *returned* clients' deltas through the fault model.

        ``live`` (weights or a {0,1} mask; ``None`` = everyone) restricts
        corruption to clients actually in the round: a client that never
        reports cannot deliver a corrupted delta — and a NaN planted on a
        zero-weight row would still poison the weighted sum (0·NaN = NaN),
        so the ``jnp.where`` *selects* the honest delta instead of relying
        on the weight to cancel it."""
        if self.fault_model is None:
            return deltas
        bad = self.fault_model.apply(deltas, r, ids)
        if live is None:
            return bad
        keep = live.reshape((-1,) + (1,) * (deltas.ndim - 1)) > 0
        return jnp.where(keep, bad, deltas)

    def _order_stat(self) -> bool:
        return self.cfg.aggregator_guard in _ORDER_STAT_GUARDS

    @obs.scoped("fl.guard")
    def _guard_clip(self, deltas):
        """The "clip" guard: reject (zero) any client delta with a
        non-finite coordinate, then cap the survivors' L2 norms.  Both are
        per-client transforms of a delta block of any leading shape, which
        is what lets them fold into the streamed chunk entries."""
        if self.cfg.aggregator_guard != "clip":
            return deltas
        finite = jnp.isfinite(deltas).all(axis=-1, keepdims=True)
        safe = jnp.where(finite, deltas, jnp.zeros_like(deltas))
        cn = self.cfg.guard_clip_norm
        if cn is not None:
            nrm = jnp.sqrt((safe.astype(jnp.float32) ** 2).sum(
                axis=-1, keepdims=True))
            fac = jnp.minimum(1.0, cn / jnp.maximum(nrm, 1e-30))
            safe = safe * fac.astype(safe.dtype)
        return safe

    @obs.scoped("fl.guard")
    def _robust_apply(self, w, deltas_all, valid):
        """Order-statistic server update over the stacked (K, d) deltas:
        rows that are invalid (non-participants) or carry any non-finite
        coordinate are excluded, and the coordinate-wise trimmed mean /
        median of the rest (:func:`robust_aggregate`) updates the
        iterate."""
        finite = jnp.isfinite(deltas_all).all(axis=1)
        valid = valid & finite
        a = (self.a_diag if self.cfg.server_scaling == "diag"
             else jnp.ones_like(w))
        return robust_aggregate(
            w, deltas_all, valid, a, self.cfg.guard_trim,
            self.cfg.aggregator_guard).astype(w.dtype)

    # -- step 3: sampling & weighting ------------------------------------- #

    def bucket_weights(self, wi: int, num_clients: int) -> jax.Array:
        """Aggregation weights for the bucket whose first client is ``wi``."""
        if self.cfg.weighting == "uniform":
            return jnp.full((num_clients,), 1.0 / self.problem.num_clients)
        if self.cfg.weighting == "sum":
            return jnp.ones((num_clients,))
        return self.problem.client_weights[wi : wi + num_clients]

    def participation_mask(self, bucket_key: jax.Array, num_clients: int) -> jax.Array:
        """i.i.d. Bernoulli(participation) mask, 1.0 = client is in-round."""
        return (jax.random.uniform(jax.random.fold_in(bucket_key, 997),
                                   (num_clients,))
                < self.cfg.participation).astype(jnp.float32)

    @obs.scoped("fl.sample")
    def participation_masks(self, key: jax.Array,
                            round_index: Optional[Any] = None
                            ) -> Optional[List[jax.Array]]:
        """The round's per-bucket participation masks, drawn **once** from
        the round key's ``fold_in`` chain — ``None`` under full
        participation.

        This is the single draw both consumers share: state freezing in
        :meth:`round_with_state` and weight zeroing in :meth:`aggregate`
        receive the same mask list instead of each re-deriving the same
        Bernoulli draw per bucket.

        With a ``participation_model`` installed, the draw is delegated to
        ``model.masks(key, round_index, offsets, sizes)`` — trace-driven
        availability/straggler masks instead of the i.i.d. Bernoulli, same
        contract (list of per-bucket float {0,1} vectors, or ``None`` for
        full participation).
        """
        if self.participation_model is not None:
            return self.participation_model.masks(
                key, self._round_index_arg(round_index), self._offsets,
                self._sizes)
        if self.cfg.participation >= 1.0:
            return None
        return [self.participation_mask(jax.random.fold_in(key, wi),
                                        b.num_clients)
                for wi, b in zip(self._offsets, self.problem.buckets)]

    # -- step 4: aggregation ----------------------------------------------- #

    def _reweightable(self, masks) -> bool:
        """Reweighting by expected/realized mass keeps the *average*
        direction unbiased; a "sum" aggregation must stay the plain partial
        sum — for dual methods each participant's delta enters exactly once
        so the primal iterate keeps tracking the
        (frozen-for-non-participants) dual blocks, w = (1/λn)Xα.  When this
        is False the mass reductions are skipped outright instead of being
        traced as dead computation into every compiled dual-method round."""
        return masks is not None and self.cfg.weighting != "sum"

    @staticmethod
    def _reweight_scale(total_mass, expected_mass):
        """The unbiased-participation reweight scalar (one definition for
        the materialized and streamed paths)."""
        return expected_mass / jnp.maximum(total_mass, 1e-9)

    @obs.scoped("fl.aggregate")
    def _finish_dense(self, w, agg, scale):
        if scale is not None:
            agg = agg * scale
        return _apply_server_update(w, agg, self.a_diag,
                                    self.cfg.server_scaling == "diag")

    @obs.scoped("fl.aggregate")
    def aggregate(self, w: jax.Array, deltas_by_bucket: Sequence[jax.Array],
                  key: jax.Array, *,
                  masks: Optional[Sequence[jax.Array]] = None) -> jax.Array:
        """Weight, subsample, reweight, scale, and apply the client deltas.

        ``deltas_by_bucket[i]`` is the (Kb, d) output of the client pass for
        bucket i; ``key`` must be the same round key handed to the passes so
        the participation draw is tied to the round.  ``masks`` are the
        round's precomputed :meth:`participation_masks`; if omitted they are
        drawn here from the same chain (bit-identical either way).
        """
        cfg = self.cfg
        pallas = cfg.aggregator == "pallas"
        if masks is None:
            masks = self.participation_masks(key)
        if self._order_stat():
            deltas_all = jnp.concatenate(list(deltas_by_bucket), axis=0)
            if masks is not None:
                valid = jnp.concatenate(list(masks)) > 0
            else:
                valid = jnp.ones((deltas_all.shape[0],), bool)
            return self._robust_apply(w, deltas_all, valid)
        reweight = self._reweightable(masks)
        agg = jnp.zeros_like(w)
        stacked: List[jax.Array] = []
        stacked_wts: List[jax.Array] = []
        total_mass = jnp.zeros(())
        expected_mass = jnp.zeros(())
        for i, (wi, b, deltas) in enumerate(zip(self._offsets,
                                                self.problem.buckets,
                                                deltas_by_bucket)):
            deltas = self._guard_clip(deltas)
            wts = self.bucket_weights(wi, b.num_clients)
            if masks is not None:
                sel = masks[i]
                if reweight:
                    total_mass = total_mass + (wts * sel).sum()
                    expected_mass = expected_mass + wts.sum()
                wts = wts * sel
            if pallas:
                stacked.append(deltas)
                stacked_wts.append(wts)
            else:
                agg = agg + (wts[:, None] * deltas).sum(axis=0)

        scale = self._reweight_scale(total_mass, expected_mass) \
            if reweight else None

        if pallas:
            # Delta-native single HBM pass: stacked deltas go to the kernel
            # as-is, with the reweight scalar and the A epilogue folded in —
            # no (K, d) w^t + δ materialization.  Same auto policy as the
            # solvers' use_kernel: the Pallas kernel on TPU, the identical
            # fused jnp expression elsewhere (interpret-mode emulation is
            # for the parity tests, not the hot path).
            wts_all = jnp.concatenate(stacked_wts)
            deltas_all = jnp.concatenate(stacked, axis=0)
            a = self.a_diag if cfg.server_scaling == "diag" else jnp.ones_like(w)
            s = scale if scale is not None else 1.0
            return _kernel("fused_aggregate")(
                w, deltas_all, wts_all, a, s).astype(w.dtype)

        return self._finish_dense(w, agg, scale)

    # -- steps 2-4: one full round ----------------------------------------- #

    def round(self, w: jax.Array, key: jax.Array,
              client_pass: ClientPassFn, *,
              round_index: Optional[Any] = None) -> jax.Array:
        """Run the client passes over every bucket, then aggregate.

        Each bucket's pass receives ``fold_in(key, wi)`` where ``wi`` is the
        bucket's first client index — the same key the round's single
        participation draw uses for that bucket.  ``round_index`` feeds
        round-dependent participation models (availability traces) and the
        fault model's draws; the Bernoulli draw ignores it.

        With a fault model installed, each bucket's deltas are corrupted
        between the pass and aggregation — the wire, not the client.
        """
        masks = self.participation_masks(key, round_index)
        r = self._fault_round(round_index)
        deltas: List[jax.Array] = []
        for bi, (wi, b) in enumerate(zip(self._offsets, self.problem.buckets)):
            with obs.scope("fl.client_pass"):
                kb = jax.random.fold_in(key, wi)
                d_b = client_pass(w, bi, self._realize(b), kb)
            if self.fault_model is not None:
                d_b = self._faulted(d_b, r, self._bucket_ids(wi, b.num_clients),
                                    masks[bi] if masks is not None else None)
            deltas.append(d_b)
        return self.aggregate(w, deltas, key, masks=masks)

    def round_with_state(self, w: jax.Array, states: Sequence[Any],
                         key: jax.Array, client_pass: DualClientPassFn, *,
                         round_index: Optional[Any] = None
                         ) -> Tuple[jax.Array, List[Any]]:
        """:meth:`round` for algorithms with per-client auxiliary state.

        ``states[i]`` is bucket i's state — any pytree of arrays whose leading
        axis is the bucket's client axis (e.g. CoCoA+'s dual blocks α_k of
        shape (Kb, m_pad), or the Primal Method's g_k of shape (Kb, d)).  The
        pass receives it alongside the bucket and returns the updated state
        with the deltas; deltas flow through the same :meth:`aggregate` path
        (weighting/scaling/participation) as stateless rounds.

        Under partial participation, a client whose aggregation weight is
        zeroed by the round's Bernoulli draw also keeps its previous state —
        the round's masks are drawn once (:meth:`participation_masks`) and
        handed to both state freezing and aggregation, so primal and dual
        views never diverge.
        """
        masks = self.participation_masks(key, round_index)
        r = self._fault_round(round_index)
        deltas: List[jax.Array] = []
        new_states: List[Any] = []
        for bi, (wi, b) in enumerate(zip(self._offsets, self.problem.buckets)):
            with obs.scope("fl.client_pass"):
                kb = jax.random.fold_in(key, wi)
                d_b, s_b = client_pass(w, bi, self._realize(b), states[bi],
                                       kb)
            if self.fault_model is not None:
                # the wire, not the client: the delta is corrupted, the
                # client's own aux state is whatever its pass computed
                d_b = self._faulted(d_b, r, self._bucket_ids(wi, b.num_clients),
                                    masks[bi] if masks is not None else None)
            if masks is not None:
                sel = masks[bi]
                with obs.scope("fl.state"):
                    s_b = jax.tree_util.tree_map(
                        lambda new, old: jnp.where(
                            sel.reshape((b.num_clients,)
                                        + (1,) * (new.ndim - 1)) > 0,
                            new, old),
                        s_b, states[bi])
            deltas.append(d_b)
            new_states.append(s_b)
        return self.aggregate(w, deltas, key, masks=masks), new_states

    # -- the streamed round: O(client_chunk · d) peak delta memory ---------- #

    def client_keys(self, bucket_key: jax.Array, num_clients: int) -> jax.Array:
        """The bucket's per-client keys — ``split(bucket_key, Kb)``, the
        exact split every client pass historically performed internally.
        The streamed round hoists it here so a chunk-sized pass can receive
        the *same* per-client keys the unchunked pass would have used."""
        return jax.random.split(bucket_key, num_clients)

    @staticmethod
    def _pad_clients(x: jax.Array, pad: int) -> jax.Array:
        if pad == 0:
            return x
        return jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)

    def _stream_bucket(self, w, bi: int, bucket: ClientBucket, kb, wts,
                       chunk_pass, state_b=None, sel=None, keys=None,
                       ids=None, r=None):
        """Run one bucket's client pass chunk-by-chunk, returning the
        bucket's weighted delta **sum** (a (d,) vector) and — for dual-state
        passes — the updated bucket state.

        The client axis is padded to a multiple of ``client_chunk`` with
        zero-weight, n_k = 0 clients (an exact no-op in the aggregate) and
        reshaped to (num_chunks, chunk, ...); ``lax.scan`` folds the chunks
        so only one (chunk, d) delta block is ever live.

        ``keys`` overrides the per-client key derivation — the cohort path
        streams a *gathered* bucket and must hand each gathered client the
        key it would have received at its original position, not a fresh
        ``split`` over the gathered axis.

        Under ``virtual_data`` the scan carries (client_ids, n_k) instead
        of rows, and the body regenerates the chunk's rows through the
        problem's :class:`~repro.core.problem.VirtualLayout` before the
        pass — only one (chunk, m_pad, nnz) row block is ever live, so
        peak data memory is independent of K.
        """
        virtual = (self._virtual is not None
                   and isinstance(bucket, VirtualBucket))
        Kb = bucket.num_clients
        chunk = min(self.cfg.client_chunk, Kb)
        pad = (-Kb) % chunk
        nch = (Kb + pad) // chunk
        if keys is None:
            keys = self.client_keys(kb, Kb)
        if pad:
            # padded clients carry weight 0; their key is never consumed in
            # a way that matters, but must be a valid key array
            keys = jnp.concatenate(
                [keys, jnp.broadcast_to(keys[:1], (pad,) + keys.shape[1:])])

        def chunked(x):
            x = self._pad_clients(x, pad)
            return x.reshape((nch, chunk) + x.shape[1:])

        if virtual:
            # padded clients have cid 0 but n_k 0 — client_rows_padded
            # zeroes all their rows, so they are exact no-ops downstream
            xs = {
                "cid": chunked(bucket.client_ids),
                "n_k": chunked(bucket.n_k),
                "keys": keys.reshape((nch, chunk) + keys.shape[1:]),
                "wts": chunked(wts),
            }
        else:
            xs = {
                "idx": chunked(bucket.idx), "val": chunked(bucket.val),
                "y": chunked(bucket.y), "n_k": chunked(bucket.n_k),
                "keys": keys.reshape((nch, chunk) + keys.shape[1:]),
                "wts": chunked(wts),
            }
        if state_b is not None:
            with obs.scope("fl.state"):
                xs["state"] = jax.tree_util.tree_map(chunked, state_b)
        if sel is not None:
            xs["sel"] = chunked(sel)
        if self.fault_model is not None:
            # the chunk's global client ids ride through the scan so fault
            # draws see the same identities as every other round path; the
            # xs entry only exists under a fault model, so fault-free scans
            # keep their historical structure (and bits) exactly.  Pad ids
            # are 0 but pad weights are 0, so _faulted leaves them honest.
            xs["ids"] = chunked(jnp.asarray(ids, jnp.uint32))
        fused = self.cfg.aggregator == "pallas"
        m_pad = bucket.m_pad

        def body(acc, x):
            with obs.scope("fl.client_pass"):
                if virtual:
                    cb = self._virtual.materialize(x["cid"], x["n_k"], m_pad)
                else:
                    cb = ClientBucket(x["idx"], x["val"], x["y"], x["n_k"])
                if state_b is None:
                    deltas = chunk_pass(w, bi, cb, x["keys"])
                    s_new = None
                else:
                    deltas, s_new = chunk_pass(w, bi, cb, x["state"],
                                               x["keys"])
                    if sel is not None:
                        with obs.scope("fl.state"):
                            s_new = jax.tree_util.tree_map(
                                lambda new, old: jnp.where(
                                    x["sel"].reshape((chunk,)
                                                     + (1,) * (new.ndim - 1))
                                    > 0, new, old),
                                s_new, x["state"])
            if self.fault_model is not None:
                # live = the chunk's (already sel-zeroed) weights: only
                # clients actually contributing to the sum can be faulted
                deltas = self._faulted(deltas, r, x["ids"], x["wts"])
            deltas = self._guard_clip(deltas)
            with obs.scope("fl.aggregate"):
                if fused:
                    # the kernel's init/acc split with an identity epilogue
                    acc = _kernel("fused_accumulate")(acc, deltas, x["wts"])
                else:
                    acc = acc + (x["wts"][:, None] * deltas).sum(axis=0)
            return acc, s_new

        acc, s_stack = jax.lax.scan(body, jnp.zeros_like(w), xs)
        if state_b is None:
            return acc, None
        with obs.scope("fl.state"):
            new_state = jax.tree_util.tree_map(
                lambda a: a.reshape((nch * chunk,) + a.shape[2:])[:Kb],
                s_stack)
        return acc, new_state

    def _streamed_round(self, w, key, chunk_pass, states, masks, *,
                        round_index=None):
        # The keyed-chunk-pass round body: per-bucket work goes through
        # _masked_bucket, which streams when cfg.client_chunk is set and
        # otherwise runs the direct keyed pass over the (realized) bucket —
        # so this one body serves round_streamed AND round_virtual.
        r = self._fault_round(round_index)
        reweight = self._reweightable(masks)
        acc = jnp.zeros_like(w)
        total_mass = jnp.zeros(())
        expected_mass = jnp.zeros(())
        new_states: Optional[List[Any]] = [] if states is not None else None
        for bi, (wi, b) in enumerate(zip(self._offsets, self.problem.buckets)):
            kb = jax.random.fold_in(key, wi)
            sel = masks[bi] if masks is not None else None
            with obs.scope("fl.aggregate"):
                wts = self.bucket_weights(wi, b.num_clients)
                if sel is not None:
                    if reweight:
                        total_mass = total_mass + (wts * sel).sum()
                        expected_mass = expected_mass + wts.sum()
                    wts = wts * sel
            with obs.scope("fl.client_pass"):
                acc_b, s_b = self._masked_bucket(
                    w, bi, b, kb, self.client_keys(kb, b.num_clients), wts,
                    sel, chunk_pass,
                    state_b=states[bi] if states is not None else None,
                    ids=(self._bucket_ids(wi, b.num_clients)
                         if self.fault_model is not None else None), r=r)
            with obs.scope("fl.aggregate"):
                acc = acc + acc_b
            if new_states is not None:
                new_states.append(s_b)
        return self._epilogue(w, acc, total_mass, expected_mass,
                              reweight), new_states

    @obs.scoped("fl.aggregate")
    def _epilogue(self, w, acc, total_mass, expected_mass, reweight):
        """The server update from the round's summed weighted deltas: the
        reweight scalar and the A scaling, folded into the kernel's
        epilogue under ``aggregator="pallas"``."""
        scale = self._reweight_scale(total_mass, expected_mass) \
            if reweight else None
        if self.cfg.aggregator == "pallas":
            a = (self.a_diag if self.cfg.server_scaling == "diag"
                 else jnp.ones_like(w))
            s = scale if scale is not None else 1.0
            return _kernel("fused_epilogue")(w, acc, a, s).astype(w.dtype)
        return self._finish_dense(w, acc, scale)

    def round_streamed(self, w: jax.Array, key: jax.Array,
                       chunk_pass: ChunkClientPassFn, *,
                       round_index: Optional[Any] = None) -> jax.Array:
        """:meth:`round` with the client axis streamed in ``client_chunk``
        chunks — the weighted delta sum accumulates chunk-by-chunk and the
        (Kb, d) stacks are never materialized.  Same weighting /
        participation / scaling semantics and the same per-client key chain
        as :meth:`round`; results agree to float tolerance (summation
        order), not bit-for-bit.
        """
        if self.cfg.client_chunk is None:
            raise ValueError("round_streamed requires cfg.client_chunk")
        w_next, _ = self._streamed_round(
            w, key, chunk_pass, None,
            self.participation_masks(key, round_index),
            round_index=round_index)
        return w_next

    def round_streamed_with_state(self, w: jax.Array, states: Sequence[Any],
                                  key: jax.Array,
                                  chunk_pass: DualChunkClientPassFn, *,
                                  round_index: Optional[Any] = None
                                  ) -> Tuple[jax.Array, List[Any]]:
        """:meth:`round_with_state`, streamed.  The pass receives chunk-sized
        state slices and the frozen-state masking applies per chunk with the
        round's single Bernoulli draw; bucket states are reassembled in
        client order, so only the (chunk, d) delta block is extra memory."""
        if self.cfg.client_chunk is None:
            raise ValueError("round_streamed_with_state requires "
                             "cfg.client_chunk")
        return self._streamed_round(w, key, chunk_pass, list(states),
                                    self.participation_masks(key, round_index),
                                    round_index=round_index)

    # -- the virtual round: rows regenerated inside the traced body --------- #

    def round_virtual(self, w: jax.Array, key: jax.Array,
                      chunk_pass: ChunkClientPassFn, *,
                      round_index: Optional[Any] = None) -> jax.Array:
        """:meth:`round` over on-demand data: each bucket's rows are
        regenerated through the problem's virtual layout inside the round
        body — chunk-by-chunk under ``lax.scan`` when ``client_chunk`` is
        set (peak data memory O(client_chunk·m_pad·nnz), the K=10⁶
        regime), one whole bucket at a time otherwise.  Same weighting /
        participation / key chain as :meth:`round`; per-client quantities
        are bit-for-bit (regenerated rows ARE the materialized rows),
        iterates match to float tolerance (summation order).
        """
        if not self.cfg.virtual_data:
            raise ValueError("round_virtual requires cfg.virtual_data")
        w_next, _ = self._streamed_round(
            w, key, chunk_pass, None,
            self.participation_masks(key, round_index),
            round_index=round_index)
        return w_next

    def round_virtual_with_state(self, w: jax.Array, states: Sequence[Any],
                                 key: jax.Array,
                                 chunk_pass: DualChunkClientPassFn, *,
                                 round_index: Optional[Any] = None
                                 ) -> Tuple[jax.Array, List[Any]]:
        """:meth:`round_with_state` over on-demand data — aux state still
        lives materialized (it is O(K·m_pad), the algorithm's own memory,
        not the dataset's); only the rows are regenerated."""
        if not self.cfg.virtual_data:
            raise ValueError("round_virtual_with_state requires "
                             "cfg.virtual_data")
        return self._streamed_round(w, key, chunk_pass, list(states),
                                    self.participation_masks(key, round_index),
                                    round_index=round_index)

    # -- the cohort round: O(participation · K) client passes --------------- #

    @obs.scoped("fl.aggregate")
    def _bucket_accumulate(self, w, deltas, wts):
        """One bucket's weighted delta sum as a (d,) vector — the fused
        kernel's accumulate entry under ``aggregator="pallas"``, the plain
        jnp weighted sum otherwise."""
        if self.cfg.aggregator == "pallas":
            return _kernel("fused_accumulate")(jnp.zeros_like(w), deltas, wts)
        return (wts[:, None] * deltas).sum(axis=0)

    def _masked_bucket(self, w, bi: int, bucket: ClientBucket, kb, keys,
                       wtsz, sel, chunk_pass, state_b=None, ids=None, r=None):
        """The masked reference body over the *keyed* chunk-pass contract:
        every client's pass runs, zero-weighted non-participants drop out of
        the sum, and dual state freezes where ``sel`` is 0.  This is both
        the cohort path's overflow fallback and its participation=1.0 /
        cap≥Kb degenerate case, so the two lax.cond branches share one
        aggregation recipe."""
        if self.cfg.client_chunk is not None:
            return self._stream_bucket(w, bi, bucket, kb, wtsz, chunk_pass,
                                       state_b=state_b, sel=sel, keys=keys,
                                       ids=ids, r=r)
        bucket = self._realize(bucket)
        if state_b is None:
            deltas = chunk_pass(w, bi, bucket, keys)
            s_new = None
        else:
            deltas, s_new = chunk_pass(w, bi, bucket, state_b, keys)
            if sel is not None:
                with obs.scope("fl.state"):
                    s_new = jax.tree_util.tree_map(
                        lambda new, old: jnp.where(
                            sel.reshape((bucket.num_clients,)
                                        + (1,) * (new.ndim - 1)) > 0,
                            new, old),
                        s_new, state_b)
        if self.fault_model is not None:
            deltas = self._faulted(deltas, r, ids, wtsz)
        deltas = self._guard_clip(deltas)
        return self._bucket_accumulate(w, deltas, wtsz), s_new

    def _cohort_bucket(self, w, bi: int, bucket: ClientBucket, kb, wts, sel,
                       chunk_pass, state_b=None, ids=None, r=None):
        """One bucket's contribution with only the sampled clients computed.

        The round's Bernoulli draw ``sel`` is turned into a gather: the
        (at most ``cap``, the bucket's own right-sized static capacity —
        see below) participating clients' rows,
        weights, per-client keys, and aux-state slices move into a padded
        fixed-capacity cohort bucket (static shapes for jit), the keyed
        chunk pass runs over that O(cap) bucket, and dual state scatters
        back to its original client slots — everyone else's state is
        untouched, which *is* the freezing contract.  Padding slots carry
        weight 0 and n_k = 0 (exact no-ops in the aggregate, same trick as
        the streamed path's pad clients) and scatter out of bounds (mode
        "drop").  A draw with more participants than ``cap`` takes the
        lax.cond fallback: the masked full-bucket pass, identical to the
        no-cohort round.
        """
        Kb = bucket.num_clients
        # per-bucket static capacity: cfg.cohort is a ceiling; each bucket
        # right-sizes its own gather to its Binomial(Kb, p) draw, so small
        # buckets don't inherit the largest bucket's capacity and compute
        # nearly all of their clients anyway
        cap = min(self.cfg.cohort, Kb,
                  cohort_capacity(self.cfg.participation, Kb)
                  if self.cfg.participation < 1.0 else Kb)
        keys = self.client_keys(kb, Kb)
        wtsz = wts * sel if sel is not None else wts
        if sel is None or cap >= Kb:
            # nothing to gain from gathering — run the masked reference body
            return self._masked_bucket(w, bi, bucket, kb, keys, wtsz, sel,
                                       chunk_pass, state_b=state_b,
                                       ids=ids, r=r)
        with obs.scope("fl.gather"):
            count = jnp.count_nonzero(sel > 0)

        def cohort_branch(_):
            with obs.scope("fl.gather"):
                gidx = jnp.nonzero(sel > 0, size=cap, fill_value=0)[0]
                valid = jnp.arange(cap) < count
                if (self._virtual is not None
                        and isinstance(bucket, VirtualBucket)):
                    # gather only the cohort's *identities*; their rows are
                    # regenerated below (realize / the streamed body) —
                    # data is only ever produced for the O(cap) sampled
                    # clients
                    g_bucket = VirtualBucket(
                        bucket.client_ids[gidx],
                        jnp.where(valid, bucket.n_k[gidx], 0), bucket.m_pad)
                else:
                    g_bucket = ClientBucket(
                        bucket.idx[gidx], bucket.val[gidx], bucket.y[gidx],
                        jnp.where(valid, bucket.n_k[gidx], 0))
                g_keys = keys[gidx]
                g_wts = jnp.where(valid, wtsz[gidx], 0.0)
                # gathered global ids: fault draws fold in the client's
                # original identity, so the cohort corrupts exactly the
                # clients the masked path would (pad rows alias ids[0] but
                # carry weight 0, so _faulted leaves them honest)
                g_ids = ids[gidx] if self.fault_model is not None else None
                g_state = None if state_b is None else jax.tree_util.tree_map(
                    lambda a: a[gidx], state_b)
            if self.cfg.client_chunk is not None:
                acc_b, s_new = self._stream_bucket(
                    w, bi, g_bucket, kb, g_wts, chunk_pass,
                    state_b=g_state, sel=None, keys=g_keys, ids=g_ids, r=r)
            elif state_b is None:
                deltas = chunk_pass(w, bi, self._realize(g_bucket), g_keys)
                if self.fault_model is not None:
                    deltas = self._faulted(deltas, r, g_ids, g_wts)
                acc_b = self._bucket_accumulate(w, self._guard_clip(deltas),
                                                g_wts)
                s_new = None
            else:
                deltas, s_new = chunk_pass(w, bi, self._realize(g_bucket),
                                           g_state, g_keys)
                if self.fault_model is not None:
                    deltas = self._faulted(deltas, r, g_ids, g_wts)
                acc_b = self._bucket_accumulate(w, self._guard_clip(deltas),
                                                g_wts)
            if state_b is None:
                return acc_b, None
            # scatter updated slices back to their original client slots;
            # padding rows target index Kb — out of bounds, dropped — and
            # non-gathered clients keep their old state (frozen).  Valid
            # gidx entries are unique, so the scatter is deterministic.
            with obs.scope("fl.gather"):
                scatter_idx = jnp.where(valid, gidx, Kb)
                new_state = jax.tree_util.tree_map(
                    lambda old, new: old.at[scatter_idx].set(new, mode="drop"),
                    state_b, s_new)
            return acc_b, new_state

        def masked_branch(_):
            return self._masked_bucket(w, bi, bucket, kb, keys, wtsz, sel,
                                       chunk_pass, state_b=state_b,
                                       ids=ids, r=r)

        return jax.lax.cond(count <= cap, cohort_branch, masked_branch, None)

    def _cohort_round(self, w, key, chunk_pass, states, masks, *,
                      round_index=None):
        """The cohort twin of :meth:`_streamed_round`: the same full-vector
        mass reductions (the reweighting contract never sees the gather —
        expected/realized mass come from the *complete* weight and mask
        vectors), with each bucket's delta sum produced by
        :meth:`_cohort_bucket` over only the sampled clients."""
        if self._order_stat():
            return self._cohort_round_robust(w, key, chunk_pass, states,
                                             masks, round_index=round_index)
        r = self._fault_round(round_index)
        reweight = self._reweightable(masks)
        acc = jnp.zeros_like(w)
        total_mass = jnp.zeros(())
        expected_mass = jnp.zeros(())
        new_states: Optional[List[Any]] = [] if states is not None else None
        for bi, (wi, b) in enumerate(zip(self._offsets, self.problem.buckets)):
            kb = jax.random.fold_in(key, wi)
            sel = masks[bi] if masks is not None else None
            with obs.scope("fl.aggregate"):
                wts = self.bucket_weights(wi, b.num_clients)
                if sel is not None and reweight:
                    total_mass = total_mass + (wts * sel).sum()
                    expected_mass = expected_mass + wts.sum()
            with obs.scope("fl.client_pass"):
                acc_b, s_b = self._cohort_bucket(
                    w, bi, b, kb, wts, sel, chunk_pass,
                    state_b=states[bi] if states is not None else None,
                    ids=(self._bucket_ids(wi, b.num_clients)
                         if self.fault_model is not None else None), r=r)
            with obs.scope("fl.aggregate"):
                acc = acc + acc_b
            if new_states is not None:
                new_states.append(s_b)
        return self._epilogue(w, acc, total_mass, expected_mass,
                              reweight), new_states

    def _cohort_round_robust(self, w, key, chunk_pass, states, masks, *,
                             round_index=None):
        """The cohort body under an order-statistic guard: instead of each
        bucket folding into a weighted (d,) sum, every bucket contributes
        its (cap, d) gathered delta stack plus a validity flag per row, and
        one :meth:`_robust_apply` call takes the coordinate-wise trimmed
        mean / median across all buckets' valid rows.

        Two deliberate departures from :meth:`_cohort_bucket`:

        * **No ``lax.cond`` overflow fallback.**  The fallback's masked
          branch produces a (Kb, d) stack while the cohort branch produces
          (cap, d) — ``lax.cond`` requires equal shapes, so it cannot
          exist here.  A draw overflowing the z=6-sized capacity (odds
          ~1e-9 per bucket-round — :func:`cohort_capacity`) instead drops
          the participants beyond ``cap`` from the round: they are treated
          exactly like non-participants (state frozen, excluded from the
          statistic), a graceful degradation rather than a wrong answer.
        * **No mass reductions.**  Order statistics are unweighted and
          need no participation reweighting (the statistic is location-,
          not mass-based).
        """
        r = self._fault_round(round_index)
        stacks: List[jax.Array] = []
        valids: List[jax.Array] = []
        new_states: Optional[List[Any]] = [] if states is not None else None
        for bi, (wi, b) in enumerate(zip(self._offsets, self.problem.buckets)):
            Kb = b.num_clients
            with obs.scope("fl.client_pass"):
                kb = jax.random.fold_in(key, wi)
                keys = self.client_keys(kb, Kb)
            sel = masks[bi] if masks is not None else None
            ids = (self._bucket_ids(wi, Kb)
                   if self.fault_model is not None else None)
            state_b = states[bi] if states is not None else None
            cap = min(self.cfg.cohort, Kb,
                      cohort_capacity(self.cfg.participation, Kb)
                      if self.cfg.participation < 1.0 else Kb)
            if sel is None or cap >= Kb:
                # degenerate case: the full keyed pass, whole-bucket stack
                with obs.scope("fl.client_pass"):
                    bucket = self._realize(b)
                    if state_b is None:
                        deltas = chunk_pass(w, bi, bucket, keys)
                        s_new = None
                    else:
                        deltas, s_new = chunk_pass(w, bi, bucket, state_b,
                                                   keys)
                        if sel is not None:
                            with obs.scope("fl.state"):
                                s_new = jax.tree_util.tree_map(
                                    lambda new, old: jnp.where(
                                        sel.reshape((Kb,)
                                                    + (1,) * (new.ndim - 1))
                                        > 0, new, old),
                                    s_new, state_b)
                if self.fault_model is not None:
                    deltas = self._faulted(deltas, r, ids, sel)
                stacks.append(deltas)
                with obs.scope("fl.aggregate"):
                    valids.append(sel > 0 if sel is not None
                                  else jnp.ones((Kb,), bool))
                if new_states is not None:
                    new_states.append(s_new)
                continue
            with obs.scope("fl.gather"):
                count = jnp.count_nonzero(sel > 0)
                gidx = jnp.nonzero(sel > 0, size=cap, fill_value=0)[0]
                gvalid = jnp.arange(cap) < count
                if self._virtual is not None and isinstance(b, VirtualBucket):
                    g_bucket = VirtualBucket(
                        b.client_ids[gidx],
                        jnp.where(gvalid, b.n_k[gidx], 0), b.m_pad)
                else:
                    g_bucket = ClientBucket(b.idx[gidx], b.val[gidx],
                                            b.y[gidx],
                                            jnp.where(gvalid, b.n_k[gidx], 0))
                g_keys = keys[gidx]
                g_ids = ids[gidx] if ids is not None else None
            with obs.scope("fl.client_pass"):
                if state_b is None:
                    deltas = chunk_pass(w, bi, self._realize(g_bucket),
                                        g_keys)
                    s_new = None
                else:
                    with obs.scope("fl.gather"):
                        g_state = jax.tree_util.tree_map(lambda a: a[gidx],
                                                         state_b)
                    deltas, s_new = chunk_pass(w, bi, self._realize(g_bucket),
                                               g_state, g_keys)
            if self.fault_model is not None:
                deltas = self._faulted(deltas, r, g_ids,
                                       gvalid.astype(jnp.float32))
            stacks.append(deltas)
            valids.append(gvalid)
            if new_states is not None:
                with obs.scope("fl.gather"):
                    scatter_idx = jnp.where(gvalid, gidx, Kb)
                    new_states.append(jax.tree_util.tree_map(
                        lambda old, new: old.at[scatter_idx].set(
                            new, mode="drop"),
                        state_b, s_new))
        with obs.scope("fl.aggregate"):
            w_next = self._robust_apply(w, jnp.concatenate(stacks, axis=0),
                                        jnp.concatenate(valids))
        return w_next, new_states

    def round_cohort(self, w: jax.Array, key: jax.Array,
                     chunk_pass: ChunkClientPassFn, *,
                     round_index: Optional[Any] = None) -> jax.Array:
        """:meth:`round` computing only the sampled cohort — same single
        Bernoulli draw, same weighting/reweighting/scaling semantics, same
        per-client key chain; results match the masked reference to float
        tolerance (summation order), not bit-for-bit.  At participation=1.0
        (or cap ≥ Kb) this degrades to the keyed full-bucket pass."""
        if self.cfg.cohort is None:
            raise ValueError("round_cohort requires cfg.cohort")
        w_next, _ = self._cohort_round(
            w, key, chunk_pass, None,
            self.participation_masks(key, round_index),
            round_index=round_index)
        return w_next

    def round_cohort_with_state(self, w: jax.Array, states: Sequence[Any],
                                key: jax.Array,
                                chunk_pass: DualChunkClientPassFn, *,
                                round_index: Optional[Any] = None
                                ) -> Tuple[jax.Array, List[Any]]:
        """:meth:`round_with_state` computing only the sampled cohort.  Aux
        state is gathered with the cohort and scattered back afterwards;
        non-participants' state is simply never touched, which coincides
        with the masked path's freezing bit-for-bit.  Cohort members'
        updates match the masked path to tight float tolerance (the
        overflow ``lax.cond`` compiles both branches, and XLA may round
        the per-client elementwise chain one ulp away from eager
        dispatch)."""
        if self.cfg.cohort is None:
            raise ValueError("round_cohort_with_state requires cfg.cohort")
        return self._cohort_round(w, key, chunk_pass, list(states),
                                  self.participation_masks(key, round_index),
                                  round_index=round_index)

    # -- the compiled round: O(1) dispatches per round ---------------------- #

    def _with_buckets(self, buckets) -> "RoundEngine":
        """This engine over ``buckets`` in place of the problem's own.

        The compiled rounds hand the bucket rows to their ``jax.jit`` as an
        argument and run on this view of them: a jitted function embeds
        every array it closes over as a program constant, and at the
        paper's widths the rows are ~1 GB."""
        view = copy.copy(self)
        view.problem = dataclasses.replace(self.problem,
                                           buckets=list(buckets))
        return view

    def _should_donate(self, donate: Optional[bool]) -> bool:
        # On by default on accelerators, where the iterate and state buffers
        # are reused in place; off on CPU, where rounds are small and tests
        # hand one array to several rounds.
        return jax.default_backend() != "cpu" if donate is None else donate

    def _require_chunk_pass(self, chunk_pass):
        if chunk_pass is None:
            raise ValueError(
                "cfg.client_chunk/cfg.cohort/cfg.virtual_data is set but no "
                "chunk_pass was supplied — streamed, cohort, and virtual "
                "rounds need the per-client-keyed chunk pass "
                "(chunk_pass(w, bi, chunk_bucket, keys, *ctx))")
        return chunk_pass

    def _use_cohort(self) -> bool:
        # Static dispatch: the gather only pays off when the draw actually
        # discards clients, so at participation=1.0 the knob is a no-op and
        # compile falls through to the streamed/materialized body.  A
        # participation model always counts as partial — its masks may drop
        # clients regardless of cfg.participation (which, with a model, is
        # the capacity-sizing bound, not the draw).
        return self.cfg.cohort is not None and (
            self.cfg.participation < 1.0
            or self.participation_model is not None)

    def compile(self, client_pass: Callable, *,
                prelude: Optional[Callable] = None,
                donate: Optional[bool] = None,
                chunk_pass: Optional[Callable] = None) -> Callable:
        """One federated round as a single compiled dispatch.

        Returns ``compiled_round(w, key) -> w_next``: the per-bucket client
        passes, the single participation draw, and the (optionally fused
        Pallas) aggregation all trace into one ``jax.jit`` over the
        precomputed ``fold_in`` offsets, with the iterate buffer donated on
        accelerator backends.

        ``prelude(w) -> tuple`` carries per-round *server* state — e.g.
        FSVRG's/DANE's full gradient, which the paper counts as its own round
        of communication.  It runs eagerly outside the jitted body (XLA
        fuses ``flat.grad`` differently under jit; keeping it out pins the
        compiled round to :meth:`round`, the reference implementation, up
        to the jit's re-association of the cross-bucket aggregation sum)
        and its results are appended to the pass's arguments:
        ``client_pass(w, bi, bucket, kb, *prelude(w))``.

        When ``cfg.client_chunk`` is set the same single ``jax.jit`` traces
        the **streamed** path (:meth:`round_streamed`) over ``chunk_pass``
        instead — peak delta memory O(client_chunk·d); :meth:`round` (and
        :meth:`reference`) stay the unchunked bit-exact reference.

        When ``cfg.cohort`` is set *and* participation < 1.0, the jitted
        body is the **cohort** path (:meth:`round_cohort`) over
        ``chunk_pass``: only the sampled clients' passes run — composed
        with ``client_chunk`` when both are set (the gathered cohort is
        streamed in chunks).

        Under ``cfg.virtual_data``, every dispatched body regenerates rows
        on demand (the cohort body generates only the gathered cohort's
        rows, the streamed body one chunk's rows per scan step); with
        neither ``cohort`` nor ``client_chunk`` set the jitted body is
        :meth:`round_virtual` over ``chunk_pass`` — bucket-at-a-time
        regeneration.
        """
        donate_args = (0,) if self._should_donate(donate) else ()

        if self._use_cohort():
            c_pass = self._require_chunk_pass(chunk_pass)

            def _round(eng, w, ctx, key, r):
                return eng.round_cohort(
                    w, key,
                    lambda w_, bi, cb, ks: c_pass(w_, bi, cb, ks, *ctx),
                    round_index=r)
        elif self.cfg.client_chunk is not None:
            c_pass = self._require_chunk_pass(chunk_pass)

            def _round(eng, w, ctx, key, r):
                return eng.round_streamed(
                    w, key,
                    lambda w_, bi, cb, ks: c_pass(w_, bi, cb, ks, *ctx),
                    round_index=r)
        elif self.cfg.virtual_data:
            c_pass = self._require_chunk_pass(chunk_pass)

            def _round(eng, w, ctx, key, r):
                return eng.round_virtual(
                    w, key,
                    lambda w_, bi, cb, ks: c_pass(w_, bi, cb, ks, *ctx),
                    round_index=r)
        else:

            def _round(eng, w, ctx, key, r):
                return eng.round(
                    w, key,
                    lambda w_, bi, b, kb: client_pass(w_, bi, b, kb, *ctx),
                    round_index=r)

        @functools.partial(jax.jit, donate_argnums=donate_args)
        def _body(w, ctx, key, r, buckets):
            return _round(self._with_buckets(buckets), w, ctx, key, r)

        def _args(w, key, round_index):
            return (w, _prelude(prelude, w), key,
                    self._round_index_arg(round_index),
                    tuple(self.problem.buckets))

        def compiled_round(w, key, round_index=None):
            args = _args(w, key, round_index)
            with obs.span("fl.dispatch"):
                return _body(*args)

        compiled_round.lower = lambda w, key, round_index=None: _body.lower(
            *_args(w, key, round_index))
        return compiled_round

    def reference(self, client_pass: Callable, *,
                  prelude: Optional[Callable] = None,
                  chunk_pass: Optional[Callable] = None) -> Callable:
        """The eager twin of :meth:`compile` — same calling convention,
        Python-loop dispatch through :meth:`round`.  The pin tests (and the
        round-latency benchmark's "eager dense" baseline) call this.

        Under ``cfg.virtual_data`` there are no per-bucket closures to
        reference (the rows don't exist until a round asks for them), so
        the eager path runs :meth:`round_virtual` over ``chunk_pass`` —
        bucket-at-a-time regeneration, Python-loop dispatch."""
        if self.cfg.virtual_data:
            c_pass = self._require_chunk_pass(chunk_pass)

            def reference_round(w, key, round_index=None):
                ctx = tuple(prelude(w)) if prelude is not None else ()
                return self.round_virtual(
                    w, key,
                    lambda w_, bi, cb, ks: c_pass(w_, bi, cb, ks, *ctx),
                    round_index=round_index)

            return reference_round

        def reference_round(w, key, round_index=None):
            ctx = tuple(prelude(w)) if prelude is not None else ()
            return self.round(
                w, key, lambda w_, bi, b, kb: client_pass(w_, bi, b, kb, *ctx),
                round_index=round_index)

        return reference_round

    def compile_with_state(self, dual_pass: Callable, *,
                           prelude: Optional[Callable] = None,
                           donate: Optional[bool] = None,
                           chunk_pass: Optional[Callable] = None) -> Callable:
        """:meth:`compile` for dual-state rounds.

        Returns ``compiled_round(w, states, key) -> (w_next, new_states)``
        over a tuple-of-pytrees ``states``; both the iterate and the state
        buffers are donated on accelerator backends.  With
        ``cfg.client_chunk`` set, the jitted body is the streamed
        :meth:`round_streamed_with_state` over ``chunk_pass``; with
        ``cfg.cohort`` set under partial participation it is the cohort
        :meth:`round_cohort_with_state` (aux state gathered with the
        cohort and scattered back).
        """
        donate_args = (0, 1) if self._should_donate(donate) else ()

        if self._use_cohort():
            c_pass = self._require_chunk_pass(chunk_pass)

            def _round(eng, w, states, ctx, key, r):
                return eng.round_cohort_with_state(
                    w, list(states), key,
                    lambda w_, bi, cb, s_c, ks: c_pass(w_, bi, cb, s_c, ks,
                                                       *ctx),
                    round_index=r)
        elif self.cfg.client_chunk is not None:
            c_pass = self._require_chunk_pass(chunk_pass)

            def _round(eng, w, states, ctx, key, r):
                return eng.round_streamed_with_state(
                    w, list(states), key,
                    lambda w_, bi, cb, s_c, ks: c_pass(w_, bi, cb, s_c, ks,
                                                       *ctx),
                    round_index=r)
        elif self.cfg.virtual_data:
            c_pass = self._require_chunk_pass(chunk_pass)

            def _round(eng, w, states, ctx, key, r):
                return eng.round_virtual_with_state(
                    w, list(states), key,
                    lambda w_, bi, cb, s_c, ks: c_pass(w_, bi, cb, s_c, ks,
                                                       *ctx),
                    round_index=r)
        else:

            def _round(eng, w, states, ctx, key, r):
                return eng.round_with_state(
                    w, list(states), key,
                    lambda w_, bi, b, s_b, kb: dual_pass(w_, bi, b, s_b, kb,
                                                         *ctx),
                    round_index=r)

        @functools.partial(jax.jit, donate_argnums=donate_args)
        def _body(w, states, ctx, key, r, buckets):
            w2, new_states = _round(self._with_buckets(buckets), w, states,
                                    ctx, key, r)
            return w2, tuple(new_states)

        def _args(w, states, key, round_index):
            return (w, tuple(states), _prelude(prelude, w), key,
                    self._round_index_arg(round_index),
                    tuple(self.problem.buckets))

        def compiled_round(w, states, key, round_index=None):
            args = _args(w, states, key, round_index)
            with obs.span("fl.dispatch"):
                return _body(*args)

        compiled_round.lower = (
            lambda w, states, key, round_index=None: _body.lower(
                *_args(w, states, key, round_index)))
        return compiled_round

    def reference_with_state(self, dual_pass: Callable, *,
                             prelude: Optional[Callable] = None,
                             chunk_pass: Optional[Callable] = None
                             ) -> Callable:
        """The eager twin of :meth:`compile_with_state` (see
        :meth:`reference` for the ``virtual_data`` dispatch)."""
        if self.cfg.virtual_data:
            c_pass = self._require_chunk_pass(chunk_pass)

            def reference_round(w, states, key, round_index=None):
                ctx = tuple(prelude(w)) if prelude is not None else ()
                w2, new_states = self.round_virtual_with_state(
                    w, list(states), key,
                    lambda w_, bi, cb, s_c, ks: c_pass(w_, bi, cb, s_c, ks,
                                                       *ctx),
                    round_index=round_index)
                return w2, tuple(new_states)

            return reference_round

        def reference_round(w, states, key, round_index=None):
            ctx = tuple(prelude(w)) if prelude is not None else ()
            w2, new_states = self.round_with_state(
                w, list(states), key,
                lambda w_, bi, b, s_b, kb: dual_pass(w_, bi, b, s_b, kb, *ctx),
                round_index=round_index)
            return w2, tuple(new_states)

        return reference_round
