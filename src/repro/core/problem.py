"""Federated finite-sum problem (eq. 1/7/8): sparse L2-regularized logistic
regression, stored in fixed-nnz sparse row format, partitioned over clients.

Provides the flat (all-data) objective/gradient used for evaluation and the
full-gradient round of FSVRG, plus a *bucketed* per-client layout: clients
are grouped by ceil(log2 n_k) so each bucket pads to its own max and local
passes run as `vmap(scan)` — the production answer to the paper's
"unbalanced" data characteristic.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def row_width(nnz: int) -> int:
    """Stored entries per example row for ``nnz`` features: the next power
    of two up to 128, else the next multiple of 128.  Rows are padded with
    ``idx = 0, val = 0`` entries, which every consumer already treats as
    absent.

    The TPU compiler lays the row axis across its 128-lane tiles; a width
    that does not divide them (62, the §4 rows: 60 words + bias + unknown)
    costs seconds of code generation for every gather, scatter and
    reshape of row arrays — 5 s for one (12,900, 62) gather, 0.5 s at
    width 64."""
    if nnz > 128:
        return -(-nnz // 128) * 128
    return 1 << max(nnz - 1, 0).bit_length()


def pad_rows(idx, val):
    """``(..., nnz)`` row arrays padded to :func:`row_width` entries (numpy
    in, numpy out; jax in, jax out)."""
    pad = row_width(idx.shape[-1]) - idx.shape[-1]
    if pad == 0:
        return idx, val
    xp = np if isinstance(idx, np.ndarray) else jnp
    widths = [(0, 0)] * (idx.ndim - 1) + [(0, pad)]
    return xp.pad(idx, widths), xp.pad(val, widths)


#: Largest dense (clients, d) block a client pass materializes at once:
#: 64 clients at the §4 width d = 20,002, chosen from a sweep of 32 to 256
#: on a TPU v5e.  A sequential step of a client pass costs about 55 µs up
#: to ~32 clients, and beyond that about 1.7 µs (FedAvg) to 2.4 µs (FSVRG)
#: a client, more still past 64 (128 clients: 2.3 and 2.5 µs).  So 64
#: cuts the short last batches of the small buckets at the same cost a
#: client, while 128 made the FedAvg round 25% slower.  The round's temp
#: grows with the block (+32 MB of 1.97 GB for the FSVRG round at 64
#: clients), and the TPU compiler's time for a scatter with its operand.
CLIENT_BLOCK_ELEMS = 1 << 21


def client_batch(d: int) -> int:
    """Clients per sequential batch of a client pass: the largest power of
    two whose (batch, d) f32 block holds at most ``CLIENT_BLOCK_ELEMS``."""
    b = max(1, CLIENT_BLOCK_ELEMS // max(d, 1))
    return 1 << (b.bit_length() - 1)


def map_clients(fn, xs, d: int):
    """``fn(*xs)`` over the leading client axis of every leaf of ``xs``, one
    batch of :func:`client_batch` ``(d)`` clients at a time under
    ``lax.map``, the last partial batch on its own.  ``fn`` maps a batch of
    clients to outputs with the same leading axis and must treat clients
    independently, so the result is ``fn(*xs)`` over the whole axis.

    Every sparse solver's client pass runs through here (FSVRG, FedAvg,
    DANE, CoCoA+, the GD baseline), as does ``scaling.omega``.  The batches
    run one after another.  Client passes build dense (clients, d) vectors
    by scatter, so a batch's local step costs time in proportion to its
    clients (above a floor), and the round's temp memory and the TPU
    compiler's time for a scatter grow with that operand (15 s for one
    6,478-client bucket at d = 20,002, under 1 s for 32 clients)."""
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    batch = client_batch(d)
    if n <= batch:
        return fn(*xs)
    full = n - n % batch
    head = jax.tree_util.tree_map(
        lambda x: x[:full].reshape((full // batch, batch) + x.shape[1:]), xs)
    out = jax.lax.map(lambda a: fn(*a), head)
    out = jax.tree_util.tree_map(
        lambda o: o.reshape((full,) + o.shape[2:]), out)
    if full == n:
        return out
    tail = fn(*jax.tree_util.tree_map(lambda x: x[full:], xs))
    return jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b]), out, tail)


@dataclasses.dataclass(frozen=True)
class LogRegProblem:
    """Flat sparse dataset + lambda, as jnp arrays."""

    idx: jax.Array   # (n, nnz) int32
    val: jax.Array   # (n, nnz) f32
    y: jax.Array     # (n,) f32 {-1,+1}
    lam: float
    num_features: int

    @property
    def n(self) -> int:
        return self.y.shape[0]

    def margins(self, w: jax.Array) -> jax.Array:
        return (self.val * w[self.idx]).sum(axis=1)

    def loss(self, w: jax.Array) -> jax.Array:
        z = self.y * self.margins(w)
        return jnp.mean(jax.nn.softplus(-z)) + 0.5 * self.lam * jnp.dot(w, w)

    def grad(self, w: jax.Array) -> jax.Array:
        z = self.y * self.margins(w)
        g_scalar = -self.y * jax.nn.sigmoid(-z) / self.n       # (n,)
        g = jnp.zeros_like(w).at[self.idx].add(g_scalar[:, None] * self.val)
        return g + self.lam * w

    def error_rate(self, w: jax.Array) -> jax.Array:
        # Deterministic tie-break: a zero margin predicts +1.  (jnp.sign(0)
        # is 0, which equals neither label — an all-zero iterate would be
        # "wrong" on every example of both classes.)
        preds = jnp.where(self.margins(w) >= 0, 1.0, -1.0)
        return jnp.mean((preds != self.y).astype(jnp.float32))


@dataclasses.dataclass(frozen=True)
class ClientBucket:
    """Clients padded to a common example count m_pad.

    idx/val: (Kb, m_pad, nnz); y: (Kb, m_pad); n_k: (Kb,) true sizes.
    Padded rows have val==0 and are masked in local passes.
    """

    idx: jax.Array
    val: jax.Array
    y: jax.Array
    n_k: jax.Array

    @property
    def num_clients(self) -> int:
        return self.n_k.shape[0]

    @property
    def m_pad(self) -> int:
        return self.y.shape[1]


# Buckets are pytrees so compiled rounds take their rows as arguments: a
# jitted function embeds every array it closes over as a program constant,
# which at the paper's widths is ~1 GB of rows baked into each round.
jax.tree_util.register_dataclass(
    ClientBucket, data_fields=["idx", "val", "y", "n_k"], meta_fields=[])


@dataclasses.dataclass(frozen=True)
class VirtualBucket:
    """A bucket of *virtual* clients: who they are and how many rows they
    have, but no rows — those regenerate on demand from the client ids
    (see :class:`VirtualLayout`).  Mirrors :class:`ClientBucket`'s
    ``num_clients``/``m_pad``/``n_k`` surface so engine bookkeeping
    (weights, offsets, masks) is layout-blind.
    """

    client_ids: jax.Array    # (Kb,) int32 global client ids
    n_k: jax.Array           # (Kb,) int32 true TRAIN sizes
    m_pad: int

    @property
    def num_clients(self) -> int:
        return self.n_k.shape[0]


jax.tree_util.register_dataclass(
    VirtualBucket, data_fields=["client_ids", "n_k"], meta_fields=["m_pad"])


@dataclasses.dataclass(frozen=True)
class VirtualLayout:
    """The bridge from virtual buckets to the rows the client passes eat.

    Wraps the :class:`~repro.data.synthetic.VirtualDataset` spec;
    ``materialize`` is traceable, so the engine can call it *inside* a
    ``lax.scan`` body to regenerate just one chunk's (or one gathered
    cohort's) rows — peak data memory O(chunk · m_pad · nnz) regardless
    of K.
    """

    vds: Any   # repro.data.synthetic.VirtualDataset

    def materialize(self, client_ids, n_k, m_pad: int) -> ClientBucket:
        idx, val, y = self.vds.client_rows_padded(client_ids, n_k, m_pad)
        idx, val = pad_rows(idx, val)
        return ClientBucket(idx, val, y, jnp.asarray(n_k, jnp.int32))

    def realize(self, vb: VirtualBucket) -> ClientBucket:
        return self.materialize(vb.client_ids, vb.n_k, vb.m_pad)


class VirtualFlat:
    """Flat-view twin over virtual data, streamed in client chunks.

    Provides what solvers and scaling actually consume from
    :class:`LogRegProblem` — ``lam``/``n``/``num_features``,
    ``grad``/``loss``/``error_rate`` — plus exact ``feature_counts``/
    ``omega`` for FSVRG's diagonal scalings, all computed by regenerating
    ``eval_chunk`` clients at a time inside a ``lax.scan`` (O(chunk·m_pad)
    live rows, never the full (n, nnz) arrays).  Per-row quantities use the
    exact :class:`LogRegProblem` expressions (``g_scalar = -y·σ(-z)/n``
    *before* the scatter), so only cross-row summation order differs from
    the materialized flat view — iterate-level parity is tight-tolerance,
    per-count quantities (feature_counts, omega, error counts) are exact.
    """

    def __init__(self, layout: VirtualLayout, buckets: List[VirtualBucket],
                 lam: float, num_features: int, n: int,
                 eval_chunk: int = 256):
        self.layout = layout
        self.lam = float(lam)
        self.num_features = int(num_features)
        self._n = int(n)
        self.eval_chunk = int(eval_chunk)
        # per-bucket (cids, nks) padded to a whole number of chunks; padded
        # clients have n_k == 0, so client_rows_padded zeroes all their rows
        # (idx 0 / val 0 / y 1) and they drop out of every masked reduction
        self._chunks: List[Tuple[jax.Array, jax.Array, int]] = []
        for vb in buckets:
            chunk = min(self.eval_chunk, vb.num_clients)
            nch = -(-vb.num_clients // chunk)
            pad = nch * chunk - vb.num_clients
            cid = jnp.concatenate(
                [vb.client_ids, jnp.zeros((pad,), vb.client_ids.dtype)])
            nk = jnp.concatenate([vb.n_k, jnp.zeros((pad,), vb.n_k.dtype)])
            self._chunks.append((cid.reshape(nch, chunk),
                                 nk.reshape(nch, chunk), vb.m_pad))
        self._stats_fns: Dict[int, Any] = {}
        self._count_fns: Dict[int, Any] = {}

    @property
    def n(self) -> int:
        return self._n

    def margins(self, w: jax.Array) -> jax.Array:
        raise NotImplementedError(
            "VirtualFlat has no materialized row axis; use loss/grad/"
            "error_rate, which stream over regenerated client chunks.")

    def _stats_fn(self, m_pad: int):
        fn = self._stats_fns.get(m_pad)
        if fn is None:
            vds, n, d = self.layout.vds, self._n, self.num_features

            @jax.jit
            def fn(w, cids, nks):
                def body(carry, x):
                    g, ls, err = carry
                    cid, nk = x
                    idx, val, y = vds.client_rows_padded(cid, nk, m_pad)
                    idx, val = pad_rows(idx, val)
                    mask = (jnp.arange(m_pad)[None, :]
                            < nk[:, None]).astype(jnp.float32)
                    margins = (val * w[idx]).sum(-1)
                    z = y * margins
                    g_scalar = -y * jax.nn.sigmoid(-z) / n
                    g = g.at[idx].add((g_scalar * mask)[..., None] * val)
                    ls = ls + (jax.nn.softplus(-z) * mask).sum()
                    preds = jnp.where(margins >= 0, 1.0, -1.0)
                    err = err + ((preds != y).astype(jnp.float32)
                                 * mask).sum()
                    return (g, ls, err), None

                init = (jnp.zeros((d,), w.dtype), jnp.float32(0.0),
                        jnp.float32(0.0))
                (g, ls, err), _ = jax.lax.scan(body, init, (cids, nks))
                return g, ls, err

            self._stats_fns[m_pad] = fn
        return fn

    def _stats(self, w: jax.Array):
        g = jnp.zeros((self.num_features,), jnp.float32)
        ls = jnp.float32(0.0)
        err = jnp.float32(0.0)
        for cids, nks, m_pad in self._chunks:
            bg, bl, be = self._stats_fn(m_pad)(w, cids, nks)
            g, ls, err = g + bg, ls + bl, err + be
        return g, ls, err

    def grad(self, w: jax.Array) -> jax.Array:
        return self._stats(w)[0] + self.lam * w

    def loss(self, w: jax.Array) -> jax.Array:
        return (self._stats(w)[1] / self._n
                + 0.5 * self.lam * jnp.dot(w, w))

    def error_rate(self, w: jax.Array) -> jax.Array:
        return self._stats(w)[2] / self._n

    def _count_fn(self, m_pad: int):
        fn = self._count_fns.get(m_pad)
        if fn is None:
            vds, d = self.layout.vds, self.num_features

            @jax.jit
            def fn(cids, nks):
                def body(carry, x):
                    cnt, om = carry
                    cid, nk = x
                    idx, val, _ = vds.client_rows_padded(cid, nk, m_pad)
                    idx, val = pad_rows(idx, val)
                    nz = (val != 0).astype(jnp.float32)
                    cnt = cnt.at[idx].add(nz)
                    chunk = cid.shape[0]
                    pres = jnp.zeros((chunk, d), jnp.float32).at[
                        jnp.arange(chunk)[:, None, None], idx].add(nz)
                    om = om + (pres > 0).astype(jnp.float32).sum(0)
                    return (cnt, om), None

                init = (jnp.zeros((d,), jnp.float32),
                        jnp.zeros((d,), jnp.float32))
                (cnt, om), _ = jax.lax.scan(body, init, (cids, nks))
                return cnt, om

            self._count_fns[m_pad] = fn
        return fn

    def _counts(self):
        cnt = jnp.zeros((self.num_features,), jnp.float32)
        om = jnp.zeros((self.num_features,), jnp.float32)
        for cids, nks, m_pad in self._chunks:
            bc, bo = self._count_fn(m_pad)(cids, nks)
            cnt, om = cnt + bc, om + bo
        return cnt, om

    def feature_counts(self) -> jax.Array:
        """#examples with feature j present — the materialized
        ``scaling.global_feature_counts`` streamed (exact: integer sums)."""
        return self._counts()[0]

    def omega(self) -> jax.Array:
        """#clients with feature j present — the materialized
        ``scaling.omega`` streamed (exact: integer sums)."""
        return self._counts()[1]


@dataclasses.dataclass(frozen=True)
class FederatedLogReg:
    """The problem as the algorithms see it: flat view + client buckets.

    When ``virtual`` is set (see :func:`build_virtual_problem`), ``flat``
    is a :class:`VirtualFlat` and ``buckets`` hold :class:`VirtualBucket`
    specs; the engine materializes rows on demand through ``virtual``
    under ``EngineConfig.virtual_data``.
    """

    flat: LogRegProblem
    buckets: List[ClientBucket]
    client_weights: jax.Array    # (K,) n_k / n, bucket-concatenated order
    num_clients: int
    virtual: Optional[VirtualLayout] = None

    @property
    def d(self) -> int:
        return self.flat.num_features


def _equal_runs(order, sorted_keys) -> List[List[int]]:
    """Contiguous runs of equal key in a stably key-sorted index order —
    one O(K) pass (the grouping is exact because equal keys are adjacent
    after the sort)."""
    if len(order) == 0:
        return []
    starts = np.flatnonzero(np.r_[True, sorted_keys[1:] != sorted_keys[:-1]])
    ends = np.r_[starts[1:], len(order)]
    return [[int(k) for k in order[s:e]] for s, e in zip(starts, ends)]


def _split_by_rows(groups: List[List[int]], sizes,
                   max_bucket_rows: int | None) -> List[List[int]]:
    """Split any group whose padded row count Kb·m_pad would exceed
    ``max_bucket_rows`` into consecutive sub-groups under the cap (a single
    client is never split, so one oversized client keeps its own bucket).
    Member order — and therefore the bucket-concatenated client order the
    weights and fold_in offsets depend on — is preserved."""
    if max_bucket_rows is None:
        return groups
    out: List[List[int]] = []
    for members in groups:
        cur: List[int] = []
        cur_pad = 0
        for k in members:
            m_pad = max(cur_pad, int(sizes[k]))
            if cur and (len(cur) + 1) * m_pad > max_bucket_rows:
                out.append(cur)
                cur, cur_pad = [k], int(sizes[k])
            else:
                cur.append(k)
                cur_pad = m_pad
        if cur:
            out.append(cur)
    return out


def _level_groups(sizes, max_bucket_rows: int | None) -> List[List[int]]:
    """The canonical client grouping: stable-sort by ceil(log2 n_k), one
    group per level, split under ``max_bucket_rows``.  Shared by
    :func:`build_problem` and :func:`build_virtual_problem` so the two
    layouts produce the *identical* bucket-concatenated client order —
    and therefore identical weights, fold_in offsets, and per-client
    keys — which is what makes virtual rounds bit-for-bit comparable to
    materialized ones."""
    levels = np.ceil(np.log2(np.maximum(sizes, 1))).astype(np.int64)
    order = np.argsort(levels, kind="stable")
    return _split_by_rows(_equal_runs(order, levels[order]), sizes,
                          max_bucket_rows)


def build_problem(ds, lam: float | None = None, *,
                  max_bucket_rows: int | None = None) -> FederatedLogReg:
    """ds: repro.data.synthetic.FederatedDataset.

    ``max_bucket_rows`` caps each bucket's padded example-row count
    Kb·m_pad: oversized ceil(log2 n_k) groups are split into consecutive
    sub-buckets so peak host memory per bucket stays bounded at paper scale
    (K = 10,000 puts thousands of clients in one level).  ``None`` keeps the
    historical one-bucket-per-level grouping bit-for-bit.
    """
    n = ds.num_examples
    lam = (1.0 / n) if lam is None else lam
    idx, val = pad_rows(ds.idx, ds.val)
    flat = LogRegProblem(
        idx=jnp.asarray(idx), val=jnp.asarray(val), y=jnp.asarray(ds.y),
        lam=float(lam), num_features=ds.num_features,
    )

    slices = ds.client_slices()
    sizes = ds.client_sizes.astype(np.int64)

    buckets: List[ClientBucket] = []
    weights: List[float] = []
    # One pass over the sorted order: each bucket is a contiguous run of
    # equal ceil(log2 n_k), so the run boundaries are where the sorted level
    # sequence changes — no per-bucket rescan of the tail.
    groups = _level_groups(sizes, max_bucket_rows)
    for members in groups:
        m_pad = int(max(sizes[k] for k in members))
        Kb = len(members)
        width = idx.shape[1]
        bi = np.zeros((Kb, m_pad, width), np.int32)
        bv = np.zeros((Kb, m_pad, width), np.float32)
        by = np.ones((Kb, m_pad), np.float32)
        nk = np.zeros(Kb, np.int32)
        for j, k in enumerate(members):
            sl = slices[k]
            m = int(sizes[k])
            bi[j, :m] = idx[sl]
            bv[j, :m] = val[sl]
            by[j, :m] = ds.y[sl]
            nk[j] = m
            weights.append(m / n)
        buckets.append(ClientBucket(jnp.asarray(bi), jnp.asarray(bv),
                                    jnp.asarray(by), jnp.asarray(nk)))

    return FederatedLogReg(
        flat=flat, buckets=buckets,
        client_weights=jnp.asarray(np.array(weights, np.float32)),
        num_clients=int(ds.num_clients),
    )


def build_virtual_problem(vds, lam: float | None = None, *,
                          max_bucket_rows: int | None = None,
                          eval_chunk: int = 256) -> FederatedLogReg:
    """vds: repro.data.synthetic.VirtualDataset.

    The virtual twin of :func:`build_problem`: same client grouping
    (:func:`_level_groups` over the TRAIN sizes), same weights, same
    default lam — but buckets carry only (client_ids, n_k, m_pad) and the
    flat view streams (:class:`VirtualFlat`), so the build is O(K) in
    memory and time regardless of Σ n_k.  Run rounds on it with
    ``EngineConfig(virtual_data=True, ...)``.
    """
    sizes = np.asarray(vds.client_sizes, np.int64)
    n = int(sizes.sum())
    lam = (1.0 / n) if lam is None else lam

    layout = VirtualLayout(vds)
    buckets: List[VirtualBucket] = []
    weight_parts: List[np.ndarray] = []
    for members in _level_groups(sizes, max_bucket_rows):
        mem = np.asarray(members, np.int64)
        buckets.append(VirtualBucket(
            client_ids=jnp.asarray(mem.astype(np.int32)),
            n_k=jnp.asarray(sizes[mem].astype(np.int32)),
            m_pad=int(sizes[mem].max()),
        ))
        weight_parts.append(sizes[mem] / n)

    flat = VirtualFlat(layout, buckets, lam=float(lam),
                       num_features=vds.num_features, n=n,
                       eval_chunk=eval_chunk)
    return FederatedLogReg(
        flat=flat, buckets=buckets,
        client_weights=jnp.asarray(
            np.concatenate(weight_parts).astype(np.float32)),
        num_clients=int(vds.num_clients),
        virtual=layout,
    )


def build_dense_problem(Xs, ys, lam: float) -> FederatedLogReg:
    """Dense per-client data (X_k: (d, m_k), y_k: (m_k,)) as a bucketed
    :class:`FederatedLogReg`, so the ridge algorithms (DANERidge and the
    Appendix-A primal/dual methods) run on the same :class:`RoundEngine`
    layout as the sparse logreg ones.

    Each example row stores its *dense* feature vector (idx = arange(d),
    val = x_i) — the fixed-nnz sparse format degenerates to dense.  Clients
    are grouped into one bucket per distinct m_k (stable, so equal-size
    clients keep their input order), and every client in a bucket has
    exactly m_k rows — no padding.  The flat view's loss/grad are logistic
    and are NOT meaningful for ridge data — ridge algorithms use only the
    bucket layout, ``client_weights``, and ``flat.n``/``flat.lam``.
    """
    d = int(Xs[0].shape[0])
    sizes = [int(y.shape[0]) for y in ys]
    n = sum(sizes)
    dtype = jnp.result_type(*[X.dtype for X in Xs])

    order = np.argsort(np.asarray(sizes, np.int64), kind="stable")
    buckets: List[ClientBucket] = []
    weights: List[float] = []
    for members in _equal_runs(order, np.asarray(sizes, np.int64)[order]):
        m = sizes[members[0]]
        bi = jnp.tile(jnp.arange(d, dtype=jnp.int32), (len(members), m, 1))
        bv = jnp.stack([jnp.asarray(Xs[k], dtype).T for k in members])
        by = jnp.stack([jnp.asarray(ys[k], dtype) for k in members])
        nk = jnp.full((len(members),), m, jnp.int32)
        weights.extend(sizes[k] / n for k in members)
        buckets.append(ClientBucket(bi, bv, by, nk))

    flat = LogRegProblem(
        idx=jnp.tile(jnp.arange(d, dtype=jnp.int32), (n, 1)),
        val=jnp.concatenate([jnp.asarray(X, dtype).T for X in Xs], axis=0),
        y=jnp.concatenate([jnp.asarray(y, dtype) for y in ys]),
        lam=float(lam), num_features=d,
    )
    return FederatedLogReg(
        flat=flat, buckets=buckets,
        client_weights=jnp.asarray(np.array(weights, np.float32)),
        num_clients=len(Xs),
    )


def build_test_problem(ds, lam: float | None = None) -> LogRegProblem:
    n = ds.num_examples
    lam = (1.0 / n) if lam is None else lam
    idx, val = pad_rows(ds.test_idx, ds.test_val)
    return LogRegProblem(
        idx=jnp.asarray(idx), val=jnp.asarray(val),
        y=jnp.asarray(ds.test_y), lam=float(lam), num_features=ds.num_features,
    )
