"""The benchmark's own copy of the §4 data generator.

A copy of the keyed sampler in ``repro.data.synthetic`` (arXiv:1610.02527
§4: power-law client sizes, per-client vocabularies, 60 nonzeros plus the
bias and unknown-word features per row, per-client label bias, a
chronological 75/25 split), kept here so that a change to the program
cannot move the benchmark's inputs.  ``tests/bench`` holds it to the
program's ``generate`` bit for bit.

Two things differ from the program's materializer, neither in the values:

* every row is made on the device in one jitted call (client parameters
  and rows in fixed blocks under ``lax.map``), with one transfer to the
  host at the end, instead of a host round trip per block; a repeated
  feature within a row is found by comparing the row's entries pairwise
  rather than by three sorts (the same mask: the first occurrence stays);
* :func:`draw_spec` can take the client sizes from a fixed ``sizes_seed``.
  The round's compiled shapes follow the sizes (the buckets group clients
  by ceil(log2 n_k) and pad to the largest) and so do the n_k/n weights
  the program compiles into its round, so with fixed sizes every seed
  runs the same programs, and only the rows and labels change;
* it can also take each row's features from a fixed ``pattern_seed``:
  the vocabularies and the features drawn into each row come from that
  seed, and the ground truth, each client's label bias and each row's
  label from the run seed.  A solver that compiles per-feature counts of
  the data into its round (FSVRG's φ and A) then runs the same program
  for every seed, on other labels.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# fold_in tag domains off the client key ck = fold_in(base, k)
_ROWS_TAG, _VOCAB_TAG, _MIX_TAG, _BIAS_TAG = 0, 1, 2, 3
# fold_in tag domains off the row key rk = fold_in(fold_in(ck, ROWS), pos)
_OWN_TAG, _GLOB_TAG, _LABEL_TAG = 0, 1, 2
#: logistic(0, s) has std s·π/√3: a per-client label bias of std 1.5
_BIAS_SCALE = 1.5 * math.sqrt(3.0) / math.pi

PARAM_BLOCK = 2048
ROW_BLOCK = 16384


def power_law_sizes(rng, K, n_total, n_min, n_max, alpha=1.6):
    """Client sizes with Σ n_k == clip(n_total, K·n_min, K·n_max), drawn as
    the program draws them (clipped mass redistributed, largest-remainder
    integerization)."""
    target = float(np.clip(n_total, K * n_min, K * n_max))
    raw = np.clip((rng.pareto(alpha, size=K) + 1.0) * n_min, n_min, n_max)
    sizes = np.clip(raw / raw.sum() * target, n_min, n_max)
    gap = target - sizes.sum()
    order = np.argsort(-sizes if gap > 0 else sizes, kind="stable")
    for k in order:
        if abs(gap) < 0.5:
            break
        if gap > 0:
            take = min(gap, n_max - sizes[k])
        else:
            take = max(gap, n_min - sizes[k])
        sizes[k] += take
        gap -= take

    base = np.clip(np.floor(sizes).astype(np.int64), n_min, n_max)
    rem = int(round(target)) - int(base.sum())
    frac_order = np.argsort(-(sizes - base), kind="stable")
    step = 1 if rem > 0 else -1
    while rem != 0:
        adjustable = False
        for k in frac_order:
            if rem == 0:
                break
            if n_min <= base[k] + step <= n_max:
                base[k] += step
                rem -= step
                adjustable = True
        if not adjustable:
            break
    return base


def train_split_sizes(sizes) -> np.ndarray:
    """Train rows per client: max(1, floor(0.75 n_k)), capped at n_k − 1."""
    sizes = np.asarray(sizes, np.int64)
    tr = np.maximum(1, (0.75 * sizes).astype(np.int64))
    return np.where(sizes >= 2, np.minimum(tr, sizes - 1), tr)


@dataclasses.dataclass(frozen=True)
class Spec:
    """What is drawn outside the keyed sampler: sizes, ground truth,
    feature popularity, and the base key."""

    seed: int                   # labels and ground truth
    pattern_seed: int           # vocabularies and the features of each row
    full_sizes: np.ndarray      # (K,) train + test rows per client
    train_sizes: np.ndarray     # (K,)
    w_true: np.ndarray          # (d,) f32
    log_pop: np.ndarray         # (d-2,) f32
    global_cdf: np.ndarray      # (d-2,) f32
    num_features: int
    nnz: int
    vocab_size: int
    n_own: int

    @property
    def num_clients(self) -> int:
        return len(self.full_sizes)


def draw_spec(problem: dict, seed: int, sizes_seed=None,
              pattern_seed=None) -> Spec:
    """The spec of the §4 problem described by ``problem`` (the keys of
    ``repro.configs.gplus_logreg.LogRegConfig``).  With ``sizes_seed`` the
    sizes come from that seed, with ``pattern_seed`` the features of every
    row; without them, everything comes from ``seed`` exactly as the
    program draws it."""
    rng = np.random.default_rng(seed)
    K, d = problem["num_clients"], problem["num_features"]
    args = (K, problem["num_examples"], problem["min_client_examples"],
            problem["max_client_examples"])
    sizes = power_law_sizes(
        rng if sizes_seed is None else np.random.default_rng(sizes_seed),
        *args)
    w_true = rng.standard_normal(d) * (rng.random(d) < 0.3)
    ranks = np.arange(2, d)
    pop = 1.0 / ranks ** 1.1
    pop /= pop.sum()
    gcdf = np.cumsum(pop)
    gcdf[-1] = 1.0
    nnz = min(problem["nnz_per_example"], d - 2)
    return Spec(
        seed=int(seed),
        pattern_seed=int(seed if pattern_seed is None else pattern_seed),
        full_sizes=sizes.astype(np.int64),
        train_sizes=train_split_sizes(sizes).astype(np.int64),
        w_true=w_true.astype(np.float32),
        log_pop=np.log(pop).astype(np.float32),
        global_cdf=gcdf.astype(np.float32),
        num_features=d, nnz=nnz,
        vocab_size=min(max(8, int(0.02 * d)), d - 2), n_own=int(0.8 * nnz))


def _client_params(ck, log_pop, vocab_size):
    g = jax.random.gumbel(jax.random.fold_in(ck, _VOCAB_TAG), log_pop.shape)
    _, top = jax.lax.top_k(log_pop + g, vocab_size)
    vocab = (top + 2).astype(jnp.int32)
    u = jax.random.uniform(jax.random.fold_in(ck, _MIX_TAG), (vocab_size,),
                           minval=1e-7, maxval=1.0)
    raw = (-jnp.log(u)) ** (1.0 / 0.3)
    cdf = jnp.cumsum(raw / raw.sum())
    cdf = cdf.at[-1].set(1.0)
    return vocab, cdf


def _label_bias(ck):
    ub = jax.random.uniform(jax.random.fold_in(ck, _BIAS_TAG), (),
                            minval=1e-6, maxval=1.0 - 1e-6)
    return _BIAS_SCALE * jnp.log(ub / (1.0 - ub))


def _row(rk, lk, vocab, cdf, bias, w_true, global_cdf, nnz, n_own):
    """One row: its features from the row key ``rk``, its label from the
    label key ``lk``."""
    V = vocab.shape[0]
    u_own = jax.random.uniform(jax.random.fold_in(rk, _OWN_TAG), (n_own,))
    own = vocab[jnp.clip(jnp.searchsorted(cdf, u_own, side="right"), 0, V - 1)]
    dg = global_cdf.shape[0]
    u_glob = jax.random.uniform(jax.random.fold_in(rk, _GLOB_TAG),
                                (nnz - n_own,))
    glob = (jnp.clip(jnp.searchsorted(global_cdf, u_glob, side="right"),
                     0, dg - 1) + 2).astype(jnp.int32)
    idx = jnp.concatenate([jnp.array([0, 1], jnp.int32), own, glob])
    val = jnp.ones((nnz + 2,), jnp.float32)
    # an entry is a repeat when an earlier entry holds the same feature
    earlier = jnp.tril(idx[:, None] == idx[None, :], k=-1)
    val = val * (~earlier.any(axis=1)).astype(jnp.float32)
    margin = (val * w_true[idx]).sum()
    p = jax.nn.sigmoid(jnp.float32(0.7) * margin + bias)
    u_y = jax.random.uniform(jax.random.fold_in(lk, _LABEL_TAG), ())
    y = jnp.where(u_y < p, 1.0, -1.0).astype(jnp.float32)
    return idx, val, y


def _params(base_key, label_key, log_pop, num_clients, vocab_size):
    """Each client's vocabulary and mixture from ``base_key``, its label
    bias from ``label_key``, and the row keys under both."""
    blocks = -(-num_clients // PARAM_BLOCK)
    ids = jnp.arange(blocks * PARAM_BLOCK, dtype=jnp.uint32).reshape(
        blocks, PARAM_BLOCK)

    def block(cids):
        def one(cid):
            ck = jax.random.fold_in(base_key, cid)
            lk = jax.random.fold_in(label_key, cid)
            vocab, cdf = _client_params(ck, log_pop, vocab_size)
            return (vocab, cdf, _label_bias(lk),
                    jax.random.fold_in(ck, _ROWS_TAG),
                    jax.random.fold_in(lk, _ROWS_TAG))
        return jax.vmap(one)(cids)

    out = jax.lax.map(block, ids)
    return jax.tree_util.tree_map(
        lambda x: x.reshape((blocks * PARAM_BLOCK,) + x.shape[2:]), out)


@functools.partial(jax.jit, static_argnames=("num_clients", "vocab_size",
                                             "nnz", "n_own"))
def _rows_on_device(base_key, label_key, log_pop, w_true, global_cdf,
                    client_of, pos, *, num_clients, vocab_size, nnz, n_own):
    """Rows ``(client_of[i], pos[i])`` for every i; both inputs are padded
    to whole row blocks."""
    vocab, cdf, bias, rows_key, labels_key = _params(
        base_key, label_key, log_pop, num_clients, vocab_size)

    def block(x):
        cof, p = x
        return jax.vmap(
            lambda rk, lk, pp, vo, cd, bi: _row(
                jax.random.fold_in(rk, pp), jax.random.fold_in(lk, pp), vo,
                cd, bi, w_true, global_cdf, nnz, n_own)
        )(rows_key[cof], labels_key[cof], p, vocab[cof], cdf[cof], bias[cof])

    blocks = client_of.shape[0] // ROW_BLOCK
    idx, val, y = jax.lax.map(
        block, (client_of.reshape(blocks, ROW_BLOCK),
                pos.reshape(blocks, ROW_BLOCK)))
    return (idx.reshape(-1, nnz + 2), val.reshape(-1, nnz + 2),
            y.reshape(-1))


def rows(spec: Spec, sizes) -> tuple:
    """Each client's first ``sizes[k]`` chronological rows, client by
    client, as host arrays ``(idx, val, y, client_of)``."""
    sizes = np.asarray(sizes, np.int64)
    n = int(sizes.sum())
    client_of = np.repeat(np.arange(spec.num_clients, dtype=np.int32), sizes)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    pos = (np.arange(n) - starts[client_of]).astype(np.uint32)
    pad = (-n) % ROW_BLOCK
    cof = np.concatenate([client_of, np.zeros(pad, np.int32)])
    pp = np.concatenate([pos, np.zeros(pad, np.uint32)])
    idx, val, y = _rows_on_device(
        jax.random.PRNGKey(spec.pattern_seed), jax.random.PRNGKey(spec.seed),
        jnp.asarray(spec.log_pop),
        jnp.asarray(spec.w_true), jnp.asarray(spec.global_cdf),
        jnp.asarray(cof), jnp.asarray(pp), num_clients=spec.num_clients,
        vocab_size=spec.vocab_size, nnz=spec.nnz, n_own=spec.n_own)
    idx, val, y = (np.asarray(a)[:n] for a in (idx, val, y))
    return idx, val, y, client_of


def generate(problem: dict, seed: int) -> dict:
    """The whole dataset, train and test split per client, with the field
    names of the program's ``FederatedDataset``."""
    spec = draw_spec(problem, seed)
    idx, val, y, client_of = rows(spec, spec.full_sizes)
    starts = np.concatenate([[0], np.cumsum(spec.full_sizes)[:-1]])
    pos = np.arange(len(y)) - starts[client_of]
    tr = pos < spec.train_sizes[client_of]
    te = ~tr
    return dict(idx=idx[tr], val=val[tr], y=y[tr], client_of=client_of[tr],
                client_sizes=spec.train_sizes.astype(np.int32),
                num_features=spec.num_features,
                test_idx=idx[te], test_val=val[te], test_y=y[te],
                test_client_of=client_of[te])
