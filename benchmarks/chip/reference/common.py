"""The plain reference round, shared by every solver's reference.

Written in straightforward ``jax.numpy`` at float32 with ``"highest"``
matmul precision; it imports nothing of the program and takes nothing the
program made: the rows come from the benchmark's generator, and the
client layout and key schedule are worked out here from the client sizes
as the round defines them (arXiv:1610.02527 §4 setting):

* clients are grouped by ceil(log2 n_k) (stable in client order), each
  group padded to its largest client; groups are concatenated in level
  order, and a group's offset is the index of its first client there;
* round r's key is ``fold_in(PRNGKey(seed), r)``; a group's key is
  ``fold_in(round_key, offset)``; its clients' keys are
  ``split(group_key, clients)``;
* a client takes part with probability p, drawn as
  ``uniform(fold_in(group_key, 997), (clients,)) < p``;
* the server update is ``w + D ⊙ (s · Σ_k (n_k/n) δ_k)`` over the clients
  that took part, with ``s`` the expected over the realized weight mass
  (1 under full participation) and ``D`` the solver's diagonal (ones
  unless it scales);
* a module that declares ``WEIGHTING = "sum"`` has each participant's
  delta added with weight 1 and no reweighting: ``w + D ⊙ Σ_k δ_k``, the
  plain sum of dual methods, whose deltas carry their own normalization.

A solver's reference module supplies the rest: ``prepare`` (per-problem
constants), ``prelude`` (per-round server state), ``deltas`` (one block
of clients' passes) and ``server_diag``.

Per-client state: a module may define ``init_state(data, layout, static,
params, dtype)``, one array per group with a leading row per member (a
dual block α_k, say).  ``deltas`` then takes the block's rows of it as
``state=`` and returns ``(deltas, new_state)``, and the state is carried
from round to round.  A client that takes no part in a round, or that the
``half_cohort`` fault leaves out, keeps its state unchanged; pad slots of
a block are never written back.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

#: clients per block of a reference pass (bounds its dense (block, d) state)
CLIENT_BLOCK = 512
#: rows per block of the flat loss and gradient
ROW_BLOCK = 1 << 18


@dataclasses.dataclass
class Data:
    """Train rows, client by client, as the generator made them."""

    idx: np.ndarray        # (n, w) int32, val == 0 marks an absent entry
    val: np.ndarray        # (n, w) f32
    y: np.ndarray          # (n,) f32 in {-1, +1}
    client_of: np.ndarray  # (n,) int32, clients contiguous
    sizes: np.ndarray      # (K,) rows per client
    num_features: int

    @property
    def n(self) -> int:
        return len(self.y)

    @property
    def lam(self) -> float:
        return 1.0 / self.n


@dataclasses.dataclass(frozen=True)
class Group:
    members: np.ndarray    # client ids, in client order
    m_pad: int
    offset: int            # index of the first member in level order


def groups(sizes) -> list:
    sizes = np.asarray(sizes, np.int64)
    levels = np.ceil(np.log2(np.maximum(sizes, 1))).astype(np.int64)
    order = np.argsort(levels, kind="stable")
    out, offset = [], 0
    for lv in np.unique(levels):
        members = order[levels[order] == lv]
        out.append(Group(members, int(sizes[members].max()), offset))
        offset += len(members)
    return out


def group_rows(data: Data, g: Group, dtype):
    """Group ``g``'s rows on the device: (C, m_pad, w) idx/val, (C, m_pad)
    y and (C,) n_k; slots past a client's rows hold idx 0, val 0, y 1."""
    starts = np.concatenate([[0], np.cumsum(data.sizes)[:-1]])
    C, width = len(g.members), data.idx.shape[1]
    idx = np.zeros((C, g.m_pad, width), np.int32)
    val = np.zeros((C, g.m_pad, width), np.float32)
    y = np.ones((C, g.m_pad), np.float32)
    nk = data.sizes[g.members]
    slot = np.repeat(np.arange(C), nk)
    pos = np.arange(int(nk.sum())) - np.repeat(np.cumsum(nk) - nk, nk)
    rows = np.repeat(starts[g.members], nk) + pos
    idx[slot, pos] = data.idx[rows]
    val[slot, pos] = data.val[rows]
    y[slot, pos] = data.y[rows]
    return (jnp.asarray(idx), jnp.asarray(val).astype(dtype),
            jnp.asarray(y).astype(dtype), jnp.asarray(nk.astype(np.int32)))


class Flat:
    """The objective over every train row: f(w) = mean softplus(−y x·w)
    + λ/2 ‖w‖², λ = 1/n, and its gradient."""

    def __init__(self, data: Data, dtype=jnp.float32):
        n = data.n
        pad = (-n) % ROW_BLOCK
        blocks = (n + pad) // ROW_BLOCK

        def blocked(a, fill):
            a = np.concatenate([a, np.full((pad,) + a.shape[1:], fill,
                                           a.dtype)])
            return jnp.asarray(a.reshape((blocks, ROW_BLOCK) + a.shape[1:]))

        self.idx = blocked(data.idx, 0)
        self.val = blocked(data.val, 0).astype(dtype)
        self.y = blocked(data.y, 1).astype(dtype)
        self.mask = blocked(np.ones(n, np.float32), 0).astype(dtype)
        self.n, self.lam, self.d = n, data.lam, data.num_features
        self.dtype = dtype

    def loss(self, w):
        return float(_loss(w.astype(self.dtype), self.idx, self.val, self.y,
                           self.mask, self.n, self.lam))

    def grad(self, w):
        return _grad(w, self.idx, self.val, self.y, self.mask, self.n,
                     self.lam)

    def feature_counts(self):
        """n^j: rows in which feature j is present."""
        return _counts(self.idx, self.val, self.d)


@jax.jit
def _margins(w, idx, val):
    return (val * w[idx]).sum(-1)


@jax.jit
def _loss(w, idx, val, y, mask, n, lam):
    def body(acc, b):
        i, v, yy, m = b
        z = yy * _margins(w, i, v)
        return acc + (jax.nn.softplus(-z).astype(jnp.float32) * m).sum(), None

    tot, _ = jax.lax.scan(body, jnp.float32(0.0), (idx, val, y, mask))
    w32 = w.astype(jnp.float32)
    return tot / n + 0.5 * lam * jnp.dot(w32, w32)


@jax.jit
def _grad(w, idx, val, y, mask, n, lam):
    def body(g, b):
        i, v, yy, m = b
        z = yy * _margins(w, i, v)
        gs = -yy * jax.nn.sigmoid(-z) / jnp.asarray(n, w.dtype) * m
        return g.at[i].add(gs[:, None] * v), None

    g, _ = jax.lax.scan(body, jnp.zeros_like(w), (idx, val, y, mask))
    return g + jnp.asarray(lam, w.dtype) * w


@functools.partial(jax.jit, static_argnames=("d",))
def _counts(idx, val, d):
    def body(c, b):
        i, v = b
        return c.at[i].add((v != 0).astype(jnp.float32)), None

    c, _ = jax.lax.scan(body, jnp.zeros((d,), jnp.float32), (idx, val))
    return c


def client_keys(seed: int, r: int, offset: int, clients: int):
    kb = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), r),
                            offset)
    return kb, jax.random.split(kb, clients)


def takes_part(kb, clients: int, p: float) -> np.ndarray:
    u = jax.random.uniform(jax.random.fold_in(kb, 997), (clients,))
    return np.asarray(u < p)


def run_rounds(data: Data, solver, params: dict, participation: float,
               seed: int, rounds: int, *, dtype=jnp.float32, fault=None):
    """The iterates after each of ``rounds`` rounds from w = 0, as host
    float32 arrays.

    ``fault`` breaks the round on purpose, for the checks that a broken
    round reads as not correct: ``"half_cohort"`` leaves out every other
    client that takes part and takes the weighted mean over the rest (the
    plain sum over the rest, under ``WEIGHTING = "sum"``)."""
    t0 = time.perf_counter()
    spent = {}
    summed = getattr(solver, "WEIGHTING", "nk") == "sum"
    with jax.default_matmul_precision("highest"):
        flat = Flat(data, dtype)
        layout = groups(data.sizes)
        rows = [group_rows(data, g, dtype) for g in layout]
        static = solver.prepare(data, flat, layout, params, dtype)
        diag = solver.server_diag(static)
        weights = (np.ones(len(data.sizes), np.float32) if summed
                   else (data.sizes / data.n).astype(np.float32))
        states = (list(solver.init_state(data, layout, static, params, dtype))
                  if hasattr(solver, "init_state") else None)
        w = jnp.zeros((data.num_features,), dtype)
        jax.block_until_ready((static, rows, states))
        spent["prepare"] = time.perf_counter() - t0
        out = []
        for r in range(rounds):
            t = time.perf_counter()
            ctx = jax.block_until_ready(
                solver.prelude(flat, w, static, params, dtype))
            spent["prelude"] = spent.get("prelude", 0.0) + (
                time.perf_counter() - t)
            acc = jnp.zeros((data.num_features,), jnp.float32)
            realized = expected = 0.0
            for gi, (g, g_rows) in enumerate(zip(layout, rows)):
                t = time.perf_counter()
                kb, keys = client_keys(seed, r, g.offset, len(g.members))
                wts = weights[g.members]
                if participation < 1.0:
                    part = takes_part(kb, len(g.members), participation)
                    realized += float(wts[part].sum(dtype=np.float32))
                    expected += float(wts.sum(dtype=np.float32))
                    chosen = np.flatnonzero(part)
                else:
                    chosen = np.arange(len(g.members))
                scale = np.float32(1.0)
                if fault == "half_cohort" and len(chosen) > 1:
                    kept = chosen[::2]
                    # a summed round reweights nothing: the plain sum
                    # over the kept half
                    if not summed:
                        scale = np.float32(wts[chosen].sum()
                                           / wts[kept].sum())
                    chosen = kept
                g_sum, g_state = _group_sum(
                    solver, g_rows, chosen, keys, wts, w, ctx, static,
                    params, dtype, None if states is None else states[gi])
                acc = jax.block_until_ready(acc + scale * g_sum)
                if states is not None:
                    states[gi] = g_state
                spent[f"group{gi}"] = spent.get(f"group{gi}", 0.0) + (
                    time.perf_counter() - t)
            s = (expected / max(realized, 1e-9)
                 if participation < 1.0 and not summed else 1.0)
            step = jnp.float32(s) * acc
            if diag is not None:
                step = diag * step
            w = (w.astype(jnp.float32) + step).astype(dtype)
            out.append(np.asarray(w, np.float32))
    print(f"reference {solver.__name__.rsplit('_', 1)[-1]} {np.dtype(dtype).name}"
          f" {fault or 'sound'}: {time.perf_counter() - t0:.1f} s "
          + " ".join(f"{k} {v:.1f}" for k, v in spent.items()),
          file=sys.stderr)
    return out


def _group_sum(solver, g_rows, chosen, keys, wts, w, ctx, static, params,
               dtype, state=None):
    """Σ over the ``chosen`` members of a group of their weight times δ_k,
    in float32, in blocks of one shape per group, and the group's state
    with the chosen members' rows replaced (None without state); pad slots
    are clients with n_k = 0 and weight 0, exact no-ops."""
    idx, val, y, nk = g_rows
    # one of a few block shapes per group: a cohort's block is the next
    # power of two above its size, so padding never doubles the work
    size = min(CLIENT_BLOCK, nk.shape[0],
               1 << max(len(chosen) - 1, 0).bit_length())
    acc = jnp.zeros(w.shape, jnp.float32)
    for c0 in range(0, len(chosen), size):
        block = chosen[c0:c0 + size]
        real = np.arange(size) < len(block)
        take = jnp.asarray(np.where(real, np.resize(block, size), 0))
        real = jnp.asarray(real)
        args = (w, idx[take], val[take], y[take],
                jnp.where(real, nk[take], 0), keys[take], ctx, static,
                params, dtype)
        if state is None:
            deltas = solver.deltas(*args)
        else:
            deltas, new = solver.deltas(*args, state=state[take])
            state = state.at[jnp.asarray(block)].set(new[:len(block)])
        wb = jnp.where(real, jnp.asarray(wts)[take], 0.0)
        acc = acc + (wb[:, None] * deltas.astype(jnp.float32)).sum(0)
    return acc, state
