"""What the round needs to compile and run on a chip, pinned on the CPU.

* compiled rounds take the bucket rows as arguments (no row baked into the
  program as a constant);
* a state owns its iterate, so buffer donation never deletes the caller's
  ``w0``;
* client passes run over client batches (``map_clients``) with results
  equal to one vmap over the bucket, FSVRG's and FedAvg's passes too; the
  §4 round's count of sequential steps at the chosen batch;
* sparse rows are stored ``row_width`` wide, and the padding changes no
  objective value;
* scripts' compile cache: the environment's directory wins, else the
  checkout's ``.jax_cache``;
* ``chip_smoke.py`` refuses to run without a TPU.
"""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_solver
from repro.core import problem as problem_mod
from repro.core.engine import RoundEngine
from repro.core.problem import (ClientBucket, build_problem, map_clients,
                                pad_rows, row_width)

SOLVERS = ["gd", "fsvrg", "fedavg", "dane", "cocoa"]


def _scaled_rows(prob, c):
    """``prob`` with every row value multiplied by ``c`` (same sparsity)."""
    flat = prob.flat
    buckets = [ClientBucket(b.idx, b.val * c, b.y, b.n_k)
               for b in prob.buckets]
    return problem_mod.FederatedLogReg(
        flat=problem_mod.LogRegProblem(flat.idx, flat.val * c, flat.y,
                                       flat.lam, flat.num_features),
        buckets=buckets, client_weights=prob.client_weights,
        num_clients=prob.num_clients)


@pytest.mark.parametrize("name", SOLVERS)
def test_compiled_round_takes_rows_as_arguments(tiny_problem, name):
    """Two problems that differ only in their row values lower to the same
    program: the rows reach the jitted round as arguments.  (A closed-over
    row array would be a program constant — ~1.2 GB per round at the
    paper's widths.)"""
    key = jax.random.PRNGKey(0)
    texts = []
    for prob in (tiny_problem, _scaled_rows(tiny_problem, 2.0)):
        solver = make_solver(name, prob)
        texts.append(solver.lower_round(solver.init(), key).as_text())
    assert texts[0] == texts[1]


def test_lower_round_is_the_dispatched_round(tiny_problem):
    """``lower_round(...).compile()`` runs the same round ``round`` does."""
    solver = make_solver("cocoa", tiny_problem)
    key = jax.random.PRNGKey(3)
    state = solver.init()
    compiled = solver.lower_round(state, key).compile()
    w, _ = compiled(state.w, state.aux, (), key, state.round,
                    tuple(tiny_problem.buckets))
    np.testing.assert_array_equal(np.asarray(w),
                                  np.asarray(solver.round(state, key).w))


def test_init_owns_its_iterate_under_donation(tiny_problem, monkeypatch):
    """With the round donating its buffers (as on an accelerator), two fits
    from one ``w0`` both run, agree, and leave ``w0`` alive."""
    monkeypatch.setattr(RoundEngine, "_should_donate",
                        lambda self, donate: True)
    solver = make_solver("fsvrg", tiny_problem)
    w0 = jnp.full((tiny_problem.d,), 0.01)
    a = solver.fit(1, seed=0, w0=w0).w
    b = solver.fit(1, seed=0, w0=w0).w
    assert not w0.is_deleted()
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n", [5, 8, 13])
def test_map_clients_equals_one_vmap(monkeypatch, n):
    """Below, at, and past a whole number of batches (a batch is 4 clients
    here), with pytree inputs and outputs."""
    monkeypatch.setattr(problem_mod, "CLIENT_BLOCK_ELEMS", 4 * 10)
    assert problem_mod.client_batch(10) == 4
    x = jax.random.normal(jax.random.PRNGKey(n), (n, 10))
    idx = jax.random.randint(jax.random.PRNGKey(1), (n, 3), 0, 10)

    def fn(x, idx):
        g = jax.vmap(lambda i, v: jnp.zeros(10).at[i].add(v[:3]))(idx, x)
        return {"g": g * x, "s": x.sum(axis=1)}

    got = map_clients(fn, (x, idx), 10)
    want = fn(x, idx)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))


def _bucket(kb, m_pad, width, d, seed=0):
    """``kb`` clients of random sparse rows, n_k in [1, m_pad]."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    n_k = jax.random.randint(ks[0], (kb,), 1, m_pad + 1)
    live = jnp.arange(m_pad)[None, :] < n_k[:, None]
    idx = jax.random.randint(ks[1], (kb, m_pad, width), 0, d)
    val = jax.random.uniform(ks[2], (kb, m_pad, width)) * live[..., None]
    y = jnp.where(jax.random.bernoulli(ks[3], 0.5, (kb, m_pad)), 1.0, -1.0)
    return ClientBucket(idx, val, y * live, n_k)


@pytest.mark.parametrize("solver", ["fsvrg", "fedavg", "fedavg-kernel"])
def test_client_pass_over_batches_equals_one_vmap(monkeypatch, solver):
    """A bucket of 11 clients in batches of 4, 4 and a last 3 gives the
    deltas of one vmap over all 11: each client runs its own pass, from
    its own key, whatever batch it lands in."""
    from repro.core import fedavg, fsvrg

    d, kb = 40, 11
    bucket = _bucket(kb, 12, 8, d)
    key = jax.random.PRNGKey(5)
    w0 = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (d,))
    if solver == "fsvrg":
        grad = 0.01 * jax.random.normal(jax.random.PRNGKey(2), (d,))
        phi = jax.random.uniform(jax.random.PRNGKey(3), (d,), minval=0.05)
        run = lambda: fsvrg._client_pass(w0, grad, bucket, 0.01, phi,
                                         fsvrg.FSVRGConfig(), key)
    else:
        cfg = fedavg.FedAvgConfig(stepsize=0.1, local_epochs=2)
        run = lambda: fedavg._local_sgd_pass(
            w0, bucket, 0.01, cfg, solver == "fedavg-kernel", key)

    monkeypatch.setattr(problem_mod, "CLIENT_BLOCK_ELEMS", 1 << 30)
    assert problem_mod.client_batch(d) >= kb
    whole = np.asarray(run())
    monkeypatch.setattr(problem_mod, "CLIENT_BLOCK_ELEMS", 4 * d)
    assert problem_mod.client_batch(d) == 4
    batched = np.asarray(run())
    assert whole.shape == batched.shape == (kb, d)
    assert np.abs(whole).max() > 0
    np.testing.assert_array_max_ulp(batched, whole, maxulp=1)


def test_client_batch_is_a_power_of_two_under_the_budget():
    for d in (1, 400, 2_002, 20_002, 3_000_000):
        b = problem_mod.client_batch(d)
        assert b >= 1 and b & (b - 1) == 0
        assert b * d <= problem_mod.CLIENT_BLOCK_ELEMS or b == 1
    assert problem_mod.client_batch(20_002) == 64


#: the §4 buckets (clients, m_pad) of the chip benchmark's client sizes
#: (``sizes_seed`` 0, 1,621,218 train rows over 10,000 clients)
GPLUS_BUCKETS = [(331, 64), (6478, 128), (2121, 256), (715, 512),
                 (239, 1020), (74, 2040), (31, 4078), (11, 6750)]


def _steps(buckets, batch, chunk=None, epochs=1):
    """Sequential local steps of a round's client pass: each bucket's
    clients (padded to whole ``chunk``s where it has more) run as batches
    of ``batch`` one after another, ``epochs`` passes of m_pad steps
    each."""
    ceil = lambda a, b: -(-a // b)
    total = 0
    for kb, m_pad in buckets:
        if chunk is not None and kb > chunk:
            batches = ceil(kb, chunk) * ceil(chunk, batch)
        else:
            batches = ceil(kb, batch)
        total += batches * epochs * m_pad
    return total


def test_gplus_round_sequential_steps():
    """The sequential steps of a round's client pass at the §4 width, FSVRG
    (one pass) and FedAvg (512-client chunks, E = 2): each costs the chip
    ~55 µs at up to ~32 clients and ~1.7–2.4 µs a client above that."""
    batch = problem_mod.client_batch(20_002)
    assert _steps(GPLUS_BUCKETS, batch) == 47_276
    assert _steps(GPLUS_BUCKETS, batch, chunk=512, epochs=2) == 102_232
    # the 32-client batch these replaced
    assert _steps(GPLUS_BUCKETS, 32) == 80_724
    assert _steps(GPLUS_BUCKETS, 32, chunk=512, epochs=2) == 178_600


@pytest.mark.parametrize("nnz,width", [(1, 1), (8, 8), (14, 16), (62, 64),
                                       (128, 128), (130, 256)])
def test_row_width(nnz, width):
    assert row_width(nnz) == width
    idx = np.arange(3 * nnz, dtype=np.int32).reshape(3, nnz) + 1
    val = np.ones((3, nnz), np.float32)
    pidx, pval = pad_rows(idx, val)
    assert pidx.shape == pval.shape == (3, width)
    assert (pidx[:, nnz:] == 0).all() and (pval[:, nnz:] == 0).all()


def test_padded_rows_change_no_objective(tiny_dataset):
    """The stored rows are wider than the dataset's, and f, ∇f and the
    per-client buckets are those of the unpadded rows."""
    prob = build_problem(tiny_dataset)
    nnz = tiny_dataset.idx.shape[1]
    assert prob.flat.idx.shape[1] == row_width(nnz) > nnz
    assert all(b.idx.shape[2] == row_width(nnz) for b in prob.buckets)
    w = jax.random.normal(jax.random.PRNGKey(0), (prob.d,)) * 0.1
    raw = problem_mod.LogRegProblem(
        jnp.asarray(tiny_dataset.idx), jnp.asarray(tiny_dataset.val),
        jnp.asarray(tiny_dataset.y), prob.flat.lam, prob.d)
    np.testing.assert_allclose(float(prob.flat.loss(w)), float(raw.loss(w)),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(prob.flat.grad(w)),
                               np.asarray(raw.grad(w)), rtol=1e-5,
                               atol=1e-8)


def test_compile_cache_rule(monkeypatch, tmp_path):
    from repro.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        root = pathlib.Path(__file__).resolve().parents[1]
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_cpu(monkeypatch, tmp_path, capsys):
    """No TPU, no run: nonzero exit and nothing on stdout."""
    import importlib.util

    root = pathlib.Path(__file__).resolve().parents[1]
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  root / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    # the environment's cache directory leaves this process's config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""
