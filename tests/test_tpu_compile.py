"""The main path's kernels compile for a TPU v5e at the paper's §4 widths.

Interpret mode (every other kernel test) runs a kernel body as plain JAX
ops, so it cannot see what the chip's compiler refuses: an unsupported
primitive inside a kernel, a block shape the TPU lowering rejects, more
fast memory than a kernel may use.  These tests compile each kernel for a
*described* v5e chip — no chip attached, nothing runs — at d = 20,002
features and K = 10,000 clients (``configs/gplus_logreg.py``), and assert
that the Pallas kernel is in the compiled program (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file.  The persistent compilation cache is off around the compiles —
an entry compiled for a described chip cannot be read back without one.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import cocoa_sdca, dane_update, fedavg_update, fsvrg_update
from repro.kernels import scaled_aggregate

D = 20_002          # §4 features
K = 10_000          # §4 clients
KB_LARGEST = 6_478  # clients in the largest bucket of the §4 problem
CHUNK = 512         # the streamed round's client chunk in chip_smoke.py


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _vec(one_chip, n=D):
    return _spec(one_chip, (n,))


def _scalar(one_chip):
    return _spec(one_chip, ())


def test_fsvrg_update_compiles(one_chip):
    v = _vec(one_chip)
    c = _compile(fsvrg_update.fsvrg_update, v, v, v, v, v, _scalar(one_chip))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("clients", [None, 600])
def test_fedavg_update_compiles(one_chip, clients):
    """Alone, and as FedAvg runs it: vmapped over a bucket's clients and
    scanned over local steps."""
    s = _scalar(one_chip)
    if clients is None:
        v = _vec(one_chip)
        c = _compile(fedavg_update.fedavg_update, v, v, s, s)
    else:
        m = _spec(one_chip, (clients, D))
        step = jax.vmap(fedavg_update.fedavg_update, in_axes=(0, 0, None, None))

        def local_sgd(w, g, h, lam):
            return jax.lax.scan(lambda wk, _: (step(wk, g, h, lam), None),
                                w, None, length=3)[0]

        c = _compile(local_sgd, m, m, s, s)
    assert "tpu_custom_call" in c.as_text()


def test_dane_update_compiles(one_chip):
    v, s = _vec(one_chip), _scalar(one_chip)
    c = _compile(dane_update.dane_update, v, v, v, v, s, s, s)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("kb", [7, KB_LARGEST])
def test_cocoa_sdca_update_compiles(one_chip, kb):
    """The Newton solve over one bucket's clients — the smallest and the
    largest bucket of the §4 problem."""
    v = _vec(one_chip, kb)
    c = _compile(cocoa_sdca.cocoa_sdca_update, v, v, v)
    assert "tpu_custom_call" in c.as_text()


def test_sdca_pass_names_its_kernel_after_the_wrapper(one_chip, monkeypatch):
    """One 64-client batch's SDCA pass as CoCoA+ runs it on a TPU (a
    lockstep scan over the row slots, the kernel once a step): the
    kernel's custom call is named after its jitted wrapper,
    ``cocoa_sdca_update``, the name the chip benchmark's
    ``kernel_ms.cocoa_sdca`` looks for in the device trace."""
    from repro.core.cocoa import _sdca_local_pass_keyed
    from repro.core.problem import ClientBucket
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    clients, slots, width = 64, 128, 62

    def sdca_pass(w, alpha, idx, val, y, n_k, keys):
        return _sdca_local_pass_keyed(w, alpha,
                                      ClientBucket(idx, val, y, n_k),
                                      1e-6, 1e6, float(K), True, keys)

    c = _compile(sdca_pass, _vec(one_chip),
                 _spec(one_chip, (clients, slots)),
                 _spec(one_chip, (clients, slots, width), jnp.int32),
                 _spec(one_chip, (clients, slots, width)),
                 _spec(one_chip, (clients, slots)),
                 _spec(one_chip, (clients,), jnp.int32),
                 _spec(one_chip, (clients, 2), jnp.uint32))
    calls = [line.split("=", 1)[0].strip() for line in
             c.as_text().splitlines() if "tpu_custom_call" in line]
    assert calls and all(re.fullmatch(r"%cocoa_sdca_update(\.\d+)?", n)
                         for n in calls), calls


def test_fused_aggregate_compiles(one_chip):
    v = _vec(one_chip)
    c = _compile(scaled_aggregate.fused_aggregate, v,
                 _spec(one_chip, (K, D)), _vec(one_chip, K), v,
                 _scalar(one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_fused_accumulate_compiles(one_chip):
    c = _compile(scaled_aggregate.fused_accumulate, _vec(one_chip),
                 _spec(one_chip, (CHUNK, D)), _vec(one_chip, CHUNK))
    assert "tpu_custom_call" in c.as_text()


def test_fused_epilogue_compiles(one_chip):
    v = _vec(one_chip)
    c = _compile(scaled_aggregate.fused_epilogue, v, v, v, _scalar(one_chip))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("mode", ["trimmed_mean", "median"])
def test_order_statistic_aggregate_compiles(one_chip, mode):
    """The trimmed-mean / median guard over all K clients' deltas: XLA's own
    sort (Mosaic has no sort lowering, so no Pallas kernel), within one
    chip's 16 GB."""
    from repro.core.engine import robust_aggregate

    v = _vec(one_chip)
    c = _compile(functools.partial(robust_aggregate, trim=0.1, mode=mode),
                 v, _spec(one_chip, (K, D)), _spec(one_chip, (K,), jnp.bool_),
                 v)
    mem = c.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9
