"""Sharding rule engine tests + a real (1x1-mesh) sharded train step, and
hypothesis checks that every rule respects divisibility.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.sharding import rules


class FakeMesh:
    """Duck-typed mesh for rule testing without touching jax device state."""

    def __init__(self, shape_map):
        self.axis_names = tuple(shape_map)
        self.shape = dict(shape_map)


MESH = FakeMesh({"data": 16, "model": 16})
MESH_POD = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_attention_rules():
    spec = rules.spec_for_param("layers/pos0/attn/wq", (32, 4096, 4096), MESH)
    assert spec == P(None, "data", "model")
    spec = rules.spec_for_param("layers/pos0/attn/wo", (32, 4096, 4096), MESH)
    assert spec == P(None, "model", "data")


def test_divisibility_guard():
    # seamless vocab 256206 is not 16-divisible -> replicated rows
    spec = rules.spec_for_param("embed", (256206, 1024), MESH)
    assert spec == P(None, None)
    spec = rules.spec_for_param("embed", (128256, 4096), MESH)
    assert spec == P("model", None)


def test_moe_expert_parallel():
    spec = rules.spec_for_param("layers/pos0/moe/w_gate", (32, 16, 4096, 6400), MESH)
    assert spec == P(None, "model", "data", None)


def test_norms_replicated():
    spec = rules.spec_for_param("layers/pos0/norm1", (32, 4096), MESH)
    assert spec == P()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_every_param_gets_valid_spec(arch):
    """Every full-config parameter receives a spec whose sharded dims divide."""
    from repro.models import build_model

    cfg = get_config(arch)
    model = build_model(cfg, jnp.bfloat16)
    p_specs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(p_specs)
    n_sharded = 0
    for kp, leaf in flat:
        path = jax.tree_util.keystr(kp)
        spec = rules.spec_for_param(path, leaf.shape, MESH)
        for dim, entry in zip(leaf.shape, tuple(spec) + (None,) * 10):
            if entry is not None:
                axes = entry if isinstance(entry, tuple) else (entry,)
                size = int(np.prod([MESH.shape[a] for a in axes]))
                assert dim % size == 0, (arch, path, leaf.shape, spec)
                n_sharded += 1
    assert n_sharded > 0, f"{arch}: no parameter sharded at all"


def test_batch_spec_client_axis():
    spec = rules.batch_spec((4, 1, 64, 4096), MESH, client_axis=True)
    assert spec == P(None, None, ("data",), None)
    spec = rules.batch_spec((256, 4096), MESH)
    assert spec == P(("data",), None)
    spec = rules.batch_spec((256, 4096), MESH_POD)
    assert spec == P(("pod", "data"), None)


def test_cache_spec_kv_sequence_parallel():
    spec = rules.cache_spec("pos0/k", (32, 128, 32768, 8, 128), MESH)
    assert spec == P(None, ("data",), "model", None, None)
    # long_500k B=1: everything shards the sequence
    spec = rules.cache_spec("pos0/k", (4, 1, 524288, 8, 128), MESH)
    assert spec == P(None, None, ("data", "model"), None, None)


def test_cache_spec_recurrent_state():
    spec = rules.cache_spec("pos0/ssm", (4, 128, 8192, 16), MESH)
    assert spec == P(None, ("data",), "model", None)


def test_sharded_train_step_runs_on_host_mesh():
    """End-to-end: reduced arch + rule-derived shardings on a 1x1 mesh."""
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model, make_batch
    from repro.configs.base import InputShape
    from repro.core.neural import FedNeuralConfig, make_fsvrg_round, make_client_batches

    mesh = make_host_mesh()
    cfg = get_config("llama3-8b").reduced()
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, InputShape("t", 32, 4, "train"), dtype=jnp.float32)
    cb = make_client_batches(batch, num_clients=2, local_steps=1)

    with jax.set_mesh(mesh):
        in_sh = (rules.params_shardings(params, mesh),
                 rules.batch_shardings(cb, mesh, client_axis=True))
        step = jax.jit(make_fsvrg_round(model, FedNeuralConfig(stepsize=0.3)),
                       in_shardings=in_sh)
        new_params, metrics = step(params, cb)
    l0 = model.loss(params, batch)[0]
    l1 = model.loss(new_params, batch)[0]
    assert float(l1) < float(l0)
    assert bool(jnp.isfinite(metrics["full_grad_norm"]))
