"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16×16 = 256 v5e chips, axes
('data','model').  Multi-pod: 2×16×16 = 512 chips, axes
('pod','data','model') — the pod axis is pure DP (and, in federated mode,
the client-group axis).
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    # Auto axes: the sharding rules place arrays and let XLA propagate;
    # jax.make_mesh's default Explicit axes would turn every
    # with_sharding_constraint hint (sharding/hints.py) into an assertion
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1×1 mesh over the single CPU device (smoke tests)."""
    return _mesh((1, 1), ("data", "model"))
