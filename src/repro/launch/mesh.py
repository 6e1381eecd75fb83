"""Production meshes. Functions, not module constants: importing this module
never touches jax device state (the dry-run sets XLA_FLAGS first).

Axes are Auto: the model and graph code places data with
`with_sharding_constraint` / `NamedSharding`, which only Auto axes accept
(`jax.make_mesh` defaults to Explicit axes)."""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has (smoke tests, examples): 1D data mesh."""
    n = len(jax.devices())
    return _mesh((n,), ("data",))
