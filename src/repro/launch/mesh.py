"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes. jax's default is Explicit, which
    types every array with its sharding and refuses ops (such as the
    sort-gather of the TPU imagination kernel) whose output sharding is
    ambiguous; this code places arrays with sharding constraints and
    lets XLA propagate the rest."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod meshes: 16x16 = 256 chips per pod; 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh():
    """1x1 mesh for CPU smoke tests (same code path, trivial collectives)."""
    return make_mesh((1, 1), ("data", "model"))
