"""Run row-wise Pallas entry points on each shard of a device mesh.

XLA cannot partition a Mosaic kernel: inside a jit whose arrays span more
than one device, a bare ``pallas_call`` is refused at lowering. The
kernel entry points that take this path (``gmm.ensemble_mlp``,
``gmm.ensemble_mlp_select``, ``imag.fused_step``) are row-wise — row b
of the output depends on row b of the batched inputs only — so under a
multi-device *ambient* mesh they run once per shard through
``jax.shard_map``: rows split over every mesh axis, weights replicated.
Gradients flow through the shard_map (replicated inputs' cotangents are
summed over shards). On one device the entry points run unchanged.

The ambient mesh is set at trace time by code that owns a role sub-mesh
(``on_mesh``): the ring trainer and the ME algorithms.
"""
from __future__ import annotations

import contextlib

import jax
from jax.sharding import PartitionSpec as P


def on_mesh(mesh):
    """Trace-time scope that makes ``mesh`` the ambient mesh (a no-op
    for ``None`` or a one-device mesh)."""
    if mesh is None or mesh.size == 1:
        return contextlib.nullcontext()
    return jax.sharding.use_abstract_mesh(mesh.abstract_mesh)


def row_axes():
    """Axes of the ambient mesh when it spans more than one device."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty or am.size == 1:
        return None
    return tuple(am.axis_names)


def per_shard(fn, row_dims, out_row_dim):
    """``fn`` run per shard of the ambient mesh, or ``fn`` itself.

    ``row_dims[i]``: the row dimension of positional argument i, or None
    for a replicated argument (a pytree of weights). ``out_row_dim``: the
    row dimension of every output leaf."""
    axes = row_axes()
    if axes is None:
        return fn
    spec = lambda d: P() if d is None else P(*([None] * d), axes)
    return jax.shard_map(fn, in_specs=tuple(spec(d) for d in row_dims),
                         out_specs=spec(out_row_dim), check_vma=False)
