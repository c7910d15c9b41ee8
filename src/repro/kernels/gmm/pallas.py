"""Pallas TPU grouped matmul (megablox-style) for MoE expert FFNs and
the MBRL dynamics-ensemble MLP.

Two kernels:

* equal-group: grid (G, M/bm, N/bn, K/bk); the contraction axis is
  innermost (sequential) with a f32 VMEM accumulator scratch; every
  group's (bm x bk)·(bk x bn) tile hits the MXU.
* ragged: ``grouped_matmul(lhs (M, K), rhs (G, K, N), group_sizes)``
  with lhs rows sorted by group. Group offsets ride in via scalar
  prefetch; grid (M/bm, N/bn, G, K/bk) accumulates every group's
  contribution to an output tile in a VMEM scratch, masking the rows of
  boundary tiles a group only partially covers and skipping (``pl.when``)
  tiles a group does not touch at all — zero-size groups therefore cost
  no MXU work. FLOPs scale with M, not G*M.

Kernels only: ``ops.py`` wraps them in a ``custom_vjp`` and builds the
ensemble MLPs on top. Validated with interpret=True against ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32 products on the MXU, stated, not left to Mosaic's default: the
# oracle's contract is f32, and on the chip it is checked at HIGHEST
_F32 = jax.lax.Precision.HIGHEST


def _kernel(lhs_ref, rhs_ref, out_ref, acc_scr, *, nk):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        lhs_ref[0].astype(jnp.float32), rhs_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=_F32,
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _done():
        out_ref[0] = acc_scr[...].astype(out_ref.dtype)


def _equal_grouped_matmul(lhs, rhs, *, block_m, block_n, block_k,
                          interpret):
    """lhs: (G, M, K); rhs: (G, K, N) -> (G, M, N)."""
    G, M, K = lhs.shape
    _, _, N = rhs.shape
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    lp = jnp.pad(lhs, ((0, 0), (0, pm), (0, pk)))
    rp = jnp.pad(rhs, ((0, 0), (0, pk), (0, pn)))
    nm, nn, nk = (M + pm) // bm, (N + pn) // bn, (K + pk) // bk

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(G, nm, nn, nk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda g, i, j, k: (g, i, k)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, k: (g, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, M + pm, N + pn), lhs.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(lp, rp)
    return out[:, :M, :N]


def _ragged_kernel(offs_ref, lhs_ref, rhs_ref, out_ref, acc_scr, *,
                   bm, ng, nk):
    i = pl.program_id(0)
    g = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when((g == 0) & (k == 0))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start, end = offs_ref[g], offs_ref[g + 1]
    tile_lo = i * bm

    # this group touches rows [start, end); skip tiles it doesn't reach
    @pl.when((end > tile_lo) & (start < tile_lo + bm))
    def _accum():
        rows = tile_lo + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        mask = (rows >= start) & (rows < end)
        lhs = jnp.where(mask, lhs_ref[...].astype(jnp.float32), 0.0)
        acc_scr[...] += jax.lax.dot_general(
            lhs, rhs_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32)

    @pl.when((g == ng - 1) & (k == nk - 1))
    def _done():
        out_ref[...] = acc_scr[...].astype(out_ref.dtype)


def _ragged_grouped_matmul(lhs, rhs, group_sizes, *, block_m, block_n,
                           block_k, interpret):
    """lhs: (M, K) rows sorted by group; rhs: (G, K, N);
    group_sizes: (G,) summing to M -> (M, N)."""
    M, K = lhs.shape
    G, _, N = rhs.shape
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    lp = jnp.pad(lhs, ((0, pm), (0, pk)))
    rp = jnp.pad(rhs, ((0, 0), (0, pk), (0, pn)))
    nm, nn, nk = (M + pm) // bm, (N + pn) // bn, (K + pk) // bk
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(group_sizes).astype(jnp.int32)])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nm, nn, G, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, g, k, offs: (i, k)),
            pl.BlockSpec((1, bk, bn), lambda i, j, g, k, offs: (g, k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, g, k, offs: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, bm=bm, ng=G, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M + pm, N + pn), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(offs, lp, rp)
    return out[:M, :N]


def grouped_matmul(lhs, rhs, group_sizes=None, *, block_m: int = 128,
                   block_n: int = 128, block_k: int = 128,
                   interpret: bool = False):
    """Equal-group (lhs 3d, no sizes) or ragged (lhs 2d + group_sizes)
    grouped matmul — same contract as ``ref.grouped_matmul``."""
    if group_sizes is None:
        return _equal_grouped_matmul(lhs, rhs, block_m=block_m,
                                     block_n=block_n, block_k=block_k,
                                     interpret=interpret)
    return _ragged_grouped_matmul(lhs, rhs, group_sizes, block_m=block_m,
                                  block_n=block_n, block_k=block_k,
                                  interpret=interpret)

