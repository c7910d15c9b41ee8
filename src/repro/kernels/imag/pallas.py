"""Pallas TPU megakernel for the fused imagination step (ISSUE 10).

One ``pallas_call`` per horizon step: for each batch row-block the
kernel runs the policy MLP head, forms the pre-tanh/tanh actions from
pre-drawn noise, normalises the dynamics input into a VMEM scratch, and
then sweeps the ensemble members sequentially — each member's whole MLP
forward runs on the row-block with every intermediate activation held in
VMEM (nothing spills to HBM between layers), and only the rows assigned
to that member are accumulated into the output.

Layout follows the ragged ``gmm`` kernel: rows arrive PRE-SORTED by
member, as one ``[s | eps]`` slab, and leave as one ``[s2 | a | pre]``
slab in the same order, so the dispatcher moves each way with a single
row gather; cumulative group offsets ride in via scalar prefetch
(``PrefetchScalarGridSpec``), boundary tiles a member only partially
covers are row-masked with a ``broadcasted_iota`` compare, and tiles a
member does not touch at all are skipped with ``pl.when`` — zero-size
groups (members no row sampled) cost no MXU work.

Grid: ``(B/bm, K)`` with the member dimension innermost and
``arbitrary`` (sequential), so the per-block scratches written at
``g == 0`` (normalised input, zeroed accumulator) stay live across the
member sweep. The output block, indexed by the row block alone, stays
resident too: ``a`` and ``pre`` are written into it at ``g == 0`` and
the next state at ``g == K - 1``.

Validated with ``interpret=True`` against ``ref`` (the pure-jnp oracle);
on real TPUs the tiny MBRL feature dims (obs+act < 8) would be padded to
the (8, 128) f32 tile by Mosaic — see docs/KERNELS.md for the bring-up
checklist.
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


def _fused_kernel(offs_ref, x_ref, *refs, bm, n_groups, obs_dim, n_dyn,
                  n_pol):
    dyn_w = refs[:n_dyn]
    dyn_b = refs[n_dyn:2 * n_dyn]
    pol_w = refs[2 * n_dyn:2 * n_dyn + n_pol]
    pol_b = refs[2 * n_dyn + n_pol:2 * n_dyn + 2 * n_pol]
    (log_std_ref, mu_in_ref, sig_in_ref, mu_out_ref, sig_out_ref,
     out_ref, xn_scr, acc_scr) = refs[2 * (n_dyn + n_pol):]

    i = pl.program_id(0)
    g = pl.program_id(1)

    @pl.when(g == 0)
    def _policy_head():
        # policy MLP + reparameterised sample, all in VMEM
        x = x_ref[...].astype(jnp.float32)
        s, eps = x[:, :obs_dim], x[:, obs_dim:]
        h = s
        for li, (w, b) in enumerate(zip(pol_w, pol_b)):
            h = jax.lax.dot_general(
                h, w[...].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                precision=_F32,
                preferred_element_type=jnp.float32) + b[...]
            if li < n_pol - 1:
                h = jnp.tanh(h)
        pre = h + jnp.exp(log_std_ref[...].astype(jnp.float32)) * eps
        a = jnp.tanh(pre)
        out_ref[:, obs_dim:] = jnp.concatenate([a, pre], axis=1).astype(
            out_ref.dtype)
        xn = jnp.concatenate([s, a], axis=1)
        xn_scr[...] = (xn - mu_in_ref[...]) / sig_in_ref[...]
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start, end = offs_ref[g], offs_ref[g + 1]
    tile_lo = i * bm

    # member g owns sorted rows [start, end); skip blocks it doesn't touch
    @pl.when((end > tile_lo) & (start < tile_lo + bm))
    def _member_mlp():
        h = xn_scr[...]
        for li, (w, b) in enumerate(zip(dyn_w, dyn_b)):
            h = jax.lax.dot_general(
                h, w[0].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                precision=_F32,
                preferred_element_type=jnp.float32) + b[0]
            if li < n_dyn - 1:
                h = jnp.tanh(h)
        rows = tile_lo + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        mask = (rows >= start) & (rows < end)
        acc_scr[...] += jnp.where(mask, h, 0.0)

    @pl.when(g == n_groups - 1)
    def _emit_next_state():
        s2 = x_ref[:, :obs_dim].astype(jnp.float32) \
            + acc_scr[...] * sig_out_ref[...] + mu_out_ref[...]
        out_ref[:, :obs_dim] = s2.astype(out_ref.dtype)


def fused_step_sorted(members, norm, pol, x, offsets, *,
                      block_b: int = 128, interpret: bool = False):
    """Fused step on rows PRE-SORTED by member.

    x: (B, obs+act) rows ``[s | eps]``; offsets: (K+1,) int32 cumulative
    group offsets (``offsets[g]..offsets[g+1]`` are member g's rows).
    Returns the (B, obs+2*act) rows ``[s2 | a | pre]`` in the same sorted
    order; the dispatcher owns the sort/unsort (hoisted out of the
    rollout scan), one row gather each way.
    """
    B, din = x.shape
    obs_dim = norm["mu_out"].shape[-1]
    act_dim = din - obs_dim
    dout = obs_dim + 2 * act_dim
    K = members["w"][0].shape[0]
    n_dyn, n_pol = len(members["w"]), len(pol["w"])
    bm = min(block_b, B)
    pm = (-B) % bm
    nm = (B + pm) // bm
    xp = jnp.pad(x, ((0, pm), (0, 0)))

    # 1-D params ride in as (1, dim) blocks (TPU refs want >= 2-D)
    row = lambda v: v.reshape(1, -1)
    operands = (
        [xp]
        + list(members["w"])                       # (K, din, dout) each
        + [b.reshape(K, 1, -1) for b in members["b"]]
        + list(pol["w"])                           # (din, dout) each
        + [row(b) for b in pol["b"]]
        + [row(pol["log_std"]), row(norm["mu_in"]), row(norm["sig_in"]),
           row(norm["mu_out"]), row(norm["sig_out"])]
    )

    def fixed(shape):        # whole-array block, same for every (i, g)
        nd = len(shape)
        return pl.BlockSpec(shape, lambda i, g, offs, _n=nd: (0,) * _n)

    def member_block(shape):  # (1, ·, ·) slice of a (K, ·, ·) stack at g
        return pl.BlockSpec((1,) + shape[1:],
                            lambda i, g, offs: (g,) + (0,) * (len(shape) - 1))

    in_specs = (
        [pl.BlockSpec((bm, din), lambda i, g, offs: (i, 0))]
        + [member_block(w.shape) for w in members["w"]]
        + [member_block((K, 1, b.shape[-1])) for b in members["b"]]
        + [fixed(w.shape) for w in pol["w"]]
        + [fixed((1, b.shape[-1])) for b in pol["b"]]
        + [fixed((1, act_dim)), fixed((1, din)), fixed((1, din)),
           fixed((1, obs_dim)), fixed((1, obs_dim))]
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nm, K),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, dout), lambda i, g, offs: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bm, din), jnp.float32),
                        pltpu.VMEM((bm, obs_dim), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_fused_kernel, bm=bm, n_groups=K,
                          obs_dim=obs_dim, n_dyn=n_dyn, n_pol=n_pol),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B + pm, dout), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(offsets.astype(jnp.int32), *operands)
    return out[:B]
