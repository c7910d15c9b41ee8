"""Dispatching wrapper for the fused imagination step.

``fused_step(members, norm, pol, s, eps, member_idx)`` runs one whole
imagination step — policy head, reparameterised action sample, assigned-
member dynamics forward, denormalised next state — as a single
dispatchable unit. ``impl``:

* ``pallas`` — sort rows by member, one Pallas megakernel over the
  row-blocks (policy + member MLPs fused in VMEM, scalar-prefetch group
  offsets, masked boundary tiles, zero-size-group skip), unsort. B rows
  of MXU FLOPs regardless of K. Default on TPU. The rows move as one
  slab each way: ``[s | eps]`` goes in by one gather (``order``) and
  the kernel's ``[s2 | a | pre]`` comes back by one gather (``inv``);
  a row gather costs half a row scatter on the chip, and each costs by
  the row, not the byte. Differentiable like the other impls: a
  ``custom_vjp`` backs the kernel with the jnp reference's VJP (a gather
  differentiates natively). No trainer path differentiates a rollout:
  MB-MPO samples from parameters held fixed.
* ``fused`` — the XLA-fused flat spelling: the policy head feeds
  straight into one flattened ``(B, din) @ (din, K*dout)`` matmul per
  dynamics layer with a per-layer member gather. K*B FLOPs, but tiny
  MBRL ensembles on CPU are launch- not FLOP-bound (the same trade as
  ``kernels/gmm``'s ``dense`` select), and collapsing the per-step
  sort / ragged matmul / unsort / policy round-trips into this one
  straight-line body is what cuts the CPU rollout latency (measured in
  ``benchmarks/hotpath.py`` as ``imagine_fused_speedup_x``). Default on
  CPU.
* ``ref`` — the pure-jnp oracle (dense compute-all + select), the
  bit-reference for both.

``sort_plan`` precomputes the pallas impl's sort/unsort plan (the
order, the group offsets and the inverse order); the rollout calls it
ONCE for the whole horizon's member draws so no argsort, bincount or
inversion runs inside the scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.imag import ref
from repro.kernels.mesh import per_shard, row_axes


def default_impl() -> str:
    """Backend-chosen impl: the megakernel on TPU, the XLA-fused flat
    spelling elsewhere (CPU/GPU have no Mosaic lowering)."""
    return "pallas" if jax.default_backend() == "tpu" else "fused"


def sort_plan(member_idx, n_groups: int):
    """Sort/unsort plan for the pallas impl: ``(order, offsets, inv)``.

    member_idx: (..., B) int — leading axes (e.g. the horizon) are
    planned in one call, so the rollout scan carries precomputed plans
    instead of re-sorting every step. ``order`` sorts the trailing axis
    by member; ``offsets`` (..., K+1) are cumulative group offsets;
    ``inv`` is the inverse permutation (``inv[order] == arange(B)``), so
    rows return to input order by a gather, ``v[inv]``, not a scatter.
    """
    order = jnp.argsort(member_idx, axis=-1)
    # the inverse by a second sort, not a scatter: on a v5e scattering
    # the (40, 4096) horizon plan took ten times as long as sorting it
    inv = jnp.argsort(order, axis=-1)
    sizes = (member_idx[..., :, None]
             == jnp.arange(n_groups)).sum(axis=-2)
    zeros = jnp.zeros(sizes.shape[:-1] + (1,), jnp.int32)
    offsets = jnp.concatenate(
        [zeros, jnp.cumsum(sizes, axis=-1).astype(jnp.int32)], axis=-1)
    return order, offsets, inv


def _fused_flat(members, norm, pol, s, eps, member_idx):
    """XLA fallback: one flattened matmul + member gather per layer."""
    mu = ref.policy_mu(pol, s)
    pre = mu + jnp.exp(pol["log_std"]) * eps
    a = jnp.tanh(pre)
    x = jnp.concatenate([s, a], -1)
    h = (x - norm["mu_in"]) / norm["sig_in"]
    K = members["w"][0].shape[0]
    col = member_idx[:, None, None]
    n = len(members["w"])
    for i, (w, b) in enumerate(zip(members["w"], members["b"])):
        din, dout = w.shape[1], w.shape[2]
        hk = (h @ w.transpose(1, 0, 2).reshape(din, K * dout)
              ).reshape(h.shape[0], K, dout)
        h = jnp.take_along_axis(hk, col, axis=1)[:, 0] + b[member_idx]
        if i < n - 1:
            h = jnp.tanh(h)
    s2 = s + h * norm["sig_out"] + norm["mu_out"]
    return s2, a, pre


# ---------------------------------------------------------------- pallas
# The kernel has no autodiff rule, so the pallas impl carries a
# custom_vjp, and ``jax.grad`` through a step works on every impl alike
# (a caller that differentiates imagination need not pick one). The
# backward pass is the VJP of the jnp reference on the same sorted rows;
# differentiating it again under a vmap over member slices does not
# trace (JAX 0.9.0), and nothing in the trainer does. ``x`` is the
# (B, obs+act) slab ``[s | eps]`` in member order, the output the
# (B, obs+2*act) slab ``[s2 | a | pre]`` in the same order.

def _sorted_ids(offsets, n_rows):
    """Member id of each sorted row, read off the cumulative offsets."""
    rows = jnp.arange(n_rows, dtype=jnp.int32)[:, None]
    return jnp.sum(rows >= offsets[1:-1], axis=1, dtype=jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _pallas_sorted(interpret, block_b, offsets, members, norm, pol, x):
    from repro.kernels.imag import pallas as pk
    return pk.fused_step_sorted(members, norm, pol, x, offsets,
                                block_b=block_b, interpret=interpret)


def _pallas_sorted_fwd(interpret, block_b, offsets, members, norm, pol,
                       x):
    out = _pallas_sorted(interpret, block_b, offsets, members, norm, pol,
                         x)
    gid = _sorted_ids(offsets, x.shape[0])
    return out, (gid, members, norm, pol, x)


def _pallas_sorted_bwd(interpret, block_b, res, ct):
    gid, members, norm, pol, x = res
    obs_dim = norm["mu_out"].shape[-1]

    def step(m, n, p, x_):
        return jnp.concatenate(ref.fused_step(
            m, n, p, x_[:, :obs_dim], x_[:, obs_dim:], gid), axis=1)

    _, vjp = jax.vjp(step, members, norm, pol, x)
    return (None,) + vjp(ct)


_pallas_sorted.defvjp(_pallas_sorted_fwd, _pallas_sorted_bwd)


def fused_step(members, norm, pol, s, eps, member_idx, *,
               impl: str | None = None, interpret: bool = False,
               plan=None, block_b: int = 128):
    """One fused imagination step; see module docstring for impls.

    ``plan``: optional precomputed ``sort_plan`` output for this step
    (pallas impl only — ``fused``/``ref`` are row-order-blind and ignore
    it). Returns ``(s2, a, pre)`` in input row order."""
    if impl is None:
        impl = default_impl()
    if impl == "pallas":
        if row_axes() is not None:
            plan = None         # per shard: each shard sorts its own rows

        def step(members, norm, pol, s, eps, member_idx):
            order, offsets, inv = plan if plan is not None else sort_plan(
                member_idx, members["w"][0].shape[0])
            x = jnp.concatenate([s, eps], axis=1)[order]
            out = _pallas_sorted(interpret, block_b, offsets, members, norm,
                                 pol, x)[inv]
            obs_dim, act_dim = s.shape[1], eps.shape[1]
            return (out[:, :obs_dim], out[:, obs_dim:obs_dim + act_dim],
                    out[:, obs_dim + act_dim:])
        return per_shard(step, (None, None, None, 0, 0, 0), 0)(
            members, norm, pol, s, eps, member_idx)
    if impl == "fused":
        return _fused_flat(members, norm, pol, s, eps, member_idx)
    return ref.fused_step(members, norm, pol, s, eps, member_idx)
