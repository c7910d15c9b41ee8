"""Dispatching wrapper for grouped matmul / ensemble MLP.

``grouped_matmul`` covers both layouts: equal-group batched (lhs 3d) and
ragged (lhs 2d + ``group_sizes``, rows sorted by group — MegaBlocks-style
sample-then-compute).  ``ensemble_mlp_select`` is the per-row
member-assigned forward built on the ragged layout; its ``impl``:

* ``pallas`` — sort rows by member, ragged Pallas kernel, unsort.
  B rows of MXU FLOPs regardless of K. Default on TPU.
* ``ref``    — same sort/compute/unsort contract on the pure-jnp ragged
  oracle (gathers per-row weights). The parity baseline.
* ``dense``  — evaluate ALL K members and select (K*B FLOPs). Small
  ensembles on small hosts (CPU imagination, K<=5, hidden<=128) are
  latency- not FLOP-bound, and one batched matmul beats per-row weight
  gathers there — measured in benchmarks/hotpath.py. Default on CPU
  only; GPU defaults to ``ref`` (FLOP-bound at real sizes, and the
  gathered batched matmul keeps the no-K*-overcompute invariant).

Every ``pallas`` entry point is differentiable: the kernel has no
autodiff rule of its own, so ``_pallas_gmm`` carries a ``custom_vjp``
(the dynamics learner and MoE training take gradients through it). The
row-wise ensemble entry points run per shard under a multi-device
ambient mesh (``kernels/mesh.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gmm import ref
from repro.kernels.mesh import per_shard


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------- pallas
# Forward = the kernel. Backward, for out = lhs @ rhs[group]:
#   d_lhs = ct @ rhs[group]^T  — the same kernel, rhs transposed, in
#           both layouts (ragged rows keep their groups).
#   d_rhs[g] = lhs[rows of g]^T @ ct[rows of g] — equal-group: the kernel
#           with lhs transposed; ragged: a row-to-group mask contraction
#           in XLA at full f32 precision (the kernel's own precision).
# ``group_sizes`` is integer data: its cotangent is None.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pallas_gmm(lhs, rhs, group_sizes, interpret):
    from repro.kernels.gmm import pallas as pk
    return pk.grouped_matmul(lhs, rhs, group_sizes, interpret=interpret)


def _pallas_gmm_fwd(lhs, rhs, group_sizes, interpret):
    return (_pallas_gmm(lhs, rhs, group_sizes, interpret),
            (lhs, rhs, group_sizes))


def _pallas_gmm_bwd(interpret, res, ct):
    from repro.kernels.gmm import pallas as pk
    lhs, rhs, group_sizes = res
    rhs_t = jnp.swapaxes(rhs, 1, 2)
    d_lhs = pk.grouped_matmul(ct, rhs_t, group_sizes, interpret=interpret)
    if group_sizes is None:
        d_rhs = pk.grouped_matmul(jnp.swapaxes(lhs, 1, 2), ct,
                                  interpret=interpret)
    else:
        ends = jnp.cumsum(group_sizes)
        rows = jnp.arange(lhs.shape[0])
        in_group = ((rows[None, :] >= (ends - group_sizes)[:, None])
                    & (rows[None, :] < ends[:, None])).astype(lhs.dtype)
        d_rhs = jnp.einsum("gm,mk,mn->gkn", in_group, lhs, ct,
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
    return d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype), None


_pallas_gmm.defvjp(_pallas_gmm_fwd, _pallas_gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes=None, *, impl: str | None = None,
                   interpret: bool = False):
    if impl is None:
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "pallas":
        return _pallas_gmm(lhs, rhs, group_sizes, interpret)
    return ref.grouped_matmul(lhs, rhs, group_sizes)


def _pallas_matmul(interpret):
    return lambda lhs, rhs, group_sizes=None: _pallas_gmm(
        lhs, rhs, group_sizes, interpret)


def ensemble_mlp(members, x, *, impl: str | None = None,
                 interpret: bool = False):
    if impl is None:
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "pallas":
        fn = lambda m, x_: ref.ensemble_mlp(
            m, x_, matmul=_pallas_matmul(interpret))
        return per_shard(fn, (None, 0), 1)(members, x)
    return ref.ensemble_mlp(members, x)


def ensemble_mlp_select(members, x, idx, *, impl: str | None = None,
                        interpret: bool = False):
    """Forward row b through member ``idx[b]`` only. Same output as
    ``ensemble_mlp(members, x)[idx[b], b]`` for every b."""
    if impl is None:
        backend = jax.default_backend()
        impl = ("pallas" if backend == "tpu"
                else "dense" if backend == "cpu" else "ref")
    if impl == "pallas":
        fn = lambda m, x_, i: ref.ensemble_mlp_select(
            m, x_, i, matmul=_pallas_matmul(interpret))
        return per_shard(fn, (None, 0, 0), 0)(members, x, idx)
    if impl == "ref":
        return ref.ensemble_mlp_select(members, x, idx)
    preds = ref.ensemble_mlp(members, x)            # (K, B, D)
    return jnp.take_along_axis(
        preds, idx[None, :, None], axis=0)[0]
