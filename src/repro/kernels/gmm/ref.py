"""Pure-jnp oracle for grouped/batched matmul kernels.

Three entry points:
  * ``ensemble_mlp`` — K-member MLP forward on shared inputs (the MBRL
    dynamics-ensemble training loop, where every member sees every row).
  * ``grouped_matmul`` — equal-group (G, M, K) x (G, K, N) batched matmul
    (MoE capacity buffers) OR, when ``group_sizes`` is given, a RAGGED
    grouped matmul: ``lhs`` is (M, K) with rows sorted by group, row m in
    group g is multiplied by ``rhs[g]`` — M total rows of FLOPs, however
    unevenly the groups are filled.  Zero-size groups are legal.
  * ``ensemble_mlp_select`` — the sample-then-compute imagination path:
    each row is evaluated by exactly ONE assigned member (sort rows by
    member, ragged grouped MLP forward, unsort), so a batch of B rows
    costs B rows of FLOPs instead of K*B.

The ragged oracle materialises the per-row gathered ``rhs`` (M, K, N);
it is the correctness reference, not the fast path — the Pallas kernel
streams group blocks instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _group_ids(group_sizes, m):
    """Row -> group id for rows sorted by group. Rows beyond
    ``sum(group_sizes)`` (e.g. tile padding) clamp to the last group."""
    ends = jnp.cumsum(group_sizes)
    return jnp.searchsorted(ends, jnp.arange(m), side="right").clip(
        0, group_sizes.shape[0] - 1)


def grouped_matmul(lhs, rhs, group_sizes=None):
    """Equal-group: lhs (G, M, K) x rhs (G, K, N) -> (G, M, N).
    Ragged (``group_sizes`` given): lhs (M, K) sorted by group x
    rhs (G, K, N) -> (M, N), with ``group_sizes`` (G,) summing to M.
    f32 accumulation either way."""
    if group_sizes is None:
        return jax.lax.dot_general(
            lhs, rhs, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32).astype(lhs.dtype)
    gid = _group_ids(group_sizes, lhs.shape[0])
    out = jnp.einsum("mk,mkn->mn", lhs, rhs[gid],
                     preferred_element_type=jnp.float32)
    return out.astype(lhs.dtype)


def ensemble_mlp(members, x, *, matmul=grouped_matmul):
    """members: {"w": [ (K,a,b) ... ], "b": [ (K,b) ... ]}; x: (B, Din)
    shared across members. Returns (K, B, Dout). tanh hidden activations.
    ``matmul`` lets the dispatcher swap in the Pallas equal-group kernel."""
    K = members["w"][0].shape[0]
    h = jnp.broadcast_to(x[None], (K,) + x.shape)
    n = len(members["w"])
    for i, (w, b) in enumerate(zip(members["w"], members["b"])):
        h = matmul(h, w) + b[:, None, :]
        if i < n - 1:
            h = jnp.tanh(h)
    return h


def ensemble_mlp_select(members, x, idx, *, matmul=grouped_matmul):
    """Per-row member-assigned MLP forward (sort / compute / unsort).

    x: (B, Din); idx: (B,) int member assignment. Row b flows through
    member ``idx[b]`` only — equivalent to ``ensemble_mlp(...)[idx[b], b]``
    at 1/K the FLOPs. Implementation contract: rows are sorted by member,
    each layer is one ragged ``grouped_matmul`` over the (B, .) batch with
    ``group_sizes = bincount(idx)`` (empty members are zero-size groups),
    and the result is scattered back to input order. ``matmul`` lets the
    dispatcher swap in the Pallas ragged kernel."""
    K = members["w"][0].shape[0]
    order = jnp.argsort(idx)
    gid = idx[order]
    group_sizes = jnp.bincount(idx, length=K)
    h = x[order]
    n = len(members["w"])
    for i, (w, b) in enumerate(zip(members["w"], members["b"])):
        h = matmul(h, w, group_sizes) + b[gid]
        if i < n - 1:
            h = jnp.tanh(h)
    return jnp.zeros_like(h).at[order].set(h)
