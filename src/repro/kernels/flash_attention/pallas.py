"""Pallas TPU flash attention (forward).

TPU-native adaptation (not a CUDA port): the grid's innermost dimension
iterates sequentially on a core, so the online-softmax running state
(m, l, acc) lives in VMEM scratch carried across kv-block grid steps —
no atomics, no shared-memory tiling. Block sizes default to MXU-aligned
(128 multiples). GQA is expressed in the kv BlockSpec index_map
(q head h reads kv head h // group).

Validated on CPU via interpret=True against ref.naive_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            block_q, block_k, nk, scale, causal, window, sq, sk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (block_q, d)
    k = k_ref[0, 0].astype(jnp.float32)          # (block_k, d)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    offset = sk - sq
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + offset
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < sk
    if causal:
        mask &= k_pos <= q_pos
    if window and window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_cur = l_scr[...] * alpha + p.sum(-1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_cur
    l_scr[...] = l_cur
    acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[0, 0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale=None, block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D) -> (B, Sq, Hq, D)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    qp = jnp.moveaxis(jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))), 2, 1)
    kp = jnp.moveaxis(jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))), 2, 1)
    vp = jnp.moveaxis(jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))), 2, 1)
    nq = qp.shape[2] // block_q
    nk = kp.shape[2] // block_k

    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, nk=nk, scale=scale,
        causal=causal, window=window, sq=Sq, sk=Sk)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, nq * block_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return jnp.moveaxis(out, 1, 2)[:, :Sq]
