"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

TPU adaptation of state-space duality (arXiv:2405.21060): the grid walks
(batch, chunk) with the chunk axis sequential ('arbitrary' semantics), so
the inter-chunk recurrent state lives in VMEM scratch carried between grid
steps — the quadratic intra-chunk block hits the MXU, the O(1) state
update replaces the CUDA kernel's cross-block shuffle.

Layout: heads stay whole inside one kernel invocation (state (H, P, N)
fits VMEM for every assigned config). Validated with interpret=True
against ref.ssd_chunked / ref.ssd_sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_scr, *,
            chunk, nc, H, P, N, G):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)      # (chunk, H, P) — pre-scaled by dt
    dt = dt_ref[0, 0].astype(jnp.float32)    # (chunk, H)
    A = a_ref[...].astype(jnp.float32)    # (H,)
    Bm = b_ref[0, 0].astype(jnp.float32)     # (chunk, G, N)
    Cm = c_ref[0, 0].astype(jnp.float32)     # (chunk, G, N)
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=1)      # (chunk, H, N)
    Ch = jnp.repeat(Cm, rep, axis=1)

    dA = dt * A[None, :]                  # (chunk, H)
    dA_cum = jnp.cumsum(dA, axis=0)       # inclusive
    xs = x * dt[..., None]                # discretised input

    # intra-chunk (quadratic, MXU): L[i,j] = exp(dA_cum_i - dA_cum_j), i>=j
    seg = dA_cum[:, None, :] - dA_cum[None, :, :]          # (q, k, H)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where((ii >= jj)[..., None], jnp.exp(seg), 0.0)  # (q, k, H)
    CB = jnp.einsum("qhn,khn->qkh", Ch, Bh)
    y = jnp.einsum("qkh,qkh,khp->qhp", CB, L, xs)

    # inter-chunk: contribution of the carried state
    state = state_scr[...]                                  # (H, P, N)
    decay_out = jnp.exp(dA_cum)                             # (q, H)
    y += jnp.einsum("qhn,hpn,qh->qhp", Ch, state, decay_out)

    # state update for the next chunk
    chunk_decay = jnp.exp(dA_cum[-1])                       # (H,)
    decay_states = jnp.exp(dA_cum[-1][None] - dA_cum)       # (q, H)
    new_state = state * chunk_decay[:, None, None] + jnp.einsum(
        "qhn,qh,qhp->hpn", Bh, decay_states, xs)
    state_scr[...] = new_state
    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd_chunked(x, dt, A, B_, C, *, chunk: int = 128, initial_state=None,
                return_final_state: bool = False, interpret: bool = False):
    """Same contract as ref.ssd_chunked (no initial_state support in the
    kernel path — prefill uses the reference; decode uses the recurrence)."""
    assert initial_state is None and not return_final_state, \
        "pallas path covers the training forward; stateful prefill uses ref"
    B, L, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    pad = (-L) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Lp = x.shape[1]
    nc = Lp // chunk
    xr = x.reshape(B, nc, chunk, H, P)
    dtr = dt.reshape(B, nc, chunk, H)
    Br = B_.reshape(B, nc, chunk, G, N)
    Cr = C.reshape(B, nc, chunk, G, N)

    kernel = functools.partial(_kernel, chunk=chunk, nc=nc, H=H, P=P, N=N,
                               G=G)
    y = pl.pallas_call(
        kernel,
        grid=(B, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, H, P), lambda b, c: (b, c, 0, 0, 0)),
            pl.BlockSpec((1, 1, chunk, H), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((H,), lambda b, c: (0,)),
            pl.BlockSpec((1, 1, chunk, G, N), lambda b, c: (b, c, 0, 0, 0)),
            pl.BlockSpec((1, 1, chunk, G, N), lambda b, c: (b, c, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, H, P),
                               lambda b, c: (b, c, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nc, chunk, H, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((H, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xr, dtr, A, Br, Cr)
    return y.reshape(B, Lp, H, P)[:, :L]
