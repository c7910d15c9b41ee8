"""Dispatching wrapper for attention.

``attention(...)`` routes to the Pallas TPU kernel when running on TPU (or
when forced via ``impl='pallas'`` with ``interpret=True`` in tests), and to
the chunked pure-jnp reference otherwise. The dry-run lowers the reference
path; its FLOPs/bytes are identical to the kernel's.
"""
from __future__ import annotations


import jax

from repro.kernels.flash_attention import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              scale: float | None = None, impl: str | None = None,
              interpret: bool = False, block_q: int = 512, block_k: int = 512):
    if impl is None:
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "pallas":
        from repro.kernels.flash_attention import pallas as pk
        return pk.flash_attention(q, k, v, causal=causal, window=window,
                                  scale=scale, interpret=interpret)
    if impl == "naive":
        return ref.naive_attention(q, k, v, causal=causal, window=window,
                                   scale=scale)
    return ref.chunked_attention(q, k, v, causal=causal, window=window,
                                 scale=scale, block_q=block_q, block_k=block_k)


decode_attention_partial = ref.decode_attention_partial
combine_partials = ref.combine_partials
