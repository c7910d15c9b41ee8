"""Dispatching wrapper for the Mamba-2 SSD scan."""
from __future__ import annotations

import jax

from repro.kernels.ssd import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def ssd(x, dt, A, B_, C, *, chunk: int = 128, initial_state=None,
        return_final_state: bool = False, impl: str | None = None,
        interpret: bool = False):
    if impl is None:
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "pallas":
        from repro.kernels.ssd import pallas as pk
        return pk.ssd_chunked(x, dt, A, B_, C, chunk=chunk,
                              initial_state=initial_state,
                              return_final_state=return_final_state,
                              interpret=interpret)
    return ref.ssd_chunked(x, dt, A, B_, C, chunk=chunk,
                           initial_state=initial_state,
                           return_final_state=return_final_state)


ssd_decode_step = ref.ssd_decode_step
ssd_sequential = ref.ssd_sequential
