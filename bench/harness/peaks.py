"""Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
interconnect (4 links of 50 GB/s). No float32 peak is published; the
kernels here run float32 at HIGHEST, so their shares are taken against
the bf16 peak. An unknown device kind is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s_per_link": 50e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add them to PEAKS with a source")
    return PEAKS[device_kind]
