"""Published peaks per chip, keyed by JAX's ``device_kind``.

TPU v5e ("TPU v5 lite" in JAX): 197 TFLOP/s bf16 and 819 GB/s of HBM
bandwidth per chip (Google Cloud documentation, "TPU v5e").  A device
kind missing here is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
