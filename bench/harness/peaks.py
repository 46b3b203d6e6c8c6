"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
16 GB of HBM2 per chip at 819 GB/s, 197 TFLOP/s bf16, 393 TOP/s int8.
The vector and scalar units that run this simulator's kernels have no
published peak, so only the memory side of a roofline can be formed.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "bf16_flops_per_s": 197e12},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
