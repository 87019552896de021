"""Peak rates of each chip, keyed by JAX's ``device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GiB HBM per chip. A kind not
in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; add "
                       "its published peaks to bench/peaks.py")
    return PEAKS[device_kind]
