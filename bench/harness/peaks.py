"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A kind missing from the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16 and
    # 16 GB of HBM at 819 GB/s per chip
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; add "
            "them, with their source, to bench/harness/peaks.py") from None
