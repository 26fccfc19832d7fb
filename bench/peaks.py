"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
394 TOP/s int8, 16 GB of HBM at 819 GB/s per chip. The profiler's own
device plane on a v5e reports 819.16 GB/s. A kind that is not in the
table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 394e12,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"with its source to bench/peaks.py (known: {sorted(PEAKS)})"
        ) from None
