"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind missing here is an error, never a
default: a roofline share against a guessed peak means nothing.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add them to bench/peaks.py with their source")
    return PEAKS[device_kind]
