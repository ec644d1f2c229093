"""Peaks of the devices the benchmark knows, keyed by ``device_kind``.

One table; a device that is not in it is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2e at 819 GB/s, per chip.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e system "
                  "architecture: per-chip peak compute and HBM bandwidth)",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add it to "
            f"benchmark/peaks.py with its source (known: {sorted(PEAKS)})"
        ) from None
