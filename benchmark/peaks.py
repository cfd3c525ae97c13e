"""Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 16 GB HBM2e at
819 GB/s per chip. A device that is not in the table is an error, never
a default (copied from the program's ``bench.CHIP_PEAKS``, whose table is
sound; the original is listed under Open questions in PERF.md).
"""

from __future__ import annotations

CHIP_PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def chip_peaks(device_kind: str) -> dict:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it "
            "to benchmark/peaks.py with its source"
        ) from None
