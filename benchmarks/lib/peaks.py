"""Published peaks of the chips the benchmark has run on, keyed by JAX's
``device_kind``. A device that is not in the table is an error, never a
default: a share of a peak that nobody looked up means nothing."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
    # 16 GB HBM2e at 819 GB/s per chip.
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}: add it to "
            "benchmarks/lib/peaks.py with its source"
        ) from None
