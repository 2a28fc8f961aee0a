"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

SOURCE = 'Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e)'

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str) -> dict:
    """The peak row of ``device_kind``; a device not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)} ({SOURCE})") from None


def least_time_s(flops: float, nbytes: float, device_kind: str) -> float:
    """Roofline floor of one piece of work: the larger of its operations
    over peak bf16 FLOP/s and its bytes over peak HBM bytes/s."""
    p = peak(device_kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
