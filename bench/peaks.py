"""Published peaks of each chip the benchmark may run on, keyed by the
``device_kind`` string JAX reports.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip. The numbers were
first carried in ``src/repro/analysis/roofline.py`` (``PEAK_FLOPS``,
``HBM_BW``); this table is the benchmark's own copy.

A kind that is not in the table is an error, never a default: a roofline
share against the wrong chip's peak is a wrong number.
"""

from __future__ import annotations

__all__ = ["PEAKS", "peaks_for"]

# device_kind -> {"flops": FLOP/s (bf16 MXU), "hbm_bytes_s": bytes/s}
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}") from None
