"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip
interconnect.  A device kind that is not listed is an error, never a
default: a share of a peak the table does not know is no number at all.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unlisted kind raises."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
