"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from ``repro.analysis.roofline.CHIPS`` so that a program change
cannot move the yardstick. Source: Google Cloud documentation, "TPU v5e":
197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes": 16e9,
                    "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    """Peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
