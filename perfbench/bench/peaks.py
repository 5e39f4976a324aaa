"""Published per-chip peak rates, keyed by JAX's ``device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s in
bf16, 16 GB of HBM at 819 GB/s.  A device that is not in the table is an
error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,         # FLOP/s
        "hbm_bw": 819e9,              # B/s
        "hbm_bytes": 16e9,            # B
    },
}


def peaks(device_kind: str) -> dict:
    """The peak-rate row of ``device_kind``; raises for an unknown device."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peak rates for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
