"""Published per-chip peaks: the one table every roofline reads.

Plain data, no jax import, so importing it touches no device state.
"""
from __future__ import annotations

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
# of inter-chip interconnect over 4 links (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,
        "peak_ops_int8": 393e12,
        "hbm_bw": 819e9,            # B/s
        "ici_bw": 50e9,             # B/s per link
        "hbm_bytes": 16e9,
    },
}

V5E = "TPU v5 lite"     # the chip the roofline models and dry-runs target


def chip_peaks(device_kind: str) -> dict:
    """Peaks of one chip; a device kind missing from `PEAKS` is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
