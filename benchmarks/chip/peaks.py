"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A device kind missing from :data:`PEAKS` is an error, never a default: a
roofline against the wrong chip's peaks is a wrong number.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float             # FLOP/s per chip
    hbm_bytes_per_s: float        # B/s per chip
    source: str


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9,
        source="Google Cloud documentation, 'TPU v5e' (system architecture)"),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
