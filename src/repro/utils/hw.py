"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A device kind missing from :data:`PEAKS` is an error, never a default: a
roofline against the wrong chip's peaks is a wrong number, not an estimate.

``ici_link_bytes_per_s`` is ONE ICI link (the roofline's collective term
divides per-chip wire bytes by a single link: ring collectives on one mesh
axis keep one link pair busy; a bidirectional ring would halve the term).
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float             # FLOP/s per chip
    hbm_bytes_per_s: float        # B/s per chip
    ici_link_bytes_per_s: float   # B/s per ICI link
    source: str


#: the v5e's ``device_kind`` as JAX reports it
V5E = "TPU v5 lite"

PEAKS = {
    V5E: ChipPeaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9,
        # 1,600 Gbit/s of chip-to-chip interconnect over 4 links
        ici_link_bytes_per_s=50e9,
        source="Google Cloud documentation, 'TPU v5e' (system architecture)"),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
