"""Production mesh construction and the per-chip peak table.

Meshes are built by functions (never module-level constants) so importing
this module never touches JAX device state.
"""
from __future__ import annotations

import dataclasses

from repro.dist.sharding import make_mesh

# the chip the production meshes are made of, as `jax.Device.device_kind`
# names it
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e target: one pod = 16x16 = 256 chips; multi-pod = 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int | None = None):
    """Small mesh over whatever devices exist (tests / examples)."""
    shape = ((pod, data, model) if pod else (data, model))
    axes = (("pod", "data", "model") if pod else ("data", "model"))
    return make_mesh(shape, axes)


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks, for rooflines."""

    bf16_flops: float      # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    ici_link_bytes_per_s: float  # one chip-to-chip link
    source: str


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        # 1,600 Gbit/s of interconnect per chip over 4 links
        ici_link_bytes_per_s=50e9,
        source="Google Cloud documentation, 'TPU v5e'"),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of a chip by its `device_kind`; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
