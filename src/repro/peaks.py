"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, and 1,600 Gbit/s
of inter-chip interconnect per chip over four links. JAX reports a v5e chip
as ``"TPU v5 lite"``.

Kept jax-free so the cycle-model benchmark drivers can read it. A device
that is not in the table is an error, never a default: a roofline share
computed against another chip's peaks is a wrong number, not an estimate.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bw: float            # bytes/s
    ici_bw_per_link: float   # bytes/s per inter-chip link


PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12,
        int8_ops=393e12,
        hbm_bw=819e9,
        ici_bw_per_link=1600e9 / 8 / 4,
    ),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind`` (``jax.devices()[0].device_kind``)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
