"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16 and
819 GB/s of HBM bandwidth per chip, 1,600 Gbit/s of inter-chip
interconnect (four links of 50 GB/s). A device kind that is not in the
table is an error: no roofline or latency model divides by an assumed
peak. Code that models a v5e on purpose names the row (``TPU_V5E``).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float    # FLOP/s per chip, bf16
    hbm_bw: float        # bytes/s per chip
    ici_bw: float        # bytes/s per inter-chip link


TPU_V5E = "TPU v5 lite"  # what jax reports as a v5e's device_kind

PEAKS = {
    TPU_V5E: ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None
