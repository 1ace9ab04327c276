"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16 and
819 GB/s of HBM bandwidth per chip. The benchmark keeps its own copy so
that the yardstick does not move with the program. A kind that is not
in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
}


def chip_peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None
