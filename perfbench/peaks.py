"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source for "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU
v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.

A kind missing from the table is an error: a roofline or utilization
against another chip's peaks is wrong, not approximate.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bytes: float         # capacity
    hbm_bytes_per_s: float


PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, int8_ops=393e12,
                             hbm_bytes=16e9, hbm_bytes_per_s=819e9),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; raises for an unknown one."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {', '.join(sorted(PEAKS))})") from None
