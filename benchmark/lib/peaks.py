"""Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.

The benchmark's own table: roofline shares and model FLOP/s utilization are
judged against these numbers, so a PR that claims a gain cannot move them.
A device that is not here is an error, never a default, and there is no CPU
row: nothing measured on a CPU is ever set against a peak.

Source: Google Cloud TPU documentation, "TPU v5e" system architecture page
(per-chip figures): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
1,600 Gbit/s of inter-chip interconnect.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s
    int8_ops: float        # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    ici_bytes_per_s: float


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9, ici_bytes_per_s=1600e9 / 8),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks recorded for device_kind {device_kind!r}: add a row "
                       f"to benchmark/lib/peaks.py with its source") from None
