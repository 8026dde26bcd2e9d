"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default: a share of a peak is only meaningful against the
peak of the chip that ran."""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float          # FLOP/s, dense bf16 matmul
    hbm_bytes_s: float         # HBM bandwidth, bytes/s
    hbm_bytes: int             # HBM capacity
    source: str


TABLE = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_s=819e9, hbm_bytes=16 * 2**30,
        source="Google Cloud documentation, 'TPU v5e' (per chip: 197 "
               "TFLOP/s bf16, 16 GB HBM at 819 GB/s)"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise SystemExit(
            f"no peaks for device_kind {device_kind!r}; the table knows "
            f"{sorted(TABLE)}") from None
