"""Published per-chip peaks, keyed by `jax.Device.device_kind`.

A device that is not in the table is an error, never a default: a share
of a peak priced against the wrong chip reads as a result."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    name: str
    bf16_flops: float       # FLOP/s per chip
    int8_ops: float         # OP/s per chip
    hbm_bw: float           # B/s per chip
    hbm_bytes: float        # HBM capacity per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        "tpu-v5e", bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None
