"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture
table): per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s. XLA's default TPU matmul precision runs float32 matmuls and
convolutions as single bf16 passes, so the bf16 peak is the ceiling for
the configured f32 work as well.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    flops_bf16: float      # FLOP/s
    hbm_bytes_s: float     # bytes/s
    hbm_bytes: float       # bytes
    source: str


PEAKS = {
    "TPU v5 lite": Peak(flops_bf16=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
                        source='Google Cloud docs, "TPU v5e"'),
}


def lookup(device_kind: str) -> Peak:
    """The peak of ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peak for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
