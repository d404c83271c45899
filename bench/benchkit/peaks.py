"""Published peaks of each chip the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A device that is not here is an error: a
share of another chip's peak would be a wrong number, not a default."""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float      # FLOP/s per chip
    hbm_bw: float          # bytes/s per chip
    hbm_bytes: int
    source: str


PEAKS: dict[str, Peaks] = {
    # Google Cloud documentation, "TPU v5e" (system architecture table):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM2 at 819 GB/s.
    "TPU v5 lite": Peaks(197e12, 819e9, 16 * 1024**3,
                         'Google Cloud, "TPU v5e"'),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
