"""Per-chip peaks for the roofline model, keyed by ``device.device_kind``.

A device that is not in :data:`PEAKS` raises: a roofline share computed
against another chip's peaks would be a wrong number, not a default.

The module-level constants are the deployment target's (TPU v5e) and feed
the analytic rooflines computed from compiled programs without a chip.
"""

from __future__ import annotations

from typing import NamedTuple


class ChipPeaks(NamedTuple):
    bf16_flops: float      # FLOP/s per chip, bf16
    hbm_bw: float          # bytes/s per chip
    hbm_bytes: int         # HBM capacity per chip
    ici_bw: float          # bytes/s per inter-chip link
    vmem_bytes: int        # vector memory per TensorCore
    source: str


PEAKS: dict[str, ChipPeaks] = {
    # Google Cloud documentation, "TPU v5e" (system architecture table):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM2 at 819 GB/s, 1,600
    # Gbit/s inter-chip interconnect per chip = 200 GB/s over 4 links.
    # VMEM is not in that table; 128 MiB per TensorCore (one per v5e
    # chip) is JAX's own hardware table, jax.experimental.pallas.tpu.
    # get_tpu_info() for "TPU v5 lite".
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, hbm_bw=819e9,
        hbm_bytes=16 * 1024**3, ici_bw=1600e9 / 8 / 4,
        vmem_bytes=128 * 1024**2,
        source='Google Cloud, "TPU v5e"; VMEM: jax pallas get_tpu_info'),
}


def peaks(device_kind: str) -> ChipPeaks:
    """Peaks of the chip JAX reports as ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


TARGET = peaks("TPU v5 lite")

PEAK_FLOPS_BF16 = TARGET.bf16_flops
HBM_BW = TARGET.hbm_bw
ICI_BW = TARGET.ici_bw
# effective inter-pod (data-center network) bandwidth: a modelling
# assumption of the multi-pod dry run, not a published figure
DCI_BW = 25e9
VMEM_BYTES = TARGET.vmem_bytes
HBM_BYTES = TARGET.hbm_bytes

# effective data volume multiplier per collective (ring algorithms):
#   all-reduce moves ~2x the buffer; gather/scatter ~1x
COLLECTIVE_FACTOR = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}
