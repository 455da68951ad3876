"""Hardware descriptions for roofline analysis.

TPU v5e is the deployment target. The H100 entry carries the paper's own
roofline constants (Table II / Fig. 1) and is used only to reproduce the
paper's analytic numbers. A process on an accelerator takes its peaks
from :func:`hardware_for` by ``device_kind``; a kind missing from
``PEAKS_BY_DEVICE_KIND`` is an error, not a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float          # FLOP/s per chip (matmul dtype of interest)
    hbm_bw: float              # bytes/s per chip
    link_bw: float             # bytes/s per ICI/NVLink link
    hbm_bytes: float           # HBM capacity per chip
    vmem_bytes: float = 0.0    # on-chip scratch (VMEM / SMEM+L2 analogue)
    host_link_bw: float = 0.0  # PCIe/DCN-ish, for host-gap modeling
    # roofline ceilings as *plotted by the paper* (Fig. 1 / Table II use the
    # single-precision CUDA-core ceiling for the attention kernels).
    plot_flops_ceiling: float = 0.0
    plot_bw_ceiling: float = 0.0


# Published peaks of one TPU v5e chip (Google Cloud documentation,
# "TPU v5e"): 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI.
TPU_V5E = Hardware(
    name="tpu-v5e",
    peak_flops=197e12,         # bf16
    hbm_bw=819e9,
    link_bw=50e9,              # per ICI link (assignment constant)
    hbm_bytes=16e9,
    vmem_bytes=128 * 2**20,
)

# The paper's H100 (64GB HBM2 variant). hbm_bw is the DRAM roofline ceiling
# the paper reports in Table II (1.63e12 B/s); peak_flops is the tensor-core
# bf16 rate (matmuls); plot_* carry the paper's Fig. 1 / Table II plotted
# ceilings (single-precision CUDA-core roofline, 2.56e13 FLOP/s) so our
# reproduced roofline figures are directly comparable.
H100_PAPER = Hardware(
    name="h100-paper",
    peak_flops=9.9e14,
    hbm_bw=1.63e12,
    link_bw=450e9,
    hbm_bytes=64e9,
    vmem_bytes=50 * 2**20,
    plot_flops_ceiling=2.56e13,
    plot_bw_ceiling=1.63e12,
)

HARDWARE = {h.name: h for h in (TPU_V5E, H100_PAPER)}

# ``jax.Device.device_kind`` -> peaks. JAX names a v5e chip "TPU v5 lite".
PEAKS_BY_DEVICE_KIND = {"TPU v5 lite": TPU_V5E}


def hardware_for(device_kind: str) -> Hardware:
    """Published peaks of an accelerator, by its ``device_kind``."""
    try:
        return PEAKS_BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; add them "
            f"to PEAKS_BY_DEVICE_KIND with their source") from None


def local_hardware() -> Hardware:
    """Peaks of the device this process computes on. The CPU backend
    (tests, CPU rehearsals) has no peaks of its own: roofline estimates
    there are modelled for the v5e deployment target."""
    import jax
    dev = jax.devices()[0]
    return TPU_V5E if dev.platform == "cpu" else hardware_for(dev.device_kind)
