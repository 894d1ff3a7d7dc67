"""Chip constants used by cost models, roofline, and VMEM sizing.

Target: TPU v5e. ``chip_for_device_kind`` maps the ``device_kind`` string
JAX reports (``jax.devices()[0].device_kind``) to its spec; a device that
is not in :data:`CHIPS_BY_DEVICE_KIND` is an error, never a default.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

MiB = 1024 ** 2


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    device_kind: str            # jax.devices()[i].device_kind
    peak_flops_bf16: float      # FLOP/s (matrix unit)
    hbm_bw: float               # bytes/s
    ici_bw_per_link: float      # bytes/s per link
    hbm_bytes: int              # HBM capacity
    vmem_bytes: int             # VMEM the cost model prices pressure against
    # scoped-VMEM limit every tile-op kernel requests from the compiler
    # (pltpu.CompilerParams.vmem_limit_bytes); row-block autosizing and
    # the grid verifier's VMEM pass budget against this same figure
    vmem_limit_bytes: int = 48 * MiB
    ici_links: int = 4          # 2D torus: 4 links/chip
    # Vector-unit issue model (used by the unified analysis subsystem to
    # price elementwise tile passes): `vpu_lanes` elements retire per
    # cycle at `clock_hz`.
    vpu_lanes: int = 8 * 128    # v5e VPU: (8, 128) vregs
    clock_hz: float = 0.94e9

    @property
    def vpu_elems_per_s(self) -> float:
        """Elementwise lanes/sec — the vector-issue roofline ceiling."""
        return self.vpu_lanes * self.clock_hz


# Published peaks: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
# bf16, 16 GB HBM at 819 GB/s). The chip has 128 MiB of VMEM; the cost
# model prices against 64 MiB of it.
TPU_V5E = ChipSpec(
    name="tpu_v5e",
    device_kind="TPU v5 lite",
    peak_flops_bf16=197e12,
    hbm_bw=819e9,
    ici_bw_per_link=50e9,
    hbm_bytes=16 * 1024**3,
    vmem_bytes=64 * MiB,
)

# The paper's own GPU (A100-PCIE-40GB, Fig. 2/4). Not a target here:
# calibration tests use it as a second, non-default set of constants.
A100_PCIE_40GB = ChipSpec(
    name="a100_pcie_40gb",
    device_kind="NVIDIA A100-PCIE-40GB",
    peak_flops_bf16=312e12,
    hbm_bw=1555e9,
    ici_bw_per_link=64e9,       # NVLink3 per-direction aggregate/ring share
    hbm_bytes=40 * 1024**3,
    vmem_bytes=192 * 1024,      # SMEM+L1 per SM
    vpu_lanes=108 * 64,         # 108 SMs × 64 FP32 lanes
    clock_hz=1.41e9,
)

DEFAULT_CHIP = TPU_V5E

# The accelerators this repo runs on, by the device_kind JAX reports.
CHIPS_BY_DEVICE_KIND: Dict[str, ChipSpec] = {
    TPU_V5E.device_kind: TPU_V5E,
}


def chip_for_device_kind(kind: str) -> ChipSpec:
    """The spec of a device JAX reports as ``kind``; unknown kinds raise."""
    try:
        return CHIPS_BY_DEVICE_KIND[kind]
    except KeyError:
        raise ValueError(
            f"unknown device_kind {kind!r}; known: "
            f"{sorted(CHIPS_BY_DEVICE_KIND)}") from None


# Mesh axis conventions used across the framework.
AXIS_POD = "pod"
AXIS_DATA = "data"
AXIS_MODEL = "model"
