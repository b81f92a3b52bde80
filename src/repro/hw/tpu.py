"""TPU hardware target specification.

The paper sizes *physical* memories for an FPGA; on TPU the memories are
fixed, so the target spec is the set of capacities/bandwidths the passes
budget against.  All roofline math in :mod:`repro.analysis.roofline` reads
these numbers, so there is a single source of truth for the hardware model.

Peaks of the default target, one TPU v5e chip (Google Cloud
documentation, "TPU v5e"): 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB
of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (4 ICI
links of 50 GB/s).  :func:`target_for_device` maps the ``device_kind``
JAX reports to a target; a kind not in the table is an error.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

KiB = 1024
MiB = 1024 * KiB
GiB = 1024 * MiB


@dataclasses.dataclass(frozen=True)
class TpuTarget:
    """Capability/capacity model of one TPU chip + its interconnect."""

    name: str = "tpu-v5e"
    # --- compute ---
    peak_bf16_flops: float = 197e12  # FLOP/s per chip (MXU, bf16)
    peak_f32_flops: float = 98.5e12  # ~half rate for fp32 accumulate paths
    mxu_dim: int = 128               # systolic array edge -> matmul tile quantum
    vpu_lanes: Tuple[int, int] = (8, 128)  # (sublane, lane) tiling quantum

    # --- memories (the "template components" with fixed size on TPU) ---
    hbm_bytes: int = 16 * GiB
    hbm_bw: float = 819e9            # bytes/s
    vmem_bytes: int = 64 * MiB       # usable VMEM planning budget per core
    smem_bytes: int = 1 * MiB        # scalar memory (for scalar prefetch)

    # --- interconnect ("channels" in the paper's template) ---
    ici_link_bw: float = 50e9        # bytes/s per ICI link, per direction
    ici_links_per_chip: int = 4      # 2D torus on v5e: 4 links
    dcn_bw: float = 6.25e9           # bytes/s per host NIC (pod axis, 50 Gb/s)

    # --- host tier (the DRAM behind the PCIe attach) ---
    # The serving engine can spill cold KV blocks to pinned host memory
    # and stream them back ahead of their decode tick; these two numbers
    # size that tier.  ``host_bytes_per_chip`` is each chip's share of
    # the host's DRAM (a v5e host serves 8 chips), ``pcie_bw`` the
    # per-chip host<->HBM DMA bandwidth the stream-back must fit in.
    pcie_bw: float = 16e9            # bytes/s per chip (PCIe Gen3 x16 class)
    host_bytes_per_chip: int = 48 * GiB

    # --- derived helpers -------------------------------------------------
    def matmul_time(self, flops: float, dtype_bytes: int = 2) -> float:
        peak = self.peak_bf16_flops if dtype_bytes <= 2 else self.peak_f32_flops
        return flops / peak

    def hbm_time(self, nbytes: float) -> float:
        return nbytes / self.hbm_bw

    def ici_time(self, nbytes: float) -> float:
        """Time to move nbytes across one ICI link."""
        return nbytes / self.ici_link_bw

    def pcie_time(self, nbytes: float) -> float:
        """Time to stream nbytes between host DRAM and HBM."""
        return nbytes / self.pcie_bw

    def align_up(self, n: int, q: int | None = None) -> int:
        q = q or self.mxu_dim
        return ((n + q - 1) // q) * q

    def vmem_fit(self, *tile_bytes: int, buffers: int = 2) -> bool:
        """Does a working set (with ``buffers``-way banking) fit in VMEM?

        ``buffers=2`` models the double-buffered pipeline (the paper's
        multi-bank PLM: one bank is filled by DMA while the other is read
        by the datapath).
        """
        return buffers * sum(tile_bytes) <= self.vmem_bytes


# Registry so configs can say ``target="tpu-v5e"``.
_TARGETS = {
    "tpu-v5e": TpuTarget(),
    "tpu-v5p": TpuTarget(
        name="tpu-v5p",
        peak_bf16_flops=459e12,
        peak_f32_flops=229.5e12,
        hbm_bytes=95 * GiB,
        hbm_bw=2765e9,
        ici_link_bw=100e9,
        ici_links_per_chip=6,  # 3D torus
        vmem_bytes=128 * MiB,
        pcie_bw=32e9,
        host_bytes_per_chip=96 * GiB,
    ),
}


# device_kind, as ``jax.devices()[i].device_kind`` reports it -> target
_DEVICE_KINDS = {
    "TPU v5 lite": "tpu-v5e",
}


def target_for_device(device) -> str:
    """Name of the target for a TPU ``device``; raises on a kind (or a
    platform) the table does not hold."""
    kind = getattr(device, "device_kind", "")
    if getattr(device, "platform", "") != "tpu" or kind not in _DEVICE_KINDS:
        raise KeyError(
            f"no TPU target for {getattr(device, 'platform', '?')} device "
            f"kind {kind!r}; have {sorted(_DEVICE_KINDS)}")
    return _DEVICE_KINDS[kind]


def get_target(name: str = "tpu-v5e") -> TpuTarget:
    try:
        return _TARGETS[name]
    except KeyError as e:
        raise KeyError(f"unknown TPU target {name!r}; have {sorted(_TARGETS)}") from e
