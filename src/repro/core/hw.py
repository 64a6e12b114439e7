"""Hardware descriptions for the COPA-GPU study and the TPU target.

Numbers come straight from the paper (Tables I, II, IV) and public TPU v5e
specifications. Everything is a frozen dataclass so configs hash and compare
cleanly and can be used as pytree aux data.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

KB = 1024
MB = 1024 * KB
GB = 1024 * MB
TB = 1024 * GB

# Throughputs use decimal units as in vendor datasheets.
GBPS = 1e9
TBPS = 1e12
TFLOPS = 1e12


@dataclass(frozen=True)
class LinkSpec:
    """An on-package UHB link (paper Table II) or an off-chip interconnect."""

    name: str
    bandwidth: float            # bytes/s, unidirectional unless noted
    energy_pj_per_bit: float    # pJ/b
    # Paper: 2.5D = 256 GB/s/mm edge density, 3D = 512 GB/s/mm^2 areal density.
    density: float = 0.0
    density_unit: str = ""

    def energy_joules(self, num_bytes: float) -> float:
        return num_bytes * 8.0 * self.energy_pj_per_bit * 1e-12


@dataclass(frozen=True)
class GpuSpec:
    """A converged-GPU (or GPM) compute+memory description (paper Table IV).

    ``l3_capacity``/``l3_bandwidth`` are zero for monolithic designs; COPA
    variants are built by ``repro.core.copa`` layering an MSM on top of a GPM.
    """

    name: str
    num_sms: int
    frequency_ghz: float
    fp32_tflops: float
    fp16_tflops: float
    l2_capacity: int            # bytes
    dram_bandwidth: float       # bytes/s
    dram_capacity: int          # bytes
    # L2 is the bandwidth filter in front of everything (GPM-internal).
    # Aggregate L2 bandwidth on modern GPUs is ~10x DRAM bandwidth.
    l2_bandwidth_ratio: float = 10.0
    # Memory-side L3 (MSM) — zero when absent.
    l3_capacity: int = 0
    l3_bandwidth: float = 0.0   # post-L2 UHB link bandwidth (per direction RD/WR)
    l3_energy_pj_per_bit: float = 0.0
    # DRAM access energy, used by the §III-D energy model. The paper states a
    # COPA L3 hit costs ~4x less than HBM access.
    dram_energy_pj_per_bit: float = 7.0
    max_threads_per_sm: int = 2048

    @property
    def l2_bandwidth(self) -> float:
        return self.dram_bandwidth * self.l2_bandwidth_ratio

    @property
    def llc_capacity(self) -> int:
        """Last-level cache the DRAM sees: L3 when present, else L2."""
        return self.l3_capacity if self.l3_capacity else self.l2_capacity

    @property
    def concurrency(self) -> int:
        return self.num_sms * self.max_threads_per_sm

    def with_(self, **kw) -> "GpuSpec":
        return dataclasses.replace(self, **kw)


# --- Paper Table IV configurations -----------------------------------------

V100 = GpuSpec(
    name="V100", num_sms=80, frequency_ghz=1.4, fp32_tflops=15.7,
    fp16_tflops=125.0, l2_capacity=6 * MB, dram_bandwidth=900 * GBPS,
    dram_capacity=16 * GB,
)

A100 = GpuSpec(
    name="A100", num_sms=108, frequency_ghz=1.4, fp32_tflops=19.5,
    fp16_tflops=312.0, l2_capacity=40 * MB, dram_bandwidth=1555 * GBPS,
    dram_capacity=40 * GB,
)

# The paper's forward projection ("GPU-N", Tables I/IV).
GPU_N = GpuSpec(
    name="GPU-N", num_sms=134, frequency_ghz=1.4, fp32_tflops=24.2,
    fp16_tflops=779.0, l2_capacity=60 * MB, dram_bandwidth=2687 * GBPS,
    dram_capacity=100 * GB,
)

P100 = GpuSpec(
    name="P100", num_sms=56, frequency_ghz=1.3, fp32_tflops=11.0,
    fp16_tflops=21.0, l2_capacity=4 * MB, dram_bandwidth=732 * GBPS,
    dram_capacity=16 * GB,
)

# --- Paper Table II link technologies ---------------------------------------

UHB_2_5D = LinkSpec(
    name="UHB-2.5D", bandwidth=14.7 * TBPS, energy_pj_per_bit=0.3,
    density=256 * GBPS, density_unit="GB/s/mm",
)
UHB_3D = LinkSpec(
    name="UHB-3D", bandwidth=14.7 * TBPS, energy_pj_per_bit=0.05,
    density=512 * GBPS, density_unit="GB/s/mm^2",
)


# --- TPU target (assignment constants) ---------------------------------------

@dataclass(frozen=True)
class TpuSpec:
    """Per-chip TPU description used by the roofline analysis."""

    name: str
    bf16_tflops: float          # peak dense matmul throughput
    hbm_bandwidth: float        # bytes/s
    hbm_capacity: int           # bytes
    ici_link_bandwidth: float   # bytes/s per link direction
    ici_links: int              # links per chip in the 2D/3D torus
    vmem_capacity: int          # on-chip vector memory

    @property
    def flops_per_byte_hbm(self) -> float:
        return self.bf16_tflops * TFLOPS / self.hbm_bandwidth


# Assignment-provided constants: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link.
TPU_V5E = TpuSpec(
    name="TPUv5e", bf16_tflops=197.0, hbm_bandwidth=819 * GBPS,
    hbm_capacity=16 * GB, ici_link_bandwidth=50 * GBPS, ici_links=4,
    vmem_capacity=128 * MB,
)


# --- TPU tiling + Pallas budgets (used by the repro.check static analyzer) ----

# The MXU is a 128x128 systolic array; the VPU operates on (8, 128) f32
# registers. VMEM tiles are (sublane, lane) with lane fixed at 128 and the
# minimum sublane count scaling inversely with dtype width.
MXU_TILE = (128, 128)
VPU_TILE = (8, 128)
TPU_LANE = 128
# dtype itemsize (bytes) -> minimum sublane count of one VMEM tile.
TPU_MIN_SUBLANE = {8: 8, 4: 8, 2: 16, 1: 32}


def min_tile(dtype_itemsize: int) -> tuple[int, int]:
    """Minimum (sublane, lane) VMEM tile for a dtype of the given width."""
    return (TPU_MIN_SUBLANE.get(int(dtype_itemsize), 8), TPU_LANE)


# Pallas double-buffers every grid-blocked operand so the next block's DMA
# overlaps the current compute step; the R5 footprint rule charges each
# in/out block twice and scratch once.
PALLAS_PIPELINE_BUFFERS = 2
# Mosaic's scoped-VMEM limit on v5e: a kernel whose pipeline buffers and
# scratch exceed it is refused at compile time ("scoped allocation ... limit
# 16.00M"), however much of the 128 MB physical VMEM is free.
PALLAS_VMEM_BUDGET = 16 * MB
# SMEM holds scalars/control state only; budget is deliberately tight.
PALLAS_SMEM_BUDGET = 1 * MB
