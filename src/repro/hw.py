"""Target-hardware constants (TPU v5e) used by the cost model and roofline.

These constants describe the chip the data-flow cost model, the megakernel
route planner and the dry-run/roofline analysis price against; they are
read the same way whether the process runs on that chip or on a CPU.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_bf16_flops: float  # FLOP/s per chip
    hbm_bandwidth: float    # bytes/s per chip
    hbm_capacity: float     # bytes per chip
    ici_link_bandwidth: float  # bytes/s per ICI link
    dcn_bandwidth: float    # bytes/s per chip across pods (data-center network)
    vmem_bytes: int         # per-core VMEM
    ici_latency_s: float = 1e-6  # per-collective launch + link latency (s)


TPU_V5E = ChipSpec(
    name="tpu_v5e",
    peak_bf16_flops=197e12,
    hbm_bandwidth=819e9,
    hbm_capacity=16 * 1024**3,
    ici_link_bandwidth=50e9,
    dcn_bandwidth=6.25e9,  # ~25 GB/s per host / 4 chips
    vmem_bytes=128 * 1024**2,
    ici_latency_s=1e-6,
)

# Default chip used throughout.
CHIP = TPU_V5E


def mesh_chip_count(mesh_shape: tuple[int, ...]) -> int:
    n = 1
    for s in mesh_shape:
        n *= s
    return n
