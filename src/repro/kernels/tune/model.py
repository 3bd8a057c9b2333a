"""Byte/flop cost model for the BSR diffusion kernels (roofline + VMEM).

The D-iteration hot loop is fluid movement: per grid step the kernels read
one ``[bs, bs]`` weight tile plus ``O(bs*C)`` fluid and do ``2*bs*bs*C``
flops, so arithmetic intensity is ~``C/2`` flops per byte — firmly
memory-bound for the paper's ``C = 1``.  This module is the single source
of truth for that model; the autotuner's feasibility check, the
``benchmarks/roofline.py`` table and the per-config ``roofline_fraction``
emitted into BENCH_kernels.json all derive from it.

Peaks are published datasheet values keyed by ``jax.Device.device_kind``
(each entry names its source); they anchor the roofline, they are not
measurements.  A device kind missing from the table is an error.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

from repro.kernels.diffusion.kernel import VMEM_LIMIT_BYTES

__all__ = [
    "HwSpec",
    "PLATFORM_SPECS",
    "hw_spec",
    "KernelCost",
    "frontier_round_cost",
    "gather_spmm_cost",
    "ideal_time_s",
    "dma_compute_ratio",
    "vmem_bytes",
    "vmem_ok",
    "roofline_fraction",
]


@dataclasses.dataclass(frozen=True)
class HwSpec:
    """Nominal hardware envelope used to anchor the roofline."""

    name: str
    peak_flops: float  # flop/s at the precision named in ``source``
    mem_bw: float  # main-memory bandwidth, bytes/s (HBM on tpu)
    vmem_budget: int  # fast-memory budget for kernel operands, bytes
    source: str


PLATFORM_SPECS: Dict[str, HwSpec] = {
    "TPU v5 lite": HwSpec(
        "tpu-v5e", 197e12, 819e9, VMEM_LIMIT_BYTES,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "16 GB HBM at 819 GB/s. The kernels run f32 at HIGHEST "
        "precision, so their compute roof is below the bf16 peak. VMEM "
        "budget: the vmem_limit_bytes the kernels compile under."),
    # the CPU runs the jnp oracle, never the kernels: nominal numbers
    # that keep the CPU tests' sweep and roofline code paths exercised
    "cpu": HwSpec("cpu-host", 2e11, 4e10, 32 * 2**20,
                  source="nominal, not a datasheet"),
}


def hw_spec(device_kind: str) -> HwSpec:
    """The table entry for ``device_kind``; an unknown kind raises."""
    try:
        return PLATFORM_SPECS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak table entry for device kind {device_kind!r}; add "
            f"one with its source (known: {sorted(PLATFORM_SPECS)})"
        ) from None


@dataclasses.dataclass(frozen=True)
class KernelCost:
    """Bytes moved / flops issued by one kernel sweep."""

    bytes_tiles: float  # the tile-pool stream (what buffer_depth pipelines)
    bytes_fluid: float  # f / wt / output traffic
    flops: float

    @property
    def total_bytes(self) -> float:
        return self.bytes_tiles + self.bytes_fluid

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.total_bytes, 1.0)


def frontier_round_cost(
    n_row_blocks: int,
    bs: int,
    c: int,
    n_blocks_active: int,
    dtype_bytes: int = 4,
) -> KernelCost:
    """One fused frontier round at a given frontier occupancy.

    ``n_blocks_active`` is the number of tiles whose block column is armed
    — the occupancy skip means inactive tiles cost *nothing* (no DMA, no
    matmul), which is why the model is parametric in the swept density.
    """
    bytes_tiles = float(n_blocks_active) * bs * bs * dtype_bytes
    # per active visit: f_col [bs, C] + wt_col [bs]; per block row: the
    # kept-fluid seed read (f_row + wt_row), the output write and the
    # per-row |.|_1 cell.
    bytes_fluid = (
        float(n_blocks_active) * (bs * c + bs)
        + float(n_row_blocks) * (2 * (bs * c + bs) + bs * c + 1)
    ) * dtype_bytes
    flops = 2.0 * n_blocks_active * bs * bs * c
    return KernelCost(bytes_tiles, bytes_fluid, flops)


def gather_spmm_cost(
    n_row_blocks: int,
    bs: int,
    c: int,
    n_visits: int,
    dtype_bytes: int = 4,
) -> KernelCost:
    """One gather-indirection SpMM sweep over ``n_visits`` tile visits."""
    bytes_tiles = float(n_visits) * bs * bs * dtype_bytes
    bytes_fluid = (
        float(n_visits) * bs * c + float(n_row_blocks) * bs * c
    ) * dtype_bytes
    flops = 2.0 * n_visits * bs * bs * c
    return KernelCost(bytes_tiles, bytes_fluid, flops)


def ideal_time_s(cost: KernelCost, spec: HwSpec) -> Tuple[float, str]:
    """Roofline-ideal runtime and which wall binds it."""
    t_mem = cost.total_bytes / spec.mem_bw
    t_comp = cost.flops / spec.peak_flops
    if t_mem >= t_comp:
        return t_mem, "memory"
    return t_comp, "compute"


def dma_compute_ratio(cost: KernelCost, spec: HwSpec) -> float:
    """DMA time over MXU time — >1 means the tile stream is the bottleneck
    and deeper buffering can only hide (never remove) the gap."""
    t_comp = cost.flops / spec.peak_flops
    t_dma = cost.bytes_tiles / spec.mem_bw
    return t_dma / max(t_comp, 1e-30)


def vmem_bytes(bs: int, c: int, buffer_depth: int,
               dtype_bytes: int = 4) -> int:
    """Peak VMEM held by one grid step of the frontier/gather kernels.

    ``buffer_depth == 1`` rides the automatic BlockSpec pipeline, which
    double-buffers the tile operand; ``>= 2`` replaces it with the manual
    ``[depth, bs, bs]`` ring.  The fluid operands (f/wt, col + row views)
    and the output tile stay on the automatic double-buffered path in both
    modes.
    """
    tile_ring = max(2, buffer_depth) * bs * bs
    fluid = 2 * 2 * (bs * c + bs)  # (f, wt) x (col, row) double-buffered
    out = 2 * (bs * c + 1)
    return (tile_ring + fluid + out) * dtype_bytes


def vmem_ok(bs: int, c: int, buffer_depth: int, spec: HwSpec,
            dtype_bytes: int = 4) -> bool:
    return vmem_bytes(bs, c, buffer_depth, dtype_bytes) <= spec.vmem_budget


def roofline_fraction(measured_s: float, ideal_s: float) -> float:
    """Fraction of the roofline the measurement achieves (1.0 = at the
    roof; interpret/oracle timings land far below it by design)."""
    if measured_s <= 0.0:
        return 0.0
    return ideal_s / measured_s
