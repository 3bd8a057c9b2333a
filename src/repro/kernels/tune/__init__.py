"""Autotuning for the BSR diffusion kernels (DESIGN.md §9).

``run_sweep`` measures (bs, buffer_depth, occupancy threshold) on the
current platform and persists the winner as a versioned JSON record;
``records`` load those at dispatch time so ``solve(method="auto")`` ranks
backends by measured throughput, and the session drivers resolve their
kernel config (``resolved_config``) from the same records.
"""
from .model import (  # noqa: F401
    PLATFORM_SPECS,
    HwSpec,
    KernelCost,
    dma_compute_ratio,
    frontier_round_cost,
    gather_spmm_cost,
    hw_spec,
    ideal_time_s,
    roofline_fraction,
    vmem_bytes,
    vmem_ok,
)
from .records import (  # noqa: F401
    DEFAULT_BS,
    DEFAULT_BUFFER_DEPTH,
    DEFAULT_OCCUPANCY_THRESHOLD,
    KERNELS,
    RECORD_VERSION,
    TunedConfig,
    best_config,
    clear_cache,
    load_record,
    record_path,
    resolved_config,
    save_record,
    tune_dir,
)
from .sweep import run_sweep  # noqa: F401
