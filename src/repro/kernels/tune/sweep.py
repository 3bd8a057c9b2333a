"""Autotune sweep for the BSR diffusion kernels.

Sweeps (block size ``bs``, ``buffer_depth``, occupancy threshold) on the
current platform, rejects configs whose VMEM footprint exceeds the
platform budget, times the survivors and persists the winner as a
versioned JSON record (:mod:`.records`) that the backend registry feeds
into measured auto-dispatch.

Timing path per platform:

* **tpu** — the compiled Pallas kernel itself (``timing_path="pallas"``).
* **cpu/gpu** — the jnp block oracle (``timing_path="oracle"``): the
  einsum+segment-sum twin is what actually runs there, so its timing *is*
  the deployable throughput.  ``buffer_depth`` does not exist on the
  oracle path, so all depths share one measurement per (bs, threshold)
  and the shallowest feasible depth wins the tie.
"""
from __future__ import annotations

import time
from datetime import datetime, timezone
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import records
from .model import (
    dma_compute_ratio,
    frontier_round_cost,
    gather_spmm_cost,
    hw_spec,
    ideal_time_s,
    roofline_fraction,
    vmem_bytes,
)

__all__ = ["run_sweep"]


def _timeit(fn, *args, iters: int = 3) -> float:
    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def _problem(n: int, bs: int, c: int, density: float, seed: int):
    """A host-ordered web graph + mid-convergence frontier at ``density``."""
    from repro.core import host_block_graph, pagerank_system
    from repro.kernels.diffusion import prepare_bsr

    g = host_block_graph(n, host_size=bs, links_per_node=8.0,
                         intra_frac=0.92, span_hosts=2, seed=seed + 1)
    p, _b = pagerank_system(g)
    m = prepare_bsr(p.indptr, p.indices, p.weights, p.n, bs=bs)
    n_pad = m.n_row_blocks * bs
    rng = np.random.default_rng(seed)
    n_blocks = n_pad // bs
    n_hot = max(1, int(round(density * n_blocks)))
    hot = rng.choice(n_blocks, size=n_hot, replace=False)
    f = np.full((n_pad, c), 0.25, dtype=np.float32)
    for b in hot:
        f[b * bs: (b + 1) * bs] = 2.0
    f *= rng.choice([-1.0, 1.0], size=(n_pad, c))
    f[p.n:] = 0.0
    w = np.zeros(n_pad, np.float32)
    w[: p.n] = 1.0
    return m, jnp.asarray(f), jnp.asarray(w), jnp.float32(1.0)


def _n_active_blocks(m, f, w, t, occ_threshold: float) -> int:
    """Tiles whose block column is armed under the given threshold."""
    sel = np.abs(np.asarray(f)) * np.asarray(w)[:, None] > float(t)
    blk = sel.reshape(m.n_row_blocks, -1)
    if occ_threshold > 0.0:
        col_active = blk.mean(axis=1) > occ_threshold
    else:
        col_active = blk.any(axis=1)
    return int(col_active[np.asarray(m.block_col)].sum())


def run_sweep(
    kernel: str = "frontier_round_bsr",
    *,
    n: int = 4096,
    c: int = 1,
    density: float = 0.25,
    bs_list: Sequence[int] = (32, 64, 128),
    depths: Sequence[int] = (1, 2, 4),
    occupancy_thresholds: Sequence[float] = (0.0,),
    iters: int = 3,
    seed: int = 0,
    save: bool = True,
    platform: Optional[str] = None,
    verbose: bool = True,
) -> dict:
    """Run the sweep and (optionally) persist the winning config record."""
    if kernel not in records.KERNELS:
        raise ValueError(
            f"unknown kernel {kernel!r}; expected one of {records.KERNELS}"
        )
    if platform is None:
        platform = jax.default_backend()
    spec = hw_spec(jax.devices()[0].device_kind)
    pallas_timing = platform == "tpu"
    rows = []
    for bs in bs_list:
        m, f, w, t = _problem(n, bs, c, density, seed)
        oracle_cache: dict = {}  # (occ) -> measured us, shared across depths
        for occ in occupancy_thresholds:
            n_act = _n_active_blocks(m, f, w, t, occ)
            if kernel == "frontier_round_bsr":
                cost = frontier_round_cost(m.n_row_blocks, bs, c, n_act)
            else:
                cost = gather_spmm_cost(m.n_row_blocks, bs, c, m.n_blocks)
            ideal_s, bound = ideal_time_s(cost, spec)
            for depth in depths:
                vb = vmem_bytes(bs, c, depth)
                feasible = vb <= spec.vmem_budget
                row = {
                    "bs": bs,
                    "buffer_depth": depth,
                    "occupancy_threshold": occ,
                    "feasible": feasible,
                    "vmem_bytes": vb,
                    "n_blocks_active": n_act,
                    "bound": bound,
                    "dma_compute_ratio": round(
                        dma_compute_ratio(cost, spec), 3),
                    "measured_us": None,
                    "throughput_gflops": None,
                    "roofline_fraction": None,
                }
                if feasible:
                    us = _measure(kernel, m, f, w, t, depth, occ,
                                  pallas_timing, iters, oracle_cache)
                    row["measured_us"] = round(us, 2)
                    row["throughput_gflops"] = round(
                        cost.flops / (us * 1e-6) / 1e9, 4)
                    row["roofline_fraction"] = round(
                        roofline_fraction(us * 1e-6, ideal_s), 6)
                rows.append(row)
                if verbose:
                    shown = (f"{row['measured_us']}us"
                             if feasible else "VMEM-infeasible")
                    print(f"[tune:{kernel}] bs={bs} depth={depth} "
                          f"occ={occ}: {shown}")
    timed = [r for r in rows if r["measured_us"] is not None]
    if not timed:
        raise RuntimeError(
            "no feasible config in the sweep — every (bs, depth) exceeded "
            f"the {spec.name} VMEM budget of {spec.vmem_budget} bytes"
        )
    win = min(timed, key=lambda r: (r["measured_us"], r["buffer_depth"],
                                    -r["bs"]))
    record = {
        "version": records.RECORD_VERSION,
        "kernel": kernel,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "jax_version": jax.__version__,
        "created_utc": datetime.now(timezone.utc).isoformat(),
        "timing_path": "pallas" if pallas_timing else "oracle",
        "problem": {"n": n, "c": c, "density": density, "seed": seed,
                    "iters": iters},
        "best": {
            "bs": win["bs"],
            "buffer_depth": win["buffer_depth"],
            "occupancy_threshold": win["occupancy_threshold"],
            "measured_us": win["measured_us"],
            "throughput_gflops": win["throughput_gflops"],
            "roofline_fraction": win["roofline_fraction"],
            "vmem_bytes": win["vmem_bytes"],
        },
        "sweep": rows,
    }
    if save:
        path = records.save_record(record)
        if verbose:
            print(f"[tune:{kernel}] best bs={win['bs']} "
                  f"depth={win['buffer_depth']} -> {path}")
    return record


def _measure(kernel, m, f, w, t, depth, occ, pallas_timing, iters,
             oracle_cache) -> float:
    """One timed config; oracle timings are cached across depths."""
    from repro.kernels.diffusion import (
        bsr_gather_spmm_pallas,
        bsr_spmm_ref,
        frontier_round_bsr,
    )

    if not pallas_timing and occ in oracle_cache:
        return oracle_cache[occ]
    if kernel == "frontier_round_bsr":
        backend = "pallas" if pallas_timing else "block"

        @jax.jit
        def fn(fv):
            f_new, _s, res = frontier_round_bsr(
                m, fv, w, t, backend=backend,
                interpret=False if pallas_timing else None,
                buffer_depth=depth if pallas_timing else 1,
                occupancy_threshold=occ,
            )
            return f_new, res

        us = _timeit(fn, f, iters=iters)
    else:  # bsr_gather_spmm
        c = f.shape[-1]
        xt = f.reshape(m.n_row_blocks, m.bs, c)
        order = jnp.arange(m.n_blocks, dtype=jnp.int32)
        if pallas_timing:

            @jax.jit
            def fn(x):
                return bsr_gather_spmm_pallas(
                    m.blocks, order, m.block_row, m.block_col, x,
                    m.n_row_blocks, bs=m.bs, interpret=False,
                    buffer_depth=depth,
                )

        else:

            @jax.jit
            def fn(x):
                return bsr_spmm_ref(m.blocks, m.block_row, m.block_col, x,
                                    m.n_row_blocks)

        us = _timeit(fn, xt, iters=iters)
    if not pallas_timing:
        oracle_cache[occ] = us
    return us
