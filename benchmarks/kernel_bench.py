"""Kernel microbenchmarks.

Two parts:

* :func:`main` — wall time of the XLA reference diffusion path on CPU
  (the Pallas kernels are TPU-target and validated in interpret mode —
  CPU interpret timings are not meaningful) + derived figures (edges,
  blocks, arithmetic intensity) used in the roofline discussion.  Prints
  ``name,us_per_call,derived`` CSV as required.

* :func:`frontier_sweep` — the solver hot path head-to-head: one frontier
  round on a host-ordered web graph via (a) the per-edge
  gather→multiply→``segment_sum`` path that ``solve_frontier_jnp`` and the
  engine historically ran, (b) the full BSR block path, and (c) the BSR
  path restricted to occupied block columns — the work the fused Pallas
  kernel's scalar-prefetched occupancy map does on TPU (``pl.when`` skips
  the MXU work of inactive tiles; off-TPU we measure the equivalent
  compacted block list).  The block oracle is jitted ONCE at module level
  and keyed on static shape only — compacted block lists are padded to
  power-of-two buckets with zero tiles so density cells share traces
  (zero tiles contribute exactly 0.0; numerics are unchanged).  Each cell
  is swept over ``buffer_depth`` and every row carries the model-derived
  ``roofline_fraction`` / ``dma_compute_ratio`` (benchmarks/roofline.py).
  Emits ``BENCH_kernels.json``; interpret-mode *correctness* of the real
  kernel — including cross-depth bit parity — is asserted on the smallest
  cell of every sweep.
"""
from __future__ import annotations

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._meta import std_meta
from benchmarks.roofline import annotate_payload

from repro.core import host_block_graph, pagerank_system, power_law_graph
from repro.kernels.diffusion import (
    BsrMatrix,
    bsr_spmm,
    bsr_spmm_ref,
    compact_visits,
    frontier_round_bsr,
    frontier_round_bsr_pallas,
    frontier_round_ref,
    prepare_bsr,
)


def timeit(fn, *args, iters=20):
    jax.block_until_ready(fn(*args))  # compile + warm (array or pytree)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


# --------------------------------------------------------------------------- #
# frontier-round sweep: segment_sum vs BSR block path vs occupancy skip
# --------------------------------------------------------------------------- #
def _edge_round_fn(src, dst, wgt, n, c):
    """The per-edge baseline: full edge list touched every round."""

    @jax.jit
    def round_(f, w, t):
        sel = jnp.abs(f) * w[:, None] > t
        sent = jnp.where(sel, f, jnp.zeros_like(f))
        msg = sent[src] * wgt[:, None]  # [L, C]
        delta = jax.ops.segment_sum(msg, dst, num_segments=n)
        return (f - sent) + delta, jnp.sum(jnp.abs(delta))

    return round_


# ONE module-level jit for every block-oracle timing: the cache keys on
# operand shapes + the static (n_row_blocks, bs) pair, so density cells
# reuse each other's traces instead of re-jitting a fresh closure per cell
# (the historical wall-time sink this bench's meta used to apologise for).
@functools.partial(jax.jit, static_argnames=("n_row_blocks", "bs"))
def _block_round(blocks, block_row, block_col, f, w, t, *, n_row_blocks,
                 bs):
    sel = jnp.abs(f) * w[:, None] > t
    sent = jnp.where(sel, f, jnp.zeros_like(f))
    xt = sent.reshape(-1, bs, f.shape[1])
    delta = bsr_spmm_ref(blocks, block_row, block_col, xt, n_row_blocks)
    f_new = (f - sent) + delta.reshape(f.shape)
    return f_new, jnp.sum(jnp.abs(f_new))


def _block_round_args(m: BsrMatrix):
    """(positional args, static kwargs) for :func:`_block_round`."""
    return ((m.blocks, m.block_row, m.block_col),
            dict(n_row_blocks=m.n_row_blocks, bs=m.bs))


def _compact_bsr(m: BsrMatrix, active_cols: np.ndarray) -> BsrMatrix:
    """Blocks whose block_col holds frontier fluid — the tile set the
    Pallas occupancy map leaves active (inactive tiles contribute nothing
    because their sent fluid is zero).  The compacted list is padded with
    zero tiles to the next power of two so different frontier densities
    land in a handful of shared jit cache shapes."""
    mask = np.isin(np.asarray(m.block_col), active_cols)
    if not mask.any():
        mask[:1] = True  # degenerate: keep one (zero-contribution) block
    blocks = np.asarray(m.blocks)[mask]
    rows = np.asarray(m.block_row)[mask]
    cols = np.asarray(m.block_col)[mask]
    bucket = 1 << (int(blocks.shape[0]) - 1).bit_length()
    pad = bucket - blocks.shape[0]
    if pad:
        blocks = np.concatenate(
            [blocks, np.zeros((pad, m.bs, m.bs), blocks.dtype)])
        # zero tiles accumulate 0.0 into the last row — numerically inert,
        # and keeping block_row sorted preserves the kernel contract
        rows = np.concatenate(
            [rows, np.full(pad, rows[-1], dtype=rows.dtype)])
        cols = np.concatenate(
            [cols, np.zeros(pad, dtype=cols.dtype)])
    return BsrMatrix(blocks, rows, cols, m.n_row_blocks, m.bs)


def _make_frontier(n_pad, n, c, bs, density, rng):
    """Residual vector with ``density`` of the block columns above T=1.

    Hot blocks get |f| = 2 (selected), cold blocks 0.25 (kept) — the
    mid-convergence shape where most fluid sits under the threshold.
    """
    n_blocks = n_pad // bs
    n_hot = max(1, int(round(density * n_blocks)))
    hot = rng.choice(n_blocks, size=n_hot, replace=False)
    f = np.full((n_pad, c), 0.25, dtype=np.float32)
    signs = rng.choice([-1.0, 1.0], size=(n_pad, c))
    for b in hot:
        f[b * bs : (b + 1) * bs] = 2.0
    f *= signs
    f[n:] = 0.0
    return f, np.sort(hot)


def _verify_depths(m, fj, wj, t, f, w, depths):
    """Interpret-mode check on one cell: kernel vs numpy twin, and bit
    parity of the manual-DMA pipeline across buffer depths."""
    fp, _s, _r = frontier_round_bsr(
        m, fj, wj, t, backend="pallas", interpret=True)
    fr, _sr, _rr = frontier_round_ref(
        np.asarray(m.blocks), np.asarray(m.block_row),
        np.asarray(m.block_col), f, w, float(t))
    np.testing.assert_allclose(np.asarray(fp), fr, rtol=2e-4, atol=2e-4)
    for depth in depths:
        if depth == 1:
            continue
        fd, _s2, _r2 = frontier_round_bsr(
            m, fj, wj, t, backend="pallas", interpret=True,
            buffer_depth=depth)
        if not np.array_equal(np.asarray(fd), np.asarray(fp)):
            raise AssertionError(
                f"buffer_depth={depth} interpret output differs bitwise "
                "from depth=1")


def frontier_sweep(
    ns=(2**16, 2**17, 2**18, 2**19, 2**20, 2**21),
    cs=(1, 8, 64),
    densities=(1.0, 0.25, 0.05),
    bs=128,
    depths=(1, 2, 4),
    iters=3,
    seed=0,
    out_path="BENCH_kernels.json",
    max_cell_floats=3.5e8,  # skip cells whose edge operands exceed this
    max_tile_bytes=14e9,  # skip graphs whose tile pool exceeds this
    verify_interpret=True,
):
    """Sweep N × C × frontier density × buffer depth; write
    ``BENCH_kernels.json`` (roofline-annotated rows)."""
    rng = np.random.default_rng(seed)
    on_tpu = jax.default_backend() == "tpu"
    rows = []
    meta = std_meta(
        "kernel_frontier_sweep",
        seed=seed,
        bs=bs,
        iters=iters,
        depths=list(depths),
        timing_path="pallas" if on_tpu else "oracle",
        graph="host_block_graph(host_size=bs, links_per_node=8, "
              "intra_frac=0.92, span_hosts=2)",
        note=(
            "pallas_skip_us is the occupancy-restricted BSR path: on TPU "
            "the fused kernel's grid runs over the visit list of the "
            "active columns' tiles (compaction untimed); off-TPU the same "
            "tile subset runs through the module-level jitted jnp block oracle "
            "(cache keyed on shape; compacted lists pow2-padded).  Off-TPU "
            "the oracle has no buffer_depth, so depth rows share the "
            "oracle timing; on TPU each depth times the real pipeline.  "
            "Correctness incl. cross-depth bit parity is asserted in "
            "interpret mode on the smallest cell."
        ),
    )
    verified = False
    for n in ns:
        g = host_block_graph(n, host_size=bs, links_per_node=8.0,
                             intra_frac=0.92, span_hosts=2, seed=1)
        p, _b = pagerank_system(g)
        m = prepare_bsr(p.indptr, p.indices, p.weights, p.n, bs=bs)
        tile_bytes = m.n_blocks * bs * bs * 4
        n_pad = m.n_row_blocks * bs
        if tile_bytes > max_tile_bytes:
            rows.append({"n": n, "skipped": "tile pool exceeds "
                         f"{max_tile_bytes:.0e} bytes ({tile_bytes:.2e})"})
            continue
        src, dst, wgt = p.edge_list()
        srcj = jnp.asarray(src, jnp.int32)
        dstj = jnp.asarray(dst, jnp.int32)
        wgtj = jnp.asarray(wgt, jnp.float32)
        w = np.zeros(n_pad, np.float32)
        w[: p.n] = 1.0
        wj = jnp.asarray(w)
        t = jnp.float32(1.0)
        full_args, full_stat = _block_round_args(m)
        for c in cs:
            if g.n_edges * c > max_cell_floats:
                rows.append({"n": n, "c": c, "skipped":
                             f"edge operands exceed {max_cell_floats:.0e} "
                             "floats"})
                continue
            edge_round = _edge_round_fn(srcj, dstj, wgtj, n_pad, c)
            # big cells: one timed call is enough — the paths differ by
            # orders of magnitude and the warm call already primed caches
            it = 1 if g.n_edges * c > 8e7 else iters
            for d in densities:
                f, hot = _make_frontier(n_pad, p.n, c, bs, d, rng)
                fj = jnp.asarray(f)
                edge_us = timeit(edge_round, fj, wj, t, iters=it)
                block_us = timeit(
                    lambda *a: _block_round(*a, fj, wj, t, **full_stat),
                    *full_args, iters=it)
                m_act = _compact_bsr(m, hot)
                skip_args, skip_stat = _block_round_args(m_act)
                # true occupied-tile count (m_act is pow2-padded with
                # zero tiles purely for jit-cache sharing)
                n_active = int(np.isin(np.asarray(m.block_col), hot).sum())
                if verify_interpret and not verified:
                    _verify_depths(m, fj, wj, t, f, w, depths)
                    verified = True
                for depth in depths:
                    if on_tpu:
                        col_active = np.zeros(m.n_row_blocks, np.int32)
                        col_active[hot] = 1
                        caj = jnp.asarray(col_active)
                        visits = compact_visits(m, caj)[:3]
                        ft = fj.reshape(-1, bs, c)
                        wt = (wj / t).reshape(-1, bs, 1)
                        skip_us = timeit(
                            lambda ft_, wt_: frontier_round_bsr_pallas(
                                m.blocks, *visits, caj, ft_, wt_,
                                m.n_row_blocks, bs=bs, buffer_depth=depth),
                            ft, wt, iters=it)
                    elif depth == depths[0]:
                        skip_us = timeit(
                            lambda *a: _block_round(
                                *a, fj, wj, t, **skip_stat),
                            *skip_args, iters=it)
                    # else: off-TPU the oracle path is depth-invariant —
                    # the measurement from the first depth applies as-is
                    rows.append({
                        "n": n, "c": c, "density": d,
                        "buffer_depth": depth,
                        "n_edges": g.n_edges, "n_blocks": m.n_blocks,
                        "n_blocks_active": n_active,
                        "segment_sum_us": round(edge_us, 1),
                        "bsr_full_us": round(block_us, 1),
                        "pallas_skip_us": round(skip_us, 1),
                        "speedup_vs_segment_sum":
                            round(edge_us / skip_us, 3),
                    })
                print(f"[frontier] N=2^{int(np.log2(n))} C={c} d={d}: "
                      f"edge={edge_us/1e3:.1f}ms full={block_us/1e3:.1f}ms "
                      f"skip={skip_us/1e3:.1f}ms "
                      f"speedup={edge_us/skip_us:.2f}x")
    payload = annotate_payload({"meta": meta, "rows": rows})
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=1)
        print(f"[frontier] wrote {out_path} ({len(rows)} rows)")
    return payload


def main():
    rng = np.random.default_rng(0)
    rows = []

    # diffusion: frontier push on a 20k-node power-law graph
    g = power_law_graph(20000, seed=1)
    p, b = pagerank_system(g)
    m = prepare_bsr(p.indptr, p.indices, p.weights, p.n, bs=128)
    x = jnp.asarray(rng.standard_normal(m.n_row_blocks * 128)
                    .astype(np.float32))
    us = timeit(lambda x: bsr_spmm(m, x, use_pallas=False), x)
    ai = 2 * g.n_edges / (m.n_blocks * 128 * 128 * 4)
    rows.append(("diffusion_bsr_ref_N20k", us,
                 f"edges={g.n_edges};blocks={m.n_blocks};ai={ai:.3f}"))

    print("name,us_per_call,derived")
    for n, us, d in rows:
        print(f"{n},{us:.1f},{d}")
    return rows


if __name__ == "__main__":
    import sys

    if "--sweep" in sys.argv:
        frontier_sweep()
    elif "--sweep-smoke" in sys.argv:
        frontier_sweep(ns=(2**12,), cs=(1, 2), densities=(1.0, 0.5),
                       iters=1, out_path="BENCH_kernels.smoke.json")
    else:
        main()
