"""Distributed-engine benchmark: segment_sum vs BSR diffusion backends.

Times the jitted chunk of :class:`repro.core.distributed.DistributedEngine`
on a host-ordered web graph (the block-compressible structure the BSR
tiling exploits) and checks both backends converge to the same residual.
Emits ``BENCH_engine.json`` so the engine's perf trajectory has a seed
point next to the kernel sweep's.

Multi-device rows run in-process on the real devices, and a row whose
``k`` exceeds them is skipped.  A process pinned to the CPU
(``JAX_PLATFORMS=cpu``) runs them instead in one subprocess each, with
fake host devices (the XLA device count must be set before JAX
initialises); the parent spawns those before it touches JAX itself.
``--child`` is that subprocess entry and prints one JSON row.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np


def run_config(n: int, k: int, backend: str, buckets_per_dev: int,
               headroom: int, n_chunks: int = 8, target_error: float = 1e-6,
               seed: int = 1) -> dict:
    """Build the engine, time ``n_chunks`` jitted chunks, report a row."""
    import jax

    from repro.balance import BucketMoveExecutor
    from repro.core import host_block_graph, pagerank_system
    from repro.core.distributed import (
        DistributedEngine,
        EngineConfig,
        build_engine_arrays,
    )

    g = host_block_graph(n, host_size=128, links_per_node=8.0,
                         intra_frac=0.92, span_hosts=2, seed=seed)
    p, b = pagerank_system(g)
    cfg = EngineConfig(k=k, target_error=target_error, eps=0.15,
                       buckets_per_dev=buckets_per_dev, headroom=headroom,
                       diffusion_backend=backend)
    t_build0 = time.perf_counter()
    arrs = build_engine_arrays(p, b, cfg)
    build_s = time.perf_counter() - t_build0
    eng = DistributedEngine(arrs, cfg)
    ex = BucketMoveExecutor(eng, eng.init_state())

    # compile + warm one chunk, then time the steady-state chunk loop
    ex.state, stats = eng._chunk(ex.state, *ex.chunk_operands())
    jax.block_until_ready(stats["residual"])
    rounds_warm = int(np.asarray(ex.state.rounds))  # untimed rounds so far
    t0 = time.perf_counter()
    for _ in range(n_chunks):
        ex.state, stats = eng._chunk(ex.state, *ex.chunk_operands())
    jax.block_until_ready(stats["residual"])
    dt = time.perf_counter() - t0
    rounds = int(np.asarray(ex.state.rounds))
    rounds_timed = rounds - rounds_warm
    resid = float(np.asarray(stats["residual"])) + float(
        np.asarray(stats["s"]).sum())
    row = {
        "n": n, "k": k, "backend": backend,
        "buckets_per_dev": buckets_per_dev, "headroom": headroom,
        "n_edges": g.n_edges,
        "bucket_size": arrs.bucket_size,
        "chunk_ms": round(dt / n_chunks * 1e3, 2),
        "rounds": rounds,
        "us_per_round": round(dt / max(rounds_timed, 1) * 1e6, 1),
        "residual_after": resid,
        "build_s": round(build_s, 2),
    }
    if arrs.tiles is not None:
        row["n_tiles"] = int(
            (np.abs(arrs.tiles).sum(axis=(2, 3)) > 0).sum())
        row["tile_shape"] = list(arrs.tiles.shape)
    return row


def _spawn_child(n, k, backend, buckets_per_dev, headroom) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={k}")
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "benchmarks.engine_bench", "--child",
           "--n", str(n), "--k", str(k), "--backend", backend,
           "--buckets-per-dev", str(buckets_per_dev),
           "--headroom", str(headroom)]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=3600,
                       env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    if r.returncode != 0:
        raise RuntimeError(f"engine bench child failed: {r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main(smoke: bool = False, out_path: str = "BENCH_engine.json"):
    if smoke:
        grid = [(2**12, 1, 36, 4)]
    else:
        grid = [(2**16, 1, 520, 8), (2**16, 8, 72, 8), (2**17, 8, 136, 8)]
    backends = ("segment_sum", "bsr")
    # fake-device children first: a parent holding a chip would starve them
    fake_devices = os.environ.get("JAX_PLATFORMS") == "cpu"
    child_rows = {
        (n, k, backend): _spawn_child(n, k, backend, bpd, hr)
        for n, k, bpd, hr in grid if k > 1 and fake_devices
        for backend in backends
    }

    import jax

    from benchmarks._meta import std_meta

    n_dev = len(jax.devices())
    meta = std_meta(
        "engine_chunk_rounds",
        seed=1,
        graph="host_block_graph(host_size=128, links_per_node=8, "
              "intra_frac=0.92, span_hosts=2)",
        note=("chunk_ms times the steady-state jitted chunk "
              "(chunk_rounds exchange cycles incl. psum_scatter); "
              "k>1 rows run on the real devices, or on fake host devices "
              "in a subprocess when pinned to the CPU. On CPU the bsr "
              "backend runs the einsum tile path; the Pallas gather "
              "kernel takes over on TPU."),
        smoke=smoke,
    )
    rows = []
    for n, k, bpd, hr in grid:
        for backend in backends:
            if (n, k, backend) in child_rows:
                row = child_rows[(n, k, backend)]
            elif k > n_dev:
                row = {"n": n, "k": k, "backend": backend,
                       "skipped": f"k={k} > {n_dev} devices"}
                rows.append(row)
                print(f"[engine] N={n} K={k} {backend}: {row['skipped']}")
                continue
            else:
                row = run_config(n, k, backend, bpd, hr,
                                 n_chunks=2 if smoke else 8)
            rows.append(row)
            print(f"[engine] N={n} K={k} {backend}: "
                  f"chunk={row['chunk_ms']}ms rounds={row['rounds']} "
                  f"resid={row['residual_after']:.3e}")
    # backend pairs must agree on the residual they reach
    for i in range(0, len(rows), 2):
        a, b = rows[i], rows[i + 1]
        if "skipped" in a or "skipped" in b:
            continue
        drift = abs(a["residual_after"] - b["residual_after"])
        scale = max(abs(a["residual_after"]), 1e-12)
        agree = drift <= 1e-5 + 1e-2 * scale
        rows[i + 1]["residual_agrees_with_segment_sum"] = bool(agree)
        if not agree:
            print(f"[engine] WARNING residual drift {drift:.3e} "
                  f"between backends at row {i}")
    payload = {"meta": meta, "rows": rows}
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=1)
        print(f"[engine] wrote {out_path} ({len(rows)} rows)")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--n", type=int, default=2**16)
    ap.add_argument("--k", type=int, default=1)
    ap.add_argument("--backend", default="segment_sum")
    ap.add_argument("--buckets-per-dev", type=int, default=72)
    ap.add_argument("--headroom", type=int, default=8)
    args = ap.parse_args()
    if args.child:
        row = run_config(args.n, args.k, args.backend,
                         args.buckets_per_dev, args.headroom)
        print(json.dumps(row))
    else:
        main(smoke=args.smoke)
