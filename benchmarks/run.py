"""Benchmark suite entry: ``python -m benchmarks.run [--quick|--full|--smoke]``.

One section per paper table/figure + kernel microbench + roofline summary.
Asserts the paper's qualitative claims (C1–C4, DESIGN.md §1) on the
regenerated data and prints CSV-ish lines throughout.

``--smoke`` is the CI guard for the perf-trajectory artifacts: it runs a
tiny frontier sweep + engine bench end-to-end, validates the JSON schema
they emit, and validates any committed ``BENCH_*.json`` against the same
schema — so a schema break is caught before it lands.

``--consolidate`` (also run at the end of ``--smoke``) folds the
per-suite artifacts (``BENCH_kernels.json`` / ``BENCH_engine.json`` /
``BENCH_api.json`` / ``BENCH_graph.json``) into ONE schema-guarded
``BENCH.json`` trajectory, so perf history is machine-readable in one
place: ``{"meta": ..., "sections": {name: {meta, rows}}}`` — and then
runs the perf-regression gate (benchmarks/perf_gate.py) against the
committed baseline when one is present, so a regressed artifact cannot
land silently.
"""
from __future__ import annotations

import json
import os
import sys
import time

from benchmarks._meta import META_KEYS, std_meta

KERNEL_ROW_KEYS = {
    "n", "c", "density", "n_edges", "n_blocks", "n_blocks_active",
    "segment_sum_us", "bsr_full_us", "pallas_skip_us",
    "speedup_vs_segment_sum", "buffer_depth", "roofline_fraction",
    "dma_compute_ratio",
}
ENGINE_ROW_KEYS = {
    "n", "k", "backend", "n_edges", "bucket_size", "chunk_ms", "rounds",
    "us_per_round", "residual_after",
}
API_ROW_KEYS = {
    "method", "resolved", "n", "n_edges", "wall_s", "n_ops",
    "cost_iterations", "residual", "converged",
}
GRAPH_ROW_KEYS = {
    "n", "method", "n_edges", "churn_frac", "changed_edges", "f0_resid",
    "warm_ops", "cold_ops", "ops_ratio", "patch_s", "rebuild_s",
    "patch_speedup", "converged",
}
CHAOS_ROW_KEYS = {
    "scenario", "method", "n", "k", "n_edges", "undisturbed_ops",
    "disturbed_ops", "overhead_ops", "overhead_frac", "x_err_l1",
    "converged",
}
STREAM_ROW_KEYS = {
    "scenario", "method", "n", "k", "requests", "served", "dropped",
    "rejected", "applied_updates", "deferred_peak", "mean_staleness",
    "total_ops", "undisturbed_ops", "wasted_ops", "max_dx_l1",
    "checked_points", "p50_latency_s", "p95_latency_s",
    "recovery_p50_s", "recovery_p95_s", "degraded_frac", "kills",
    "restores", "rescales", "converged",
}

SERVE_ROW_KEYS = {
    "scenario", "n", "requests", "max_lanes", "clusters", "served",
    "dropped", "rejected", "qps", "seq_qps", "seq_sample",
    "speedup_vs_sequential", "p50_latency_s", "p99_latency_s",
    "pool_hit_rate", "pool_miss_rate", "mean_occupancy",
    "padding_waste", "bucket", "bit_parity", "max_dx_l1_seq",
    "max_dx_l1_ref", "dx_bound", "total_ops", "degrades",
    "applied_updates", "degraded_frac", "converged",
}

# one registry drives per-suite validation AND the BENCH.json merge
BENCH_SECTIONS = {
    "kernels": ("BENCH_kernels.json", KERNEL_ROW_KEYS),
    "engine": ("BENCH_engine.json", ENGINE_ROW_KEYS),
    "api": ("BENCH_api.json", API_ROW_KEYS),
    "graph": ("BENCH_graph.json", GRAPH_ROW_KEYS),
    "chaos": ("BENCH_chaos.json", CHAOS_ROW_KEYS),
    "stream": ("BENCH_stream.json", STREAM_ROW_KEYS),
    "serve": ("BENCH_serve.json", SERVE_ROW_KEYS),
}


def _validate_bench(payload: dict, required: set, name: str) -> None:
    meta = payload.get("meta")
    assert isinstance(meta, dict), f"{name}: missing meta"
    meta_missing = META_KEYS - meta.keys()
    assert not meta_missing, (
        f"{name}: meta missing normalized keys {sorted(meta_missing)} "
        "(emit it via benchmarks._meta.std_meta)")
    rows = payload.get("rows")
    assert isinstance(rows, list) and rows, f"{name}: missing rows"
    real = [r for r in rows if "skipped" not in r]
    assert real, f"{name}: every row skipped"
    for r in real:
        missing = required - r.keys()
        assert not missing, f"{name}: row missing keys {sorted(missing)}"
    print(f"  {name}: {len(real)} measured rows, schema OK")


def consolidate(out_path: str = "BENCH.json") -> dict:
    """Merge the per-suite BENCH_*.json into one validated trajectory."""
    sections = {}
    for name, (path, keys) in BENCH_SECTIONS.items():
        if not os.path.exists(path):
            print(f"  {name}: {path} not present, section omitted")
            continue
        with open(path) as fh:
            payload = json.load(fh)
        _validate_bench(payload, keys, path)
        sections[name] = payload
    payload = {
        "meta": std_meta(
            "consolidated_perf_trajectory",
            sections_present=sorted(sections),
            section_files={n: BENCH_SECTIONS[n][0] for n in sections},
        ),
        "sections": sections,
    }
    if sections:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=1)
        print(f"  wrote {out_path} ({len(sections)} sections)")
    return payload


def _validate_consolidated(path: str = "BENCH.json") -> None:
    if not os.path.exists(path):
        print(f"  {path} not present (perf trajectory not seeded yet)")
        return
    with open(path) as fh:
        payload = json.load(fh)
    assert isinstance(payload.get("meta"), dict), f"{path}: missing meta"
    sections = payload.get("sections")
    assert isinstance(sections, dict) and sections, (
        f"{path}: missing sections")
    for name, sec in sections.items():
        assert name in BENCH_SECTIONS, f"{path}: unknown section {name!r}"
        _validate_bench(sec, BENCH_SECTIONS[name][1], f"{path}:{name}")


def smoke() -> int:
    """Fast end-to-end bench smoke + BENCH_*.json schema validation."""
    from benchmarks import api_bench, engine_bench, graph_bench, kernel_bench

    print("[smoke] frontier kernel sweep (tiny)")
    kp = kernel_bench.frontier_sweep(
        ns=(2**12,), cs=(1, 2), densities=(1.0, 0.5), iters=1,
        out_path="BENCH_kernels.smoke.json")
    _validate_bench(kp, KERNEL_ROW_KEYS, "kernel sweep (smoke)")
    print("[smoke] engine bench (tiny)")
    ep = engine_bench.main(smoke=True, out_path="BENCH_engine.smoke.json")
    _validate_bench(ep, ENGINE_ROW_KEYS, "engine bench (smoke)")
    print("[smoke] api auto-dispatch bench (tiny)")
    ap = api_bench.main(smoke=True, out_path="BENCH_api.smoke.json")
    _validate_bench(ap, API_ROW_KEYS, "api bench (smoke)")
    auto_rows = [r for r in ap["rows"]
                 if r.get("method") == "auto" and "skipped" not in r]
    assert auto_rows and auto_rows[0]["resolved"] != "auto", (
        "auto dispatch did not resolve to a concrete backend")
    print("[smoke] graph delta-vs-cold bench (tiny)")
    gp = graph_bench.main(smoke=True, out_path="BENCH_graph.smoke.json")
    _validate_bench(gp, GRAPH_ROW_KEYS, "graph bench (smoke)")
    warm_rows = [r for r in gp["rows"] if "skipped" not in r]
    assert warm_rows and all(r["ops_ratio"] > 1.0 for r in warm_rows), (
        "delta re-solve did not beat the cold solve")
    print("[smoke] chaos recovery-overhead bench (tiny)")
    from benchmarks import chaos_bench

    cp = chaos_bench.main(smoke=True, out_path="BENCH_chaos.smoke.json")
    _validate_bench(cp, CHAOS_ROW_KEYS, "chaos bench (smoke)")
    chaos_rows = [r for r in cp["rows"] if "skipped" not in r]
    assert chaos_rows and all(r["converged"] for r in chaos_rows), (
        "a chaos scenario failed to converge after recovery")
    print("[smoke] stream soak bench (shortened, seeded chaos)")
    from benchmarks import stream_bench

    sp = stream_bench.main(smoke=True, out_path="BENCH_stream.smoke.json")
    _validate_bench(sp, STREAM_ROW_KEYS, "stream bench (smoke)")
    soak = [r for r in sp["rows"] if r["scenario"] == "soak"]
    assert soak, "stream smoke emitted no soak row"
    s = soak[0]
    assert s["requests"] >= 100, s  # shortened soak still streams >=100
    assert s["kills"] >= 1 and s["restores"] >= 1, s
    assert s["rescales"] >= 1, s
    assert s["applied_updates"] >= 1, s  # continuous churn reached apply
    assert s["dropped"] == 0, "supervised stream dropped a request"
    assert s["max_dx_l1"] <= 1e-6, (
        "served solutions diverged from the effective-schedule replay")
    print("[smoke] continuous-batching serve bench (tiny)")
    from benchmarks import serve_bench

    vp = serve_bench.main(smoke=True, out_path="BENCH_serve.smoke.json")
    _validate_bench(vp, SERVE_ROW_KEYS, "serve bench (smoke)")
    head = [r for r in vp["rows"] if r["scenario"] == "serving"]
    over = [r for r in vp["rows"] if r["scenario"] == "overload"]
    assert head and over, "serve smoke missing a scenario"
    assert all(r["dropped"] == 0 for r in vp["rows"]), (
        "continuous batching dropped a request")
    assert head[0]["served"] == head[0]["requests"], head[0]
    assert head[0]["speedup_vs_sequential"] > 1.0, (
        "continuous batching did not beat the sequential path")
    assert head[0]["max_dx_l1_seq"] <= head[0]["dx_bound"], (
        "batched solutions diverged from the sequential twin")
    assert all(r["bit_parity"] for r in vp["rows"]), (
        "pow2 lane padding changed the solution bits")
    assert over[0]["degrades"] >= 1, (
        "overload cell never engaged the pressure ladder")
    for tmp in ("BENCH_kernels.smoke.json", "BENCH_engine.smoke.json",
                "BENCH_serve.smoke.json",
                "BENCH_api.smoke.json", "BENCH_graph.smoke.json",
                "BENCH_chaos.smoke.json", "BENCH_stream.smoke.json"):
        if os.path.exists(tmp):
            os.remove(tmp)
    # consolidate() validates each committed per-suite artifact as it
    # merges them, then the merged BENCH.json is re-checked on disk
    print("[smoke] committed artifacts -> consolidated trajectory")
    consolidate()
    _validate_consolidated()
    print("[smoke] OK")
    return 0


def main():
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    quick = "--quick" in sys.argv
    full = "--full" in sys.argv
    if "--smoke" in sys.argv:
        return smoke()
    if "--consolidate" in sys.argv:
        consolidate()
        _validate_consolidated()
        # perf-regression gate: compare the consolidated trajectory
        # against the committed baseline (skipped until one is seeded)
        from benchmarks import perf_gate

        if os.path.exists(perf_gate.BASELINE_PATH):
            return perf_gate.main(["--check"])
        print(f"  {perf_gate.BASELINE_PATH} not present — gate skipped "
              "(seed it with python -m benchmarks.perf_gate "
              "--update-baseline)")
        return 0
    t0 = time.time()
    print("=" * 70)
    print("D-iteration dynamic-partition benchmark suite")
    print("=" * 70)

    # ---------------- Tables 1–3 ----------------
    from benchmarks import paper_tables

    tables = paper_tables.main(quick=quick)
    t1, t2, t3 = tables["table1"], tables["table2"], tables["table3"]

    def chk(name, cond, detail=""):
        print(f"  CLAIM {name}: {'PASS' if cond else 'FAIL'} {detail}")
        return cond

    print("\n[claims vs paper]")
    ok = True
    # C4: K=1 cost is a few normalized iterations at target 1/N
    ok &= chk("C4 K=1 cost O(1) matvecs", t1[(1, 'uniform', False)] < 15,
              f"cost={t1[(1, 'uniform', False)]:.2f}")
    if not quick:
        # C2: dynamic rescues skewed orderings at K=16 (Tables 2/3 pattern)
        ok &= chk(
            "C2 dynamic beats static on out-degree order (K=16, unif)",
            t2[(16, 'uniform', True)] < t2[(16, 'uniform', False)],
            f"{t2[(16, 'uniform', True)]:.2f} < "
            f"{t2[(16, 'uniform', False)]:.2f}")
        ok &= chk(
            "C2 dynamic beats static on in-degree order (K=16, cb)",
            t3[(16, 'cb', True)] < t3[(16, 'cb', False)],
            f"{t3[(16, 'cb', True)]:.2f} < {t3[(16, 'cb', False)]:.2f}")
        # parallel speedup exists (C3 direction)
        ok &= chk("C3 K=16 cheaper than K=1 (random order)",
                  t1[(16, 'uniform', False)] < t1[(1, 'uniform', False)],
                  f"{t1[(16, 'uniform', False)]:.2f} < "
                  f"{t1[(1, 'uniform', False)]:.2f}")

    # ---------------- Figures 1–4, 15–18 ----------------
    from benchmarks import fig_convergence

    fig_convergence.main(quick=quick)

    # ---------------- Figures 5/6 ----------------
    from benchmarks import webgraph_speedup

    rows = webgraph_speedup.run(
        ns=(1000,) if quick else ((1000, 10000, 100000) if full
                                  else (1000, 10000)),
        ks=(1, 2, 4) if quick else (1, 2, 4, 8, 16, 32, 64),
    )
    if not quick:
        # C1: with exchange cost charged, parallel EFFICIENCY collapses for
        # large K at small N ("the gain is limited ... when N/K becomes too
        # small"): static-uniform efficiency at K=max is under half of the
        # K=4 efficiency (the curve is also non-monotone, see fig5_6.csv).
        n1 = [r for r in rows if r[0] == 1000 and r[2] == "uniform"
              and r[3] == 0]
        speeds = {r[1]: float(r[5]) for r in n1}
        best = max(speeds.values())
        k_max = max(speeds)
        eff_max = speeds[k_max] / k_max
        eff_4 = speeds.get(4, speeds[min(speeds)]) / 4
        ok &= chk("C1 efficiency collapses at small N/K (static)",
                  eff_max < 0.5 * eff_4,
                  f"eff(K={k_max})={eff_max:.2f} vs eff(K=4)={eff_4:.2f}")
        # C3: larger N sustains speedup to larger K
        n2 = [r for r in rows if r[0] == 10000 and r[2] == "uniform"
              and r[3] == 1]
        if n2:
            sp2 = {r[1]: float(r[5]) for r in n2}
            ok &= chk("C3 larger N, larger useful K (dyn)",
                      max(sp2.values()) >= best * 0.9,
                      f"N=10k best={max(sp2.values()):.2f} vs "
                      f"N=1k best={best:.2f}")

    # ---------------- kernel microbench ----------------
    print("\n[kernel microbench]")
    from benchmarks import kernel_bench

    kernel_bench.main()

    # ---------------- roofline summary ----------------
    print("\n[roofline (from BENCH_kernels.json, if present)]")
    from benchmarks import roofline

    try:
        rows_r = roofline.build_table()
        if rows_r:
            bounds = {}
            for r in rows_r:
                bounds[r["bound"]] = bounds.get(r["bound"], 0) + 1
            print(f"  {len(rows_r)} rows analysed; binding wall: {bounds}")
            worst = sorted(rows_r,
                           key=lambda r: r["roofline_fraction"])[:5]
            for r in worst:
                print(f"  worst-frac: n={r['n']} c={r['c']} "
                      f"density={r['density']} depth={r['buffer_depth']} "
                      f"frac={r['roofline_fraction']:.4f} "
                      f"bound={r['bound']} "
                      f"dma/compute={r['dma_compute_ratio']:.2f}")
        else:
            print("  (no BENCH_kernels.json — run "
                  "python -m benchmarks.kernel_bench --sweep first)")
    except Exception as e:  # pragma: no cover
        print("  roofline summary unavailable:", e)

    print(f"\nsuite finished in {time.time()-t0:.0f}s; "
          f"claims {'ALL PASS' if ok else 'SOME FAILED'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
