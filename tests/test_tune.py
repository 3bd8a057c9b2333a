"""kernels.tune: record persistence, sweep, measured dispatch, perf gate.

The contract under test (DESIGN.md §9):

* tuned records round-trip through versioned JSON and a stale version is
  treated as "no record";
* the sweep respects the VMEM feasibility model (infeasible configs are
  never timed);
* ``resolved_config`` precedence is explicit option > platform record >
  historical default;
* ``method="auto"`` demonstrably flips its backend choice when a tuned
  record appears for the current platform — and reverts when it is gone;
* the perf gate fails on a synthetic 2x slowdown (both metric kinds) and
  skips wall metrics across platforms.
"""
import json
import os
import sys

import numpy as np
import pytest

# the perf gate lives in benchmarks/, which is not an installed package
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import repro
from repro.api.registry import _auto_select
from repro.core import webgraph_like
from repro.kernels.tune import (
    DEFAULT_BS,
    DEFAULT_BUFFER_DEPTH,
    DEFAULT_OCCUPANCY_THRESHOLD,
    RECORD_VERSION,
    best_config,
    clear_cache,
    load_record,
    record_path,
    resolved_config,
    run_sweep,
    save_record,
    vmem_bytes,
    vmem_ok,
)
from repro.kernels.diffusion.kernel import VMEM_LIMIT_BYTES
from repro.kernels.tune.model import PLATFORM_SPECS, hw_spec

from benchmarks import perf_gate


def _record(kernel="frontier_round_bsr", platform="cpu", *,
            version=RECORD_VERSION, bs=64, buffer_depth=2,
            occupancy_threshold=0.1, gflops=123.0):
    return {
        "version": version,
        "kernel": kernel,
        "platform": platform,
        "device_kind": "test-device",
        "jax_version": "0.0.test",
        "created_utc": "2026-08-08T00:00:00+00:00",
        "timing_path": "oracle",
        "problem": {"n": 4096, "c": 1, "density": 0.25},
        "best": {
            "bs": bs,
            "buffer_depth": buffer_depth,
            "occupancy_threshold": occupancy_threshold,
            "measured_us": 10.0,
            "throughput_gflops": gflops,
            "roofline_fraction": 0.5,
            "vmem_bytes": vmem_bytes(bs, 1, buffer_depth),
        },
        "sweep": [],
    }


@pytest.fixture()
def tune_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path))
    clear_cache()
    yield tmp_path
    clear_cache()


# --------------------------------------------------------------------------- #
# records
# --------------------------------------------------------------------------- #
def test_record_round_trip(tune_dir):
    path = save_record(_record())
    assert path == record_path("frontier_round_bsr", "cpu")
    rec = load_record("frontier_round_bsr", "cpu")
    assert rec is not None and rec["best"]["bs"] == 64
    best = best_config("frontier_round_bsr", "cpu")
    assert (best.bs, best.buffer_depth, best.occupancy_threshold) == \
        (64, 2, 0.1)
    assert best.throughput_gflops == 123.0


def test_stale_version_is_no_record(tune_dir):
    rec = _record(version=RECORD_VERSION + 1)
    record_path("frontier_round_bsr", "cpu").parent.mkdir(
        parents=True, exist_ok=True)
    record_path("frontier_round_bsr", "cpu").write_text(json.dumps(rec))
    clear_cache()
    assert load_record("frontier_round_bsr", "cpu") is None
    assert best_config("frontier_round_bsr", "cpu") is None


def test_save_rejects_malformed(tune_dir):
    rec = _record()
    del rec["best"]["throughput_gflops"]
    with pytest.raises(ValueError):
        save_record(rec)
    with pytest.raises(ValueError):
        save_record(_record(kernel="not_a_kernel"))


def test_resolved_config_precedence(tune_dir):
    # no record: historical defaults
    assert resolved_config("frontier_round_bsr", platform="cpu") == (
        DEFAULT_BS, DEFAULT_BUFFER_DEPTH, DEFAULT_OCCUPANCY_THRESHOLD)
    save_record(_record(bs=64, buffer_depth=2, occupancy_threshold=0.1))
    # record beats defaults
    assert resolved_config("frontier_round_bsr", platform="cpu") == \
        (64, 2, 0.1)
    # explicit options beat the record, field by field
    assert resolved_config("frontier_round_bsr", platform="cpu",
                           bs=256) == (256, 2, 0.1)
    assert resolved_config("frontier_round_bsr", platform="cpu",
                           buffer_depth=1, occupancy_threshold=0.0) == \
        (64, 1, 0.0)


# --------------------------------------------------------------------------- #
# model / sweep
# --------------------------------------------------------------------------- #
def test_vmem_feasibility_model():
    spec = PLATFORM_SPECS["TPU v5 lite"]
    assert spec.vmem_budget == VMEM_LIMIT_BYTES
    assert vmem_ok(128, 1, 2, spec)
    # a tile ring this deep cannot fit the kernels' VMEM limit
    assert not vmem_ok(2048, 64, 8, spec)
    assert vmem_bytes(128, 1, 4) > vmem_bytes(128, 1, 2)


def test_unknown_device_kind_has_no_default_spec():
    assert hw_spec("cpu") is PLATFORM_SPECS["cpu"]
    with pytest.raises(KeyError, match="no peak table entry"):
        hw_spec("TPU v99")


def test_sweep_skips_infeasible_and_persists(tune_dir):
    rec = run_sweep(
        "frontier_round_bsr", n=1024, c=1, density=0.5,
        bs_list=(32,), depths=(1, 2), iters=1, save=True,
        verbose=False)
    # persisted and loadable through the registry-facing reader
    import jax

    platform = jax.default_backend()
    assert record_path("frontier_round_bsr", platform).exists()
    clear_cache()
    best = best_config("frontier_round_bsr", platform)
    assert best is not None and best.measured_us > 0
    assert rec["timing_path"] in ("oracle", "pallas")
    timed = [r for r in rec["sweep"] if r["feasible"]]
    assert timed and all(r["measured_us"] > 0 for r in timed)
    for r in rec["sweep"]:
        if not r["feasible"]:
            assert r.get("measured_us") is None


# --------------------------------------------------------------------------- #
# measured dispatch flip
# --------------------------------------------------------------------------- #
def _small_problem():
    return repro.Problem.pagerank(webgraph_like(2048, seed=1),
                                  target_error=1e-6)


def test_auto_dispatch_flips_on_record(tune_dir, monkeypatch):
    import jax

    platform = jax.default_backend()
    p = _small_problem()
    opts = repro.SolverOptions()
    without = _auto_select(p, opts)
    assert without == "frontier:segment_sum"  # historical priority rule
    save_record(_record(platform=platform, gflops=999.0))
    with_rec = _auto_select(p, opts)
    assert with_rec == "frontier:pallas"
    # record gone -> old behavior again
    record_path("frontier_round_bsr", platform).unlink()
    clear_cache()
    assert _auto_select(p, opts) == without


def test_auto_dispatch_measured_ranks_beat_priorities(tune_dir):
    import jax

    platform = jax.default_backend()
    # both tuned backends measured: higher throughput wins regardless of
    # auto_priority (engine:bsr priority 30 < frontier:pallas 40)
    save_record(_record("frontier_round_bsr", platform, gflops=10.0))
    save_record(_record("bsr_gather_spmm", platform, gflops=500.0))
    p = repro.Problem.pagerank(webgraph_like(1 << 17, seed=1),
                               target_error=1e-6)
    assert _auto_select(p, repro.SolverOptions()) == "engine:bsr"


@pytest.mark.parametrize("platform,n,expect_without,expect_with", [
    ("cpu", 2048, "frontier:segment_sum", "frontier:pallas"),
    ("tpu", 2048, "frontier:pallas", "frontier:pallas"),
    ("gpu", 2048, "frontier:segment_sum", "frontier:pallas"),
])
def test_auto_dispatch_platform_matrix(tune_dir, monkeypatch, platform,
                                       n, expect_without, expect_with):
    """Capability matrix over mocked platforms: without a record the
    priority rule holds per-platform; a record makes the tuned backend
    native and top-ranked everywhere."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    p = repro.Problem.pagerank(webgraph_like(n, seed=1),
                               target_error=1e-6)
    opts = repro.SolverOptions()
    assert _auto_select(p, opts) == expect_without
    save_record(_record(platform=platform, gflops=999.0))
    assert _auto_select(p, opts) == expect_with


def test_auto_dispatch_batch_and_dynamic_unaffected(tune_dir):
    """Gates the record must NOT override: frontier:pallas has no batch
    path and no dynamic partition, so those requests keep their backend
    even with a dominant tuned record present."""
    import jax

    platform = jax.default_backend()
    save_record(_record(platform=platform, gflops=9999.0))
    g = webgraph_like(2048, seed=1)
    pref = np.full((2048, 3), 1.0 / 2048, np.float32)
    pb = repro.Problem.pagerank(g, target_error=1e-6,
                                personalization=pref)
    assert _auto_select(pb, repro.SolverOptions()) == \
        "frontier:segment_sum"
    p = repro.Problem.pagerank(g, target_error=1e-6)
    dyn = _auto_select(p, repro.SolverOptions(dynamic=True, k=4))
    assert dyn != "frontier:pallas"


def test_solve_end_to_end_matches_across_flip(tune_dir):
    """The flipped backend must solve to the same answer."""
    import jax

    p = _small_problem()
    r0 = repro.solve(p, method="auto")
    save_record(_record(platform=jax.default_backend(), gflops=999.0,
                        bs=DEFAULT_BS, buffer_depth=2,
                        occupancy_threshold=0.0))
    r1 = repro.solve(p, method="auto")
    assert r0.method == "frontier:segment_sum"
    assert r1.method == "frontier:pallas"
    np.testing.assert_allclose(r0.x, r1.x, atol=1e-4)


# --------------------------------------------------------------------------- #
# perf gate
# --------------------------------------------------------------------------- #
def _bench_payload(skip_us=100.0, n_ops=1000.0):
    return {
        "meta": {"platform": "cpu"},
        "sections": {
            "kernels": {"rows": [{
                "n": 4096, "c": 1, "density": 0.5, "buffer_depth": 1,
                "pallas_skip_us": skip_us, "segment_sum_us": 300.0,
            }]},
            "api": {"rows": [{
                "method": "auto", "n": 4096, "n_ops": n_ops,
                "wall_s": 1.0,
            }]},
        },
    }


def test_perf_gate_passes_identical():
    base = perf_gate.make_baseline(_bench_payload())
    cur = perf_gate.extract_metrics(_bench_payload())
    results, ok = perf_gate.compare(cur, base, platform="cpu")
    assert ok and all(r["status"] == "ok" for r in results)


def test_perf_gate_fails_on_2x_wall_slowdown():
    base = perf_gate.make_baseline(_bench_payload(skip_us=100.0))
    cur = perf_gate.extract_metrics(_bench_payload(skip_us=210.0))
    results, ok = perf_gate.compare(cur, base, platform="cpu")
    assert not ok
    failed = [r for r in results if r["status"] == "fail"]
    assert any("pallas_skip_us" in r["metric"] for r in failed)


def test_perf_gate_fails_on_counter_regression():
    # counters get the tight band: +20% ops is already a failure
    base = perf_gate.make_baseline(_bench_payload(n_ops=1000.0))
    cur = perf_gate.extract_metrics(_bench_payload(n_ops=1200.0))
    _results, ok = perf_gate.compare(cur, base, platform="cpu")
    assert not ok


def test_perf_gate_platform_mismatch_skips_wall_only():
    base = perf_gate.make_baseline(_bench_payload())
    # 10x wall slowdown AND 2x counter regression, on another platform
    cur = perf_gate.extract_metrics(
        _bench_payload(skip_us=1000.0, n_ops=2000.0))
    results, ok = perf_gate.compare(cur, base, platform="tpu")
    assert not ok  # the counter still fails
    status = {r["metric"]: r["status"] for r in results}
    assert status["kernels/pallas_skip_us/n4096.c1.d0.5.bd1"] == \
        "skipped_platform"
    assert status["api/n_ops/auto.n4096"] == "fail"


def test_perf_gate_missing_metric_fails():
    base = perf_gate.make_baseline(_bench_payload())
    cur = perf_gate.extract_metrics(
        {"meta": {"platform": "cpu"}, "sections": {}})
    results, ok = perf_gate.compare(cur, base, platform="cpu")
    assert not ok
    assert all(r["status"] == "missing" for r in results)


def test_perf_gate_improvement_is_not_failure():
    base = perf_gate.make_baseline(_bench_payload(skip_us=100.0))
    cur = perf_gate.extract_metrics(_bench_payload(skip_us=10.0))
    results, ok = perf_gate.compare(cur, base, platform="cpu")
    assert ok
    assert any(r["status"] == "improved" for r in results)


def test_committed_baseline_matches_committed_bench():
    """The repo ships BENCH.json + perf_baseline.json in lockstep."""
    import os

    if not (os.path.exists("BENCH.json")
            and os.path.exists(perf_gate.BASELINE_PATH)):
        pytest.skip("committed artifacts not present")
    with open("BENCH.json") as fh:
        payload = json.load(fh)
    with open(perf_gate.BASELINE_PATH) as fh:
        baseline = json.load(fh)
    results, ok = perf_gate.compare(
        perf_gate.extract_metrics(payload), baseline,
        platform=baseline.get("meta", {}).get("platform"))
    assert ok, [r for r in results if r["status"] in ("fail", "missing")]
