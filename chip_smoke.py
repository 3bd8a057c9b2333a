"""Bring-up smoke run: the solver, the K-way engine and the PageRank service
on a TPU, through the entry points a user calls, at sizes that fill a real
share of one v5e chip.

    python chip_smoke.py               # one chip: solve, engine (k=1), serve
    python chip_smoke.py --four-chips  # four chips: engine:bsr k=4,
                                       # static and slope_ema, nothing else

Phases (one process, float32, no x64):

* solve  -- ``repro.solve(Problem.pagerank(g))`` with ``method="auto"`` on
  ``host_block_graph(2**21, ...)`` (the largest kernel-bench graph).  Auto
  dispatch must pick ``frontier:pallas`` and the round it runs must lower
  to the Mosaic kernel (``tpu_custom_call`` in its HLO).
* engine -- ``engine:bsr`` with ``k=1`` at N = 2**20, 128-node buckets; the
  engine's jitted chunk must hold the gather kernel.
* serve  -- the continuous-batching ``Scheduler`` that
  ``python -m repro.launch.serve rank`` runs, ``max_lanes=16``, on
  ``webgraph_like(2**17)``: 6 requests on 6 clusters, then 2 warm repeats
  of clusters 0 and 1 that must hit the session pool; 0 dropped.  The
  lanes run a per-edge gather and scatter (no Pallas kernel), which on a
  v5e took more than 1000 s for these 8 requests at N = 2**20; 2**17
  keeps the whole script inside its 20-minute limit.
* four   -- (``--four-chips`` only) ``engine:bsr`` with ``k=4`` on the
  solve graph, static and with ``policy="slope_ema"``; the state's shards
  must sit on 4 distinct devices.

Every answer is compared with a float64 reference that shares no code with
the solver: Jacobi ``x <- b + P x`` with a ``scipy.sparse`` matrix built
from the raw link lists, run to a residual of 1e-12.  The bound each error
must meet is the solve's own certificate plus an f32 allowance, see
:func:`error_bound`.

Each phase prints one line of facts (not benchmark metrics): N, L, bytes
on the device, compile seconds (tracing, lowering and compiling inside the
phase), run seconds (the rest of the phase's solve wall time), and the
error against the reference.  The last line of standard output is the
JSON line ``{"ok": true, "device": {...}}``.  Any failure raises, so the
exit code is non-zero; without a TPU the script exits with code 2 before
it does anything.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

DAMPING = 0.85
U32 = 2.0 ** -24  # unit roundoff of float32
REF_TOL = 1e-12
BUCKET = 128  # engine bucket size = BSR block size (the default rule's)
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_compile_s = [0.0]


def _count_compile(event: str, duration: float, **_kw) -> None:
    if event in COMPILE_EVENTS:
        _compile_s[0] += duration


class Phase:
    """Times one phase: wall seconds, and the compile seconds inside it."""

    def __enter__(self):
        self.c0, self.t0 = _compile_s[0], time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.compile_s = _compile_s[0] - self.c0
        self.run_s = time.perf_counter() - self.t0 - self.compile_s
        return False


# --------------------------------------------------------------------------- #
# the independent reference
# --------------------------------------------------------------------------- #
def pagerank_reference(g, b: np.ndarray) -> np.ndarray:
    """Solve ``x = b + P x`` in float64 by Jacobi, ``b`` of shape [N] or
    [N, C]; ``P[j, i] = damping / outdeg(i)`` for every link i -> j of the
    raw graph ``g``, dangling nodes absorb their fluid."""
    import scipy.sparse as sp

    deg = np.diff(np.asarray(g.indptr, dtype=np.int64))
    src = np.repeat(np.arange(g.n), deg)
    dst = np.asarray(g.indices, dtype=np.int64)
    p = sp.csr_matrix((DAMPING / deg[src], (dst, src)), shape=(g.n, g.n))
    x = np.array(b, dtype=np.float64)
    for _ in range(10_000):
        x_new = b + p @ x
        res = np.abs(x_new - x).sum(axis=0).max()
        x = x_new
        if res <= REF_TOL:
            return x
    raise RuntimeError(f"reference Jacobi stalled at residual {res:.3e}")


def error_bound(residual: float, rounds: int, terms: int, b_l1: float,
                x_l1: float, warm: bool = False) -> float:
    """What ``|x - x*|_1`` may be: certificate plus f32 allowance.

    In exact arithmetic the solver keeps ``B = (I - P) H + F``, so
    ``|H - x*|_1 <= |F|_1 / (1 - d)``.  In float32 the invariant drifts by
    E.  Each round ``H += sent`` rounds every entry once, ``F' = kept +
    P sent`` rounds once more, and the product sums at most ``terms``
    nonzero addends per entry (the largest in-degree; zero tiles entries
    add exactly).  The fluid is non-negative, so the sent fluid sums to
    ``|H|_1`` over the solve.  To first order, with ``u = 2^-24``::

        |E|_1 <= u (rounds (|B|_1 + 2 |H|_1) + terms d |H|_1)

    A factor 4 covers the MXU's multi-pass f32 products; a warm start
    (``F' = B' - H + P H``) adds one more round and one more product.
    """
    if warm:
        rounds, terms = rounds + 1, 2 * terms
    drift = 4 * U32 * (rounds * (b_l1 + 2 * x_l1) + terms * DAMPING * x_l1)
    return (residual + drift) / (1 - DAMPING)


def _check(name: str, x: np.ndarray, x_ref: np.ndarray, bound: float,
           certificate: float) -> float:
    err = float(np.abs(x - x_ref).sum())
    assert np.all(np.isfinite(x)), f"{name}: non-finite values"
    assert err <= bound, (
        f"{name}: |x - x_ref|_1 = {err:.3e} exceeds the bound {bound:.3e} "
        f"(certificate {certificate:.3e})")
    return err


def _max_in_degree(g) -> int:
    return int(np.bincount(np.asarray(g.indices), minlength=g.n).max())


def _assert_kernel(compiled, what: str) -> None:
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{what}: no Mosaic kernel (tpu_custom_call) in the compiled HLO")


def _device_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _report(phase: str, g, timer: Phase, err: float, bound: float,
            **facts) -> None:
    extra = " ".join(f"{k}={v}" for k, v in facts.items())
    print(f"[{phase}] N={g.n} L={g.n_edges} "
          f"peak_device_bytes={_device_bytes()} "
          f"compile_s={timer.compile_s:.2f} run_s={timer.run_s:.2f} "
          f"err_l1={err:.3e} bound_l1={bound:.3e} {extra}", flush=True)


def _block_graph(n: int):
    from repro.core import host_block_graph

    return host_block_graph(n, host_size=128, links_per_node=8.0,
                            intra_frac=0.92, span_hosts=2, seed=1)


def _kernel_knobs() -> dict:
    """The default kernel knobs, explicit, so no untracked tuned record
    under ``results/tuned`` can change what this script runs."""
    from repro.kernels.tune import (DEFAULT_BS, DEFAULT_BUFFER_DEPTH,
                                    DEFAULT_OCCUPANCY_THRESHOLD)

    return dict(bs=DEFAULT_BS, buffer_depth=DEFAULT_BUFFER_DEPTH,
                occupancy_threshold=DEFAULT_OCCUPANCY_THRESHOLD)


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #
def phase_solve(n: int = 2**21) -> None:
    import jax
    import jax.numpy as jnp

    import repro
    from repro.kernels.diffusion import BsrMatrix, frontier_round_bsr

    g = _block_graph(n)
    problem = repro.Problem.pagerank(g)
    knobs = _kernel_knobs()
    with Phase() as timer:
        report = repro.solve(problem, method="auto", **knobs)
    assert report.method == "frontier:pallas", report.method
    assert report.converged, f"solve stopped at |F|_1={report.residual:.3e}"

    # the round the driver runs (auto backend, auto interpret), lowered
    # from shapes alone for this chip
    view = problem.graph.bsr(bs=knobs["bs"])
    nrb, bs = view.n_row_blocks, view.bs
    spec = jax.ShapeDtypeStruct
    m = BsrMatrix.from_arrays(
        spec(view.blocks.shape, jnp.float32),
        spec(view.block_row.shape, jnp.int32),
        spec(view.block_col.shape, jnp.int32),
        spec((nrb,), jnp.bool_), nrb, bs)
    vec = spec((nrb * bs,), jnp.float32)
    round_fn = jax.jit(functools.partial(
        frontier_round_bsr, buffer_depth=knobs["buffer_depth"],
        occupancy_threshold=knobs["occupancy_threshold"]))
    compiled = round_fn.lower(m, vec, vec, spec((), jnp.float32)).compile()
    _assert_kernel(compiled, "frontier round")
    mem = compiled.memory_analysis()
    print(f"[solve] tile_pool_bytes={view.blocks.nbytes} "
          f"n_tiles={view.n_blocks} round memory_analysis: "
          f"arguments={mem.argument_size_in_bytes} "
          f"temporaries={mem.temp_size_in_bytes} "
          f"outputs={mem.output_size_in_bytes}", flush=True)

    x_ref = pagerank_reference(g, problem.b)
    bound = error_bound(report.residual, report.n_rounds, _max_in_degree(g),
                        float(problem.b.sum()), float(report.x.sum()))
    err = _check("solve", report.x, x_ref, bound, report.residual)
    _report("solve", g, timer, err, bound, method=report.method,
            rounds=report.n_rounds, residual=f"{report.residual:.3e}")


def _engine_session(problem, k: int, **kw):
    import repro

    knobs = _kernel_knobs()
    return repro.SolverSession(
        problem, method="engine:bsr", k=k, bs=knobs["bs"],
        buffer_depth=knobs["buffer_depth"], **kw)


def phase_engine(n: int = 2**20) -> None:
    import repro

    g = _block_graph(n)
    problem = repro.Problem.pagerank(g)
    with Phase() as timer:
        session = _engine_session(problem, k=1)
        report = session.solve()
    drv = session._driver  # the engine and its sharded state
    assert drv.arrays.bucket_size == BUCKET, drv.arrays.bucket_size
    assert report.converged, f"engine stopped at {report.residual:.3e}"
    compiled = drv.engine._chunk.lower(
        drv.ex.state, *drv.ex.chunk_operands()).compile()
    _assert_kernel(compiled, "engine chunk")
    tile_bytes = int(drv.arrays.tiles.nbytes)
    del session, drv

    x_ref = pagerank_reference(g, problem.b)
    bound = error_bound(report.residual, report.n_rounds, _max_in_degree(g),
                        float(problem.b.sum()), float(report.x.sum()))
    err = _check("engine", report.x, x_ref, bound, report.residual)
    _report("engine", g, timer, err, bound, k=1, tile_bytes=tile_bytes,
            rounds=report.n_rounds, residual=f"{report.residual:.3e}")


def phase_serve(n: int = 2**17) -> None:
    import repro
    from repro.core import webgraph_like
    from repro.serving import Scheduler

    g = webgraph_like(n, seed=1)
    problem = repro.Problem.pagerank(g)
    rng = np.random.default_rng(0)
    waves = [[(rid, rid) for rid in range(6)], [(6, 0), (7, 1)]]
    rhs = {}
    b = problem.b
    with Phase() as timer:
        sch = Scheduler(problem, max_lanes=16, rounds_per_tick=32)
        for wave in waves:
            for rid, cluster in wave:
                b = np.abs(b * (1.0 + 0.02 * rng.standard_normal(n)))
                rhs[rid] = b
                sch.submit(b, cluster=cluster, request_id=rid)
            sch.run_until_idle()
    assert sch.dropped == 0, sch.dropped
    served = {r.request_id: r for r in sch.results}
    assert sorted(served) == list(range(8)), sorted(served)
    assert all(served[rid].pool_hit for rid in (6, 7)), "no warm repeats"
    assert all(r.converged for r in served.values())

    ids = sorted(served)
    x_ref = pagerank_reference(g, np.stack([rhs[i] for i in ids], axis=1))
    terms = _max_in_degree(g)
    errs, bounds = [], []
    for col, rid in enumerate(ids):
        r = served[rid]
        bound = error_bound(r.residual, r.rounds, terms,
                            float(rhs[rid].sum()), float(r.x.sum()),
                            warm=True)
        errs.append(_check(f"serve request {rid}", r.x, x_ref[:, col],
                           bound, r.residual))
        bounds.append(bound)
    worst = int(np.argmax(np.asarray(errs) / np.asarray(bounds)))
    _report("serve", g, timer, errs[worst], bounds[worst],
            served=len(served), dropped=sch.dropped,
            pool_hits=sum(r.pool_hit for r in served.values()),
            lane_width=sch.batcher.width)


def phase_four_chips(n: int = 2**21) -> None:
    import jax

    import repro

    assert len(jax.devices()) == 4, f"need 4 chips, have {jax.devices()}"
    g = _block_graph(n)
    problem = repro.Problem.pagerank(g)
    x_ref = pagerank_reference(g, problem.b)
    terms = _max_in_degree(g)
    for label, kw in (("static", {}), ("slope_ema",
                                       {"policy": "slope_ema"})):
        with Phase() as timer:
            session = _engine_session(problem, k=4, **kw)
            report = session.solve()
        drv = session._driver
        assert drv.arrays.bucket_size == BUCKET, drv.arrays.bucket_size
        assert report.converged, f"{label}: stopped at {report.residual:.3e}"
        shard_devices = {s.device for s in drv.ex.state.f.addressable_shards}
        assert len(shard_devices) == 4, shard_devices
        compiled = drv.engine._chunk.lower(
            drv.ex.state, *drv.ex.chunk_operands()).compile()
        _assert_kernel(compiled, f"k=4 {label} engine chunk")
        del session, drv
        bound = error_bound(report.residual, report.n_rounds, terms,
                            float(problem.b.sum()), float(report.x.sum()))
        err = _check(f"four {label}", report.x, x_ref, bound,
                     report.residual)
        _report(f"four:{label}", g, timer, err, bound, k=4,
                shard_devices=len(shard_devices),
                moves=len(report.move_log), rounds=report.n_rounds,
                residual=f"{report.residual:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the k=4 engine phase on four chips")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); nothing run",
              file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__} "
          f"compile_cache={enable_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    phases = ([phase_four_chips] if args.four_chips
              else [phase_solve, phase_engine, phase_serve])
    for phase in phases:
        phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
