"""Serving driver: one mode, ``rank`` (the mode word is optional).

* ``rank`` — warm-start multi-RHS PageRank serving on a
  :class:`repro.SolverSession` (DESIGN.md §4): one cold solve builds
  the (H, F) fluid state, then a stream of perturbed teleport vectors
  is served via ``warm_start`` (re-seed ``F = B' − (I−P)H``, §2.2) and
  a personalization batch via the vmapped ``solve_batch`` path.  Prints
  the edge-push ops each warm request saved vs a cold solve.

  The request stream also carries **graph updates** (DESIGN.md §7):
  every ``--churn-every`` requests a link-rotation delta of
  ``--churn`` × L edges flows through ``session.update_graph`` — the
  GraphStore patches its views in place and the fluid re-seeds via
  ``F' = F + (P'−P)·H``, so the evolving graph re-solves warm instead
  of cold.

  Every request passes **admission control** (DESIGN.md §10): poison
  personalization vectors (NaN / negative / zero mass — exercised by
  ``--poison-every``) and stale or malformed graph deltas are rejected
  into a quarantine WITHOUT killing the session; the stream keeps
  serving and the quarantine tally prints at exit.  A graph update
  that fails mid-apply rolls back transactionally (the session keeps
  serving the pre-delta graph).

  The serving process is **elastic and fault tolerant** (DESIGN.md §8):
  ``--ckpt-dir`` cuts an atomic checkpoint of the (H, F) fluid state
  after every request; ``--resume`` restores the newest checkpoint that
  passes the ``B = (I−P)H + F`` invariant check instead of solving
  cold (torn/stale steps are rejected and skipped); ``--rescale-at R
  --rescale-k K`` shrinks/grows the engine's pid axis mid-stream
  (device loss / scale-up) without recomputing H — engine methods only.

    PYTHONPATH=src python -m repro.launch.serve --n 20000 --requests 8
    PYTHONPATH=src python -m repro.launch.serve rank --churn 0.01 \\
        --churn-every 3
    PYTHONPATH=src python -m repro.launch.serve rank --ckpt-dir /tmp/ck \\
        --resume
"""
import argparse
import sys
import time

import numpy as np


def rank_main(argv):
    ap = argparse.ArgumentParser(prog="serve rank")
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--method", default="frontier:segment_sum",
                    help="warm-startable registry key")
    ap.add_argument("--requests", type=int, default=8,
                    help="warm-start requests to serve after the cold "
                    "solve")
    ap.add_argument("--batch", type=int, default=8,
                    help="personalization columns for the solve_batch "
                    "demo")
    ap.add_argument("--drift", type=float, default=0.02,
                    help="per-request fractional perturbation of B")
    ap.add_argument("--churn", type=float, default=0.0,
                    help="graph-update request: fraction of edges "
                    "link-rotated per update (0 disables)")
    ap.add_argument("--churn-every", type=int, default=3,
                    help="serve a graph-update request every this many "
                    "warm requests")
    ap.add_argument("--poison-every", type=int, default=0,
                    help="inject a poison (NaN) personalization vector "
                    "every this many requests to exercise admission "
                    "control (0 disables)")
    ap.add_argument("--target-error", type=float, default=None)
    ap.add_argument("--k", type=int, default=None,
                    help="engine methods: devices on the pid axis")
    ap.add_argument("--ckpt-dir", default=None,
                    help="atomic fluid-state checkpoint after every "
                    "served request (DESIGN.md §8)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest VALID checkpoint from "
                    "--ckpt-dir instead of a cold solve")
    ap.add_argument("--rescale-at", type=int, default=None,
                    help="request index at which to rescale the pid "
                    "axis (engine methods)")
    ap.add_argument("--rescale-k", type=int, default=None,
                    help="pid-axis width to rescale to at --rescale-at")
    ap.add_argument("--no-batching", action="store_true",
                    help="serve the stream strictly sequentially (the "
                    "pre-scheduler path; output is bit-identical to it)")
    ap.add_argument("--max-lanes", type=int, default=16,
                    help="continuous batching: lane-axis cap (pow2)")
    ap.add_argument("--rounds-per-tick", type=int, default=32,
                    help="continuous batching: frontier rounds per "
                    "scheduler micro-step")
    args = ap.parse_args(argv)
    if args.churn > 0 and args.churn_every < 1:
        ap.error("--churn-every must be >= 1 when --churn is set")
    if (args.rescale_at is None) != (args.rescale_k is None):
        ap.error("--rescale-at and --rescale-k go together")
    if args.resume and not args.ckpt_dir:
        ap.error("--resume needs --ckpt-dir")
    # the scheduler is frontier-native and stateless across processes:
    # session-exclusive features (checkpoint/resume, pid-axis rescale,
    # engine backends) keep the sequential path (DESIGN.md §11
    # migration note)
    sequential = (args.no_batching or args.ckpt_dir or args.resume
                  or args.rescale_at is not None
                  or args.method != "frontier:segment_sum")
    if sequential:
        return _rank_sequential(args)
    return _rank_batched(args)


def _rank_batched(args):
    """Default rank serving: the request stream flows through the
    continuous-batching :class:`repro.serving.Scheduler` — same seeded
    stream (drift chain, poison schedule, churn deltas) as the
    sequential path, but rank requests between graph updates are
    served concurrently in kernel lanes.  Graph updates are natural
    drain barriers: the scheduler flushes each delta against the
    post-predecessor store, exactly the sequential ordering."""
    import repro
    from repro.core import webgraph_like
    from repro.graph import rotation_churn
    from repro.resilience import RequestRejected
    from repro.serving import Scheduler

    rng = np.random.default_rng(0)
    g = webgraph_like(args.n, seed=1)
    problem = repro.Problem.pagerank(g, target_error=args.target_error)
    print(f"N={g.n} L={g.n_edges} method={args.method} "
          f"target_error={problem.target_error:.2e}")
    sch = Scheduler(problem, max_lanes=args.max_lanes,
                    rounds_per_tick=args.rounds_per_tick)
    print(f"[mode ] continuous batching: max_lanes={sch.batcher.max_lanes}"
          f" rounds_per_tick={sch.rounds_per_tick} "
          f"pool_capacity={sch.pool.capacity}")

    printed = 0

    def drain_and_report():
        nonlocal printed
        sch.run_until_idle()
        for r in sch.results[printed:]:
            print(f"[served {r.request_id}] |res|={r.residual:.2e} "
                  f"{r.ops} ops, {r.rounds} rounds, "
                  f"pool_hit={r.pool_hit}, lat={r.latency_s:.3f}s"
                  + (f" [degraded rung={r.rung}]" if r.degraded else ""))
        printed = len(sch.results)

    t0 = time.time()
    b = problem.b
    for req in range(args.requests):
        if args.churn > 0 and req % args.churn_every == args.churn_every - 1:
            drain_and_report()  # the update's drain barrier
            n_rot = max(1, int(args.churn * sch.problem.n_edges) // 2)
            delta = rotation_churn(sch.problem.graph, n_rot,
                                   seed=1000 + req)
            try:
                sch.submit_update(
                    delta, store_version=sch.problem.store_version)
                sch.run_until_idle()  # flush: apply at the barrier
                print(f"[update {req}] {delta.n_changes} changed edges "
                      f"applied, store at version "
                      f"{sch.problem.store_version}")
            except RequestRejected as e:
                print(f"[quarantine {req}] update rejected: {e}")
            continue
        b = b * (1.0 + args.drift * rng.standard_normal(g.n))
        b = np.abs(b)
        b_req = b
        if args.poison_every and req % args.poison_every == (
                args.poison_every - 1):
            b_req = b.copy()
            b_req[rng.integers(g.n)] = np.nan  # a client sent garbage
        try:
            sch.submit(b_req, cluster=0, request_id=req)
        except RequestRejected as e:
            print(f"[quarantine {req}] rank request rejected: {e}")
    drain_and_report()
    wall = time.time() - t0
    if sch.quarantine.total:
        print(f"[quarantine] {sch.quarantine.total} rejected: "
              f"{sch.quarantine.to_jsonable()['by_reason']}")
    served = len(sch.results)
    lat = sch.latency_percentiles()
    print(f"[stats] served={served} dropped={sch.dropped} "
          f"qps={served / max(wall, 1e-9):.2f} "
          f"pool_hit_rate={sch.pool.hit_rate:.2f} "
          f"occupancy={sch.batcher.mean_occupancy:.2f} "
          f"p50={lat['p50']:.3f}s p99={lat['p99']:.3f}s "
          f"rung={sch.ladder.rung.name}")


def _rank_sequential(args):
    """The pre-scheduler rank loop, preserved verbatim: one
    warm-started session, strictly one request at a time.  The
    ``--no-batching`` regression test holds this path's output
    bit-identical to the pre-PR-8 CLI."""
    import repro
    from repro.core import webgraph_like

    rng = np.random.default_rng(0)
    g = webgraph_like(args.n, seed=1)
    problem = repro.Problem.pagerank(g, target_error=args.target_error)
    options = repro.SolverOptions(k=args.k)
    print(f"N={g.n} L={g.n_edges} method={args.method} "
          f"target_error={problem.target_error:.2e}")

    session = None
    if args.resume:
        try:
            t0 = time.time()
            session = repro.SolverSession.restore(
                args.ckpt_dir, problem, method=args.method,
                options=options)
            info = session.restored_from
            print(f"[resume] step {info['step']} "
                  f"({len(info['rejected'])} rejected), residual="
                  f"{session.residual:.2e}, {time.time()-t0:.2f}s — "
                  "H carried over, no cold solve")
            session.solve()  # drain whatever fluid remains
            # no cold baseline this process: savings are reported
            # against what a cold solve of this problem WOULD cost
            baseline_ops = None
        except FileNotFoundError:
            print("[resume] no checkpoint yet — starting cold")
            session = None
        except ValueError as e:
            # checkpoints exist but every step was rejected (torn /
            # stale / wrong graph): serving must come up cold, not die
            print(f"[resume] no VALID checkpoint ({e}) — starting cold")
            session = None
    if session is None:
        session = repro.SolverSession(problem, method=args.method,
                                      options=options)
        t0 = time.time()
        cold = session.solve()
        baseline_ops = cold.n_ops
        print(f"[cold ] {cold.n_ops} edge pushes, {cold.n_rounds} "
              f"rounds, {time.time()-t0:.2f}s — the serving baseline")
    if args.ckpt_dir:
        print(f"[ckpt ] {session.checkpoint(args.ckpt_dir)}")

    from repro.graph import rotation_churn
    from repro.resilience import (Quarantine, RequestRejected,
                                  validate_graph_update, validate_rhs)

    quarantine = Quarantine()
    b = problem.b
    for req in range(args.requests):
        if args.rescale_at is not None and req == args.rescale_at:
            t0 = time.time()
            drains = session.rescale(args.rescale_k)
            print(f"[rescale {req}] pid axis -> k={args.rescale_k} "
                  f"({len(drains)} buckets drained through the executor "
                  f"path), {time.time()-t0:.2f}s — H not recomputed")
        if args.churn > 0 and req % args.churn_every == args.churn_every - 1:
            # a graph-update request: the crawl delivered link churn
            n_rot = max(1, int(args.churn * session.problem.n_edges) // 2)
            delta = rotation_churn(session.problem.graph, n_rot,
                                   seed=1000 + req)
            t0 = time.time()
            try:
                # admission: a delta built against a stale store
                # version or naming edges the store doesn't hold never
                # reaches the session
                validate_graph_update(
                    session.problem.graph, delta,
                    store_version=session.problem.store_version)
                resid0 = session.update_graph(delta)
            except RequestRejected as e:
                quarantine.record(req, e.reason)
                print(f"[quarantine {req}] update rejected: {e}")
                continue
            except Exception as e:
                # update_graph rolled the store back: the session still
                # serves the pre-delta graph, the stream keeps flowing
                quarantine.record(req, "update-failed")
                print(f"[quarantine {req}] update failed, rolled back: "
                      f"{e}")
                continue
            rep = session.solve()
            saved = (f"{1.0 - rep.n_ops / max(baseline_ops, 1):.0%}"
                     if baseline_ops else "n/a")
            print(f"[update {req}] {delta.n_changes} changed edges "
                  f"|F0|={resid0:.2e} {rep.n_ops} ops ({saved} saved "
                  f"vs cold), {rep.n_rounds} rounds, {time.time()-t0:.2f}s")
            if args.ckpt_dir:
                session.checkpoint(args.ckpt_dir)
            continue
        # a drifting teleport vector: what a freshness-weighted or
        # user-conditioned ranking update looks like between requests
        b = b * (1.0 + args.drift * rng.standard_normal(g.n))
        b = np.abs(b)
        b_req = b
        if args.poison_every and req % args.poison_every == (
                args.poison_every - 1):
            b_req = b.copy()
            b_req[rng.integers(g.n)] = np.nan  # a client sent garbage
        t0 = time.time()
        try:
            b_ok = validate_rhs(b_req, g.n)
        except RequestRejected as e:
            quarantine.record(req, e.reason)
            print(f"[quarantine {req}] rank request rejected: {e}")
            continue
        resid0 = session.warm_start(b_ok)
        rep = session.solve()
        saved = (f"{1.0 - rep.n_ops / max(baseline_ops, 1):.0%}"
                 if baseline_ops else "n/a")
        print(f"[warm {req}] |F0|={resid0:.2e} {rep.n_ops} ops "
              f"({saved} saved vs cold), {rep.n_rounds} rounds, "
              f"{time.time()-t0:.2f}s")
        if args.ckpt_dir:
            session.checkpoint(args.ckpt_dir)
    if quarantine.total:
        print(f"[quarantine] {quarantine.total} rejected: "
              f"{quarantine.to_jsonable()['by_reason']}")

    # personalized batch: C independent teleport columns, one vmapped run
    hot = rng.choice(g.n, size=args.batch, replace=False)
    pref = np.zeros((g.n, args.batch))
    pref[hot, np.arange(args.batch)] = 1.0
    t0 = time.time()
    batch = session.solve_batch((1.0 - problem.damping) * pref)
    dt = time.time() - t0
    print(f"[batch] {args.batch} personalized columns in one vmapped "
          f"solve: {batch.n_ops} ops, {batch.n_rounds} rounds, {dt:.2f}s "
          f"({args.batch/max(dt, 1e-9):.1f} rankings/s), "
          f"converged={batch.converged}")
    for c in range(min(3, args.batch)):
        top = np.argsort(-batch.x[:, c])[:3]
        print(f"  persona {c} (seed node {hot[c]}): top-3 {top.tolist()}")


def main():
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    argv = sys.argv[1:]
    if argv and argv[0] == "rank":
        argv = argv[1:]
    return rank_main(argv)


if __name__ == "__main__":
    main()
