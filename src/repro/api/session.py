"""Stateful solving: :class:`SolverSession` + the resumable drivers.

The paper's central object is the fluid pair ``(H, F)`` — the
accumulated history and the residual fluid.  The asynchronous-scheme
companion (arXiv:1202.6168) stresses that this *state* is what travels
between machines; here it is what travels between *solves*:

* ``run(until=...)`` — stream :class:`RoundReport`\\ s while draining F
  (the serving loop's progress feed).
* ``warm_start(b_new)`` — keep H, re-seed ``F = B' − (I−P)·H`` (the
  §2.2 residual identity ``X_exact − H = (I−P)^{-1} F`` applied to the
  new RHS).  A nearby B' leaves |F| tiny, so re-solving costs a small
  fraction of a cold solve — measured in edge-push ops, tested in
  tests/test_api.py.
* ``solve_batch(B)`` — multi-RHS personalized PageRank via a vmapped
  frontier loop (per-column thresholds and convergence masks) over the
  shared edge list.
* ``update_graph(delta)`` — keep (H, F), mutate P: the GraphStore
  patches its views incrementally and the fluid re-seeds via
  ``F' = F + (P'−P)·H`` (arXiv:1202.3108), so a churned graph
  re-solves warm rather than cold (DESIGN.md §7).

Drivers adapt one warm-startable backend each behind a tiny protocol
(``seed`` / ``advance`` / ``x`` / ``residual`` / ``ops`` / ``rounds``);
:mod:`repro.api.backends` reuses them for the one-shot ``solve()``
adapters so the streaming and batch paths are the *same* code the
registry runs.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Iterator, List, Optional, Tuple

import numpy as np

from repro.kernels.tune import resolved_config

from .options import SolverOptions
from .problem import Problem
from .report import RoundReport, SolveReport

__all__ = ["SolverSession"]


# --------------------------------------------------------------------------- #
# the batched multi-RHS kernel (shared by solve_batch and repro.serving)
# --------------------------------------------------------------------------- #
def _bucket_width(c: int, floor: int = 1) -> int:
    """Smallest power of two >= max(c, floor) — the XLA trace bucket.

    Batched solves pad their lane axis to this width so a drifting
    request count replays a compiled trace instead of re-tracing (the
    PR-6 kernel_bench pow2 trick applied to the lane axis)."""
    cp = max(int(floor), 1)
    while cp < c:
        cp *= 2
    return cp


def _edge_device_arrays(problem: Problem):
    """``(src, dst, wgt, w, dang)`` device arrays of the *damped* matrix
    ``problem.p`` — the one edge-list upload every batch path shares."""
    import jax.numpy as jnp

    p = problem.p
    src, dst, wgt = p.edge_list()
    return (jnp.asarray(src, dtype=jnp.int32),
            jnp.asarray(dst, dtype=jnp.int32),
            jnp.asarray(wgt),
            jnp.asarray(problem.node_weights()),
            jnp.asarray(p.dangling_mask()))


_BATCH_FNS: dict = {}


def _batch_fns() -> dict:
    """Process-wide jitted batch kernels, built once.

    The pre-PR-8 ``solve_batch`` closed over the edge arrays and called
    ``lax.while_loop`` through a *fresh* closure per call, so every
    invocation — even at an identical batch width — re-traced and
    re-compiled the loop.  These module-level jitted functions take the
    edge arrays as arguments instead: the jit cache is keyed on shapes
    alone, so a given ``[C_pad, N]`` bucket compiles exactly once per
    process and every later call at that bucket replays the trace.

    All lane-axis state is ``[C, N]`` (lane-major); per-lane reductions
    run over ``axis=1`` and lanes are fully independent — a zero-fluid
    lane selects nothing, pushes nothing, and leaves every other lane's
    arithmetic untouched, which is what makes pow2 zero-padding
    *bitwise* invisible to the real lanes (tested in
    tests/test_serving.py).

    ``solve`` runs to convergence; ``tick`` is the continuous-batching
    micro-step (bounded rounds, resumable); ``warm`` / ``place`` /
    ``clear`` are the lane-lifecycle helpers ``repro.serving`` uses to
    swap converged lanes for queued requests without re-tracing.
    """
    if _BATCH_FNS:
        return _BATCH_FNS
    import jax
    import jax.numpy as jnp

    def _round(f, h, t, ops, lane_rounds, tol_cols, src, dst, wgt, w,
               dang, gamma):
        n = f.shape[1]
        active = jnp.abs(f).sum(axis=1) > tol_cols  # [C]
        sel = ((jnp.abs(f) * w[None, :]) > t[:, None]) & active[:, None]
        sent = jnp.where(sel, f, 0.0)
        h = h + sent
        f = f - sent
        msg = jnp.take(sent, src, axis=1) * wgt[None, :]  # [C, L]
        seg = jax.vmap(
            lambda m: jax.ops.segment_sum(m, dst, num_segments=n))
        f = f + seg(msg)
        edge_active = jnp.take(sel, src, axis=1)  # [C, L]
        dops = jnp.sum(edge_active, axis=1).astype(jnp.int32)
        dops = dops + jnp.sum(
            sel & dang[None, :], axis=1).astype(jnp.int32)
        any_sel = jnp.any(sel, axis=1)
        t = jnp.where(any_sel | ~active, t, t / gamma)
        return (f, h, t, ops + dops,
                lane_rounds + active.astype(jnp.int32))

    def solve(f, h, t, ops, tol_cols, max_rounds, src, dst, wgt, w,
              dang, gamma):
        def cond(state):
            f, h, t, ops, lane_rounds, rounds = state
            return (jnp.any(jnp.abs(f).sum(axis=1) > tol_cols)
                    & (rounds < max_rounds))

        def body(state):
            f, h, t, ops, lane_rounds, rounds = state
            f, h, t, ops, lane_rounds = _round(
                f, h, t, ops, lane_rounds, tol_cols, src, dst, wgt, w,
                dang, gamma)
            return f, h, t, ops, lane_rounds, rounds + 1

        return jax.lax.while_loop(
            cond, body,
            (f, h, t, ops, jnp.zeros_like(ops),
             jnp.zeros((), jnp.int32)))

    def tick(f, h, t, ops, lane_rounds, tol_cols, budget, src, dst, wgt,
             w, dang, gamma):
        def cond(state):
            f, h, t, ops, lane_rounds, done = state
            return (jnp.any(jnp.abs(f).sum(axis=1) > tol_cols)
                    & (done < budget))

        def body(state):
            f, h, t, ops, lane_rounds, done = state
            f, h, t, ops, lane_rounds = _round(
                f, h, t, ops, lane_rounds, tol_cols, src, dst, wgt, w,
                dang, gamma)
            return f, h, t, ops, lane_rounds, done + 1

        return jax.lax.while_loop(
            cond, body,
            (f, h, t, ops, lane_rounds, jnp.zeros((), jnp.int32)))

    def warm(b_col, h_col, src, dst, wgt, w):
        # F' = B' − H + P·H (§2.2) for one lane, entirely on device
        ph = jax.ops.segment_sum(
            jnp.take(h_col, src) * wgt, dst,
            num_segments=b_col.shape[0])
        f_col = b_col - h_col + ph
        t_col = jnp.abs(f_col * w).max() * 2.0
        return f_col, t_col

    def place(f, h, t, ops, lane_rounds, lane, f_col, h_col, t_col):
        f = jax.lax.dynamic_update_slice_in_dim(
            f, f_col[None], lane, axis=0)
        h = jax.lax.dynamic_update_slice_in_dim(
            h, h_col[None], lane, axis=0)
        t = t.at[lane].set(t_col.astype(t.dtype))
        ops = ops.at[lane].set(0)
        lane_rounds = lane_rounds.at[lane].set(0)
        return f, h, t, ops, lane_rounds

    def clear(f, h, lane):
        zero = jnp.zeros((1, f.shape[1]), dtype=f.dtype)
        return (jax.lax.dynamic_update_slice_in_dim(f, zero, lane,
                                                    axis=0),
                jax.lax.dynamic_update_slice_in_dim(h, zero, lane,
                                                    axis=0))

    _BATCH_FNS.update(
        solve=jax.jit(solve), tick=jax.jit(tick), warm=jax.jit(warm),
        place=jax.jit(place), clear=jax.jit(clear))
    return _BATCH_FNS


# --------------------------------------------------------------------------- #
# frontier drivers (single-process jnp / Pallas)
# --------------------------------------------------------------------------- #
class _SegmentSumDriver:
    """frontier:segment_sum — per-edge gather→multiply→segment-sum rounds."""

    native_round = "frontier round"

    def __init__(self, problem: Problem, options: SolverOptions):
        g = problem.p
        self.n = g.n
        self.l = max(g.n_edges, 1)
        (self.src, self.dst, self.wgt, self.w,
         self.dang) = _edge_device_arrays(problem)
        self.gamma = options.gamma
        self._state = None

    def seed(self, f_nodes: np.ndarray,
             h_nodes: Optional[np.ndarray] = None) -> None:
        import jax.numpy as jnp

        f = jnp.asarray(f_nodes)
        h = jnp.zeros_like(f) if h_nodes is None else jnp.asarray(
            h_nodes, dtype=f.dtype)
        t = jnp.abs(f * self.w).max() * 2.0
        self._state = (f, h, t, jnp.zeros((), jnp.int32),
                       jnp.zeros((), jnp.int32))

    def warm_seed(self, b_new: np.ndarray) -> float:
        """Device-resident warm start: ``F' = B' − H + P·H`` without
        materializing H on the host.  The history stays where it lives;
        only the O(N) request payload ``b_new`` crosses to the device.
        Counters reset (new phase).  Returns |F'|_1 (scalar readback).
        """
        import jax
        import jax.numpy as jnp

        f_old, h, _t, _ops, _rounds = self._state
        b = jnp.asarray(b_new, dtype=h.dtype)
        ph = jax.ops.segment_sum(h[self.src] * self.wgt, self.dst,
                                 num_segments=self.n)
        f = b - h + ph
        t = jnp.abs(f * self.w).max() * 2.0
        self._state = (f, h, t, jnp.zeros((), jnp.int32),
                       jnp.zeros((), jnp.int32))
        return float(jnp.abs(f).sum())

    def advance(self, tol: float, round_limit: int) -> None:
        """Run until |F|_1 <= tol or the *total* round count hits the
        limit; resumable (identical round sequence to one long loop)."""
        import jax
        import jax.numpy as jnp

        from repro.core.diteration import frontier_step

        src, dst, wgt, w, dang, n, gamma = (
            self.src, self.dst, self.wgt, self.w, self.dang, self.n,
            self.gamma)

        def cond(state):
            f, h, t, ops, rounds = state
            return (jnp.abs(f).sum() > tol) & (rounds < round_limit)

        def body(state):
            f, h, t, ops, rounds = state
            f, h, t, dops = frontier_step(
                f, h, t, src, dst, wgt, w, dang, n, gamma)
            # dops may promote to int64 under jax_enable_x64; the carry
            # dtype must stay put or while_loop rejects the body
            return f, h, t, ops + dops.astype(ops.dtype), rounds + 1

        self._state = jax.lax.while_loop(cond, body, self._state)

    def x(self) -> np.ndarray:
        return np.asarray(self._state[1], dtype=np.float64)

    def residual(self) -> float:
        import jax.numpy as jnp

        return float(jnp.abs(self._state[0]).sum())

    def ops(self) -> int:
        return int(self._state[3])

    def rounds(self) -> int:
        return int(self._state[4])

    def exhausted(self) -> bool:
        return False

    def move_log(self) -> List[Tuple[int, int, int, int]]:
        return []

    # ---- checkpointable state (node space) --------------------------------
    def fluid(self) -> Tuple[np.ndarray, np.ndarray]:
        """(F, H) as float64 node-space vectors."""
        return (np.asarray(self._state[0], dtype=np.float64),
                np.asarray(self._state[1], dtype=np.float64))

    def threshold(self) -> np.ndarray:
        return np.asarray(self._state[2], dtype=np.float64)

    def set_threshold(self, t: np.ndarray) -> None:
        import jax.numpy as jnp

        t = np.asarray(t, dtype=np.float64).reshape(-1)
        if t.shape != (1,):
            return  # checkpointed at a different width (per-device T):
            # keep the re-derived threshold — any schedule is valid
        f, h, t_old, ops, rounds = self._state
        self._state = (f, h,
                       jnp.asarray(t[0], dtype=t_old.dtype).reshape(()),
                       ops, rounds)

    # ---- batched multi-RHS loop (vmap over columns) -----------------------
    def solve_batch(self, b_matrix: np.ndarray, tol: float,
                    max_rounds: int, pad: bool = True):
        """All columns at once: per-column thresholds + convergence masks.

        Converged columns stop diffusing (their frontier is masked), so
        ops accrue per column exactly as in the single-RHS loop.  The
        lane axis is padded to a pow2 bucket (:func:`_bucket_width`)
        with zero-RHS fill: a zero lane never selects and never pushes,
        so the real lanes are *bitwise* unaffected while XLA compiles
        once per bucket instead of once per batch width (``pad=False``
        keeps the exact width — the parity test's control arm).
        Returns ``(x [N, C], ops [C], rounds, res_cols, stats)``.
        """
        import jax.numpy as jnp

        c = b_matrix.shape[1]
        cp = _bucket_width(c) if pad else c
        b_t = jnp.asarray(np.ascontiguousarray(b_matrix.T))  # [C, N]
        if cp != c:
            f0 = jnp.zeros((cp, self.n), dtype=b_t.dtype).at[:c].set(b_t)
        else:
            f0 = b_t
        h0 = jnp.zeros_like(f0)
        t0 = jnp.abs(f0 * self.w[None, :]).max(axis=1) * 2.0  # [C_pad]
        tol_cols = jnp.full((cp,), tol, dtype=f0.dtype)
        f, h, t, ops, _lane_rounds, rounds = _batch_fns()["solve"](
            f0, h0, t0, jnp.zeros(cp, jnp.int32), tol_cols, max_rounds,
            self.src, self.dst, self.wgt, self.w, self.dang, self.gamma)
        res_cols = np.asarray(
            jnp.abs(f).sum(axis=1), dtype=np.float64)[:c]
        stats = {"bucket": cp,
                 "padding_waste": float((cp - c) / cp)}
        return (np.asarray(h.T, dtype=np.float64)[:, :c],
                np.asarray(ops)[:c], int(rounds), res_cols, stats)


_BSR_ADVANCE: dict = {}


def _bsr_advance():
    """The jitted ``frontier:pallas`` loop, built once per process.

    The BSR operand, the tolerance and the round limit are arguments, so
    every resumed ``advance`` (one per ``trace_every`` rounds) replays
    one compiled program instead of tracing and compiling a new loop.
    """
    if _BSR_ADVANCE:
        return _BSR_ADVANCE["fn"]
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.diffusion import frontier_round_bsr

    @functools.partial(jax.jit, static_argnames=(
        "gamma", "backend", "interpret", "buffer_depth",
        "occupancy_threshold"))
    def advance(state, m, w, out_deg, dang, tol, round_limit, *, gamma,
                backend, interpret, buffer_depth, occupancy_threshold):
        def cond(state):
            f, res, h, t, ops, rounds = state
            return (res > tol) & (rounds < round_limit)

        def body(state):
            f, _res, h, t, ops, rounds = state
            f_new, sent, res = frontier_round_bsr(
                m, f, w, t, backend=backend, interpret=interpret,
                buffer_depth=buffer_depth,
                occupancy_threshold=occupancy_threshold)
            # the op's threshold predicate is authoritative (the pallas
            # backend folds t into the weights); sel follows the sent fluid
            sel = sent != 0
            dops = jnp.sum(jnp.where(sel, out_deg, 0))
            dops = dops + jnp.sum((sel & dang).astype(jnp.int32))
            t_new = jnp.where(jnp.any(sel), t, t / gamma)
            return (f_new, res, h + sent, t_new,
                    ops + dops.astype(ops.dtype), rounds + 1)

        return jax.lax.while_loop(cond, body, state)

    _BSR_ADVANCE["fn"] = advance
    return advance


class _BsrFrontierDriver:
    """frontier:pallas — fused BSR frontier rounds (jnp oracle off-TPU)."""

    native_round = "frontier round"

    def __init__(self, problem: Problem, options: SolverOptions):
        import jax.numpy as jnp

        g = problem.p
        self.n = g.n
        self.l = max(g.n_edges, 1)
        # kernel config: explicit options > platform tuned record > defaults
        bs, self.buffer_depth, self.occupancy_threshold = resolved_config(
            "frontier_round_bsr",
            bs=options.bs,
            buffer_depth=options.buffer_depth,
            occupancy_threshold=options.occupancy_threshold,
        )
        # the store's cached BSR view: graph deltas patch dirty tiles
        # in place, so a post-update rebuild re-uploads — not re-tiles
        self.m = problem.graph.bsr(bs=bs).to_device()
        n_pad = self.m.n_row_blocks * bs
        dt = self.m.blocks.dtype
        # device edge list for the device-resident warm start (P·H via
        # segment_sum — the BSR pool only exposes fused rounds)
        src, dst, wgt = g.edge_list()
        self._src_d = jnp.asarray(src, dtype=jnp.int32)
        self._dst_d = jnp.asarray(dst, dtype=jnp.int32)
        self._wgt_d = jnp.asarray(wgt, dtype=dt)
        pad = lambda v, t: jnp.zeros(n_pad, dtype=t).at[: g.n].set(
            jnp.asarray(v, dtype=t))
        self.w = pad(problem.node_weights(), dt)
        self.out_deg = pad(g.out_degree(), jnp.int32)
        self.dang = pad(g.dangling_mask(), bool)
        self.gamma = options.gamma
        self.interpret = options.interpret
        # interpret forces the real kernel; otherwise auto (pallas on
        # TPU, jnp block oracle elsewhere) — same rule as the legacy path
        self.op_backend = "pallas" if options.interpret else None
        self._n_pad = n_pad
        self._dt = dt
        self._state = None

    def seed(self, f_nodes: np.ndarray,
             h_nodes: Optional[np.ndarray] = None) -> None:
        import jax.numpy as jnp

        f = jnp.zeros(self._n_pad, dtype=self._dt).at[: self.n].set(
            jnp.asarray(f_nodes, dtype=self._dt))
        h = jnp.zeros_like(f)
        if h_nodes is not None:
            h = h.at[: self.n].set(jnp.asarray(h_nodes, dtype=self._dt))
        t = jnp.abs(f * self.w).max() * 2.0
        self._state = (f, jnp.abs(f).sum(), h, t,
                       jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))

    def warm_seed(self, b_new: np.ndarray) -> float:
        """Device-resident warm start over the padded state (see
        :meth:`_SegmentSumDriver.warm_seed`)."""
        import jax
        import jax.numpy as jnp

        f_old, _res, h, _t, _ops, _rounds = self._state
        h_n = h[: self.n]
        b = jnp.asarray(b_new, dtype=self._dt)
        ph = jax.ops.segment_sum(h_n[self._src_d] * self._wgt_d,
                                 self._dst_d, num_segments=self.n)
        f_n = b - h_n + ph
        f = jnp.zeros(self._n_pad, dtype=self._dt).at[: self.n].set(f_n)
        res = jnp.abs(f).sum()
        t = jnp.abs(f * self.w).max() * 2.0
        self._state = (f, res, h, t, jnp.zeros((), jnp.int32),
                       jnp.zeros((), jnp.int32))
        return float(res)

    def advance(self, tol: float, round_limit: int) -> None:
        self._state = _bsr_advance()(
            self._state, self.m, self.w, self.out_deg, self.dang, tol,
            round_limit, gamma=self.gamma, backend=self.op_backend,
            interpret=self.interpret or None,
            buffer_depth=self.buffer_depth,
            occupancy_threshold=self.occupancy_threshold)

    def x(self) -> np.ndarray:
        return np.asarray(self._state[2][: self.n], dtype=np.float64)

    def residual(self) -> float:
        return float(self._state[1])

    def ops(self) -> int:
        return int(self._state[4])

    def rounds(self) -> int:
        return int(self._state[5])

    def exhausted(self) -> bool:
        return False

    def move_log(self) -> List[Tuple[int, int, int, int]]:
        return []

    # ---- checkpointable state (node space) --------------------------------
    def fluid(self) -> Tuple[np.ndarray, np.ndarray]:
        return (np.asarray(self._state[0][: self.n], dtype=np.float64),
                np.asarray(self._state[2][: self.n], dtype=np.float64))

    def threshold(self) -> np.ndarray:
        return np.asarray(self._state[3], dtype=np.float64)

    def set_threshold(self, t: np.ndarray) -> None:
        import jax.numpy as jnp

        t = np.asarray(t, dtype=np.float64).reshape(-1)
        if t.shape != (1,):
            return  # cross-width checkpoint: keep the re-derived T
        f, res, h, t_old, ops, rounds = self._state
        self._state = (f, res, h,
                       jnp.asarray(t[0], dtype=t_old.dtype).reshape(()),
                       ops, rounds)


# --------------------------------------------------------------------------- #
# engine driver (shard_map production solver, chunk-granular)
# --------------------------------------------------------------------------- #
def _bsr_buckets_per_dev(n: int, k: int, options: SolverOptions) -> int:
    """BSR tiles are dense [S, S] blocks: cap the bucket size (≤ 512)
    so the tile pool stays MXU-shaped instead of ballooning to
    [R, T, N/K, N/K] on big problems.  Auto-sizing only ever *raises*
    the bucket count the caller configured.  One rule shared by driver
    construction and mid-solve rescale, so a rescaled engine's layout
    always matches what a cold start at the same k would build."""
    max_s = 512
    real_needed = -(-n // (k * max_s))  # ceil
    return max(options.buckets_per_dev, real_needed + options.headroom)


class _EngineDriver:
    """engine:chunk / engine:bsr — the distributed engine, one jitted
    chunk per advance, with the balance control plane between chunks."""

    native_round = "engine round"

    def __init__(self, problem: Problem, options: SolverOptions,
                 diffusion_backend: str):
        import jax
        import jax.numpy as jnp

        from repro.core.distributed import (
            DistributedEngine,
            EngineConfig,
            build_engine_arrays,
        )

        if problem.weights is not None or problem.weight_mode != "inv_out":
            raise ValueError(
                "engine backends run the default inv_out selection "
                "weights; custom Problem.weights cannot be honored"
            )
        k = options.k or 1
        n_dev = len(jax.devices())
        if k > n_dev:
            raise ValueError(
                f"engine backends need k physical devices: k={k} > "
                f"{n_dev} available (use method='simulator' for virtual "
                "PIDs)"
            )
        buckets_per_dev = (_bsr_buckets_per_dev(problem.n, k, options)
                           if diffusion_backend == "bsr"
                           else options.buckets_per_dev)
        self.cfg = EngineConfig(
            k=k,
            target_error=problem.target_error,
            eps=problem.eps,
            buckets_per_dev=buckets_per_dev,
            headroom=options.headroom,
            max_inner=options.max_inner,
            gamma=options.gamma,
            dynamic=options.dynamic,
            policy=options.policy,
            signal=options.signal,
            eta=options.eta,
            z=options.z,
            chunk_rounds=options.chunk_rounds,
            max_chunks=options.max_chunks,
            dtype=options.dtype or jnp.float32,
            diffusion_backend=diffusion_backend,
            pallas_interpret=options.interpret,
            # explicit option > platform tuned record > default depth 1
            pallas_buffer_depth=resolved_config(
                "bsr_gather_spmm", buffer_depth=options.buffer_depth
            )[1],
        )
        # the store's cached engine-layout view: graph deltas patch
        # dirty bucket rows / tiles in place before we land here
        self.arrays = build_engine_arrays(problem.graph, problem.b,
                                          self.cfg)
        self.engine = DistributedEngine(self.arrays, self.cfg)
        self.problem = problem
        self.options = options
        self.l = max(problem.n_edges, 1)
        self._seeded = False

    def seed(self, f_nodes: np.ndarray,
             h_nodes: Optional[np.ndarray] = None) -> None:
        from repro.balance.executors import BucketMoveExecutor

        # fresh policy state per solve phase: a warm start is a new
        # convergence trajectory, stale EMA slopes would misfire
        self._fresh_rebalancer()
        self.ex = BucketMoveExecutor(
            self.engine, self.engine.init_state(f_nodes, h_nodes))
        self._resid = float(np.abs(np.asarray(f_nodes)).sum())
        self._chunks = 0
        self._moves: List[Tuple[int, int, int, int]] = []
        self._prev_ops = np.zeros(self.cfg.k, dtype=np.int64)
        # rescale carry-over: a rescale re-inits the sharded counters at
        # the new width, so phase totals accumulate into host offsets
        self._ops_offset = 0
        self._rounds_offset = 0
        self._warm_maps = None  # (arrays-identity, layout-bytes, maps)
        self._seeded = True

    def _fresh_rebalancer(self) -> None:
        from repro.balance.policies import make_rebalancer

        if self.engine.rebalancer is not None:
            self.engine.rebalancer = make_rebalancer(
                self.cfg.policy or "slope_ema", k=self.cfg.k,
                target_error=self.cfg.target_error, eta=self.cfg.eta,
                z=self.cfg.z, unit="bucket",
            )

    def warm_seed(self, b_new: np.ndarray) -> float:
        """Device-resident warm start over the sharded bucket layout.

        The history H never leaves the devices: the bucketed [R, S]
        state is permuted home-layout-wise, flattened to node space,
        run through ``P·H`` (device segment_sum over a cached device
        edge list), and the re-seeded ``F' = B' − H + P·H`` scattered
        back — all jnp ops.  Only ``b_new`` (the request payload) is
        uploaded and only the scalar |F'|_1 is read back.  Index maps
        are cached per (arrays, bucket-layout) and rebuilt when a
        rescale or bucket move changes either.  Counters reset; the
        rebalancer restarts fresh (new convergence trajectory).
        """
        import jax
        import jax.numpy as jnp

        a, cfg, eng, ex = self.arrays, self.cfg, self.engine, self.ex
        r_rows, s_slots = a.n_rows, a.bucket_size
        rob = np.asarray(ex.row_of_bucket)
        cache = self._warm_maps
        if (cache is None or cache[0] is not a
                or cache[1] != rob.tobytes()):
            src, dst, wgt = self.problem.p.edge_list()
            # cur_of_home[home row] = row currently holding that bucket
            cur = np.empty(r_rows, dtype=np.int64)
            cur[np.asarray(a.pos_of_bucket, dtype=np.int64)] = rob
            inv = np.empty(r_rows, dtype=np.int64)
            inv[cur] = np.arange(r_rows)
            nos = a.node_of_slot  # [R, S], home-row indexed
            valid = nos >= 0
            flat_slot = (np.arange(r_rows)[:, None] * s_slots
                         + np.arange(s_slots)[None, :])[valid]
            maps = {
                "src": jnp.asarray(src, jnp.int32),
                "dst": jnp.asarray(dst, jnp.int32),
                "wgt": jnp.asarray(wgt, cfg.dtype),
                "perm": jnp.asarray(cur, jnp.int32),
                "inv": jnp.asarray(inv, jnp.int32),
                "flat_slot": jnp.asarray(flat_slot, jnp.int32),
                "node_ids": jnp.asarray(nos[valid], jnp.int32),
                "w_home": jnp.asarray(a.w, cfg.dtype).reshape(
                    r_rows, s_slots),
            }
            self._warm_maps = (a, rob.tobytes(), maps)
        maps = self._warm_maps[2]
        st = ex.state
        h_rows = st.h.reshape(r_rows, s_slots)
        h_home = jnp.take(h_rows, maps["perm"], axis=0)
        h_node = jnp.zeros(a.n, cfg.dtype).at[maps["node_ids"]].set(
            h_home.reshape(-1)[maps["flat_slot"]])
        b_dev = jnp.asarray(np.asarray(b_new), cfg.dtype)
        ph = jax.ops.segment_sum(h_node[maps["src"]] * maps["wgt"],
                                 maps["dst"], num_segments=a.n)
        f_node = b_dev - h_node + ph
        f_home = jnp.zeros(r_rows * s_slots, cfg.dtype).at[
            maps["flat_slot"]].set(f_node[maps["node_ids"]]
                                   ).reshape(r_rows, s_slots)
        fw_cur = jnp.take(jnp.abs(f_home) * maps["w_home"], maps["inv"],
                          axis=0)
        t0 = fw_cur.reshape(cfg.k, -1).max(axis=1) * 2.0 + 1e-30
        f_cur = jnp.take(f_home, maps["inv"], axis=0)
        put_row = lambda x: jax.device_put(x, eng.row_sharding)
        ex.state = dataclasses.replace(
            st,
            f=put_row(f_cur.reshape(st.f.shape).astype(cfg.dtype)),
            outbox=put_row(jnp.zeros_like(st.outbox)),
            t=put_row(t0.astype(cfg.dtype)),
            ops=put_row(jnp.zeros(cfg.k, dtype=jnp.int32)),
            rounds=jax.device_put(jnp.zeros((), dtype=jnp.int32),
                                  eng.rep_sharding),
        )
        self._fresh_rebalancer()
        self._resid = float(jnp.abs(f_node).sum())
        self._chunks = 0
        self._moves = []
        self._prev_ops = np.zeros(cfg.k, dtype=np.int64)
        self._ops_offset = 0
        self._rounds_offset = 0
        return self._resid

    def advance(self, tol: float, round_limit: int) -> None:
        """One jitted chunk + one control-plane pass (engine grain)."""
        eng, ex = self.engine, self.ex
        ex.state, stats = eng._chunk(ex.state, *ex.chunk_operands())
        r = np.asarray(stats["r"])
        s_ = np.asarray(stats["s"])
        self._resid = float(np.asarray(stats["residual"])) + float(s_.sum())
        self._chunks += 1
        if self._resid <= tol:
            return
        self._prev_ops = eng.apply_control_plane(
            ex, r, s_, self._chunks, self._prev_ops, self._moves)

    def x(self) -> np.ndarray:
        return self.engine.extract_solution(self.ex.state,
                                            self.ex.row_of_bucket)

    def residual(self) -> float:
        return self._resid

    def ops(self) -> int:
        return self._ops_offset + int(
            np.asarray(self.ex.state.ops).astype(np.int64).sum())

    def rounds(self) -> int:
        return self._rounds_offset + int(np.asarray(self.ex.state.rounds))

    def exhausted(self) -> bool:
        return self._chunks >= self.cfg.max_chunks

    def move_log(self) -> List[Tuple[int, int, int, int]]:
        return list(self._moves)

    # ---- checkpointable state (node space) --------------------------------
    def fluid(self) -> Tuple[np.ndarray, np.ndarray]:
        return (self.engine.gather_nodes(self.ex.state.f,
                                         self.ex.row_of_bucket),
                self.engine.gather_nodes(self.ex.state.h,
                                         self.ex.row_of_bucket))

    def threshold(self) -> np.ndarray:
        return np.asarray(self.ex.state.t, dtype=np.float64)

    def set_threshold(self, t: np.ndarray) -> None:
        import jax

        t = np.asarray(t, dtype=np.float64).reshape(-1)
        if t.shape != (self.cfg.k,):
            return  # checkpointed at a different width: keep the
            # re-derived thresholds (any schedule is a valid D-iteration)
        self.ex.state = dataclasses.replace(
            self.ex.state,
            t=jax.device_put(t.astype(self.cfg.dtype),
                             self.engine.row_sharding))

    # ---- elasticity -------------------------------------------------------
    def note_straggler(self, pid: int, slowdown: float) -> None:
        """Signal-level straggler injection: the control plane sees the
        PID's load inflated by ``slowdown`` (a real straggling device
        cannot be slowed from the host, but the controller's view can —
        it then sheds buckets exactly as in production)."""
        scale = (self.engine.load_scale if self.engine.load_scale
                 is not None else np.ones(self.cfg.k))
        scale = np.asarray(scale, dtype=np.float64).copy()
        scale[pid] = slowdown
        self.engine.load_scale = scale

    def rescale(self, k_new: int,
                strict: bool = False) -> List[Tuple[int, int, int]]:
        """Grow/shrink the pid axis mid-solve (H and F travel in node
        space; shrink drains through the BucketMoveExecutor path when
        the surviving headroom can absorb it).  Returns the executed
        drain triples; they are also appended to the move log as
        ``(chunk, src, dst, units)``."""
        if k_new == self.cfg.k:
            return []
        prev_ops, prev_rounds = self.ops(), self.rounds()
        bpd = (_bsr_buckets_per_dev(self.problem.n, k_new, self.options)
               if self.cfg.diffusion_backend == "bsr"
               else self.options.buckets_per_dev)
        old_scale = self.engine.load_scale
        self.engine, self.ex, drains = self.engine.rescale(
            self.ex, k_new, self.problem.graph, self.problem.b,
            buckets_per_dev=bpd, strict=strict)
        if old_scale is not None:
            # surviving stragglers stay stragglers across the re-mesh;
            # dropped/grown slots are fresh (healthy) capacity
            scale = np.ones(k_new, dtype=np.float64)
            m = min(k_new, old_scale.shape[0])
            scale[:m] = old_scale[:m]
            self.engine.load_scale = scale
        self.cfg = self.engine.cfg
        self.arrays = self.engine.a
        for src, dst, moved in drains:
            self._moves.append((self._chunks, src, dst, moved))
        self._prev_ops = np.zeros(k_new, dtype=np.int64)
        self._ops_offset = prev_ops
        self._rounds_offset = prev_rounds
        return drains

    def note_graph_churn(self, churn_per_node: np.ndarray) -> None:
        """Feed edge churn to the balance control plane.

        The paper's thesis applied to graph drift: the controller needs
        only a per-PID load magnitude, never the structure.  Per-node
        changed-edge counts are mapped onto owning devices through the
        current bucket layout and run through one rebalancer pass as a
        ``graph-churn`` :class:`LoadSignal`, so a device absorbing the
        churn can shed buckets *before* the delta re-solve starts.
        """
        from repro.balance.signals import LoadSignal

        eng = self.engine
        if eng.rebalancer is None:
            return
        a = eng.a
        churn = np.asarray(churn_per_node, dtype=np.int64)
        valid = a.node_of_slot >= 0
        row_churn = np.zeros(a.n_rows, dtype=np.int64)
        rows = np.broadcast_to(
            np.arange(a.n_rows)[:, None], a.node_of_slot.shape)
        np.add.at(row_churn, rows[valid], churn[a.node_of_slot[valid]])
        b_loc = self.cfg.buckets_per_dev
        dev_churn = np.zeros(self.cfg.k, dtype=np.float64)
        for bid in range(a.n_rows):
            home = int(a.pos_of_bucket[bid])  # node map lives at home row
            cur = int(self.ex.row_of_bucket[bid])  # data owner now
            dev_churn[cur // b_loc] += row_churn[home]
        if dev_churn.sum() == 0:
            return
        sig = LoadSignal.from_graph_churn(dev_churn, self.ex.sizes(),
                                          step=self._chunks)
        for plan in eng.rebalancer.propose(sig):
            moved = self.ex.apply(plan)
            if moved:
                self._moves.append((self._chunks, plan.src, plan.dst,
                                    moved))


_DRIVERS = {
    "frontier:segment_sum": lambda p, o: _SegmentSumDriver(p, o),
    "frontier:pallas": lambda p, o: _BsrFrontierDriver(p, o),
    "engine:chunk": lambda p, o: _EngineDriver(p, o, "segment_sum"),
    "engine:bsr": lambda p, o: _EngineDriver(p, o, "bsr"),
}


def _invariant_violation(problem: Problem, b: np.ndarray, h: np.ndarray,
                         f: np.ndarray, edges=None) -> float:
    """|B − (I−P)·H − F|_1 against the problem's *current* matrix.

    Zero (up to accumulation rounding) along any valid D-iteration
    schedule — the checkpoint-integrity oracle: a torn write, a
    corrupted leaf, or a checkpoint taken against a different P all
    violate it by a macroscopic margin.  ``edges`` optionally supplies
    a pre-materialized ``(src, dst, w)`` edge list so repeated checks
    (restore's candidate walk) pay the O(L) materialization once.
    """
    src, dst, w = edges if edges is not None else problem.p.edge_list()
    ph = np.bincount(dst, weights=h[src] * w, minlength=problem.n)
    return float(np.abs(b - h + ph - f).sum())


# --------------------------------------------------------------------------- #
# the session
# --------------------------------------------------------------------------- #
class SolverSession:
    """A long-lived solver owning the (H, F) fluid state of one Problem.

    ``method`` must be a warm-startable registry backend
    (``frontier:segment_sum``, ``frontier:pallas``, ``engine:chunk``,
    ``engine:bsr`` — see ``repro.api.list_backends()``).  The session
    seeds ``F = B, H = 0`` on construction; ``warm_start`` re-seeds F
    for a new RHS while keeping H, resetting the per-phase op/round
    counters so reports measure the *current* solve.
    """

    def __init__(self, problem: Problem,
                 method: str = "frontier:segment_sum",
                 options: Optional[SolverOptions] = None, **kw):
        from .registry import get_backend

        be = get_backend(method)
        if not be.caps.supports_warm_start:
            raise ValueError(
                f"backend {method!r} is one-shot; SolverSession needs a "
                "warm-startable backend "
                "(frontier:segment_sum | frontier:pallas | engine:chunk "
                "| engine:bsr)"
            )
        opts = options if options is not None else SolverOptions()
        if kw:
            opts = dataclasses.replace(opts, **kw)
        self.options = opts.validated(be.caps, method)
        self.problem = problem
        self.method = method
        self._driver = _DRIVERS[method](problem, self.options)
        self._driver.seed(problem.b)
        self._b = np.asarray(problem.b, dtype=np.float64)
        # cached once: warm_start re-derives P·H per serving request and
        # must not pay the O(L) edge-list materialization every time
        self._edges = problem.p.edge_list()
        self._ckpt_step = 0
        self.restored_from: Optional[dict] = None
        # lifetime §2.3 accounting: phase counters reset on every
        # warm_start / update_graph, so re-seeds bank them here first —
        # ``lifetime_ops`` is THE one rule recovery-cost consumers sum
        self._ops_banked = 0
        self._rounds_banked = 0

    # ---- state views ------------------------------------------------------
    @property
    def x(self) -> np.ndarray:
        """Current solution estimate H (node space, float64)."""
        return self._driver.x()

    @property
    def residual(self) -> float:
        return self._driver.residual()

    @property
    def n_ops(self) -> int:
        """Edge pushes charged in the current solve phase (§2.3)."""
        return self._driver.ops()

    @property
    def n_rounds(self) -> int:
        return self._driver.rounds()

    @property
    def lifetime_ops(self) -> int:
        """Edge pushes charged across the session's whole life (§2.3):
        every solve phase since construction or restore, including work
        banked by warm_start / update_graph re-seeds."""
        return self._ops_banked + self._driver.ops()

    @property
    def lifetime_rounds(self) -> int:
        return self._rounds_banked + self._driver.rounds()

    def _bank_phase(self) -> None:
        """Fold the current phase counters into the lifetime totals —
        call ONLY immediately before a re-seed resets them."""
        self._ops_banked += self._driver.ops()
        self._rounds_banked += self._driver.rounds()

    def _tol(self, until: Optional[float]) -> float:
        te = until if until is not None else self.problem.target_error
        return te * self.problem.eps

    def _check_fresh(self) -> None:
        """Refuse to run over a stale graph snapshot.

        Views are patched IN PLACE by ``GraphStore.apply_delta``, so a
        second session sharing the store would otherwise mix patched
        device arrays with its pre-delta (P, B, H) state.  Touching
        ``problem.graph`` raises the store-version mismatch; sessions
        that never materialized a store skip the check (nothing
        shared, nothing to go stale).
        """
        if self.problem.store is not None:
            self.problem.graph

    # ---- streaming solve --------------------------------------------------
    def run(self, until: Optional[float] = None,
            max_rounds: Optional[int] = None,
            chaos=None) -> Iterator[RoundReport]:
        """Drain F toward ``until`` (a target_error), streaming one
        :class:`RoundReport` per trace grain (``options.trace_every``
        frontier rounds / one engine chunk).  The final yielded report
        is the converged (or budget-exhausted) state.

        ``chaos`` is an optional :class:`repro.chaos.SessionInjector`:
        its plan's events fire *before* each grain (rounds = grain
        indices, starting at 1).  A ``kill`` event raises
        :class:`repro.chaos.ChaosKill` — recovery is the caller's
        restore + rescale flow (DESIGN.md §8)."""
        self._check_fresh()
        if chaos is not None:
            chaos.bind(self)
        tol = self._tol(until)
        cap = max_rounds if max_rounds is not None else (
            self.options.max_rounds)
        while True:
            d = self._driver
            if d.residual() <= tol or d.rounds() >= cap or d.exhausted():
                yield RoundReport(d.rounds(), d.residual(), d.ops())
                return
            if chaos is not None:
                chaos.before_grain(self)
                d = self._driver  # chaos may have rebuilt the driver
            if isinstance(d, _EngineDriver):
                d.advance(tol, cap)
            else:
                d.advance(tol, min(d.rounds() + self.options.trace_every,
                                   cap))
            yield RoundReport(d.rounds(), d.residual(), d.ops())

    def solve(self, until: Optional[float] = None,
              max_rounds: Optional[int] = None,
              chaos=None) -> SolveReport:
        """Run to convergence and return the unified report."""
        t0 = time.perf_counter()
        trace = list(self.run(until=until, max_rounds=max_rounds,
                              chaos=chaos))
        d = self._driver
        return SolveReport(
            x=d.x(),
            residual=d.residual(),
            n_ops=d.ops(),
            cost_iterations=d.ops() / d.l,
            n_rounds=d.rounds(),
            converged=d.residual() <= self._tol(until),
            method=self.method,
            trace=trace,
            move_log=d.move_log(),
            wall_time_s=time.perf_counter() - t0,
            extras={"session": True},
        )

    # ---- warm start (§2.2 residual identity) ------------------------------
    def warm_start(self, b_new: np.ndarray) -> float:
        """Re-seed for a new RHS, reusing the accumulated history H.

        ``F' = B' − (I−P)·H = B' − H + P·H`` — exactly the residual the
        old H leaves against the new system, so |F'| (returned) is small
        whenever B' is near the RHS H was built for, and the follow-up
        ``run``/``solve`` charges correspondingly few edge pushes.
        Phase counters (ops, rounds, trace) reset to zero after banking
        into the lifetime totals.

        The serving hot path: drivers exposing ``warm_seed`` re-seed
        entirely on device (H and F never round-trip through host
        numpy; only ``b_new`` is uploaded and the scalar |F'|_1 read
        back) — all four warm-startable backends do.
        """
        self._check_fresh()
        b_new = np.asarray(b_new, dtype=np.float64)
        if b_new.shape != (self.problem.n,):
            raise ValueError(
                f"b_new has shape {b_new.shape}, expected "
                f"({self.problem.n},)"
            )
        self._bank_phase()
        warm_seed = getattr(self._driver, "warm_seed", None)
        if warm_seed is not None:
            resid = warm_seed(b_new)
            self._b = b_new
            self.problem = self.problem.with_b(b_new)
            return resid
        h = self._driver.x()
        src, dst, w = self._edges
        ph = np.bincount(dst, weights=h[src] * w, minlength=self.problem.n)
        f_new = b_new - h + ph
        self._driver.seed(f_new, h)
        self._b = b_new
        self.problem = self.problem.with_b(b_new)
        return float(np.abs(f_new).sum())

    # ---- graph delta (the F' = F + (P'−P)·H update) -----------------------
    def update_graph(self, delta) -> float:
        """Apply edge churn to the Problem's GraphStore and re-seed warm.

        The asynchronous-scheme companions (arXiv:1202.3108,
        arXiv:1301.3007) show the fluid pair survives matrix drift:
        ``F' = F + (P'−P)·H``.  We evaluate it through the invariant
        ``F = B − (I−P)·H`` — i.e. ``F' = B − H + P'·H`` over the
        *patched* matrix — so the cost is the store's dirty-view patch
        plus one O(L) product, and the follow-up ``run``/``solve``
        drains only the churn-injected fluid instead of restarting
        cold.  Routed through :meth:`Problem.with_graph`; on engine
        backends the churn also feeds the balance control plane as a
        ``graph-churn`` LoadSignal.  Phase counters reset (banked into
        the lifetime totals); returns ``|F'|_1``.

        **Transactional**: a malformed delta is rejected before any
        mutation (the CSR splice validates first, and the inverse delta
        is captured up front — both raise with the session untouched);
        a failure *after* the store mutated (view patch, driver
        rebuild, re-seed) rolls the store back via the inverse delta
        and re-seeds the old state over a fresh driver, so the next
        request serves the pre-delta graph instead of a half-patched
        one.  The original exception re-raises either way.
        """
        from repro.graph import GraphDelta, invert_delta

        if not isinstance(delta, GraphDelta):
            raise TypeError(
                f"update_graph wants a GraphDelta, got "
                f"{type(delta).__name__}"
            )
        h = self._driver.x()
        if delta.is_empty:
            return self._driver.residual()
        store = self.problem.graph
        # rollback token; also pre-validates that every removed /
        # reweighted edge exists (raises BEFORE any mutation)
        inverse = invert_delta(store, delta)
        self._bank_phase()
        applied = False
        try:
            store.apply_delta(delta)  # patches every materialized view
            applied = True
            self.problem = self.problem.with_graph(store)
            src, dst, w = self.problem.p.edge_list()
            self._edges = (src, dst, w)
            ph = np.bincount(dst, weights=h[src] * w,
                             minlength=self.problem.n)
            f_new = self._b - h + ph
            # fresh driver over the PATCHED views (cache hits inside the
            # store: tiles/buckets/rows were spliced, not rebuilt)
            self._driver = _DRIVERS[self.method](self.problem,
                                                 self.options)
            self._driver.seed(f_new, h)
        except Exception:
            if applied:
                store.apply_delta(inverse)
            # even a failed apply_delta may have partially patched a
            # view the old driver captured (the store rolls its CSR
            # back and drops the view cache) — rebuild the driver over
            # the restored store and re-seed the held (H, F) via the
            # same invariant identity F = B − H + P·H
            self.problem = self.problem.with_graph(store)
            src, dst, w = self.problem.p.edge_list()
            self._edges = (src, dst, w)
            ph = np.bincount(dst, weights=h[src] * w,
                             minlength=self.problem.n)
            self._driver = _DRIVERS[self.method](self.problem,
                                                 self.options)
            self._driver.seed(self._b - h + ph, h)
            self._batch_driver = None
            raise
        if isinstance(self._driver, _EngineDriver):
            self._driver.note_graph_churn(
                delta.churn_per_node(self.problem.n))
        self._batch_driver = None  # edge list went stale
        return float(np.abs(f_new).sum())

    # ---- elasticity: mid-solve PID rescale --------------------------------
    def rescale(self, k_new: int,
                strict: bool = False) -> List[Tuple[int, int, int]]:
        """Grow/shrink the engine's ``pid`` axis mid-solve.

        Shrink drains the dying devices' buckets through the existing
        :class:`~repro.balance.executors.BucketMoveExecutor` path
        (survivors' headroom rows absorb the moves, logged in the move
        log; with insufficient headroom the drain is skipped — or
        raises under ``strict=True``), then the axis re-meshes at
        ``k_new`` over the store's cached engine-layout view; grow
        re-meshes directly and the rebalancer spreads any residual
        skew.  The accumulated (H, F) fluid pair travels in node space
        — H is never recomputed.  Returns the executed drain triples
        ``(src, dst, units)``.
        """
        self._check_fresh()
        d = self._driver
        if not isinstance(d, _EngineDriver):
            raise ValueError(
                f"rescale needs an engine backend (engine:chunk | "
                f"engine:bsr); {self.method!r} has no pid axis"
            )
        d.problem = self.problem  # warm starts may have re-snapshotted
        drains = d.rescale(k_new, strict=strict)
        self.options = dataclasses.replace(self.options, k=k_new)
        return drains

    # ---- fault tolerance: atomic checkpoint / verified restore ------------
    def checkpoint(self, root: str) -> str:
        """Persist the session's fluid state under ``root`` atomically.

        One step directory per call (monotonic step counter, atomic
        ``os.replace`` commit via :mod:`repro.checkpoint.store`):
        node-space ``(B, F, H)`` + thresholds as array leaves, plus a
        manifest extra carrying method, counters, the move log, and the
        GraphStore version the state was built against.  Returns the
        committed directory path.
        """
        from repro.checkpoint import save_checkpoint

        self._check_fresh()
        d = self._driver
        f, h = d.fluid()
        self._ckpt_step += 1
        tree = {"b": self._b, "f": f, "h": h, "t": d.threshold()}
        extra = {
            "method": self.method,
            "n": self.problem.n,
            "n_edges": self.problem.n_edges,
            "store_version": self.problem.store_version,
            "ops": d.ops(),
            "rounds": d.rounds(),
            "lifetime_ops": self.lifetime_ops,
            "lifetime_rounds": self.lifetime_rounds,
            "residual": d.residual(),
            "move_log": [list(m) for m in d.move_log()],
        }
        return save_checkpoint(root, self._ckpt_step, tree, extra)

    @staticmethod
    def _reject_reason(problem: Problem, b: np.ndarray, f: np.ndarray,
                       h: np.ndarray, extra: dict, rtol: float,
                       edges=None) -> Optional[str]:
        """Why a loaded checkpoint cannot resume against ``problem``
        (None = accept).  The decisive oracle is the §2.2 invariant
        ``B = (I−P)H + F`` evaluated against the problem's CURRENT
        matrix: a torn/corrupted leaf or a checkpoint taken before a
        graph delta both violate it macroscopically."""
        if b.shape != (problem.n,):
            return (f"shape mismatch: checkpoint N={b.shape[0]}, "
                    f"problem N={problem.n}")
        if extra.get("n") not in (None, problem.n):
            return f"stale: checkpoint N={extra['n']} != {problem.n}"
        if extra.get("n_edges") not in (None, problem.n_edges):
            return (f"stale: checkpoint graph had {extra['n_edges']} "
                    f"edges, problem has {problem.n_edges}")
        sv = extra.get("store_version")
        if (sv is not None and problem.store is not None
                and problem.graph.version != sv):
            return (f"stale: GraphStore advanced to version "
                    f"{problem.graph.version}, checkpoint captured {sv}")
        viol = _invariant_violation(problem, b, h, f, edges=edges)
        scale = max(1.0, float(np.abs(b).sum() + np.abs(h).sum()))
        if viol > rtol * scale:
            return (f"invariant violated: |B−(I−P)H−F|₁ = {viol:.3e} > "
                    f"{rtol * scale:.3e} (torn or stale checkpoint)")
        return None

    @classmethod
    def restore(cls, root: str, problem: Problem,
                method: Optional[str] = None,
                options: Optional[SolverOptions] = None,
                step: Optional[int] = None,
                invariant_rtol: float = 1e-4, **kw) -> "SolverSession":
        """Resume a session from the newest checkpoint that *verifies*.

        Every candidate step (newest first; exactly ``step`` when given)
        is loaded and checked — ``B − (I−P)H − F ≈ 0`` against
        ``problem``'s current matrix, N/edge-count/store-version
        agreement — and a failing checkpoint is **rejected rather than
        silently resumed**, falling back to the next older complete
        step.  Raises with the per-step rejection reasons when nothing
        survives.  The restored session keeps the checkpoint's RHS
        (``problem.with_b``), re-seeds ``(F, H)`` and the thresholds,
        and records provenance in ``session.restored_from``.
        """
        import os

        from repro.checkpoint import list_steps, load_checkpoint

        steps = list_steps(root)
        # adversarial directories: step-like dirs that list_steps
        # refused (torn manifest mid-write, permission-denied,
        # unparsable JSON) surface as rejection provenance instead of
        # disappearing silently
        rejected: List[Tuple[int, str]] = []
        complete = {f"step_{s:09d}" for s in steps}
        try:
            entries = sorted(os.listdir(root))
        except OSError:
            entries = []
        for name in entries:
            if (name.startswith("step_") and not name.endswith(".tmp")
                    and name not in complete):
                try:
                    s_bad = int(name.split("_", 1)[1])
                except ValueError:
                    s_bad = -1
                rejected.append(
                    (s_bad, "incomplete or unreadable manifest"))
        if step is not None:
            if step not in steps:
                raise FileNotFoundError(
                    f"no complete checkpoint for step {step} under {root}"
                )
            candidates = [step]
        else:
            candidates = steps[::-1]
        if not candidates:
            raise FileNotFoundError(f"no complete checkpoint under {root}")
        tree_like = {
            "b": np.zeros(problem.n), "f": np.zeros(problem.n),
            "h": np.zeros(problem.n), "t": np.zeros(()),
        }
        edges = problem.p.edge_list()  # once, not per candidate (O(L))
        for s in candidates:
            try:
                tree, _, extra = load_checkpoint(root, tree_like, s)
            except Exception as e:
                rejected.append((s, f"unreadable: {e}"))
                continue
            b, f, h = tree["b"], tree["f"], tree["h"]
            reason = cls._reject_reason(problem, b, f, h, extra,
                                        invariant_rtol, edges=edges)
            if reason is not None:
                rejected.append((s, reason))
                continue
            session = cls(problem.with_b(b),
                          method=method or extra["method"],
                          options=options, **kw)
            session._driver.seed(f, h)
            session._driver.set_threshold(tree["t"])
            session._ckpt_step = s
            session.restored_from = {
                "step": s,
                "ops": extra.get("ops", 0),
                "rounds": extra.get("rounds", 0),
                "lifetime_ops": extra.get("lifetime_ops",
                                          extra.get("ops", 0)),
                "move_log": [tuple(m) for m in extra.get("move_log", [])],
                "rejected": rejected,
            }
            return session
        detail = "; ".join(f"step {s}: {r}" for s, r in rejected)
        raise ValueError(f"no valid checkpoint under {root}: {detail}")

    # ---- batched multi-RHS ------------------------------------------------
    def solve_batch(self, b_matrix: np.ndarray,
                    until: Optional[float] = None,
                    pad: bool = True) -> SolveReport:
        """Solve every column of ``b_matrix`` ([N, C]) over the shared P.

        Runs the vmapped frontier loop (per-column thresholds and
        convergence masks) regardless of the session's method — the
        batch serving path is frontier-native by design (DESIGN.md §4).
        The session's own (H, F) state is untouched.  The lane axis is
        bucket-padded (``pad=False`` opts out — see the driver) so a
        drifting batch width reuses the compiled trace; the padding
        bookkeeping lands in ``extras`` (``bucket``, ``padding_waste``).
        """
        self._check_fresh()
        b_matrix = np.asarray(b_matrix, dtype=np.float64)
        if b_matrix.ndim != 2 or b_matrix.shape[0] != self.problem.n:
            raise ValueError(
                f"b_matrix must be [N, C] with N={self.problem.n}, got "
                f"{b_matrix.shape}"
            )
        if isinstance(self._driver, _SegmentSumDriver):
            batch_driver = self._driver
        else:
            batch_driver = getattr(self, "_batch_driver", None)
            if batch_driver is None:
                batch_driver = _SegmentSumDriver(self.problem, self.options)
                self._batch_driver = batch_driver
        t0 = time.perf_counter()
        tol = self._tol(until)
        x, ops, rounds, res_cols, stats = batch_driver.solve_batch(
            b_matrix, tol, self.options.max_rounds, pad=pad)
        n_ops = int(ops.astype(np.int64).sum())
        return SolveReport(
            x=x,
            residual=float(res_cols.max()),
            n_ops=n_ops,
            cost_iterations=n_ops / max(self.problem.n_edges, 1),
            n_rounds=rounds,
            converged=bool((res_cols <= tol).all()),
            method="frontier:segment_sum",
            trace=[RoundReport(rounds, float(res_cols.max()), n_ops)],
            wall_time_s=time.perf_counter() - t0,
            extras={"batch": b_matrix.shape[1],
                    "bucket": stats["bucket"],
                    "padding_waste": stats["padding_waste"],
                    "ops_per_column": ops.tolist(),
                    "residual_per_column": res_cols.tolist()},
        )
