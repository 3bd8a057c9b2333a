"""Per-granularity executors: MovePlan -> actual state mutation.

Policies decide; executors act.  Each executor handles exactly one unit
kind and reports how many units actually moved (0 when the plan is
infeasible — e.g. the source would be emptied, or no free landing rows
exist), so policies/consumers can account moves truthfully.

* :class:`NodeMoveExecutor` — node-granular, drives the faithful
  simulator: boundary-node reassignment via
  :func:`repro.core.partition.apply_move`, owner-map update, the §2.4
  reassignment-cost charging (moved nodes billed to BOTH PIDs), and the
  receiver-threshold re-seed.
* :class:`BucketMoveExecutor` — bucket-granular, drives the distributed
  engine: plans a row permutation onto the destination device's inert
  headroom rows and applies it in-graph (``jnp.take`` on the sharded
  axis).
"""
from __future__ import annotations

from typing import Protocol, runtime_checkable

import numpy as np

from .plan import MovePlan

__all__ = [
    "MoveExecutor",
    "NodeMoveExecutor",
    "BucketMoveExecutor",
]


@runtime_checkable
class MoveExecutor(Protocol):
    kind: str

    def apply(self, plan: MovePlan) -> int:
        """Execute ``plan``; return the number of units actually moved."""
        ...


class NodeMoveExecutor:
    """Node moves inside :class:`repro.core.simulator.DistributedSimulator`.

    Owns the full §2.5.2 move side-effects that used to live inline in
    the simulator's ``_repartition``:

    * tail-boundary reassignment (:func:`apply_move`, never emptying the
      source set),
    * owner-map update for the moved nodes,
    * §2.4 cost charging — the number of re-affected nodes is billed to
      BOTH PIDs' ``count_active`` and pushed into their debt (freeze
      artifact),
    * receiver threshold re-seed — the destination may now hold hotter
      fluid than its current T.
    """

    kind = "node"

    def __init__(self, sim):
        self.sim = sim

    def apply(self, plan: MovePlan) -> int:
        from repro.core.partition import apply_move

        sim = self.sim
        new_sets, moved = apply_move(sim.sets, plan.to_instruction())
        if moved == 0:
            return 0
        sim.sets = new_sets
        sim.n_moves += 1
        sim.owner[sim.sets[plan.dst]] = plan.dst
        # §2.4: charge the number of re-affected nodes to both PIDs
        sim.count_active[plan.src] += moved
        sim.count_active[plan.dst] += moved
        sim.debt[plan.src] -= moved
        sim.debt[plan.dst] -= moved
        # thresholds: receiving PID may now hold hotter fluid than its T
        s_dst = sim.sets[plan.dst]
        if s_dst.size:
            mx = float(
                (np.abs(sim.f[s_dst]) * sim.weights[s_dst]).max()
            )
            if mx > 0:
                sim.t_k[plan.dst] = min(sim.t_k[plan.dst], mx * 1.0001)
        return moved


class BucketMoveExecutor:
    """Bucket-row moves inside :class:`repro.core.distributed.DistributedEngine`.

    Owns the mutable solve-time layout state: the stable-bucket → row
    map plus the row-permuted edge/weight arrays and the sharded
    :class:`EngineState`.  ``apply`` plans a permutation of up to
    ``plan.units`` real buckets from the source device's tail onto the
    destination device's inert rows and runs the engine's jitted
    in-graph repartition.
    """

    kind = "bucket"

    def __init__(self, engine, state):
        self.engine = engine
        self.state = state
        self.row_of_bucket = np.array(engine.a.pos_of_bucket)
        self.w = engine.w
        self.src_slot = engine.src_slot
        self.dst_bucket = engine.dst_bucket
        self.dst_slot = engine.dst_slot
        self.wgt = engine.wgt
        # BSR tile operands travel with their rows too (None when the
        # engine runs the per-edge segment-sum backend)
        self.tiles = getattr(engine, "tiles", None)
        self.tile_dst = getattr(engine, "tile_dst", None)
        self.slot_out_deg = getattr(engine, "slot_out_deg", None)

    def chunk_operands(self) -> tuple:
        """Row-sharded operands in the order the engine's chunk expects."""
        ops = (self.w, self.src_slot, self.dst_bucket, self.dst_slot,
               self.wgt)
        if self.tiles is not None:
            ops = ops + (self.tiles, self.tile_dst, self.slot_out_deg)
        return ops

    def sizes(self) -> np.ndarray:
        """Real (non-inert) buckets currently owned per device."""
        eng = self.engine
        cfg = eng.cfg
        n_real = cfg.k * (cfg.buckets_per_dev - cfg.headroom)
        dev_of_bucket = self.row_of_bucket // cfg.buckets_per_dev
        return np.bincount(dev_of_bucket[:n_real], minlength=cfg.k)

    def apply(self, plan: MovePlan, keep_min: int = 1) -> int:
        """Execute ``plan``.  ``keep_min=1`` (rebalancing) never empties
        the source device; the rescale drain passes ``keep_min=0`` so a
        dying device can hand over its last bucket."""
        import jax

        eng = self.engine
        perm, new_map, moved = eng._plan_move(
            self.row_of_bucket, plan.src, plan.dst, plan.units,
            keep_min=keep_min)
        if moved == 0:
            return 0
        self.row_of_bucket = new_map
        self.state, arrs = eng._repartition(
            self.state,
            jax.device_put(perm, eng.rep_sharding),
            jax.device_put(new_map.astype(np.int32), eng.rep_sharding),
            self.chunk_operands())
        (self.w, self.src_slot, self.dst_bucket, self.dst_slot,
         self.wgt) = arrs[:5]
        if self.tiles is not None:
            self.tiles, self.tile_dst, self.slot_out_deg = arrs[5:8]
        return moved

