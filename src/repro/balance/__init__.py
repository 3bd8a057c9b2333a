"""Unified rebalancing control plane (DESIGN.md §5).

The paper's claim is that one measurement-driven controller "equalizes
the computation load between PIDs without any deep analysis of the
matrix or graph structure" — i.e. the same policy works at any
granularity.  This package is that claim turned into an architecture:

* :class:`~repro.balance.signals.LoadSignal` — the one measurement
  container every layer produces (per-PID residuals, per-device edge-op
  counts, per-worker graph churn, serving latency and queue pressure).
* :class:`~repro.balance.policies.Rebalancer` — the policy protocol:
  ``propose(LoadSignal) -> [MovePlan]`` + ``reset_worker(k)``.  Three
  implementations ship: :class:`SlopeEMAPolicy` (paper §2.5.2 exact),
  :class:`CostRefreshPolicy` (periodic CB re-split from observed costs),
  :class:`HysteresisPolicy` (slope-EMA with a deadband and multi-move
  batching), :class:`PressurePolicy` (serving-tier overload control:
  ±1 degradation-ladder rung recommendations from a ``latency``
  signal).
* :class:`~repro.balance.plan.MovePlan` — granularity-agnostic
  "move ``units`` from worker ``src`` to worker ``dst``" decision with a
  declared unit kind (``node`` | ``bucket``).
* :mod:`~repro.balance.executors` — per-granularity executors that turn
  a MovePlan into actual state mutation: node moves in the faithful
  simulator (with the §2.4 reassignment-cost charging) and bucket-row
  permutations in the distributed engine.

Consumers: :mod:`repro.core.simulator` (node-granular) and
:mod:`repro.core.distributed` (bucket-granular); the serving scheduler
and the resilience supervisor feed ``queue-depth`` / ``latency``
signals to :class:`PressurePolicy`.
"""
from .plan import MovePlan
from .signals import LoadSignal
from .policies import (
    CostRefreshPolicy,
    HysteresisPolicy,
    PressurePolicy,
    Rebalancer,
    SlopeEMAPolicy,
    make_rebalancer,
)
from .executors import (
    BucketMoveExecutor,
    MoveExecutor,
    NodeMoveExecutor,
)

__all__ = [
    "LoadSignal",
    "MovePlan",
    "Rebalancer",
    "SlopeEMAPolicy",
    "CostRefreshPolicy",
    "HysteresisPolicy",
    "PressurePolicy",
    "make_rebalancer",
    "MoveExecutor",
    "NodeMoveExecutor",
    "BucketMoveExecutor",
]
