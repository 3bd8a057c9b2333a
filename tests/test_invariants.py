"""Fluid-conservation + residual-monotonicity oracles, all six backends.

The D-iteration's defining invariant (§2.2, and the restore oracle of
the chaos harness): along ANY schedule, ``B = (I−P)·H + F`` where F
includes in-flight fluid.  After every round/exchange each backend must
satisfy ``|B − (I−P)H − F|₁ ≤ ε`` (ε scaled to the backend's compute
dtype) and report a monotonically non-increasing residual — for
nonnegative PageRank systems every diffusion strictly shrinks |F|₁ by
the dangling/damping leak, and an exchange only relocates it.

``repro.api.session._invariant_violation`` is the single shared
implementation — the same function ``SolverSession.restore`` uses to
reject torn checkpoints, so this suite is also the chaos harness's
oracle pinned under test.
"""
import numpy as np
import pytest

import repro
from repro.api.session import _invariant_violation
from repro.core import pagerank_system, power_law_graph

from block_corners import BLOCK_CORNERS

# (method, session kwargs, invariant rtol) — f64 backends get a tight
# bound, f32 ones a dtype-scaled bound
F64_RTOL = 1e-10
F32_RTOL = 1e-5


@pytest.fixture(scope="module")
def problem400():
    g = power_law_graph(400, seed=3)
    return repro.Problem.pagerank(g, target_error=1e-6)


def _check(problem, snapshots, rtol, method):
    """snapshots: iterable of (h, f_total, residual) after each grain."""
    prev = np.inf
    n_checked = 0
    for h, f, resid in snapshots:
        viol = _invariant_violation(problem, problem.b, h, f)
        scale = max(1.0, float(np.abs(problem.b).sum() + np.abs(h).sum()))
        assert viol <= rtol * scale, (
            f"{method}: conservation broken at grain {n_checked}: "
            f"{viol:.3e} > {rtol * scale:.3e}"
        )
        assert resid <= prev * (1 + 1e-6) + 1e-12, (
            f"{method}: residual increased at grain {n_checked}: "
            f"{resid:.6e} > {prev:.6e}"
        )
        prev = resid
        n_checked += 1
    assert n_checked >= 3, f"{method}: too few grains observed"


# --------------------------------------------------------------------------- #
# session-driven backends (frontier + engine), grain = trace round / chunk,
# on problem400 and on every block-structure corner (block_corners.py)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("graph", ["problem400", *sorted(BLOCK_CORNERS)])
@pytest.mark.parametrize("method,opts,rtol", [
    ("frontier:segment_sum", {}, F32_RTOL),
    ("frontier:pallas", {"interpret": True, "bs": 64}, F32_RTOL),
    ("engine:chunk", {}, F32_RTOL),
    ("engine:bsr", {}, F32_RTOL),
])
def test_session_backends_conserve_fluid(request, graph, method, opts,
                                        rtol):
    if graph == "problem400":
        problem, trace_every = request.getfixturevalue("problem400"), 4
    else:
        # 16-round frontier grains still leave every corner 29 or more
        # grains to check (_check asks for 3) at a quarter of the
        # per-grain retraces frontier:segment_sum pays at 4
        problem, trace_every = BLOCK_CORNERS[graph](), 16
    options = repro.SolverOptions(trace_every=trace_every, **opts)
    session = repro.SolverSession(problem, method=method, options=options)

    def snapshots():
        for rep in session.run():
            f, h = session._driver.fluid()
            yield h, f, rep.residual

    _check(problem, snapshots(), rtol, f"{method} on {graph}")
    assert session.residual <= problem.tol


# --------------------------------------------------------------------------- #
# sequential — observer hook, grain = threshold sweep
# --------------------------------------------------------------------------- #
def test_sequential_conserves_fluid(problem400):
    from repro.core.diteration import run_sequential

    recs = []
    res = run_sequential(
        problem400.p, problem400.b, target_error=1e-6, eps=0.15,
        observer=lambda f, h: recs.append(
            (h.copy(), f.copy(), float(np.abs(f).sum()))),
    )
    assert res.residual <= problem400.tol
    _check(problem400, recs, F64_RTOL, "sequential")


# --------------------------------------------------------------------------- #
# simulator — manual step loop, invariant checked after EVERY exchange,
# F includes the in-flight outboxes
# --------------------------------------------------------------------------- #
def _sim_snapshots(sim, max_steps=50_000):
    step = 0
    while step < max_steps:
        step += 1
        for k in range(sim.k):
            sim._local_step(k)
        for k in range(sim.k):
            if sim.s_abs[k] > 0 and sim.s_abs[k] > sim.r_of(k) / 2.0:
                sim._exchange(k)
                yield (sim.h.copy(), sim.f + np.sum(sim.outbox, axis=0),
                       sim.global_residual())
        if sim.rebalancer is not None:
            sim._repartition(step)
        yield (sim.h.copy(), sim.f + np.sum(sim.outbox, axis=0),
               sim.global_residual())
        if sim.global_residual() <= sim.tol:
            return


@pytest.mark.parametrize("dynamic", [False, True])
def test_simulator_conserves_fluid(problem400, dynamic):
    from repro.core.simulator import DistributedSimulator, SimulatorConfig

    cfg = SimulatorConfig(k=4, target_error=1e-6, eps=0.15, mode="batch",
                          dynamic=dynamic)
    sim = DistributedSimulator(problem400.p, problem400.b, cfg)
    _check(problem400, _sim_snapshots(sim), F64_RTOL,
           f"simulator(dynamic={dynamic})")
    assert sim.global_residual() <= sim.tol


# --------------------------------------------------------------------------- #
# the same oracle under chaos — recovery must land back ON the manifold
# --------------------------------------------------------------------------- #
def test_simulator_chaos_preserves_invariant(problem400):
    """kill + rescale relocate capacity, never fluid: conservation holds
    to f64 precision through both events (the chaos-recovery oracle)."""
    from repro.chaos import ChaosPlan
    from repro.core.simulator import DistributedSimulator, SimulatorConfig

    cfg = SimulatorConfig(k=4, target_error=1e-6, eps=0.15, mode="batch",
                          dynamic=True)
    sim = DistributedSimulator(problem400.p, problem400.b, cfg)
    plan = ChaosPlan(seed=0).straggler(1, 4.0, round=2).kill(
        3, round=5).rescale(2, round=9)
    res = sim.run(chaos=plan)
    assert res.converged
    assert [k for _, k in res.chaos_log] == ["straggler", "kill",
                                             "rescale"]
    f_total = sim.f + np.sum(sim.outbox, axis=0)
    viol = _invariant_violation(problem400, problem400.b, sim.h, f_total)
    assert viol <= F64_RTOL * max(
        1.0, float(np.abs(problem400.b).sum() + np.abs(sim.h).sum()))


def test_restored_session_satisfies_invariant(problem400, tmp_path):
    """A checkpoint/restore round trip stays on the manifold — and a
    torn checkpoint (invariant violator) is rejected, not resumed."""
    from repro.chaos import tear_checkpoint

    session = repro.SolverSession(problem400,
                                  method="frontier:segment_sum")
    for i, _ in enumerate(session.run()):
        if i >= 2:
            break
    session.checkpoint(str(tmp_path))
    restored = repro.SolverSession.restore(str(tmp_path), problem400)
    f, h = restored._driver.fluid()
    viol = _invariant_violation(problem400, restored._b, h, f)
    assert viol <= F32_RTOL * max(
        1.0, float(np.abs(restored._b).sum() + np.abs(h).sum()))
    # tear the only checkpoint: restore must refuse loudly
    tear_checkpoint(str(tmp_path / "step_000000001"))
    with pytest.raises(ValueError, match="invariant violated"):
        repro.SolverSession.restore(str(tmp_path), problem400)
