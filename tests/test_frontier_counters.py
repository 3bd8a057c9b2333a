"""What the ``frontier:pallas`` session records about itself: the
armed-tile tally, the exact §2.3 push count, and the ``repro.*`` host spans.

The armed-tile tally counts, each round, the tiles of the block columns
that hold a sent node (the tiles the fused kernel's grid visits);
it lives in the driver's loop state, so it banks, checkpoints and rewinds
with the push count.  Both tallies are two 32-bit words, exact past
2**31.
"""
import glob
import os

import numpy as np
import pytest

import repro
from repro.obs import count_add, count_value
from repro.core.graph import host_block_graph
from repro.graph import rotation_churn

BS = 64


def _problem():
    g = host_block_graph(2048, host_size=BS, links_per_node=6.0, seed=1)
    return repro.Problem.pagerank(g)


@pytest.fixture(scope="module")
def problem():
    return _problem()


def _session(problem, **kw):
    return repro.SolverSession(problem, method="frontier:pallas", bs=BS,
                               **kw)


def _numpy_armed(d, occupancy_threshold) -> int:
    """Tiles in the block columns that hold a node the next round sends,
    counted in numpy from the round's own ``sent``."""
    from repro.kernels.diffusion import frontier_round_bsr

    f, _res, _h, t = d._state[:4]
    _f, sent, _r = frontier_round_bsr(
        d.m, f, d.w, t, backend=d.op_backend or "block",
        interpret=d.interpret or None,
        occupancy_threshold=occupancy_threshold)
    cols = np.unique(np.flatnonzero(np.asarray(sent)) // BS)
    return int(np.isin(np.asarray(d.m.block_col), cols).sum())


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["block", "pallas_interpret"])
@pytest.mark.parametrize("occupancy_threshold", [0.0, 0.3])
def test_armed_tiles_match_a_numpy_count(problem, interpret,
                                         occupancy_threshold):
    s = _session(problem, trace_every=1, interpret=interpret,
                 occupancy_threshold=occupancy_threshold)
    it = s.run()
    expected = 0
    for _ in range(6):
        expected += _numpy_armed(s._driver, occupancy_threshold)
        next(it)
        assert s.n_armed_tiles == expected
    # some rounds arm some columns, and none arms every tile every round
    assert 0 < expected < 6 * s._driver.m.n_blocks


@pytest.mark.parametrize("occupancy_threshold", [0.0, 0.3])
def test_armed_tiles_count_the_kernels_visits(problem, monkeypatch,
                                              occupancy_threshold):
    """Over a grain, the tally equals the sum of the rounds' visit counts,
    the fused kernel's grid steps: armed share = tally / (rounds * tiles)."""
    import jax

    from repro.api import session as session_mod
    from repro.kernels.diffusion import ops

    visits = []
    real = ops.frontier_round_bsr_pallas

    def counting(blocks, visit_tile, visit_rc, n_visits, *args, **kw):
        jax.debug.callback(lambda n: visits.append(int(n)), n_visits)
        return real(blocks, visit_tile, visit_rc, n_visits, *args, **kw)

    monkeypatch.setattr(ops, "frontier_round_bsr_pallas", counting)
    monkeypatch.setattr(session_mod, "_BSR_ADVANCE", {})
    s = _session(problem, trace_every=8, interpret=True,
                 occupancy_threshold=occupancy_threshold)
    next(s.run())
    assert len(visits) == s.n_rounds == 8
    assert s.n_armed_tiles == sum(visits) > 0
    assert 0 < s.n_armed_tiles < s.n_rounds * s._driver.m.n_blocks


def test_armed_tiles_bank_across_warm_start_and_update_graph():
    problem = _problem()  # the delta patches its graph store in place
    s = _session(problem, trace_every=4)
    s.solve(until=1e-2)
    first = s.n_armed_tiles
    assert first > 0 and s.lifetime_armed_tiles == first
    s.warm_start(problem.b * 1.01)
    assert s.n_armed_tiles == 0 and s.lifetime_armed_tiles == first
    s.solve(until=1e-2)
    second = s.n_armed_tiles
    assert second > 0
    s.update_graph(rotation_churn(s.problem.graph, 5, seed=0))
    assert s.n_armed_tiles == 0
    assert s.lifetime_armed_tiles == first + second


def test_counters_survive_a_checkpoint_round_trip(problem, tmp_path):
    s = _session(problem, trace_every=4)
    s.solve(until=1e-2)
    s.checkpoint(str(tmp_path))
    r = repro.SolverSession.restore(str(tmp_path), problem, bs=BS)
    assert r.restored_from["armed_tiles"] == s.n_armed_tiles > 0
    assert r.restored_from["lifetime_armed_tiles"] == s.lifetime_armed_tiles
    assert r.restored_from["ops"] == s.n_ops > 0


def test_a_state_swap_rewinds_the_counters(problem):
    s = _session(problem, trace_every=4)
    it = s.run()
    next(it)
    kept = s._driver._state
    before = (s.n_ops, s.n_rounds, s.n_armed_tiles)
    next(it)
    assert s.n_armed_tiles > before[2]
    s._driver._state = kept
    assert (s.n_ops, s.n_rounds, s.n_armed_tiles) == before


@pytest.mark.parametrize("start, add", [
    (0, 7), (2**31 - 3, 5), (2**32 - 2, 3), (6 * 2**32 - 1, 2**32 - 1)])
def test_exact_tally_adds_past_32_bits(start, add):
    import jax.numpy as jnp

    count = jnp.asarray([start >> 32, start & 0xFFFFFFFF], jnp.uint32)
    got = count_add(count, jnp.asarray(add, jnp.uint32))
    assert count_value(got) == start + add


def test_push_count_is_exact_past_2_31(problem):
    """A solve whose push count starts just below 2**32 reads the same
    pushes above it as a twin counted from zero."""
    import jax.numpy as jnp

    twin, s = _session(problem), _session(problem)
    start = 2**32 - 1000
    f, res, h, t, _ops, rounds, armed = s._driver._state
    s._driver._state = (f, res, h, t,
                        jnp.asarray([0, start], jnp.uint32), rounds, armed)
    twin.solve()
    rep = s.solve()
    assert twin.n_ops > 1000
    assert rep.n_ops == s.n_ops == start + twin.n_ops > 2**32
    assert isinstance(rep.n_ops, int)
    assert rep.cost_iterations == pytest.approx(
        (start + twin.n_ops) / problem.n_edges)


def test_a_profile_of_run_holds_the_program_spans(problem, tmp_path):
    import jax
    from jax.profiler import ProfileData

    s = _session(problem, trace_every=4)
    it = s.run()
    next(it)  # compiles outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            next(it)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = sorted(
        (ev.start_ns, -ev.end_ns, ev.name)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("repro."))
    grains = [(a, -b) for a, b, n in spans if n == "repro.grain"]
    assert len(grains) == 3
    for lo, hi in grains:
        inside = [n for a, b, n in spans if lo <= a and -b <= hi
                  and n != "repro.grain"]
        assert inside.count("repro.dispatch") == 1
        after = inside[inside.index("repro.dispatch") + 1:]
        assert {"repro.readback.residual", "repro.readback.rounds",
                "repro.readback.ops"} <= set(after)
        assert all(n.startswith("repro.readback.") for n in after)
