"""Production shard_map engine: single-device in-process, multi-device via
a subprocess with 8 fake host devices (smoke tests must see 1 device)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import pagerank_system, power_law_graph
from repro.core.distributed import (
    DistributedEngine,
    EngineConfig,
    build_engine_arrays,
)


def test_engine_k1_matches_dense(small_pagerank):
    p, b, x = small_pagerank
    cfg = EngineConfig(k=1, target_error=1e-6, eps=0.15,
                       buckets_per_dev=8, headroom=2)
    arrs = build_engine_arrays(p, b, cfg)
    eng = DistributedEngine(arrs, cfg)
    xs, info = eng.solve()
    assert info["converged"]
    np.testing.assert_allclose(xs, x, atol=1e-5)


def test_engine_k1_bsr_matches_dense(small_pagerank):
    """BSR tile backend: same fixed point through the dense-tile push."""
    p, b, x = small_pagerank
    cfg = EngineConfig(k=1, target_error=1e-6, eps=0.15,
                       buckets_per_dev=8, headroom=2,
                       diffusion_backend="bsr")
    arrs = build_engine_arrays(p, b, cfg)
    assert arrs.tiles is not None and arrs.tile_dst is not None
    eng = DistributedEngine(arrs, cfg)
    xs, info = eng.solve()
    assert info["converged"]
    np.testing.assert_allclose(xs, x, atol=1e-5)


def test_engine_k1_bsr_pallas_interpret():
    """The Pallas gather kernel inside the jitted chunk (interpret mode)."""
    g = power_law_graph(200, seed=3)
    p, b = pagerank_system(g)
    x = np.linalg.solve(np.eye(g.n) - p.to_dense(), b)
    cfg = EngineConfig(k=1, target_error=1e-6, eps=0.15,
                       buckets_per_dev=6, headroom=2,
                       diffusion_backend="bsr", pallas_interpret=True,
                       max_inner=4, chunk_rounds=2)
    arrs = build_engine_arrays(p, b, cfg)
    eng = DistributedEngine(arrs, cfg)
    xs, info = eng.solve()
    assert info["converged"]
    np.testing.assert_allclose(xs, x, atol=1e-5)


def test_engine_tile_push_pallas_parity(small_pagerank):
    """einsum and Pallas-gather implementations of the tile push agree."""
    import jax.numpy as jnp

    from repro.core.distributed import _tile_push_stable

    p, b, _ = small_pagerank
    cfg = EngineConfig(k=1, target_error=1e-6, eps=0.15,
                       buckets_per_dev=8, headroom=2,
                       diffusion_backend="bsr")
    a = build_engine_arrays(p, b, cfg)
    rng = np.random.default_rng(1)
    sent = rng.standard_normal((a.n_rows, a.bucket_size)).astype(np.float32)
    o1 = _tile_push_stable(
        jnp.asarray(a.tiles, jnp.float32), jnp.asarray(a.tile_dst),
        jnp.asarray(sent), a.n_rows, use_pallas=False)
    o2 = _tile_push_stable(
        jnp.asarray(a.tiles, jnp.float32), jnp.asarray(a.tile_dst),
        jnp.asarray(sent), a.n_rows, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-4)


def _armed_case(a, case, rng):
    """``sent`` for one case of the armed-rows push: some rows zero, none
    armed, every row armed, or every row that targets one bucket zero."""
    sent = rng.standard_normal((a.n_rows, a.bucket_size)).astype(np.float32)
    real = (a.w != 0).any(axis=1)
    sent[~real] = 0.0
    if case == "some_rows_zero":
        sent[rng.random(a.n_rows) < 0.5] = 0.0
    elif case == "none_armed":
        sent[:] = 0.0
    elif case == "bucket_only_unarmed_rows_target":
        reaches = [(a.tile_dst == d) & (a.tiles != 0).any(axis=(2, 3))
                   for d in range(a.n_rows)]
        sources = [np.flatnonzero(r.any(axis=1)) for r in reaches]
        d = min((d for d in range(1, a.n_rows) if len(sources[d])),
                key=lambda d: len(sources[d]))
        sent[sources[d]] = 0.0
        return sent, d
    return sent, None


@pytest.mark.parametrize("case", ["some_rows_zero", "none_armed",
                                  "all_armed",
                                  "bucket_only_unarmed_rows_target"])
def test_tile_push_visits_armed_rows_only(small_pagerank, case):
    """The gather kernel's grid runs over the tiles of the rows that send
    fluid: the push is bit-identical to the all-slot push (up to the sign
    of a zero) and agrees with the einsum path to float32 rounding (the
    CPU einsum sums a tile's products in another order than the kernel's
    dot), rows the visits never reach read zero, and the visit count is T
    times the armed rows."""
    import jax.numpy as jnp

    from repro.core.distributed import _armed_visits, _tile_push_stable

    p, b, _ = small_pagerank
    cfg = EngineConfig(k=1, target_error=1e-6, eps=0.15,
                       buckets_per_dev=40, headroom=2,
                       diffusion_backend="bsr")
    a = build_engine_arrays(p, b, cfg)
    sent, lone = _armed_case(a, case, np.random.default_rng(2))
    armed = (sent != 0).any(axis=1)
    tiles = jnp.asarray(a.tiles, jnp.float32)
    args = (tiles, jnp.asarray(a.tile_dst), jnp.asarray(sent), a.n_rows)
    push = np.asarray(_tile_push_stable(
        *args, use_pallas=True, interpret=True,
        row_armed=jnp.asarray(armed)))
    every_slot = np.asarray(_tile_push_stable(
        *args, use_pallas=True, interpret=True))
    einsum = np.asarray(_tile_push_stable(*args, use_pallas=False))
    np.testing.assert_array_equal(push, every_slot)
    np.testing.assert_allclose(push, einsum, rtol=1e-6, atol=1e-6)
    n_visits = int(_armed_visits(jnp.asarray(a.tile_dst),
                                 jnp.asarray(armed), a.n_rows)[3])
    assert n_visits == a.tile_dst.shape[1] * int(armed.sum())
    if case == "none_armed":
        assert n_visits == 0 and not push.any()
    elif case == "all_armed":
        assert armed[(a.w != 0).any(axis=1)].all()
    elif case == "bucket_only_unarmed_rows_target":
        assert 0 < armed.sum() and not push[lone].any()
    else:
        assert 0 < armed.sum() < (a.w != 0).any(axis=1).sum()


def test_armed_visit_list_keeps_the_all_slot_order(small_pagerank):
    """The armed slots come first, in the order the all-slot push visits
    them (destination, then pool slot); the reached buckets are those
    the armed slots target."""
    import jax.numpy as jnp

    from repro.core.distributed import _armed_visits

    p, b, _ = small_pagerank
    cfg = EngineConfig(k=1, target_error=1e-6, eps=0.15,
                       buckets_per_dev=40, headroom=2,
                       diffusion_backend="bsr")
    a = build_engine_arrays(p, b, cfg)
    t_cap = a.tile_dst.shape[1]
    armed = np.random.default_rng(4).random(a.n_rows) < 0.4
    block, row, col, n, reached = (np.asarray(v) for v in _armed_visits(
        jnp.asarray(a.tile_dst), jnp.asarray(armed), a.n_rows))
    dst = a.tile_dst.reshape(-1)
    slot_armed = np.repeat(armed, t_cap)
    every = np.argsort(dst, kind="stable")
    assert int(n) == slot_armed.sum()
    np.testing.assert_array_equal(block[:n], every[slot_armed[every]])
    np.testing.assert_array_equal(block[n:], every[~slot_armed[every]])
    np.testing.assert_array_equal(row, dst[block])
    np.testing.assert_array_equal(col, block // t_cap)
    np.testing.assert_array_equal(
        reached, np.isin(np.arange(a.n_rows), dst[slot_armed]))


SOLVE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={k}"
    import sys
    sys.path.insert(0, {src!r})
    import numpy as np
    import repro
    from repro.core import distributed as D
    from repro.core.graph import host_block_graph

    g = host_block_graph(1024, host_size=64, links_per_node=6.0, seed=3)
    problem = repro.Problem.pagerank(g)

    def solve():
        D._CHUNK_CACHE.clear()
        s = repro.SolverSession(problem, method="engine:bsr", k={k},
                                bs=64, interpret=True, max_chunks=3,
                                chunk_rounds=2, max_inner=4)
        s.solve()
        f, h = s._driver.fluid()
        return f, h, s.n_ops, s.n_armed_tiles, s

    f, h, ops, armed, s = solve()
    real = D._tile_push_stable
    # the all-slot push: every tile a visit, the grid over the whole pool
    D._tile_push_stable = lambda *a, row_armed=None, **kw: real(*a, **kw)
    f0, h0, ops0, _armed0, _s0 = solve()
    D._tile_push_stable = real
    assert np.array_equal(f, f0) and np.array_equal(h, h0), "fluid differs"
    assert ops == ops0 > 0, (ops, ops0)
    a = s._driver.arrays
    rounds = s.n_rounds
    assert 0 < armed < rounds * a.tile_dst.size, (armed, rounds)
    if {k} > 1:
        s.rescale(1)
        assert s.n_armed_tiles == armed, (s.n_armed_tiles, armed)
    print("SOLVE_OK", ops, armed)
    """
)


@pytest.mark.parametrize("k", [1, 2])
def test_armed_push_solve_matches_the_all_slot_push(k):
    """A short ``engine:bsr`` solve through the Pallas gather kernel
    (interpret mode) gives the same F, H and push count, bit for bit,
    whether the kernel's grid runs over the armed tiles or over every
    slot; the armed-tile tally lies strictly between zero and every slot
    of every local round, and a rescale carries it."""
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c", SOLVE_SCRIPT.format(src=src, k=k)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SOLVE_OK" in r.stdout


def test_engine_arrays_roundtrip(small_pagerank):
    """Every node and edge lands exactly once in the bucketed layout."""
    p, b, _ = small_pagerank
    cfg = EngineConfig(k=2, target_error=1e-6, eps=0.15,
                       buckets_per_dev=6, headroom=2)
    a = build_engine_arrays(p, b, cfg)
    nodes = a.node_of_slot[a.node_of_slot >= 0]
    assert np.array_equal(np.sort(nodes), np.arange(p.n))
    assert int((a.wgt != 0).sum()) == p.n_edges
    np.testing.assert_allclose(a.f0.sum(), b.sum(), rtol=1e-12)


MULTI_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import numpy as np
    from repro.core import pagerank_system, power_law_graph
    from repro.core.distributed import (
        DistributedEngine, EngineConfig, build_engine_arrays)

    g = power_law_graph(1200, seed=7)
    order = np.argsort(-g.out_degree(), kind="stable")
    g = g.reorder(order)
    p, b = pagerank_system(g)
    P = np.zeros((g.n, g.n))
    for i in range(g.n):
        js, ws = p.out_neighbors(i)
        P[js, i] += ws
    x_ref = np.linalg.solve(np.eye(g.n) - P, b)

    for K, dyn in [(4, False), (8, True)]:
        cfg = EngineConfig(k=K, target_error=1e-6, eps=0.15,
                           buckets_per_dev=12, headroom=4, dynamic=dyn)
        arrs = build_engine_arrays(p, b, cfg)
        eng = DistributedEngine(arrs, cfg)
        xs, info = eng.solve()
        assert info["converged"], (K, dyn, info["residual"])
        err = np.abs(xs - x_ref).max()
        assert err < 1e-5, (K, dyn, err)

    # deterministic repartition test: force a bucket move mid-solve through
    # the balance control plane's executor and check the solution is still
    # exact (state+edges travel with buckets) — the MovePlan round-trip at
    # bucket granularity
    from repro.balance import BucketMoveExecutor, MovePlan

    cfg = EngineConfig(k=4, target_error=1e-6, eps=0.15,
                       buckets_per_dev=12, headroom=4, dynamic=False)
    arrs = build_engine_arrays(p, b, cfg)
    eng = DistributedEngine(arrs, cfg)
    ex = BucketMoveExecutor(eng, eng.init_state())
    sizes0 = ex.sizes().copy()
    ex.state, _ = eng._chunk(ex.state, ex.w, ex.src_slot, ex.dst_bucket,
                             ex.dst_slot, ex.wgt)
    moved = ex.apply(MovePlan(src=0, dst=3, units=2, kind="bucket"))
    assert moved == 2, moved
    sizes1 = ex.sizes()
    assert sizes1[0] == sizes0[0] - 2 and sizes1[3] == sizes0[3] + 2
    # a move exceeding the destination headroom is clipped to free rows
    moved2 = ex.apply(MovePlan(src=1, dst=3, units=99, kind="bucket"))
    assert moved2 == cfg.headroom - 2, moved2  # only 2 inert rows left
    tol = cfg.target_error * cfg.eps
    for _ in range(cfg.max_chunks):
        ex.state, stats = eng._chunk(ex.state, ex.w, ex.src_slot,
                                     ex.dst_bucket, ex.dst_slot, ex.wgt)
        resid = float(np.asarray(stats["residual"])) + float(
            np.asarray(stats["s"]).sum())
        if resid <= tol:
            break
    assert resid <= tol, resid
    h = np.asarray(ex.state.h).reshape(arrs.n_rows, arrs.bucket_size)
    x2 = np.zeros(arrs.n)
    for bid in range(arrs.n_rows):
        nodes = arrs.node_of_slot[int(arrs.pos_of_bucket[bid])]
        valid = nodes >= 0
        if valid.any():
            x2[nodes[valid]] = h[int(ex.row_of_bucket[bid]), valid]
    err = np.abs(x2 - x_ref).max()
    assert err < 1e-5, ("post-move solution wrong", err)
    print("MULTI_OK")
    """
)


def test_engine_multidevice_subprocess():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run(
        [sys.executable, "-c", MULTI_SCRIPT.format(src=src)],
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MULTI_OK" in r.stdout


REPLAY_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import numpy as np
    from repro.core import pagerank_system, power_law_graph
    from repro.core.distributed import (
        DistributedEngine, EngineConfig, build_engine_arrays)
    from repro.balance import BucketMoveExecutor, MovePlan

    g = power_law_graph(1600, seed=7)
    order = np.argsort(-g.out_degree(), kind="stable")
    g = g.reorder(order)
    p, b = pagerank_system(g)
    P = np.zeros((g.n, g.n))
    for i in range(g.n):
        js, ws = p.out_neighbors(i)
        P[js, i] += ws
    x_ref = np.linalg.solve(np.eye(g.n) - P, b)

    # ---- replay: both diffusion backends must make the SAME MovePlan
    # decisions and converge to the same residual -----------------------
    out = {{}}
    for be in ("segment_sum", "bsr"):
        cfg = EngineConfig(k=8, target_error=1e-8, eps=0.15,
                           buckets_per_dev=40, headroom=8, dynamic=True,
                           eta=0.9, diffusion_backend=be)
        arrs = build_engine_arrays(p, b, cfg)
        eng = DistributedEngine(arrs, cfg)
        xs, info = eng.solve()
        assert info["converged"], (be, info["residual"])
        err = np.abs(xs - x_ref).max()
        assert err < 1e-5, (be, err)
        out[be] = (info["move_log"], info["residual"], xs)
    seg, bsr = out["segment_sum"], out["bsr"]
    assert len(seg[0]) > 0, "replay exercised no bucket moves"
    assert seg[0] == bsr[0], ("MovePlan decisions diverged",
                              seg[0], bsr[0])
    assert abs(seg[1] - bsr[1]) <= 1e-5, (seg[1], bsr[1])
    assert np.abs(seg[2] - bsr[2]).max() < 1e-5

    # ---- forced mid-solve move under the bsr backend: the tile groups
    # must travel with their bucket rows ---------------------------------
    cfg = EngineConfig(k=4, target_error=1e-6, eps=0.15,
                       buckets_per_dev=12, headroom=4,
                       diffusion_backend="bsr")
    arrs = build_engine_arrays(p, b, cfg)
    eng = DistributedEngine(arrs, cfg)
    ex = BucketMoveExecutor(eng, eng.init_state())
    ex.state, _ = eng._chunk(ex.state, *ex.chunk_operands())
    moved = ex.apply(MovePlan(src=0, dst=3, units=2, kind="bucket"))
    assert moved == 2, moved
    tol = cfg.target_error * cfg.eps
    for _ in range(cfg.max_chunks):
        ex.state, stats = eng._chunk(ex.state, *ex.chunk_operands())
        resid = float(np.asarray(stats["residual"])) + float(
            np.asarray(stats["s"]).sum())
        if resid <= tol:
            break
    assert resid <= tol, resid
    h = np.asarray(ex.state.h).reshape(arrs.n_rows, arrs.bucket_size)
    x2 = np.zeros(arrs.n)
    for bid in range(arrs.n_rows):
        nodes = arrs.node_of_slot[int(arrs.pos_of_bucket[bid])]
        valid = nodes >= 0
        if valid.any():
            x2[nodes[valid]] = h[int(ex.row_of_bucket[bid]), valid]
    err = np.abs(x2 - x_ref).max()
    assert err < 1e-5, ("post-move bsr solution wrong", err)
    print("REPLAY_OK")
    """
)


def test_engine_backend_replay_subprocess():
    """Acceptance: identical MovePlans + same residual for either backend."""
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run(
        [sys.executable, "-c", REPLAY_SCRIPT.format(src=src)],
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "REPLAY_OK" in r.stdout
