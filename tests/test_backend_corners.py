"""Cross-backend parity on corner-case graphs (the PR 4 bug class).

``CSRGraph.to_dense`` once mis-merged parallel edges — the class of bug
where a *representation* detail (duplicate edge entries, self-loops,
empty rows, column sums above 1) silently changes the matrix one
backend solves.  Guard: for each corner graph, every registry backend
must land within tolerance of a dense reference built INDEPENDENTLY by
``np.add.at`` accumulation over the raw edge list (not through
``to_dense`` or any view), so a representation bug in any layer shows
up as cross-backend divergence.

Corner graphs:
* ``self_loops``       — every node carries a self-edge (diagonal P)
* ``dangling_heavy``   — 60% zero-out-degree nodes (PageRank dangling
                          mass dominates; §2.3 charges them 1 op each)
* ``parallel_edges``   — multigraph input: duplicate (src, dst) entries
                          must merge by weight summation everywhere
* ``overweight_rows``  — weighted columns summing above 1 (spectral
                          radius still < 1): schedules see transient
                          |F|₁ growth

Block-structure corners (``block_corners.py``): the tile and bucket
shapes the chip path meets — a partial last tile row (``tile_tail``),
only diagonal tiles (``block_diagonal``), hosts in popularity order
(``popsort_hosts``), a single seeded page (``one_seed``), a host that
never sends (``dangling_host``) or never receives (``unreached_block``)
fluid, a full tile row (``hub_out``) and one hot column (``hub_in``).
"""
import numpy as np
import pytest

import repro
from repro.core import pagerank_system, power_law_graph
from repro.core.graph import CSRGraph

from block_corners import BLOCK_CORNERS

ALL_BACKENDS = ("sequential", "frontier:segment_sum", "frontier:pallas",
                "engine:chunk", "engine:bsr", "simulator")


def _dense_from_edges(p: CSRGraph) -> np.ndarray:
    """Independent dense build: accumulate raw edges, no view code."""
    src, dst, w = p.edge_list()
    m = np.zeros((p.n, p.n))
    np.add.at(m, (dst, src), w)
    return m


def _self_loops():
    n = 60
    src = np.concatenate([np.arange(n), np.arange(n)])
    dst = np.concatenate([(np.arange(n) + 1) % n, np.arange(n)])
    w = np.concatenate([np.full(n, 0.4), np.full(n, 0.45)])
    p = CSRGraph.from_edges(src.astype(np.int32), dst.astype(np.int32),
                            w, n)
    b = np.abs(np.sin(np.arange(n) + 1.0)) / n + 1e-3
    return repro.Problem.linear(p, b, eps=0.15, target_error=1e-5)


def _dangling_heavy():
    rng = np.random.default_rng(5)
    n = 80
    talkers = np.arange(n // 5 * 2)  # 40% have out-links, 60% dangle
    src = np.repeat(talkers, 3)
    dst = rng.integers(0, n, size=src.shape[0]).astype(np.int32)
    keep = src != dst
    g = CSRGraph.from_edges(src[keep].astype(np.int32), dst[keep],
                            np.ones(keep.sum()), n)
    assert (g.out_degree() == 0).sum() >= 0.5 * n
    p, b = pagerank_system(g, damping=0.85)
    return repro.Problem.linear(p, b, eps=0.15, target_error=1e-5)


def _parallel_edges():
    g0 = power_law_graph(50, seed=2)
    p0, _ = pagerank_system(g0)
    src, dst, w = p0.edge_list()
    # duplicate a third of the edges with split weights: the multigraph
    # must canonicalize to the same matrix everywhere
    pick = np.arange(0, src.shape[0], 3)
    src2 = np.concatenate([src, src[pick]])
    dst2 = np.concatenate([dst, dst[pick]])
    w2 = np.concatenate([w, 0.1 * w[pick]])
    w2[pick] *= 0.9  # total per-pair weight back to the original
    p = CSRGraph.from_edges(src2, dst2, w2, p0.n)
    b = np.full(p0.n, 0.15 / p0.n)
    return repro.Problem.linear(p, b, eps=0.15, target_error=1e-5)


def _overweight_rows():
    n = 40
    ring_src = np.arange(n)
    ring_dst = (np.arange(n) + 1) % n
    ring_w = np.full(n, 0.3)
    # a hot 2-cycle whose columns sum above 1 (0.3 + 1.3) while the
    # spectral radius stays < 1
    src = np.concatenate([ring_src, [0, 1]])
    dst = np.concatenate([ring_dst, [1, 0]])
    w = np.concatenate([ring_w, [1.3, 0.5]])
    p = CSRGraph.from_edges(src.astype(np.int32), dst.astype(np.int32),
                            w, n)
    dense = _dense_from_edges(p)
    rho = float(np.max(np.abs(np.linalg.eigvals(dense))))
    assert 1.0 < dense.sum(axis=0).max() and rho < 0.95
    b = np.abs(np.cos(np.arange(n) + 1.0)) / n + 1e-3
    return repro.Problem.linear(p, b, eps=0.1, target_error=1e-5)


CORNERS = {
    "self_loops": _self_loops,
    "dangling_heavy": _dangling_heavy,
    "parallel_edges": _parallel_edges,
    "overweight_rows": _overweight_rows,
    **BLOCK_CORNERS,
}


@pytest.mark.parametrize("method", ALL_BACKENDS)
@pytest.mark.parametrize("corner", sorted(CORNERS))
def test_corner_graph_parity(corner, method):
    problem = CORNERS[corner]()
    x_ref = np.linalg.solve(
        np.eye(problem.n) - _dense_from_edges(problem.p), problem.b)
    opts = {}
    if method == "frontier:pallas":
        opts = {"interpret": True, "bs": 16}
    elif method == "simulator":
        opts = {"k": 2, "mode": "batch", "record_every": 50}
    rep = repro.solve(problem, method=method,
                      options=repro.SolverOptions(**opts))
    assert rep.converged, (corner, method, rep.residual)
    # the stopping rule leaves |x − h|₁ ≤ |F|₁·‖(I−P)⁻¹‖₁ ≈ 1e-5 here;
    # a representation bug (wrong matrix) diverges by orders of
    # magnitude more, so 1e-4 separates the failure mode cleanly
    l1 = float(np.abs(rep.x - x_ref).sum())
    assert l1 <= 1e-4, (corner, method, l1)
    assert rep.n_ops > 0


@pytest.mark.parametrize("corner",
                         ["dangling_heavy", *sorted(BLOCK_CORNERS)])
def test_ops_accounting_parity(corner):
    """§2.3: every backend charges dangling diffusions 1 op, so the
    normalized costs stay within schedule slack of each other — even when
    60% of the mass flows through dangling nodes, and on every block
    corner the chip path meets."""
    problem = CORNERS[corner]()
    costs = {}
    for method in ("sequential", "frontier:segment_sum", "engine:chunk"):
        rep = repro.solve(problem, method=method)
        assert rep.converged
        costs[method] = rep.cost_iterations
    ref = costs["sequential"]
    for method, c in costs.items():
        assert 0.5 * ref <= c <= 2.0 * ref, (method, costs)
