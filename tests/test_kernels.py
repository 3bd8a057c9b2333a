"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # optional dep: property tests skip, fallbacks run
    HAVE_HYPOTHESIS = False

from repro.core import CSRGraph, pagerank_system, power_law_graph
from repro.kernels.diffusion import (
    BsrMatrix,
    bsr_gather_spmm_pallas,
    bsr_spmm,
    bsr_spmm_ref,
    frontier_round_bsr,
    frontier_round_ref,
    prepare_bsr,
)

RNG = np.random.default_rng(0)


# --------------------------------------------------------------------------- #
# diffusion / BSR
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("n,seed", [(300, 0), (500, 2), (900, 5)])
@pytest.mark.parametrize("cols", [1, 4])
def test_bsr_diffusion_vs_dense(n, seed, cols):
    g = power_law_graph(n, seed=seed)
    p, _ = pagerank_system(g)
    m = prepare_bsr(p.indptr, p.indices, p.weights, p.n, bs=128)
    x = RNG.standard_normal(
        (m.n_row_blocks * 128, cols) if cols > 1 else (m.n_row_blocks * 128,)
    ).astype(np.float32)
    out = np.asarray(bsr_spmm(m, jnp.asarray(x)))
    ref = np.asarray(bsr_spmm(m, jnp.asarray(x), use_pallas=False))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bs", [8, 64, 128])
def test_bsr_block_sizes(bs):
    g = power_law_graph(200, seed=7)
    p, _ = pagerank_system(g)
    m = prepare_bsr(p.indptr, p.indices, p.weights, p.n, bs=bs)
    x = RNG.standard_normal(m.n_row_blocks * bs).astype(np.float32)
    out = np.asarray(bsr_spmm(m, jnp.asarray(x)))
    ref = np.asarray(bsr_spmm(m, jnp.asarray(x), use_pallas=False))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_bsr_empty_rows_masked():
    """Rows with no blocks must come out exactly zero."""
    import numpy as np

    from repro.kernels.diffusion.ref import dense_to_bsr
    from repro.kernels.diffusion.ops import BsrMatrix

    p = np.zeros((256, 256), np.float32)
    p[:128, :128] = RNG.standard_normal((128, 128))
    blocks, br, bc = dense_to_bsr(p, 128)
    m = BsrMatrix(blocks, br, bc, 2, 128)
    x = RNG.standard_normal(256).astype(np.float32)
    out = np.asarray(bsr_spmm(m, jnp.asarray(x)))
    assert np.all(out[128:] == 0)
    np.testing.assert_allclose(out[:128], p[:128] @ x, rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------- #
# diffusion / fused frontier round (pallas ≈ block oracle ≈ numpy ref)
# --------------------------------------------------------------------------- #
def _check_frontier_round(n, c, seed, t_quantile, bs=64):
    """Parity of the fused frontier round across all three backends on a
    random CSR graph, at a threshold hitting ``t_quantile`` of the fluid."""
    rng = np.random.default_rng(seed)
    if n == 1:  # single node, no edges (all-dangling degenerate graph)
        p = CSRGraph(indptr=np.zeros(2, np.int64),
                     indices=np.zeros(0, np.int32),
                     weights=np.zeros(0, np.float64), n=1)
    else:
        g = power_law_graph(n, seed=seed)
        p, _ = pagerank_system(g)
    m = prepare_bsr(p.indptr, p.indices, p.weights, p.n, bs=bs)
    n_pad = m.n_row_blocks * bs
    f = np.zeros((n_pad, c), np.float32)
    f[: p.n] = rng.standard_normal((p.n, c))
    w = np.zeros(n_pad, np.float32)
    w[: p.n] = 1.0 / np.maximum(np.diff(p.indptr), 1)
    fw = (np.abs(f) * w[:, None]).ravel()
    if t_quantile >= 1.0:
        t = float(fw.max()) * 2.0 + 1.0  # empty frontier
    else:
        t = max(float(np.quantile(fw, t_quantile)), 1e-6)
    f_in = f[:, 0] if c == 1 else f
    fr, sr, rr = frontier_round_ref(
        np.asarray(m.blocks), np.asarray(m.block_row),
        np.asarray(m.block_col), f_in, w, t)
    for backend in ("block", "pallas"):
        fo, so, ro = frontier_round_bsr(
            m, jnp.asarray(f_in), jnp.asarray(w), jnp.float32(t),
            backend=backend, interpret=True if backend == "pallas" else None)
        np.testing.assert_allclose(np.asarray(fo), fr, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(so), sr, rtol=1e-6, atol=1e-6)
        assert abs(float(ro) - rr) <= 1e-3 * max(rr, 1.0), (backend, ro, rr)


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(
        n=st.integers(2, 300),
        c=st.sampled_from([1, 3]),
        seed=st.integers(0, 1000),
        t_quantile=st.sampled_from([0.0, 0.5, 0.9, 1.0]),
    )
    def test_frontier_round_property(n, c, seed, t_quantile):
        _check_frontier_round(n, c, seed, t_quantile)


@pytest.mark.parametrize(
    "n,c,seed,t_quantile",
    [
        (1, 1, 0, 0.0),  # single-node graph
        (2, 1, 3, 0.5),
        (150, 1, 1, 0.0),  # full frontier
        (150, 1, 1, 1.0),  # empty frontier: f must pass through unchanged
        (300, 3, 7, 0.5),
        (257, 1, 11, 0.9),  # sparse frontier (occupancy skip exercised)
    ],
)
def test_frontier_round_cases(n, c, seed, t_quantile):
    """Deterministic fallback for the property test (no hypothesis)."""
    _check_frontier_round(n, c, seed, t_quantile)


def test_frontier_round_interleaved_empty_rows():
    """Block rows 1 and 3 own no tiles: the kernel leaves their output
    uninitialised — the epilogue must substitute the kept fluid exactly."""
    bs = 8
    rng = np.random.default_rng(5)
    p = np.zeros((4 * bs, 4 * bs), np.float32)
    p[:bs, :bs] = rng.random((bs, bs)) * 0.1  # block row 0
    p[2 * bs : 3 * bs, bs : 2 * bs] = rng.random((bs, bs)) * 0.1  # row 2
    from repro.kernels.diffusion.ref import dense_to_bsr

    blocks, br, bc = dense_to_bsr(p, bs)
    m = BsrMatrix(blocks, br, bc, 4, bs)
    assert not m.row_occupied[1] and not m.row_occupied[3]
    f = rng.standard_normal(4 * bs).astype(np.float32)
    w = np.ones(4 * bs, np.float32)
    t = 0.5
    fr, sr, rr = frontier_round_ref(blocks, br, bc, f, w, t)
    for backend in ("block", "pallas"):
        fo, so, ro = frontier_round_bsr(
            m, jnp.asarray(f), jnp.asarray(w), jnp.float32(t),
            backend=backend, interpret=True if backend == "pallas" else None)
        np.testing.assert_allclose(np.asarray(fo), fr, rtol=2e-5, atol=2e-5)
        # empty rows keep exactly the un-diffused residual
        keep = np.where(np.abs(f) * w > t, 0.0, f)
        np.testing.assert_allclose(np.asarray(fo)[bs : 2 * bs],
                                   keep[bs : 2 * bs], atol=0)


def test_bsr_spmm_interleaved_empty_rows_masked():
    """bsr_spmm's row-occupancy epilogue zeroes every block row without
    tiles, also when occupied/empty rows interleave."""
    bs = 8
    rng = np.random.default_rng(9)
    p = np.zeros((4 * bs, 4 * bs), np.float32)
    p[:bs] = rng.standard_normal((bs, 4 * bs))
    p[2 * bs : 3 * bs] = rng.standard_normal((bs, 4 * bs))
    from repro.kernels.diffusion.ref import dense_to_bsr

    blocks, br, bc = dense_to_bsr(p, bs)
    m = BsrMatrix(blocks, br, bc, 4, bs)
    x = rng.standard_normal(4 * bs).astype(np.float32)
    out = np.asarray(bsr_spmm(m, jnp.asarray(x)))
    assert np.all(out[bs : 2 * bs] == 0) and np.all(out[3 * bs :] == 0)
    np.testing.assert_allclose(out, p @ x, rtol=2e-4, atol=2e-4)


def test_bsr_gather_spmm_shuffled_pool():
    """The gather kernel consumes tiles from an arbitrarily-ordered pool
    through the visit indirection (the engine's row-owned layout)."""
    bs = 16
    rng = np.random.default_rng(3)
    n_tiles, nrb = 24, 6
    pool = rng.standard_normal((n_tiles, bs, bs)).astype(np.float32) * 0.1
    dst = rng.integers(0, nrb, n_tiles).astype(np.int32)
    col = rng.integers(0, nrb, n_tiles).astype(np.int32)
    x = rng.standard_normal((nrb, bs, 2)).astype(np.float32)
    order = np.argsort(dst, kind="stable").astype(np.int32)
    out = np.asarray(bsr_gather_spmm_pallas(
        jnp.asarray(pool), jnp.asarray(order), jnp.asarray(dst[order]),
        jnp.asarray(col[order]), jnp.asarray(x), nrb, bs=bs,
        interpret=True))
    ref = np.zeros((nrb, bs, 2), np.float32)
    for i in range(n_tiles):
        ref[dst[i]] += pool[i] @ x[col[i]]
    occ = np.zeros(nrb, bool)
    occ[dst] = True
    np.testing.assert_allclose(out[occ], ref[occ], rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------- #
# diffusion / multi-buffered DMA pipeline (buffer_depth > 1)
# --------------------------------------------------------------------------- #
def _frontier_fixture(n=300, c=3, seed=7, bs=64, t_quantile=0.5):
    rng = np.random.default_rng(seed)
    g = power_law_graph(n, seed=seed)
    p, _ = pagerank_system(g)
    m = prepare_bsr(p.indptr, p.indices, p.weights, p.n, bs=bs)
    n_pad = m.n_row_blocks * bs
    f = np.zeros((n_pad, c), np.float32)
    f[: p.n] = rng.standard_normal((p.n, c))
    w = np.zeros(n_pad, np.float32)
    w[: p.n] = 1.0 / np.maximum(np.diff(p.indptr), 1)
    fw = (np.abs(f) * w[:, None]).ravel()
    t = max(float(np.quantile(fw, t_quantile)), 1e-6)
    return m, f, w, t


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_frontier_round_buffer_depths_match_ref(depth):
    """Each pipeline depth reproduces the numpy twin (interpret mode)."""
    m, f, w, t = _frontier_fixture()
    fr, sr, rr = frontier_round_ref(
        np.asarray(m.blocks), np.asarray(m.block_row),
        np.asarray(m.block_col), f, w, t)
    fo, so, ro = frontier_round_bsr(
        m, jnp.asarray(f), jnp.asarray(w), jnp.float32(t),
        backend="pallas", interpret=True, buffer_depth=depth)
    np.testing.assert_allclose(np.asarray(fo), fr, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(so), sr, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("depth", [2, 4])
def test_frontier_round_depth_bit_parity(depth):
    """The multi-buffered ring is BIT-identical to the depth-1 kernel:
    the pipeline reorders DMA issue, never the accumulation order."""
    m, f, w, t = _frontier_fixture()
    out1 = frontier_round_bsr(
        m, jnp.asarray(f), jnp.asarray(w), jnp.float32(t),
        backend="pallas", interpret=True, buffer_depth=1)
    outd = frontier_round_bsr(
        m, jnp.asarray(f), jnp.asarray(w), jnp.float32(t),
        backend="pallas", interpret=True, buffer_depth=depth)
    for a, b in zip(out1, outd):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"depth={depth} not bit-identical to depth=1")


@pytest.mark.parametrize("depth", [2, 4])
def test_gather_spmm_depth_bit_parity(depth):
    """Same bit-parity contract for the engine's gather kernel."""
    bs = 16
    rng = np.random.default_rng(3)
    n_tiles, nrb = 24, 6
    pool = rng.standard_normal((n_tiles, bs, bs)).astype(np.float32) * 0.1
    dst = rng.integers(0, nrb, n_tiles).astype(np.int32)
    col = rng.integers(0, nrb, n_tiles).astype(np.int32)
    x = rng.standard_normal((nrb, bs, 2)).astype(np.float32)
    order = np.argsort(dst, kind="stable").astype(np.int32)
    args = (jnp.asarray(pool), jnp.asarray(order), jnp.asarray(dst[order]),
            jnp.asarray(col[order]), jnp.asarray(x), nrb)
    out1 = np.asarray(bsr_gather_spmm_pallas(
        *args, bs=bs, interpret=True, buffer_depth=1))
    outd = np.asarray(bsr_gather_spmm_pallas(
        *args, bs=bs, interpret=True, buffer_depth=depth))
    assert np.array_equal(out1, outd)


def test_gather_spmm_depth_exceeds_visits():
    """A pipeline deeper than the visit list must still be exact (the
    warmup clamps to n_visits)."""
    bs = 8
    rng = np.random.default_rng(11)
    pool = rng.standard_normal((2, bs, bs)).astype(np.float32)
    dst = np.array([0, 1], np.int32)
    col = np.array([1, 0], np.int32)
    x = rng.standard_normal((2, bs, 1)).astype(np.float32)
    order = np.arange(2, dtype=np.int32)
    out = np.asarray(bsr_gather_spmm_pallas(
        jnp.asarray(pool), jnp.asarray(order), jnp.asarray(dst),
        jnp.asarray(col), jnp.asarray(x), 2, bs=bs, interpret=True,
        buffer_depth=4))
    ref = np.stack([pool[0] @ x[1], pool[1] @ x[0]])
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("n_visits", [0, 1, 11, 24], ids=[
    "none", "one", "some", "all"])
def test_gather_spmm_traced_visit_count(n_visits, depth):
    """With a traced ``n_visits`` the grid runs over the first ``n_visits``
    entries of the visit list only: every row they reach is bit-identical
    to the depth-1 kernel over just those entries, at either depth.  The
    entries after them (valid indices, sorted on their own, as the engine
    lays them out) add nothing.  With no visit the single step the grid
    still runs touches entry 0's row only, which the caller masks."""
    bs = 16
    rng = np.random.default_rng(5)
    n_tiles, nrb = 24, 6
    pool = rng.standard_normal((n_tiles, bs, bs)).astype(np.float32) * 0.1
    dst = rng.integers(0, nrb, n_tiles).astype(np.int32)
    x = rng.standard_normal((nrb, bs, 1)).astype(np.float32)
    # the armed entries first, then the rest, each sorted by destination
    armed = np.zeros(n_tiles, bool)
    armed[rng.permutation(n_tiles)[:n_visits]] = True
    order = np.lexsort((dst, ~armed)).astype(np.int32)
    col = order % nrb
    lists = (jnp.asarray(order), jnp.asarray(dst[order]), jnp.asarray(col))
    out = np.asarray(bsr_gather_spmm_pallas(
        jnp.asarray(pool), *lists, jnp.asarray(x), nrb, bs=bs,
        interpret=True, buffer_depth=depth, n_visits=jnp.int32(n_visits)))
    first = max(n_visits, 1)
    ref = np.asarray(bsr_gather_spmm_pallas(
        jnp.asarray(pool), *(a[:first] for a in lists), jnp.asarray(x),
        nrb, bs=bs, interpret=True, buffer_depth=1))
    reached = np.unique(dst[order[:first]])
    assert np.array_equal(out[reached], ref[reached])
    if n_visits == 0:
        assert reached.tolist() == [dst[order[0]]]
        np.testing.assert_allclose(out[reached[0]], pool[order[0]]
                                   @ x[col[0]], rtol=2e-5, atol=2e-5)
    else:
        expect = np.zeros((nrb, bs, 1), np.float32)
        for i in order[:n_visits]:
            expect[dst[i]] += pool[i] @ x[i % nrb]
        np.testing.assert_allclose(out[reached], expect[reached],
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("depth", [2, 4])
def test_dma_kernels_trace_under_x64_with_f32_data(depth):
    """The DMA rings index their slot with an int32 ``rem`` even when
    ``jax_enable_x64`` widens Python ints; f32 data stays f32 and the
    result is bit-identical to the same call without x64."""
    m, f, w, t = _frontier_fixture()

    def both():
        fr = frontier_round_bsr(
            m, jnp.asarray(f), jnp.asarray(w), jnp.float32(t),
            backend="pallas", interpret=True, buffer_depth=depth)
        pool = np.asarray(m.blocks)
        order = np.arange(m.n_blocks, dtype=np.int32)
        x = np.asarray(f).reshape(m.n_row_blocks, m.bs, -1)
        gs = bsr_gather_spmm_pallas(
            jnp.asarray(pool), jnp.asarray(order), m.block_row,
            m.block_col, jnp.asarray(x), m.n_row_blocks, bs=m.bs,
            interpret=True, buffer_depth=depth)
        return [np.asarray(a) for a in (*fr, gs)]

    plain = both()
    with jax.enable_x64(True):
        wide = both()
    for a, b in zip(plain, wide):
        assert a.dtype == b.dtype == np.float32
        assert np.array_equal(a, b)


def _uncompacted_round(m, f, w, t, occupancy_threshold, depth):
    """The round over the whole tile pool in its row-sorted order: every
    tile is a visit, and the weights of the unarmed columns are zeroed so
    that their tiles add exactly nothing and their rows keep their fluid.
    Returns ``(f_new, sent)`` as ``frontier_round_bsr`` would."""
    from repro.kernels.diffusion import frontier_round_bsr_pallas

    f2 = f[:, None] if f.ndim == 1 else f
    bs, c = m.bs, f2.shape[1]
    wt = (jnp.asarray(w) / jnp.float32(t)).astype(jnp.float32)
    sel = jnp.abs(jnp.asarray(f2)) * wt[:, None] > 1.0
    blk = sel.reshape(-1, bs * c)
    if occupancy_threshold > 0.0:
        col_active = jnp.mean(blk.astype(jnp.float32), axis=1) > \
            occupancy_threshold
    else:
        col_active = jnp.any(blk, axis=1)
    sel = sel & col_active.repeat(bs)[:, None]
    wt = jnp.where(col_active.repeat(bs), wt, 0.0)
    every = jnp.arange(m.n_blocks, dtype=jnp.int32)
    out, _ = frontier_round_bsr_pallas(
        m.blocks, every, m.block_row << 16 | m.block_col,
        jnp.int32(m.n_blocks), col_active.astype(jnp.int32),
        jnp.asarray(f2).reshape(-1, bs, c), wt.reshape(-1, bs, 1),
        m.n_row_blocks, bs=bs, interpret=True, buffer_depth=depth)
    kept = jnp.where(sel, 0.0, jnp.asarray(f2)).reshape(-1, bs, c)
    f_new = np.asarray(jnp.where(m.row_occupied[:, None, None], out, kept))
    sent = np.asarray(jnp.where(sel, jnp.asarray(f2), 0.0))
    return f_new.reshape(f.shape), sent.reshape(f.shape)


def _visit_case(case, c):
    """A small tile pool, fluid, weights and threshold for one visit-list
    case (bs = 8, four block rows)."""
    from repro.kernels.diffusion.ref import dense_to_bsr

    bs, nb = 8, 4
    rng = np.random.default_rng(17)
    p = np.zeros((nb * bs, nb * bs), np.float32)
    band = [(r, q) for r in range(nb) for q in range(nb) if abs(r - q) <= 1]
    if case == "interleaved_empty_rows":
        band = [(0, 0), (0, 1), (2, 1), (2, 3)]
    for r, q in band:
        p[r * bs:(r + 1) * bs, q * bs:(q + 1) * bs] = \
            rng.random((bs, bs)) * 0.1
    if case == "row_unarmed_own_col_armed":
        # block row 3 owns tiles of columns 0 and 1 only, and only column
        # 3 is armed: row 3 is not visited though its own column fires
        p[3 * bs:] = 0.0
        p[3 * bs:, :2 * bs] = rng.random((bs, 2 * bs)) * 0.1
    blocks, br, bc = dense_to_bsr(p, bs)
    m = BsrMatrix(blocks, br, bc, nb, bs)
    f = (rng.standard_normal((nb * bs, c)) * 0.4).astype(np.float32)
    w = np.ones(nb * bs, np.float32)
    t, tau = 1.0, 0.0
    if case == "none_armed":
        t = 1e3
    elif case == "all_armed":
        f[::bs] = 5.0  # one node above threshold in every column
    elif case == "row_unarmed_own_col_armed":
        f[np.abs(f) > t] = 0.0
        f[3 * bs + 2] = 3.0  # only block column 3 fires
    elif case == "occupancy_threshold":
        f[:bs] = 4.0  # column 0 fires densely, the others sparsely
        tau = 0.25
    return m, (f[:, 0] if c == 1 else f), w, t, tau


@pytest.mark.parametrize("c", [1, 8])
@pytest.mark.parametrize("depth", [1, 2, 4])
@pytest.mark.parametrize("case", [
    "none_armed", "all_armed", "row_unarmed_own_col_armed",
    "interleaved_empty_rows", "occupancy_threshold"])
def test_frontier_round_visit_list(case, depth, c):
    """The kernel's grid runs over the round's visit list, the tiles of the
    armed columns: ``f_new`` and ``sent`` are bit-identical to the round
    in the uncompacted tile order, ``res`` agrees to 1e-6 relative, and
    the block backend and the numpy twin agree within float32."""
    from repro.kernels.diffusion import compact_visits

    m, f, w, t, tau = _visit_case(case, c)
    fo, so, ro = frontier_round_bsr(
        m, jnp.asarray(f), jnp.asarray(w), jnp.float32(t),
        backend="pallas", interpret=True, buffer_depth=depth,
        occupancy_threshold=tau)
    fo, so = np.asarray(fo), np.asarray(so)
    fu, su = _uncompacted_round(m, f, w, t, tau, depth)
    assert np.array_equal(fo, fu) and np.array_equal(so, su)
    assert abs(float(ro) - np.abs(fu.astype(np.float64)).sum()) <= \
        1e-6 * max(np.abs(fu.astype(np.float64)).sum(), 1e-30)
    fb, sb, rb = frontier_round_bsr(
        m, jnp.asarray(f), jnp.asarray(w), jnp.float32(t), backend="block",
        occupancy_threshold=tau)
    np.testing.assert_allclose(fo, np.asarray(fb), rtol=2e-5, atol=2e-5)
    assert np.array_equal(so, np.asarray(sb))
    if tau == 0.0:
        fr, sr, rr = frontier_round_ref(
            np.asarray(m.blocks), np.asarray(m.block_row),
            np.asarray(m.block_col), f, w, t)
        np.testing.assert_allclose(fo, fr, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(so, sr, rtol=0, atol=0)
    # the case is what it says: the visit list it makes
    sel = np.abs(so.reshape(-1, m.bs * c)) > 0
    col_active = jnp.asarray(sel.any(axis=1).astype(np.int32))
    visit_tile, visit_rc, n_visits, row_visited = compact_visits(
        m, col_active)
    n = int(n_visits)
    armed = np.asarray(col_active)[np.asarray(m.block_col)] != 0
    assert np.array_equal(np.asarray(visit_tile)[:n], np.flatnonzero(armed))
    packed = np.asarray(m.block_row) << 16 | np.asarray(m.block_col)
    assert np.array_equal(np.asarray(visit_rc), packed[np.asarray(visit_tile)])
    assert np.array_equal(np.asarray(row_visited),
                          np.isin(np.arange(m.n_row_blocks),
                                  np.asarray(m.block_row)[armed]))
    expect = {"none_armed": n == 0, "all_armed": n == m.n_blocks,
              "row_unarmed_own_col_armed":
                  not row_visited[3] and bool(col_active[3]),
              "interleaved_empty_rows":
                  not m.row_occupied[1] and not m.row_occupied[3],
              "occupancy_threshold": 0 < n < int(armed.size)}
    assert expect[case], (case, n)


def test_frontier_round_rejects_bad_depth():
    m, f, w, t = _frontier_fixture(n=100, c=1)
    with pytest.raises(ValueError):
        frontier_round_bsr(m, jnp.asarray(f), jnp.asarray(w),
                           jnp.float32(t), backend="pallas",
                           interpret=True, buffer_depth=0)


def test_occupancy_threshold_defers_exactly():
    """τ > 0 suppresses low-occupancy block columns this round — the
    pallas and block backends agree on the deferred frontier, and τ=0
    reproduces the historical behavior bitwise."""
    m, f, w, t = _frontier_fixture(n=300, c=1, t_quantile=0.9)
    outs = {}
    for backend in ("block", "pallas"):
        fo, so, ro = frontier_round_bsr(
            m, jnp.asarray(f), jnp.asarray(w), jnp.float32(t),
            backend=backend, interpret=True, occupancy_threshold=0.5)
        outs[backend] = np.asarray(fo)
    np.testing.assert_allclose(outs["block"], outs["pallas"],
                               rtol=2e-4, atol=2e-4)
    # τ=0 must be the historical behavior exactly
    f0, _, _ = frontier_round_bsr(
        m, jnp.asarray(f), jnp.asarray(w), jnp.float32(t),
        backend="block", occupancy_threshold=0.0)
    fh, _, _ = frontier_round_bsr(
        m, jnp.asarray(f), jnp.asarray(w), jnp.float32(t),
        backend="block")
    assert np.array_equal(np.asarray(f0), np.asarray(fh))
