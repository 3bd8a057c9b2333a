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
from repro.kernels.attention import attention_ref, flash_attention
from repro.kernels.diffusion import (
    BsrMatrix,
    bsr_gather_spmm_pallas,
    bsr_spmm,
    bsr_spmm_ref,
    frontier_round_bsr,
    frontier_round_ref,
    prepare_bsr,
)
from repro.kernels.fm import (
    fm_interaction,
    fm_interaction_naive,
    fm_interaction_ref,
)
from repro.kernels.segment import (
    embedding_bag,
    embedding_bag_ref,
    segment_sum_ref,
    segment_sum_sorted,
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


# --------------------------------------------------------------------------- #
# segment
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "e,d,s", [(100, 4, 7), (513, 8, 64), (2048, 32, 500), (4096, 128, 11)]
)
def test_segment_sum_shapes(e, d, s):
    seg = np.sort(RNG.integers(0, s, e)).astype(np.int32)
    data = RNG.standard_normal((e, d)).astype(np.float32)
    out = np.asarray(segment_sum_sorted(jnp.asarray(data), jnp.asarray(seg), s))
    ref = np.asarray(segment_sum_ref(jnp.asarray(data), jnp.asarray(seg), s))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def _check_segment_sum(e, d, s, seed):
    rng = np.random.default_rng(seed)
    seg = np.sort(rng.integers(0, s, e)).astype(np.int32)
    data = rng.standard_normal((e, d)).astype(np.float32)
    out = np.asarray(
        segment_sum_sorted(jnp.asarray(data), jnp.asarray(seg), s, tile=128)
    )
    ref = np.asarray(segment_sum_ref(jnp.asarray(data), jnp.asarray(seg), s))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


if HAVE_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(
        e=st.integers(1, 600),
        d=st.sampled_from([1, 3, 8]),
        s=st.integers(1, 64),
        seed=st.integers(0, 1000),
    )
    def test_segment_sum_property(e, d, s, seed):
        _check_segment_sum(e, d, s, seed)


@pytest.mark.parametrize(
    "e,d,s,seed", [(1, 1, 1, 0), (257, 3, 5, 11), (600, 8, 64, 3)]
)
def test_segment_sum_property_cases(e, d, s, seed):
    """Deterministic fallback for the property test (no hypothesis)."""
    _check_segment_sum(e, d, s, seed)


@pytest.mark.parametrize("mode", ["sum", "mean", "max"])
def test_embedding_bag_modes(mode):
    table = RNG.standard_normal((500, 16)).astype(np.float32)
    ids = RNG.integers(0, 500, (32, 8)).astype(np.int32)
    o = np.asarray(embedding_bag(jnp.asarray(table), jnp.asarray(ids),
                                 mode=mode))
    r = np.asarray(embedding_bag_ref(jnp.asarray(table), jnp.asarray(ids),
                                     mode=mode))
    np.testing.assert_allclose(o, r, rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# fm
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("b,f,d", [(7, 5, 4), (300, 39, 10), (256, 26, 32)])
def test_fm_vs_naive(b, f, d):
    v = RNG.standard_normal((b, f, d)).astype(np.float32)
    o = np.asarray(fm_interaction(jnp.asarray(v)))
    r = np.asarray(fm_interaction_ref(jnp.asarray(v)))
    n = np.asarray(fm_interaction_naive(jnp.asarray(v)))
    np.testing.assert_allclose(o, r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(r, n, rtol=1e-2, atol=1e-2)


def _check_fm(b, f, d, seed):
    rng = np.random.default_rng(seed)
    v = rng.standard_normal((b, f, d)).astype(np.float32)
    o = np.asarray(fm_interaction(jnp.asarray(v)))
    n = np.asarray(fm_interaction_naive(jnp.asarray(v)))
    np.testing.assert_allclose(o, n, rtol=5e-2, atol=5e-2)


if HAVE_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(
        b=st.integers(1, 300),
        f=st.integers(2, 40),
        d=st.integers(1, 32),
        seed=st.integers(0, 1000),
    )
    def test_fm_property(b, f, d, seed):
        _check_fm(b, f, d, seed)


@pytest.mark.parametrize(
    "b,f,d,seed", [(1, 2, 1, 0), (17, 13, 7, 9), (300, 40, 32, 5)]
)
def test_fm_property_cases(b, f, d, seed):
    """Deterministic fallback for the property test (no hypothesis)."""
    _check_fm(b, f, d, seed)


# --------------------------------------------------------------------------- #
# attention
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "b,hq,hkv,s,dh,causal",
    [
        (2, 4, 2, 256, 64, True),
        (1, 8, 1, 128, 32, True),  # MQA
        (2, 4, 4, 384, 64, False),  # MHA bidirectional
        (1, 2, 1, 100, 64, True),  # padded seq
        (1, 16, 2, 128, 128, True),
    ],
)
def test_flash_attention(b, hq, hkv, s, dh, causal):
    q = (RNG.standard_normal((b, hq, s, dh)) * 0.2).astype(np.float32)
    k = (RNG.standard_normal((b, hkv, s, dh)) * 0.2).astype(np.float32)
    v = RNG.standard_normal((b, hkv, s, dh)).astype(np.float32)
    o = np.asarray(
        flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        causal=causal)
    )
    r = np.asarray(
        attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                      causal=causal)
    )
    np.testing.assert_allclose(o, r, rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    q = (RNG.standard_normal((1, 4, 128, 64)) * 0.2).astype(jnp.bfloat16)
    k = (RNG.standard_normal((1, 2, 128, 64)) * 0.2).astype(jnp.bfloat16)
    v = RNG.standard_normal((1, 2, 128, 64)).astype(jnp.bfloat16)
    o = flash_attention(q, k, v, causal=True)
    r = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(r, np.float32),
        rtol=3e-2, atol=3e-2,
    )
