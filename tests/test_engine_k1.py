"""The K-way engine (``engine:bsr``) with one PID at default knobs, on a
host-ordered crawl whose hosts are sorted by out-links (``popsort``).

* the bucket rule: a bucket is the resolved BSR block ``bs``, so a bucket
  is one host and its tiles reach 2 * span_hosts + 1 hosts, in any host
  order; a rescale keeps it;
* the answer: H within the certificate |F|_1 / (1 - d) of a float64 solve,
  and the invariant B = (I - P) H + F with F holding the outboxes, on the
  einsum tile path and on the Pallas gather kernel (interpret mode);
* the push count: two uint32 words a device, exact past 2**31;
* the armed-tile tally: T tiles for each bucket row holding a selected
  node, each local round, banked across a warm start;
* the jitted chunk: compiled once, found again after a kept state is put
  back;
* what it records: ``repro.*`` host spans around the chunk's dispatch, the
  stats read back and the control plane, and ``engine.*`` named scopes on
  the chunk's stages.
"""
import dataclasses
import glob
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import repro
from repro.api.options import SolverOptions
from repro.api.session import _bsr_buckets_per_dev
from repro.core.graph import host_block_graph

HOST = 128
SPAN = 2


def popsort_crawl(n: int, seed: int = 1, links_per_node: float = 8.0):
    """``host_block_graph`` with whole hosts relabelled in descending order
    of their out-links (ties by crawl position)."""
    g = host_block_graph(n, host_size=HOST, links_per_node=links_per_node,
                         span_hosts=SPAN, seed=seed)
    host_links = g.out_degree().reshape(-1, HOST).sum(axis=1)
    hosts = np.argsort(-host_links, kind="stable")
    perm = (hosts[:, None] * HOST + np.arange(HOST)[None, :]).reshape(-1)
    return g.reorder(perm)


@pytest.fixture(scope="module")
def problem():
    return repro.Problem.pagerank(popsort_crawl(2048))


@pytest.fixture(scope="module")
def exact(problem):
    """X of X = P X + B, in float64."""
    p = problem.p.to_dense()
    return np.linalg.solve(np.eye(problem.n) - p, np.asarray(problem.b))


def _session(problem, **kw):
    return repro.SolverSession(problem, method="engine:bsr", k=1, **kw)


# ---- the bucket rule --------------------------------------------------------
@pytest.mark.parametrize("bs", [None, 64])
def test_a_bucket_is_the_resolved_block(problem, bs):
    s = _session(problem, bs=bs)
    a = s._driver.arrays
    block = bs or HOST
    assert a.bucket_size == block
    assert a.n_rows == problem.n // block + SolverOptions().headroom
    # a bucket of one host reaches its own host and 2 * SPAN neighbours,
    # wherever popsort put them; half a host reaches both halves of each
    assert a.tiles.shape[1] == (2 * SPAN + 1) * (HOST // block)


@pytest.mark.parametrize("k", [1, 2, 3, 4])
def test_the_rule_keeps_the_block_at_every_width(k):
    """One rule at construction and on rescale: at any k the buckets hold
    at most ``bs`` nodes, and exactly ``bs`` where k * bs divides N."""
    n = 2**21
    opts = SolverOptions(k=k).validated()
    bpd = _bsr_buckets_per_dev(n, k, opts)
    s = -(-n // (k * (bpd - opts.headroom)))
    assert s == HOST
    assert _bsr_buckets_per_dev(n, k, dataclasses.replace(opts, bs=256)) \
        < bpd


def test_a_rescale_keeps_the_bucket():
    """Two fake devices: k = 2 shrinks to 1 and grows back, and each
    engine it builds has 128-node buckets."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.dirname(__file__)!r})
        import numpy as np
        import repro
        from test_engine_k1 import popsort_crawl

        p = repro.Problem.pagerank(popsort_crawl(2048))
        s = repro.SolverSession(p, method="engine:bsr", k=2)
        sizes = [s._driver.arrays.bucket_size]
        it = s.run()
        next(it)
        s.rescale(1)
        sizes.append(s._driver.arrays.bucket_size)
        s.rescale(2)
        sizes.append(s._driver.arrays.bucket_size)
        rep = s.solve()
        print("SIZES", sizes, rep.converged)
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SIZES [128, 128, 128] True" in out.stdout


# ---- the answer against the plain reference ---------------------------------
@pytest.mark.parametrize("interpret", [False, True],
                         ids=["einsum", "pallas_interpret"])
def test_the_solution_is_within_its_certificate(problem, exact, interpret):
    s = _session(problem, interpret=interpret)
    rep = s.solve()
    assert rep.converged
    f, h = s._driver.fluid()
    d = problem.damping
    # |H - X|_1 <= |F|_1 / (1 - d), plus the float32 rounding of H
    err = np.abs(h - exact).sum()
    assert err <= np.abs(f).sum() / (1 - d) + 1e-5 * np.abs(exact).sum()
    assert rep.residual <= problem.target_error * problem.eps
    # the invariant B = (I - P) H + F
    p = problem.p.to_dense()
    b = np.asarray(problem.b)
    gap = np.abs(b - h + p @ h - f).sum() / np.abs(b).sum()
    assert gap < 1e-5


def test_the_fluid_holds_the_outboxes(problem):
    s = _session(problem)
    next(s.run())
    d = s._driver
    f, h = d.fluid()
    st = d.ex.state
    moved = st.f.reshape(-1)[:HOST]
    d.ex.state = dataclasses.replace(
        st, f=st.f.at[0].set(0.0),
        outbox=st.outbox.at[0, :HOST].add(moved))
    f2, h2 = d.fluid()
    np.testing.assert_array_equal(h2, h)
    np.testing.assert_allclose(f2, f, rtol=0, atol=1e-12)


# ---- the push count ---------------------------------------------------------
def test_push_count_is_exact_past_2_31(problem):
    """A solve whose device counter starts just below 2**32 reads the same
    pushes above it as a twin counted from zero."""
    import jax
    import jax.numpy as jnp

    twin, s = _session(problem), _session(problem)
    start = 2**32 - 1000
    d = s._driver
    words = jnp.asarray([[0, start]], jnp.uint32)
    d.ex.state = dataclasses.replace(
        d.ex.state, ops=jax.device_put(words, d.engine.row_sharding))
    twin_rep = twin.solve()
    rep = s.solve()
    assert twin_rep.n_ops > 1000
    assert rep.n_ops == s.n_ops == start + twin_rep.n_ops > 2**32
    assert isinstance(rep.n_ops, int)


# ---- the armed-tile tally ---------------------------------------------------
def _numpy_armed(d) -> int:
    """T times the bucket rows holding a node the next local round
    selects, counted in numpy from the state before it (k = 1)."""
    st = d.ex.state
    sel = np.abs(np.asarray(st.f)) * np.asarray(d.ex.w) > \
        np.asarray(st.t)[0]
    return d.arrays.tile_dst.shape[1] * int(sel.any(axis=1).sum())


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["einsum", "pallas_interpret"])
def test_armed_tiles_match_a_numpy_count(problem, interpret):
    """One local round a grain: after each, the tally holds the tiles of
    the rows with a selected node, summed over the rounds so far."""
    s = _session(problem, chunk_rounds=1, max_inner=1, interpret=interpret)
    d = s._driver
    it = s.run()
    expected = 0
    for _ in range(6):
        expected += _numpy_armed(d)
        next(it)
        assert s.n_armed_tiles == expected
    assert s.n_rounds == 6
    assert 0 < expected < s.n_rounds * d.arrays.tile_dst.size


def test_armed_tiles_bank_across_warm_start(problem):
    s = _session(problem)
    s.solve(until=1e-2)
    first = s.n_armed_tiles
    assert 0 < first < s.n_rounds * s._driver.arrays.tile_dst.size
    assert s.lifetime_armed_tiles == first
    s.warm_start(np.asarray(problem.b) * 1.01)
    assert s.n_armed_tiles == 0 and s.lifetime_armed_tiles == first
    s.solve(until=1e-2)
    second = s.n_armed_tiles
    assert second > 0
    assert s.lifetime_armed_tiles == first + second


def test_the_per_edge_engine_has_no_armed_tiles(problem):
    s = repro.SolverSession(problem, method="engine:chunk", k=1)
    next(s.run())
    assert s.n_armed_tiles is None and s.lifetime_armed_tiles is None


# ---- the jitted chunk -------------------------------------------------------
class _Compiles:
    """Traces and compiles JAX reports while ``on``."""

    events = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")
    log: list = []
    on = False
    registered = False

    @classmethod
    def listen(cls, event, duration, **_kw):
        if cls.on and event in cls.events:
            cls.log.append(event)

    def __enter__(self):
        import jax

        if not _Compiles.registered:
            jax.monitoring.register_event_duration_secs_listener(
                _Compiles.listen)
            _Compiles.registered = True
        _Compiles.log = []
        _Compiles.on = True
        return _Compiles.log

    def __exit__(self, *exc):
        _Compiles.on = False


def test_the_chunk_compiles_once_and_a_rewind_finds_it(problem):
    s = _session(problem, max_chunks=64)
    d = s._driver
    it = s.run()
    next(it)  # the first chunk traces and compiles
    kept = d.ex.state
    with _Compiles() as log:
        for _ in range(3):
            next(it)
        d.ex.state = kept
        next(it)
    assert log == []
    assert d.engine._chunk._cache_size() == 1


# ---- what the engine records about itself -----------------------------------
def test_a_profile_of_run_holds_the_engine_spans(problem, tmp_path):
    import jax
    from jax.profiler import ProfileData

    s = _session(problem)
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
        assert inside == ["repro.dispatch", "repro.readback.stats",
                          "repro.control"]


def test_the_chunks_stages_carry_engine_scopes(problem):
    s = _session(problem)
    d = s._driver
    hlo = d.engine._chunk.lower(d.ex.state, *d.ex.chunk_operands()) \
        .compile().as_text()
    for scope in ("engine.select", "engine.push", "engine.exchange",
                  "engine.residual"):
        assert scope in hlo, scope
