"""The engine cell's parts at toy size: entry ``engine`` drives the K-way
engine through ``repro.SolverSession`` and replays its span exactly, its
fluid holds the outboxes, its set-up refuses a layout that does not fit the
device, and the gather kernel's readers read what the program builds."""
import json
import time

import numpy as np
import pytest

import benchtoy
from benchlib import harness
from benchlib.peaks import PEAKS
from benchlib.spec import Catalog
from benchlib.trace import Trace

CELLS = {"toy.engine-k1": "popsort21.engine-k1"}
MS = 1_000_000  # ns


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    return benchtoy.toy_catalog(tmp_path_factory.mktemp("engine"), CELLS)


def _reader(name):
    return Catalog([benchtoy.BENCH], benchtoy.spec()).metric(name)


def _open(catalog, seed=2**31 + 5):
    cell = catalog.cell("toy.engine-k1")
    config = catalog.config(cell["config"])
    traffic = catalog.traffic(cell["traffic"])
    inst = harness.build_instance(catalog, config, seed)
    entry = catalog.entry(traffic["entry"]).prepare(inst, traffic)
    return inst, entry, entry.open()


def test_the_toy_cell_runs_the_engine_and_is_correct(catalog, tmp_path):
    r = harness.run_cell(catalog, "toy.engine-k1", 2**31 + 11, 1.0,
                         t_start=time.perf_counter(),
                         trace_dir=str(tmp_path / "trace"),
                         clock=benchtoy.StepClock(0.1))
    assert r["correct"], r["checks"]
    w = r["window"]
    assert w["method"] == "engine:bsr"
    assert w["compiles"] == 0
    # one PID: nothing is remote, so every exchange cycle runs all its
    # local rounds, 4 cycles of 8 a chunk
    assert w["rounds"] == 32 * w["grains"]
    assert r["metrics"]["engine.round_ms"]["value"] == pytest.approx(
        1e3 * w["seconds"] / w["rounds"])
    # no chip, no kernel in the trace: the device readers stay silent
    assert "kernel.gather_ms_per_round" not in r["metrics"]
    assert "kernel.gather_roofline" not in r["metrics"]


def test_the_session_builds_one_host_buckets(catalog):
    _inst, _entry, s = _open(catalog)
    a = s.s._driver.arrays
    params = catalog.config("webhost-popsort-2e21-toy")["params"]
    assert a.bucket_size == params["host_size"]
    assert a.tiles.shape[1] == 2 * params["span_hosts"] + 1


def test_a_rewind_replays_the_span_exactly(catalog):
    _inst, _entry, s = _open(catalog)
    s.grain()
    mark = s.mark()
    d = s.s._driver
    kept = (d._chunks, d._resid, s.s.n_ops, s.s.n_rounds)
    first = [s.grain() for _ in range(3)]
    h_first = s.history()
    s.rewind(mark)
    assert (d._chunks, d._resid, s.s.n_ops, s.s.n_rounds) == kept
    again = [s.grain() for _ in range(3)]
    assert again == first
    np.testing.assert_array_equal(s.history(), h_first)


def test_the_fluid_holds_the_outboxes(catalog):
    import dataclasses

    import jax.numpy as jnp

    _inst, _entry, s = _open(catalog)
    s.grain()
    h, f = s.fluid()
    ex = s.s._driver.ex
    st = ex.state
    # move the fluid of the first two bucket rows into the outbox, as a
    # local round leaves remote fluid there before the exchange
    moved = st.f.reshape(-1)[: 2 * st.f.shape[1]]
    ex.state = dataclasses.replace(
        st, f=st.f.at[:2].set(0.0),
        outbox=st.outbox.at[0, : moved.shape[0]].add(moved))
    assert float(jnp.abs(ex.state.f[:2]).sum()) == 0.0
    h2, f2 = s.fluid()
    np.testing.assert_array_equal(h2, h)
    np.testing.assert_allclose(f2, f, rtol=0, atol=1e-12)


def test_a_layout_that_does_not_fit_the_device_stops_set_up(catalog):
    inst, entry, _s = _open(catalog)
    mod = catalog.entry("engine")
    traffic = catalog.traffic("solve.engine-k1")
    lay = mod.layout_bytes(inst.indptr, inst.indices, inst.n,
                           traffic["options"])
    need = lay["tile_bytes"] + lay["edge_bytes"]
    mod.Entry(inst, traffic, limit=need)  # fits exactly
    with pytest.raises(RuntimeError, match="does not fit"):
        mod.Entry(inst, traffic, limit=need - 1)


def test_the_layout_reckoning_is_the_programs(catalog):
    inst, entry, s = _open(catalog)
    a = s.s._driver.arrays
    lay = catalog.entry("engine").layout_bytes(
        inst.indptr, inst.indices, inst.n, entry.options)
    assert lay["rows"] == a.n_rows
    assert lay["bucket"] == a.bucket_size
    assert lay["tiles_per_row"] == a.tiles.shape[1]
    assert lay["edge_cap"] == a.edge_cap


def test_the_roofline_tile_count_is_the_programs_layout():
    """At 2^18 pages (2048 hosts), the reader's tiles a round lie within
    0.1% above the distinct (bucket, destination bucket) pairs of the
    layout the program's bucket rule builds, and below its R * T slots."""
    import repro
    from repro.api.options import SolverOptions
    from repro.api.session import _bsr_buckets_per_dev
    from repro.core.graph import CSRGraph
    from repro.graph import GraphStore

    with open(benchtoy.BENCH / "configs" / "webhost-popsort-2e21.json") as fh:
        params = dict(json.load(fh)["params"], n=2**18, links_per_node=20.0)
    gen = Catalog([benchtoy.BENCH], benchtoy.spec()).graph("host_block")
    indptr, indices, n = gen.generate(params, 2**31 + 3)
    g = CSRGraph(indptr=indptr, indices=indices,
                 weights=np.ones(indices.shape[0]), n=n)
    problem = repro.Problem.pagerank(g)
    opts = SolverOptions(k=1).validated()
    bpd = _bsr_buckets_per_dev(n, 1, opts)
    lay = GraphStore.from_csr(problem.p).engine_layout(
        1, bpd, opts.headroom)
    assert lay.bucket_size == params["host_size"]
    real = lay.wgt != 0
    rows = np.broadcast_to(np.arange(lay.n_rows)[:, None], real.shape)
    pairs = np.unique(rows[real].astype(np.int64) * lay.n_rows
                      + lay.dst_bucket[real])
    t_cap = np.bincount(pairs // lay.n_rows, minlength=lay.n_rows).max()
    reckoned = _reader("kernel.gather_roofline").tiles_per_round(params)
    assert pairs.size <= reckoned <= lay.n_rows * t_cap
    assert reckoned - pairs.size <= 1e-3 * pairs.size


class _Window:
    def __init__(self, rounds):
        self.rounds, self.seconds, self.grains = rounds, 1.0, 1


class _Ctx:
    def __init__(self, trace, window, params):
        self.trace, self.window = trace, window
        self.config = {"params": params}


def _kernel_trace(kernel_ns):
    return Trace({0: [["bsr_gather_spmm_pallas", 1 * MS,
                       1 * MS + kernel_ns]]},
                 [["bench.window", 0, 2 * MS + kernel_ns]])


def test_the_gather_readers_are_exact_on_a_made_up_trace():
    params = {"n": 4096, "host_size": 128, "span_hosts": 2}
    ctx = _Ctx(_kernel_trace(4 * MS), _Window(rounds=2), params)
    need = 2 * (32 * 5) * (128 * 128 + 2 * 128) * 4
    peak = PEAKS["TPU v5 lite"]
    assert _reader("kernel.gather_roofline").roofline(ctx, peak) == (
        pytest.approx(100 * need / (4e-3 * 819e9)))
    assert _reader("kernel.gather_ms_per_round").read(ctx) == (
        pytest.approx(2.0))


def test_a_kernel_reading_its_whole_pool_at_the_peak_reads_under_100():
    """The fastest the kernel can be: every slot of the toy pool ([R, T, S,
    S], R = hosts + 2 headroom rows), with its fluid, at the HBM peak."""
    params = {"n": 4096, "host_size": 128, "span_hosts": 2}
    peak = PEAKS["TPU v5 lite"]
    rounds, slots = 3, (32 + 2) * 5
    pool_bytes = rounds * slots * (128 * 128 + 2 * 128) * 4
    ns = int(pool_bytes / peak.hbm_bytes_per_s * 1e9)
    ctx = _Ctx(_kernel_trace(ns), _Window(rounds=rounds), params)
    share = _reader("kernel.gather_roofline").roofline(ctx, peak)
    assert 90.0 < share < 100.0


@pytest.mark.parametrize("name", ["kernel.gather_ms_per_round",
                                  "kernel.gather_roofline"])
def test_the_gather_readers_are_silent_without_the_kernel(name):
    params = {"n": 4096, "host_size": 128, "span_hosts": 2}
    trace = Trace({0: [["frontier_round_bsr_pallas", 1 * MS, 2 * MS]]},
                  [["bench.window", 0, 3 * MS]])
    ctx = _Ctx(trace, _Window(rounds=2), params)
    assert _reader(name).read(ctx) is None


@pytest.fixture(scope="module")
def control_catalog(tmp_path_factory):
    return benchtoy.toy_catalog(tmp_path_factory.mktemp("engine-control"),
                                CELLS, n=2**16)


@pytest.mark.parametrize("precision, correct", [("high", False),
                                                ("highest", True)])
def test_the_control_fails_the_engine_cells_limits(control_catalog,
                                                   precision, correct):
    """The plain reference in the engine's place, over the cell's span at
    2^16 pages: at ``Precision.HIGH`` not correct, at ``HIGHEST`` correct,
    under this cell's limits."""
    r = harness.run_cell(
        control_catalog, "toy.engine-k1", 2**31 + 77, 0.0,
        t_start=time.perf_counter(),
        traffic_override={"entry": "reference", "precision": precision},
        clock=benchtoy.StepClock(0.125))
    assert r["window"]["spans"] == 1
    assert r["correct"] is correct, r["checks"]
