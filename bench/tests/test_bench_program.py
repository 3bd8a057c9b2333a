"""The readers of what the program records about itself: exact on a small
made-up trace with ``repro.*`` spans, silent where the program records
nothing, and without effect on the reductions of the ``bench.*`` spans."""
from pathlib import Path

import pytest

import benchtoy
import probe_program_trace as probe
from benchlib.peaks import PEAKS
from benchlib.program import necessary_bytes
from benchlib.spec import Catalog
from benchlib.trace import Trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns
POOL = {"tiles": 10, "bs": 128, "itemsize": 4, "row_blocks": 4}


def _reader(name):
    return Catalog([benchtoy.BENCH], benchtoy.spec()).metric(name)


class _Window:
    def __init__(self, rounds=2, grains=2, armed_tiles=None, tile_pool=None):
        self.rounds, self.grains = rounds, grains
        if armed_tiles is not None:
            self.armed_tiles, self.tile_pool = armed_tiles, tile_pool


class _Ctx:
    def __init__(self, trace, window):
        self.trace, self.window = trace, window


def made_up(program_spans=True) -> Trace:
    """Two grains, whose loops run [1, 4] and [6, 9] ms with the kernel
    2 ms of each; the chip idles [0, 1], [4, 6] and [9, 12] ms."""
    ops = {0: [["while", 1 * MS, 4 * MS],
               ["frontier_round_bsr_pallas", 1 * MS, 3 * MS],
               ["while", 6 * MS, 9 * MS],
               ["frontier_round_bsr_pallas", 6 * MS, 8 * MS]]}
    spans = [["bench.window", 0, 12 * MS],
             ["bench.readback", 3 * MS, 5 * MS]]
    if program_spans:
        spans += [["repro.grain", 0, 5 * MS],
                  ["repro.dispatch", 0, 1 * MS + MS // 2],
                  ["repro.readback.residual", 3 * MS, 5 * MS + MS // 2],
                  ["repro.grain", 5 * MS + MS // 2, 11 * MS],
                  ["repro.dispatch", 5 * MS + MS // 2, 6 * MS],
                  ["repro.readback.ops", 9 * MS, 11 * MS]]
    return Trace(ops, spans)


def test_idle_readers_are_exact_on_a_made_up_trace():
    ctx = _Ctx(made_up(), _Window())
    # readback: [4, 6] (mid 5, in [3, 5.5]) and [9, 12] (mid 10.5, in
    # [9, 11])
    assert _reader("host.readback_idle_ms_per_grain").read(ctx) == (
        pytest.approx((2 + 3) / 2))
    # dispatch: [0, 1] (mid 0.5, in [0, 1.5])
    assert _reader("host.dispatch_idle_ms_per_grain").read(ctx) == (
        pytest.approx(1 / 2))


def test_roofline_is_exact_on_a_made_up_trace():
    ctx = _Ctx(made_up(), _Window(armed_tiles=7, tile_pool=POOL))
    need = (7 * (128 * 128 * 4 + (128 + 128) * 4)
            + 2 * 4 * (2 * (128 + 128) + 128 + 1) * 4)
    assert necessary_bytes(7, 2, POOL) == need
    reader = _reader("kernel.frontier_roofline")
    peak = PEAKS["TPU v5 lite"]
    assert reader.roofline(ctx, peak) == pytest.approx(
        100 * need / (4e-3 * 819e9))


@pytest.mark.parametrize("name", ["kernel.frontier_roofline",
                                  "host.readback_idle_ms_per_grain",
                                  "host.dispatch_idle_ms_per_grain"])
def test_readers_are_silent_where_the_program_records_nothing(name):
    """A program without the spans or the tally (and a run on the CPU,
    which has no peak) gives no reading, and no error."""
    ctx = _Ctx(made_up(program_spans=False), _Window())
    assert _reader(name).read(ctx) is None


def test_program_spans_leave_the_bench_reductions_as_they_were():
    """The recorded chip trace, with and without ``repro.*`` spans over
    its readbacks: the same busy time, idle gaps, breakdown and readings
    of the accepted readers."""
    t = Trace.from_json(str(DATA / "frontier_trace.json.gz"))
    extra = [["repro.readback.residual", s, e] for n, s, e in t.spans
             if n == "bench.readback"]
    u = Trace(t.ops, [list(s) for s in t.spans] + extra)
    win = _Window(rounds=128, grains=4)
    for tr in (t, u):
        assert tr.busy_s() == pytest.approx(5.874920230000001, rel=1e-12)
        assert tr.gap_attribution() == pytest.approx(
            {"bench.readback": 0.016345519000000003,
             "bench.advance": 2.466000000000001e-06,
             "bench.window": 2.4910000000000012e-06}, rel=1e-12)
        top = tr.breakdown()
        assert top["device_ops"][:3] == [
            ["frontier_round_bsr_pallas", pytest.approx(4.878991918000001)],
            ["abs_reduce_fusion", pytest.approx(0.5450246249999998)],
            ["copy", pytest.approx(0.420045673)]]
        ctx = _Ctx(tr, win)
        assert _reader("device.idle_pct").read(ctx) == pytest.approx(
            100 * (1 - 5.874920230000001 / 5.891270706), rel=1e-12)
        assert _reader("kernel.frontier_ms_per_round").read(
            ctx) == pytest.approx(1e3 * 4.878991918000001 / 128)
    assert u.breakdown() == t.breakdown()
    assert _reader("host.readback_idle_ms_per_grain").read(
        _Ctx(u, win)) == pytest.approx(
            1e3 * 0.016345519000000003 / 4, rel=1e-6)


def test_stage_seconds_follow_the_named_scopes_of_the_hlo():
    hlo = """
  %abs_reduce_fusion.2 = f32[] fusion(%a), kind=kLoop, metadata={op_name="jit(advance)/while/body/frontier.residual/reduce_sum"}
  %copy.19 = f32[8] copy(%b), metadata={op_name="jit(advance)/while/body/frontier.select/div"}
  ROOT %while.1 = (f32[]) while(%t), metadata={op_name="jit(advance)/while"}
"""
    scopes = probe.scopes_of(hlo)
    assert scopes == {"abs_reduce_fusion.2": "frontier.residual",
                      "copy.19": "frontier.select", "while.1": "other"}
    named = Trace({0: [["while.1", 0, 10 * MS],
                       ["abs_reduce_fusion.2", 1 * MS, 3 * MS],
                       ["copy.19", 4 * MS, 5 * MS],
                       ["copy.7", 6 * MS, 7 * MS]]},
                  [["bench.window", 0, 10 * MS]])
    assert probe.stage_seconds(named, scopes) == pytest.approx(
        {"other": 7e-3, "frontier.residual": 2e-3,
         "frontier.select": 1e-3})


def test_idle_overlap_splits_a_gap_between_the_host_steps():
    # gaps [0, 1], [4, 6], [9, 12] ms; spans cut them into pieces
    parts = probe.idle_overlap(made_up())
    assert parts == pytest.approx({
        "repro.dispatch": 1.5e-3,  # [0, 1] and [5.5, 6]
        "repro.readback.residual": 1.5e-3,  # [4, 5.5]
        "repro.readback.ops": 2e-3,  # [9, 11]
        "neither": 1e-3})  # [11, 12]
