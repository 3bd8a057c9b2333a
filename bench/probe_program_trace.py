"""Run a cell once, traced, and read what the program records about itself
beside what the harness reads.

    python3 bench/probe_program_trace.py --workload webhost21.frontier \
        --seed <n> --seconds <s> [--out PATH]

A traced run of ``run.py`` keeps only the benchmark's ``bench.*`` host
spans and reads no counter but the push count.  This script runs the same
``harness.run_cell`` with three additions, and prints its result line:

* the trace it reduces keeps the program's ``repro.*`` host spans too, on
  the same clock (``Trace.spans``; the ``bench.*`` reductions skip them);
* the window reads ``SolverSession.n_armed_tiles`` at every grain boundary
  (one more readback a grain) and records the tile pool's geometry, as
  ``window.armed_tiles`` and ``window.tile_pool``;
* the per-layer metrics are the cell's and the three that read those:
  ``kernel.frontier_roofline``, ``host.readback_idle_ms_per_grain`` and
  ``host.dispatch_idle_ms_per_grain``.

It adds to the line ``probe``: all device-idle milliseconds per grain and
the part under neither program span (the caller's loop), the idle
seconds that overlap each program span, the window's armed tiles against
every tile of every round, and ``stages``, the device self seconds of the window by the ``frontier.*`` named scope of each
operation, read from the HLO metadata of the grain's compiled program.
Needs the chip, like ``run.py``; the benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"
PROGRAM_PREFIX = "repro."
METRICS = ("kernel.frontier_roofline", "host.readback_idle_ms_per_grain",
           "host.dispatch_idle_ms_per_grain")
SCOPE = re.compile(r"frontier\.[a-z_]+")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*?"
                         r"op_name=\"([^\"]*)\"", re.M)


def read_capture(log_dir: str):
    """From the newest capture under ``log_dir``: every ``repro.*`` host
    event as ``(name, start_ns, end_ns)``, and the device operations of
    each chip under their HLO instruction names with their numbers
    (``abs_reduce_fusion.2``)."""
    from benchlib import trace as tr
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    spans: list = []
    ops: dict = {}
    for plane in ProfileData.from_file(files[-1]).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == tr.OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    (ev.name.split(" = ", 1)[0].strip().lstrip("%"),
                     int(ev.start_ns), int(ev.end_ns))
                    for ev in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend((ev.name, int(ev.start_ns), int(ev.end_ns))
                             for ev in line.events
                             if ev.name.startswith(PROGRAM_PREFIX))
    return spans, ops


def scopes_of(hlo_text: str) -> dict:
    """Instruction name -> the ``frontier.*`` scope of its op_name metadata
    (``other`` where it has none)."""
    out = {}
    for name, op_name in INSTRUCTION.findall(hlo_text):
        m = SCOPE.search(op_name)
        out[name] = m.group(0) if m else "other"
    return out


def stage_seconds(named, scopes: dict) -> dict:
    """Device self seconds in the window by named scope, from a trace
    whose operations keep their instruction names."""
    from benchlib.trace import Trace

    staged = {chip: [(scopes.get(n, "other"), s, e) for n, s, e in evs]
              for chip, evs in named.ops.items()}
    return Trace(staged, named.spans).op_seconds()


def idle_overlap(named) -> dict:
    """Device-idle seconds of the window that overlap each program span
    name (``repro.grain`` aside, whose children do not overlap), averaged
    over the chips; ``neither`` is the rest.  Unlike the readers, which
    give a whole gap to the span over its midpoint, this splits a gap
    between the host's steps."""
    lo, hi = named.window
    spans = [(n, max(a, lo), min(b, hi)) for n, a, b in named.spans
             if n.startswith(PROGRAM_PREFIX) and n != "repro.grain"]
    total: dict = {}
    idle = 0
    for chip in named.ops:
        for s, e in named.idle_gaps(chip):
            idle += e - s
            for n, a, b in spans:
                if min(e, b) > max(s, a):
                    total[n] = total.get(n, 0) + min(e, b) - max(s, a)
    total["neither"] = idle - sum(total.values())
    return {n: v * 1e-9 / len(named.ops) for n, v in total.items()}


def probe(catalog, name: str, seed: int, seconds: float, *, t_start: float,
          trace_dir: str, devs=None, cache_dir=None, marks=()) -> dict:
    import jax

    from benchlib import harness
    from benchlib import trace as tr

    seen: dict = {}
    real_load, real_window = tr.load, harness.run_window

    def load(log_dir):
        t = real_load(log_dir)
        # read before the harness removes the capture
        spans, ops = read_capture(log_dir)
        t = tr.Trace(t.ops, t.spans + spans)
        seen["named"] = tr.Trace(ops, t.spans)
        return t

    def run_window(session, first, seconds_, end, clock):
        s, drv = session.s, session.s._driver
        last = [s.n_armed_tiles]  # at the kept state
        armed = [0]
        grain, rewind = session.grain, session.rewind

        def counted_grain():
            g = grain()
            now = s.n_armed_tiles
            armed[0] += now - last[0]
            last[0] = now
            return g

        def counted_rewind(mark):
            rewind(mark)
            last[0] = s.n_armed_tiles

        session.grain, session.rewind = counted_grain, counted_rewind
        w = real_window(session, first, seconds_, end, clock)
        w.armed_tiles = armed[0]
        w.tile_pool = {"tiles": drv.m.n_blocks, "bs": drv.m.bs,
                       "itemsize": drv.m.blocks.dtype.itemsize,
                       "row_blocks": drv.m.n_row_blocks}
        seen["window"] = w
        # the grain's program, as shapes, for its HLO metadata
        seen["advance"] = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            (drv._state, drv.m, drv.w, drv.out_deg, drv.dang,
             drv.col_tiles))
        seen["static"] = dict(
            gamma=drv.gamma, backend=drv.op_backend,
            interpret=drv.interpret or None, buffer_depth=drv.buffer_depth,
            occupancy_threshold=drv.occupancy_threshold)
        return w

    tr.load, harness.run_window = load, run_window
    try:
        r = harness.run_cell(catalog, name, seed, seconds, t_start=t_start,
                             marks=marks, trace_dir=trace_dir, devs=devs,
                             cache_dir=cache_dir)
    finally:
        tr.load, harness.run_window = real_load, real_window
    w, named = seen["window"], seen["named"]
    out = {"armed_tiles": w.armed_tiles, "tile_pool": w.tile_pool,
           "rounds": w.rounds, "grains": w.grains,
           "armed_share": w.armed_tiles / (w.tile_pool["tiles"] * w.rounds)}
    if named.window is not None and named.ops:
        # every idle gap, and those under neither program span
        program = [(a, b) for n, a, b in named.spans
                   if n.startswith(("repro.readback", "repro.dispatch"))]
        gaps = [g for chip in named.ops for g in named.idle_gaps(chip)]
        per_grain = 1e-6 / len(named.ops) / w.grains
        out["idle_ms_per_grain"] = sum(e - s for s, e in gaps) * per_grain
        out["caller_idle_ms_per_grain"] = sum(
            e - s for s, e in gaps
            if not any(a <= (s + e) // 2 < b for a, b in program)
        ) * per_grain
        out["idle_overlap_s"] = idle_overlap(named)
    from repro.api.session import _bsr_advance

    hlo = _bsr_advance().lower(*seen["advance"], 0.0, 0,
                               **seen["static"]).compile().as_text()
    out["stages"] = stage_seconds(named, scopes_of(hlo))
    r["probe"] = out
    return r


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    from benchlib import device
    from benchlib.spec import Catalog

    with open(ROOT / "BENCHMARK.json") as fh:
        spec = json.load(fh)
    cell = Catalog([BENCH], spec).cell(args.workload)
    try:
        devs = device.require(cell["chips"])
    except device.NoChip as e:
        return e.code
    device.enable_compile_cache(CACHE / "jax")
    r = probe(with_metrics(spec, args.workload), args.workload, args.seed,
              args.seconds, t_start=T_START,
              marks=[("jax", time.perf_counter())],
              trace_dir=str(CACHE / "trace" / "probe"), devs=devs,
              cache_dir=str(CACHE / "graphs"))
    line = json.dumps(r)
    if args.out:
        with open(args.out, "a") as fh:
            fh.write(line + "\n")
    print(line, flush=True)
    return 0


def with_metrics(spec: dict, cell: str, dirs=(BENCH,)):
    """A catalog whose cell ``cell`` also reads :data:`METRICS`."""
    from benchlib.spec import Catalog

    spec = json.loads(json.dumps(spec))
    for m, unit in zip(METRICS, ("%", "ms", "ms")):
        spec["per_layer"].append({"name": m, "workloads": [cell],
                                  "unit": unit, "moves": "decades_per_s"})
    return Catalog(list(dirs), spec)


if __name__ == "__main__":
    sys.exit(main())
