"""Roofline analysis of the BSR diffusion kernels (rebuilt for the PR-6
pipelined kernels; the old version predated the BSR kernel and read
``results/dryrun/`` artifacts that no longer exist).

The model lives in :mod:`repro.kernels.tune.model` — bytes are what the
kernels actually move (**active** tiles × tile bytes at the swept
frontier density, plus the fluid streams), flops are the MXU work of the
active tiles only, because the scalar-prefetched occupancy skip makes
inactive tiles free.  Per measured row this module derives:

* ``roofline_fraction`` — ideal-time / measured-time against the
  platform's nominal envelope (interpret/oracle rows land far below 1.0
  by design; the field tracks the *trajectory*, hardware runs move it),
* ``dma_compute_ratio`` — tile-stream DMA time over MXU time: >1 means
  the kernel is DMA-bound and ``buffer_depth`` can only hide (never
  remove) the gap,
* ``arithmetic_intensity`` and the binding wall (memory vs compute).

``annotate_payload`` merges these into BENCH_kernels.json rows at emit
time; ``build_table`` recomputes them from a committed artifact.
"""
from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.kernels.tune.model import (  # noqa: E402
    dma_compute_ratio,
    frontier_round_cost,
    hw_spec,
    ideal_time_s,
    roofline_fraction,
)

BENCH_PATH = "BENCH_kernels.json"

# the timed columns of a frontier-sweep row, and which tile population
# each one touches (the skip path only moves/multiplies active tiles)
_MEASURED_COLS = (
    ("pallas_skip_us", "n_blocks_active"),
    ("bsr_full_us", "n_blocks"),
)


def analyse_row(row: Dict, bs: int, device_kind: str) -> Optional[Dict]:
    """Roofline terms for one sweep row; None for skipped rows."""
    if "skipped" in row or "n" not in row:
        return None
    spec = hw_spec(device_kind)
    n, c = int(row["n"]), int(row["c"])
    n_row_blocks = -(-n // bs)
    out: Dict = {}
    for col, pop in _MEASURED_COLS:
        if row.get(col) is None or pop not in row:
            continue
        cost = frontier_round_cost(n_row_blocks, bs, c, int(row[pop]))
        ideal_s, bound = ideal_time_s(cost, spec)
        frac = roofline_fraction(row[col] * 1e-6, ideal_s)
        out[col] = {
            "bytes": cost.total_bytes,
            "flops": cost.flops,
            "arithmetic_intensity": round(cost.arithmetic_intensity, 4),
            "ideal_us": round(ideal_s * 1e6, 3),
            "bound": bound,
            "dma_compute_ratio": round(dma_compute_ratio(cost, spec), 3),
            "roofline_fraction": round(frac, 6),
        }
    return out or None


def annotate_payload(payload: Dict) -> Dict:
    """Merge roofline fields into sweep rows, in place (emit-time hook).

    The headline ``roofline_fraction`` / ``dma_compute_ratio`` of a row
    follow its occupancy-skip measurement (the deployable path); the
    full-path fraction keeps a ``full_`` prefix.
    """
    meta = payload.get("meta", {})
    device_kind = meta["device_kind"]
    bs = int(meta.get("bs", 128))
    for row in payload.get("rows", []):
        terms = analyse_row(row, bs, device_kind)
        if terms is None:
            continue
        row.setdefault("buffer_depth", 1)
        skip = terms.get("pallas_skip_us")
        if skip is not None:
            row["roofline_fraction"] = skip["roofline_fraction"]
            row["dma_compute_ratio"] = skip["dma_compute_ratio"]
            row["arithmetic_intensity"] = skip["arithmetic_intensity"]
        full = terms.get("bsr_full_us")
        if full is not None:
            row["full_roofline_fraction"] = full["roofline_fraction"]
    return payload


def build_table(bench_path: str = BENCH_PATH) -> List[Dict]:
    """Roofline table recomputed from a BENCH_kernels.json artifact."""
    if not os.path.exists(bench_path):
        return []
    with open(bench_path) as fh:
        payload = json.load(fh)
    meta = payload.get("meta", {})
    device_kind = meta["device_kind"]
    bs = int(meta.get("bs", 128))
    table: List[Dict] = []
    for row in payload.get("rows", []):
        terms = analyse_row(row, bs, device_kind)
        if terms is None:
            continue
        skip = terms.get("pallas_skip_us") or terms.get("bsr_full_us")
        if skip is None:
            continue
        table.append({
            "n": row["n"],
            "c": row["c"],
            "density": row["density"],
            "bs": bs,
            "buffer_depth": row.get("buffer_depth", 1),
            "measured_us": row.get("pallas_skip_us",
                                   row.get("bsr_full_us")),
            "ideal_us": skip["ideal_us"],
            "arithmetic_intensity": skip["arithmetic_intensity"],
            "bound": skip["bound"],
            "dma_compute_ratio": skip["dma_compute_ratio"],
            "roofline_fraction": skip["roofline_fraction"],
        })
    return table


def main(argv=None) -> int:
    path = BENCH_PATH
    if argv and argv[0] not in ("-h", "--help"):
        path = argv[0]
    table = build_table(path)
    if not table:
        print(f"no analysable rows in {path} — run "
              "python -m benchmarks.kernel_bench --sweep first")
        return 1
    print("n,c,density,depth,measured_us,ideal_us,ai,bound,"
          "dma_compute_ratio,roofline_fraction")
    for r in table:
        print(f"{r['n']},{r['c']},{r['density']},{r['buffer_depth']},"
              f"{r['measured_us']},{r['ideal_us']},"
              f"{r['arithmetic_intensity']},{r['bound']},"
              f"{r['dma_compute_ratio']},{r['roofline_fraction']}")
    membound = sum(1 for r in table if r["bound"] == "memory")
    print(f"# {len(table)} rows; {membound} memory-bound, "
          f"{len(table) - membound} compute-bound")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
