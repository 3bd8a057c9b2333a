"""Reductions of what the program records about itself: its ``repro.*``
host spans, and the armed-tile tally of the fused frontier round.

The readers of ``metrics/`` that use these find what they read in a traced
run's context: the spans in ``ctx.trace.spans``, beside the benchmark's
``bench.*`` spans and on the same clock; the tally in
``ctx.window.armed_tiles`` (tiles the kernel's column predicate admitted,
summed over the window's rounds) and the pool's geometry in
``ctx.window.tile_pool`` (``tiles``, ``bs``, ``itemsize``,
``row_blocks``).  Where the program or the run gives none of these, they
return None.
"""
from __future__ import annotations

from typing import Optional

# the fluid, the selection weights, the output and the row residual are
# float32; a solve has one right-hand side
FLUID_BYTES = 4
LANES = 1


def idle_ms_per_grain(ctx, prefix: str) -> Optional[float]:
    """Device-idle milliseconds per grain of the window whose gap midpoint
    lies in a host span named ``prefix``..., averaged over the chips."""
    t = ctx.trace
    spans = [(s, e) for name, s, e in t.spans if name.startswith(prefix)]
    if not spans or t.window is None or not t.ops or ctx.window.grains <= 0:
        return None
    idle_ns = 0
    for chip in sorted(t.ops):
        for s, e in t.idle_gaps(chip):
            mid = (s + e) // 2
            if any(a <= mid < b for a, b in spans):
                idle_ns += e - s
    return idle_ns * 1e-6 / len(t.ops) / ctx.window.grains


def necessary_bytes(armed_tiles: int, rounds: int, pool: dict) -> int:
    """HBM bytes the window's fused rounds could not do without: each armed
    tile with its gathered fluid and weights, and per round each row
    block's seed reads, output and row residual."""
    bs, c = int(pool["bs"]), LANES
    tile = bs * bs * int(pool["itemsize"])
    gathered = (bs * c + bs) * FLUID_BYTES
    per_row = (2 * (bs * c + bs) + bs * c + 1) * FLUID_BYTES
    return (armed_tiles * (tile + gathered)
            + rounds * int(pool["row_blocks"]) * per_row)
