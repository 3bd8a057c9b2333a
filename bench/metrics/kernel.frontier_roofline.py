"""kernel.frontier_roofline: the fused frontier kernel's share of the chip's
HBM roofline, in %: 100 * the bytes the window's rounds needed (the armed
tiles and what each round reads and writes per row block;
``benchlib.program.necessary_bytes``) / (the kernel's device seconds * the
chip's HBM bytes/s from ``benchlib.peaks``).  The kernel's products are
thin (one lane), so bandwidth, not the flop peak, is its roof.  A kernel
that reads at least its armed tiles reads at most 100%."""
from benchlib.peaks import PEAKS
from benchlib.program import necessary_bytes

PATTERN = r"^frontier_round_bsr_pallas$"


def roofline(ctx, peak):
    w = ctx.window
    armed = getattr(w, "armed_tiles", None)
    pool = getattr(w, "tile_pool", None)
    s = ctx.trace.kernel_seconds(PATTERN)
    if peak is None or armed is None or not pool or not s or w.rounds <= 0:
        return None
    return 100.0 * necessary_bytes(armed, w.rounds, pool) / (
        s * peak.hbm_bytes_per_s)


def read(ctx):
    import jax

    return roofline(ctx, PEAKS.get(jax.devices()[0].device_kind))
