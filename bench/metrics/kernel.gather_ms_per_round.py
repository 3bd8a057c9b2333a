"""kernel.gather_ms_per_round: device milliseconds of the engine's tile
kernel (the Mosaic call ``bsr_gather_spmm_pallas``) per local round of the
engine in the traced window; the kernel runs once a local round."""

PATTERN = r"^bsr_gather_spmm_pallas$"


def read(ctx):
    s = ctx.trace.kernel_seconds(PATTERN)
    if s is None or ctx.window.rounds <= 0:
        return None
    return 1e3 * s / ctx.window.rounds
