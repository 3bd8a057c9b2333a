"""host.readback_idle_ms_per_grain: device-idle milliseconds per grain whose
gap midpoint lies in one of the program's ``repro.readback.*`` host spans
(the solver reading |F|_1, the push count, the round count or the tile
tally back from the chip)."""
from benchlib.program import idle_ms_per_grain


def read(ctx):
    return idle_ms_per_grain(ctx, "repro.readback")
