"""host.dispatch_idle_ms_per_grain: device-idle milliseconds per grain whose
gap midpoint lies in the program's ``repro.dispatch`` host span (the host
handing the next grain's jitted loop to the chip)."""
from benchlib.program import idle_ms_per_grain


def read(ctx):
    return idle_ms_per_grain(ctx, "repro.dispatch")
