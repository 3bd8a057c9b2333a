"""engine.round_ms: the traced window's wall milliseconds per local round
of the engine (every round of every exchange cycle of every chunk)."""


def read(ctx):
    w = ctx.window
    if w.rounds <= 0:
        return None
    return 1e3 * w.seconds / w.rounds
