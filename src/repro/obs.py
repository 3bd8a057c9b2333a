"""Host spans on the profiler's clock, and exact device tallies.

``span(name)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``: a TraceMe event in the host plane of a profiler capture,
on the clock the device operations are on, so an idle gap of the chip can
be put down to what the host was doing in it.  Spans are always in the
code; with no profiler running each costs one inactive TraceMe and records
nothing.

``count_add`` / ``count_value`` keep a counter on the device as two uint32
words, ``[high, low]``: an int32 counter would wrap within one long solve
(about 2**31 edge pushes).
"""
from __future__ import annotations

__all__ = ["PREFIX", "count_add", "count_value", "span"]

PREFIX = "repro."


def span(name: str):
    """A host span named ``repro.<name>``, for use as a context manager."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(PREFIX + name)


def count_add(count, d):
    """Add ``d`` (an int, 0 <= d < 2**32) to the exact tally ``count``:
    ``[high, low]`` uint32 words of ``high * 2**32 + low``."""
    import jax.numpy as jnp

    low = count[1] + d.astype(jnp.uint32)
    return jnp.stack([count[0] + (low < count[1]).astype(jnp.uint32), low])


def count_value(count) -> int:
    """The Python int an exact tally (:func:`count_add`) holds."""
    import numpy as np

    high, low = (int(v) for v in np.asarray(count))
    return high << 32 | low
