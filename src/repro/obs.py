"""Host spans on the profiler's clock.

``span(name)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``: a TraceMe event in the host plane of a profiler capture,
on the clock the device operations are on, so an idle gap of the chip can
be put down to what the host was doing in it.  Spans are always in the
code; with no profiler running each costs one inactive TraceMe and records
nothing.
"""
from __future__ import annotations

__all__ = ["PREFIX", "span"]

PREFIX = "repro."


def span(name: str):
    """A host span named ``repro.<name>``, for use as a context manager."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(PREFIX + name)
