"""Host spans of the sweep path, on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``:
with the profiler off it is an inactive trace event, and in a traced run
it lands on the host plane beside the device's ``XLA Ops``, so an idle
gap of the device can be put down to the host phase around it.  Spans
mark phases of a request (validate, plan, place, dispatch, wait,
unpack), never single lanes, chunks or cycles.
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str) -> jax.profiler.TraceAnnotation:
    """The host span ``repro.<name>``, to use as a context manager."""
    return jax.profiler.TraceAnnotation(PREFIX + name)
