"""Named host spans at the boundaries of the memory hierarchy.

``span("stream.wait", group=3)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.stream.wait``.  It lands in the profiler's trace on the thread that
entered it, on the clock the device's events are put on, so a trace can say
what the host was doing while the device waited.  Outside a profiler trace
a span costs about a microsecond.

Names are ``repro.<layer>.<what>``.  A name ends in ``.wait`` exactly when
its thread is blocked on another thread or on the device.  Spans are per
step, per group or per page (never per leaf), and their metadata is small
integers and flags only.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["span"]


def span(name: str, **meta: int) -> TraceAnnotation:
    return TraceAnnotation(f"repro.{name}", **meta)
