"""Host spans the harness puts around its calls into each layer.

They land in the profiler's trace on the host's clock, so that the trace
reduction can say what the host was doing in each long device gap.  Outside
a trace they cost a few microseconds.
"""
from __future__ import annotations


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Phases:
    """Spans that also keep their host-clock seconds, for the set-up log."""

    def __init__(self) -> None:
        self.seconds: dict = {}

    def __call__(self, name: str):
        import contextlib
        import time

        @contextlib.contextmanager
        def timed():
            t0 = time.perf_counter()
            with span(f"bench.setup.{name}"):
                yield
            self.seconds[name] = time.perf_counter() - t0

        return timed()
