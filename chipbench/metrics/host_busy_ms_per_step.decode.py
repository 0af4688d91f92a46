"""Milliseconds per decode step (``repro.serve.step``) in which the main
thread was busy, not blocked in a ``*.wait`` span: dispatch, page tables,
admissions."""
from chipbench.metrics._spans import host_busy_s


def read(run):
    s = host_busy_s(run, "serve", "repro.serve.step")
    return None if s is None else 1000.0 * s
