"""Seconds per train step (``repro.train.step``) in which the main thread was
busy, not blocked in a ``*.wait`` span: dispatch, packing on the main
thread, re-homing."""
from chipbench.metrics._spans import host_busy_s


def read(run):
    return host_busy_s(run, "train", "repro.train.step")
