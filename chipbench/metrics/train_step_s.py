"""Seconds per train step: the whole window over every step it completed."""


def read(run):
    return run["window_s"] / run["steps"] if run["kind"] == "train" and run["steps"] else None
