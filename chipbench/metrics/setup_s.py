"""Seconds from the start of the process to the start of the window: loading,
compiling (or reading the compile cache), homing the weights, warming up."""


def read(run):
    return run["setup_s"]
