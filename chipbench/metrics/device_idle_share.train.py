"""Share of the traced window in which no operation ran on the device, in %
(1 minus the union of device op intervals over the window)."""
from chipbench.metrics._idle import idle


def read(run):
    return idle(run, "train")
