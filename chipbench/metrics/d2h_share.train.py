"""Share of the traced window the transfer engine spent copying gradients and
updated optimizer groups back to the host (``repro.engine.d2h``), in %."""
from chipbench.metrics._spans import share


def read(run):
    return share(run, "train", "repro.engine.d2h")
