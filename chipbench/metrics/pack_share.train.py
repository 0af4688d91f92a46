"""Share of the traced window the transfer engine spent packing groups into
their staging buffers (``repro.engine.pack``), in %."""
from chipbench.metrics._spans import share


def read(run):
    return share(run, "train", "repro.engine.pack")
