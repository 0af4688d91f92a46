"""Share of the traced window the transfer engine spent issuing a staged
buffer to the device and waiting for it to land (``repro.engine.put``), in %."""
from chipbench.metrics._spans import share


def read(run):
    return share(run, "serve", "repro.engine.put")
