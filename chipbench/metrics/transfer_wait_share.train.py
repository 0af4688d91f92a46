"""Share of the traced window the main thread spent blocked on a weight or
optimizer-state transfer (``repro.stream.wait``) or on the writeback drain
(``repro.stream.drain.wait``), in all three phases of the step, in %."""
from chipbench.metrics._spans import share


def read(run):
    return share(run, "train", "repro.stream.wait", "repro.stream.drain.wait")
