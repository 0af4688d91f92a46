"""Model FLOPs of the window's train steps (chipbench/flops.py) over the
window and the chip's bf16 peak, in %."""
from chipbench.metrics._mfu import mfu


def read(run):
    return mfu(run, "train")
