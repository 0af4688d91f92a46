"""Model FLOPs of the window's decode tokens and prefills (chipbench/flops.py)
over the window and the chip's bf16 peak, in %."""
from chipbench.metrics._mfu import mfu


def read(run):
    return mfu(run, "serve")
