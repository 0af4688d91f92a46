"""95th percentile, in ms, of the gaps between consecutive output tokens of
every request, over all gaps that fall in the window (steps that also
prefill an admitted request included)."""
import numpy as np


def read(run):
    gaps = run.get("tpot_s") or []
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
