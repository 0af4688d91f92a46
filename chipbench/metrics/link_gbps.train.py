"""GB/s over the host link in the window: both directions of the weight
passes (forward and backward) and of the optimizer phase."""


def read(run):
    c = run["counters"]
    if run["kind"] != "train" or "opt" not in c:
        return None
    moved = sum(c[s]["bytes_h2d"] + c[s]["bytes_d2h"] for s in ("weights", "opt"))
    return moved / run["window_s"] / 1e9
