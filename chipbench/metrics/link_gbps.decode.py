"""GB/s over the host link in the window: both directions of the weight
stream and of the KV page stream."""


def read(run):
    c = run["counters"]
    if run["kind"] != "serve":
        return None
    moved = sum(c[s]["bytes_h2d"] + c[s]["bytes_d2h"] for s in ("weights", "kv"))
    return moved / run["window_s"] / 1e9 if moved else None
