"""MB (1e6 B) of cold KV pages brought to the device per decode step, over
the window."""


def read(run):
    c = run["counters"].get("kv")
    if run["kind"] != "serve" or not c or not c["n_groups"]:
        return None
    return c["bytes_h2d"] / run["steps"] / 1e6
