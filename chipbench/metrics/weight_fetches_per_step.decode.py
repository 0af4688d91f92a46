"""Weight groups that crossed the link per decode step (residency-cache
misses), over the window."""


def read(run):
    c = run["counters"].get("weights")
    if run["kind"] != "serve" or not c or not c["n_groups"]:
        return None
    return c["unique_group_fetches"] / run["steps"]
