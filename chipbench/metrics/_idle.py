"""Shared arithmetic of the ``device_idle_share.*`` readers."""


def idle(run, kind):
    t = run.get("trace")
    if run["kind"] != kind or not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
