"""Every token emitted in the window (prefill's first tokens included) over
the window."""


def read(run):
    return run["tokens"] / run["window_s"] if run["kind"] == "serve" else None
