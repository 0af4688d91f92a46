"""The device's ``peak_bytes_in_use`` at the end of the window, in GB (1e9 B):
the footprint a device budget promises to bound."""


def read(run):
    return run["peak_bytes"] / 1e9 if run["peak_bytes"] else None
