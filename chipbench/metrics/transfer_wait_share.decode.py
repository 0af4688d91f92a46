"""Share of the window the compute path spent blocked on a weight or KV
transfer, in %."""


def read(run):
    c = run["counters"]
    if run["kind"] != "serve":
        return None
    wait = sum(c[s]["transfer_wait_s"] for s in ("weights", "kv") if s in c)
    streams = sum(c[s]["n_groups"] for s in ("weights", "kv") if s in c)
    return 100.0 * wait / run["window_s"] if streams else None
