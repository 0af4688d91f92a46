"""Shared arithmetic of the readers of the program's spans (``repro.*``),
from the ``program`` and ``module_s`` tables that
``chipbench.program_trace.reduce`` adds to a traced run."""


def program(run, kind):
    """The span table of a traced run of ``kind``, or None."""
    t = run.get("trace")
    if run["kind"] != kind or not t or not t.get("program"):
        return None
    return t["program"]


def share(run, kind, *names):
    """Seconds of the spans ``names`` in the window over the window, in %."""
    prog = program(run, kind)
    if prog is None:
        return None
    return 100.0 * sum(prog[n]["s"] for n in names if n in prog) / run["trace"]["window_s"]


def module_share(run, kind, module):
    """Device seconds of the program ``module`` over the window, in %."""
    if program(run, kind) is None:
        return None
    t = run["trace"]
    return 100.0 * t["module_s"].get(module, 0.0) / t["window_s"]


def host_busy_s(run, kind, name):
    """Seconds per ``name`` span in which its thread was not blocked in a
    ``*.wait`` span."""
    prog = program(run, kind)
    if prog is None or name not in prog:
        return None
    e = prog[name]
    return (e["s"] - e["blocked_s"]) / e["n"]
