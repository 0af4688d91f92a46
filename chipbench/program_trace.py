#!/usr/bin/env python3
"""The program's own spans in a profiler trace, and the metrics they feed.

The program under test puts a ``jax.profiler.TraceAnnotation`` named
``repro.<layer>.<what>`` at each boundary of its memory hierarchy
(``repro.core.spans``).  ``load`` reads them beside what
``chipbench.trace.load`` reads, each with the host thread it ran on.
``reduce`` adds to what ``chipbench.trace.reduce`` gives:

``program``
    per span name: ``n`` (spans in the window), ``s`` (their seconds inside
    it) and ``blocked_s`` (the part of ``s`` covered by ``*.wait`` spans of
    the same thread).
``module_s``
    device seconds per program (the ``<module>`` of the op names), averaged
    over the devices.

It also puts each long idle gap down to the innermost span of the main
thread covering the gap's middle, ``bench.`` or ``repro.``.  When that span
is a ``.wait``, ``<`` and the innermost ``repro.`` span of an engine thread
at that instant follow (``<idle`` when none does).  A trace with no
``repro.`` span reduces exactly as ``chipbench.trace`` reduces it.

In jax 0.9.0 each host thread is one line of the ``/host:CPU`` plane and
every such line is named ``python``, so a thread is keyed by its line's
index: the main thread is the line that holds ``bench.window``, an engine
thread one that holds ``repro.engine.`` or ``repro.disk.`` spans.

``chipbench/run.py`` reduces with ``chipbench.trace`` alone, so the
benchmark's runs do not report the per-layer metrics of ``METRICS`` yet.
This script runs one cell's window traced and prints them, with the span
table, the transfer counters and the breakdown, as one JSON line:

    python3 chipbench/program_trace.py --workload <name> --seed <n> --seconds <s>

from the root of a checkout, on a TPU.  ``--out DIR`` keeps the trace.
"""
from __future__ import annotations

import bisect
import sys
import time
from collections import defaultdict
from pathlib import Path

T_START = time.perf_counter()

HERE = Path(__file__).resolve().parent
for _p in (HERE.parent / "src", HERE.parent):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chipbench import trace as tr  # noqa: E402

PREFIX = "repro."
ENGINE_PREFIXES = ("repro.engine.", "repro.disk.")
WAIT = ".wait"
WINDOW = "bench.window"

_TRAIN = ["smollm-360m.train.host-state"]
_DECODE = ["smollm-360m.decode.host-weights", "olmo-1b.decode.host-kv"]


def _metric(name, unit, layer, moves, workloads, better="lower"):
    return {"name": name, "unit": unit, "better": better, "source": "device_trace",
            "layer": layer, "moves": moves, "workloads": workloads}


#: the ``per_layer`` entries of the readers of ``program`` and ``module_s``
#: (``chipbench/metrics/<name>.py``), as ``BENCHMARK.json`` would list them
METRICS = [
    _metric("transfer_wait_share.train", "%", "transfer engine", "train_step_s", _TRAIN),
    _metric("pack_share.train", "%", "transfer engine", "train_step_s", _TRAIN),
    _metric("pack_share.decode", "%", "transfer engine", "decode_tokens_per_s", _DECODE),
    _metric("h2d_share.train", "%", "transfer engine", "train_step_s", _TRAIN),
    _metric("h2d_share.decode", "%", "transfer engine", "decode_tokens_per_s", _DECODE),
    _metric("d2h_share.train", "%", "transfer engine", "train_step_s", _TRAIN),
    _metric("unpack_device_share.train", "%", "stream plan and residency",
            "train_step_s", _TRAIN),
    _metric("unpack_device_share.decode", "%", "stream plan and residency",
            "decode_tokens_per_s", _DECODE),
    _metric("host_busy_s_per_step.train", "s/step", "step programs", "train_step_s",
            _TRAIN),
    _metric("host_busy_ms_per_step.decode", "ms/step", "entry point",
            "decode_tokens_per_s", _DECODE),
]


def load(path: str) -> dict:
    """``chipbench.trace.load``'s dict, plus ``"program": [[name, start_ns,
    dur_ns, thread], ...]`` (every ``repro.`` host span) and
    ``"main_thread"`` (the thread of ``bench.window``, or None)."""
    from jax.profiler import ProfileData

    events = tr.load(path)
    program: list = []
    main = None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    program.append(
                        [ev.name, float(ev.start_ns), float(ev.duration_ns), thread]
                    )
                elif ev.name == WINDOW:
                    main = thread
    events["program"] = program
    events["main_thread"] = main
    return events


def reduce(events: dict, window_ns: tuple, top: int = 10) -> dict:
    """``chipbench.trace.reduce`` over ``window_ns``, plus ``program`` and
    ``module_s``, and the idle gaps labelled by the program's spans."""
    out = tr.reduce(events, window_ns, top)
    program = events.get("program")
    if not program:
        return out
    lo, hi = window_ns
    engine = {t for name, _, _, t in program if name.startswith(ENGINE_PREFIXES)}
    out["program"] = per_name(program, lo, hi)
    out["module_s"] = _module_seconds(events["devices"], lo, hi)
    main = [s[:3] for s in program if s[3] == events.get("main_thread")]
    main += events["spans"]
    workers = [s[:3] for s in program if s[3] in engine]
    out["breakdown"]["idle_gaps"] = [
        [_gap_label(main, workers, (a + b) / 2), (b - a) / 1e9]
        for a, b in _gaps(events["devices"], lo, hi, top)
    ]
    return out


def per_name(program: list, lo: float, hi: float) -> dict:
    waits = defaultdict(list)
    for name, s, d, t in program:
        if name.endswith(WAIT):
            waits[t].append((name, s, d))
    blocked = {t: tr._union(iv, lo, hi) for t, iv in waits.items()}
    out: dict = {}
    for name, s, d, t in program:
        a, b = max(s, lo), min(s + d, hi)
        if b < a:
            continue
        e = out.setdefault(name, {"n": 0, "s": 0.0, "blocked_s": 0.0})
        e["n"] += 1
        e["s"] += (b - a) / 1e9
        e["blocked_s"] += _covered(blocked.get(t, []), a, b) / 1e9
    return out


def _covered(merged: list, a: float, b: float) -> float:
    """Length of ``[a, b]`` covered by the sorted disjoint ``merged``."""
    i = max(0, bisect.bisect_right(merged, [a, float("inf")]) - 1)
    total = 0.0
    for s, e in merged[i:]:
        if s >= b:
            break
        total += max(0.0, min(e, b) - max(s, a))
    return total


def _module_seconds(devices: dict, lo: float, hi: float) -> dict:
    out: dict = defaultdict(float)
    for ops in devices.values():
        by_module = defaultdict(list)
        for op in ops:
            by_module[op[0].split("/", 1)[0]].append(op)
        for module, ivs in by_module.items():
            busy = sum(e - s for s, e in tr._union(ivs, lo, hi))
            out[module] += busy / len(devices) / 1e9
    return dict(out)


def _gaps(devices: dict, lo: float, hi: float, top: int) -> list:
    """The ``top`` longest idle ``(start, end)`` of the first device, as
    ``chipbench.trace.reduce`` finds them."""
    first = tr._union(devices[sorted(devices)[0]], lo, hi)
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda g: g[0] - g[1])
    return gaps[:top]


def _gap_label(main: list, workers: list, t: float) -> str:
    label = tr._label(main, t)
    if label.endswith(WAIT):
        inner = tr._label(workers, t)
        label += "<" + ("idle" if inner == "outside spans" else inner)
    return label


def clock_offset_ms(events: dict) -> float | None:
    """Median of the host's end of each ``repro.serve.sync.wait`` less the
    end of the last device op inside it (on the first device): how much
    later the host learns of the step's end than the device's clock says."""
    devices = events["devices"]
    if not devices:
        return None
    ops = sorted(devices[sorted(devices)[0]], key=lambda op: op[1] + op[2])
    ends = [s + d for _, s, d in ops]
    lags = []
    for name, s, d, t in events.get("program", []):
        if name != "repro.serve.sync.wait" or t != events.get("main_thread"):
            continue
        i = bisect.bisect_right(ends, s + d) - 1
        if i >= 0 and ends[i] >= s:
            lags.append((s + d - ends[i]) / 1e6)
    lags.sort()
    return lags[len(lags) // 2] if lags else None


def run_traced(workload: str, seed: int, seconds: float, trace_dir: str, *,
               smoke: bool = False, log=print) -> dict:
    """One cell's set-up and window, traced into ``trace_dir``; returns the
    metrics of the cell (end to end, per layer, and ``METRICS``), the span
    table, ``module_s``, the transfer counters and the breakdown."""
    import jax

    from chipbench import counters
    from chipbench import run as rn
    from chipbench.spans import span

    bench = rn.load_benchmark()
    wl = rn.workload_entry(bench, workload)
    devices = jax.devices()[: wl["chips"]]
    mesh = jax.make_mesh(
        (1, len(devices)), ("data", "model"), devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    cell = rn.build_cell(bench, workload, seed, smoke=smoke)
    cell.setup(mesh)
    setup_s = time.perf_counter() - T_START - cell.excluded_s
    before = counters.snapshot(cell.stats())
    jax.profiler.start_trace(trace_dir)
    try:
        with span(WINDOW):
            run = cell.window(seconds)
    finally:
        jax.profiler.stop_trace()
    run["counters"] = counters.delta(before, counters.snapshot(cell.stats()))
    run["peak_bytes"] = rn._peak_bytes(devices[0])
    cell.close()
    run.update(kind=cell.kind, setup_s=setup_s,
               peaks=rn._peaks(devices[0].device_kind, smoke))
    path = tr.find(trace_dir)
    log(f"trace {path}: {Path(path).stat().st_size} bytes")
    events = load(path)
    run["trace"] = reduce(events, tr.window_of(events, WINDOW))
    metrics = {}
    for m in bench["end_to_end"] + bench["per_layer"] + METRICS:
        if workload in m.get("workloads", [workload]):
            value = rn.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = value
    t = run["trace"]
    return {
        "workload": workload, "seed": seed, "metrics": metrics,
        "program": t.get("program", {}), "module_s": t.get("module_s", {}),
        "counters": run["counters"], "busy_s": t["busy_s"], "window_s": t["window_s"],
        "host_window_s": run["window_s"], "steps": run["steps"],
        "clock_offset_ms": clock_offset_ms(events), "breakdown": t["breakdown"],
    }


def main(argv=None) -> int:
    import argparse
    import json
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None, help="keep the trace in this directory")
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("program_trace: no TPU", file=sys.stderr)
        return 3
    from chipbench.run import _enable_compile_cache

    _enable_compile_cache()
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    with tempfile.TemporaryDirectory(prefix="program-trace-") as tmp:
        result = run_traced(args.workload, args.seed, args.seconds, args.out or tmp,
                            log=log)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
