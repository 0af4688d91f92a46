"""Reduction of a profiler trace to device busy time and a breakdown.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into a plain dict:
the device operations of every ``/device:<kind>:<n>`` plane (its ``XLA Ops``
line, each op named ``<program>/<op>``) and the harness's own host spans
(names starting ``bench.``), in nanoseconds.  ``reduce`` works on that dict.
A small trace recorded on the chip is checked in under ``tests/data``.

The profiler puts the device's timestamps on the host's clock; in the
recorded trace they read ~1.3 ms earlier than the host's dispatch, so a
window edge or a gap's label can be off by that much.

Busy time is the union of a device's operation intervals inside the traced
window, averaged over the devices.  Each idle gap of device 0 is named by
the innermost harness span that covers its middle, which says what the host
was doing while the chip waited.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def find(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "spans": [[name, start_ns, dur_ns], ...]}`` from an xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: list(line.events) for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            modules = sorted(
                (float(m.start_ns), _short_module(m.name))
                for m in lines.get(MODULES_LINE, [])
            )
            starts = [m[0] for m in modules]
            devices[plane.name] = [
                [
                    _op_name(ev.name, modules, starts, float(ev.start_ns)),
                    float(ev.start_ns),
                    float(ev.duration_ns),
                ]
                for ev in lines[OPS_LINE]
            ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name, float(ev.start_ns), float(ev.duration_ns)])
    return {"devices": devices, "spans": spans}


def _short_module(name: str) -> str:
    """``jit_step(1234...)`` -> ``jit_step``."""
    return name.split("(", 1)[0]


def _op_name(text: str, modules, starts, t: float) -> str:
    """``<module>/<op>``: the op's HLO name (the text before `` = ``) under
    the program that was running when it started."""
    op = text.split(" = ", 1)[0].lstrip("%")
    i = bisect.bisect_right(starts, t) - 1
    return f"{modules[i][1]}/{op}" if i >= 0 else op


def _union(intervals, lo: float, hi: float) -> list:
    """Merged ``[start, end]`` intervals clipped to ``[lo, hi]``."""
    out: list = []
    for s, e in sorted((max(s, lo), min(s + d, hi)) for _, s, d in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(spans, t: float) -> str:
    """Innermost harness span covering ``t``, else ``"outside spans"``."""
    best = None
    for name, s, d in spans:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside spans"


def reduce(events: dict, window_ns=None, top: int = 10) -> dict:
    """``busy_s`` (mean over devices), ``window_s``, and a ``breakdown`` of
    the ``top`` device operations by time and the ``top`` longest idle gaps
    of the first device, each ``[name, seconds]``.  ``window_ns`` is
    ``(start, end)``; by default the span of every recorded event."""
    devices = events["devices"]
    if not devices or not any(devices.values()):
        raise ValueError("the trace holds no device operation")
    spans = events["spans"]
    if window_ns is None:
        allev = [e for evs in devices.values() for e in evs] + spans
        window_ns = (min(e[1] for e in allev), max(e[1] + e[2] for e in allev))
    lo, hi = window_ns
    busy = []
    for name in sorted(devices):
        busy.append(sum(e - s for s, e in _union(devices[name], lo, hi)))
    op_time: dict = defaultdict(float)
    for evs in devices.values():
        for name, s, d in evs:
            clipped = min(s + d, hi) - max(s, lo)
            if clipped > 0:
                op_time[name] += clipped / len(devices)
    first = _union(devices[sorted(devices)[0]], lo, hi)
    gaps = []
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            gaps.append((a, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = [[_label(spans, (a + b) / 2), (b - a) / 1e9] for a, b in gaps[:top]]
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(devices),
        "breakdown": {
            "device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": gaps,
        },
    }


def window_of(events: dict, span_name: str):
    """``(start, end)`` of the harness span ``span_name`` (the traced window)."""
    for name, s, d in events["spans"]:
        if name == span_name:
            return s, s + d
    raise ValueError(f"no span {span_name!r} in the trace")
