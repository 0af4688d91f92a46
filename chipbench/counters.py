"""Deltas of the program's transfer counters over the window.

``repro.core.hoststream.StreamStats`` counts per stream: bytes each way,
link requests, weight groups that crossed the link and those found resident,
and the time the compute path blocked on a transfer.  The window's share is
the difference of two snapshots.
"""
from __future__ import annotations

FIELDS = (
    "bytes_h2d", "bytes_d2h", "h2d_requests", "d2h_requests",
    "unique_group_fetches", "cache_hits", "cache_misses", "n_groups",
    "transfer_wait_s", "writeback_drain_s",
)


def snapshot(streams: dict) -> dict:
    return {
        name: {f: float(getattr(s, f)) for f in FIELDS}
        for name, s in streams.items() if s is not None
    }


def delta(before: dict, after: dict) -> dict:
    return {
        name: {f: after[name][f] - before[name][f] for f in FIELDS}
        for name in after
    }
