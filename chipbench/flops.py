"""Model FLOPs of the work a cell does, and the chip's published peaks.

Each architecture (``chipbench/archs/<arch>.py``) counts its own FLOPs of the
mathematics the model requires, from its sizes alone (``train_per_token``,
``decode_token``, ``prefill``): two FLOPs per multiply-add, recomputation not
counted, so a sparse layer counts the experts a token is routed to and a
windowed layer the positions it sees.  The table of peaks is common to all.

``matmul_params``, ``train_per_token``, ``decode_token`` and ``prefill`` are
the dense architecture's, kept under the names they had here.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
_DENSE = ("matmul_params", "train_per_token", "decode_token", "prefill")


def __getattr__(name: str):
    if name in _DENSE:
        from chipbench.archs import dense

        return getattr(dense, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise ValueError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]
