"""Model FLOPs of the work a cell does, and the chip's published peaks.

Counts are of the mathematics the model requires, from its sizes alone:
two FLOPs per multiply-add, recomputation not counted.  ``N`` is the
parameters every token multiplies by: the layers (norm scales included),
the final norm and the output head (the tied table, used once as the head;
the embedding lookup is no multiply).  Attention scores follow PaLM
(arXiv:2204.02311, App. B): a causal token at context ``c`` costs
``4 * layers * heads * head_dim * c`` in the forward pass (QK^T and AV), so
a training token of a length-``s`` row costs ``6 * N + 6 * L * H * Q * s``.
"""
from __future__ import annotations

import json
from pathlib import Path

from chipbench.model import Dims

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def matmul_params(dims: Dims) -> int:
    return dims.non_embedding_params + dims.d * dims.vocab


def _attn(dims: Dims) -> int:
    return dims.layers * dims.n_heads * dims.head_dim


def train_per_token(dims: Dims, seq_len: int) -> float:
    """Forward and backward FLOPs per token of a causal row of ``seq_len``."""
    return 6.0 * matmul_params(dims) + 6.0 * _attn(dims) * seq_len


def decode_token(dims: Dims, context: int) -> float:
    """Forward FLOPs of one token that attends to ``context`` positions
    (itself included)."""
    return 2.0 * matmul_params(dims) + 4.0 * _attn(dims) * context


def prefill(dims: Dims, prompt_len: int) -> float:
    """Forward FLOPs of a causal prompt of ``prompt_len`` tokens."""
    p = prompt_len
    return 2.0 * matmul_params(dims) * p + 2.0 * _attn(dims) * p * (p + 1)


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise ValueError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]
