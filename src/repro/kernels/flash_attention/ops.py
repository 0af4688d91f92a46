"""Public jit'd wrapper for flash attention (GQA, causal, sliding window)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_p


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, S, N, H)
    k: jax.Array,  # (B, T, KH, H)
    v: jax.Array,  # (B, T, KH, H)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """GQA flash attention; matches ``ref.attention_ref`` semantics.

    Queries/keys are padded up to block multiples; padded keys are masked out
    via the causal/validity structure (pad queries produce garbage rows that
    are sliced away; pad keys sit at positions > every real query position so
    the causal mask removes them — for non-causal use, an explicit validity
    bound is applied by padding `q_offset`-relative masking).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, s, n, h = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = n // kh

    bq = min(block_q, _ceil_to(s, 8))
    bkv = min(block_kv, _ceil_to(t, 128))
    sp, tp = _ceil_to(s, bq), _ceil_to(t, bkv)

    # Fold (B, KH) into one grid axis; q heads of each group ride with q,
    # head-major so the kernel folds them into rows with a free reshape.
    qg = q.reshape(b, s, kh, g, h).transpose(0, 2, 3, 1, 4).reshape(b * kh, g, s, h)
    kg = k.transpose(0, 2, 1, 3).reshape(b * kh, t, h)
    vg = v.transpose(0, 2, 1, 3).reshape(b * kh, t, h)

    # Padding: pad keys land at positions >= t; causal masking vs real query
    # positions (< t for self-attention) excludes them. Pad queries are
    # sliced off after the call.
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    kg = jnp.pad(kg, ((0, 0), (0, tp - t), (0, 0)))
    vg = jnp.pad(vg, ((0, 0), (0, tp - t), (0, 0)))
    if not causal and tp != t:
        raise NotImplementedError(
            "non-causal flash attention requires block-aligned key length "
            f"(T={t}, block_kv={bkv})"
        )

    out = flash_attention_p(
        qg,
        kg,
        vg,
        causal=causal,
        window=window,
        q_offset=q_offset,
        block_q=bq,
        block_kv=bkv,
        interpret=interpret,
    )
    out = out[:, :, :s].reshape(b, kh, g, s, h).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, s, n, h)
