"""Public jit'd wrapper for flash-decode attention."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.refspec import PrefetchSpec
from repro.kernels.decode_attention.kernel import decode_attention_p

_DEFAULT_SPEC = PrefetchSpec(buffer_size=2, elements_per_fetch=1, distance=1)


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("spec", "block_kv", "interpret"))
def decode_attention(
    q: jax.Array,  # (B, N, H)
    k: jax.Array,  # (B, T, KH, H)
    v: jax.Array,  # (B, T, KH, H)
    lengths: jax.Array,  # (B,) int32 — valid prefix per sequence
    *,
    spec: PrefetchSpec = _DEFAULT_SPEC,
    block_kv: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """One-token GQA attention vs a large KV cache streamed from HBM.

    Matches ``ref.decode_attention_ref``; the PrefetchSpec only changes the
    DMA schedule, never the value (property-tested).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, n, h = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = n // kh

    bkv = min(block_kv, _ceil_to(t, 128))
    tp = _ceil_to(t, bkv)
    # head_dim is zero-padded to the 128-lane tile: the kernel's DMA slabs
    # must be lane-aligned, and the TPU's HBM layout pads a narrower minor
    # dim to 128 anyway.  Zero lanes add nothing to q.k or to p.v.
    hp = _ceil_to(h, 128)

    qg = q.reshape(b, kh, g, h).reshape(b * kh, g, h)
    kg = k.transpose(0, 2, 1, 3).reshape(b * kh, t, h)
    vg = v.transpose(0, 2, 1, 3).reshape(b * kh, t, h)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, hp - h)))
    kg = jnp.pad(kg, ((0, 0), (0, tp - t), (0, hp - h)))
    vg = jnp.pad(vg, ((0, 0), (0, tp - t), (0, hp - h)))
    lens = jnp.repeat(lengths.astype(jnp.int32), kh)

    out = decode_attention_p(
        qg, kg, vg, lens, spec=spec, block_kv=bkv, sm_scale=h ** -0.5,
        interpret=interpret,
    )
    return out[..., :h].reshape(b, kh, g, h).reshape(b, n, h)


def decode_attention_paged(
    q: jax.Array,  # (B, N, H)
    k_pages,  # sequence of (B, Tp, KH, H) device-resident pages
    v_pages,  # sequence of (B, Tp, KH, H)
    lengths: jax.Array,  # (B,) int32 — valid prefix per sequence
    *,
    spec: PrefetchSpec = _DEFAULT_SPEC,
    block_kv: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash-decode over a paged KV-cache view, by reference.

    The kernel-level counterpart of the serving pager's
    :func:`repro.core.kvpager.assemble_view`: the TPU decode path for a
    page-granular cache (this container's CPU serving session instead
    assembles the dense view and decodes through the XLA attention —
    see ``train.steps.make_paged_decode_step``).  ``k_pages`` /
    ``v_pages`` are the per-page device tensors.  They are joined at trace
    time (pure concatenation, no host copies); the kernel's DMA grid then
    streams ``block_kv``-row slabs out of HBM exactly as for a contiguous
    cache.  ``block_kv`` defaults to the page length, floored at the
    TPU lane width (128) — so each DMA covers one page when pages are
    >= 128 tokens, and a whole number of pages per slab otherwise.
    Values are bitwise-identical to :func:`decode_attention` on the dense
    cache (property-tested).
    """
    k_pages, v_pages = tuple(k_pages), tuple(v_pages)
    if not k_pages or len(k_pages) != len(v_pages):
        raise ValueError("k_pages / v_pages must be equal-length, non-empty")
    if block_kv is None:
        block_kv = max(k_pages[0].shape[1], 128)
    k = jnp.concatenate(k_pages, axis=1)
    v = jnp.concatenate(v_pages, axis=1)
    return decode_attention(
        q, k, v, lengths, spec=spec, block_kv=block_kv, interpret=interpret
    )
