"""Chunked linear-recurrence scan: the RG-LRU hot-spot, TPU-native.

The recurrence ``h_t = a_t h_{t-1} + b_t`` is elementwise over the channel
dim and sequential over time — exactly the memory-hierarchy shape the paper
targets: the (B, S, W) gate tensors live in HBM, and only a
``(chunk_t, block_w)`` tile is ever resident in VMEM.  The time axis is the
*innermost* grid dim with "arbitrary" semantics, so the carried state
``h`` persists in a VMEM scratch across time chunks while Mosaic
double-buffers the chunk loads (the implicit prefetch pipeline — the
paper's ``distance=1``).

Versus ``lax.associative_scan`` (the XLA path): the associative scan is
O(log S) depth but materializes O(S) intermediates per level in HBM;
the chunked kernel makes one pass, fully sequential in VMEM, and
parallelizes over (B, W) — the natural TPU mapping because B·W/block_w
grid cells keep the VPU busy while S streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lru_kernel(a_ref, b_ref, o_ref, h_ref, *, chunk_t: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    # rows are read through the refs: a dynamic index into a loaded
    # (chunk_t, block_w) value would lower to dynamic_slice, which Mosaic
    # does not implement
    def step(i, h):  # h: (1, block_w)
        row = pl.ds(i, 1)
        h = a_ref[0, row, :] * h + b_ref[0, row, :]
        o_ref[0, row, :] = h
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk_t, step, h_ref[...])


def linear_recurrence_p(
    a: jax.Array,  # (B, S, W) f32
    b: jax.Array,
    *,
    chunk_t: int,
    block_w: int,
    interpret: bool,
) -> jax.Array:
    bsz, s, w = a.shape
    assert s % chunk_t == 0 and w % block_w == 0, (a.shape, chunk_t, block_w)
    kernel = functools.partial(_lru_kernel, chunk_t=chunk_t)
    return pl.pallas_call(
        kernel,
        grid=(bsz, w // block_w, s // chunk_t),
        in_specs=[
            pl.BlockSpec((1, chunk_t, block_w), lambda i, j, t: (i, t, j)),
            pl.BlockSpec((1, chunk_t, block_w), lambda i, j, t: (i, t, j)),
        ],
        out_specs=pl.BlockSpec((1, chunk_t, block_w), lambda i, j, t: (i, t, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, w), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_w), a.dtype)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(a, b)
