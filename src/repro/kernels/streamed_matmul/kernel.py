"""Streamed matmul: weights passed by reference (HBM), tiles prefetched to VMEM.

This kernel is the paper's §3.1 mechanism rendered in the TPU memory
hierarchy.  The weight matrix is **not** staged into fast memory up front
(the paper's "eager copy"); instead the kernel receives a *reference*
(``pl.ANY`` memory space = compiler leaves the operand in HBM) and an explicit
DMA engine moves ``(bk, bn)`` tiles into a VMEM ring buffer:

  ring depth  = ``PrefetchSpec.buffer_size``   (paper: elements reserved on-core)
  tile shape  = ``elements_per_fetch``          (paper: elements per transfer)
  lookahead   = ``PrefetchSpec.distance``       (paper: when transfer is issued)

``distance=0`` reproduces the paper's *on-demand* mode — the copy for tile
``k`` starts only when tile ``k`` is needed and the MXU stalls on the DMA
semaphore, exactly the "block until the transfer has completed" behaviour.
``distance=d>=1`` issues the copy for tile ``k+d`` before computing tile
``k``; with ``buffer_size >= d+1`` the DMA of the next weights overlaps the
current tile's matmul, which is the paper's 21-25x fix.

Grid: ``(M/bm, N/bn)``; the K dimension is an in-kernel pipelined loop, since
that is the axis being streamed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.engine import static_auto_distance
from repro.core.refspec import PrefetchSpec


def _streamed_matmul_kernel(
    x_ref,  # (bm, K)   VMEM — activations (already at the fast tier)
    w_hbm,  # (K, N)    ANY  — weights, by reference
    o_ref,  # (bm, bn)  VMEM
    acc_ref,  # (bm, bn) f32 VMEM scratch
    ring,  # (slots, bk, bn) VMEM scratch — the prefetch ring buffer
    sems,  # (slots,) DMA semaphores
    *,
    block_k: int,
    n_k: int,
    distance: int,
    slots: int,
):
    j = pl.program_id(1)
    bn = o_ref.shape[1]

    def tile_copy(k_idx, slot):
        """DMA one (bk, bn) weight tile HBM -> ring[slot]."""
        return pltpu.make_async_copy(
            w_hbm.at[pl.ds(k_idx * block_k, block_k), pl.ds(j * bn, bn)],
            ring.at[slot],
            sems.at[slot],
        )

    acc_ref[...] = jnp.zeros_like(acc_ref)

    if distance > 0:
        # warm-up: issue the first `distance` tile fetches ahead of compute
        for t in range(min(distance, n_k)):
            tile_copy(t, t % slots).start()

    def body(k, _):
        slot = jax.lax.rem(k, slots)
        if distance == 0:
            # on-demand: fetch in the critical path, stall until it lands
            tile_copy(k, slot).start()
            tile_copy(k, slot).wait()
        else:
            nxt = k + distance
            @pl.when(nxt < n_k)
            def _():
                tile_copy(nxt, jax.lax.rem(nxt, slots)).start()
            tile_copy(k, slot).wait()
        x_blk = x_ref[:, pl.dslice(k * block_k, block_k)]
        acc_ref[...] += jnp.dot(
            x_blk, ring[slot], preferred_element_type=jnp.float32
        )
        return ()

    jax.lax.fori_loop(0, n_k, body, (), unroll=False)
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def streamed_matmul_p(
    x: jax.Array,  # (M, K)
    w: jax.Array,  # (K, N)
    *,
    spec: PrefetchSpec,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call (shapes must already be block-aligned)."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        f"unpadded shapes {x.shape} x {w.shape} vs blocks "
        f"({block_m},{block_n},{block_k})"
    )
    n_k = k // block_k
    # the VMEM ring is static: "auto" resolves to a fixed head start here,
    # exactly like the compiled graph engine (prefetch.streamed_scan)
    distance = spec.numeric_distance(static_auto_distance(n_k))
    # ring must hold the in-use tile + `distance` in flight
    slots = max(spec.buffer_size, distance + 1, 1)

    kernel = functools.partial(
        _streamed_matmul_kernel,
        block_k=block_k,
        n_k=n_k,
        distance=distance,
        slots=slots,
    )
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),  # x: row-block in VMEM
            pl.BlockSpec(memory_space=pl.ANY),  # w: by reference, stays in HBM
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),  # accumulator
            pltpu.VMEM((slots, block_k, block_n), w.dtype),  # prefetch ring
            pltpu.SemaphoreType.DMA((slots,)),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
    )(x, w)
