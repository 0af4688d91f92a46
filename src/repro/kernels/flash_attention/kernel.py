"""Flash attention (blockwise online-softmax) with bounded VMEM working set.

The VMEM discipline is the paper's on-core buffer discipline: only
``(block_q x H)`` of queries and ``(block_kv x H)`` of keys/values are ever
resident in fast memory; K/V blocks stream through the implicit BlockSpec
grid pipeline (the TPU's hardware analogue of the paper's prefetch ring —
Mosaic double-buffers grid operands automatically, i.e. ``distance=1``).

Causal + sliding-window masking; fully-masked K/V blocks are skipped via the
grid (we never *launch* them) for the causal lower triangle, and cheaply
via ``pl.when`` for window-expired blocks.

GQA: grid is over KV heads; the q block holds all ``G = N/KH`` query heads of
the group, head-major (``(G, block_q, H)``), folded into the row dimension
(``G * block_q`` rows), so the MXU matmul is dense and KV is never
replicated.  Folding the *leading* head axis into the sublane-aligned
``block_q`` axis is a free reshape for Mosaic at any head_dim; folding a
small trailing head axis into rows is not (refused at head_dim 64).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
LANES = 128


def _flash_kernel(
    q_ref,  # (1, G, block_q, H)
    k_ref,  # (1, block_kv, H)
    v_ref,  # (1, block_kv, H)
    o_ref,  # (1, G, block_q, H)
    m_ref,  # (G * block_q, LANES) f32 — running max
    l_ref,  # (G * block_q, LANES) f32 — running sum
    acc_ref,  # (G * block_q, H) f32
    *,
    causal: bool,
    window: int,
    q_offset: int,
    block_q: int,
    block_kv: int,
    n_kv_blocks: int,
    sm_scale: float,
):
    qi = pl.program_id(2)  # query block index
    ki = pl.program_id(3)  # kv block index

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = q_ref.shape[1]
    h = q_ref.shape[3]
    rows = g * block_q

    q_start = qi * block_q + q_offset  # absolute position of query row 0
    k_start = ki * block_kv

    # Skip blocks that the mask fully excludes. Two cases:
    #   causal:   k_start > q_end  (block strictly above the diagonal)
    #   windowed: k_end <= q_start - window + 1 (block entirely expired)
    q_end = q_start + block_q - 1
    run = jnp.asarray(True)
    if causal:
        run &= k_start <= q_end
    if window:
        run &= (k_start + block_kv - 1) > (q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].reshape(rows, h)  # (G*bq, H) — group heads folded into rows
        k = k_ref[0]  # (bkv, H)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (rows, bkv)
        s = s * sm_scale

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (rows, block_kv), 0) % block_q
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (rows, block_kv), 1)
        mask = jnp.ones((rows, block_kv), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]  # (rows, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows (all NEG_INF): keep exp finite
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zero output
        out = (acc_ref[...] / l).astype(o_ref.dtype)
        o_ref[0] = out.reshape(g, block_q, h)


def flash_attention_p(
    q: jax.Array,  # (BKH, G, S, H)  — batch*kv_heads flattened, G query heads
    k: jax.Array,  # (BKH, T, H)
    v: jax.Array,  # (BKH, T, H)
    *,
    causal: bool,
    window: int,
    q_offset: int,
    block_q: int,
    block_kv: int,
    interpret: bool,
) -> jax.Array:
    bkh, g, s, h = q.shape
    t = k.shape[1]
    assert s % block_q == 0 and t % block_kv == 0, (q.shape, k.shape, block_q, block_kv)
    n_kv_blocks = t // block_kv

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        window=window,
        q_offset=q_offset,
        block_q=block_q,
        block_kv=block_kv,
        n_kv_blocks=n_kv_blocks,
        sm_scale=h ** -0.5,
    )
    grid = (bkh, 1, s // block_q, n_kv_blocks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g, block_q, h), lambda b, _, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, block_kv, h), lambda b, _, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, h), lambda b, _, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, block_q, h), lambda b, _, i, j: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * block_q, LANES), jnp.float32),
            pltpu.VMEM((g * block_q, LANES), jnp.float32),
            pltpu.VMEM((g * block_q, h), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(q, k, v)
