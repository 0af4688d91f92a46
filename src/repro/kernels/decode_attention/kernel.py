"""Flash-decode: one query token vs an arbitrarily large KV cache.

This is the paper's headline capability ("compute with data sets of
arbitrarily large size") in kernel form: the KV cache lives in HBM (or, at
the framework level, host memory — see ``core.memkind``) and is **passed by
reference** (``pl.ANY``).  The kernel walks it block-by-block through a VMEM
ring buffer with explicit ``make_async_copy`` DMAs:

  ring depth  = ``PrefetchSpec.buffer_size``
  block rows  = ``block_kv``  (the paper's elements-per-fetch)
  lookahead   = ``PrefetchSpec.distance`` (0 = the paper's on-demand mode)

Only ``ceil(length / block_kv)`` blocks are fetched (dynamic trip count), so
per-token work is proportional to the *valid* context, not the allocated
cache.  Online softmax keeps the VMEM working set at
``2 * slots * block_kv * H`` bytes regardless of context length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.engine import static_auto_distance
from repro.core.refspec import PrefetchSpec

NEG_INF = -1e30
LANES = 128


def _decode_kernel(
    len_ref,  # (BKH,) int32 SMEM (scalar prefetch) — valid cache lengths
    q_ref,  # (1, G, H) VMEM
    k_hbm,  # (BKH, T, H) ANY — by reference
    v_hbm,  # (BKH, T, H) ANY
    o_ref,  # (1, G, H) VMEM
    ring_k,  # (slots, block_kv, H) VMEM
    ring_v,  # (slots, block_kv, H) VMEM
    sem_k,  # (slots,) DMA
    sem_v,  # (slots,) DMA
    *,
    block_kv: int,
    n_t: int,
    distance: int,
    slots: int,
    sm_scale: float,
):
    b = pl.program_id(0)
    g, h = q_ref.shape[1], q_ref.shape[2]
    length = len_ref[b]
    needed = (length + block_kv - 1) // block_kv  # dynamic trip count

    def copy_block(i, slot):
        ck = pltpu.make_async_copy(
            k_hbm.at[b, pl.ds(i * block_kv, block_kv), :], ring_k.at[slot], sem_k.at[slot]
        )
        cv = pltpu.make_async_copy(
            v_hbm.at[b, pl.ds(i * block_kv, block_kv), :], ring_v.at[slot], sem_v.at[slot]
        )
        return ck, cv

    if distance > 0:
        def warm(t, _):
            @pl.when(t < needed)
            def _():
                ck, cv = copy_block(t, jax.lax.rem(t, slots))
                ck.start()
                cv.start()
            return ()
        jax.lax.fori_loop(0, distance, warm, (), unroll=True)

    q = q_ref[0]  # (G, H)

    def body(i, carry):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(i, slots)
        if distance == 0:
            # on-demand: blocking fetch in the critical path (paper baseline)
            ck, cv = copy_block(i, slot)
            ck.start(); cv.start()
            ck.wait(); cv.wait()
        else:
            nxt = i + distance
            @pl.when(nxt < needed)
            def _():
                ck, cv = copy_block(nxt, jax.lax.rem(nxt, slots))
                ck.start()
                cv.start()
            ck, cv = copy_block(i, slot)
            ck.wait(); cv.wait()

        kb = ring_k[slot]  # (bkv, H)
        vb = ring_v[slot]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (G, bkv)
        kpos = i * block_kv + jax.lax.broadcasted_iota(jnp.int32, (g, block_kv), 1)
        mask = kpos < length
        s = jnp.where(mask, s, NEG_INF)

        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc

    init = (
        jnp.full((g, 1), NEG_INF, jnp.float32),
        jnp.zeros((g, 1), jnp.float32),
        jnp.zeros((g, h), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(0, needed, body, init)
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def decode_attention_p(
    q: jax.Array,  # (BKH, G, H)
    k: jax.Array,  # (BKH, T, H)
    v: jax.Array,  # (BKH, T, H)
    lengths: jax.Array,  # (BKH,) int32
    *,
    spec: PrefetchSpec,
    block_kv: int,
    sm_scale: float,
    interpret: bool,
) -> jax.Array:
    bkh, g, h = q.shape
    t = k.shape[1]
    assert t % block_kv == 0, (t, block_kv)
    n_t = t // block_kv
    # static VMEM ring: "auto" resolves to a fixed head start at trace time
    distance = spec.numeric_distance(static_auto_distance(n_t))
    slots = max(spec.buffer_size, distance + 1, 1)

    kernel = functools.partial(
        _decode_kernel,
        block_kv=block_kv,
        n_t=n_t,
        distance=distance,
        slots=slots,
        sm_scale=sm_scale,
    )
    # lengths ride in SMEM as a scalar-prefetch operand: the whole (BKH,)
    # vector is resident and each program reads its own entry
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bkh,),
        in_specs=[
            pl.BlockSpec((1, g, h), lambda b, lens: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, g, h), lambda b, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((slots, block_kv, h), k.dtype),
            pltpu.VMEM((slots, block_kv, h), v.dtype),
            pltpu.SemaphoreType.DMA((slots,)),
            pltpu.SemaphoreType.DMA((slots,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bkh, g, h), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
    )(lengths, q, k, v)
