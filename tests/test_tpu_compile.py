"""Compile rehearsals for a described TPU v5e, at real widths.

Nothing here runs on a chip: each test lowers a kernel (``interpret=False``)
or the transfer engine's unpack for one chip of a described ``v5e:2x2``
topology and compiles it with the TPU compiler, which refuses what the chip
would refuse (unsupported Mosaic layouts, SMEM block shapes, programs that
do not fit).  The topology is described inside a fixture, never at import,
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.engine import GroupLayout
from repro.kernels.decode_attention.ops import decode_attention, decode_attention_paged
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rglru_scan.ops import linear_recurrence
from repro.kernels.streamed_matmul.ops import streamed_matmul
from repro.train import steps as st


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """Described-topology compiles cannot be read back from the persistent
    cache without a chip; keep it off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel, not a fallback
    return compiled


def test_streamed_matmul_compiles(one_chip):
    x = _sds(one_chip, (2048, 960), jnp.bfloat16)
    w = _sds(one_chip, (960, 2560), jnp.bfloat16)
    _compile_kernel(functools.partial(streamed_matmul, interpret=False), x, w)


@pytest.mark.parametrize(
    "n_heads,n_kv_heads,head_dim",
    [(15, 5, 64), (32, 8, 128)],
    ids=["smollm_hd64", "hd128"],
)
def test_flash_attention_compiles(one_chip, n_heads, n_kv_heads, head_dim):
    b, s = 1, 2048
    q = _sds(one_chip, (b, s, n_heads, head_dim), jnp.bfloat16)
    kv = _sds(one_chip, (b, s, n_kv_heads, head_dim), jnp.bfloat16)
    _compile_kernel(functools.partial(flash_attention, interpret=False), q, kv, kv)


def test_decode_attention_compiles(one_chip):
    b, t, n, kh, h = 8, 4096, 15, 5, 64
    q = _sds(one_chip, (b, n, h), jnp.bfloat16)
    kv = _sds(one_chip, (b, t, kh, h), jnp.bfloat16)
    lens = _sds(one_chip, (b,), jnp.int32)
    _compile_kernel(functools.partial(decode_attention, interpret=False), q, kv, kv, lens)


def test_paged_decode_attention_compiles(one_chip):
    b, page, n_pages, n, kh, h = 4, 128, 5, 15, 5, 64
    q = _sds(one_chip, (b, n, h), jnp.bfloat16)
    pages = tuple(_sds(one_chip, (b, page, kh, h), jnp.bfloat16) for _ in range(n_pages))
    lens = _sds(one_chip, (b,), jnp.int32)

    def fn(q, kp, vp, lens):
        return decode_attention_paged(q, kp, vp, lens, interpret=False)

    _compile_kernel(fn, q, pages, pages, lens)


def test_rglru_scan_compiles(one_chip):
    a = _sds(one_chip, (1, 2048, 2560), jnp.float32)
    _compile_kernel(functools.partial(linear_recurrence, interpret=False), a, a)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_engine_unpack_temp_stays_near_staged_bytes(one_chip, dtype):
    """The coalesced unpack of a 4-layer smollm-360m group compiles with
    device temp under 2x the staged bytes (a byte-staged f32 unpack needed
    ~32x: the (n, 4) uint8 view pads to a full tile per row)."""
    blocks = st.abstract_train_state(get_config("smollm-360m"))[0]["blocks"]
    group = jax.tree.map(lambda s: np.zeros((4,) + s.shape[1:], dtype), blocks)
    layout = GroupLayout(group)
    flat = _sds(one_chip, layout.new_staging().shape, np.uint32)
    mem = layout._unpack.lower(flat).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 2 * layout.staging_bytes, (
        mem.temp_size_in_bytes, layout.staging_bytes,
    )
