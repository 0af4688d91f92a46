"""Core offload abstraction tests: memory kinds, refs, streaming engines.

Includes hypothesis property tests on the system invariants:
  * streaming schedule never changes values (paper §3.1),
  * every (buffer_size, elems_per_fetch, distance) is either valid or
    raises at construction,
  * kind placement round-trips.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType
from proptest import given, settings, strategies as hst

from repro.core import memkind as mk
from repro.core.hoststream import HostStreamExecutor, StreamStats
from repro.core.offload import offload
from repro.core.prefetch import streamed_scan, stream_blocks
from repro.core.refspec import Access, OffloadRef, PrefetchSpec


# ---------------------------------------------------------------------------
# memory kinds
# ---------------------------------------------------------------------------

def test_backend_enumerates_kinds():
    kinds = mk.backend_memory_kinds()
    assert kinds  # every backend exposes at least its default tier
    default = mk.default_memory_kind()
    assert default is None or default in kinds


def test_kind_resolution_fallback_only_for_host():
    assert mk.resolve_kind("device") == mk.DEVICE
    k = mk.resolve_kind("pinned_host")
    assert k.jax_kind in ("pinned_host", "device")


def test_sharding_for_every_kind_is_constructible():
    """Logical kinds must map onto *some* tier on every backend."""
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    for kind in (mk.DEVICE, mk.PINNED_HOST, mk.UNPINNED_HOST):
        s = mk.sharding_for(mesh, jax.sharding.PartitionSpec(), kind)
        y = jax.device_put(jnp.arange(4.0), s)
        np.testing.assert_array_equal(np.asarray(y), np.arange(4.0))


def test_place_round_trip():
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    x = jnp.arange(16.0)
    y = mk.place(x, mesh, jax.sharding.PartitionSpec(), mk.DEVICE)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_policy_one_line_change():
    """The paper's 'swap the kind' property: a policy change is one field."""
    pol = mk.ALL_DEVICE
    moved = pol.with_(opt_state=mk.PINNED_HOST)
    assert moved.opt_state.jax_kind == "pinned_host"
    assert moved.params == pol.params
    assert moved.requires_host()
    assert not pol.requires_host()


def test_new_kind_is_a_subclass():
    """Paper §3.2: a new hierarchy level is a new Kind subclass."""

    class RemotePool(mk.MemKind):
        jax_kind = "pinned_host"  # transport; logically a new level
        level = 9
        directly_addressable = False

    k = RemotePool()
    assert k.level == 9 and not k.directly_addressable


# ---------------------------------------------------------------------------
# PrefetchSpec validation (property)
# ---------------------------------------------------------------------------

@given(
    buffer_size=hst.integers(-2, 8),
    elems=hst.integers(-2, 8),
    distance=hst.integers(-2, 8),
)
def test_prefetch_spec_valid_or_raises(buffer_size, elems, distance):
    valid = (
        buffer_size >= 1
        and elems >= 1
        and 0 <= distance < buffer_size + elems
    )
    if valid:
        s = PrefetchSpec(buffer_size, elems, distance)
        assert s.on_demand == (distance == 0)
    else:
        with pytest.raises(ValueError):
            PrefetchSpec(buffer_size, elems, distance)


# ---------------------------------------------------------------------------
# streamed_scan: schedule-invariance property
# ---------------------------------------------------------------------------

def _layer_body(carry, p):
    return jnp.tanh(carry @ p["w"] + p["b"]), None


@settings(max_examples=20, deadline=None)
@given(
    distance=hst.integers(0, 3),
    elems=hst.sampled_from([1, 2, 4]),
)
def test_streamed_scan_schedule_invariance(distance, elems):
    L, d = 8, 4
    key = jax.random.PRNGKey(0)
    stacked = {
        "w": jax.random.normal(key, (L, d, d)) * 0.5,
        "b": jnp.zeros((L, d)),
    }
    x0 = jnp.ones((2, d))
    spec = PrefetchSpec(buffer_size=max(distance + 1, 1), elements_per_fetch=elems,
                        distance=distance)
    ref, _ = jax.lax.scan(_layer_body, x0, stacked)
    out, _ = streamed_scan(_layer_body, x0, stacked, prefetch=spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_stream_blocks_elementwise():
    xs = jnp.arange(64.0).reshape(16, 4)
    ys = jnp.ones((16, 4))
    spec = PrefetchSpec(buffer_size=2, elements_per_fetch=4, distance=1)
    out = stream_blocks(lambda a, b: a + b, (xs, ys), prefetch=spec)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(xs + ys))


# ---------------------------------------------------------------------------
# @offload decorator (paper Listings 1-3)
# ---------------------------------------------------------------------------

def test_offload_listing1_semantics():
    @offload
    def mykernel(a, b):
        return a + b

    a = np.arange(1000.0, dtype=np.float32)
    b = np.ones(1000, dtype=np.float32)
    out = mykernel(a, b)
    np.testing.assert_array_equal(np.asarray(out), a + b)


def test_offload_eager_equals_streamed():
    refs = dict(
        a=OffloadRef(kind=mk.PINNED_HOST,
                     prefetch=PrefetchSpec(buffer_size=4, elements_per_fetch=2, distance=2)),
        b=OffloadRef(kind=mk.PINNED_HOST,
                     prefetch=PrefetchSpec(buffer_size=4, elements_per_fetch=2, distance=2)),
    )

    @offload(refs=refs)
    def mykernel(a, b):
        return a * 2.0 + b

    a = np.random.randn(16, 8).astype(np.float32)
    b = np.random.randn(16, 8).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(mykernel(a, b)), np.asarray(mykernel.eager(a, b)), rtol=1e-6
    )


def test_offload_place_device_resident():
    """Paper's define_on_device/copy_to_device: pre-place then reuse."""

    @offload
    def k(a, b):
        return a + b

    a_dev = k.place("a", np.ones(8, np.float32))
    out = k(a_dev, np.ones(8, np.float32))
    np.testing.assert_array_equal(np.asarray(out), np.full(8, 2.0, np.float32))


def test_offload_ref_rejects_device_prefetch():
    with pytest.raises(ValueError):
        OffloadRef(kind=mk.DEVICE, prefetch=PrefetchSpec())


# ---------------------------------------------------------------------------
# host-stream executor: request accounting (paper Table 2's real story)
# ---------------------------------------------------------------------------

def test_hoststream_modes_same_result_different_schedule():
    @jax.jit
    def apply(carry, g):
        return carry + jnp.sum(g)

    groups = [np.full((4, 4), float(i), np.float32) for i in range(6)]
    results = {}
    stats = {}
    for mode in ("eager", "on_demand", "prefetch"):
        ex = HostStreamExecutor(apply)
        st = StreamStats()
        out, _ = ex.run(jnp.zeros(()), groups, mode=mode,
                        prefetch=PrefetchSpec(buffer_size=3, elements_per_fetch=1, distance=2),
                        stats=st)
        results[mode] = float(out)
        stats[mode] = st
    assert len(set(results.values())) == 1  # identical values
    assert all(stats[m].n_transfers == 6 for m in stats)
    assert stats["prefetch"].bytes_h2d == stats["on_demand"].bytes_h2d


def test_hoststream_writeback_rw_access():
    """Paper's 'rw' access modifier: written groups return to the host."""
    @jax.jit
    def apply(carry, g):
        return carry, g * 2.0

    groups = [np.ones((2, 2), np.float32) * i for i in range(4)]
    ex = HostStreamExecutor(apply, writeback=True)
    _, outs = ex.run(jnp.zeros(()), groups, mode="prefetch",
                     prefetch=PrefetchSpec(buffer_size=2, elements_per_fetch=1, distance=1))
    assert len(outs) == 4
    np.testing.assert_array_equal(outs[3], np.full((2, 2), 6.0, np.float32))


def test_host_kind_never_falls_back_to_device_off_cpu(monkeypatch):
    """Off the CPU a host kind the backend cannot execute raises instead of
    landing in HBM; the CPU keeps its single-memory fallback."""
    assert mk.resolve_kind("pinned_host") in (mk.PINNED_HOST, mk.DEVICE)
    monkeypatch.setattr(mk, "_on_cpu", lambda: False)
    monkeypatch.setattr(mk, "host_offload_supported", lambda: False)
    with pytest.raises(RuntimeError, match="refusing"):
        mk.resolve_kind("pinned_host")
    with pytest.raises(RuntimeError, match="refusing"):
        mk.resolve_kind(mk.DISK_HOST)
    assert mk.resolve_kind("device") == mk.DEVICE
    monkeypatch.setattr(mk, "backend_memory_kinds", lambda: ("device",))
    with pytest.raises(RuntimeError, match="refusing"):
        mk.backend_kind_string("pinned_host")
    assert mk.backend_kind_string("device") == "device"


def test_offload_probe_error_propagates_off_cpu(monkeypatch):
    """The capability probe swallows a compile error only on the CPU."""
    real_jit = jax.jit

    def broken_jit(*a, **kw):
        raise RuntimeError("probe compile failed")

    monkeypatch.setattr(
        mk, "backend_memory_kinds", lambda: ("device", "pinned_host")
    )
    monkeypatch.setattr(jax, "jit", broken_jit)
    try:
        mk.host_offload_supported.cache_clear()
        assert mk.host_offload_supported() is False  # CPU: unsupported
        mk.host_offload_supported.cache_clear()
        monkeypatch.setattr(mk, "_on_cpu", lambda: False)
        with pytest.raises(RuntimeError, match="probe compile failed"):
            mk.host_offload_supported()
    finally:
        monkeypatch.setattr(jax, "jit", real_jit)
        mk.host_offload_supported.cache_clear()
