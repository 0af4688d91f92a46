"""Transfer-engine tests: coalescing, buffer reuse, pipelined writeback,
adaptive prefetch distance (ISSUE 1 tentpole).

The invariants the paper's runtime depends on:
  * coalescing never changes bytes — packed/unpacked leaves are bitwise
    identical to per-leaf transfers, for every dtype,
  * the engine's schedule never changes results — every (config, mode,
    distance) setting equals the seed executor and plain numpy,
  * 'rw' write-back preserves group order even when pipelined,
  * the adaptive controller converges instead of oscillating.
"""
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (
    AdaptiveDistance,
    EngineConfig,
    GroupLayout,
    LinkModel,
    ShardedGroupLayout,
    TransferEngine,
    static_auto_distance,
)
from repro.core.hoststream import HostStreamExecutor, StreamStats
from repro.core.refspec import AUTO, PrefetchSpec

SEED_CONFIG = EngineConfig(coalesce=False, async_writeback=False)


def _mixed_group(rng):
    return {
        "f32": rng.standard_normal((5, 7)).astype(np.float32),
        "f16": rng.standard_normal((3, 4)).astype(np.float16),
        "i32": rng.integers(-1000, 1000, (11,)).astype(np.int32),
        "u8": rng.integers(0, 255, (13,)).astype(np.uint8),
        "bool": rng.integers(0, 2, (9,)).astype(bool),
    }


# ---------------------------------------------------------------------------
# coalescing: pack/unpack is bitwise exact
# ---------------------------------------------------------------------------

def test_layout_pack_unpack_bitwise_roundtrip():
    rng = np.random.default_rng(0)
    group = _mixed_group(rng)
    layout = GroupLayout(group)
    leaves = jax.tree.leaves(group)
    staging = layout.new_staging()
    layout.pack_into(leaves, staging)
    flat = jax.device_put(staging)
    out = layout.unpack(flat, leaves)
    for a, b in zip(jax.tree.leaves(group), jax.tree.leaves(out)):
        assert np.asarray(b).dtype == a.dtype
        np.testing.assert_array_equal(np.asarray(b), a)


def test_coalesced_equals_per_leaf_transfer_bitwise():
    rng = np.random.default_rng(1)
    group = _mixed_group(rng)
    results = {}
    for name, cfg in (("coalesced", EngineConfig()), ("per_leaf", SEED_CONFIG)):
        with TransferEngine(cfg) as eng:
            fut = eng.submit_group(0, group)
            fut.wait()
            results[name] = jax.tree.map(np.asarray, fut.group())
    for a, b in zip(
        jax.tree.leaves(results["coalesced"]), jax.tree.leaves(results["per_leaf"])
    ):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def _strided_group(rng):
    """Host leaves laid out the way ``jax.device_get`` can hand them back
    (column-major, axes permuted), plus slices, reversals and broadcasts."""
    base = rng.standard_normal((6, 5, 70, 3)).astype(np.float32)
    return {
        "col_major": np.asfortranarray(rng.standard_normal((1500, 90)).astype(np.float32)),
        "bf16_col_major": np.asfortranarray(
            rng.standard_normal((70, 2100)).astype(jnp.bfloat16)),
        "permuted": base.transpose(2, 0, 3, 1).copy().transpose(1, 3, 0, 2),
        "u8_permuted": np.ascontiguousarray(
            rng.integers(0, 255, (3, 5, 1030)).astype(np.uint8).transpose(2, 1, 0)
        ).transpose(2, 1, 0),
        "gappy": base[:, ::2],
        "reversed": base[::-1, :, ::-1],
        "broadcast": np.broadcast_to(np.arange(70, dtype=np.int32)[:, None], (70, 1100)),
        "f64_col_major": np.asfortranarray(np.arange(2400.0).reshape(1200, 2)),
    }


@pytest.mark.parametrize("sharded", [False, True], ids=["default", "sharded"])
def test_strided_leaves_pack_bitwise(sharded):
    """Leaves in any memory order pack, tile by tile where strided, to the
    same bytes as the per-leaf transfer, for every dtype and placement."""
    group = _strided_group(np.random.default_rng(7))
    shardings = (
        jax.sharding.SingleDeviceSharding(jax.devices()[0]) if sharded else None
    )
    results = {}
    for name, cfg in (("coalesced", EngineConfig()), ("per_leaf", SEED_CONFIG)):
        with TransferEngine(cfg) as eng:
            fut = eng.submit_group(0, group, device_shardings=shardings)
            fut.wait()
            results[name] = jax.tree.map(np.asarray, fut.group())
    for k, leaf in group.items():
        a, b = results["coalesced"][k], results["per_leaf"][k]
        assert a.dtype == b.dtype and a.shape == leaf.shape
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, leaf.astype(a.dtype))


def test_request_accounting_and_passthrough():
    """Coalescing: 1 request per group regardless of leaf count; leaves
    already device-resident are passed by reference, never re-sent."""
    rng = np.random.default_rng(2)
    host = {"a": rng.standard_normal((4, 4)).astype(np.float32),
            "b": rng.standard_normal((8,)).astype(np.float32)}
    with TransferEngine() as eng:
        fut = eng.submit_group(0, host)
        fut.wait()
        assert fut.n_requests == 1

        dev_leaf = jnp.arange(6.0)
        mixed = {"host": host["a"], "dev": dev_leaf}
        fut2 = eng.submit_group(1, mixed)
        fut2.wait()
        assert fut2.n_requests == 1
        staged = fut2.group()
        assert staged["dev"] is dev_leaf  # by reference, not copied
    with TransferEngine(SEED_CONFIG) as eng:
        fut = eng.submit_group(0, host)
        fut.wait()
        assert fut.n_requests == 2  # one per host leaf (the seed's cost)


def test_coalesced_canonicalizes_wide_dtypes_like_device_put():
    """float64/int64 host leaves must coalesce to the same (canonical f32/
    i32) result the per-leaf device_put path produces (found in review)."""
    group = (np.arange(6, dtype=np.float64).reshape(2, 3),
             np.arange(4, dtype=np.int64))
    results = {}
    for name, cfg in (("coalesced", EngineConfig()), ("per_leaf", SEED_CONFIG)):
        with TransferEngine(cfg) as eng:
            fut = eng.submit_group(0, group)
            fut.wait()
            results[name] = jax.tree.map(np.asarray, fut.group())
    for a, b in zip(
        jax.tree.leaves(results["coalesced"]), jax.tree.leaves(results["per_leaf"])
    ):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_failed_run_does_not_leak_writebacks_into_next_run():
    """An exception mid-run leaves pending writeback tickets; the next run
    on the same executor must not drain them (found in review)."""
    calls = {"n": 0}

    def apply(carry, g):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("injected")
        return carry, g * 2.0

    groups = [np.full((2,), float(i), np.float32) for i in range(4)]
    with HostStreamExecutor(apply, writeback=True) as ex:
        with pytest.raises(RuntimeError):
            ex.run(jnp.zeros(()), groups, mode="prefetch")
        _, outs = ex.run(jnp.zeros(()), [groups[3], groups[2]], mode="prefetch")
    assert len(outs) == 2
    np.testing.assert_array_equal(outs[0], groups[3] * 2.0)
    np.testing.assert_array_equal(outs[1], groups[2] * 2.0)


def test_adaptive_controller_persists_across_runs():
    """The train loop issues one short run() per step; the learned window
    must carry over instead of restarting at min_distance (found in
    review)."""
    @jax.jit
    def apply(carry, g):
        return carry + jnp.sum(g)

    groups = [np.ones((32, 32), np.float32)] * 4
    link = LinkModel(request_s=1e-4, bandwidth_Bps=1e9, latency_s=2e-3)
    with HostStreamExecutor(apply, engine_config=EngineConfig(link=link)) as ex:
        st = StreamStats()
        for _ in range(6):  # six "training steps" of 4 groups each
            ex.run(jnp.zeros(()), groups, mode="prefetch",
                   prefetch=PrefetchSpec(buffer_size=10, distance=AUTO), stats=st)
        trace = list(st.distance_trace)
    # with a fresh controller per run the window could never exceed ~3 for
    # 4-group runs; persistence lets later steps start where earlier ended
    assert trace[-4] > 1


def test_staging_pool_is_reused_not_grown():
    """Buffer reuse: many groups of one layout allocate O(slots) staging
    buffers, not O(groups)."""
    rng = np.random.default_rng(3)
    groups = [
        {"x": rng.standard_normal((16,)).astype(np.float32)} for _ in range(32)
    ]
    with TransferEngine() as eng:
        for i, grp in enumerate(groups):
            eng.submit_group(i, grp).wait()
        assert eng.staging_allocs <= eng.config.staging_slots + 1


class _LandedFlat:
    """A put's flat buffer as its backend reports it: the device it landed
    on, and its buffer pointer (``None``: the backend will not report one)."""

    def __init__(self, platform: str, pointer) -> None:
        self._device = SimpleNamespace(platform=platform)
        self._pointer = pointer

    def devices(self):
        return [self._device]

    def unsafe_buffer_pointer(self) -> int:
        if self._pointer is None:
            raise RuntimeError("no host pointer for this buffer")
        return self._pointer

    def block_until_ready(self):
        return self


def _landed(platform: str, reports: str, staging: np.ndarray) -> _LandedFlat:
    pointer = {"raises": None, "staging": staging.ctypes.data,
               "elsewhere": staging.ctypes.data + 64}[reports]
    return _LandedFlat(platform, pointer)


# (platform the put landed on, what its pointer reports, pool recycles?)
_PLACEMENTS = [
    ("tpu", "raises", True),      # an accelerator holds its own copy
    ("cpu", "elsewhere", True),   # a CPU put that copied
    ("cpu", "staging", False),    # a CPU put that zero-copied the buffer
    ("cpu", "raises", False),     # a CPU backend that will not say: assume aliasing
]


@pytest.mark.parametrize("platform,reports,recycles", _PLACEMENTS)
def test_staging_pool_recycles_by_placement(monkeypatch, platform, reports, recycles):
    """The pool recycles a staging buffer unless the put could share its
    memory, which only a CPU device can; every pack either reuses a pooled
    buffer or allocates one."""
    monkeypatch.setattr(
        GroupLayout, "put_staged",
        lambda self, staging: _landed(platform, reports, staging),
    )
    rng = np.random.default_rng(5)
    with TransferEngine() as eng:
        for i in range(32):
            eng.submit_group(i, {"x": rng.standard_normal((16,)).astype(np.float32)}).wait()
        assert eng.staging_allocs + eng.staging_reuses == 32
        assert eng.staging_allocs == (1 if recycles else 32)


@pytest.mark.parametrize("platform,reports,recycles", _PLACEMENTS)
def test_sharded_staging_pool_recycles_by_placement(monkeypatch, platform, reports, recycles):
    """The same rule per device for sharded layouts: the per-device buffer
    set is recycled unless some device's put could share its buffer."""
    monkeypatch.setattr(
        ShardedGroupLayout, "put_staged",
        lambda self, stagings: [_landed(platform, reports, b) for b in stagings],
    )
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    rng = np.random.default_rng(6)
    with TransferEngine() as eng:
        for i in range(32):
            grp = {"x": rng.standard_normal((4, 4)).astype(np.float32)}
            eng.submit_group(i, grp, device_shardings=sharding).wait()
        assert eng.staging_allocs + eng.staging_reuses == 32
        assert eng.staging_allocs == (1 if recycles else 32)


def test_sharded_any_alias_asks_each_device():
    """One device whose put may share its buffer keeps the whole set out of
    the pool; a set whose puts all landed off the CPU goes back."""
    grp = {"x": np.zeros((4,), np.float32)}
    layout = ShardedGroupLayout(
        grp, [jax.sharding.SingleDeviceSharding(jax.devices()[0])]
    )
    bufs = [np.zeros(4, np.uint32), np.zeros(4, np.uint32)]
    tpu = [_landed("tpu", "raises", b) for b in bufs]
    assert not layout.any_alias(tpu, bufs)
    mixed = [tpu[0], _landed("cpu", "staging", bufs[1])]
    assert layout.any_alias(mixed, bufs)


# ---------------------------------------------------------------------------
# executor: every (config, mode, distance) setting is value-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["eager", "on_demand", "prefetch"])
@pytest.mark.parametrize("config", [None, SEED_CONFIG], ids=["engine", "seed"])
def test_executor_schedule_invariance(mode, config):
    @jax.jit
    def apply(carry, g):
        x, w = g
        return carry + jnp.sum(x @ w)

    rng = np.random.default_rng(4)
    groups = [
        (rng.standard_normal((4, 8)).astype(np.float32),
         rng.standard_normal((8, 2)).astype(np.float32))
        for _ in range(7)
    ]
    expected = sum(float(np.sum(x @ w)) for x, w in groups)
    with HostStreamExecutor(apply, engine_config=config) as ex:
        st = StreamStats()
        out, _ = ex.run(
            jnp.zeros(()), groups, mode=mode,
            prefetch=PrefetchSpec(buffer_size=4, distance=2), stats=st,
        )
    np.testing.assert_allclose(float(out), expected, rtol=1e-5)
    assert st.n_groups == 7
    if config is None:
        assert st.requests_per_group == 1.0  # the tentpole claim
    else:
        assert st.requests_per_group == 2.0  # one per leaf


@pytest.mark.parametrize("distance", [1, 3, AUTO])
def test_executor_distance_sweep_matches_eager(distance):
    @jax.jit
    def apply(carry, g):
        return carry + jnp.sum(g)

    groups = [np.full((3, 3), float(i), np.float32) for i in range(9)]
    with HostStreamExecutor(apply) as ex:
        ref, _ = ex.run(jnp.zeros(()), groups, mode="eager")
        out, _ = ex.run(
            jnp.zeros(()), groups, mode="prefetch",
            prefetch=PrefetchSpec(buffer_size=10, distance=distance),
        )
    assert float(out) == float(ref)


# ---------------------------------------------------------------------------
# pipelined writeback ('rw' access)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["eager", "on_demand", "prefetch"])
def test_async_writeback_preserves_group_order(mode):
    @jax.jit
    def apply(carry, g):
        return carry, g * 2.0

    groups = [np.full((2, 2), float(i), np.float32) for i in range(8)]
    outs = {}
    for name, cfg in (("async", EngineConfig()), ("sync", SEED_CONFIG)):
        with HostStreamExecutor(apply, writeback=True, engine_config=cfg) as ex:
            st = StreamStats()
            _, o = ex.run(
                jnp.zeros(()), groups, mode=mode,
                prefetch=PrefetchSpec(buffer_size=3, distance=2), stats=st,
            )
            outs[name] = o
            assert st.d2h_requests > 0
    for i in range(8):
        np.testing.assert_array_equal(outs["async"][i], groups[i] * 2.0)
        np.testing.assert_array_equal(outs["async"][i], outs["sync"][i])


def test_writeback_drain_returns_host_arrays():
    @jax.jit
    def apply(carry, g):
        return carry, {"y": g["x"] + 1.0}

    groups = [{"x": np.full((4,), float(i), np.float32)} for i in range(5)]
    with HostStreamExecutor(apply, writeback=True) as ex:
        _, outs = ex.run(jnp.zeros(()), groups, mode="prefetch")
    assert len(outs) == 5
    for i, o in enumerate(outs):
        assert isinstance(o["y"], np.ndarray)
        np.testing.assert_array_equal(o["y"], groups[i]["x"] + 1.0)


# ---------------------------------------------------------------------------
# adaptive prefetch distance
# ---------------------------------------------------------------------------

def test_adaptive_distance_grows_on_stall():
    c = AdaptiveDistance(initial=1, max_distance=8, wait_eps_s=1e-4)
    for _ in range(5):
        c.observe(1e-2)  # heavy stalls
    assert c.distance > 1


def test_adaptive_distance_shrinks_when_idle():
    c = AdaptiveDistance(initial=6, max_distance=8, wait_eps_s=1e-4, shrink_after=2)
    for _ in range(20):
        c.observe(0.0)
    assert c.distance == c.min_distance


def test_adaptive_distance_sticky_floor_prevents_oscillation():
    c = AdaptiveDistance(initial=3, max_distance=8, wait_eps_s=1e-4, shrink_after=1)
    c.observe(0.0)  # shrink 3 -> 2
    assert c.distance == 2
    c.observe(1e-2)  # stall right after shrinking: 3 was minimal
    assert c.distance == 3
    for _ in range(10):
        c.observe(0.0)
    assert c.distance == 3  # floor holds: no repeated shrink/stall cycle


def test_auto_distance_converges_with_emulated_link():
    """distance='auto' on a slow emulated link: window grows off 1, waits
    after convergence are lower than the steady distance=1 waits."""
    @jax.jit
    def apply(carry, g):
        return carry + jnp.sum(g * g)

    rng = np.random.default_rng(5)
    groups = [rng.standard_normal((64, 64)).astype(np.float32) for _ in range(24)]
    link = LinkModel(request_s=1e-4, bandwidth_Bps=2e9, latency_s=2e-3)
    waits = {}
    vals = {}
    for dist in (1, AUTO):
        with HostStreamExecutor(
            apply, engine_config=EngineConfig(link=link)
        ) as ex:
            st = StreamStats()
            out, _ = ex.run(
                jnp.zeros(()), groups, mode="prefetch",
                prefetch=PrefetchSpec(buffer_size=16, distance=dist), stats=st,
            )
            waits[dist] = list(st.wait_per_group)
            vals[dist] = float(out)
    assert vals[1] == vals[AUTO]  # schedule never changes values
    # steady state: second half of the run
    tail = lambda w: sum(w[len(w) // 2:])
    assert tail(waits[AUTO]) < tail(waits[1])


def test_prefetch_spec_auto_validation():
    s = PrefetchSpec(buffer_size=4, distance=AUTO)
    assert s.is_auto and not s.on_demand
    assert s.numeric_distance(3) == 3
    assert PrefetchSpec(distance=2).numeric_distance(3) == 2
    with pytest.raises(ValueError):
        PrefetchSpec(distance="nonsense")
    assert static_auto_distance(10) == 4
    assert static_auto_distance(2) == 1


# ---------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------

def test_stream_stats_row_is_json_serializable():
    @jax.jit
    def apply(carry, g):
        return carry + jnp.sum(g)

    groups = [np.ones((2, 2), np.float32)] * 4
    with HostStreamExecutor(apply) as ex:
        st = StreamStats()
        ex.run(jnp.zeros(()), groups, mode="prefetch", stats=st)
    row = st.as_row()
    json.dumps(row)  # must not raise
    assert row["requests_per_group"] == 1.0
    assert sum(row["wait_hist"].values()) == 4


def test_stream_stats_reset():
    st = StreamStats(mode="prefetch")
    st.n_transfers = 5
    st.wait_per_group.append(0.1)
    st.reset()
    assert st.mode == "prefetch"
    assert st.n_transfers == 0 and len(st.wait_per_group) == 0


# ---------------------------------------------------------------------------
# streamed optimizer update (the train-loop wiring)
# ---------------------------------------------------------------------------

def test_streamed_adamw_matches_reference():
    from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
    from repro.train.steps import host_opt_state, make_streamed_opt_updater

    key = jax.random.PRNGKey(0)
    params = {
        "a": jax.random.normal(key, (16, 8)),
        "b": {"w": jax.random.normal(key, (8,)), "u": jax.random.normal(key, (4, 4))},
    }
    cfg = AdamWConfig(peak_lr=1e-2, warmup_steps=2, total_steps=20)
    ref_step = jax.jit(lambda g, o: adamw_update(cfg, g, o, compute_dtype=jnp.float32))

    p_ref, opt_ref = params, adamw_init(params)
    p_st, opt_h = params, host_opt_state(params)
    upd = make_streamed_opt_updater(
        cfg, compute_dtype=jnp.float32, n_groups=2,
        prefetch=PrefetchSpec(buffer_size=4, distance=AUTO),
    )
    st = StreamStats()
    try:
        for i in range(5):
            g = jax.tree.map(lambda p: jnp.ones_like(p) * 0.1 * (i + 1), params)
            p_ref, opt_ref, m_ref = ref_step(g, opt_ref)
            p_st, opt_h, m_st = upd(g, opt_h, stats=st)
    finally:
        upd.close()
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_st)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-7)
    for a, b in zip(jax.tree.leaves(opt_ref["leaves"]), jax.tree.leaves(opt_h["leaves"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-7)
    # state home is the host: plain numpy leaves, coalesced single requests
    assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(opt_h["leaves"]))
    assert st.requests_per_group == 1.0
    np.testing.assert_allclose(float(m_ref["lr"]), float(m_st["lr"]), rtol=1e-6)


def test_host_opt_state_is_eval_shape_safe():
    """The driver's restore path builds its template with
    ``jax.eval_shape(init_state)`` — host_opt_state must trace cleanly
    (found by verification: np.asarray on tracers)."""
    from repro.train.steps import host_opt_state

    def build():
        return host_opt_state({"w": jnp.ones((3, 2)) * 2.0})

    tpl = jax.eval_shape(build)
    assert tpl["leaves"]["w"]["m"].shape == (3, 2)
    concrete = build()
    assert isinstance(concrete["leaves"]["w"]["m"], np.ndarray)


def test_offload_stream_host_matches_compiled_paths():
    from repro.core import memkind as mk
    from repro.core.offload import offload
    from repro.core.refspec import OffloadRef

    spec = PrefetchSpec(buffer_size=4, elements_per_fetch=4, distance=2)

    @offload(refs=dict(
        a=OffloadRef(kind=mk.PINNED_HOST, prefetch=spec),
        b=OffloadRef(kind=mk.PINNED_HOST, prefetch=spec),
    ))
    def k(a, b):
        return a * 2.0 + b

    rng = np.random.default_rng(6)
    a = rng.standard_normal((16, 8)).astype(np.float32)
    b = rng.standard_normal((16, 8)).astype(np.float32)
    st = StreamStats()
    out = k.stream_host(a, b, stats=st)
    np.testing.assert_allclose(out, np.asarray(k(a, b)), rtol=1e-6)
    np.testing.assert_allclose(out, np.asarray(k.eager(a, b)), rtol=1e-6)
    assert st.requests_per_group == 1.0  # blocks of (a, b) coalesce
