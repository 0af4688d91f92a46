"""Cross-kind conformance matrix: the uniform-semantics guarantee.

The paper's abstraction promises that *where* data lives never changes
*what* a kernel computes — a kind swap is a one-line change (§3.2), valid
for levels the accelerator cannot address directly (host) or at all
(disk).  This suite runs one streamed workload over every registered
``MemKind`` x access mode (``ro``/``rw``) x prefetch distance
(``0``/``1``/``"auto"``) and asserts:

  * bitwise equality with the eager (bulk-copy) path at the same kind and
    with the all-device reference,
  * correct per-tier ``StreamStats`` request accounting (device leaves are
    never re-sent; host groups coalesce to 1 H2D request; disk groups add
    exactly 1 disk request each).

Also here: the ``DiskHost`` acceptance tests (data + optimizer state
larger than the host budget, sourced from disk, same values) and the
``stream_host`` executor-cache regression (cache must key on policy/kinds,
not just the streamed-arg set).
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import memkind as mk
from repro.core.hoststream import HostStreamExecutor, StreamStats
from repro.core.offload import offload
from repro.core.refspec import AUTO, OffloadRef, PrefetchSpec
from repro.core.spillstore import SpillStore, is_disk_leaf

N_GROUPS = 5


def _host_groups(rng):
    return [
        {
            "w": rng.standard_normal((4, 4)).astype(np.float32),
            "b": np.asarray(
                jnp.asarray(rng.standard_normal((4,)), jnp.bfloat16)
            ),
        }
        for _ in range(N_GROUPS)
    ]


def _groups_at_kind(kind: mk.MemKind, groups_host, tmp_path):
    """The home representation of the groups at a hierarchy level."""
    if kind.jax_kind == "device":
        return [jax.tree.map(jnp.asarray, g) for g in groups_host]
    if not kind.jax_addressable:
        store = SpillStore(tmp_path / "spill")
        out = []
        for i, g in enumerate(groups_host):
            store.put(f"g{i}", g)
            out.append(store.get(f"g{i}"))
        return out
    # pinned/unpinned host: host-resident numpy is the home representation
    # the stream engine serves (the backend fallback story is memkind's)
    return groups_host


@jax.jit
def _apply_ro(carry, g):
    return carry + jnp.sum(g["w"]) * 2.0 + jnp.sum(g["b"].astype(jnp.float32))


@jax.jit
def _apply_rw(carry, g):
    out = {"w": g["w"] * 2.0 + 1.0, "b": g["b"]}
    return carry + jnp.sum(g["w"]), out


@pytest.mark.parametrize("distance", [0, 1, AUTO], ids=["d0", "d1", "auto"])
@pytest.mark.parametrize("access", ["ro", "rw"])
@pytest.mark.parametrize(
    "kind", mk.all_kinds(), ids=[type(k).__name__ for k in mk.all_kinds()]
)
def test_kind_conformance_matrix(kind, access, distance, tmp_path):
    rng = np.random.default_rng(7)
    groups_host = _host_groups(rng)
    groups = _groups_at_kind(kind, groups_host, tmp_path)
    writeback = access == "rw"
    apply = _apply_rw if writeback else _apply_ro

    # the all-device reference: everything already at the fast tier
    dev_groups = [jax.tree.map(jnp.asarray, g) for g in groups_host]
    with HostStreamExecutor(apply, writeback=writeback) as ex:
        ref, ref_outs = ex.run(jnp.zeros(()), dev_groups, mode="eager")

    mode = "on_demand" if distance == 0 else "prefetch"
    prefetch = (
        None
        if distance == 0
        else PrefetchSpec(buffer_size=N_GROUPS + 2, distance=distance)
    )
    st = StreamStats()
    with HostStreamExecutor(apply, writeback=writeback) as ex:
        eager, eager_outs = ex.run(jnp.zeros(()), groups, mode="eager")
        out, outs = ex.run(
            jnp.zeros(()), groups, mode=mode, prefetch=prefetch, stats=st
        )

    # uniform semantics: same value at every kind, every schedule — bitwise
    assert float(out) == float(eager) == float(ref)
    if writeback:
        for o, eo, ro in zip(outs, eager_outs, ref_outs):
            for a, b, c in zip(
                jax.tree.leaves(o), jax.tree.leaves(eo), jax.tree.leaves(ro)
            ):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
                np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    # request accounting per tier
    assert st.n_groups == N_GROUPS
    if kind.jax_kind == "device":
        assert st.h2d_requests == 0  # pass-by-reference: nothing re-sent
        assert st.disk_requests == 0
    else:
        assert st.h2d_requests == N_GROUPS  # coalesced: 1 request per group
        if kind.jax_addressable:
            assert st.disk_requests == 0
        else:
            assert st.disk_requests == N_GROUPS  # 1 chunk file per group
            assert st.bytes_disk > 0
    if writeback:
        assert st.d2h_requests > 0


# ---------------------------------------------------------------------------
# DiskHost acceptance: data + optimizer state larger than the host budget
# ---------------------------------------------------------------------------


def test_streamed_kernel_from_disk_exceeds_host_budget(tmp_path):
    """An offloaded streamed kernel whose streamed data is sourced from the
    DiskHost tier, with total bytes far above the host-staging footprint
    (the engine holds at most O(window) chunks in RAM), bitwise-equal to
    the host-kind streamed run and to eager."""
    spec = PrefetchSpec(buffer_size=4, elements_per_fetch=4, distance=AUTO)

    @offload(refs=dict(
        a=OffloadRef(kind=mk.PINNED_HOST, prefetch=spec),
        b=OffloadRef(kind=mk.PINNED_HOST, prefetch=spec),
    ))
    def k(a, b):
        return a * 2.0 + b

    rng = np.random.default_rng(8)
    a = rng.standard_normal((64, 32)).astype(np.float32)
    b = rng.standard_normal((64, 32)).astype(np.float32)
    st_host, st_disk = StreamStats(), StreamStats()
    try:
        out_host = k.stream_host(a, b, stats=st_host)
        out_disk = k.stream_host(
            a, b, stats=st_disk, policy=mk.DISK_PARAMS,
            spill_dir=tmp_path / "spill",
        )
        eager = np.asarray(k.eager(a, b))
    finally:
        k.close()
    np.testing.assert_array_equal(out_disk, out_host)  # tier swap: bitwise
    np.testing.assert_allclose(out_disk, eager, rtol=1e-6)
    # every block came off disk, one chunk request each, still 1 H2D/group
    n_blocks = 64 // 4
    assert st_disk.disk_requests == n_blocks
    assert st_disk.requests_per_group == 1.0
    assert st_host.disk_requests == 0
    # the host-staging footprint is bounded by the engine pools, not the
    # data size: the store holds the full data set, RAM only a window
    total_bytes = a.nbytes + b.nbytes
    assert st_disk.bytes_disk == total_bytes


def test_streamed_adamw_spilled_beyond_budget_matches_host(tmp_path):
    """Streamed AdamW with moments spilled to disk under a host-RAM budget
    smaller than the state: bitwise-identical params and state trajectory
    to the all-host streamed run, disk groups stay disk-homed."""
    from repro.optim.adamw import AdamWConfig, opt_state_bytes
    from repro.train.steps import (
        host_opt_state,
        make_streamed_opt_updater,
        spill_opt_state,
    )

    key = jax.random.PRNGKey(0)
    params = {
        "a": jax.random.normal(key, (32, 8)),
        "b": {"w": jax.random.normal(key, (16,)),
              "u": jax.random.normal(key, (8, 8))},
    }
    cfg = AdamWConfig(peak_lr=1e-2, warmup_steps=2, total_steps=20)
    pf = PrefetchSpec(buffer_size=6, distance=AUTO)
    store = SpillStore(tmp_path / "opt")

    total = opt_state_bytes(params)
    budget = total // 3  # forces most of the state below the budget to disk
    opt_host = host_opt_state(params)
    opt_disk = spill_opt_state(
        host_opt_state(params), store, n_groups=3, host_budget_bytes=budget
    )
    disk_leaves = [x for x in jax.tree.leaves(opt_disk["leaves"]) if is_disk_leaf(x)]
    ram_bytes = sum(
        x.nbytes for x in jax.tree.leaves(opt_disk["leaves"]) if not is_disk_leaf(x)
    )
    assert disk_leaves, "budget should force some groups to disk"
    assert ram_bytes <= budget

    upd_h = make_streamed_opt_updater(
        cfg, compute_dtype=jnp.float32, n_groups=3, prefetch=pf
    )
    upd_d = make_streamed_opt_updater(
        cfg, compute_dtype=jnp.float32, n_groups=3, prefetch=pf, spill_store=store
    )
    st = StreamStats()
    p_h, p_d = params, params
    try:
        for i in range(4):
            g = jax.tree.map(lambda p: jnp.ones_like(p) * 0.1 * (i + 1), params)
            p_h, opt_host, _ = upd_h(g, opt_host)
            p_d, opt_disk, _ = upd_d(g, opt_disk, stats=st)
    finally:
        upd_h.close()
        upd_d.close()
    for a, b in zip(jax.tree.leaves(p_h), jax.tree.leaves(p_d)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(opt_host["leaves"]), jax.tree.leaves(opt_disk["leaves"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # updated moments went back to their disk home, not host RAM
    assert any(is_disk_leaf(x) for x in jax.tree.leaves(opt_disk["leaves"]))
    assert st.disk_requests > 0 and st.requests_per_group == 1.0


@pytest.mark.slow
def test_disk_opt_trainer_end_to_end_and_restore_respills(tmp_path):
    """launch.train wiring: a DISK_OPT streamed-optimizer trainer runs,
    spills moments to the spill dir, produces finite losses — and a
    checkpoint-restored continuation re-imposes the disk budget (restored
    state is plain host numpy; it must not silently stay in RAM)."""
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import build_trainer
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.driver import DriverConfig

    cfg = get_smoke_config("smollm-360m")
    mesh = make_local_mesh()

    def make_driver(total_steps):
        return build_trainer(
            cfg,
            mesh,
            global_batch=2,
            seq_len=16,
            opt_cfg=AdamWConfig(peak_lr=1e-3, warmup_steps=1, total_steps=4),
            driver_cfg=DriverConfig(
                total_steps=total_steps,
                checkpoint_every=2,
                checkpoint_dir=str(tmp_path / "ckpt"),
                log_every=0,
            ),
            policy=mk.DISK_OPT,
            stream_opt=True,
            spill_dir=str(tmp_path / "spill"),
            host_budget_mb=0.0,  # spill everything
        )

    driver = make_driver(2)
    driver.run()
    losses = [h["loss"] for h in driver.history]
    assert len(losses) == 2 and all(np.isfinite(losses))
    assert driver.stream_stats.disk_requests > 0
    assert driver.spill_store is not None and driver.spill_store.total_bytes() > 0

    # resume from the checkpoint: restored moments are plain numpy, the
    # budget must be re-imposed so the disk tier keeps serving them
    driver2 = make_driver(4)
    driver2.run()
    assert [h["step"] for h in driver2.history] == [2, 3]
    assert driver2.stream_stats.disk_requests > 0


# ---------------------------------------------------------------------------
# sharded axis: the same matrix on a forced 2-device host mesh
# ---------------------------------------------------------------------------

_SHARDED_MATRIX_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import memkind as mk
from repro.core.engine import TransferEngine
from repro.core.hoststream import HostStreamExecutor, StreamStats
from repro.core.refspec import AUTO, PrefetchSpec
from repro.core.spillstore import SpillStore

N_GROUPS = 4
assert len(jax.devices()) == 2, jax.devices()
mesh = jax.make_mesh((1, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
# one model-sharded leaf, one replicated bf16 leaf per group
shardings = {"w": NamedSharding(mesh, P(None, "model")),
             "b": NamedSharding(mesh, P())}
rng = np.random.default_rng(7)
groups_host = [
    {"w": rng.standard_normal((4, 4)).astype(np.float32),
     "b": np.asarray(jnp.asarray(rng.standard_normal((4,)), jnp.bfloat16))}
    for _ in range(N_GROUPS)
]

def groups_at(kind, tmp):
    if kind.jax_kind == "device":
        return [jax.device_put(g, shardings) for g in groups_host]
    if not kind.jax_addressable:
        store = SpillStore(tmp)
        out = []
        for i, g in enumerate(groups_host):
            store.put(f"g{i}", g)
            out.append(store.get(f"g{i}"))
        return out
    return groups_host

@jax.jit
def apply_ro(carry, g):
    return carry + jnp.sum(g["w"]) * 2.0 + jnp.sum(g["b"].astype(jnp.float32))

@jax.jit
def apply_rw(carry, g):
    return carry + jnp.sum(g["w"]), {"w": g["w"] * 2.0 + 1.0, "b": g["b"]}

# engine level: staged leaves carry the exact sharding AND bytes of eager
# sharded placement
eng = TransferEngine()
fut = eng.submit_group(0, groups_host[0], device_shardings=shardings)
fut.wait()
staged = fut.group()
eager0 = jax.device_put(groups_host[0], shardings)
for k in ("w", "b"):
    assert staged[k].sharding == eager0[k].sharding, (k, staged[k].sharding)
    np.testing.assert_array_equal(np.asarray(staged[k]), np.asarray(eager0[k]))
assert fut.n_requests == 2 and fut.n_devices == 2, (fut.n_requests, fut.n_devices)
eng.close()

eager_groups = [jax.device_put(g, shardings) for g in groups_host]
for access in ("ro", "rw"):
    wb = access == "rw"
    apply = apply_rw if wb else apply_ro
    with HostStreamExecutor(apply, writeback=wb, device_shardings=shardings) as ex:
        ref, ref_outs = ex.run(jnp.zeros(()), eager_groups, mode="eager")
    for kind in mk.all_kinds():
        for dist in (0, 1, AUTO):
            tmp = tempfile.mkdtemp(prefix=f"conf-{kind.jax_kind}-")
            groups = groups_at(kind, tmp)
            mode = "on_demand" if dist == 0 else "prefetch"
            pf = None if dist == 0 else PrefetchSpec(
                buffer_size=N_GROUPS + 2, distance=dist)
            st = StreamStats()
            with HostStreamExecutor(apply, writeback=wb,
                                    device_shardings=shardings) as ex:
                out, outs = ex.run(jnp.zeros(()), groups, mode=mode,
                                   prefetch=pf, stats=st)
            cell = (kind.jax_kind, access, dist)
            # bitwise vs eager sharded placement at every kind x schedule
            assert float(out) == float(ref), cell
            if wb:
                for o, ro in zip(outs, ref_outs):
                    for a, b in zip(jax.tree.leaves(o), jax.tree.leaves(ro)):
                        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            # per-device request accounting: one request per (device, group)
            assert st.n_groups == N_GROUPS, cell
            tier = st.per_tier()
            if kind.jax_kind == "device":
                assert st.h2d_requests == 0 and st.disk_requests == 0, cell
                assert st.n_devices == 1, cell
            else:
                assert st.n_devices == 2, cell
                assert st.h2d_requests == 2 * N_GROUPS, (cell, st.h2d_requests)
                assert st.requests_per_group == 2.0, cell
                assert tier["h2d"]["requests_per_device_group"] == 1.0, cell
                if kind.jax_addressable:
                    assert st.disk_requests == 0, cell
                else:
                    assert st.disk_requests == N_GROUPS, cell
                    assert st.bytes_disk > 0, cell
print("SHARDED_CONFORMANCE_OK")
"""


@pytest.mark.slow
def test_sharded_conformance_matrix_2way_mesh():
    """The tentpole pin: every MemKind x ro/rw x distance 0/1/auto on a
    forced 2-device host mesh — bitwise equal to eager sharded placement,
    exactly one H2D request per (device, group)."""
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_MATRIX_SCRIPT],
        capture_output=True,
        text=True,
        timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": "/root"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED_CONFORMANCE_OK" in proc.stdout


# ---------------------------------------------------------------------------
# regression: stream_host executor cache must key on policy/kinds/engine
# ---------------------------------------------------------------------------


def test_stream_host_cache_keys_on_policy_and_engine(tmp_path):
    """Switching PlacementPolicy (or engine) between stream_host calls must
    build a fresh executor — the old cache keyed only on the streamed-arg
    set, so the second call silently reused the first call's tier/engine."""
    from repro.core.engine import TransferEngine

    spec = PrefetchSpec(buffer_size=4, elements_per_fetch=2, distance=1)

    @offload(refs=dict(x=OffloadRef(kind=mk.PINNED_HOST, prefetch=spec)))
    def k(x):
        return x + 1.0

    rng = np.random.default_rng(9)
    x = rng.standard_normal((8, 3)).astype(np.float32)
    eng = TransferEngine()
    try:
        st_host, st_disk = StreamStats(), StreamStats()
        out1 = k.stream_host(x, stats=st_host)
        out2 = k.stream_host(
            x, stats=st_disk, policy=mk.DISK_PARAMS, spill_dir=tmp_path / "s"
        )
        out3 = k.stream_host(x, engine=eng)
        # three distinct (kinds, engine) bindings -> three executors
        assert len(k._stream_host_cache) == 3
        # the disk-policy call really went through the disk tier (a stale
        # host executor would leave disk_requests at 0)
        assert st_disk.disk_requests > 0 and st_host.disk_requests == 0
        for o in (out2, out3):
            np.testing.assert_array_equal(out1, o)
        # same binding twice -> cache hit, not a fourth executor
        k.stream_host(x)
        assert len(k._stream_host_cache) == 3
    finally:
        k.close()
        eng.close()


def test_stream_host_cache_keys_on_streamed_tree_structure():
    """The executor's broadcast device_shardings are derived from the first
    call's streamed pytree structure; a different structure for the same
    arg name must build a fresh executor instead of tripping a leaf-count
    mismatch (found in review of the sharded-coalescing change)."""
    spec = PrefetchSpec(buffer_size=4, elements_per_fetch=2, distance=1)

    @offload(refs=dict(x=OffloadRef(kind=mk.PINNED_HOST, prefetch=spec)))
    def k(x):
        return jax.tree.map(lambda a: a + 1.0, x)

    rng = np.random.default_rng(11)
    a = rng.standard_normal((8, 3)).astype(np.float32)
    b = rng.standard_normal((8, 3)).astype(np.float32)
    try:
        out1 = k.stream_host({"a": a})
        out2 = k.stream_host({"a": a, "b": b})  # same arg, wider pytree
        assert len(k._stream_host_cache) == 2
        np.testing.assert_allclose(out1["a"], a + 1.0, rtol=1e-6)
        np.testing.assert_allclose(out2["b"], b + 1.0, rtol=1e-6)
    finally:
        k.close()
