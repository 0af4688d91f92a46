"""Property-based coalescing round-trip tests (via the proptest grid shim).

``tests/test_engine.py`` covers hand-picked layouts; this suite sweeps the
pack -> device_put -> bitcast-unpack round trip over the property space the
engine actually sees in training: mixed dtypes (bf16, f32, i32,
f64-canonicalized), odd and zero-length shapes, deep pytrees, and
disk-tier (spill store) sources — asserting bitwise equality with the
per-leaf ``jax.device_put`` reference in every cell.

The sharded axis (``ShardedGroupLayout`` on a forced 2-device mesh) runs
the same property sweep in a subprocess: odd/unaligned shard byte-lengths
(JAX rejects non-divisible explicit shardings outright, so "uneven" means
shards whose sizes force unaligned offsets into the per-device staging
buffers), replicated/zero-length/scalar leaves, bf16/f64, deep pytrees —
asserting bitwise reassembly vs eager sharded placement and exact
per-device request accounting.
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proptest import given, settings, strategies as hst

from repro.core.engine import GroupLayout, TransferEngine
from repro.core.spillstore import SpillStore

#: dtype menu: extension (bf16), native, integer, and canonicalized-wide
_DTYPES = ["bfloat16", "float32", "int32", "float64"]


def _make_leaf(rng, n, dtype_name):
    a = rng.standard_normal((max(n, 0),))
    if dtype_name == "bfloat16":
        return np.asarray(jnp.asarray(a, jnp.bfloat16))
    if dtype_name in ("int32",):
        return (a * 100).astype(dtype_name)
    return a.astype(dtype_name)


def _roundtrip_equals_device_put(group):
    """pack -> H2D -> unpack must equal per-leaf device_put, bitwise."""
    leaves = jax.tree.leaves(group)
    layout = GroupLayout(group)
    staging = layout.new_staging()
    layout.pack_into(leaves, staging)
    flat = jax.device_put(staging)
    out = layout.unpack(flat, leaves)
    for got, src in zip(jax.tree.leaves(out), leaves):
        ref = jax.device_put(src)  # the canonicalizing per-leaf reference
        got, ref = np.asarray(got), np.asarray(ref)
        assert got.dtype == ref.dtype
        assert got.shape == ref.shape
        np.testing.assert_array_equal(got, ref)


@settings(max_examples=40, deadline=None)
@given(
    n=hst.integers(min_value=0, max_value=19),
    dtype_idx=hst.integers(min_value=0, max_value=len(_DTYPES) - 1),
)
def test_single_leaf_roundtrip(n, dtype_idx):
    """Every (length, dtype) cell — including zero-length and odd lengths
    that leave unaligned tails inside the 64B-padded staging buffer."""
    rng = np.random.default_rng(n * 31 + dtype_idx)
    _roundtrip_equals_device_put({"x": _make_leaf(rng, n, _DTYPES[dtype_idx])})


@settings(max_examples=30, deadline=None)
@given(
    depth=hst.integers(min_value=1, max_value=4),
    seed=hst.integers(min_value=0, max_value=3),
)
def test_deep_mixed_pytree_roundtrip(depth, seed):
    """Nested dict/tuple/list pytrees with one leaf of every dtype per
    level, lengths varying per level (incl. an empty leaf)."""
    rng = np.random.default_rng(seed)
    tree = {"empty": _make_leaf(rng, 0, "float32")}
    node = tree
    for lvl in range(depth):
        leaves = tuple(
            _make_leaf(rng, 2 * lvl + i + 1, dt) for i, dt in enumerate(_DTYPES)
        )
        node["child"] = {"leaves": leaves, "l": [leaves[0], leaves[-1]]}
        node = node["child"]
    _roundtrip_equals_device_put(tree)


@settings(max_examples=20, deadline=None)
@given(
    n=hst.integers(min_value=1, max_value=9),
    dtype_idx=hst.integers(min_value=0, max_value=len(_DTYPES) - 1),
)
def test_mixed_device_host_passthrough(n, dtype_idx):
    """Device-resident leaves interleaved with host leaves: the device
    leaves pass by reference, the host leaves round-trip bitwise."""
    rng = np.random.default_rng(n * 7 + dtype_idx)
    dev = jnp.arange(float(n))
    group = {
        "host": _make_leaf(rng, n, _DTYPES[dtype_idx]),
        "dev": dev,
        "host2": _make_leaf(rng, 2 * n + 1, "float32"),
    }
    leaves = jax.tree.leaves(group)
    layout = GroupLayout(group)
    staging = layout.new_staging()
    layout.pack_into(leaves, staging)
    out = layout.unpack(jax.device_put(staging), leaves)
    assert out["dev"] is dev
    np.testing.assert_array_equal(
        np.asarray(out["host"]), np.asarray(jax.device_put(group["host"]))
    )
    np.testing.assert_array_equal(np.asarray(out["host2"]), group["host2"])


@settings(max_examples=12, deadline=None)
@given(
    n=hst.integers(min_value=0, max_value=11),
    dtype_idx=hst.integers(min_value=0, max_value=len(_DTYPES) - 1),
)
def test_disk_tier_roundtrip_through_engine(n, dtype_idx):
    """Full engine path for spill-store (DiskHost) groups: disk -> host
    staging -> pack -> device must equal device_put of the original."""
    import tempfile

    rng = np.random.default_rng(n * 13 + dtype_idx)
    group = {
        "a": _make_leaf(rng, n, _DTYPES[dtype_idx]),
        "b": _make_leaf(rng, n + 3, "float32"),
    }
    with tempfile.TemporaryDirectory() as d:
        store = SpillStore(d)
        store.put("g", group)
        disk_group = store.get("g")
        with TransferEngine() as eng:
            fut = eng.submit_group(0, disk_group)
            fut.wait()
            staged = fut.group()
        for got, src in zip(jax.tree.leaves(staged), jax.tree.leaves(group)):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(jax.device_put(src))
            )


# ---------------------------------------------------------------------------
# sharded axis: ShardedGroupLayout property sweep on a forced 2-device mesh
# ---------------------------------------------------------------------------

_SHARDED_PROP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import itertools
import tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.engine import TransferEngine
from repro.core.spillstore import SpillStore
assert len(jax.devices()) == 2, jax.devices()
mesh = jax.make_mesh((1, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
SH = NamedSharding(mesh, P(None, "model"))
SH0 = NamedSharding(mesh, P("model"))
REP = NamedSharding(mesh, P())

DTYPES = ["bfloat16", "float32", "int32", "float64"]


def make_leaf(rng, shape, dtype_name):
    a = rng.standard_normal(shape) if shape else rng.standard_normal()
    a = np.asarray(a)
    if dtype_name == "bfloat16":
        return np.asarray(jnp.asarray(a, jnp.bfloat16))
    if dtype_name == "int32":
        return (a * 100).astype(np.int32)
    return a.astype(dtype_name)


def check(group, shardings, expect_devices=2):
    '''engine submit -> staged group must equal eager sharded placement
    bitwise, at exactly one request per (addressable device, group).'''
    eng = TransferEngine()
    try:
        fut = eng.submit_group(0, group, device_shardings=shardings)
        fut.wait()
        staged = fut.group()
        flat_g = jax.tree.leaves(group)
        flat_s = jax.tree.leaves(staged)
        flat_sh, _ = jax.tree.flatten(shardings, is_leaf=lambda s: s is None)
        any_host = any(not isinstance(x, jax.Array) for x in flat_g)
        # exact per-device request accounting: one coalesced request per
        # addressable device (zero when everything already device-resident)
        assert fut.n_requests == (expect_devices if any_host else 0), (
            fut.n_requests, expect_devices)
        for src, got, sh in zip(flat_g, flat_s, flat_sh):
            ref = jax.device_put(src, sh) if sh is not None else jax.device_put(src)
            assert got.dtype == ref.dtype, (got.dtype, ref.dtype)
            assert got.shape == ref.shape
            if sh is not None and not isinstance(src, jax.Array):
                assert got.sharding == ref.sharding, (got.sharding, ref.sharding)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    finally:
        eng.close()


rng = np.random.default_rng(0)

# cell 1: every dtype x shard-unfriendly shapes (odd shard byte-lengths that
# leave unaligned tails in the per-device staging buffer), plus replicated
# odd/zero-length/scalar leaves riding in the same group
for dt in DTYPES:
    for m in (1, 3, 5):  # sharded dim 2*m over 2 devices -> odd shards
        group = {
            "w": make_leaf(rng, (3, 2 * m), dt),      # (3, m) per device
            "v": make_leaf(rng, (2 * m,), dt),        # (m,) per device
            "rep_odd": make_leaf(rng, (7,), "float32"),
            "zero": make_leaf(rng, (0,), dt),
            "scalar": make_leaf(rng, (), dt),
        }
        shardings = {
            "w": SH, "v": SH0, "rep_odd": REP, "zero": REP, "scalar": REP,
        }
        check(group, shardings)

# cell 2: deep pytrees with mixed placement markers (None = default device)
for depth in (1, 2, 3):
    tree, shs = {}, {}
    node, shnode = tree, shs
    for lvl in range(depth):
        leaves = tuple(
            make_leaf(rng, (2, 2 * (lvl + 1) + 2), dt)
            for dt in DTYPES
        )
        node["child"] = {"leaves": leaves, "l": [leaves[0]]}
        shnode["child"] = {
            "leaves": tuple(SH if i % 2 == 0 else REP for i in range(len(DTYPES))),
            "l": [None],
        }
        node, shnode = node["child"], shnode["child"]
    tree["top"] = make_leaf(rng, (4,), "float32")
    shs["top"] = SH0
    check(tree, shs)

# cell 3: device-resident leaves pass through by reference in a sharded group
dev = jax.device_put(make_leaf(rng, (2, 4), "float32"), SH)
group = {"host": make_leaf(rng, (2, 4), "float32"), "dev": dev}
check(group, {"host": SH, "dev": SH})

# cell 4: all-device group costs zero requests
check({"a": dev}, {"a": SH})

# cell 5: disk-tier (spill store) leaves ride the same sharded path
with tempfile.TemporaryDirectory() as d:
    store = SpillStore(d)
    for dt in ("bfloat16", "float64"):
        src = {"w": make_leaf(rng, (2, 6), dt), "b": make_leaf(rng, (7,), "float32")}
        store.put(f"g-{dt}", src)
        disk = store.get(f"g-{dt}")
        eng = TransferEngine()
        try:
            fut = eng.submit_group(0, disk, device_shardings={"w": SH, "b": REP})
            fut.wait()
            staged = fut.group()
            assert fut.n_requests == 2 and fut.disk_requests == 1, (
                fut.n_requests, fut.disk_requests)
            for k, sh in (("w", SH), ("b", REP)):
                ref = jax.device_put(src[k], sh)
                np.testing.assert_array_equal(
                    np.asarray(staged[k]), np.asarray(ref))
        finally:
            eng.close()

print("SHARDED_PROP_OK")
"""


@pytest.mark.slow
def test_sharded_layout_property_sweep_2way_mesh():
    """ShardedGroupLayout over the property space: odd/unaligned shard
    lengths, bf16/f64 canonicalization, zero-length and scalar leaves, deep
    pytrees, device pass-through, and disk-tier sources — bitwise vs eager
    sharded placement with exact per-device request accounting."""
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_PROP_SCRIPT],
        capture_output=True,
        text=True,
        timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": "/root"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED_PROP_OK" in proc.stdout
