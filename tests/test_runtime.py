"""Runtime-layer tests: checkpointing, fault tolerance, elastic, stragglers,
optimizer, data determinism, gradient compression."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType
from proptest import given, settings, strategies as hst

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_smoke_config
from repro.data.loader import PrefetchLoader
from repro.data.synthetic import SyntheticConfig, synthetic_batch
from repro.optim import grad_compress as gc
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update, global_norm
from repro.optim.schedule import cosine_schedule
from repro.runtime.driver import DriverConfig, TrainDriver
from repro.runtime.elastic import elastic_mesh_shape
from repro.runtime.straggler import StragglerMonitor


# ---------------------------------------------------------------------------
# checkpoint manager
# ---------------------------------------------------------------------------

def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "a": jax.random.normal(k, (4, 8)),
        "nested": {"b": jnp.arange(6, dtype=jnp.int32), "c": jnp.ones(())},
    }


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    t = _tree()
    mgr.save(3, t, blocking=True)
    step, restored = mgr.restore(jax.eval_shape(lambda: _tree()))
    assert step == 3
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_roundtrip_bf16(tmp_path):
    """Extension dtypes serialize as raw void in npy; restore must re-view
    them through meta.json (bf16 params resume — found by verification)."""
    mgr = CheckpointManager(tmp_path, keep=2)
    t = {"w": jnp.linspace(-2, 2, 16).astype(jnp.bfloat16)}
    mgr.save(1, t, blocking=True)
    _, restored = mgr.restore(jax.eval_shape(lambda: t))
    assert restored["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(restored["w"], np.float32), np.asarray(t["w"], np.float32)
    )
    jnp.asarray(restored["w"])  # must be a valid JAX input


def test_checkpoint_keep_k_prunes(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s), blocking=True)
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_atomic_no_tmp_left(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3)
    mgr.save(1, _tree(), blocking=True)
    assert not list(tmp_path.glob("*.tmp"))


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3)
    mgr.save(7, _tree())
    mgr.wait()
    assert mgr.latest_step() == 7


def test_checkpoint_overwrite_crash_window_keeps_old_copy(tmp_path, monkeypatch):
    """A crash at the commit rename while overwriting a step must not lose
    the previous copy (the seed rmtree'd it *before* the rename)."""
    import pathlib

    mgr = CheckpointManager(tmp_path, keep=3)
    mgr.save(1, _tree(0), blocking=True)

    real_rename = pathlib.Path.rename

    def boom(self, target):
        if self.name.endswith(".tmp"):
            raise OSError("injected crash at commit")
        return real_rename(self, target)

    monkeypatch.setattr(pathlib.Path, "rename", boom)
    with pytest.raises(OSError, match="injected"):
        mgr.save(1, _tree(1), blocking=True)
    monkeypatch.undo()

    step, restored = mgr.restore(jax.eval_shape(lambda: _tree()))
    assert step == 1
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(_tree(0))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not list(tmp_path.glob("*.old"))  # rolled back, nothing dangling


def test_checkpoint_init_sweeps_stale_tmp_and_recovers_old(tmp_path):
    """Leftovers of a crashed save: partial .tmp dirs are deleted on init;
    a .old whose commit never landed is restored as the step."""
    mgr = CheckpointManager(tmp_path, keep=3)
    mgr.save(5, _tree(5), blocking=True)
    (tmp_path / "step_00000007.tmp").mkdir()
    old = tmp_path / "step_00000003.old"
    old.mkdir()
    mgr2 = CheckpointManager(tmp_path, keep=3)
    assert not (tmp_path / "step_00000007.tmp").exists()
    assert (tmp_path / "step_00000003").exists()  # crash-window recovery
    assert mgr2.all_steps() == [3, 5]


def test_checkpoint_keep_zero_keeps_all_negative_rejected(tmp_path):
    with pytest.raises(ValueError):
        CheckpointManager(tmp_path / "bad", keep=-1)
    mgr = CheckpointManager(tmp_path, keep=0)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s), blocking=True)
    assert mgr.all_steps() == [1, 2, 3, 4]  # keep=0: keep all, documented


def test_checkpoint_elastic_reshard(tmp_path):
    """Restore places leaves onto a *different* sharding (elastic resume)."""
    mgr = CheckpointManager(tmp_path)
    t = _tree()
    mgr.save(1, t, blocking=True)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    sh = jax.tree.map(
        lambda _: jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()), t
    )
    _, restored = mgr.restore(jax.eval_shape(lambda: _tree()), shardings=sh)
    assert all(x.sharding.mesh.shape == {"data": 1} for x in jax.tree.leaves(restored))


# ---------------------------------------------------------------------------
# fault-tolerant driver
# ---------------------------------------------------------------------------

def _driver(tmp_path, fail_at=None, steps=12, every=4):
    cfg = get_smoke_config("smollm-360m")
    from repro.train import steps as st
    from repro.models import transformer

    opt_cfg = AdamWConfig(peak_lr=1e-3, warmup_steps=1, total_steps=steps)
    step_raw = st.make_train_step(cfg, opt_cfg)
    jitted = jax.jit(step_raw)

    def step_fn(state, batch):
        p, o, m = jitted(state["params"], state["opt"], batch)
        return {"params": p, "opt": o}, m

    sc = SyntheticConfig(cfg.vocab_size, 16, 2, seed=1)

    def make_batch(i):
        return synthetic_batch(cfg, sc, i)

    def init_state():
        p, o = st.init_train_state(jax.random.PRNGKey(0), cfg)
        return {"params": p, "opt": o}

    dcfg = DriverConfig(
        total_steps=steps,
        checkpoint_every=every,
        checkpoint_dir=str(tmp_path),
        log_every=0,
        max_restarts=3,
    )
    return TrainDriver(dcfg, step_fn, make_batch, init_state, fail_at=fail_at)


@pytest.mark.slow
def test_driver_trains_and_checkpoints(tmp_path):
    d = _driver(tmp_path / "a")
    state = d.run()
    assert d.ckpt.latest_step() is not None
    losses = [h["loss"] for h in d.history]
    assert all(np.isfinite(losses))


@pytest.mark.slow
def test_driver_restart_replays_identical_losses(tmp_path):
    """Node-failure recovery: inject a failure; the restarted run must
    produce bit-identical loss at each step vs an unfailed run."""
    d_ok = _driver(tmp_path / "ok")
    d_ok.run()
    ok_losses = {h["step"]: h["loss"] for h in d_ok.history}

    d_fail = _driver(tmp_path / "fail", fail_at={6})
    d_fail.run()
    assert d_fail.restarts == 1
    fail_losses = {}
    for h in d_fail.history:  # later entries overwrite replayed steps
        fail_losses[h["step"]] = h["loss"]
    for s in ok_losses:
        assert abs(ok_losses[s] - fail_losses[s]) < 1e-5, (s, ok_losses[s], fail_losses[s])


def test_driver_gives_up_after_max_restarts(tmp_path):
    d = _driver(tmp_path / "x", fail_at={2, 3, 4, 5, 6})
    d.cfg = DriverConfig(
        total_steps=8, checkpoint_every=100, checkpoint_dir=str(tmp_path / "x"),
        log_every=0, max_restarts=2,
    )
    with pytest.raises(RuntimeError):
        d.run()


# ---------------------------------------------------------------------------
# elastic / straggler
# ---------------------------------------------------------------------------

def test_elastic_mesh_shapes():
    assert elastic_mesh_shape(512, model=16) == ((2, 16, 16), ("pod", "data", "model"))
    assert elastic_mesh_shape(256, model=16) == ((16, 16), ("data", "model"))
    shape, axes = elastic_mesh_shape(480, model=16)  # lost 2 hosts
    assert np.prod(shape) == 480 and axes[-1] == "model"
    with pytest.raises(ValueError):
        elastic_mesh_shape(100, model=16)


def test_straggler_monitor_flags_outlier():
    m = StragglerMonitor(window=32, z_threshold=6.0)
    import time as _t

    for i in range(12):
        m.start_step(i)
        m.end_step()
    # fake a straggling step by injecting window values
    m.window.clear()
    m.window.extend([0.010] * 20)
    m.start_step(99)
    _t.sleep(0.08)
    ev = m.end_step()
    assert ev is not None and ev.step == 99


def test_straggler_deadline():
    m = StragglerMonitor(deadline_s=0.01)
    import time as _t

    m.start_step(0)
    _t.sleep(0.02)
    assert m.check_deadline()


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_reduces_quadratic_loss():
    w = {"w": jnp.array([3.0, -2.0])}
    opt = adamw_init(w)
    cfg = AdamWConfig(peak_lr=0.1, warmup_steps=1, total_steps=100, weight_decay=0.0)
    params = w
    for _ in range(60):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, opt, _ = adamw_update(cfg, g, opt, compute_dtype=jnp.float32)
    assert float(jnp.sum(params["w"] ** 2)) < 0.2


def test_adamw_grad_clip():
    w = {"w": jnp.ones(4) * 100}
    opt = adamw_init(w)
    cfg = AdamWConfig(peak_lr=1e-2, warmup_steps=1, total_steps=10, grad_clip=1.0)
    g = {"w": jnp.ones(4) * 1e6}
    _, _, m = adamw_update(cfg, g, opt)
    assert float(m["grad_norm"]) > 1.0  # raw norm reported


def test_cosine_schedule_shape():
    lr0 = cosine_schedule(jnp.asarray(0), peak_lr=1.0, warmup_steps=10, total_steps=100)
    lr_peak = cosine_schedule(jnp.asarray(10), peak_lr=1.0, warmup_steps=10, total_steps=100)
    lr_end = cosine_schedule(jnp.asarray(100), peak_lr=1.0, warmup_steps=10, total_steps=100)
    assert float(lr0) == 0.0
    assert abs(float(lr_peak) - 1.0) < 1e-6
    assert abs(float(lr_end) - 0.1) < 1e-6


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_synthetic_batches_deterministic():
    cfg = get_smoke_config("olmo-1b")
    sc = SyntheticConfig(cfg.vocab_size, 32, 4, seed=3)
    a = synthetic_batch(cfg, sc, 17)
    b = synthetic_batch(cfg, sc, 17)
    np.testing.assert_array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))
    c = synthetic_batch(cfg, sc, 18)
    assert not np.array_equal(np.asarray(a["tokens"]), np.asarray(c["tokens"]))


def test_prefetch_loader_order_and_restart():
    cfg = get_smoke_config("olmo-1b")
    sc = SyntheticConfig(cfg.vocab_size, 16, 2, seed=0)
    loader = PrefetchLoader(lambda s: synthetic_batch(cfg, sc, s), distance=2)
    b3 = loader(3)
    b4 = loader(4)
    # restart back at step 3: identical batch
    loader2 = PrefetchLoader(lambda s: synthetic_batch(cfg, sc, s), distance=2)
    b3r = loader2(3)
    np.testing.assert_array_equal(np.asarray(b3["tokens"]), np.asarray(b3r["tokens"]))


# ---------------------------------------------------------------------------
# gradient compression (property: error feedback closes the loop)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(hst.integers(0, 2 ** 31 - 1))
def test_int8_error_feedback_identity(seed):
    g = {"w": jax.random.normal(jax.random.PRNGKey(seed), (16,)) * 10.0}
    q, s, err = gc.compress_int8(g)
    back = gc.decompress_int8(q, s)
    np.testing.assert_allclose(
        np.asarray(back["w"] + err["w"]), np.asarray(g["w"]), rtol=1e-5, atol=1e-5
    )


def test_bf16_compress_roundtrip_close():
    g = {"w": jnp.linspace(-2, 2, 64)}
    back = gc.decompress_bf16(gc.compress_bf16(g))
    np.testing.assert_allclose(np.asarray(back["w"]), np.asarray(g["w"]), atol=2e-2)


def test_int8_requantize_identity_and_no_clip():
    """q*s == q'*t + extra_error exactly (f32), for t = the cross-pod max
    scale; nothing clips because |q*s| <= 127 s <= 127 t."""
    g = {"w": jax.random.normal(jax.random.PRNGKey(3), (64,)) * 1e-3}
    q, s, _ = gc.compress_int8(g)
    t = jax.tree.map(lambda x: x * 1000.0, s)  # a much-larger shared scale
    q2, extra = gc.requantize_int8(q, s, t)
    lhs = np.asarray(q["w"], np.float32) * float(s["w"])
    rhs = np.asarray(q2["w"], np.float32) * float(t["w"]) + np.asarray(extra["w"])
    np.testing.assert_allclose(lhs, rhs, rtol=1e-6, atol=1e-12)
    assert np.abs(np.asarray(q2["w"], np.int32)).max() <= 127


_POD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
from functools import partial
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax.experimental.shard_map import shard_map
from repro.optim import grad_compress as gc

mesh = jax.make_mesh((2,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
# two pods with VERY different gradient magnitudes: the seed bug psummed
# raw int8 quantized under per-pod scales, inflating pod 0's contribution
# by pmax/scale0 ~ 1e4
g0 = np.linspace(-1e-3, 1e-3, 32, dtype=np.float32)
g1 = np.linspace(-10.0, 10.0, 32, dtype=np.float32)
stacked = jnp.stack([g0, g1])
err0 = jnp.zeros((2, 32), jnp.float32)

@partial(shard_map, mesh=mesh, in_specs=(P("pod"), P("pod")),
         out_specs=(P("pod"), P("pod")))
def reduce_fn(g, e):
    out, err = gc.pod_allreduce_int8({"w": g[0]}, "pod", {"w": e[0]})
    return out["w"][None], err["w"][None]

out, err = jax.jit(reduce_fn)(stacked, err0)
out, err = np.asarray(out), np.asarray(err)
true_mean = (g0 + g1) / 2
pmax = max(np.abs(g0).max(), np.abs(g1).max()) / 127.0
assert np.allclose(out[0], out[1]), "allreduce must agree across pods"
# shared-scale quantization error is O(pmax) per contribution; the seed
# bug's inflation error was ~ |g0| * pmax/s0 / 2 ~ 5.0 >> pmax
worst = np.abs(out[0] - true_mean).max()
assert worst <= pmax + 1e-6, (worst, pmax)
# error feedback closes the loop exactly: contribution(=g-err) sums to out
c0, c1 = g0 - err[0], g1 - err[1]
np.testing.assert_allclose((c0 + c1) / 2, out[0], rtol=1e-5, atol=1e-7)
print("POD_ALLREDUCE_OK")
"""


@pytest.mark.slow
def test_pod_allreduce_int8_shared_scale_2pods():
    """shard_map pin for the cross-pod scale bug: pods with gradients of
    very different magnitude must agree on one scale before the psum."""
    proc = subprocess.run(
        [sys.executable, "-c", _POD_SCRIPT],
        capture_output=True,
        text=True,
        timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": "/root"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "POD_ALLREDUCE_OK" in proc.stdout
