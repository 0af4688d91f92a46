"""Self-healing streamed runtime (ISSUE 6): elastic re-mesh recovery,
transient-fault retry in the transfer engine, and spill-store integrity.

Pins the fault model end to end:
  * transient H2D / disk-stage faults retry with backoff and complete
    **bitwise-equal** (retry counters == injected fault count); permanent
    faults surface on the waiter after exactly ``max_attempts``,
  * spill chunks carry per-leaf CRC32s — a flipped byte is detected on
    fetch, recovered once from the durable home, or surfaced with the
    chunk key/offset (never silently consumed),
  * ``close()`` detects wedged worker threads instead of silently
    abandoning them,
  * the driver's restart budget resets after ``checkpoint_every``
    consecutive healthy steps; straggler events widen the engine's
    prefetch window,
  * chaos: a kill at every pipeline phase (forward fetch, D2H drain,
    checkpoint commit) of a disk-homed streamed train recovers to a
    bitwise-equal loss series,
  * elastic: a 2-device streamed run resumed on 1 device (and 1 on 2)
    re-partitions the grouped checkpoint by streaming and continues with
    a loss series bitwise-equal to an unresharded resume.
"""
import dataclasses
import json
import shutil
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_smoke_config
from repro.core.engine import EngineConfig, TransferEngine
from repro.core.hoststream import HostStreamExecutor, StreamStats
from repro.core.spillstore import SpillCorruptionError, SpillStore
from repro.core.weightstream import WeightStreamPlan
from repro.runtime import elastic as el
from repro.runtime.driver import DriverConfig, TrainDriver
from repro.runtime.straggler import StragglerMonitor
from repro.train import steps as st

TIMEOUT_S = 60.0


def run_with_timeout(fn, timeout_s: float = TIMEOUT_S):
    box: dict = {}

    def target():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised on the caller
            box["error"] = e

    t = threading.Thread(target=target, daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        pytest.fail(f"timed out after {timeout_s}s (possible deadlock)")
    if "error" in box:
        raise box["error"]
    return box.get("value")


def _groups(n=4, shape=(4, 4)):
    rng = np.random.default_rng(0)
    return [{"x": rng.standard_normal(shape).astype(np.float32)} for _ in range(n)]


# ---------------------------------------------------------------------------
# transient-fault retry in the transfer engine
# ---------------------------------------------------------------------------


def test_transient_h2d_fault_retries_bitwise(monkeypatch):
    """One injected H2D fault with ``max_attempts=3``: the run completes,
    values are bitwise-equal to the host source, and the retry counter
    equals the injected fault count."""
    real_put = jax.device_put
    faults = {"n": 0}

    def flaky_put(x, *a, **kw):
        if faults["n"] == 0:
            faults["n"] += 1
            raise RuntimeError("injected transient H2D fault")
        return real_put(x, *a, **kw)

    groups = _groups(4)
    st_ = StreamStats()

    def body():
        cfg = EngineConfig(max_attempts=3, retry_backoff_s=1e-4)
        with HostStreamExecutor(
            lambda c, g: (c, g["x"] * 2.0), writeback=True, engine_config=cfg
        ) as ex:
            monkeypatch.setattr(jax, "device_put", flaky_put)
            _, outs = ex.run(jnp.zeros(()), groups, mode="prefetch", stats=st_)
            for i, o in enumerate(outs):
                np.testing.assert_array_equal(np.asarray(o), groups[i]["x"] * 2.0)

    run_with_timeout(body)
    assert faults["n"] == 1
    assert st_.retries == 1
    assert st_.give_ups == 0


def test_permanent_fault_surfaces_after_max_attempts(monkeypatch):
    """A fault that never clears surfaces on the waiter after exactly
    ``max_attempts`` tries and counts as a give-up."""
    real_put = jax.device_put
    calls = {"n": 0}

    def dead_put(x, *a, **kw):
        calls["n"] += 1
        raise RuntimeError("injected permanent H2D fault")

    st_ = StreamStats()

    def body():
        cfg = EngineConfig(max_attempts=3, retry_backoff_s=1e-4)
        with HostStreamExecutor(lambda c, g: c, engine_config=cfg) as ex:
            monkeypatch.setattr(jax, "device_put", dead_put)
            with pytest.raises(RuntimeError, match="permanent H2D fault"):
                ex.run(jnp.zeros(()), _groups(2), mode="on_demand", stats=st_)

    run_with_timeout(body)
    assert calls["n"] == 3  # exactly max_attempts tries
    assert st_.give_ups == 1
    assert st_.retries == 2  # attempts - 1 transparent retries before giving up


def test_transient_disk_stage_fault_retries_bitwise(tmp_path, monkeypatch):
    """One injected disk-staging fault: the group re-fetches from the
    intact cold home and the stream completes bitwise-equal."""
    store = SpillStore(tmp_path / "spill")
    host = _groups(4)
    disk = []
    for i, g in enumerate(host):
        store.put(f"g{i}", g)
        disk.append(store.get(f"g{i}"))

    real = TransferEngine._acquire_disk_staging
    faults = {"n": 0}

    def flaky_acquire(self, dsig, layout):
        if faults["n"] == 0:
            faults["n"] += 1
            raise RuntimeError("injected disk staging fault")
        return real(self, dsig, layout)

    st_ = StreamStats()

    def body():
        monkeypatch.setattr(TransferEngine, "_acquire_disk_staging", flaky_acquire)
        cfg = EngineConfig(max_attempts=3, retry_backoff_s=1e-4)
        with HostStreamExecutor(
            lambda c, g: (c, g["x"] + 1.0), writeback=True, engine_config=cfg
        ) as ex:
            _, outs = ex.run(jnp.zeros(()), disk, mode="prefetch", stats=st_)
            for i, o in enumerate(outs):
                np.testing.assert_array_equal(np.asarray(o), host[i]["x"] + 1.0)

    run_with_timeout(body)
    store.close()
    assert faults["n"] == 1
    assert st_.retries == 1
    assert st_.give_ups == 0


def test_legacy_fail_fast_default():
    """``max_attempts`` defaults to 1: a single fault surfaces immediately
    (the pre-retry contract every existing fault test pins)."""
    assert EngineConfig().max_attempts == 1


# ---------------------------------------------------------------------------
# spill-store integrity (CRC32)
# ---------------------------------------------------------------------------


def _corrupt_chunk(store, key, byte=10):
    entry = store._entry(key)
    path = store.dir / entry["file"]
    raw = bytearray(path.read_bytes())
    raw[byte] ^= 0xFF
    path.write_bytes(bytes(raw))


def test_crc_detects_flipped_byte(tmp_path):
    store = SpillStore(tmp_path / "spill")
    g = _groups(1)[0]
    store.put("k", g)
    store.verify_chunk("k")  # intact: no raise
    _corrupt_chunk(store, "k")
    with pytest.raises(SpillCorruptionError) as ei:
        store.verify_chunk("k")
    err = ei.value
    assert err.key == "k"
    assert err.offset is not None and err.nbytes > 0
    assert "crc32" in str(err) and "k" in str(err)
    assert store.crc_failures >= 1
    store.close()


def test_crc_fetch_recovers_from_durable_home(tmp_path):
    """A corrupt chunk consumed through the engine is re-fetched once via
    the recovery callback (the durable home) and the values are bitwise
    the originals — never the corrupted bytes."""
    store = SpillStore(tmp_path / "spill")
    host = _groups(2)
    for i, g in enumerate(host):
        store.put(f"g{i}", g)
    disk = [store.get(f"g{i}") for i in range(2)]
    store.set_recovery(lambda key: host[int(key[1:])])
    _corrupt_chunk(store, "g1")

    def body():
        with HostStreamExecutor(
            lambda c, g: (c, g["x"] * 3.0), writeback=True
        ) as ex:
            _, outs = ex.run(jnp.zeros(()), disk, mode="prefetch")
            for i, o in enumerate(outs):
                np.testing.assert_array_equal(np.asarray(o), host[i]["x"] * 3.0)

    run_with_timeout(body)
    assert store.crc_failures >= 1
    assert store.recoveries == 1
    store.verify_chunk("g1")  # the rewritten chunk is intact
    store.close()


def test_crc_without_recovery_surfaces_rich_error(tmp_path):
    """No durable home to recover from: the corruption surfaces on the
    engine waiter as a SpillCorruptionError naming the chunk — the stream
    never silently consumes corrupt bytes."""
    store = SpillStore(tmp_path / "spill")
    g = _groups(1)[0]
    store.put("k", g)
    disk = store.get("k")
    _corrupt_chunk(store, "k")

    def body():
        cfg = EngineConfig(max_attempts=3, retry_backoff_s=1e-4)
        with HostStreamExecutor(lambda c, g: c, engine_config=cfg) as ex:
            with pytest.raises(SpillCorruptionError, match="'k'"):
                ex.run(jnp.zeros(()), [disk], mode="on_demand")

    run_with_timeout(body)
    store.close()


# ---------------------------------------------------------------------------
# close() leak detection
# ---------------------------------------------------------------------------


def test_close_detects_wedged_worker(monkeypatch):
    """A worker stuck in a transfer past ``close_timeout_s`` is reported
    via ``leaked_threads`` (loud failure), and a later successful close
    clears the flag."""
    gate = threading.Event()
    real_put = jax.device_put

    def stuck_put(x, *a, **kw):
        gate.wait(TIMEOUT_S)
        return real_put(x, *a, **kw)

    def body():
        eng = TransferEngine(EngineConfig(close_timeout_s=0.2))
        monkeypatch.setattr(jax, "device_put", stuck_put)
        fut = eng.submit_group(0, _groups(1)[0])
        eng.close()
        assert eng.leaked_threads is True
        gate.set()  # un-wedge; the worker finishes its drain
        fut.wait()
        eng.close()
        assert eng.leaked_threads is False

    run_with_timeout(body)


# ---------------------------------------------------------------------------
# driver: restart-budget decay + straggler -> widen
# ---------------------------------------------------------------------------


def _cheap_driver(tmp_path, *, steps=12, every=2, max_restarts=1, fail_at=None,
                  engine=None, always_fail_from=None):
    def step_fn(state, batch):
        if always_fail_from is not None and batch >= always_fail_from:
            raise RuntimeError(f"persistent fault at step {batch}")
        x = state["x"] + 1.0
        return {"x": x}, {"loss": float(np.sum(x))}

    dcfg = DriverConfig(
        total_steps=steps, checkpoint_every=every, checkpoint_dir=str(tmp_path),
        log_every=0, max_restarts=max_restarts,
    )
    return TrainDriver(
        dcfg, step_fn, lambda i: i, lambda: {"x": np.zeros(4, np.float32)},
        fail_at=fail_at, engine=engine,
    )


def test_restart_budget_resets_after_healthy_steps(tmp_path):
    """Two isolated faults separated by >= checkpoint_every healthy steps
    survive a budget of 1; ``restarts`` stays cumulative for observability."""
    d = _cheap_driver(tmp_path / "a", max_restarts=1, fail_at={4, 9})
    d.run()
    assert d.restarts == 2  # cumulative, never decays
    steps = [h["step"] for h in d.history]
    assert steps[-1] == 11  # ran to completion


def test_restart_budget_still_trips_on_crash_loop(tmp_path):
    """A *persistent* fault (every attempt dies at the same step, never a
    healthy checkpoint-interval between) still exhausts the budget — the
    decay must not mask genuine crash loops."""
    d = _cheap_driver(tmp_path / "b", max_restarts=1, always_fail_from=4)
    with pytest.raises(RuntimeError, match="persistent fault"):
        run_with_timeout(d.run)


def test_straggler_event_widens_engine_prefetch():
    """The driver wires StragglerMonitor events into the engine: a flagged
    step boosts every registered AdaptiveDistance and the disk window."""
    eng = TransferEngine(EngineConfig(disk_slots=1, disk_max_slots=4))
    try:
        from repro.core.engine import AdaptiveDistance

        ctrl = AdaptiveDistance(initial=1, max_distance=8)
        eng.register_controller(ctrl)
        d = _cheap_driver("/tmp/unused-straggler", engine=eng)
        mon = d.monitor
        for _ in range(10):  # warm the window with fast steps
            mon.start_step(0)
            mon.end_step()
        before = ctrl.distance
        mon.start_step(1)
        time.sleep(0.15)  # >> z_threshold robust z-scores above the median
        ev = mon.end_step()
        assert ev is not None
        assert ctrl.distance > before
    finally:
        eng.close()


def test_straggler_monitor_on_event_callback():
    seen = []
    mon = StragglerMonitor(window=16, z_threshold=6.0, on_event=seen.append)
    for _ in range(10):
        mon.start_step(0)
        mon.end_step()
    mon.start_step(1)
    time.sleep(0.15)
    mon.end_step()
    assert len(seen) == 1 and seen[0].step == 1


# ---------------------------------------------------------------------------
# elastic: unit coverage
# ---------------------------------------------------------------------------


def test_parse_group_key():
    assert el.parse_group_key("g000_embed")["kind"] == "embed"
    assert el.parse_group_key("g004_head")["kind"] == "head"
    g = el.parse_group_key("g002_layers_002_004")
    assert (g["kind"], g["lo"], g["hi"]) == ("layers", 2, 4)
    assert el.parse_group_key("step") is None
    assert el.parse_group_key("leaves") is None


def test_check_restart_mesh_raises_on_device_count_change():
    fp = el.mesh_fingerprint(el.elastic_local_mesh(model=1))
    el.check_restart_mesh(fp)  # same count: no raise
    with pytest.raises(el.RemeshRequired, match="relaunch"):
        el.check_restart_mesh(
            {"n_devices": fp["n_devices"] + 1, "shape": [fp["n_devices"] + 1],
             "axes": ["data"]}
        )


def test_elastic_local_mesh_degrades_model_axis():
    n = len(jax.devices())
    mesh = el.elastic_local_mesh(model=n + 1)  # cannot fit: degrades
    assert mesh.devices.size == n
    assert mesh.axis_names[-1] == "model"


def test_prune_stale_spill(tmp_path):
    cfg = dataclasses.replace(get_smoke_config("smollm-360m"), n_layers=4)
    plan = WeightStreamPlan(cfg, st.abstract_params(cfg), layers_per_group=2)
    store = SpillStore(tmp_path / "spill")
    g = _groups(1)[0]
    for key in ("wp/g001_layers_000_001", "wopt/g001_layers_000_001",  # stale
                plan.spill_key(plan.groups[0]), "other/unrelated"):
        store.put(key, g)
    removed = el.prune_stale_spill(store, plan)
    assert removed == 2
    keys = set(store.keys())
    assert plan.spill_key(plan.groups[0]) in keys
    assert "other/unrelated" in keys  # non-weight chunks untouched
    store.close()


@pytest.mark.slow
def test_reshard_grouped_checkpoint_bitwise(tmp_path):
    """Stream-repartitioning a grouped checkpoint (lpg=1 -> lpg=3, an
    uneven split needing both slicing and concatenation) preserves every
    assembled param and moment bitwise."""
    cfg = dataclasses.replace(get_smoke_config("smollm-360m"), n_layers=4)
    abs_p = st.abstract_params(cfg)
    plan_a = WeightStreamPlan(cfg, abs_p, layers_per_group=1)
    plan_b = WeightStreamPlan(cfg, abs_p, layers_per_group=3)
    key = jax.random.PRNGKey(0)
    state = st.init_weight_streamed_state(key, cfg, plan_a)

    ck = CheckpointManager(tmp_path, keep=2)
    ck.save(7, state)
    ck.wait()
    assert not el.reshard_grouped_checkpoint(CheckpointManager(tmp_path, keep=0), plan_a)
    assert el.reshard_grouped_checkpoint(CheckpointManager(tmp_path, keep=0), plan_b)

    tmpl = jax.eval_shape(lambda: st.init_weight_streamed_state(key, cfg, plan_b))
    step, restored = CheckpointManager(tmp_path, keep=2).restore(tmpl)
    assert step == 7

    ref = st.init_weight_streamed_state(key, cfg, plan_a)
    pa = plan_a.assemble(ref["params"])
    pb = plan_b.assemble(restored["params"])
    for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert int(restored["opt"]["step"]) == int(ref["opt"]["step"])

    def field_home(groups, field):
        return {"groups": {
            k: jax.tree.map(
                lambda t: t[field], v,
                is_leaf=lambda t: isinstance(t, dict) and field in t,
            )
            for k, v in groups.items()
        }}

    for field in ("master", "m", "v"):
        fa = plan_a.assemble(field_home(ref["opt"]["groups"], field))
        fb = plan_b.assemble(field_home(restored["opt"]["groups"], field))
        for x, y in zip(jax.tree.leaves(fa), jax.tree.leaves(fb)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# chaos: kill a disk-homed streamed train at every pipeline phase
# ---------------------------------------------------------------------------


def _ws_driver(tmp_path, *, steps=6, every=2, fail_at=None):
    from repro.launch.train import build_trainer
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.elastic import elastic_local_mesh

    cfg = dataclasses.replace(get_smoke_config("smollm-360m"), n_layers=2)
    mesh = elastic_local_mesh(model=1)
    return build_trainer(
        cfg,
        mesh,
        global_batch=2,
        seq_len=16,
        opt_cfg=AdamWConfig(peak_lr=1e-3, warmup_steps=1, total_steps=steps),
        driver_cfg=DriverConfig(
            total_steps=steps, checkpoint_every=every,
            checkpoint_dir=str(tmp_path), log_every=0, max_restarts=3,
        ),
        fail_at=fail_at,
        param_kind="disk_host",
        param_layers_per_group=1,
        transfer_retries=1,
    )


@pytest.mark.slow
@pytest.mark.parametrize("sanitize", [False, True], ids=["plain", "sanitized"])
@pytest.mark.parametrize("phase", ["forward_fetch", "d2h_drain", "ckpt_commit"])
def test_chaos_phase_kill_recovers_bitwise(tmp_path, monkeypatch, phase,
                                           sanitize):
    """Kill a disk-homed streamed train mid-step at a specific pipeline
    phase; the restarted run's loss series must be bitwise-equal to the
    unfailed reference.

    The sanitized variant reruns the same kills under ``REPRO_SANITIZE=1``:
    a kill mid-drain leaves D2H tickets pending, and the restart path must
    discard them before re-fetching the same groups — a hazard report here
    means recovery re-fetched through an in-flight writeback."""
    if sanitize:
        monkeypatch.setenv("REPRO_SANITIZE", "1")
    ref = _ws_driver(tmp_path / "ref")
    ref.run()
    ref_losses = {h["step"]: h["loss"] for h in ref.history}

    armed = {"at": 5, "n": 0}  # 5th step_fn entry = step 4 (not a ckpt step)

    if phase == "forward_fetch":
        real = HostStreamExecutor.run

        def chaos(self, *a, **kw):
            if armed["at"] is not None:
                armed["n"] += 1
                if armed["n"] == armed["at"]:
                    armed["at"] = None
                    raise RuntimeError("injected forward-fetch kill")
            return real(self, *a, **kw)

        monkeypatch.setattr(HostStreamExecutor, "run", chaos)
    elif phase == "d2h_drain":
        real = TransferEngine.drain_writebacks

        def chaos(self, *a, **kw):
            if armed["at"] is not None:
                armed["n"] += 1
                if armed["n"] == armed["at"]:
                    armed["at"] = None
                    raise RuntimeError("injected D2H-drain kill")
            return real(self, *a, **kw)

        monkeypatch.setattr(TransferEngine, "drain_writebacks", chaos)
    else:  # ckpt_commit
        real = CheckpointManager.save

        def chaos(self, *a, **kw):
            if armed["at"] is not None:
                armed["n"] += 1
                if armed["n"] == 2:  # second periodic save (after step 3)
                    armed["at"] = None
                    raise RuntimeError("injected checkpoint-commit kill")
            return real(self, *a, **kw)

        monkeypatch.setattr(CheckpointManager, "save", chaos)

    d = _ws_driver(tmp_path / "chaos")
    d.run()
    assert armed["at"] is None, "chaos fault never fired"
    assert d.restarts == 1
    got = {}
    for h in d.history:  # later entries overwrite replayed steps
        got[h["step"]] = h["loss"]
    assert set(ref_losses) == set(got)
    for s in ref_losses:
        assert ref_losses[s] == got[s], (s, ref_losses[s], got[s])


# ---------------------------------------------------------------------------
# elastic re-mesh: forced 2<->1 device subprocess resume, bitwise
# ---------------------------------------------------------------------------

_ENV = {
    "PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
    "HOME": "/root",
    # the re-mesh resumes run fully sanitized: kill + reshard + replay must
    # produce zero transfer-hazard reports, not just bitwise losses
    "REPRO_SANITIZE": "1",
    # the CLI points JAX's persistent compile cache into the checkout;
    # tests keep it off
    "JAX_ENABLE_COMPILATION_CACHE": "false",
}


def _train_cli(ckpt_dir, *, devices, steps, lpg, model_parallel, hist=None,
               param_kind="pinned_host", extra=()):
    env = dict(_ENV)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    cmd = [
        sys.executable, "-m", "repro.launch.train", "--arch", "smollm-360m",
        "--smoke", "--steps", str(steps), "--batch", "2", "--seq", "16",
        "--checkpoint-dir", str(ckpt_dir), "--checkpoint-every", "2",
        "--model-parallel", str(model_parallel), "--param-kind", param_kind,
        "--param-layers-per-group", str(lpg), *extra,
    ]
    if hist is not None:
        cmd += ["--history-out", str(hist)]
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=600, env=env
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    return proc


def _losses(hist_path):
    return {int(h["step"]): h["loss"] for h in json.loads(hist_path.read_text())}


@pytest.mark.slow
def test_remesh_2_to_1_device_resumes_bitwise(tmp_path):
    """A 2-device disk-homed streamed run killed mid-train and resumed on
    1 device with a different grouping re-shards by streaming and replays
    a loss series bitwise-equal to an unresharded resume."""
    ckpt = tmp_path / "ckpt"
    spill = tmp_path / "spill"
    # phase 1: 2 devices, lpg=1, killed once mid-train (recovers in-process)
    _train_cli(ckpt, devices=2, steps=4, lpg=1, model_parallel=2,
               param_kind="disk_host",
               extra=("--spill-dir", str(spill), "--fail-at", "2"))
    ref_dir = tmp_path / "ckpt-ref"
    shutil.copytree(ckpt, ref_dir)

    # elastic resume: 1 device (model axis degrades), lpg=2 -> reshard
    _train_cli(ckpt, devices=1, steps=8, lpg=2, model_parallel=2,
               param_kind="disk_host", hist=tmp_path / "el.json",
               extra=("--spill-dir", str(spill)))
    # reference resume: same 1-device mesh, unchanged lpg=1 -> no reshard
    _train_cli(ref_dir, devices=1, steps=8, lpg=1, model_parallel=2,
               param_kind="disk_host", hist=tmp_path / "ref.json",
               extra=("--spill-dir", str(tmp_path / "spill-ref")))

    got, ref = _losses(tmp_path / "el.json"), _losses(tmp_path / "ref.json")
    assert got and set(got) == set(ref)
    for s in sorted(ref):
        assert got[s] == ref[s], (s, got[s], ref[s])


@pytest.mark.slow
def test_remesh_1_to_2_device_resumes_bitwise(tmp_path):
    """The mirror direction: a 1-device run resumed on 2 devices with a
    re-derived grouping."""
    ckpt = tmp_path / "ckpt"
    _train_cli(ckpt, devices=1, steps=4, lpg=2, model_parallel=1)
    ref_dir = tmp_path / "ckpt-ref"
    shutil.copytree(ckpt, ref_dir)

    # elastic resume: 2 devices, lpg=1 -> reshard
    _train_cli(ckpt, devices=2, steps=8, lpg=1, model_parallel=1,
               hist=tmp_path / "el.json")
    # reference resume: same 2-device mesh, unchanged lpg=2 -> no reshard
    _train_cli(ref_dir, devices=2, steps=8, lpg=2, model_parallel=1,
               hist=tmp_path / "ref.json")

    got, ref = _losses(tmp_path / "el.json"), _losses(tmp_path / "ref.json")
    assert got and set(got) == set(ref)
    for s in sorted(ref):
        assert got[s] == ref[s], (s, got[s], ref[s])
