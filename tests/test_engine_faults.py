"""Fault-injection tests for the transfer engine (three-level pipeline).

The paper's host service must stay correct when things go wrong mid-run:
a worker exception on one group must surface on that group's waiter and
leave the engine serviceable; a failed run's writeback tickets must never
drain into the next run; ``close()`` during in-flight prefetch (including
in-flight *disk* fetches) must drain cleanly and allow transparent
restart; and the adaptive-distance controllers must keep their learned
state across runs — including failed ones.

Every test body runs under a watchdog (daemon thread + join timeout), so
a deadlock fails the test instead of hanging the suite.

The whole module runs twice: once plain and once under ``REPRO_SANITIZE=1``
(the runtime hazard sanitizer), proving that fault injection, retries, and
restart recovery raise zero hazard reports — the chaos paths are
happens-before clean, not just bitwise-correct.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import EngineConfig, GroupLayout, LinkModel, TransferEngine
from repro.core.hoststream import HostStreamExecutor, StreamStats
from repro.core.refspec import AUTO, PrefetchSpec
from repro.core.spillstore import SpillStore

TIMEOUT_S = 60.0


@pytest.fixture(autouse=True, params=["plain", "sanitized"])
def sanitize_mode(request, monkeypatch):
    if request.param == "sanitized":
        monkeypatch.setenv("REPRO_SANITIZE", "1")
    else:
        monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    return request.param


def run_with_timeout(fn, timeout_s: float = TIMEOUT_S):
    """Per-test deadlock watchdog: run ``fn`` on a daemon thread; a hang
    fails the test instead of wedging the whole suite."""
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
    return [rng.standard_normal(shape).astype(np.float32) for _ in range(n)]


def _disk_groups(tmp_path, n=4, shape=(4, 4)):
    store = SpillStore(tmp_path / "spill")
    host = _groups(n, shape)
    out = []
    for i, g in enumerate(host):
        store.put(f"g{i}", {"x": g})
        out.append(store.get(f"g{i}"))
    return host, out


# ---------------------------------------------------------------------------
# worker exception mid-group
# ---------------------------------------------------------------------------


def test_worker_exception_surfaces_on_waiter_and_engine_survives(monkeypatch):
    """An H2D failure on group k raises on *that* future's wait(); other
    groups complete, and the engine keeps serving — with uncorrupted
    staging contents — afterwards."""
    real_put = jax.device_put
    fail_on = {"n": 0}

    def flaky_put(x, *a, **kw):
        fail_on["n"] += 1
        if fail_on["n"] == 2:  # second transfer (group index 1)
            raise RuntimeError("injected H2D fault")
        return real_put(x, *a, **kw)

    groups = [{"x": g} for g in _groups(3)]

    def body():
        with TransferEngine() as eng:
            monkeypatch.setattr(jax, "device_put", flaky_put)
            futs = [eng.submit_group(i, g) for i, g in enumerate(groups)]
            futs[0].wait()
            with pytest.raises(RuntimeError, match="injected H2D fault"):
                futs[1].wait()
            futs[2].wait()
            np.testing.assert_array_equal(
                np.asarray(futs[2].group()["x"]), groups[2]["x"]
            )
            monkeypatch.setattr(jax, "device_put", real_put)
            # same layout after the fault: staging pool must hand back a
            # correctly-packed buffer, not a stale/corrupted one
            fut = eng.submit_group(3, groups[0])
            fut.wait()
            np.testing.assert_array_equal(
                np.asarray(fut.group()["x"]), groups[0]["x"]
            )

    run_with_timeout(body)


def test_disk_stage_exception_surfaces_and_pool_recovers(tmp_path, monkeypatch):
    """A fault while a *disk* group's H2D runs must not deadlock the
    read-ahead window (the buffer is released on the error path) and later
    disk groups must stream correctly."""
    host, disk = _disk_groups(tmp_path, n=4)
    real_put = jax.device_put
    fail_on = {"n": 0}

    def flaky_put(x, *a, **kw):
        fail_on["n"] += 1
        if fail_on["n"] == 1:
            raise RuntimeError("injected disk-group fault")
        return real_put(x, *a, **kw)

    def body():
        # window of 1: a leaked disk buffer would wedge every later fetch
        with TransferEngine(EngineConfig(disk_slots=1, disk_max_slots=1)) as eng:
            monkeypatch.setattr(jax, "device_put", flaky_put)
            futs = [eng.submit_group(i, g) for i, g in enumerate(disk)]
            with pytest.raises(RuntimeError, match="injected disk-group fault"):
                futs[0].wait()
            for i in (1, 2, 3):
                futs[i].wait()
                np.testing.assert_array_equal(
                    np.asarray(futs[i].group()["x"]), host[i]
                )

    run_with_timeout(body)


# ---------------------------------------------------------------------------
# stale writeback tickets after a failed run
# ---------------------------------------------------------------------------


def test_stale_writeback_tickets_discarded_after_failed_run():
    calls = {"n": 0}

    def apply(carry, g):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("injected apply fault")
        return carry, g * 2.0

    groups = _groups(5)

    def body():
        with HostStreamExecutor(apply, writeback=True) as ex:
            with pytest.raises(RuntimeError, match="injected apply fault"):
                ex.run(jnp.zeros(()), groups, mode="prefetch")
            # the failed run left pending tickets behind; they must be
            # visible to discard and must never drain into the next run
            assert ex.engine.discard_writebacks() >= 0
            _, outs = ex.run(jnp.zeros(()), groups[:2], mode="prefetch")
            assert len(outs) == 2
            for i in range(2):
                np.testing.assert_array_equal(outs[i], groups[i] * 2.0)

    run_with_timeout(body)


# ---------------------------------------------------------------------------
# close() during in-flight prefetch
# ---------------------------------------------------------------------------


def test_close_with_inflight_prefetch_drains_and_restarts():
    """close() while transfers are in flight drains pending work (no
    future left unset), then a later submit transparently restarts the
    workers (the driver's close-at-shutdown / resurrect-if-reused
    contract)."""
    link = LinkModel(request_s=2e-3, bandwidth_Bps=1e9)
    groups = [{"x": g} for g in _groups(6)]

    def body():
        eng = TransferEngine(EngineConfig(link=link))
        futs = [eng.submit_group(i, g) for i, g in enumerate(groups)]
        eng.close()  # in-flight: several transfers still queued
        for i, fut in enumerate(futs):
            fut.wait()  # all futures completed before the worker exited
            np.testing.assert_array_equal(
                np.asarray(fut.group()["x"]), groups[i]["x"]
            )
        assert eng._worker is None
        fut = eng.submit_group(99, groups[0])  # resurrects the worker
        fut.wait()
        np.testing.assert_array_equal(
            np.asarray(fut.group()["x"]), groups[0]["x"]
        )
        eng.close()

    run_with_timeout(body)


def test_close_with_inflight_disk_fetches_drains_cleanly(tmp_path):
    """Same contract one tier down: close() with queued disk fetches must
    complete every stage-1 ticket and stage-2 future, no deadlock."""
    host, disk = _disk_groups(tmp_path, n=6)
    cfg = EngineConfig(
        disk_link=LinkModel(request_s=2e-3, bandwidth_Bps=1e9),
        disk_slots=1, disk_max_slots=2,
    )

    def body():
        eng = TransferEngine(cfg)
        futs = [eng.submit_group(i, g) for i, g in enumerate(disk)]
        eng.close()
        for i, fut in enumerate(futs):
            fut.wait()
            np.testing.assert_array_equal(np.asarray(fut.group()["x"]), host[i])
        assert eng._disk_worker is None

    run_with_timeout(body)


# ---------------------------------------------------------------------------
# adaptive-controller persistence (incl. across a failed run)
# ---------------------------------------------------------------------------


def test_adaptive_controller_survives_failed_run():
    """The executor's learned prefetch window persists across run() calls
    — including a run that raises mid-way.  A fresh controller per run
    would restart every training step at the minimum distance."""
    link = LinkModel(request_s=1e-4, bandwidth_Bps=1e9, latency_s=2e-3)
    groups = _groups(6, shape=(16, 16))
    state = {"fail": False}

    def apply(carry, g):
        if state["fail"]:
            state["fail"] = False
            raise RuntimeError("injected")
        return carry + jnp.sum(g)

    pf = PrefetchSpec(buffer_size=12, distance=AUTO)

    def body():
        with HostStreamExecutor(apply, engine_config=EngineConfig(link=link)) as ex:
            st = StreamStats()
            for _ in range(3):  # learn a window > 1 on the slow link
                ex.run(jnp.zeros(()), groups, mode="prefetch", prefetch=pf, stats=st)
            ctrl = ex._controller
            assert ctrl is not None
            learned = ctrl.distance
            assert learned > 1
            state["fail"] = True
            with pytest.raises(RuntimeError, match="injected"):
                ex.run(jnp.zeros(()), groups, mode="prefetch", prefetch=pf, stats=st)
            # same controller object, learned state intact (within one
            # observe step of where the failed run left it)
            assert ex._controller is ctrl
            assert ctrl.distance >= learned - 1
            st2 = StreamStats()
            ex.run(jnp.zeros(()), groups, mode="prefetch", prefetch=pf, stats=st2)
            assert st2.distance_trace[0] == ctrl.distance or st2.distance_trace[0] > 1

    run_with_timeout(body)


def test_same_signature_groups_with_different_disk_positions(tmp_path):
    """Regression: group_signature cannot tell a memmap from a same-shaped
    ndarray, so the disk-stage layout must key on *which* leaves are
    disk-resident — mixed groups with swapped positions must not share a
    fetch plan."""
    store = SpillStore(tmp_path / "s")
    rng = np.random.default_rng(3)
    x = rng.standard_normal((4, 4)).astype(np.float32)
    y = rng.standard_normal((4, 4)).astype(np.float32)
    store.put("x", x)
    store.put("y", y)
    ga = {"p": store.get("x"), "q": y}  # disk at position 0
    gb = {"p": x, "q": store.get("y")}  # disk at position 1, same signature

    def body():
        with TransferEngine() as eng:
            fa = eng.submit_group(0, ga)
            fb = eng.submit_group(1, gb)
            fa.wait()
            fb.wait()
            np.testing.assert_array_equal(np.asarray(fa.group()["p"]), x)
            np.testing.assert_array_equal(np.asarray(fa.group()["q"]), y)
            np.testing.assert_array_equal(np.asarray(fb.group()["p"]), x)
            np.testing.assert_array_equal(np.asarray(fb.group()["q"]), y)

    run_with_timeout(body)


def test_disk_controller_persists_across_runs(tmp_path):
    """The engine-level disk read-ahead controller is engine state, not
    run state: a slow disk link grows the window and it stays grown for
    the next run on the same engine."""
    host, disk = _disk_groups(tmp_path, n=8, shape=(32, 32))
    cfg = EngineConfig(
        disk_link=LinkModel(request_s=1e-4, bandwidth_Bps=5e7, latency_s=1e-3),
        disk_slots=1,
    )

    @jax.jit
    def apply(carry, g):
        return carry + jnp.sum(g["x"])

    def body():
        eng = TransferEngine(cfg)
        with HostStreamExecutor(apply, engine=eng) as ex:
            ex.run(jnp.zeros(()), disk, mode="prefetch",
                   prefetch=PrefetchSpec(buffer_size=12, distance=AUTO))
            assert eng._disk_controller is not None
            grown = eng._disk_window
            assert grown > 1  # slow disk forced the window open
            ex.run(jnp.zeros(()), disk, mode="prefetch",
                   prefetch=PrefetchSpec(buffer_size=12, distance=AUTO))
            assert eng._disk_window >= 1 and eng._disk_controller is not None
        eng.close()

    run_with_timeout(body)


# ---------------------------------------------------------------------------
# serve path: disk-stage fault mid-decode
# ---------------------------------------------------------------------------


def test_disk_stage_fault_mid_decode_recovers(tmp_path, monkeypatch):
    """A disk-stage failure while a page fetch is in flight surfaces on the
    decode step, releases its read-ahead window slot (no wedged pipeline),
    and the session finishes with exactly the tokens of an un-faulted run —
    the cold home copy is intact, so the page is simply re-fetched."""
    from repro.configs import get_smoke_config
    from repro.launch import serve as sv
    from repro.launch.mesh import make_local_mesh

    cfg = get_smoke_config("smollm-360m")
    mesh = make_local_mesh()
    prompt = np.arange(1, 14, dtype=np.int32)

    def run(fault: bool):
        from repro.core.engine import TransferEngine as TE

        real_acquire = TE._acquire_disk_staging
        armed = {"on": False, "fired": 0}

        def flaky_acquire(self, dsig, layout):
            if armed["on"]:
                armed["on"] = False
                armed["fired"] += 1
                raise RuntimeError("injected disk-stage fault")
            return real_acquire(self, dsig, layout)

        monkeypatch.setattr(TE, "_acquire_disk_staging", flaky_acquire)
        with sv.ServeSession(
            cfg, mesh, slots=1, max_len=24, kv_kind="disk_host",
            page_len=4, hot_pages=0, seed=5,
            spill_dir=str(tmp_path / ("faulted" if fault else "clean")),
        ) as s:
            rid = s.submit(prompt, 8)
            s.admit_pending()
            for _ in range(2):
                s.step()
            if fault:
                armed["on"] = True
                with pytest.raises(RuntimeError, match="injected disk-stage"):
                    while s.pending_work():
                        s.step()
                assert armed["fired"] == 1
                # the failed fetch must have released its window slot
                assert s._engine._disk_in_use == 0
            while s.pending_work():
                s.step()
            toks = np.asarray(s.requests[rid].emitted, np.int32)
            # retire deleted the request's spill chunks — nothing leaked
            assert not [k for k in s._store.keys() if k.startswith("kv/")]
            assert s._engine._disk_in_use == 0
        monkeypatch.setattr(TE, "_acquire_disk_staging", real_acquire)
        return toks

    clean = run_with_timeout(lambda: run(False))
    faulted = run_with_timeout(lambda: run(True))
    np.testing.assert_array_equal(faulted, clean)


# ---------------------------------------------------------------------------
# a failed fetch settles the engine before its error surfaces
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("consumer", ["page_stream", "executor"])
def test_failed_fetch_settles_the_engine_before_raising(tmp_path, monkeypatch, consumer):
    """The disk stage of the first group fails while later groups of the
    window are in flight behind it, on a transfer worker slowed to release
    each disk buffer late.  When the error reaches the caller the rest of
    the window has landed: no disk buffer is still checked out of the
    read-ahead window, and the later groups arrive intact."""
    import time

    from repro.core.kvpager import PageStream

    host, disk = _disk_groups(tmp_path, n=4)
    real_acquire = TransferEngine._acquire_disk_staging
    real_release = TransferEngine._release_disk_staging
    armed = {"on": True}

    def flaky_acquire(self, dsig, layout):
        if armed["on"]:
            armed["on"] = False
            raise RuntimeError("injected disk-stage fault")
        return real_acquire(self, dsig, layout)

    def slow_release(self, dsig, buf):
        time.sleep(0.2)
        real_release(self, dsig, buf)

    monkeypatch.setattr(TransferEngine, "_acquire_disk_staging", flaky_acquire)
    monkeypatch.setattr(TransferEngine, "_release_disk_staging", slow_release)

    def body():
        with TransferEngine(EngineConfig(disk_slots=4, disk_max_slots=4)) as eng:
            if consumer == "page_stream":
                stream = PageStream(eng, distance=4)
                for i, g in enumerate(disk):
                    stream.push(0, f"p{i}", g)
                with pytest.raises(RuntimeError, match="injected disk-stage"):
                    stream.pop(0, "p0", disk[0], StreamStats())
                assert eng._disk_in_use == 0
                for i in (1, 2, 3):
                    got = stream.pop(0, f"p{i}", disk[i], StreamStats())
                    np.testing.assert_array_equal(np.asarray(got["x"]), host[i])
            else:
                pf = PrefetchSpec(buffer_size=4, distance=3)
                with HostStreamExecutor(lambda c, g: c + g["x"].sum(), engine=eng) as ex:
                    with pytest.raises(RuntimeError, match="injected disk-stage"):
                        ex.run(jnp.zeros(()), disk, prefetch=pf)
                    assert eng._disk_in_use == 0
                    carry, _ = ex.run(jnp.zeros(()), disk, prefetch=pf)
                    np.testing.assert_allclose(
                        float(carry), sum(float(h.sum()) for h in host), rtol=1e-5
                    )

    run_with_timeout(body)


# ---------------------------------------------------------------------------
# staging pool under the sanitizer
# ---------------------------------------------------------------------------


def test_sanitized_pool_recycles_and_a_faulted_put_drops_its_buffer(monkeypatch):
    """32 groups of one layout under the hazard sanitizer reuse their
    staging buffer with no hazard; the buffer of a put that fails is
    dropped (a half-issued put may still read it), not recycled."""
    real_put = GroupLayout.put_staged
    seen = {"n": 0, "faulted": None}

    def put(self, staging):
        seen["n"] += 1
        if seen["n"] == 10:
            seen["faulted"] = staging
            raise RuntimeError("injected fault mid-put")
        # the device gets memory of its own, as a put to an accelerator does
        return real_put(self, staging.copy())

    groups = [{"x": g} for g in _groups(32)]

    def body():
        with TransferEngine(EngineConfig(sanitize=True)) as eng:
            monkeypatch.setattr(GroupLayout, "put_staged", put)
            for i, grp in enumerate(groups):
                fut = eng.submit_group(i, grp)
                if i == 9:
                    with pytest.raises(RuntimeError, match="injected fault"):
                        fut.wait()
                    continue
                fut.wait()
                np.testing.assert_array_equal(np.asarray(fut.group()["x"]), grp["x"])
            assert eng.sanitizer.hazards == 0
            assert eng.staging_allocs + eng.staging_reuses == 32
            # the first buffer, and one to replace the dropped buffer
            assert eng.staging_allocs == 2
            (free,) = eng._staging_free.values()
            assert len(free) == 1 and free[0] is not seen["faulted"]

    run_with_timeout(body)
