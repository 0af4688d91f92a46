"""Serving-path tests: paged KV cache, continuous batching, bugfix pins.

Covers the PR's acceptance surface:
  * prefill+decode smoke on the smollm-360m smoke config,
  * paged vs unpaged bitwise equality for every kv kind x page length,
  * eviction + readmission of a request mid-decode (bitwise resume),
  * per-tier StreamStats accounting (1 H2D request per fetched page group,
    one disk request per disk-homed group, writebacks per demotion),
  * the seed bugfix pins: plan-spec placement under model parallelism
    (subprocess, 2-way mesh), no deleted-buffer error with host-kind
    caches, --seed plumbed through,
  * the paged flash-decode kernel view (bitwise vs the dense cache).
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import memkind as mk
from repro.core.engine import TransferEngine
from repro.core.hoststream import StreamStats
from repro.core.kvpager import KVPager, KVPagerConfig, paged_cache_supported
from repro.core.refspec import AUTO
from repro.launch import serve as sv
from repro.launch.mesh import make_local_mesh
from repro.train import steps as st


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("smollm-360m")


@pytest.fixture(scope="module")
def mesh():
    return make_local_mesh()


@pytest.fixture(scope="module")
def reference(cfg, mesh):
    """All-device unpaged run: the baseline every placement must match."""
    return sv.serve(
        cfg, mesh, batch=2, prompt_len=21, gen=8, kv_kind="device",
        kv_page_len=0, seed=7,
    )


def test_prefill_decode_smoke(reference):
    gen = reference["generated"]
    assert gen.shape == (2, 8)
    assert gen.dtype == np.int32
    assert (gen >= 0).all()
    assert reference["prefill_s"] > 0 and reference["decode_s"] > 0


@pytest.mark.parametrize("kv_kind", ["device", "pinned_host", "disk_host"])
@pytest.mark.parametrize("page_len", [4, 8])
def test_paged_bitwise_equals_unpaged(cfg, mesh, reference, kv_kind, page_len):
    res = sv.serve(
        cfg, mesh, batch=2, prompt_len=21, gen=8, kv_kind=kv_kind,
        kv_page_len=page_len, seed=7,
    )
    assert np.array_equal(res["generated"], reference["generated"])
    if kv_kind != "device":
        # the hierarchy must actually bound the device working set
        assert res["peak_resident_bytes"] < res["total_cache_bytes"]


def test_unpaged_host_kind_bitwise_and_no_deleted_buffer(cfg, mesh, reference):
    """Satellite bugfix pin: the host-kind unpaged path re-places the cache
    every step; with unconditional donation this raised a deleted-buffer
    error.  Must run clean and match the device run bitwise."""
    res = sv.serve(
        cfg, mesh, batch=2, prompt_len=21, gen=8, kv_kind="pinned_host",
        kv_page_len=0, seed=7,
    )
    assert np.array_equal(res["generated"], reference["generated"])
    assert res["stats"].d2h_requests > 0  # the round trip actually happened


def test_seed_is_plumbed(cfg, mesh):
    """Satellite bugfix pin: ``seed`` reaches param init (the seed repo
    dropped it between main() and serve())."""
    a = sv.serve(cfg, mesh, batch=1, prompt_len=9, gen=4, kv_page_len=4, seed=1)
    b = sv.serve(cfg, mesh, batch=1, prompt_len=9, gen=4, kv_page_len=4, seed=1)
    c = sv.serve(cfg, mesh, batch=1, prompt_len=9, gen=4, kv_page_len=4, seed=2)
    assert np.array_equal(a["generated"], b["generated"])
    assert not np.array_equal(a["generated"], c["generated"])


def test_gen1_request_retires_with_pending_demotions(cfg, mesh):
    """A gen==1 request finishes straight from admission, while its
    admission demotions are still in flight — retire must flush them
    before dropping the page records (regression: IndexError)."""
    res = sv.serve(
        cfg, mesh, batch=1, prompt_len=12, gen=1, kv_kind="pinned_host",
        kv_page_len=4, seed=0,
    )
    assert res["generated"].shape == (1, 1)


def test_failed_session_constructor_does_not_leak(mesh):
    """Bad pager knobs must be rejected before the engine thread / spill
    dir are allocated."""
    import threading

    mx = get_smoke_config("smollm-360m")
    n0 = threading.active_count()
    with pytest.raises(ValueError, match="hot_pages"):
        sv.ServeSession(
            mx, mesh, slots=1, max_len=16, kv_kind="pinned_host",
            page_len=4, hot_pages=-1,
        )
    assert threading.active_count() == n0  # no orphaned engine worker


def test_codebook_arch_serves_paged_and_unpaged(mesh):
    """Audio (codebook) archs: the prompt/step batches carry ``codes``; the
    paged and unpaged paths must both work and agree (regression: the serve
    rewrite briefly dropped the codes branch)."""
    mg = get_smoke_config("musicgen-medium")
    u = sv.serve(mg, mesh, batch=2, prompt_len=9, gen=5, kv_kind="device",
                 kv_page_len=0, seed=0)
    p = sv.serve(mg, mesh, batch=2, prompt_len=9, gen=5,
                 kv_kind="pinned_host", kv_page_len=4, seed=0)
    assert u["generated"].shape == (2, 5)
    assert np.array_equal(u["generated"], p["generated"])


def test_ring_cache_arch_serves_unpaged(mesh):
    """SWA ring caches cannot page (shared slot_pos) but the unpaged
    lock-step path must still serve them (regression: the vector-pos
    rewrite briefly broke it)."""
    mx = get_smoke_config("mixtral-8x7b")
    r = sv.serve(mx, mesh, batch=2, prompt_len=9, gen=4,
                 kv_kind="pinned_host", kv_page_len=0, seed=0)
    assert r["generated"].shape == (2, 4)
    with pytest.raises(ValueError, match="not pageable"):
        sv.serve(mx, mesh, batch=2, prompt_len=9, gen=4,
                 kv_kind="pinned_host", kv_page_len=4, seed=0)


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------


def test_slot_reuse_and_late_admission(cfg, mesh):
    """More requests than slots: finished requests retire and their slot is
    reused; every request still matches its dedicated-run tokens."""
    prompts = {
        0: np.arange(1, 10, dtype=np.int32),         # 9 tokens
        1: np.arange(3, 16, dtype=np.int32),         # 13 tokens (pad-free)
        2: np.arange(5, 12, dtype=np.int32),
    }
    gens = {0: 6, 1: 3, 2: 5}

    def solo(rid):
        with sv.ServeSession(
            cfg, mesh, slots=1, max_len=24, kv_kind="pinned_host",
            page_len=4, seed=11,
        ) as s:
            s.submit(prompts[rid], gens[rid])
            return s.run()[0]

    expected = {rid: solo(rid) for rid in prompts}

    with sv.ServeSession(
        cfg, mesh, slots=2, max_len=24, kv_kind="pinned_host", page_len=4,
        seed=11,
    ) as s:
        rids = {rid: s.submit(prompts[rid], gens[rid]) for rid in prompts}
        out = s.run()
    for rid in prompts:
        assert np.array_equal(out[rids[rid]], expected[rid]), rid


@pytest.mark.parametrize("kv_kind", ["pinned_host", "disk_host"])
def test_evict_readmit_mid_decode(cfg, mesh, kv_kind):
    """A request parked at the host mid-decode and readmitted later must
    finish with exactly the tokens of an uninterrupted run."""
    prompt = np.arange(1, 14, dtype=np.int32)
    other = np.arange(2, 11, dtype=np.int32)

    def run(interrupt):
        with sv.ServeSession(
            cfg, mesh, slots=2, max_len=32, kv_kind=kv_kind, page_len=4,
            hot_pages=1, seed=5,
        ) as s:
            rid = s.submit(prompt, 10)
            s.submit(other, 12)
            s.admit_pending()
            for _ in range(3):
                s.step()
            if interrupt:
                s.evict(rid)
                assert rid not in s.active
                s.step()  # the other request decodes on without it
                s.readmit(rid)
            while s.pending_work():
                s.step()
            return np.asarray(s.requests[rid].emitted, np.int32)

    assert np.array_equal(run(True), run(False))


# ---------------------------------------------------------------------------
# stats accounting
# ---------------------------------------------------------------------------


def test_stream_stats_per_tier_accounting(cfg, mesh):
    """1 H2D request per fetched page group; disk groups add exactly one
    disk request each; demotions drain through D2H."""
    res = sv.serve(
        cfg, mesh, batch=2, prompt_len=16, gen=9, kv_kind="pinned_host",
        kv_page_len=4, hot_pages=1, seed=3,
    )
    stats = res["stats"]
    assert stats.n_groups > 0
    assert stats.h2d_requests == stats.n_groups  # coalesced: 1 req/group
    assert stats.disk_requests == 0
    assert res["demoted_groups"] > 0
    # each demoted page group drains k+v leaves through the D2H pipeline
    assert stats.d2h_requests == 2 * res["demoted_groups"]

    resd = sv.serve(
        cfg, mesh, batch=2, prompt_len=16, gen=9, kv_kind="disk_host",
        kv_page_len=4, hot_pages=1, seed=3,
    )
    sd = resd["stats"]
    assert sd.h2d_requests == sd.n_groups
    assert sd.disk_requests == sd.n_groups  # one chunk file per page group
    per = sd.per_tier()
    assert per["disk"]["requests"] == sd.disk_requests
    assert per["h2d"]["bytes"] == sd.bytes_h2d > 0


def test_device_kind_never_transfers(cfg, mesh):
    res = sv.serve(
        cfg, mesh, batch=2, prompt_len=16, gen=6, kv_kind="device",
        kv_page_len=4, seed=3,
    )
    stats = res["stats"]
    assert stats.h2d_requests == 0
    assert stats.d2h_requests == 0
    assert stats.transfer_wait_s == 0.0


def test_adaptive_distance_grows_under_modeled_link(cfg, mesh):
    from repro.core.engine import EngineConfig, LinkModel

    engine = TransferEngine(
        EngineConfig(link=LinkModel(request_s=0.2e-3, bandwidth_Bps=88e6))
    )
    try:
        res = sv.serve(
            cfg, mesh, batch=1, prompt_len=24, gen=10, kv_kind="pinned_host",
            kv_page_len=4, distance=AUTO, engine=engine, seed=3,
        )
    finally:
        engine.close()
    assert res["stats"].distance_trace[-1] > 1  # the window actually grew


# ---------------------------------------------------------------------------
# pager unit coverage
# ---------------------------------------------------------------------------


def test_pager_rejects_unpageable_cache():
    rg = get_smoke_config("recurrentgemma-2b")
    template = st.abstract_caches(rg, 1, 16)
    assert not paged_cache_supported(template)
    engine = TransferEngine()
    try:
        with pytest.raises(ValueError, match="full-attention"):
            KVPager(
                template, KVPagerConfig(page_len=4), slots=1, engine=engine
            )
    finally:
        engine.close()


def test_pager_requires_page_aligned_length(cfg):
    template = st.abstract_caches(cfg, 1, 18)
    engine = TransferEngine()
    try:
        with pytest.raises(ValueError, match="multiple"):
            KVPager(
                template, KVPagerConfig(page_len=4), slots=1, engine=engine
            )
    finally:
        engine.close()


def test_disk_kind_requires_store(cfg):
    template = st.abstract_caches(cfg, 1, 16)
    engine = TransferEngine()
    try:
        with pytest.raises(ValueError, match="SpillStore"):
            KVPager(
                template,
                KVPagerConfig(page_len=4, kind=mk.DISK_HOST),
                slots=1,
                engine=engine,
            )
    finally:
        engine.close()


# ---------------------------------------------------------------------------
# paged flash-decode kernel view
# ---------------------------------------------------------------------------


def test_decode_attention_paged_matches_dense():
    from repro.kernels.decode_attention import (
        decode_attention,
        decode_attention_paged,
    )

    b, n, kh, h, t, page = 2, 4, 2, 16, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, n, h), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, kh, h), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, kh, h), jnp.float32)
    lengths = jnp.array([t, t - 7], jnp.int32)

    dense = decode_attention(q, k, v, lengths, block_kv=page, interpret=True)
    k_pages = [k[:, i : i + page] for i in range(0, t, page)]
    v_pages = [v[:, i : i + page] for i in range(0, t, page)]
    paged = decode_attention_paged(
        q, k_pages, v_pages, lengths, block_kv=page, interpret=True
    )
    assert jnp.array_equal(dense, paged)  # bitwise: the view is a reference


# ---------------------------------------------------------------------------
# model-parallel placement regression (satellite bugfix, 2-way mesh)
# ---------------------------------------------------------------------------

_MP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
from jax.sharding import PartitionSpec as P
from repro.configs import get_smoke_config
from repro.core import memkind as mk
from repro.models import transformer
from repro.parallel import sharding as sh

cfg = get_smoke_config("smollm-360m")
mesh = jax.make_mesh((1, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
plan = sh.make_plan(mesh, mode="serve")
batch = 2
caches = jax.jit(lambda: transformer.init_caches(cfg, batch, 16))()
specs = sh.cache_specs_tree(plan, caches, batch)
placed = mk.place(caches, mesh, specs, mk.as_kind("pinned_host"))
back = mk.place(placed, mesh, specs, mk.DEVICE)
flat_b = jax.tree.leaves(back)
flat_s = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
# regression: the seed placed with a bare P() and silently dropped the plan;
# round-tripped caches must keep the plan's spec (head dim sharded 2-way)
assert any(any(ax is not None for ax in s) for s in flat_s), flat_s
for leaf, spec in zip(flat_b, flat_s):
    got = leaf.sharding.spec
    assert got == spec, (got, spec)
print("MP_PLACEMENT_OK")
"""


@pytest.mark.slow
def test_cache_placement_keeps_plan_specs_2way_mesh():
    proc = subprocess.run(
        [sys.executable, "-c", _MP_SCRIPT],
        capture_output=True,
        text=True,
        timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": "/root"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MP_PLACEMENT_OK" in proc.stdout


# ---------------------------------------------------------------------------
# fault injection / shutdown hardening (ISSUE 5 satellites)
# ---------------------------------------------------------------------------


def test_session_close_with_inflight_prefetch_and_undrained_demotions(cfg, mesh, tmp_path):
    """close() while page prefetches are in flight and demotion writebacks
    are undrained must shut down cleanly: workers drain and join, the
    read-ahead window frees, staging pools stay bounded, and the ephemeral
    spill dir is removed."""
    from test_engine_faults import run_with_timeout

    def body():
        import os

        s = sv.ServeSession(
            cfg, mesh, slots=2, max_len=24, kv_kind="disk_host",
            page_len=4, hot_pages=0, seed=3,
        )
        spill_dir = s._store.dir
        rids = [s.submit(np.arange(1, 10 + i, dtype=np.int32), 8) for i in range(2)]
        s.admit_pending()
        for _ in range(2):
            s.step()
        # leave demotions undrained and prefetches in flight, then close
        for rid in rids:
            table = s.pager.tables[rid]
            p = s.pager.current_page(table)
            if table.records[p].state == "device":
                s.pager._demote(table, p)
        assert s.pager._pending_demotions  # undrained by construction
        s.pager.prefetch()  # in-flight page fetches at close time
        s.close()
        assert s._engine._worker is None and s._engine._disk_worker is None
        assert s._engine._disk_in_use == 0
        for free in s._engine._staging_free.values():
            assert len(free) <= max(1, s._engine.config.staging_slots)
        assert not os.path.exists(spill_dir)  # ephemeral store removed

    run_with_timeout(body)


@pytest.mark.parametrize("kv_kind", ["pinned_host", "disk_host"])
def test_readmit_after_fault_resumes_bitwise(cfg, mesh, kv_kind, monkeypatch, tmp_path):
    """A fetch fault on the step right after readmission must not corrupt
    the parked pages: the faulted step re-raises, the retry re-fetches from
    the intact cold copies, and the request finishes with exactly the
    tokens of an uninterrupted run."""
    import jax as _jax
    from test_engine_faults import run_with_timeout

    prompt = np.arange(1, 14, dtype=np.int32)
    other = np.arange(2, 11, dtype=np.int32)

    def run(fault: bool):
        real_put = _jax.device_put
        armed = {"on": False, "fired": 0}

        def flaky_put(x, *a, **kw):
            if armed["on"]:
                armed["on"] = False
                armed["fired"] += 1
                raise RuntimeError("injected readmit fetch fault")
            return real_put(x, *a, **kw)

        with sv.ServeSession(
            cfg, mesh, slots=2, max_len=32, kv_kind=kv_kind, page_len=4,
            hot_pages=1, seed=5,
            spill_dir=str(tmp_path / f"{kv_kind}-{fault}") if kv_kind == "disk_host" else None,
        ) as s:
            rid = s.submit(prompt, 10)
            s.submit(other, 12)
            s.admit_pending()
            for _ in range(3):
                s.step()
            s.evict(rid)
            s.step()
            s.readmit(rid)
            if fault:
                # the next step's view() must fetch the readmitted request's
                # cold pages through the engine — fail that H2D once
                monkeypatch.setattr(_jax, "device_put", flaky_put)
                armed["on"] = True
                with pytest.raises(RuntimeError, match="injected readmit"):
                    while s.pending_work():
                        s.step()
                monkeypatch.setattr(_jax, "device_put", real_put)
                assert armed["fired"] == 1
            while s.pending_work():
                s.step()
            assert s._engine._disk_in_use == 0
            return np.asarray(s.requests[rid].emitted, np.int32)

    clean = run_with_timeout(lambda: run(False))
    faulted = run_with_timeout(lambda: run(True))
    np.testing.assert_array_equal(faulted, clean)


# ---------------------------------------------------------------------------
# streamed model parameters (ISSUE 5 tentpole, serve side)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("param_kind", ["pinned_host", "disk_host"])
def test_streamed_params_serve_matches_device_run(cfg, mesh, reference, param_kind):
    """Host/disk-homed weights streamed per prefill/decode step produce
    exactly the device-resident run's tokens, at one coalesced H2D request
    per fetched (device, group), with streamed residency bounded while the
    cache stays paged as before."""
    res = sv.serve(
        cfg, mesh, batch=2, prompt_len=21, gen=8, kv_kind="pinned_host",
        kv_page_len=4, seed=7, param_kind=param_kind, device_budget_mb=2.0,
    )
    assert np.array_equal(res["generated"], reference["generated"])
    ps = res["param_stats"]
    assert ps.n_groups > 0
    # groups that did cross the link cost ONE coalesced request each; the
    # residency cache turns repeat visits into zero-request pass-throughs
    assert ps.per_tier()["h2d"]["requests_per_fetched_device_group"] == 1.0
    assert ps.unique_group_fetches > 0
    assert ps.peak_inflight_bytes > 0
    if param_kind == "disk_host":
        assert ps.disk_requests > 0
    # KV paging unaffected: pages still fetched/demoted through their own
    # accounting
    assert res["stats"].n_groups > 0
    assert res["peak_resident_bytes"] < res["total_cache_bytes"]


def test_streamed_params_on_unpaged_path_bitwise(cfg, mesh):
    """The unpaged schedule carries streamed params too (the route for
    archs whose cache is not pageable): tokens bitwise vs device-resident."""
    kw = dict(batch=2, prompt_len=9, gen=4, kv_page_len=0, warmup=False)
    ref = sv.serve(cfg, mesh, **kw)
    res = sv.serve(cfg, mesh, **kw, param_kind="pinned_host")
    np.testing.assert_array_equal(res["generated"], ref["generated"])
    ps = res["param_stats"]
    assert ps.per_tier()["h2d"]["requests_per_fetched_device_group"] == 1.0
