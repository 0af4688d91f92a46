"""The program's spans (``repro.core.spans``) in a profiler trace.

A smoke weight-streamed train step and smoke ``ServeSession`` decode steps
run under the JAX profiler inside a ``bench.window`` span, as the chip
benchmark runs them; ``chipbench.program_trace.load`` reads the trace back.
Each span must sit on the thread the reduction keys it to (the main thread,
which holds ``bench.window``, or an engine thread) and nest as the program
nests it, and the span-timed transfer wait must agree with the
``StreamStats.transfer_wait_s`` counter the benchmark reads.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

CHECKOUT = Path(__file__).resolve().parents[1]
if str(CHECKOUT) not in sys.path:
    sys.path.insert(0, str(CHECKOUT))

from chipbench import program_trace as pt  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.core.refspec import PrefetchSpec  # noqa: E402
from repro.core.residency import ResidencyCache  # noqa: E402
from repro.core.spillstore import SpillStore  # noqa: E402
from repro.core.weightstream import WeightStreamPlan  # noqa: E402
from repro.data.synthetic import SyntheticConfig, synthetic_batch  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.train import steps as st  # noqa: E402

ENGINE = {"repro.engine.pack", "repro.engine.put", "repro.engine.d2h"}
DISK_ENGINE = {"repro.engine.disk.wait", "repro.disk.read"}


def traced(tmp_path, fn):
    """``(events, fn())`` with ``fn`` run inside ``bench.window`` under the
    profiler."""
    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    try:
        with jax.profiler.TraceAnnotation(pt.WINDOW):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    return pt.load(tr.find(d)), out


def by_thread(events) -> dict:
    """span name -> set of threads it ran on, ``"main"`` for the main one."""
    out: dict = {}
    for name, _s, _d, t in events["program"]:
        out.setdefault(name, set()).add("main" if t == events["main_thread"] else t)
    return out


def spans(events, name) -> list:
    return [e for e in events["program"] if e[0] == name]


def assert_nested(events, inner: str, outers: tuple):
    """Every ``inner`` span lies inside an ``outers`` span of its thread."""
    cover = [e for o in outers for e in spans(events, o)]
    for _, s, d, t in spans(events, inner):
        assert any(
            ct == t and cs <= s and s + d <= cs + cd for _, cs, cd, ct in cover
        ), f"{inner} at {s} is inside none of {outers}"


def assert_agrees(span_s: float, counter_s: float):
    assert abs(span_s - counter_s) <= 0.1 * counter_s + 2e-3, (span_s, counter_s)


# ---------------------------------------------------------------------------
# weight-streamed train step
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(get_smoke_config("smollm-360m"), n_layers=4)


@pytest.mark.parametrize("kind", ["pinned_host", "disk_host"])
def test_train_step_spans(cfg, kind, tmp_path):
    plan = WeightStreamPlan(cfg, st.abstract_params(cfg), layers_per_group=2)
    store = SpillStore(tmp_path / "spill") if kind == "disk_host" else None
    step = st.make_weight_streamed_train_step(
        cfg, AdamWConfig(peak_lr=1e-3, warmup_steps=2, total_steps=32),
        plan=plan, param_kind=kind, spill_store=store,
        prefetch=PrefetchSpec(buffer_size=plan.n_groups + 2, distance="auto"),
        residency=ResidencyCache(0),  # no cache: every group crosses the link
    )
    state = st.init_weight_streamed_state(jax.random.PRNGKey(0), cfg, plan)
    if kind == "disk_host":
        state = st.spill_weight_streamed_state(plan, state, store)
    batch = synthetic_batch(cfg, SyntheticConfig(cfg.vocab_size, 16, 2, seed=0), 0)
    try:
        state, _ = step(state, batch)  # compiles outside the trace
        w0 = step.param_stats.transfer_wait_s + step.opt_stats.transfer_wait_s

        def two_steps():
            s = state
            for _ in range(2):
                s, m = step(s, batch)
                float(m["loss"])
            return s

        events, _ = traced(tmp_path, two_steps)
        waited = step.param_stats.transfer_wait_s + step.opt_stats.transfer_wait_s - w0
    finally:
        step.close()
        if store is not None:
            store.close()

    threads = by_thread(events)
    main = {
        "repro.train.step", "repro.train.fwd", "repro.train.bwd", "repro.train.opt",
        "repro.train.rehome", "repro.stream.wait", "repro.stream.unpack",
        "repro.stream.sync.wait", "repro.stream.drain.wait",
    }
    for name in main:
        assert threads.get(name) == {"main"}, (name, threads.get(name))
    engine = ENGINE | (DISK_ENGINE if kind == "disk_host" else set())
    for name in engine:
        assert threads.get(name) and "main" not in threads[name], (name, threads)
    assert set(threads) == main | engine
    assert [e[0] for e in spans(events, "repro.train.step")] == ["repro.train.step"] * 2
    for phase in ("repro.train.fwd", "repro.train.bwd", "repro.train.opt",
                  "repro.train.rehome"):
        assert_nested(events, phase, ("repro.train.step",))
    for name in ("repro.stream.wait", "repro.stream.unpack", "repro.stream.sync.wait"):
        assert_nested(events, name, ("repro.train.fwd", "repro.train.bwd",
                                     "repro.train.opt"))
    assert_nested(events, "repro.stream.drain.wait", ("repro.train.bwd", "repro.train.opt"))
    if kind == "disk_host":
        # the disk read runs on its own worker, apart from the H2D worker
        assert threads["repro.disk.read"].isdisjoint(threads["repro.engine.put"])
    lo, hi = tr.window_of(events, pt.WINDOW)
    table = pt.per_name(events["program"], lo, hi)
    assert_agrees(table["repro.stream.wait"]["s"], waited)
    step_row = table["repro.train.step"]
    assert step_row["n"] == 2
    assert 0.0 < step_row["blocked_s"] < step_row["s"]


# ---------------------------------------------------------------------------
# ServeSession decode steps
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_kind", ["pinned_host", "disk_host"])
def test_serve_step_spans(kv_kind, tmp_path):
    from repro.launch import serve as sv
    from repro.launch.mesh import make_local_mesh

    scfg = get_smoke_config("smollm-360m")
    rng = np.random.default_rng(0)
    with sv.ServeSession(
        scfg, make_local_mesh(), slots=2, max_len=32, kv_kind=kv_kind,
        page_len=4, hot_pages=1, seed=3, param_kind="pinned_host",
        device_budget_mb=5.0, param_cache_mb=0.0,
        spill_dir=str(tmp_path / "spill") if kv_kind == "disk_host" else None,
    ) as s:
        for gen in (3, 9, 6, 4):
            s.submit(rng.integers(1, scfg.vocab_size, 9), gen)
        s.admit_pending()
        s.step()  # compiles outside the trace
        w_weights, w_kv = s.param_stats.transfer_wait_s, s.stats.transfer_wait_s

        def steps():
            n = 0
            while s.pending_work():
                s.step()
                n += 1
            return n

        events, n = traced(tmp_path, steps)
        waited_weights = s.param_stats.transfer_wait_s - w_weights
        waited_kv = s.stats.transfer_wait_s - w_kv

    threads = by_thread(events)
    main = {
        "repro.serve.step", "repro.serve.admit", "repro.serve.sync.wait",
        "repro.kv.view", "repro.kv.wait", "repro.kv.flush", "repro.stream.wait",
        "repro.stream.unpack", "repro.stream.sync.wait", "repro.stream.drain.wait",
    }
    for name in main:
        assert threads.get(name) == {"main"}, (name, threads.get(name))
    engine = ENGINE | (DISK_ENGINE if kv_kind == "disk_host" else set())
    for name in engine:
        assert threads.get(name) and "main" not in threads[name], (name, threads)
    assert set(threads) == main | engine
    assert len(spans(events, "repro.serve.step")) == n
    for name in ("repro.serve.admit", "repro.kv.view", "repro.kv.flush"):
        assert_nested(events, name, ("repro.serve.step",))
    assert_nested(events, "repro.kv.wait", ("repro.kv.view",))
    assert_nested(events, "repro.serve.sync.wait", ("repro.serve.step",))
    assert_nested(events, "repro.stream.drain.wait", ("repro.kv.flush",))
    # admissions inside the window prefill through the weight stream too
    assert_nested(events, "repro.stream.wait", ("repro.serve.step",))
    lo, hi = tr.window_of(events, pt.WINDOW)
    table = pt.per_name(events["program"], lo, hi)
    assert_agrees(table["repro.stream.wait"]["s"], waited_weights)
    assert_agrees(table["repro.kv.wait"]["s"], waited_kv)
