"""The reduction of the program's spans (``chipbench/program_trace.py``) and
the per-layer readers that read it, on synthetic events and on the
recorded chip trace the benchmark's own tests use."""
import json
import re
import sys
from pathlib import Path

import pytest

CHECKOUT = Path(__file__).resolve().parents[1]
if str(CHECKOUT) not in sys.path:
    sys.path.insert(0, str(CHECKOUT))

from chipbench import program_trace as pt  # noqa: E402
from chipbench import run as rn  # noqa: E402
from chipbench import trace as tr  # noqa: E402

SMALL_TRACE = CHECKOUT / "chipbench" / "tests" / "data" / "small_trace.xplane.pb"
MS = 1e6  # ns


def test_a_trace_without_program_spans_reduces_as_before():
    events = pt.load(str(SMALL_TRACE))
    assert events["program"] == [] and events["main_thread"] is not None
    window = tr.window_of(events, "bench.window")
    plain = tr.load(str(SMALL_TRACE))
    assert pt.reduce(events, window) == tr.reduce(plain, window)


def _events(program, spans=(), devices=None):
    """Device 0 busy over [0, 10] and [50, 60] ms and [90, 100] ms: its
    idle gaps are (10, 50) ms, the longer, and (60, 90) ms."""
    if devices is None:
        devices = {"/device:TPU:0": [
            ["jit_step/fusion", 0.0, 10 * MS],
            ["jit__unpack/copy", 50 * MS, 10 * MS],
            ["jit_step/fusion", 90 * MS, 10 * MS],
        ]}
    return {"devices": devices, "spans": [list(s) for s in spans],
            "program": [list(p) for p in program], "main_thread": 0}


def test_blocked_s_is_the_union_of_waits_on_the_same_thread():
    program = [
        ("repro.train.step", 0.0, 100 * MS, 0),
        ("repro.stream.wait", 10 * MS, 20 * MS, 0),  # 10-30
        ("repro.stream.drain.wait", 20 * MS, 20 * MS, 0),  # 20-40: overlaps
        ("repro.engine.disk.wait", 50 * MS, 20 * MS, 1),  # another thread
        ("repro.engine.pack", 45 * MS, 30 * MS, 1),
    ]
    table = pt.per_name(program, 5 * MS, 95 * MS)
    step = table["repro.train.step"]
    assert step["n"] == 1
    assert step["s"] == pytest.approx(0.090)  # clipped to the window
    assert step["blocked_s"] == pytest.approx(0.030)  # 10-40, once
    assert table["repro.stream.wait"]["blocked_s"] == table["repro.stream.wait"]["s"]
    assert table["repro.engine.pack"]["blocked_s"] == pytest.approx(0.020)
    # outside the window: not counted
    assert "repro.x" not in pt.per_name([("repro.x", 200 * MS, MS, 0)], 0, 100 * MS)


def test_module_s_is_the_union_of_a_programs_ops_over_devices():
    devices = {
        "/device:TPU:0": [
            ["jit__unpack/while", 0.0, 10 * MS],
            ["jit__unpack/fusion", 2 * MS, 3 * MS],  # nested in the while
            ["jit_step/fusion", 20 * MS, 5 * MS],
        ],
        "/device:TPU:1": [["jit__unpack/copy", 0.0, 20 * MS]],
    }
    out = pt.reduce(_events([("repro.serve.step", 0.0, 30 * MS, 0)], devices=devices),
                    (0.0, 30 * MS))
    assert out["module_s"]["jit__unpack"] == pytest.approx((0.010 + 0.020) / 2)
    assert out["module_s"]["jit_step"] == pytest.approx(0.005 / 2)


@pytest.mark.parametrize(
    "program, spans, labels",
    [
        # a wait on the main thread, the engine packing under it
        ([("repro.serve.step", 0, 100 * MS, 0), ("repro.stream.wait", 12 * MS, 70 * MS, 0),
          ("repro.engine.pack", 65 * MS, 20 * MS, 1), ("repro.engine.put", 20 * MS, 40 * MS, 1)],
         [], ["repro.stream.wait<repro.engine.put", "repro.stream.wait<repro.engine.pack"]),
        # a wait with no engine span at that instant
        ([("repro.serve.step", 0, 100 * MS, 0), ("repro.kv.wait", 12 * MS, 70 * MS, 0),
          ("repro.engine.pack", 1 * MS, 2 * MS, 1)],
         [], ["repro.kv.wait<idle", "repro.kv.wait<idle"]),
        # a busy span: no worker named; a harness span where no program span is
        ([("repro.kv.view", 55 * MS, 40 * MS, 0), ("repro.engine.put", 0, 100 * MS, 1)],
         [("bench.window", 0, 100 * MS), ("bench.serve_step", 5 * MS, 50 * MS)],
         ["bench.serve_step", "repro.kv.view"]),
        # an engine span is never the main thread's label
        ([("repro.engine.put", 0, 100 * MS, 1)], [("bench.serve_step", 0, 100 * MS)],
         ["bench.serve_step", "bench.serve_step"]),
    ],
)
def test_idle_gaps_are_put_down_to_the_program_spans(program, spans, labels):
    out = pt.reduce(_events(program, spans), (0.0, 100 * MS))
    gaps = out["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == labels  # at 30 ms, then at 75 ms
    assert [g[1] for g in gaps] == pytest.approx([0.040, 0.030])


def test_clock_offset_reads_the_lag_behind_the_last_device_op():
    devices = {"/device:TPU:0": [
        ["jit_decode_step/fusion", 10 * MS, 5 * MS],
        ["jit__lambda/reduce", 16 * MS, 1 * MS],  # ends at 17 ms
        ["jit_decode_step/fusion", 30 * MS, 5 * MS],
        ["jit__lambda/reduce", 36 * MS, 1 * MS],  # ends at 37 ms
    ]}
    program = [("repro.serve.sync.wait", 11 * MS, 7.5 * MS, 0),
               ("repro.serve.sync.wait", 31 * MS, 7.5 * MS, 0),
               ("repro.serve.sync.wait", 31 * MS, 9 * MS, 1)]  # not the main thread
    assert pt.clock_offset_ms(_events(program, devices=devices)) == pytest.approx(1.5)
    assert pt.clock_offset_ms(_events([], devices=devices)) is None


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------


def _run(kind, program=None, module_s=None):
    trace = {"busy_s": 0.1, "window_s": 2.0, "devices": 1, "breakdown": {}}
    if program is not None:
        trace.update(program=program, module_s=module_s or {})
    return {"kind": kind, "trace": trace, "window_s": 2.0}


PROGRAM = {
    "repro.stream.wait": {"n": 10, "s": 1.0, "blocked_s": 1.0},
    "repro.stream.drain.wait": {"n": 2, "s": 0.2, "blocked_s": 0.2},
    "repro.engine.pack": {"n": 10, "s": 0.5, "blocked_s": 0.0},
    "repro.engine.put": {"n": 10, "s": 0.3, "blocked_s": 0.0},
    "repro.engine.d2h": {"n": 4, "s": 0.1, "blocked_s": 0.0},
    "repro.train.step": {"n": 2, "s": 1.9, "blocked_s": 1.5},
    "repro.serve.step": {"n": 4, "s": 1.9, "blocked_s": 1.5},
}
MODULES = {"jit__unpack": 0.04, "jit_step": 0.06}
EXPECTED = {
    "transfer_wait_share.train": 60.0,
    "pack_share.train": 25.0,
    "pack_share.decode": 25.0,
    "h2d_share.train": 15.0,
    "h2d_share.decode": 15.0,
    "d2h_share.train": 5.0,
    "unpack_device_share.train": 2.0,
    "unpack_device_share.decode": 2.0,
    "host_busy_s_per_step.train": 0.2,
    "host_busy_ms_per_step.decode": 100.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader(name):
    read = rn.reader(name)
    kind = "train" if name.endswith(".train") else "serve"
    other = "serve" if kind == "train" else "train"
    assert read(_run(kind, PROGRAM, MODULES)) == pytest.approx(EXPECTED[name])
    assert read(_run(other, PROGRAM, MODULES)) is None  # the other kind
    assert read(_run(kind)) is None  # a trace with no program span
    assert read({"kind": kind, "trace": None, "window_s": 2.0}) is None  # untraced


def test_metrics_are_well_formed_benchmark_entries():
    bench = rn.load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    taken = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    names = [m["name"] for m in pt.METRICS]
    assert sorted(names) == sorted(EXPECTED) and not taken & set(names)
    for m in pt.METRICS:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", m["name"])
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] == "device_trace"
        assert m["layer"] in layers | {"entry point"}
        assert callable(rn.reader(m["name"]))
        moves = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in moves["workloads"]
            traffic = json.loads(
                (CHECKOUT / "chipbench" / "traffic" / f"{cells[w]['traffic']}.json").read_text()
            )
            assert {"train": "train", "serve": "decode"}[traffic["entry"]] == \
                m["name"].rsplit(".", 1)[1]
