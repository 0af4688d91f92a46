"""The trace reduction, on a trace recorded on one TPU v5 lite: three
``bench.compute`` spans, each one jitted 2048^2 bf16 reduction, separated
by 20 ms ``bench.host_wait`` sleeps, all inside ``bench.window`` (the
recording script's source path in the trace's metadata is renamed)."""
from pathlib import Path

import pytest

from chipbench import trace as tr

FIXTURE = Path(__file__).parent / "data" / "small_trace.xplane.pb"


@pytest.fixture(scope="module")
def events():
    return tr.load(str(FIXTURE))


def test_load_reads_device_ops_and_harness_spans(events):
    assert list(events["devices"]) == ["/device:TPU:0"]
    ops = events["devices"]["/device:TPU:0"]
    assert len(ops) == 9  # three programs of three ops
    assert {name.split("/")[0] for name, _, _ in ops} == {"jit__lambda"}
    names = [s[0] for s in events["spans"]]
    assert names.count("bench.compute") == 3
    assert names.count("bench.host_wait") == 3
    assert names.count("bench.window") == 1


def test_reduce_window(events):
    r = tr.reduce(events, tr.window_of(events, "bench.window"))
    assert r["window_s"] == pytest.approx(0.064910238)
    assert r["devices"] == 1
    # the device's clock runs ~1.3 ms behind the host's in this trace, so the
    # first program (90.1 us) starts before the host's window: two remain
    assert r["busy_s"] == pytest.approx(2 * 90.108e-6, rel=1e-3)
    top = r["breakdown"]["device_ops"][0]
    assert top[0] == "jit__lambda/convolution_reduce_fusion"
    gaps = r["breakdown"]["idle_gaps"]
    assert len(gaps) <= 10
    assert [g[0] for g in gaps[:3]] == ["bench.host_wait"] * 3
    assert all(0.019 < g[1] < 0.024 for g in gaps[:3])
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_reduce_busy_is_the_union_of_overlapping_ops():
    events = {
        "devices": {
            "/device:TPU:0": [["a", 0.0, 10.0], ["b", 5.0, 10.0], ["c", 30.0, 10.0]],
            "/device:TPU:1": [["a", 0.0, 40.0]],
        },
        "spans": [["bench.window", 0.0, 50.0], ["bench.wait", 15.0, 15.0]],
    }
    r = tr.reduce(events, (0.0, 50.0))
    assert r["busy_s"] == pytest.approx((25.0 + 40.0) / 2 / 1e9)
    assert r["breakdown"]["idle_gaps"][0] == ["bench.wait", pytest.approx(15e-9)]
    assert r["breakdown"]["idle_gaps"][1] == ["bench.window", pytest.approx(10e-9)]


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        tr.reduce({"devices": {}, "spans": []}, (0.0, 1.0))
