"""The reduction from a profiler trace to the device numbers."""

import os

import pytest

from conftest import BENCH

trace = pytest.importorskip("run").module("trace")
DATA = os.path.join(BENCH, "tests", "data", "small.xplane.pb")


def test_reduce_merges_overlaps_clips_to_the_window_and_names_gaps():
    ms = 1e6
    spans = [(0, 100 * ms, "bench.window"),
             (0, 100 * ms, "bench.request"),
             (40 * ms, 60 * ms, "bench.solve")]
    ops = [(-5 * ms, 10 * ms, "fusion.1", False),      # clipped to 0..10
           (5 * ms, 20 * ms, "scatter.2", True),       # overlaps fusion.1
           (45 * ms, 50 * ms, "fusion.1", False),
           (90 * ms, 120 * ms, "scatter.2", True)]     # clipped to 90..100
    spans.append((52 * ms, 55 * ms, "device_get"))
    t = trace.reduce([ops], spans)
    assert t["window_s"] == pytest.approx(0.1)
    assert t["busy_s"] == pytest.approx(0.035)          # 0-20, 45-50, 90-100
    assert t["op_s"] == pytest.approx(0.040)
    assert t["scatter_s"] == pytest.approx(0.025)
    assert t["device_ops"] == [["scatter.2", pytest.approx(0.025)],
                               ["fusion.1", pytest.approx(0.015)]]
    gaps = dict((n, v) for n, v in t["idle_gaps"])
    assert gaps["bench.request"] == pytest.approx(0.020 + 0.030)
    assert gaps["bench.solve"] == pytest.approx(0.012)
    assert gaps["bench.solve > device_get"] == pytest.approx(0.003)


def test_labels_and_scatters():
    assert trace.op_label("jit_scatter_add(8781)", "%fusion.3 = f32[8] "
                          "fusion(f32[8] %a), kind=kCustom") == \
        "jit_scatter_add/fusion.3"
    assert trace.is_scatter("jit_scatter_add/fusion.3", "")
    assert trace.is_scatter("jit_step/fusion", "%s = f32[4] scatter(f32[4] %a)")
    assert not trace.is_scatter("jit_gather/fusion", "%f = f32[4] fusion()")


def test_a_trace_without_a_window_or_device_is_refused():
    with pytest.raises(ValueError):
        trace.reduce([[]], [(0, 1, "bench.window")])
    with pytest.raises(ValueError):
        trace.reduce([[(0, 1, "x", False)]], [])


def test_a_trace_recorded_on_the_chip():
    """``data/small.xplane.pb``: three ``bench.request`` spans on one v5e,
    each running a jitted ``segment_sum`` and a multiply-add inside
    ``bench.solve`` and then sleeping on the host."""
    devices, spans = trace.load(DATA)
    assert len(devices) == 1 and len(devices[0]) == 6
    assert {n for _, _, n in spans} >= {"bench.window", "bench.request",
                                        "bench.solve"}
    t = trace.reduce(devices, spans)
    assert 0 < t["busy_s"] < t["window_s"] < 1
    assert t["op_s"] == pytest.approx(t["busy_s"])
    labels = [n for n, _ in t["device_ops"]]
    assert labels[0] == "jit__lambda/fusion"          # the segment_sum
    idle = sum(v for _, v in t["idle_gaps"])
    assert idle == pytest.approx(t["window_s"] - t["busy_s"], rel=1e-6)
    gaps = dict(t["idle_gaps"])
    assert gaps["bench.request"] > 0.01               # the host's sleeps
    assert any(n.startswith("bench.solve > PjitFunction") for n in gaps)
