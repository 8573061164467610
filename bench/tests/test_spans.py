"""The reduction from a profiler trace to the program's own layers
(``bench/spans.py``) and the readers of its five quantities."""

import json
import math
import os

import pytest

from conftest import BENCH

import spans

DATA = os.path.join(BENCH, "tests", "data")
MS = 1e6
READERS = ("host_syncs_per_iter", "spmv_device_ms", "vcycle_device_ms",
           "vcycle_idle_share", "programs_per_iter")


def _read(name, summary, iters, monkeypatch):
    import run

    monkeypatch.setattr(spans, "for_record", lambda record: (summary, iters))
    return run.module("metrics", name).read({})


def _synthetic():
    """A solve whose PCG holds one V-cycle level with one SpMV, and a host
    fetch inside and one outside the PCG; four programs, launched in the
    SpMV, the level, the PCG and outside every span."""
    events = [(0, 100 * MS, "bench.window"),
              (0, 90 * MS, "repro.solve"),
              (5 * MS, 85 * MS, "repro.pcg"),
              (10 * MS, 40 * MS, "repro.vcycle.L0"),
              (20 * MS, 30 * MS, "repro.spmv"),
              (50 * MS, 52 * MS, "repro.sync"),
              (86 * MS, 87 * MS, "repro.sync")]
    enqueues = {1: 22 * MS, 2: 12 * MS, 3: 60 * MS, 4: 95 * MS}
    modules = [(23 * MS, 25 * MS, 1), (35 * MS, 38 * MS, 2),
               (60 * MS, 61 * MS, 3), (95 * MS, 96 * MS, 4)]
    ops = [(s, e) for s, e, _ in modules]
    return [(modules, ops)], enqueues, events


def test_programs_go_to_the_innermost_span_open_at_their_launch():
    s = spans.reduce(*_synthetic())
    assert s["offset_ms"] == 0.0 and s["pairs"] == 4
    pcg = "repro.solve > repro.pcg"
    assert s["device_s"] == {
        f"{pcg} > repro.vcycle.L0 > repro.spmv": pytest.approx(0.002),
        f"{pcg} > repro.vcycle.L0": pytest.approx(0.003),
        pcg: pytest.approx(0.001),
        "": pytest.approx(0.001)}
    assert s["op_s"] == pytest.approx(0.007)
    assert s["attributed_s"] == pytest.approx(0.006)
    assert s["programs"] == {f"{pcg} > repro.vcycle.L0 > repro.spmv": 1,
                             f"{pcg} > repro.vcycle.L0": 1, pcg: 1, "": 1}
    assert s["opened"][f"{pcg} > repro.sync"] == 1
    assert s["opened"]["repro.solve > repro.sync"] == 1
    # idle while the level itself was innermost: 10-20 ms, and 30-40 ms
    # less the program at 35-38 ms
    assert s["idle_s"]["repro.vcycle.L0"] == pytest.approx(0.017)
    assert sum(s["idle_s"].values()) == pytest.approx(0.1 - 0.007)


def test_the_readers_of_the_five_quantities(monkeypatch):
    s = spans.reduce(*_synthetic())
    got = {name: _read(name, s, 2, monkeypatch) for name in READERS}
    assert got == dict(host_syncs_per_iter=0.5,
                       spmv_device_ms=pytest.approx(1.0),
                       vcycle_device_ms=pytest.approx(2.5),
                       vcycle_idle_share=pytest.approx(17.0),
                       programs_per_iter=1.5)


def test_a_device_plane_shifted_early_is_put_back_after_its_launch():
    """Programs start 0-40 us after their enqueue; the device clock reads
    1.2 ms early. Unshifted, the program launched in the SpMV would seem to
    run before it."""
    planes, enqueues, events = _synthetic()
    lags = {1: 0.0, 2: 40e3, 3: 5e3, 4: 12e3}
    modules = [(enqueues[r] + lags[r] - 1.2 * MS,
                enqueues[r] + lags[r] - 1.2 * MS + (e - s), r)
               for s, e, r in planes[0][0]]
    ops = [(s, e) for s, e, _ in modules]
    s = spans.reduce([(modules, ops)], enqueues, events)
    assert s["offset_ms"] == pytest.approx(1.2)
    offset, pairs = spans.clock_offset([(modules, ops)], enqueues)
    assert pairs == 4
    assert all(m[0] + offset >= enqueues[m[2]] for m in modules)
    spmv = "repro.solve > repro.pcg > repro.vcycle.L0 > repro.spmv"
    assert s["device_s"][spmv] == pytest.approx(0.002)
    assert s["op_s"] == pytest.approx(0.007)


def test_a_trace_without_spans_reads_nothing(monkeypatch):
    """A program without ``repro.`` spans (the parent of this reduction):
    every reader returns None and none raises."""
    planes, enqueues, events = _synthetic()
    bare = [ev for ev in events if not ev[2].startswith("repro.")]
    s = spans.reduce(planes, enqueues, bare)
    monkeypatch.setattr(spans, "find", lambda window_s: "x")
    monkeypatch.setattr(spans, "summarize", lambda path: s)
    import run

    record = dict(trace=dict(window_s=0.1), requests=[dict(iters=3)])
    for name in READERS:
        assert run.module("metrics", name).read(record) is None
        assert run.module("metrics", name).read(
            dict(trace=None, requests=[])) is None


def test_the_chip_trace_pairs_every_program_by_run_id():
    """``data/small.xplane.pb`` (one v5e, six programs): the device clock
    reads 1.2 ms early against the host's enqueues."""
    planes, enqueues, _ = spans.load(os.path.join(DATA, "small.xplane.pb"))
    (modules, _), = planes
    assert len(modules) == 6 and all(m[2] in enqueues for m in modules)
    offset, pairs = spans.clock_offset(planes, enqueues)
    assert pairs == 6
    assert 1.19 <= offset / MS <= 1.26
    assert all(s + offset >= enqueues[r] for s, _, r in modules)
    assert sum(s + offset == enqueues[r] for s, _, r in modules) == 1


def test_a_chip_trace_of_the_solver_with_its_spans(monkeypatch):
    """``data/spans.xplane.pb`` (``record_spans_trace.py`` on one v5e): three
    PCG iterations of a solve on an 8 x 8 grid, 956 device programs."""
    path = os.path.join(DATA, "spans.xplane.pb")
    meta = json.load(open(os.path.join(DATA, "spans.json")))
    assert os.path.getsize(path) < 2 * 2**20
    s = spans.summarize(path)
    iters = sum(meta["iters"])
    assert s["pairs"] == s["modules"] > 0
    assert 0 < s["offset_ms"] < 5
    assert s["attributed_s"] >= 0.99 * s["op_s"] > 0
    got = {name: _read(name, s, iters, monkeypatch) for name in READERS}
    assert all(math.isfinite(v) and v > 0 for v in got.values()), got
    assert got["host_syncs_per_iter"] == (2 * iters + 1) / iters
    monkeypatch.undo()
    monkeypatch.setattr(spans, "find", lambda window_s: path)
    record = dict(trace=dict(window_s=s["window_s"]),
                  requests=[dict(iters=i) for i in meta["iters"]])
    import run

    assert run.module("metrics", "programs_per_iter").read(record) == \
        got["programs_per_iter"]
