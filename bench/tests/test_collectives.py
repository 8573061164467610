"""The exposed-collective reduction (``bench/collectives.py``)."""

import os

import pytest

from conftest import BENCH

collectives = pytest.importorskip("run").module("collectives")
DATA = os.path.join(BENCH, "tests", "data", "small.xplane.pb")
MS = 1e6
C, O, LOOP = collectives.COLLECTIVE, collectives.OTHER, "control"


def test_opcodes_and_names_that_are_collectives():
    yes = [
        "%all-reduce.9 = (f32[343084]{0:T(1024)}, f32[1,343083]{1,0}) "
        "all-reduce(f32[343084]{0:T(1024)} %a, f32[1,343083]{1,0} %b)",
        "%psum_invariant.65 = f32[1,646364]{1,0:T(1,128)S(1)} "
        "all-reduce(f32[1,646364]{1,0:T(1,128)S(1)} %reshape.3)",
        "%all-gather-start.2 = (f32[8]{0}, f32[32]{0}) "
        "all-gather-start(f32[8]{0} %p)",
        "%collective-permute-done = f32[8]{0} "
        "collective-permute-done((f32[8]{0}, f32[8]{0}) %cp)",
        "%reduce-scatter.1 = f32[8]{0} reduce-scatter(f32[32]{0} %x)",
        "%x.4 = f32[8]{0} all-to-all(f32[8]{0} %y)",
    ]
    no = [
        "%fusion.475 = f32[8314]{0:T(1024)S(1)} fusion(s32[16777216]"
        "{0:T(1024)} %all-reduce.9, f32[8]{0} %b), kind=kLoop",
        "%while.32 = (s32[]{:T(128)}, f32[646364,1]{0,1:T(1,128)}) "
        "while((s32[]{:T(128)}, f32[646364,1]{0,1:T(1,128)}) %tuple.1)",
        "%copy-start = (s32[64]{0:T(128)S(1)}, s32[64]{0:T(128)}, "
        "u32[]{:S(2)}) copy-start(s32[64]{0:T(128)} %x.1)",
    ]
    assert {collectives.kind(h) for h in yes} == {collectives.COLLECTIVE}
    assert [collectives.kind(h) for h in no] == [
        collectives.OTHER, "control", collectives.OTHER]


def test_a_collective_under_compute_is_hidden_and_an_exposed_one_counts():
    ops = [(0, 10 * MS, O),                      # compute
           (2 * MS, 6 * MS, C),                  # all-reduce under it: 0
           (20 * MS, 30 * MS, C),                # exposed: 10 ms
           (40 * MS, 50 * MS, C),                # exposed 40-45 only
           (45 * MS, 60 * MS, O)]
    assert collectives.exposed_s(ops, 0, 100 * MS) == pytest.approx(0.015)


def test_a_loop_that_holds_a_collective_does_not_hide_it():
    ops = [(0, 100 * MS, LOOP),                  # while: encloses the rest
           (10 * MS, 40 * MS, O),                # fusion in the body
           (40 * MS, 45 * MS, C),                # exposed psum: 5 ms
           (45 * MS, 90 * MS, O),
           (80 * MS, 85 * MS, C)]                # under the fusion: 0
    assert collectives.exposed_s(ops, 0, 100 * MS) == pytest.approx(0.005)


def test_collectives_are_clipped_to_the_window():
    ops = [(-10 * MS, 10 * MS, C), (95 * MS, 120 * MS, C)]
    assert collectives.exposed_s(ops, 0, 100 * MS) == pytest.approx(0.015)


def test_a_trace_without_collectives_reads_zero():
    assert collectives.exposed_s([(0, 5 * MS, O)], 0, 10 * MS) == 0.0
    # one v5e, a jitted segment_sum and a multiply-add per request
    assert collectives.exposed_share(DATA) == 0.0


def test_a_run_without_a_trace_reads_nothing():
    assert collectives.for_record({"trace": None}) is None
