"""The traffic generator, driven directly on the CPU at a tiny size."""

import numpy as np
import pytest

import load
from conftest import tiny_cell


def _loop(name, seed=5):
    cell = tiny_cell(name)
    return load.ClosedLoop(cell.config, cell.traffic, seed, load.Spans())


def test_rhs_is_mean_free_and_drawn_from_the_seed():
    a = load.rhs(100, 1, load.stream(2**33 + 1, load.REQUEST_RHS, 3))
    b = load.rhs(100, 1, load.stream(2**33 + 1, load.REQUEST_RHS, 3))
    c = load.rhs(100, 1, load.stream(2**33 + 1, load.REQUEST_RHS, 4))
    assert a.dtype == np.float32 and a.shape == (100,)
    assert abs(float(a.mean())) < 1e-6
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert load.rhs(50, 3, load.stream(1, 0)).shape == (50, 3)


@pytest.mark.parametrize("name", ["rmat19.solve", "grid256.solve"])
def test_solve_traffic_serves_one_graph(name):
    loop = _loop(name)
    loop.prepare()
    r0, r1 = loop.request(0), loop.request(1)
    assert r0["graph"] is r1["graph"] is loop.graph
    assert not np.array_equal(r0["b"], r1["b"])
    for r in (r0, r1):
        assert r["error"] is None and r["status"] == "converged"
        assert r["x"].shape == r["b"].shape and r["iters"] > 0
        assert r["t1"] > r["t0"] and r["solve_s"] == r["t1"] - r["t0"]
    names = [s[0] for s in loop.spans.records]
    assert names.count("bench.hierarchy") == 1
    assert names.count("bench.solve") == 3         # warm-up + 2 requests


@pytest.mark.parametrize("name", ["rmat19.solve", "grid256.solve"])
def test_the_graph_is_the_configuration_s_and_the_rhs_the_seed_s(name):
    a, b = _loop(name, 9), _loop(name, 10)
    a.prepare()
    b.prepare()
    for x, y in zip(a.graph[1:], b.graph[1:]):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.request(0)["b"], b.request(0)["b"])
    np.testing.assert_array_equal(
        a.request(0)["b"],
        load.rhs(a.graph[0], 1, load.stream(9, load.REQUEST_RHS, 0)))
