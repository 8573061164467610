"""``repro.obs``: the spans and host-fetch counts of the solve path, and
that they only observe."""

import glob
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import Problem, SolverOptions, setup
from repro.graphs.generators import grid_2d

OPTS = SolverOptions(coarsest_size=16, tol=1e-6, fallback=False)


@pytest.fixture(scope="module")
def grid_solver():
    n, rows, cols, vals = grid_2d(20, 20, weighted=True, seed=3)
    return setup(Problem.from_edges(n, rows, cols, vals), OPTS)


def _rhs(n, seed=0):
    b = np.random.default_rng(seed).standard_normal(n).astype(np.float32)
    return b - b.mean()


def _solve(solver, b, loop):
    """One solve through ``pcg_block`` (the facade) or ``pcg`` (the
    handle's single-RHS ``LaplacianSolver.solve``); returns ``(x, iters)``."""
    if loop == "pcg_block":
        x, res = solver.solve(b, tol=1e-6)
        return np.asarray(x), int(res.iters)
    x, info = solver._handle._solver.solve(b, tol=1e-6)
    assert info.converged
    return np.asarray(x), int(info.iters)


@pytest.mark.parametrize("loop", ["pcg_block", "pcg"])
def test_pcg_fetches_twice_per_iteration_and_once_before(grid_solver, loop):
    b = _rhs(grid_solver.problem.n)
    obs.reset()
    _, iters = _solve(grid_solver, b, loop)
    c = obs.counters()
    assert iters > 3
    assert c["repro.pcg"] == 1
    assert c["repro.pcg>repro.sync"] == 2 * iters + 1
    # the PCG's own fine SpMV: the initial residual, then one per iteration
    assert c["repro.pcg>repro.spmv"] == iters + 1


@pytest.mark.parametrize("loop", ["pcg_block", "pcg"])
def test_every_vcycle_level_counts_one_span_per_application(grid_solver,
                                                            loop):
    n_transfers = len(grid_solver._handle._solver.hierarchy.transfers)
    assert n_transfers >= 2
    obs.reset()
    _, iters = _solve(grid_solver, _rhs(grid_solver.problem.n, 1), loop)
    c = obs.counters()
    # applied once before the first iteration, and after every iteration
    # but the converging one
    for k in range(n_transfers):
        assert c[f"repro.vcycle.L{k}"] == iters, k
    assert c[f"repro.vcycle.L{n_transfers - 1}>repro.vcycle.coarse"] == iters
    assert f"repro.vcycle.L{n_transfers}" not in c
    if loop == "pcg_block":
        assert c["repro.solve"] == 1
        assert c["repro.solve>repro.pcg"] == 1


@pytest.mark.parametrize("loop", ["pcg_block", "pcg"])
def test_answers_are_bitwise_equal_under_a_profiler_trace(grid_solver, loop,
                                                          tmp_path):
    b = _rhs(grid_solver.problem.n, 2)
    x0, it0 = _solve(grid_solver, b, loop)
    with jax.profiler.trace(str(tmp_path)):
        x1, it1 = _solve(grid_solver, b, loop)
    assert it0 == it1
    assert np.array_equal(x0.view(np.uint32), x1.view(np.uint32))
    # and the trace holds the spans, on the host clock
    from jax.profiler import ProfileData

    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert {"repro.pcg", "repro.spmv", "repro.sync",
            "repro.vcycle.L0", "repro.vcycle.coarse"} <= names


def test_a_jitted_solve_step_records_no_span(grid_solver):
    solver = grid_solver._handle._solver
    step = jax.jit(solver.build_solve_step(n_iters=3))
    b = jax.numpy.asarray(_rhs(solver.n, 3))
    obs.reset()
    x, norms = step(b)
    jax.block_until_ready(x)
    assert not [k for k in obs.counters() if k.startswith("repro.")]


def test_setup_dispatches_each_super_step_inside_its_span():
    from repro.core import setup_step as ss

    n, rows, cols, vals = grid_2d(12, 12, weighted=True, seed=4)
    obs.reset()
    setup(Problem.from_edges(n, rows, cols, vals),
          SolverOptions(coarsest_size=16), cache=False)
    steps = ss.counters()["steps"]
    c = obs.counters()
    assert steps and ss.counters()["host_syncs"] > 0
    for name, st in steps.items():
        assert c[f"repro.setup.{name}"] == st["calls"], name
    ss.reset_counters()
    assert ss.counters() == dict(steps={}, host_syncs=0)
    assert c["repro.setup.agg"] == obs.counters()["repro.setup.agg"]


def test_span_counts_nest_per_thread_and_reset_by_prefix():
    obs.reset()
    with obs.span("a"):
        with obs.span("b"):
            pass
        done = threading.Event()

        def other():
            with obs.span("b"):       # no span is open on this thread
                done.set()

        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive() and done.is_set()
    obs.count("x.y", 3)
    assert obs.counters() == {"a": 1, "b": 2, "a>b": 1, "x.y": 3}
    assert obs.fetch(jax.numpy.float32(2.5)) == 2.5
    assert obs.fetch(jax.numpy.ones(2), 7)[1] == 7
    assert obs.counters()["repro.sync"] == 2
    obs.reset("x.")
    assert "x.y" not in obs.counters() and obs.counters()["a"] == 1
