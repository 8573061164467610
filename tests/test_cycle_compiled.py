"""The compiled multigrid cycle (``repro.core.cycles``): each level runs as
jitted programs keyed by shapes, and answers as the eager cycle does."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import Problem, SolverOptions, setup
from repro.core import CycleConfig, LaplacianSolver, SetupConfig, SmootherConfig
from repro.core.hierarchy import apply_cycle
from repro.graphs.generators import ensure_connected, grid_2d

from test_solver import GRAPHS, mean_free


@pytest.fixture(scope="module", params=list(GRAPHS))
def graph_solver(request):
    n, r, c, v = GRAPHS[request.param]()
    solver = LaplacianSolver.setup(n, r, c, v, SetupConfig(coarsest_size=64),
                                   random_ordering=False)
    b = jnp.asarray(mean_free(np.random.default_rng(21), n))
    return solver, b


@pytest.mark.parametrize("smoother", ["jacobi", "chebyshev"])
@pytest.mark.parametrize("kind", ["V", "W", "K"])
def test_compiled_cycle_matches_the_eager_cycle(graph_solver, kind, smoother):
    solver, b = graph_solver
    cfg = CycleConfig(kind=kind, smoother=SmootherConfig(kind=smoother))
    solver = dataclasses.replace(solver, cycle_config=cfg)
    z = np.asarray(apply_cycle(solver.hierarchy, b, cfg), np.float64)
    with jax.disable_jit():
        z_eager = np.asarray(apply_cycle(solver.hierarchy, b, cfg), np.float64)
    assert np.linalg.norm(z - z_eager) <= 1e-5 * np.linalg.norm(z_eager)

    _, info = solver.solve(b, tol=1e-6, maxiter=100)
    with jax.disable_jit():
        _, info_eager = solver.solve(b, tol=1e-6, maxiter=100)
    assert info.converged and info_eager.converged
    assert abs(info.iters - info_eager.iters) <= 1


def test_level_programs_are_keyed_by_shapes_alone():
    # a graph no other test builds, so its level shapes are new here
    n, r, c, v = ensure_connected(*grid_2d(23, 19, weighted=True, seed=7))
    problem = Problem.from_edges(n, r, c, v)
    opts = SolverOptions(coarsest_size=16, tol=1e-6, fallback=False)
    solver = setup(problem, opts, cache=False)
    n_transfers = len(solver._handle._solver.hierarchy.transfers)
    rng = np.random.default_rng(22)

    before = obs.counters().get("vcycle.compiles", 0)
    x0, res0 = solver.solve(mean_free(rng, n))
    first = obs.counters().get("vcycle.compiles", 0) - before
    assert res0.converged
    assert 0 < first <= 2 * n_transfers + 1

    before = obs.counters().get("vcycle.compiles", 0)
    for _ in range(2):
        _, res = solver.solve(mean_free(rng, n))
        assert res.converged
    again = setup(problem, opts, cache=False)
    _, res = again.solve(mean_free(rng, n))
    assert res.converged
    assert obs.counters().get("vcycle.compiles", 0) == before


def test_scanned_solve_step_inlines_the_level_programs():
    n, r, c, v = GRAPHS["grid"]()
    solver = LaplacianSolver.setup(n, r, c, v, SetupConfig(coarsest_size=64),
                                   random_ordering=False)
    b = jnp.asarray(mean_free(np.random.default_rng(23), n))
    x_e, info = solver.solve(b, tol=1e-6, maxiter=100)
    assert info.converged
    step = solver.build_solve_step(n_iters=info.iters)
    jaxpr = str(jax.make_jaxpr(step)(b))
    assert "_descend" in jaxpr and "_ascend" in jaxpr
    x_s, _ = jax.jit(step)(b)
    x_s, x_e = np.asarray(x_s, np.float64), np.asarray(x_e, np.float64)
    assert np.linalg.norm(x_s - x_e) <= 1e-4 * np.linalg.norm(x_e)
