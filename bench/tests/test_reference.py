"""The float64 reference against a direct solve of a grounded system."""

import numpy as np
import scipy.sparse.linalg as spla

import graphs
import reference


def _grounded_solution(lap, b):
    """Solve ``L x = b`` with vertex 0 grounded (x0 = 0)."""
    x = np.zeros(lap.shape[0])
    x[1:] = spla.spsolve(lap[1:, 1:].tocsc(), b[1:])
    return x


def test_laplacian_rows_sum_to_zero_and_match_the_edges():
    g = graphs.grid_2d(5, 4, weighted=True, seed=1)
    lap = reference.laplacian(*g)
    np.testing.assert_allclose(np.asarray(lap.sum(axis=1)).ravel(), 0,
                               atol=1e-12)
    n, rows, cols, vals = g
    dense = np.zeros((n, n))
    dense[rows, cols] = -vals
    np.fill_diagonal(dense, -dense.sum(axis=1))
    np.testing.assert_allclose(lap.toarray(), dense, rtol=1e-7)


def test_direct_solution_reads_round_off_and_a_wrong_one_does_not():
    g = graphs.largest_component(*graphs.rmat(9, 16, 3, weighted=True))
    lap = reference.laplacian(*g)
    b = np.random.default_rng(0).standard_normal(g[0])
    b -= b.mean()
    x = _grounded_solution(lap, b)
    assert reference.rel_residual(lap, b, x) < 1e-10
    # a constant shift is no error: the solution is defined up to it
    assert reference.rel_residual(lap, b, x + 3.0) < 1e-10
    y = x.copy()
    y[np.argmax(np.abs(y))] *= -1
    assert reference.rel_residual(lap, b, y) > 1e-2
    assert reference.rel_residual(lap, b, np.zeros_like(x)) == 1.0


def test_non_finite_or_misshapen_answers_read_inf():
    g = graphs.grid_2d(3, 3)
    lap = reference.laplacian(*g)
    b = np.arange(9.0) - 4.0
    x = np.zeros(9)
    x[2] = np.nan
    assert reference.rel_residual(lap, b, x) == float("inf")
    assert reference.rel_residual(lap, b, np.zeros(8)) == float("inf")
