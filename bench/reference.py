"""The plain reference: a float64 graph Laplacian built on the host from the
benchmark's own edge list, and the true residual of an answer against it.

It imports nothing of the solver and takes nothing the solver made: the
matrix comes from the ``(n, rows, cols, vals)`` that ``bench/graphs.py``
generated, the right-hand side from the benchmark's own generator.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def laplacian(n, rows, cols, vals) -> sp.csr_matrix:
    """``L = D - A`` in float64, CSR, from a symmetric edge list."""
    a = sp.csr_matrix((np.asarray(vals, np.float64),
                       (np.asarray(rows, np.int64),
                        np.asarray(cols, np.int64))), shape=(n, n))
    deg = np.asarray(a.sum(axis=1)).ravel()
    return (sp.diags(deg) - a).tocsr()


def rel_residual(lap: sp.csr_matrix, b, x) -> float:
    """``||P(b - L x)|| / ||P b||`` in float64, where ``P`` removes the mean
    (the Laplacian of a connected graph is singular along the constants;
    a solution is defined up to a constant and ``b`` must be mean-free).
    A non-finite answer reads as ``inf``."""
    b = np.asarray(b, np.float64)
    x = np.asarray(x, np.float64)
    if x.shape != b.shape or not np.all(np.isfinite(x)):
        return float("inf")
    b = b - b.mean(axis=0)
    r = b - lap @ x
    r = r - r.mean(axis=0)
    return float(np.linalg.norm(r) / np.linalg.norm(b))
