"""The benchmark's own graph generators (host-side numpy).

Copies of ``rmat``, ``grid_2d``, ``_dedup_sym`` and ``largest_component``
as the solver's ``repro.graphs.generators`` had them when the benchmark was
written, kept here so that a change to the program's generators cannot
change the benchmark's data. Every function returns ``(n, rows, cols,
vals)`` with both edge directions present, no self loops and float32
weights. ``seed`` is anything ``numpy.random.default_rng`` accepts.
"""

from __future__ import annotations

import numpy as np


def _dedup_sym(n, u, v, w=None, rng=None):
    """Symmetrise and dedup an undirected edge list given as (u, v) pairs;
    with ``rng`` and no ``w``, weights are uniform in [0.5, 1.5)."""
    keep = u != v
    u, v = u[keep], v[keep]
    if w is not None:
        w = w[keep]
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    key = lo.astype(np.int64) * n + hi
    _, idx = np.unique(key, return_index=True)
    lo, hi = lo[idx], hi[idx]
    if w is None:
        w = np.ones(len(lo), np.float32) if rng is None else rng.uniform(
            0.5, 1.5, len(lo)).astype(np.float32)
    else:
        w = w[idx].astype(np.float32)
    rows = np.concatenate([lo, hi]).astype(np.int32)
    cols = np.concatenate([hi, lo]).astype(np.int32)
    vals = np.concatenate([w, w])
    return n, rows, cols, vals


def rmat(scale: int, edge_factor: int = 8, seed=0,
         a=0.57, b=0.19, c=0.19, weighted: bool = False):
    """R-MAT / Kronecker generator (Graph500 parameters by default)."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    n_edges = n * edge_factor
    u = np.zeros(n_edges, np.int64)
    v = np.zeros(n_edges, np.int64)
    for _ in range(scale):
        r = rng.random(n_edges)
        right = r >= a + b  # falls in c or d quadrant (row bit set)
        bottom = ((r >= a) & (r < a + b)) | (r >= a + b + c)  # col bit set
        u = (u << 1) | right.astype(np.int64)
        v = (v << 1) | bottom.astype(np.int64)
    return _dedup_sym(n, u, v, rng=rng if weighted else None)


def grid_2d(nx: int, ny: int, weighted: bool = False, seed=0):
    """4-neighbour ``nx`` × ``ny`` lattice."""
    rng = np.random.default_rng(seed)
    idx = np.arange(nx * ny).reshape(nx, ny)
    u = np.concatenate([idx[:, :-1].ravel(), idx[:-1, :].ravel()])
    v = np.concatenate([idx[:, 1:].ravel(), idx[1:, :].ravel()])
    return _dedup_sym(nx * ny, u, v, rng=rng if weighted else None)


def largest_component(n, rows, cols, vals):
    """Restrict a graph to its largest connected component, relabeled
    ``0..n'-1`` in the original vertex order."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components

    a = sp.coo_matrix((np.ones(len(rows)), (rows, cols)), shape=(n, n))
    _, labels = connected_components(a, directed=False)
    keep = labels == np.argmax(np.bincount(labels))
    new_id = np.cumsum(keep) - 1
    e = keep[rows]                     # both endpoints share a component
    return (int(keep.sum()), new_id[rows[e]].astype(np.int32),
            new_id[cols[e]].astype(np.int32), vals[e].astype(np.float32))


def build(graph: dict, seed):
    """The graph a configuration's ``graph`` block describes, from ``seed``.

    ``generator`` is ``"rmat"`` (keys ``scale``, ``edge_factor``, ``a``,
    ``b``, ``c``) or ``"grid_2d"`` (key ``side``); ``weighted`` draws the
    weights uniform in [0.5, 1.5); ``cut`` ``"largest_component"`` keeps
    the largest connected component.
    """
    kind = graph["generator"]
    if kind == "rmat":
        g = rmat(graph["scale"], graph["edge_factor"], seed, graph["a"],
                 graph["b"], graph["c"], weighted=graph["weighted"])
    elif kind == "grid_2d":
        g = grid_2d(graph["side"], graph["side"], graph["weighted"], seed)
    else:
        raise ValueError(f"unknown graph generator {kind!r}")
    if graph.get("cut") == "largest_component":
        g = largest_component(*g)
    return g
