"""Graph-Laplacian level container.

Every multigrid level is represented by the *adjacency* of its graph (padded
COO, both edge directions, positive weights) plus the weighted degree vector.
The Laplacian is never materialised: L = diag(deg) − A, and every level
produced by the paper's two coarsening mechanisms (Schur-complement
elimination on an independent set; unsmoothed-aggregation contraction) is
again exactly of this form — Laplacians are closed under both operations
(row sums stay zero, off-diagonals stay ≤ 0). Tests assert this invariant.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import obs
from repro.sparse import matvec as matvec_ops
from repro.sparse.coo import COO, row_sums, spmv, degrees
from repro.sparse.ell import ELL


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GraphLevel:
    """One multigrid level: adjacency + degrees of a weighted graph.

    ``ell``/``ell_rem`` are an optional hybrid ELL+COO twin of ``adj``,
    attached at setup time (``core.hierarchy.attach_ell_transfers``) when
    the solver runs with ``matvec_backend != "coo"``. The twin changes the
    *execution format* of the hot-loop SpMV only — ``adj`` stays the
    source of truth for setup, coarsening, and stats. ``ell_mode`` records
    whether the twin executes through the Pallas kernels or the jnp
    reference (see ``repro.sparse.matvec.resolve_ell_mode``).
    """

    adj: COO          # symmetric adjacency, off-diagonal, w > 0
    deg: jax.Array    # weighted degrees = Laplacian diagonal, [n]
    ell: ELL | None = None       # hybrid twin: fixed-width part
    ell_rem: COO | None = None   # hybrid twin: spill remainder (None = empty)
    ell_mode: str = dataclasses.field(default="pallas",
                                      metadata=dict(static=True))

    @property
    def n(self) -> int:
        return self.adj.n_rows

    def laplacian_matvec(self, x: jax.Array) -> jax.Array:
        """L @ x = deg ⊙ x − A @ x (dispatches through repro.sparse.matvec),
        inside a ``repro.spmv`` span."""
        with obs.span("repro.spmv"):
            return matvec_ops.laplacian_matvec(self, x)

    def unweighted_degrees(self) -> jax.Array:
        return degrees(self.adj)


def graph_from_adjacency(adj: COO) -> GraphLevel:
    return GraphLevel(adj=adj, deg=row_sums(adj))


def pow2_bucket(n: int, floor: int = 0) -> int:
    """Round up to the next power of two, with an optional floor.

    The shared capacity-bucket rule: hierarchy level capacities, the setup
    super-step padding shapes (``repro.core.setup_step``) and the
    internally padded strength/λmax reductions all use it, so the eager
    and super-step setup paths compute over identical shapes.
    """
    import math

    b = 1 << max(int(math.ceil(math.log2(max(n, 1)))), 0)
    return max(b, floor, 1)


def laplacian_dense(level: GraphLevel) -> jax.Array:
    """Dense L (tests / coarsest solve only)."""
    return jnp.diag(level.deg) - level.adj.to_dense()


def hash32(x: jax.Array) -> jax.Array:
    """splitmix-style avalanche hash of vertex ids (uint32).

    Alg 1 eliminates the min-*hash* candidate in each neighbourhood instead
    of the min-id, so that sequential vertex orderings don't serialise chain
    elimination (paper Fig 2). Deterministic across devices by construction.
    """
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x
