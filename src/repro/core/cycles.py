"""Multigrid cycles over a static hierarchy (paper §3: V(2,2)-cycle).

The hierarchy is a Python list of transfer levels (static structure), so the
recursion unrolls at trace time and the whole cycle jits into one XLA
computation — the TPU analogue of the paper's fused MPI solve loop. W- and
K-cycles (paper §4 future work) are provided as beyond-paper options: the
K-cycle wraps the recursive correction in 2 steps of flexible CG, trading the
paper's dot-product concern for TPU's cheap psums.

All per-level matvecs (smoothing, residuals, W/K-cycle corrections) go
through ``GraphLevel.laplacian_matvec`` and hence the
``repro.sparse.matvec`` dispatch layer: levels carrying a hybrid ELL+COO
twin execute in fixed-width layout (the Jacobi smoother additionally takes
the fused-kernel path inside ``_smooth``); plain levels stay on COO.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence, Union

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.coarsen import AggregationLevel
from repro.core.elimination import EliminationLevel
from repro.core.graph import GraphLevel
from repro.core.smoothers import SmootherConfig, chebyshev, jacobi

Transfer = Union[EliminationLevel, AggregationLevel]


@dataclasses.dataclass(frozen=True)
class CycleConfig:
    kind: str = "V"               # "V" | "W" | "K"
    smoother: SmootherConfig = SmootherConfig()
    k_cycle_steps: int = 2


def _smooth(level: GraphLevel, b, x, sweeps: int, cfg: SmootherConfig, lam_max):
    if sweeps == 0:
        return x
    if cfg.kind == "chebyshev":
        return chebyshev(level, b, x, lam_max, degree=cfg.cheby_degree * sweeps // 2
                         if sweeps > 1 else cfg.cheby_degree)
    return jacobi(level, b, x, n_sweeps=sweeps, omega=cfg.omega)


def coarse_solve(coarse_inv: jax.Array, b: jax.Array) -> jax.Array:
    """Dense bottom solve via precomputed (L + α·J)⁻¹; result mean-free."""
    with obs.span("repro.vcycle.coarse"):
        x = coarse_inv @ b
        return x - jnp.mean(x)


def cycle(transfers: Sequence[Transfer], lam_maxes: Sequence[jax.Array],
          coarse_inv: jax.Array, b: jax.Array, cfg: CycleConfig,
          k: int = 0) -> jax.Array:
    """Apply one multigrid cycle to L_k x = b (x0 = 0). Returns x_k.

    Level ``k``'s work, the coarser levels' included, runs inside a
    ``repro.vcycle.L<k>`` span."""
    if k == len(transfers):
        return coarse_solve(coarse_inv, b)
    with obs.span(f"repro.vcycle.L{k}"):
        return _cycle_level(transfers, lam_maxes, coarse_inv, b, cfg, k)


def _cycle_level(transfers, lam_maxes, coarse_inv, b, cfg: CycleConfig,
                 k: int) -> jax.Array:
    t = transfers[k]
    if isinstance(t, EliminationLevel):
        # Exact elimination: no smoothing needed on this level (Schur).
        b_c = t.restrict(b)
        x_c = cycle(transfers, lam_maxes, coarse_inv, b_c, cfg, k + 1)
        return t.prolong(x_c, b)

    level = t.fine
    sm = cfg.smoother
    x = jnp.zeros_like(b)
    x = _smooth(level, b, x, sm.pre_sweeps, sm, lam_maxes[k])
    r = b - level.laplacian_matvec(x)
    r_c = t.restrict(r)
    r_c = r_c - jnp.mean(r_c)  # keep coarse RHS in range(L_c)

    n_recurse = 1 if cfg.kind == "V" or k + 1 >= len(transfers) else 2
    if cfg.kind == "K" and k + 1 < len(transfers):
        x_c = _fcg_accelerated(transfers, lam_maxes, coarse_inv, r_c, cfg, k + 1)
    else:
        x_c = cycle(transfers, lam_maxes, coarse_inv, r_c, cfg, k + 1)
        for _ in range(n_recurse - 1):  # W-cycle second visit
            r2 = r_c - t.coarse.laplacian_matvec(x_c)
            x_c = x_c + cycle(transfers, lam_maxes, coarse_inv, r2, cfg, k + 1)

    x = x + t.prolong(x_c)
    x = _smooth(level, b, x, sm.post_sweeps, sm, lam_maxes[k])
    return x


def _fcg_accelerated(transfers, lam_maxes, coarse_inv, b, cfg: CycleConfig, k: int):
    """K-cycle inner acceleration: ``k_cycle_steps`` of flexible CG whose
    preconditioner is the (k+1)-level cycle (Notay's K-cycle, DRA-style)."""
    level = transfers[k].fine if k < len(transfers) else None
    matvec = (level.laplacian_matvec if level is not None
              else (lambda v: v))
    x = jnp.zeros_like(b)
    r = b
    d_prev = None
    for _ in range(cfg.k_cycle_steps):
        z = cycle(transfers, lam_maxes, coarse_inv, r, cfg, k)
        d = z
        if d_prev is not None:
            Ad_prev = matvec(d_prev)
            beta = jnp.vdot(z, Ad_prev) / jnp.maximum(jnp.vdot(d_prev, Ad_prev), 1e-30)
            d = z - beta * d_prev
        Ad = matvec(d)
        alpha = jnp.vdot(r, d) / jnp.maximum(jnp.vdot(d, Ad), 1e-30)
        x = x + alpha * d
        r = r - alpha * Ad
        d_prev = d
    return x
