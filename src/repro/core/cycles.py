"""Multigrid cycles over a static hierarchy (paper §3: V(2,2)-cycle).

The hierarchy is a Python list of transfer levels (static structure) and
the recursion over it runs on the host, but each level's work runs
compiled, as two XLA programs per level:

* an aggregation level: ``_descend`` (pre-smooth, residual, restriction,
  mean projection of the coarse RHS) and ``_ascend`` (prolongation,
  correction, post-smooth);
* an elimination level: ``_restrict`` and ``_prolong``;
* the dense bottom solve: ``_coarse``.

A V-cycle over T transfers is thus 2·T + 1 dispatches rather than one per
jnp primitive. Levels, transfers, λmax and the coarse inverse are program
*arguments* and only the smoother's configuration is static, so programs
are keyed by shapes: a repeated solve, or a new hierarchy of the same
shapes, compiles nothing. Each trace of a level program adds one to the
``vcycle.compiles`` counter of ``repro.obs``. Under an outer trace
(``pcg_scanned``, the dist scan, ``vmap``) the programs inline, so the
whole cycle jits into one XLA computation there — the TPU analogue of the
paper's fused MPI solve loop. ``jax.disable_jit()`` runs the same code
eagerly, primitive by primitive.

W- and K-cycles (paper §4 future work) are provided as beyond-paper
options over the same level programs; the W-cycle's second-visit residual
and the K-cycle's flexible-CG scalars run eagerly between them. The
K-cycle wraps the recursive correction in 2 steps of flexible CG, trading
the paper's dot-product concern for TPU's cheap psums.

All per-level matvecs (smoothing, residuals, W/K-cycle corrections) go
through ``GraphLevel.laplacian_matvec`` and hence the
``repro.sparse.matvec`` dispatch layer: levels carrying a hybrid ELL+COO
twin execute in fixed-width layout (the Jacobi smoother additionally takes
the fused-kernel path inside ``_smooth``); plain levels stay on COO.
"""

from __future__ import annotations

import dataclasses
import functools
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


def _level_program(*static_argnames: str):
    """``jax.jit`` a level program, static in ``static_argnames`` alone;
    each trace adds one to the ``vcycle.compiles`` counter."""
    def wrap(fn):
        @functools.wraps(fn)
        def traced(*args, **kwargs):
            obs.count("vcycle.compiles")
            return fn(*args, **kwargs)
        return jax.jit(traced, static_argnames=static_argnames)
    return wrap


@_level_program()
def _coarse(coarse_inv: jax.Array, b: jax.Array) -> jax.Array:
    x = coarse_inv @ b
    return x - jnp.mean(x)


@_level_program()
def _restrict(t: EliminationLevel, b: jax.Array) -> jax.Array:
    return t.restrict(b)


@_level_program()
def _prolong(t: EliminationLevel, x_c: jax.Array, b: jax.Array) -> jax.Array:
    return t.prolong(x_c, b)


@_level_program("sm")
def _descend(t: AggregationLevel, lam_max, b: jax.Array, *,
             sm: SmootherConfig) -> tuple:
    """Pre-smooth from x = 0, restrict the residual; returns ``(x, r_c)``
    with ``r_c`` mean-free (in range(L_c))."""
    level = t.fine
    x = _smooth(level, b, jnp.zeros_like(b), sm.pre_sweeps, sm, lam_max)
    r_c = t.restrict(b - level.laplacian_matvec(x))
    return x, r_c - jnp.mean(r_c)


@_level_program("sm")
def _ascend(t: AggregationLevel, lam_max, b: jax.Array, x: jax.Array,
            x_c: jax.Array, *, sm: SmootherConfig) -> jax.Array:
    """Add the prolonged coarse correction, then post-smooth."""
    x = x + t.prolong(x_c)
    return _smooth(t.fine, b, x, sm.post_sweeps, sm, lam_max)


def coarse_solve(coarse_inv: jax.Array, b: jax.Array) -> jax.Array:
    """Dense bottom solve via precomputed (L + α·J)⁻¹; result mean-free."""
    with obs.span("repro.vcycle.coarse"):
        return _coarse(coarse_inv, b)


def cycle(transfers: Sequence[Transfer], lam_maxes: Sequence[jax.Array],
          coarse_inv: jax.Array, b: jax.Array, cfg: CycleConfig,
          k: int = 0) -> jax.Array:
    """Apply one multigrid cycle to L_k x = b (x0 = 0). Returns x_k.

    Level ``k``'s programs, and the coarser levels, run inside a
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
        x_c = cycle(transfers, lam_maxes, coarse_inv, _restrict(t, b), cfg,
                    k + 1)
        return _prolong(t, x_c, b)

    sm = cfg.smoother
    x, r_c = _descend(t, lam_maxes[k], b, sm=sm)

    n_recurse = 1 if cfg.kind == "V" or k + 1 >= len(transfers) else 2
    if cfg.kind == "K" and k + 1 < len(transfers):
        x_c = _fcg_accelerated(transfers, lam_maxes, coarse_inv, r_c, cfg, k + 1)
    else:
        x_c = cycle(transfers, lam_maxes, coarse_inv, r_c, cfg, k + 1)
        for _ in range(n_recurse - 1):  # W-cycle second visit
            r2 = r_c - t.coarse.laplacian_matvec(x_c)
            x_c = x_c + cycle(transfers, lam_maxes, coarse_inv, r2, cfg, k + 1)

    return _ascend(t, lam_maxes[k], b, x, x_c, sm=sm)


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
