"""Krylov solvers: CG and preconditioned CG (paper §3).

The paper uses its V-cycle as a PCG preconditioner ("not as powerful as
LAMG's adaptive energy correction, but dot products stay ~5% of solve time").
Jacobi-PCG is the paper's distributed baseline (Fig 3, third column).

Two execution modes:

* ``pcg``        — eager host loop with a stopping tolerance + full residual
                   history (benchmarks, WDA accounting),
* ``pcg_scanned``— fixed-iteration ``lax.scan`` body that jits into a single
                   XLA program (the distributed ``solve_step`` the multi-pod
                   dry-run lowers; no host round-trips, TPU-friendly).

Graph Laplacians are singular (nullspace = constants on connected graphs), so
residuals/preconditioned residuals are projected mean-free each iteration —
standard semidefinite-CG practice. Disconnected graphs pass a per-component
``project`` callable instead (``repro.core.components``); the default
``None`` keeps the original global-mean projection bitwise-unchanged.

**Breakdown guards** (PR 8): the eager solvers watch for the three ways PCG
dies on hostile inputs — a non-finite residual norm (NaN/Inf anywhere in the
iteration poisons it within one step), an indefinite or non-finite ``p·Ap``
(the CG invariant requires it strictly positive on a PSD operator), and a
stagnation window (no relative residual improvement for ``stagnation_window``
iterations — the "silently iterating forever" mode). A tripped guard stops
the affected solve/column with an explicit status instead of iterating on
garbage; statuses surface on ``SolveInfo.status`` / ``BlockSolveInfo.status``
and feed the ``repro.api`` degradation ladder. Guards only *observe* — on a
clean solve the iterates are bitwise identical to the unguarded loop.

The ``matvec`` callables these solvers drive are level matvecs that route
through the ``repro.sparse.matvec`` operator layer: with
``matvec_backend="ell"``/``"auto"`` every PCG iteration's SpMV executes in
hybrid ELL+COO layout (jnp under ``"auto"``, interpret-mode Pallas
kernels under ``"ell"``) instead of the
gather+segment-sum COO path — same trajectory, different execution format.
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.testing import faults

# Status codes reported by the eager solvers (SolveInfo.status and the
# per-column BlockSolveInfo.status). BREAKDOWN_STATUSES are the ones the
# repro.api degradation ladder reacts to; "max_iters" is an honest
# non-convergence, not a breakdown.
STATUS_CONVERGED = "converged"
STATUS_MAX_ITERS = "max_iters"
STATUS_NONFINITE = "breakdown_nonfinite"
STATUS_INDEFINITE = "breakdown_indefinite"
STATUS_STAGNATION = "stagnation"
# Silent-data-corruption codes (PR 10): "sdc_spmv" = the in-flight ABFT
# checksum (SolverOptions verify="cheap"/"paranoid") caught a hot-path
# SpMV whose output violates the Laplacian column-sum identity;
# "sdc_certificate" = the solve *claimed* convergence but the independent
# float64 residual certificate refused to certify it. Both are breakdowns:
# the degradation ladder treats a detected-corrupt column exactly like an
# indefinite one (frozen at the last trusted iterate, re-solved on the
# next rung).
STATUS_SDC = "sdc_spmv"
STATUS_SDC_CERT = "sdc_certificate"

BREAKDOWN_STATUSES = frozenset(
    {STATUS_NONFINITE, STATUS_INDEFINITE, STATUS_STAGNATION,
     STATUS_SDC, STATUS_SDC_CERT})

# Device-side status codes for the scanned/dist solve path (PR 9): the
# in-scan guards carry one int32 per column through the scan instead of
# host strings. 0 = still healthy (resolved host-side into converged /
# max_iters from the final norms); nonzero = the guard that froze the
# column. Kept disjoint from 1 so a future "converged-in-scan" lane can
# take it without renumbering.
SCAN_OK = 0
SCAN_NONFINITE = 2
SCAN_INDEFINITE = 3
SCAN_STAGNATION = 4
SCAN_SDC = 5

_SCAN_CODE_STATUS = {
    SCAN_NONFINITE: STATUS_NONFINITE,
    SCAN_INDEFINITE: STATUS_INDEFINITE,
    SCAN_STAGNATION: STATUS_STAGNATION,
    SCAN_SDC: STATUS_SDC,
}


def is_breakdown(status: str) -> bool:
    return status in BREAKDOWN_STATUSES


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Breakdown-guard policy for the eager PCG loops.

    ``stagnation_window`` iterations without the best residual improving
    by a relative ``stagnation_rtol`` trips the stagnation guard. The
    window is deliberately generous: a healthy PCG trajectory (even an
    unpreconditioned one on a hard graph) improves far more than 0.01%
    per 50 iterations, while a float32 solve pushed past its attainable
    accuracy flatlines exactly.
    """

    stagnation_window: int = 50
    stagnation_rtol: float = 1e-4


def _as_guard(guard) -> GuardConfig | None:
    if guard is None or guard is False:
        return None
    if guard is True:
        return GuardConfig()
    return guard


@dataclasses.dataclass
class SolveInfo:
    iters: int
    residual_norms: list
    converged: bool
    status: str = STATUS_MAX_ITERS


@dataclasses.dataclass
class BlockSolveInfo:
    """Per-column info for a blocked multi-RHS solve (``pcg_block``)."""

    iters: np.ndarray           # int64 [k] — iterations each column ran
    residual_norms: np.ndarray  # float [T+1, k] — lockstep residual history
    converged: np.ndarray       # bool [k]
    status: np.ndarray | None = None   # str [k] — per-column status codes


def _project(v):
    return v - jnp.mean(v)


@obs.spanned("repro.pcg")
def pcg(matvec: Callable, b: jax.Array, precond: Callable | None = None,
        x0: jax.Array | None = None, tol: float = 1e-8, maxiter: int = 500,
        project: Callable | None = None, guard=True, check=None):
    """Eager PCG with residual history. Returns (x, SolveInfo).

    ``project`` overrides the nullspace projection (default: global mean
    subtraction — connected graphs). ``guard`` enables the breakdown
    guards (bool or a :class:`GuardConfig`); they only observe, so clean
    solves are bitwise-identical with guards on or off. ``check`` is an
    optional ABFT checksum ``check(p, Ap) -> bool`` (see
    ``repro.core.verify.make_check``): a mismatch freezes the solve at the
    last trusted iterate with status ``"sdc_spmv"``. The check is fetched
    fused with ``p·Ap``, and like the guards it only observes.
    """
    proj = _project if project is None else project
    g = _as_guard(guard)
    b = proj(b)
    x = jnp.zeros_like(b) if x0 is None else x0
    r = proj(b - matvec(x))
    M = precond if precond is not None else (lambda v: v)
    z = proj(faults.site("solve.precond", M(r)))
    p = z
    rz = jnp.vdot(r, z)
    r0n = float(obs.fetch(jnp.linalg.norm(r)))
    hist = [r0n]
    if r0n == 0:
        return x, SolveInfo(0, hist, True, STATUS_CONVERGED)
    if g is not None and not math.isfinite(r0n):
        return x, SolveInfo(0, hist, False, STATUS_NONFINITE)
    best, stall = r0n, 0
    for it in range(maxiter):
        Ap = faults.site("solve.spmv", matvec(p))
        pAp = jnp.vdot(p, Ap)
        if check is not None:
            pApf, bad = obs.fetch(pAp, check(p, Ap))
            if bool(bad):
                # checksum mismatch: this Ap can't be trusted, freeze x at
                # the last trusted iterate before the poisoned update
                return x, SolveInfo(it, hist, False, STATUS_SDC)
            if g is not None:
                pApf = float(pApf)
                if not math.isfinite(pApf) or pApf <= 0.0:
                    return x, SolveInfo(it, hist, False, STATUS_INDEFINITE)
        elif g is not None:
            pApf = float(obs.fetch(pAp))
            if not math.isfinite(pApf) or pApf <= 0.0:
                # stop BEFORE applying the poisoned step: x is the last
                # finite iterate, not a NaN field
                return x, SolveInfo(it, hist, False, STATUS_INDEFINITE)
        alpha = rz / pAp
        x = x + alpha * p
        r = proj(faults.site("solve.residual", r - alpha * Ap))
        rn = float(obs.fetch(jnp.linalg.norm(r)))
        hist.append(rn)
        if rn <= tol * r0n:
            return x, SolveInfo(it + 1, hist, True, STATUS_CONVERGED)
        if g is not None:
            if not math.isfinite(rn):
                return x, SolveInfo(it + 1, hist, False, STATUS_NONFINITE)
            if rn < best * (1.0 - g.stagnation_rtol):
                best, stall = rn, 0
            else:
                stall += 1
                if stall >= g.stagnation_window:
                    return x, SolveInfo(it + 1, hist, False,
                                        STATUS_STAGNATION)
        z = proj(faults.site("solve.precond", M(r)))
        rz_new = jnp.vdot(r, z)
        beta = rz_new / rz
        p = z + beta * p
        rz = rz_new
    return x, SolveInfo(maxiter, hist, False, STATUS_MAX_ITERS)


@obs.spanned("repro.pcg")
def pcg_block(matvec: Callable, B: jax.Array, precond: Callable | None = None,
              tol: float = 1e-8, maxiter: int = 500,
              exact_columns: bool = True, x0: jax.Array | None = None,
              project: Callable | None = None, guard=True, check=None):
    """Blocked multi-RHS PCG: k single-RHS trajectories advanced in lockstep.

    ``B`` is ``(n, k)`` — one graph, many right-hand sides (the serving
    scenario: the hierarchy is built once, every column reuses it). ``matvec``
    and ``precond`` act on single length-n vectors and are lifted over the
    columns; all k solves share one iteration loop, one convergence check per
    iteration, and of course one setup.

    ``exact_columns=True`` (default) lifts the operators by a trace-time loop
    over columns and computes every scalar reduction (means, dots, norms)
    with the same 1-D primitives ``pcg`` uses, making each column's iterates
    — and the returned solutions — bitwise identical to standalone ``pcg``
    solves. ``exact_columns=False`` lifts with ``jax.vmap`` instead: the SpMV
    and V-cycle run as single batched ops (the throughput path), at the cost
    of low-bit drift from the single-RHS trajectories (XLA reduces 1-D arrays
    and 2-D columns in different orders).

    Columns converge independently: once a column's residual drops below
    ``tol * ||r0||`` its step size is zeroed (x, r freeze) while the rest
    keep iterating; the loop exits when every column has converged. The
    breakdown guards (``guard``) work the same way per column: a column
    whose residual goes non-finite, whose ``p·Ap`` stops being positive, or
    whose residual stagnates freezes with its own status code while the
    healthy columns keep iterating — one poisoned request cannot take down
    a batched block.

    ``tol`` and ``maxiter`` accept a scalar or a per-column ``(k,)`` array
    (the serving layer batches requests with different tolerances into one
    block). Scalars keep the exact pre-existing trajectory; with arrays a
    column also freezes once it has run its own ``maxiter[j]`` rounds.

    ``x0`` is an optional ``(n, k)`` block of per-column initial guesses
    (LOBPCG inner refinement, incremental embeddings). Mirroring ``pcg``,
    it is used as-is — no nullspace projection: any constant component
    survives into the returned ``X`` (the Laplacian cannot see it).
    ``x0=None`` starts from zeros and is bitwise-identical to the
    pre-``x0`` behavior.

    ``project`` overrides the per-column nullspace projection (a single-
    vector callable, lifted over columns the same way the operators are).

    ``check`` is an optional per-column ABFT checksum
    ``check(P, Ap) -> bool[k]`` (``repro.core.verify.make_check``): a
    flagged column freezes at its last trusted iterate with status
    ``"sdc_spmv"`` while healthy columns keep iterating, mirroring the
    breakdown-guard freeze semantics. The check result rides the existing
    per-iteration device fetch.

    Returns ``(X, BlockSolveInfo)`` with per-column iteration counts,
    converged flags, status codes, and the (T+1, k) residual history (rows
    beyond a column's own convergence hold its frozen residual norm).
    """
    B = jnp.asarray(B)
    if B.ndim != 2:
        raise ValueError(f"pcg_block expects B of shape (n, k), got {B.shape}")
    k = B.shape[1]
    g = _as_guard(guard)
    # Per-column tol/maxiter: scalars pass through untouched (bitwise-stable
    # trajectories); arrays must be (k,) and act elementwise below.
    if np.ndim(tol):
        tol = np.asarray(tol)
        if tol.shape != (k,):
            raise ValueError(f"per-column tol must have shape ({k},), "
                             f"got {tol.shape}")
    if np.ndim(maxiter):
        maxiter = np.asarray(maxiter, np.int64)
        if maxiter.shape != (k,):
            raise ValueError(f"per-column maxiter must have shape ({k},), "
                             f"got {maxiter.shape}")
        n_rounds = int(maxiter.max(initial=0))
    else:
        n_rounds = maxiter
    M = precond if precond is not None else (lambda v: v)
    if exact_columns:
        # Eager column loops have no fixed-shape constraint, so frozen
        # columns skip their SpMV/V-cycle entirely (their outputs only ever
        # meet zeroed alphas / stale-Z selects).
        def bmv(V, act):
            return jnp.stack([matvec(V[:, j]) if act[j]
                              else jnp.zeros_like(V[:, j])
                              for j in range(k)], axis=1)

        def bM(V, act):
            return jnp.stack([M(V[:, j]) if act[j]
                              else jnp.zeros_like(V[:, j])
                              for j in range(k)], axis=1)
    else:
        _bmv = jax.vmap(matvec, in_axes=1, out_axes=1)
        _bM = jax.vmap(M, in_axes=1, out_axes=1)

        def bmv(V, act):
            return _bmv(V)

        def bM(V, act):
            return _bM(V)

    def cmean(V):
        return jnp.stack([jnp.mean(V[:, j]) for j in range(k)])

    if project is None:
        def proj(V):
            return V - cmean(V)[None, :]
    elif exact_columns:
        def proj(V):
            return jnp.stack([project(V[:, j]) for j in range(k)], axis=1)
    else:
        _bproj = jax.vmap(project, in_axes=1, out_axes=1)

        def proj(V):
            return _bproj(V)

    def cdot(U, V):
        return jnp.stack([jnp.vdot(U[:, j], V[:, j]) for j in range(k)])

    def cnorm(V):
        return jnp.stack([jnp.linalg.norm(V[:, j]) for j in range(k)])

    all_cols = np.ones(k, bool)
    B = proj(B)
    if x0 is None:
        X = jnp.zeros_like(B)
    else:
        X = jnp.asarray(x0, B.dtype)
        if X.shape != B.shape:
            raise ValueError(f"x0 must match B's shape {B.shape}, "
                             f"got {X.shape}")
    R = proj(B - bmv(X, all_cols))
    Z = proj(faults.site("solve.precond", bM(R, all_cols)))
    P = Z
    rz = cdot(R, Z)
    r0n = np.asarray(obs.fetch(cnorm(R)))
    hist = [r0n]
    status = np.full(k, "", dtype="<U24")
    if x0 is None:
        # bitwise-pinned pre-x0 path: tolerance relative to the initial
        # residual, which IS ||proj b|| when starting from zeros. NB the
        # done-test is written so a NaN r0n stays ACTIVE (every comparison
        # with NaN is False) and falls through to the guard below.
        ref = r0n
        done0 = r0n == 0.0
    else:
        # warm starts measure against ||proj b|| (scipy's convention): a
        # column whose guess is already converged runs zero iterations
        # instead of chasing tol times its own tiny initial residual
        ref = np.asarray(obs.fetch(cnorm(B)))
        done0 = r0n <= tol * ref
    status[done0] = STATUS_CONVERGED
    active = ~done0
    if g is not None:
        dead = active & ~np.isfinite(r0n)
        if dead.any():
            status[dead] = STATUS_NONFINITE
            active = active & ~dead
    best = np.where(np.isfinite(r0n), r0n, np.inf)
    stall = np.zeros(k, np.int64)
    iters = np.zeros(k, np.int64)
    for _ in range(n_rounds):
        active = active & (iters < maxiter)
        if not active.any():
            break
        Ap = faults.site("solve.spmv", bmv(P, active))
        pAp = cdot(P, Ap)
        pApf = None
        if check is not None:
            # one fused fetch covers both the checksum verdict and (when
            # guarded) the p·Ap read the indefinite guard needs anyway
            pApf, sdc = obs.fetch(pAp, check(P, Ap))
            bad = active & np.asarray(sdc)
            if bad.any():
                # checksum mismatch: freeze the flagged columns at their
                # last trusted iterate; healthy columns keep iterating
                status[bad] = STATUS_SDC
                active = active & ~bad
                if not active.any():
                    break
        if g is not None:
            pApf = np.asarray(obs.fetch(pAp) if pApf is None else pApf)
            bad = active & (~np.isfinite(pApf) | (pApf <= 0.0))
            if bad.any():
                # freeze the broken columns BEFORE the update: their x stays
                # the last finite iterate while healthy columns continue
                status[bad] = STATUS_INDEFINITE
                active = active & ~bad
                if not active.any():
                    break
        act = jnp.asarray(active)
        iters += active
        alpha = jnp.where(act, rz / pAp, 0.0)
        X = X + alpha[None, :] * P
        # Freeze converged columns exactly: re-projecting them every
        # iteration would keep shaving off the ~eps nullspace leak and
        # drift their (already reported) residuals.
        R = jnp.where(act[None, :],
                      proj(faults.site("solve.residual",
                                       R - alpha[None, :] * Ap)), R)
        rn = np.asarray(obs.fetch(cnorm(R)))
        hist.append(rn)
        just_done = active & (rn <= tol * ref)
        status[just_done] = STATUS_CONVERGED
        active = active & ~just_done
        if g is not None:
            dead = active & ~np.isfinite(rn)
            if dead.any():
                status[dead] = STATUS_NONFINITE
                active = active & ~dead
            improved = active & (rn < best * (1.0 - g.stagnation_rtol))
            best = np.where(improved, rn, best)
            stall = np.where(improved, 0, stall + active)
            stalled = active & (stall >= g.stagnation_window)
            if stalled.any():
                status[stalled] = STATUS_STAGNATION
                active = active & ~stalled
        # Z only matters for still-active columns (a just-converged column
        # never uses its search direction again — pcg returns right here).
        Z = jnp.where(jnp.asarray(active)[None, :],
                      proj(faults.site("solve.precond", bM(R, active))), Z)
        rz_new = cdot(R, Z)
        beta = jnp.where(jnp.asarray(active), rz_new / rz, 0.0)
        P = Z + beta[None, :] * P
        rz = rz_new
    norms = np.stack(hist)
    converged = norms[-1] <= tol * ref
    status[status == ""] = np.where(converged, STATUS_CONVERGED,
                                    STATUS_MAX_ITERS)[status == ""]
    return X, BlockSolveInfo(iters=iters, residual_norms=norms,
                             converged=converged, status=status)


def pcg_scanned(matvec: Callable, b: jax.Array, precond: Callable | None = None,
                n_iters: int = 50, project: Callable | None = None,
                guard=None, tol: float = 0.0):
    """Fixed-iteration PCG as one scanned XLA program.

    With ``guard=None`` (the default, the pre-PR 9 program): returns
    ``(x, residual_norms [n_iters+1])``. This is the jit/dry-run path: all
    collectives (matvec + 2 dots + preconditioner) appear in one HLO so
    the roofline extraction sees the whole iteration.

    With ``guard`` a :class:`GuardConfig` (or True): the breakdown guards
    run *inside* the scan as device-side status lanes — an int32 code,
    the best residual norm, and a stall counter ride the carry — and the
    return grows a third element: ``(x, norms, code)`` where ``code`` is
    one of the ``SCAN_*`` constants. Semantics mirror the eager ``pcg``
    exactly: an indefinite/non-finite ``p·Ap`` freezes x BEFORE the
    poisoned update (last finite iterate), a non-finite residual norm
    freezes after it, and ``stagnation_window`` iterations without
    relative improvement trip the stagnation lane. A frozen solve carries
    its state unchanged through the remaining iterations — the program
    shape never changes. On a clean trajectory every freeze predicate is
    false, every ``jnp.where`` selects the exact same float, and the
    returned ``x``/``norms`` are bitwise identical to the unguarded scan
    (pinned by ``BENCH_robust.json``'s dist bitwise check).

    ``tol`` (guarded path only) exempts an already-converged trajectory
    (``rn <= tol * r0n``) from the stagnation guard: a solve sitting at
    its attainable-accuracy floor *below* tolerance is finished, not
    stagnating — without this a long fixed-iteration run would always
    "stagnate" after it converged. It does NOT freeze the iteration (that
    would change clean-path bits); it only resets the stall counter.
    """
    proj = _project if project is None else project
    M = precond if precond is not None else (lambda v: v)
    g = _as_guard(guard)
    b = proj(b)
    x0 = jnp.zeros_like(b)
    r0 = proj(b - matvec(x0))
    z0 = proj(M(r0))
    r0n = jnp.linalg.norm(r0)

    if g is None:
        carry0 = (x0, r0, z0, z0, jnp.vdot(r0, z0))

        def body(carry, _):
            x, r, z, p, rz = carry
            Ap = matvec(p)
            alpha = rz / jnp.maximum(jnp.vdot(p, Ap), 1e-30)
            x = x + alpha * p
            r = proj(r - alpha * Ap)
            z = proj(M(r))
            rz_new = jnp.vdot(r, z)
            beta = rz_new / jnp.maximum(rz, 1e-30)
            p = z + beta * p
            return (x, r, z, p, rz_new), jnp.linalg.norm(r)

        (x, r, *_), norms = jax.lax.scan(body, carry0, None, length=n_iters)
        return x, jnp.concatenate([r0n[None], norms])

    code0 = jnp.where(jnp.isfinite(r0n), SCAN_OK,
                      SCAN_NONFINITE).astype(jnp.int32)
    carry0 = (x0, r0, z0, z0, jnp.vdot(r0, z0), code0,
              jnp.where(jnp.isfinite(r0n), r0n, jnp.inf),
              jnp.zeros((), jnp.int32))

    def gbody(carry, _):
        x, r, z, p, rz, code, best, stall = carry
        ok = code == SCAN_OK
        Ap = matvec(p)
        pAp = jnp.vdot(p, Ap)
        indef = ok & ~(jnp.isfinite(pAp) & (pAp > 0.0))
        code = jnp.where(indef, SCAN_INDEFINITE, code)
        ok = ok & ~indef
        alpha = jnp.where(ok, rz / jnp.maximum(pAp, 1e-30),
                          jnp.zeros_like(rz))
        x = x + alpha * p
        r = jnp.where(ok, proj(r - alpha * Ap), r)
        rn = jnp.linalg.norm(r)
        nonf = ok & ~jnp.isfinite(rn)
        code = jnp.where(nonf, SCAN_NONFINITE, code)
        ok = ok & ~nonf
        improved = ok & (rn < best * (1.0 - g.stagnation_rtol))
        best = jnp.where(improved, rn, best)
        conv = rn <= tol * r0n
        stall = jnp.where(improved | conv, 0,
                          stall + ok.astype(jnp.int32))
        stalled = ok & (stall >= g.stagnation_window)
        code = jnp.where(stalled, SCAN_STAGNATION, code)
        ok = ok & ~stalled
        z = jnp.where(ok, proj(M(r)), z)
        rz_new = jnp.where(ok, jnp.vdot(r, z), rz)
        beta = jnp.where(ok, rz_new / jnp.maximum(rz, 1e-30),
                         jnp.zeros_like(rz))
        p = jnp.where(ok, z + beta * p, p)
        return (x, r, z, p, rz_new, code, best, stall), rn

    (x, r, _, _, _, code, _, _), norms = jax.lax.scan(
        gbody, carry0, None, length=n_iters)
    return x, jnp.concatenate([r0n[None], norms]), code


def scan_status_from_codes(codes, norms, tol, ref) -> np.ndarray:
    """Per-column status strings from in-scan device codes + final norms.

    ``codes`` is the int32 ``SCAN_*`` lane a guarded scan carried (scalar
    or ``(k,)``); ``norms`` the ``(T+1,)`` / ``(T+1, k)`` residual
    history. A nonzero code wins; a zero code resolves to ``"converged"``
    iff the final norm is within ``tol * ref``, else ``"max_iters"`` —
    the same resolution the eager path applies host-side.
    """
    codes = np.atleast_1d(np.asarray(jax.device_get(codes)))
    norms = np.asarray(norms, np.float64)
    if norms.ndim == 1:
        norms = norms[:, None]
    k = codes.shape[0]
    status = np.full(k, STATUS_MAX_ITERS, dtype="<U24")
    final = norms[-1]
    conv = np.isfinite(final) & (final <= np.asarray(tol) * np.asarray(ref))
    status[conv] = STATUS_CONVERGED
    for c, s in _SCAN_CODE_STATUS.items():
        status[codes == c] = s
    return status


def _norms_status(norms: np.ndarray, tol, ref: np.ndarray) -> np.ndarray:
    """Status codes from a residual history alone (no deprecation gate).

    The guards-off scanned path resolves converged/max_iters from this —
    with guards disabled there is no code lane and a norms-only read is
    the *intended* semantics, not the deprecated postmortem cross-check.
    """
    norms = np.asarray(norms, np.float64)
    if norms.ndim == 1:
        norms = norms[:, None]
    k = norms.shape[1]
    status = np.full(k, STATUS_MAX_ITERS, dtype="<U24")
    finite = np.isfinite(norms).all(axis=0)
    status[~finite] = STATUS_NONFINITE
    status[finite & (norms[-1] <= np.asarray(tol) * ref)] = STATUS_CONVERGED
    return status


def scan_norms_status(norms: np.ndarray, tol, ref: np.ndarray) -> np.ndarray:
    """Per-column status codes from a (T+1, k) scanned residual history.

    .. deprecated:: PR 9
        Debug helper only (emits :class:`DeprecationWarning` since PR 10).
        The scanned/dist solve now carries breakdown codes *inside* the
        scan (``pcg_scanned(guard=...)`` /
        ``DistLaplacianSolver.solve_block(guard=...)`` →
        :func:`scan_status_from_codes`), which detects strictly more than
        this postmortem can: an indefinite ``p·Ap`` is caught and frozen
        *before* NaN ever reaches the residual history, so this
        norms-only reconstruction reports ``max_iters`` where the in-scan
        lane reports ``breakdown_indefinite`` (and it can never see
        stagnation at all). It remains as a cross-check — on clean runs
        and on nonfinite-residual faults the two agree exactly (asserted
        in ``tests/test_dist_faults.py``) — and as the fallback for
        ``SolverOptions(guard_mode="postmortem")``.

    A column whose history contains a non-finite entry broke down,
    otherwise it converged iff its final norm is within ``tol * ref``.
    """
    warnings.warn(
        "scan_norms_status is a deprecated postmortem cross-check: the "
        "scanned/dist solve carries in-scan breakdown codes "
        "(guard_mode='in_scan' -> scan_status_from_codes) which detect "
        "strictly more; use those instead",
        DeprecationWarning, stacklevel=2)
    return _norms_status(norms, tol, ref)


def cg(matvec, b, **kw):
    return pcg(matvec, b, precond=None, **kw)


def jacobi_pcg(level, b, **kw):
    """The paper's baseline: CG preconditioned by diag(L)⁻¹."""
    inv_d = 1.0 / jnp.maximum(level.deg, 1e-30)
    return pcg(level.laplacian_matvec, b, precond=lambda r: inv_d * r, **kw)
