"""The built-in backends behind the ``repro.api`` facade.

Each backend adapts one of the repo's solver implementations to the common
handle protocol the facade consumes:

    handle = setup_fn(problem, options, mesh)
    X, norms, iters = handle.solve_block(B, tol, max_iters)   # B: (n, k)
    handle.work_per_iteration                                 # WDA units
    handle.stats()                                            # hierarchy dict

``solve_block`` always takes and returns 2-D blocks; the facade does the
(n,) <-> (n, 1) plumbing. ``norms`` is the (T+1, k) lockstep residual
history, ``iters`` the per-column iteration counts.

PR 8 extends the protocol with a fourth element: per-column Krylov status
codes (``repro.core.krylov``). The facade still accepts the legacy
3-tuple from third-party handles (statuses are then None and the
degradation ladder never triggers for them).
"""

from __future__ import annotations

import numpy as np

from repro import obs
from repro.api.registry import register_backend


def default_mesh():
    """A √P×√P-ish ("data", "model") mesh over all visible devices.

    Used when the dist backend is selected (explicitly or by ``"auto"``)
    without a mesh: the device count is factored as pr × pc with pr the
    largest divisor ≤ √P, matching the paper's 2D processor grid.
    """
    import jax

    from repro.launch.mesh import make_mesh

    ndev = len(jax.devices())
    pr = max(d for d in range(1, int(ndev ** 0.5) + 1) if ndev % d == 0)
    return make_mesh((pr, ndev // pr))


class _EagerHandle:
    """Handle over a ``LaplacianSolver`` (the ``single`` and ``serial_ref``
    backends share the solve phase; only hierarchy construction differs)."""

    def __init__(self, solver, options):
        self._solver = solver
        self._options = options
        self.work_per_iteration = solver.iteration_work(
            precondition=options.precondition)
        # PR 10: the ABFT checksum closure is built ONCE per handle from
        # the clean setup-time operator (deg, and — paranoid — a clean
        # u = Lw witness product), so later operator corruption cannot
        # poison the reference the checks compare against.
        vcfg = options.verify_config()
        self._check = None
        if vcfg is not None:
            from repro.core.verify import make_check

            self._check = make_check(solver._fine.deg, vcfg,
                                     matvec=solver.matvec)

    def solve_block(self, B, tol: float, max_iters: int, x0=None,
                    guard=None):
        # ``guard`` overrides the options-derived policy for this call
        # (the triage layer passes a tightened GuardConfig); None keeps
        # the options default.
        g = self._options.guard_config() if guard is None else guard
        X, info = self._solver.solve_block(
            B, tol=tol, maxiter=max_iters,
            precondition=self._options.precondition,
            exact_columns=self._options.exact_columns, x0=x0,
            guard=g or False, check=self._check)
        return (np.asarray(obs.fetch(X)), info.residual_norms,
                np.asarray(info.iters, np.int64), info.status)

    def stats(self) -> dict:
        return self._solver.stats()


class _DistHandle:
    """Handle over a ``DistLaplacianSolver`` on a device mesh."""

    def __init__(self, solver, options):
        self._solver = solver
        self._options = options
        self.work_per_iteration = solver.work_per_iteration
        # PR 10: checksum closure over the PADDED iteration space (the
        # scanned PCG's P/Ap blocks are [n_pad, k]); padded rows carry
        # deg=0 and the padded operator is symmetric, so both the
        # column-sum identity and the Rademacher witness hold unchanged.
        vcfg = options.verify_config()
        self._check = None
        if vcfg is not None:
            import jax.numpy as jnp

            from repro.core.verify import make_check
            from repro.dist.solver import DistGraphLevel

            fine = solver.arrays.fine
            if isinstance(fine, DistGraphLevel):
                deg = jnp.pad(fine.deg, (0, solver.n_pad - solver.n))
                mv = fine.matvec_padded
            else:                       # replicated fallback: n_pad == n
                deg = fine.deg
                mv = fine.laplacian_matvec
            self._check = make_check(deg, vcfg, matvec=mv)

    def solve_block(self, B, tol: float, max_iters: int, x0=None,
                    guard=None):
        if x0 is not None:
            raise NotImplementedError(
                "the dist backend's scanned solve does not accept per-column "
                "initial guesses yet; use backend='single' or 'serial_ref' "
                "for x0 warm starts")
        g = self._options.guard_config() if guard is None else (guard or None)
        check = self._check
        if check is not None and g is None:
            # the SDC verdict needs the in-scan code lane to land in
            from repro.core.krylov import GuardConfig

            g = GuardConfig()
        if g is not None and (self._options.guard_mode == "in_scan"
                              or check is not None):
            # PR 9: the guards run INSIDE the scanned program as status
            # lanes — statuses are live device truth (an indefinite p·Ap
            # freezes the column before the poisoned update, which a
            # norms-only postmortem can never see). Clean paths are
            # bitwise-unchanged (BENCH_robust.json dist bitwise check).
            from repro.core.krylov import scan_status_from_codes

            X, norms, iters, codes = self._solver.solve_block(
                B, n_iters=max_iters, tol=tol, guard=g, check=check)
            norms = np.asarray(norms)
            statuses = scan_status_from_codes(codes, norms, tol, norms[0])
        elif g is not None:
            # guard_mode="postmortem": the pre-PR 9 unguarded program plus
            # the (deprecated) host-side norms reconstruction — callers who
            # opted into postmortem mode see its DeprecationWarning.
            from repro.core.krylov import scan_norms_status

            X, norms, iters = self._solver.solve_block(B, n_iters=max_iters,
                                                       tol=tol)
            norms = np.asarray(norms)
            statuses = scan_norms_status(norms, tol, norms[0])
        else:
            # guards off: converged/max_iters/non-finite derived from the
            # fetched norms is the *intended* semantics here, not a
            # postmortem cross-check — use the silent internal helper.
            from repro.core.krylov import _norms_status

            X, norms, iters = self._solver.solve_block(B, n_iters=max_iters,
                                                       tol=tol)
            norms = np.asarray(norms)
            statuses = _norms_status(norms, tol, norms[0])
        return (np.asarray(obs.fetch(X)), norms, np.asarray(iters, np.int64),
                statuses)

    def stats(self) -> dict:
        import jax

        from repro.core.hierarchy import hierarchy_stats

        s = self._solver
        levels = [dict(kind=m.kind, n=m.n, nnz=m.nnz,
                       fill_fraction=m.fill_fraction, distributed=True,
                       executor=m.executor, ell_width=m.ell_width,
                       ell_spill=m.ell_spill)
                  for m in s.level_meta]
        if s.coarse_h.transfers:
            tail = hierarchy_stats(s.coarse_h)
        else:
            # fully distributed hierarchy: the replicated tail is just the
            # dense coarsest solve — emit its row like hierarchy_stats does
            row = dict(kind="coarse",
                       n=int(s.coarse_h.coarse_inv.shape[0]),
                       nnz=None, capacity=None, executor="coo")
            if s.arrays.transfers:
                c = s.arrays.transfers[-1].coarse
                row.update(nnz=int(jax.device_get(c.adj.nnz)),
                           capacity=c.adj.capacity)
            tail = dict(levels=[row], n_levels=1)
        for lvl in tail["levels"]:
            lvl["distributed"] = False
        return dict(levels=levels + tail["levels"],
                    n_levels=len(levels) + tail["n_levels"],
                    mesh_shape=dict(s.mesh.shape))


def _setup_single(problem, options, mesh=None):
    from repro.core.solver import LaplacianSolver

    solver = LaplacianSolver.setup(
        problem.n, problem.rows, problem.cols,
        problem.vals.astype(np.float32),
        setup_config=options.setup_config(),
        cycle_config=options.cycle_config(),
        random_ordering=options.random_ordering)
    return _EagerHandle(solver, options)


def _setup_serial_ref(problem, options, mesh=None):
    from repro.core.serial_ref import serial_lamg_solver

    solver = serial_lamg_solver(
        problem.n, problem.rows, problem.cols,
        problem.vals.astype(np.float32),
        setup_config=options.setup_config(),
        cycle_config=options.cycle_config(),
        random_ordering=options.random_ordering)
    return _EagerHandle(solver, options)


def _setup_dist(problem, options, mesh=None):
    from repro.dist.solver import DistLaplacianSolver

    if not options.precondition:
        raise ValueError(
            "the dist backend always preconditions with the multigrid "
            "cycle; use backend='single' for the plain-CG ablation")
    if mesh is None:
        mesh = default_mesh()
    solver = DistLaplacianSolver.setup(
        problem.n, problem.rows, problem.cols,
        problem.vals.astype(np.float32), mesh,
        setup_config=options.setup_config(),
        cycle_config=options.cycle_config(),
        dist_nnz_threshold=options.dist_nnz_threshold,
        max_dist_levels=options.max_dist_levels,
        random_ordering=options.random_ordering)
    return _DistHandle(solver, options)


register_backend("single", _setup_single)
register_backend("serial_ref", _setup_serial_ref)
register_backend("dist", _setup_dist)
