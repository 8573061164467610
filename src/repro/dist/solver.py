"""Distributed SOLVE phase: PCG + V-cycle with 2D-sharded SpMV (paper §3).

``DistLaplacianSolver`` builds the same multigrid hierarchy as
``core.solver.LaplacianSolver`` (setup is eager and host-driven), then
splits it at ``dist_nnz_threshold`` / ``max_dist_levels``:

* the top (largest) levels get their fine adjacency partitioned into the
  paper's 2D block layout (``repro.dist.partition``) and their SpMV — the
  dominant cost of PCG, smoothing and residual computation — runs as a
  ``shard_map`` over the device mesh: each device contracts its block's
  edges against the vector, and one psum over the mesh axes plays the
  paper's column-reduce + row-broadcast;
* levels below the threshold fall back to the replicated serial
  hierarchy (``coarse_h``) — exactly the paper's observation that coarse
  grids are too small to be worth distributing.

The transfer operators (Schur elimination, aggregation contraction) are
reused from ``repro.core`` unchanged; only the per-level fine adjacency
is swapped for its 2D-partitioned twin, so the distributed solver is
numerically the serial solver with its big SpMVs sharded.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core.cycles import CycleConfig, cycle
from repro.core.elimination import EliminationLevel
from repro.core.graph import GraphLevel, graph_from_adjacency
from repro.core.hierarchy import (Hierarchy, SetupConfig,
                                  attach_ell_transfers, build_hierarchy)
from repro.core.krylov import (SCAN_INDEFINITE, SCAN_NONFINITE, SCAN_OK,
                               SCAN_SDC, SCAN_STAGNATION, GuardConfig,
                               _as_guard)
from repro.dist.partition import (edge_spec, ell_block_spec,
                                  ell_blocks_from_partition, mesh_geometry,
                                  partition_edges_2d)
from repro.graphs.generators import random_relabel, to_laplacian_coo
from repro.sparse.matvec import executor_name, resolve_ell_mode
from repro.testing import faults


def _shard_coords(mesh):
    """(traced linear shard index, static shard count) inside shard_map."""
    idx = jnp.zeros((), jnp.int32)
    n_shards = 1
    for name in mesh.axis_names:
        size = mesh.shape[name]
        idx = idx * size + jax.lax.axis_index(name)
        n_shards *= int(size)
    return idx, n_shards


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DistGraphLevel:
    """A multigrid level whose adjacency lives 2D-partitioned on a mesh.

    Drop-in for ``core.graph.GraphLevel`` wherever only ``n``, ``deg`` and
    ``laplacian_matvec`` are used (smoothers, residuals, PCG) — the matvec
    is the distributed semiring SpMV instead of a replicated segment-sum.

    When ``matvec_backend != "coo"`` the level additionally carries each
    device's local edge block in hybrid ELL+COO layout (``ell_col`` /
    ``ell_val`` plus the ``spill_*`` remainder, built at partition time by
    ``partition.ell_blocks_from_partition``): the within-block contraction
    then runs in fixed-width layout (the jnp ELL reference under
    ``"auto"``, the Pallas ELL SpMV kernel under ``"ell"``) instead of a
    segment-sum, while the communication schedule — one psum over the
    mesh axes — is unchanged.
    """

    row_local: jax.Array   # int32 [pods, pr, pc, cap], sharded over the mesh
    col_local: jax.Array   # int32 [pods, pr, pc, cap]
    val: jax.Array         # float32 [pods, pr, pc, cap]
    deg: jax.Array         # float32 [n] weighted degrees (replicated)
    n: int = dataclasses.field(metadata=dict(static=True))
    n_pad: int = dataclasses.field(metadata=dict(static=True))
    nb: int = dataclasses.field(metadata=dict(static=True))
    nb_col: int = dataclasses.field(metadata=dict(static=True))
    mesh: object = dataclasses.field(metadata=dict(static=True))
    # hybrid ELL+COO twin of the local blocks (None = COO execution)
    ell_col: jax.Array | None = None    # int32 [pods, pr, pc, nb, width]
    ell_val: jax.Array | None = None    # float32 [pods, pr, pc, nb, width]
    spill_row: jax.Array | None = None  # int32 [pods, pr, pc, spill_cap]
    spill_col: jax.Array | None = None  # int32 [pods, pr, pc, spill_cap]
    spill_val: jax.Array | None = None  # float32 [pods, pr, pc, spill_cap]
    ell_mode: str = dataclasses.field(default="pallas",
                                      metadata=dict(static=True))

    @property
    def capacity(self) -> int:
        return int(self.val.shape[-1])

    def spmv_padded(self, x_pad: jax.Array) -> jax.Array:
        """y = A @ x on [n_pad] vectors via the 2D-sharded edge blocks."""
        if self.ell_col is not None:
            return self._spmv_padded_ell(x_pad)
        mesh = self.mesh
        _, row_axis, col_axis, *_ = mesh_geometry(mesh)
        axes = tuple(mesh.axis_names)
        espec = edge_spec(mesh)
        nb, nb_col, n_pad = self.nb, self.nb_col, self.n_pad

        def local(row_l, col_l, val, x):
            i = jax.lax.axis_index(row_axis)
            j = jax.lax.axis_index(col_axis)
            sidx, nsh = _shard_coords(mesh)
            row_l = row_l.reshape(-1)
            col_l = col_l.reshape(-1)
            # One seeded shard's local value payload can be silently
            # corrupted (trace-time site; a no-op unless a plan is armed).
            val = faults.site_traced("sdc.shard_payload", val.reshape(-1),
                                     axis_index=sidx, n_shards=nsh)
            valid = row_l < nb
            row_g = jnp.where(valid, i * nb + row_l, n_pad)
            col_g = jnp.where(valid, j * nb_col + col_l, n_pad)
            xg = jnp.take(x, col_g, mode="fill", fill_value=0)
            prod = jnp.where(valid, val * xg, 0)
            part = jax.ops.segment_sum(prod, row_g, num_segments=n_pad)
            # One seeded shard's allreduce contribution can be corrupted
            # (trace-time site; a no-op unless a fault plan is armed).
            part = faults.site_traced("dist.psum", part,
                                      axis_index=sidx, n_shards=nsh)
            # Column-communicator reduce + row broadcast == one psum.
            return jax.lax.psum(part, axes)

        return jax.shard_map(local, mesh=mesh,
                             in_specs=(espec, espec, espec, P()),
                             out_specs=P())(self.row_local, self.col_local,
                                            self.val, x_pad)

    def _spmv_padded_ell(self, x_pad: jax.Array) -> jax.Array:
        """ELL execution of the same 2D schedule: each device contracts
        its block in fixed-width layout (Pallas kernel or jnp reference),
        adds its COO spill, and the one psum plays the paper's
        column-reduce + row-broadcast exactly as in the COO path.

        ``check_vma=False``: shard_map has no replication rule for
        ``pallas_call`` (the result is replicated by the psum anyway).
        """
        from repro.kernels.spmv_ell import spmv_ell
        from repro.sparse.ell import ELL, ell_spmv_ref

        mesh = self.mesh
        _, row_axis, _, *_ = mesh_geometry(mesh)
        axes = tuple(mesh.axis_names)
        espec = edge_spec(mesh)
        ell_spec = ell_block_spec(mesh)
        nb, n_pad = self.nb, self.n_pad
        width = int(self.ell_col.shape[-1])
        use_pallas = self.ell_mode == "pallas"

        has_spill = self.spill_row is not None

        def local(ec, ev, *rest):
            *spill, x = rest
            i = jax.lax.axis_index(row_axis)
            sidx, nsh = _shard_coords(mesh)
            ec = ec.reshape(nb, width)
            # same one-bad-shard payload model as the COO path, on the
            # fixed-width ELL values the Pallas kernel contracts
            ev = faults.site_traced("sdc.shard_payload", ev.reshape(nb, width),
                                    axis_index=sidx, n_shards=nsh)
            # Column ids are global with sentinel n_pad, so the gather
            # source is the replicated x itself.
            if use_pallas:
                y = spmv_ell(ec, ev, x)
            else:
                y = ell_spmv_ref(ELL(ec, ev, n_pad), x)
            part = jnp.zeros((n_pad,), x.dtype)
            part = jax.lax.dynamic_update_slice(
                part, y.astype(x.dtype), (i * nb,))
            if has_spill:            # spill-free levels: pure ELL contraction
                sr, sc, sv = (a.reshape(-1) for a in spill)
                xg = jnp.take(x, sc, mode="fill", fill_value=0)
                prod = jnp.where(sr < n_pad, sv * xg, 0)
                part = part + jax.ops.segment_sum(prod, sr,
                                                  num_segments=n_pad)
            part = faults.site_traced("dist.psum", part,
                                      axis_index=sidx, n_shards=nsh)
            return jax.lax.psum(part, axes)

        spill_args = ((self.spill_row, self.spill_col, self.spill_val)
                      if has_spill else ())
        spill_specs = (espec,) * len(spill_args)
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(ell_spec, ell_spec) + spill_specs + (P(),),
            out_specs=P(), check_vma=False)(
            self.ell_col, self.ell_val, *spill_args, x_pad)

    def laplacian_matvec(self, x: jax.Array) -> jax.Array:
        """L @ x on length-n vectors (smoother / residual interface)."""
        x_pad = jnp.pad(x, (0, self.n_pad - self.n))
        return self.deg * x - self.spmv_padded(x_pad)[: self.n]

    def matvec_padded(self, x_pad: jax.Array) -> jax.Array:
        """L @ x on [n_pad] vectors (the PCG iteration space)."""
        deg_pad = jnp.pad(self.deg, (0, self.n_pad - self.n))
        return deg_pad * x_pad - self.spmv_padded(x_pad)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DistArrays:
    """Device-resident distributed state: the jit-able half of the solver.

    ``fine`` is the finest level (a ``DistGraphLevel`` when any level is
    distributed, the serial ``GraphLevel`` otherwise); ``transfers`` are
    the distributed-prefix transfer operators with their fine levels
    swapped for ``DistGraphLevel`` twins.
    """

    fine: object          # DistGraphLevel | GraphLevel
    transfers: tuple      # distributed-prefix Transfer objects
    lam_maxes: tuple      # matching λmax estimates


@dataclasses.dataclass(frozen=True)
class DistLevelMeta:
    """Host-side description of one distributed level (``kind`` per test API)."""

    kind: str             # "elim" | "agg"
    n: int
    nnz: int
    n_pad: int
    capacity: int
    fill_fraction: float
    executor: str = "coo"          # "coo" | "ell-jnp" | "pallas"
    ell_width: int | None = None   # hybrid block width (None = COO execution)
    ell_spill: int | None = None   # total spill edges across blocks


def _block_ops(matvec, precond, n: int, n_pad: int):
    """Column-lifted operators + masked projection for [n_pad, k] blocks.

    The mean-free projection (Laplacian nullspace handling) averages over
    the n real entries and pins padding to zero — padded slots never
    contribute to dot products or norms. ``matvec``/``precond`` are
    single-vector functions vmapped over the columns, so the distributed
    SpMV and V-cycle collectives run once per iteration for the whole
    block.
    """
    mask = (jnp.arange(n_pad) < n)[:, None]
    bmv = jax.vmap(matvec, in_axes=1, out_axes=1)
    bM = jax.vmap(precond, in_axes=1, out_axes=1)

    def proj(V):
        V = jnp.where(mask, V, 0)
        return jnp.where(mask, V - jnp.sum(V, axis=0)[None, :] / n, 0)

    def cnorm(V):
        return jnp.linalg.norm(V, axis=0)

    return bmv, bM, proj, cnorm


def _pcg_block_init(matvec, B, precond, n: int, n_pad: int, guard=None):
    """Blocked PCG carry for B [n_pad, k].

    Unguarded (``guard=None``, the pre-PR 9 program):
    ``(X, R, Z, P, rz, iters, r0n)``. With a ``GuardConfig``, three
    device-side status lanes ride the carry — per-column int32 ``SCAN_*``
    codes, the best residual norm, and a stall counter:
    ``(X, R, Z, P, rz, iters, code, best, stall, r0n)``. A column whose
    initial residual norm is already non-finite starts frozen with
    ``SCAN_NONFINITE``.
    """
    bmv, bM, proj, cnorm = _block_ops(matvec, precond, n, n_pad)
    k = B.shape[1]
    B = proj(B)
    X0 = jnp.zeros_like(B)
    R0 = proj(B - bmv(X0))
    Z0 = proj(bM(R0))
    r0n = cnorm(R0)
    base = (X0, R0, Z0, Z0, jnp.sum(R0 * Z0, axis=0),
            jnp.zeros((k,), jnp.int32))
    if guard is None:
        return base + (r0n,)
    fin = jnp.isfinite(r0n)
    code0 = jnp.where(fin, SCAN_OK, SCAN_NONFINITE).astype(jnp.int32)
    best0 = jnp.where(fin, r0n, jnp.inf)
    return base + (code0, best0, jnp.zeros((k,), jnp.int32), r0n)


def _pcg_block_step(matvec, precond, n: int, n_pad: int, tol: float, r0n,
                    guard=None, check=None):
    """One blocked PCG step and the test that a column still needs one.

    Returns ``(running, step)``: ``running(state) -> bool[k]`` and
    ``step(state) -> (state, norms [k])`` over the carry without its
    trailing ``r0n``. A column is active while its residual norm is above
    ``tol * ||r0||``; an inactive column's alpha is zeroed and its residual
    pinned, so x/r stop updating while the other columns go on. ``tol=0``
    keeps every column with a nonzero residual active.

    With ``guard`` a ``GuardConfig`` (carry from the guarded init), the
    PR 8 breakdown guards run *inside* the step per column: an indefinite
    or non-finite ``p·Ap`` freezes the column BEFORE the poisoned update
    (x stays the last finite iterate, exactly like eager ``pcg_block``), a
    non-finite residual norm freezes it after, and ``stagnation_window``
    active iterations with no relative improvement trip the stagnation
    lane. Frozen columns fold into the same active mask the convergence
    exit already uses, so on a clean trajectory every guard predicate is
    false and the emitted X/norms/iters are bitwise identical to the
    unguarded program (pinned by the bench's dist bitwise check). The
    iteration SpMV routes through the ``dist.spmv`` trace-time fault site
    (mirroring the eager path's ``solve.spmv``); a no-op unless a fault
    plan is armed.

    ``check`` (guarded carry only) is the ABFT checksum
    ``check(P, Ap) -> bool[k]`` from ``repro.core.verify.make_check``
    built on the *padded* degree vector: a flagged column freezes with
    ``SCAN_SDC`` before the poisoned update, ahead of the indefinite
    guard — the verdict is a pure extra lane, so clean trajectories stay
    bitwise identical with the check on.
    """
    bmv, bM, proj, cnorm = _block_ops(matvec, precond, n, n_pad)

    if guard is None:
        def running(state):
            return cnorm(state[1]) > tol * r0n

        def step(state):
            X, R, Z, P, rz, iters = state
            active = running(state)
            iters = iters + active.astype(jnp.int32)
            Ap = bmv(P)
            pAp = jnp.sum(P * Ap, axis=0)
            alpha = jnp.where(active, rz / jnp.maximum(pAp, 1e-30), 0.0)
            X = X + alpha[None, :] * P
            # Converged columns stop updating: freeze r exactly rather than
            # re-projecting it (which would drift the reported norms).
            R = jnp.where(active[None, :], proj(R - alpha[None, :] * Ap), R)
            Z = jnp.where(active[None, :], proj(bM(R)), Z)
            rz_new = jnp.sum(R * Z, axis=0)
            beta = jnp.where(active, rz_new / jnp.maximum(rz, 1e-30), 0.0)
            P = Z + beta[None, :] * P
            return (X, R, Z, P, rz_new, iters), cnorm(R)

        return running, step

    g = guard

    def grunning(state):
        return (cnorm(state[1]) > tol * r0n) & (state[6] == SCAN_OK)

    def gstep(state):
        X, R, Z, P, rz, iters, code, best, stall = state
        active = grunning(state)
        Ap = faults.site_traced("dist.spmv", bmv(P))
        if check is not None:
            sdc = active & check(P, Ap)
            code = jnp.where(sdc, SCAN_SDC, code)
            active = active & ~sdc
        pAp = jnp.sum(P * Ap, axis=0)
        indef = active & ~(jnp.isfinite(pAp) & (pAp > 0.0))
        code = jnp.where(indef, SCAN_INDEFINITE, code)
        active = active & ~indef
        iters = iters + active.astype(jnp.int32)
        alpha = jnp.where(active, rz / jnp.maximum(pAp, 1e-30), 0.0)
        X = X + alpha[None, :] * P
        R = jnp.where(active[None, :], proj(R - alpha[None, :] * Ap), R)
        rn = cnorm(R)
        nonf = active & ~jnp.isfinite(rn)
        code = jnp.where(nonf, SCAN_NONFINITE, code)
        active = active & ~nonf
        improved = active & (rn < best * (1.0 - g.stagnation_rtol))
        best = jnp.where(improved, rn, best)
        stall = jnp.where(improved, 0, stall + active.astype(jnp.int32))
        stalled = active & (stall >= g.stagnation_window)
        code = jnp.where(stalled, SCAN_STAGNATION, code)
        active = active & ~stalled
        # the tail is op-for-op the unguarded step (bitwise parity on
        # clean paths); frozen columns meet zeroed betas, and a broken
        # column's NaN rz can never reach X (its alpha selects 0)
        Z = jnp.where(active[None, :], proj(bM(R)), Z)
        rz_new = jnp.sum(R * Z, axis=0)
        beta = jnp.where(active, rz_new / jnp.maximum(rz, 1e-30), 0.0)
        P = Z + beta[None, :] * P
        return (X, R, Z, P, rz_new, iters, code, best, stall), rn

    return grunning, gstep


def _pcg_block_chunk(matvec, precond, n: int, n_pad: int, tol: float,
                     length: int, carry, guard=None, check=None):
    """Advance a blocked PCG carry by at most ``length`` steps of
    :func:`_pcg_block_step`, stopping as soon as no column is active.

    The loop is a ``lax.while_loop``: a block that converges after 6 steps
    runs 6, not ``length``. A step run past that point would change
    nothing (every alpha zero, every residual pinned), so X, iters and the
    norms of the steps that ran are those of a fixed-length scan.

    Returns ``(carry, norms [length, k], steps)``: ``steps`` (int32) is the
    number of steps run, and only the first ``steps`` rows of ``norms``
    are written (the rest are zero). ``carry[5]`` counts the steps each
    column was active for, cumulative across chunks.
    """
    r0n = carry[-1]
    running, step = _pcg_block_step(matvec, precond, n, n_pad, tol, r0n,
                                    guard=guard, check=check)

    def cond(loop):
        i, _, state = loop
        return (i < length) & jnp.any(running(state))

    def body(loop):
        i, norms, state = loop
        state, rn = step(state)
        return i + 1, norms.at[i].set(rn), state

    norms0 = jnp.zeros((length, r0n.shape[0]), r0n.dtype)
    steps, norms, state = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), norms0, tuple(carry[:-1])))
    return state + (r0n,), norms, steps


def _partition_level(level: GraphLevel, mesh, matvec_backend: str = "coo",
                     ell_width_percentile: float = 95.0,
                     ell_width_cap: int = 64
                     ) -> tuple[DistGraphLevel, float, object]:
    """2D-partition one level's adjacency and place it on the mesh.

    With ``matvec_backend != "coo"`` each block is additionally converted
    to the hybrid ELL+COO layout at partition time, so the per-device
    contraction in ``shard_map`` runs in fixed-width layout.
    Returns ``(level, fill_fraction, EllBlocks-or-None)``.
    """
    from repro.sparse.matvec import validate_backend

    validate_backend(matvec_backend)
    _, _, _, pods, pr, pc = mesh_geometry(mesh)
    adj = level.adj
    row, col, val, valid = jax.device_get(
        (adj.row, adj.col, adj.val, adj.valid))
    obs.count("dist.partition_host_bytes",
              sum(a.nbytes for a in (row, col, val, valid)))
    # A corrupted upstream setup (fault injection, overflowed aggregate
    # ids) can leave vertex ids outside [0, n). Those edges are
    # structurally impossible — drop them here so the damage surfaces as
    # a breakdown status at solve time instead of a bincount crash mid-
    # partition. Clean levels always have in-range ids: identical mask.
    valid = valid & (row >= 0) & (row < level.n) \
        & (col >= 0) & (col < level.n)
    part = partition_edges_2d(level.n, row[valid], col[valid], val[valid],
                              pr, pc, pods=pods, random_ordering=False)
    espec = edge_spec(mesh)
    sharding = NamedSharding(mesh, espec)
    ell_kw: dict = {}
    blocks = None
    if matvec_backend != "coo":
        # Per-level layout selection rides inside: "auto" may return None
        # (level stays on COO execution), "ell" always converts.
        blocks = ell_blocks_from_partition(part,
                                           percentile=ell_width_percentile,
                                           cap=ell_width_cap,
                                           backend=matvec_backend)
        if blocks is not None:
            ell_sharding = NamedSharding(mesh, ell_block_spec(mesh))
            ell_kw = dict(
                ell_col=jax.device_put(jnp.asarray(blocks.col), ell_sharding),
                ell_val=jax.device_put(jnp.asarray(blocks.val), ell_sharding),
                ell_mode=resolve_ell_mode(matvec_backend))
            if blocks.spill_nnz:     # spill-free levels skip the COO pass
                ell_kw.update(
                    spill_row=jax.device_put(jnp.asarray(blocks.spill_row),
                                             sharding),
                    spill_col=jax.device_put(jnp.asarray(blocks.spill_col),
                                             sharding),
                    spill_val=jax.device_put(jnp.asarray(blocks.spill_val),
                                             sharding))
    dlevel = DistGraphLevel(
        row_local=jax.device_put(jnp.asarray(part.row_local), sharding),
        col_local=jax.device_put(jnp.asarray(part.col_local), sharding),
        val=jax.device_put(jnp.asarray(part.val), sharding),
        deg=level.deg, n=level.n, n_pad=part.n_pad,
        nb=part.nb, nb_col=part.nb_col, mesh=mesh, **ell_kw)
    return dlevel, part.fill_fraction, blocks


@dataclasses.dataclass
class DistLaplacianSolver:
    """2D-distributed PCG + V-cycle solver (the paper's solve phase).

    Public surface (pinned by tests / configs / examples):

    * ``setup(n, rows, cols, vals, mesh, setup_config, ...)``
    * ``solve(b, n_iters, tol)`` -> ``(x, residual_norms)``
    * ``solve_block(B, n_iters, tol)`` -> ``(X, norms, iters)`` multi-RHS
    * ``build_solve_step(n_iters)`` -> jit-able ``(arrays, coarse_h, b_pad)``
    * ``level_meta`` (per distributed level, with ``.kind``), ``coarse_h``
      (replicated tail ``Hierarchy``), ``arrays``, ``n_pad``,
      ``work_per_iteration`` (WDA accounting, from the pre-split hierarchy).
    """

    arrays: DistArrays
    coarse_h: Hierarchy
    level_meta: list
    cycle_config: CycleConfig
    n: int
    n_pad: int
    mesh: object
    perm: np.ndarray | None = None         # §2.2 random ordering
    inv_perm: np.ndarray | None = None
    work_per_iteration: float = 0.0        # PCG iter cost in finest matvecs
    # jitted solve steps keyed by n_iters, so repeat solves (multiple
    # right-hand sides, benchmark loops) hit the jit cache instead of
    # recompiling the whole PCG + V-cycle program.
    _steps: dict = dataclasses.field(default_factory=dict, repr=False,
                                     compare=False)

    # ------------------------------------------------------------------
    @staticmethod
    def setup(n: int, rows, cols, vals, mesh,
              setup_config: SetupConfig = SetupConfig(),
              cycle_config: CycleConfig = CycleConfig(),
              dist_nnz_threshold: int = 10_000,
              max_dist_levels: int = 3,
              random_ordering: bool = True) -> "DistLaplacianSolver":
        resolve_ell_mode(setup_config.matvec_backend)  # refuse "ell" early
        rows = np.asarray(rows)
        cols = np.asarray(cols)
        vals = np.asarray(vals, np.float32)
        perm = inv_perm = None
        if random_ordering:
            with obs.span("repro.dist.setup.relabel"):
                rows, cols, perm, inv_perm = random_relabel(
                    n, rows, cols, setup_config.seed)

        adj = to_laplacian_coo(n, rows, cols, vals)
        # Build the hierarchy without replicated ELL twins: the largest
        # levels are about to get *per-block* ELL layouts instead, so
        # attaching serial twins there would be discarded setup work. The
        # replicated coarse tail gets its twins after the split below.
        #
        # setup_mode="superstep" (the default) runs the DISTRIBUTED
        # bucketed super-step loop: Alg 1 selection and the Alg 2 vote
        # rounds execute as shard_map programs over the 2D edge partition
        # of the mesh, with device-side re-partitioning between levels and
        # one batched scalar fetch per level-advance decision. The
        # produced hierarchy is equivalent to the serial paths — same
        # level structure and integer decisions, floats to rounding
        # (repro.dist.setup) — so the split/partition logic below is
        # unchanged. "eager" keeps the host-driven reference loop.
        setup_cfg = dataclasses.replace(setup_config, matvec_backend="coo")
        if setup_config.setup_mode == "superstep":
            from repro.dist.setup import build_hierarchy_superstep_dist

            with obs.span("repro.dist.setup.superstep"):
                h = build_hierarchy_superstep_dist(adj, setup_cfg, mesh)
        else:
            h = build_hierarchy(adj, setup_cfg)

        dist_transfers = []
        lam_maxes = []
        level_meta = []
        # One batched device_get for every candidate level's nnz (the
        # split decision), instead of a host round-trip per level.
        nnzs = [int(x) for x in jax.device_get(
            tuple(t.fine.adj.nnz for t in h.transfers))]
        for t, lam, nnz in zip(h.transfers, h.lam_maxes, nnzs):
            if len(dist_transfers) >= max_dist_levels:
                break
            if nnz < dist_nnz_threshold:
                break
            with obs.span("repro.dist.setup.partition"):
                dfine, fill, blocks = _partition_level(
                    t.fine, mesh, matvec_backend=setup_config.matvec_backend,
                    ell_width_percentile=setup_config.ell_width_percentile,
                    ell_width_cap=setup_config.ell_width_cap)
            dist_transfers.append(dataclasses.replace(t, fine=dfine))
            lam_maxes.append(lam)
            level_meta.append(DistLevelMeta(
                kind="elim" if isinstance(t, EliminationLevel) else "agg",
                n=t.fine.n, nnz=nnz, n_pad=dfine.n_pad,
                capacity=dfine.capacity, fill_fraction=fill,
                executor=executor_name(dfine),
                ell_width=blocks.width if blocks is not None else None,
                ell_spill=blocks.spill_nnz if blocks is not None else None))

        k = len(dist_transfers)
        coarse_transfers = attach_ell_transfers(h.transfers[k:],
                                                setup_config)
        coarse_h = Hierarchy(transfers=coarse_transfers,
                             lam_maxes=h.lam_maxes[k:],
                             coarse_inv=h.coarse_inv)

        if k:
            fine = dist_transfers[0].fine
            n_pad = fine.n_pad
        elif coarse_transfers:
            fine = coarse_transfers[0].fine     # full serial fallback
            n_pad = n
        else:
            fine = graph_from_adjacency(adj)
            n_pad = n

        arrays = DistArrays(fine=fine, transfers=tuple(dist_transfers),
                            lam_maxes=tuple(lam_maxes))
        from repro.core.wda import pcg_iteration_work
        work = pcg_iteration_work(h, cycle_config)  # pre-split hierarchy
        return DistLaplacianSolver(
            arrays=arrays, coarse_h=coarse_h, level_meta=level_meta,
            cycle_config=cycle_config, n=n, n_pad=n_pad, mesh=mesh,
            perm=perm, inv_perm=inv_perm, work_per_iteration=work)

    # ------------------------------------------------------------------
    def _operators(self, arrays, coarse_h):
        """(matvec, precond) on [n_pad] vectors for the current split."""
        n, n_pad = self.n, self.n_pad
        cyc = self.cycle_config
        if isinstance(arrays.fine, DistGraphLevel):
            matvec = arrays.fine.matvec_padded
        else:
            matvec = arrays.fine.laplacian_matvec       # n_pad == n fallback
        transfers = arrays.transfers + coarse_h.transfers
        lams = arrays.lam_maxes + coarse_h.lam_maxes

        def precond(r_pad):
            z = cycle(transfers, lams, coarse_h.coarse_inv, r_pad[:n], cyc)
            return jnp.pad(z, (0, n_pad - n))

        return matvec, precond

    def build_init_step(self, guard=None):
        """(arrays, coarse_h, B_pad [n_pad, k]) -> blocked PCG carry."""
        n, n_pad = self.n, self.n_pad

        def step(arrays, coarse_h, B_pad):
            matvec, precond = self._operators(arrays, coarse_h)
            return _pcg_block_init(matvec, B_pad, precond, n, n_pad,
                                   guard=guard)

        return step

    def build_chunk_step(self, length: int, tol: float = 0.0, guard=None,
                         check=None):
        """(arrays, coarse_h, carry) -> (carry, norms [length, k], steps):
        at most ``length`` steps, ending once every column is done."""
        n, n_pad = self.n, self.n_pad

        def step(arrays, coarse_h, carry):
            matvec, precond = self._operators(arrays, coarse_h)
            return _pcg_block_chunk(matvec, precond, n, n_pad, tol, length,
                                    carry, guard=guard, check=check)

        return step

    def build_solve_block_step(self, n_iters: int = 30, tol: float = 0.0,
                               guard=None, check=None):
        """(arrays, coarse_h, B_pad [n_pad, k]) -> (X_pad, norms, iters).

        One fused program — init + one chunk of ``n_iters`` steps — so a
        dry-run lowering sees every collective of the solve phase in a
        single HLO. ``norms`` always has ``n_iters + 1`` rows: once every
        column is done the loop ends, and the rows after it repeat the last
        one, as further steps would. With ``guard`` a ``GuardConfig`` the
        in-loop status lanes run and the return grows a fourth element:
        per-column int32 ``SCAN_*`` codes.
        """
        init = self.build_init_step(guard=guard)
        chunk = self.build_chunk_step(n_iters, tol=tol, guard=guard,
                                      check=check)

        def step(arrays, coarse_h, B_pad):
            carry = init(arrays, coarse_h, B_pad)
            r0n = carry[-1]
            carry, norms, steps = chunk(arrays, coarse_h, carry)
            norms = jnp.concatenate([r0n[None, :], norms], axis=0)
            ran = jnp.arange(n_iters + 1)[:, None] <= steps
            norms = jnp.where(ran, norms, norms[steps][None, :])
            if guard is None:
                return carry[0], norms, carry[5]
            return carry[0], norms, carry[5], carry[6]

        return step

    def build_solve_step(self, n_iters: int = 30, tol: float = 0.0):
        """(arrays, coarse_h, b_pad [n_pad]) -> (x_pad, residual_norms).

        The single-RHS jit/dry-run entry point (pinned by configs and the
        HLO-lowering tests): a k=1 column through the blocked scanned PCG.
        """
        block_step = self.build_solve_block_step(n_iters, tol=tol)

        def step(arrays, coarse_h, b_pad):
            x, norms, _ = block_step(arrays, coarse_h, b_pad[:, None])
            return x[:, 0], norms[:, 0]

        return step

    # ------------------------------------------------------------------
    def _to_internal(self, b: jax.Array) -> jax.Array:
        return b[jnp.asarray(self.inv_perm)] if self.perm is not None else b

    def _from_internal(self, x: jax.Array) -> jax.Array:
        return x[jnp.asarray(self.perm)] if self.perm is not None else x

    def solve(self, b, n_iters: int = 30, tol: float = 1e-8):
        """Distributed PCG solve: at most ``n_iters`` scan steps, with a
        residual-based early exit at ``tol * ||r0||`` (the converged column
        freezes; pass ``tol=0`` for the fixed-iteration behavior).

        Returns (x [n], norms [T+1]) with T <= n_iters (the solve stops at
        the step where it converged).
        """
        b = jnp.asarray(b, jnp.float32)
        X, norms, _ = self.solve_block(b[:, None], n_iters=n_iters, tol=tol)
        return X[:, 0], norms[:, 0]

    # chunk length for the eager solve path: the host checks the iteration
    # cap and the statuses once per chunk; a chunk itself ends as soon as
    # every column is done.
    _CHUNK = 16

    def _get_step(self, key, build):
        """Jit-cache lookup, bypassed while a traced fault plan is armed.

        Trace-time fault sites (``dist.spmv``/``dist.psum``) bake the
        corruption into the traced program, so an armed plan must never
        reuse a cached clean program nor poison the cache: a non-None
        ``faults.trace_token()`` forces a fresh uncached jit per call.
        """
        if faults.trace_token() is not None:
            return jax.jit(build())
        step = self._steps.get(key)
        if step is None:
            step = self._steps[key] = jax.jit(build())
        return step

    @obs.spanned("repro.pcg")
    def solve_block(self, B, n_iters: int = 30, tol: float = 1e-8,
                    guard=None, check=None):
        """Blocked multi-RHS distributed solve: ``B`` is (n, k).

        All k columns ride one compiled PCG loop — the 2D-sharded SpMV
        and V-cycle collectives run once per iteration for the whole block.
        With ``tol > 0`` the loop runs in chunks of at most ``_CHUNK``
        iterations, and each chunk ends at the step where every column has
        converged, so a generous ``n_iters`` cap costs nothing once the
        block is done. The host fetches once after the init and once per
        chunk. Returns (X [n, k], norms [T+1, k], iters [k]) with T <=
        n_iters the steps that ran.

        ``guard`` (bool or ``repro.core.krylov.GuardConfig``) turns on the
        in-scan breakdown lanes: the return grows a fourth element — the
        per-column int32 ``SCAN_*`` codes, fetched live from the carry —
        and broken columns also count as done for the early exit (a
        fully-broken block stops at once instead of burning the whole
        iteration cap). Clean-path X/norms/iters are bitwise identical to
        the unguarded program.

        ``check`` is an ABFT checksum closure over *padded* (P, Ap) blocks
        (``repro.core.verify.make_check`` on the padded degree vector);
        a flagged column freezes with ``SCAN_SDC``. The verdict needs the
        in-scan code lane to land in, so a non-None ``check`` implies the
        guarded program (a default ``GuardConfig`` when ``guard`` is None).
        """
        B = jnp.asarray(B, jnp.float32)
        if B.ndim != 2:
            raise ValueError(f"solve_block expects B of shape (n, k), "
                             f"got {B.shape}")
        k = B.shape[1]
        B_pad = jnp.pad(self._to_internal(B), ((0, self.n_pad - self.n),
                                               (0, 0)))
        tol = float(tol)
        g = _as_guard(guard)
        if check is not None and g is None:
            g = GuardConfig()

        init = self._get_step(("init", k, g),
                              lambda: self.build_init_step(guard=g))
        with obs.span("repro.dist.init"):
            carry = init(self.arrays, self.coarse_h, B_pad)
        r0n = np.asarray(obs.fetch(carry[-1]))

        # a cap far beyond typical convergence runs as chunks of _CHUNK
        # steps (one program, one fetch each); a small cap as one chunk
        chunked = tol > 0 and n_iters > 2 * self._CHUNK
        norms_parts = [r0n[None, :]]
        it = 0
        while True:
            length = min(self._CHUNK, n_iters - it) if chunked else n_iters
            key = ("chunk", k, length, tol, g, check)
            step = self._get_step(
                key, lambda: self.build_chunk_step(length, tol=tol, guard=g,
                                                   check=check))
            with obs.span("repro.dist.chunk"):
                carry, ns, steps = step(self.arrays, self.coarse_h, carry)
            lanes = (ns, steps, carry[5]) + ((carry[6],) if g is not None
                                             else ())
            ns, steps, iters, *codes = obs.fetch(*lanes)
            steps = int(steps)
            obs.count("dist.chunks")
            obs.count("dist.scan_steps", steps)
            norms_parts.append(np.asarray(ns)[:steps])
            it += length
            if steps < length or it >= n_iters:
                break
            # a chunk that ran its full length may have ended just as the
            # last column finished: then no further chunk is launched
            done = norms_parts[-1][-1] <= tol * r0n
            if g is not None:
                done = done | (np.asarray(codes[0]) != SCAN_OK)
            if tol > 0 and np.all(done):
                break
        iters = np.asarray(iters)
        obs.count("dist.pcg_iters", int(iters.max(initial=0)))
        out = (self._from_internal(carry[0][: self.n]),
               np.concatenate(norms_parts, axis=0), iters)
        if g is not None:
            out = out + (np.asarray(codes[0]),)
        return out
