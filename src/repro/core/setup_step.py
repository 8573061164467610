"""Compile-once, device-resident multigrid setup: bucketed super-steps.

The eager setup loop in ``core.hierarchy`` pays a fresh XLA compile for
every level's exact shapes and blocks on a host round-trip for every
data-dependent decision (elimination count, coarsening ratio, capacity
shrink) — the serialization the paper's "everything is an SpMV"
formulation exists to avoid, and the cost center LAMG and the GPU UA-AMG
work (Brannick et al.) both report for aggregation-based setup.

This module restructures the per-level work into a handful of jitted
**super-steps** whose compiled programs are keyed only on power-of-two
*capacity buckets*, never on exact level sizes:

* ``elim``        — Alg 1 candidate selection fused with the
  Schur-complement level construction (the default
  ``elim_sizing="conservative"`` path: F-slot arrays sized at the vertex
  bucket instead of the fetched count, so selection and construction run
  as ONE program with ONE batched decision fetch),
* ``elim_select`` / ``elim_build`` — the two-fetch split of the same work
  (``elim_sizing="exact"``: F-slots sized at ``bucket(n_elim)``, which
  needs the count on host before construction),
* ``agg``         — strength sweeps, Alg 2 voting rounds through the
  vectorised ELL vote reduction (``repro.kernels.agg_vote.ref``; overlong
  rows spill to the staged segment reduction and lex-merge exactly),
  device-side ``cumsum`` renumbering, edge-contraction coalesce, and the
  λmax power iteration, fused into one program,
* ``rebucket``    — shrink the carry to the next level's buckets,
* ``ingest``      — degree computation for the padded finest level.

A level of logical size ``n`` with ``nnz`` edges is carried as arrays
padded to ``(bucket(n), bucket(nnz))`` with the *logical* size passed as a
traced scalar; padding vertices are isolated (degree 0, sentinel edge ids
``= n_cap``) and masked out of the few places where isolated vertices
behave differently (elimination candidacy, vote state init, renumbering
roots, mean/rescale reductions). Two levels — or two graphs — that land in
the same buckets therefore reuse one compiled program per step: the
compiled-function registry below records hits/misses, and a second
same-bucket graph triggers **zero** new super-step compiles
(``tests/test_setup_superstep.py`` pins this).

Host contact is reduced to the level-advance decisions: ONE batched
scalar ``device_get`` per constructed level (eliminated count + coarse
nnz after the fused ``elim`` step; coarse size + nnz + renumbering
invariant after ``agg``), plus the entry ingest probe — everything else,
including renumbering and contraction, stays on device. Inputs already in
padding-last layout (any coalesce output qualifies) take a jitted
device-side compaction instead of the old host-NumPy pass. The produced
hierarchy is equivalent to the eager path's (same level sizes and kinds,
same PCG iteration counts); exact-shape wrapping into
``GraphLevel``/``Transfer`` objects happens once at the end with plain
slices.

The per-level programs are created through a :class:`SuperstepBuilders`
factory; ``repro.dist.setup`` subclasses it to run the semiring
reductions of Alg 1 and Alg 2 as ``shard_map`` programs over the 2D edge
partition — the loop, the bucketing policy and the sync contract are
shared verbatim between the serial and distributed setups.

**Batch-rank polymorphism.** The setup loop itself is written once, as a
*plan*: a generator (:func:`_setup_plan`) that yields step/fetch requests
and never touches the registry directly. ``build_hierarchy_superstep``
drives one plan, executing each request immediately — behaviourally
identical to the pre-plan loop. ``build_hierarchy_superstep_batch``
drives N plans in lockstep rounds: requests for the same ``(step,
bucket-key)`` are stacked and executed as ONE ``jax.vmap``-ped registry
program (amortizing dispatch and compile lookups across graphs), and
every plan waiting on host scalars shares ONE batched ``device_get`` per
round. Per-graph level-advance decisions stay per-plan host control
flow, so each hierarchy in the batch is **bit-identical** to its
single-graph build (``tests/test_setup_batch.py`` pins this); graphs
whose decisions diverge simply fall out of the shared group for the
affected rounds and keep building correctly on their own.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.aggregation import (aggregate, quantise_strength,
                                    renumber_device, vote_edge_reduce)
from repro.core.coarsen import AggregationLevel, contract_arrays
from repro.core.elimination import (EliminationLevel, schur_arrays,
                                    select_eliminated)
from repro.core.graph import GraphLevel, graph_from_adjacency, pow2_bucket
from repro.core.smoothers import estimate_lambda_max
from repro.core.strength import STRENGTH_METRICS
from repro.sparse.coo import COO, coalesce_arrays
from repro.sparse.ell import ELL, ell_layout_traced
from repro.testing import faults


# ----------------------------------------------------------------------------
# Compiled-step registry: one jitted program per (step, bucket-key).
# ----------------------------------------------------------------------------

_CACHE: dict = {}
# The compile/call/host-sync ledger lives in ``repro.obs`` under
# ``setup.calls.<step>``, ``setup.compiles.<step>`` and ``setup.host_syncs``.
_LEDGER = "setup."


def reset_counters() -> None:
    """Zero the compile/call/host-sync counters (the cache stays warm)."""
    obs.reset(_LEDGER)


def clear_cache() -> None:
    """Drop every compiled super-step (cold-start benchmarking)."""
    _CACHE.clear()


def counters() -> dict:
    """Snapshot: per-step ``{"compiles", "calls"}`` plus batched host
    fetches since the last :func:`reset_counters`.

    ``compiles`` counts registry misses. Each registry entry is a
    ``jax.jit`` that only ever sees one set of shapes (its bucket), so a
    miss is exactly one XLA compile and a hit is a cache reuse.
    """
    steps: dict = {}
    host_syncs = 0
    for name, n in obs.counters().items():
        if not name.startswith(_LEDGER):
            continue
        kind, _, step = name[len(_LEDGER):].partition(".")
        if kind == "host_syncs":
            host_syncs = n
        else:
            steps.setdefault(step, dict(compiles=0, calls=0))[kind] = n
    return dict(steps=steps, host_syncs=host_syncs)


def _step(name: str, key, builder):
    """The registry program for ``(name, key)``, built on a miss; calling
    it dispatches inside a ``repro.setup.<name>`` span."""
    obs.count(f"{_LEDGER}calls.{name}")
    fn = _CACHE.get((name, key))
    if fn is None:
        obs.count(f"{_LEDGER}compiles.{name}")
        fn = _CACHE[(name, key)] = builder()
    return functools.partial(_dispatch, f"repro.setup.{name}", fn)


def _dispatch(span_name: str, fn, *args):
    with obs.span(span_name):
        return fn(*args)


def _fetch(*vals):
    """One batched host sync for this decision point."""
    obs.count(f"{_LEDGER}host_syncs")
    return obs.fetch(vals)


def bucket(n: int, floor: int = 0) -> int:
    """Round up to the next power of two, with an optional floor.

    The floor (``SetupConfig.setup_bucket_floor``, itself a power of two)
    widens compile reuse: every level smaller than the floor shares the
    floor-sized programs instead of compiling tiny per-size variants.
    Delegates to ``graph.pow2_bucket`` — the ONE bucket rule shared with
    the strength/λmax RNG padding and the eager path's capacity shrink
    (the eager/super-step bit-identity depends on these agreeing).
    """
    return pow2_bucket(n, floor)


# ----------------------------------------------------------------------------
# Super-step builders. Each returns a jitted function whose shapes are fully
# determined by the bucket key; logical sizes ride as traced scalars.
# ----------------------------------------------------------------------------

def _plevel(row, col, val, deg) -> GraphLevel:
    """Bucket-padded arrays as a real GraphLevel of n_cap isolated-padded
    vertices (sentinel ids == n_cap keep every segment reduction exact)."""
    n_cap = deg.shape[0]
    return GraphLevel(adj=COO(row, col, val, n_cap, n_cap), deg=deg)


def _build_ingest(n_cap: int, e_cap: int):
    def step(row, col, val):
        valid = row < n_cap
        return jax.ops.segment_sum(jnp.where(valid, val, 0), row,
                                   num_segments=n_cap)

    return jax.jit(step)


def _build_ingest_fast(raw_cap: int, n_cap: int, e_cap: int):
    """Device-side compaction for inputs already in padding-last layout
    (any coalesce output qualifies): renormalise sentinels to the carry
    convention and resize ``raw_cap -> e_cap`` with a slice/pad — no
    host-NumPy pass, no full-array transfer."""
    def step(row, col, val, n0):
        valid = row < n0
        r = jnp.where(valid, row, n_cap).astype(jnp.int32)
        c = jnp.where(valid, col, n_cap).astype(jnp.int32)
        v = jnp.where(valid, val, 0)
        if e_cap <= raw_cap:
            # sound only for padding-last inputs (the probe checked).
            r, c, v = r[:e_cap], c[:e_cap], v[:e_cap]
        else:
            pad = e_cap - raw_cap
            r = jnp.concatenate([r, jnp.full((pad,), n_cap, jnp.int32)])
            c = jnp.concatenate([c, jnp.full((pad,), n_cap, jnp.int32)])
            v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
        deg = jax.ops.segment_sum(v, r, num_segments=n_cap)
        return r, c, v, deg

    return jax.jit(step)


@jax.jit
def _ingest_probe(row, n0):
    """(nnz, padding-last?) of a raw edge list — the one-scalar-pair probe
    that decides between the device compaction fast path and the host
    fallback. Plain jit (keyed on the raw capacity), not a registry step."""
    valid = row < n0
    nnz = jnp.sum(valid.astype(jnp.int32))
    plast = jnp.all(valid == (jnp.arange(row.shape[0]) < nnz))
    return nnz, plast


def _build_probe(raw_cap: int):
    """Registry form of the ingest probe, for the batched driver (a
    single-graph build keeps the plain-jit ``_ingest_probe`` and its
    uncounted status)."""
    def step(row, n0):
        valid = row < n0
        nnz = jnp.sum(valid.astype(jnp.int32))
        plast = jnp.all(valid == (jnp.arange(raw_cap) < nnz))
        return nnz, plast

    return jax.jit(step)


def _build_elim_select(n_cap: int, e_cap: int, max_degree: int,
                       select_fn=None):
    def step(row, col, val, deg, n):
        if select_fn is None:
            level = _plevel(row, col, val, deg)
            elim = select_eliminated(level, max_degree, n_valid=n)
        else:
            elim = select_fn(row, col, val, deg, n)
        return elim, jnp.sum(elim.astype(jnp.int32))

    return jax.jit(step)


def _build_elim_build(n_cap: int, e_cap: int, f_cap: int, max_degree: int):
    # Schur fill cliques come from an [n_cap, max_degree] neighbour table —
    # the width must cover the selection rule's degree bound. The algebra
    # itself is elimination.schur_arrays, shared with the eager path.
    w = max_degree

    def step(row, col, val, deg, n, elim):
        level = _plevel(row, col, val, deg)
        return schur_arrays(level.adj, level.deg, elim, n, f_cap=f_cap,
                            max_degree=max_degree,
                            out_capacity=e_cap + f_cap * w * w,
                            sentinel=n_cap)

    return jax.jit(step)


def _build_elim_fused(n_cap: int, e_cap: int, max_degree: int,
                      select_fn=None):
    """Selection + Schur construction as ONE program (the default
    ``elim_sizing="conservative"`` path): F-slot arrays are sized at the
    vertex bucket ``n_cap`` — a conservative capacity that never depends
    on the eliminated count, so no host fetch separates the two phases
    and the whole elimination level costs one batched decision fetch
    (count + coarse nnz, after the fact). The count-independent sizing
    also erases ``f_cap`` from the compile key: every elim level of a
    bucket shares one program."""
    w = max_degree

    def step(row, col, val, deg, n):
        if select_fn is None:
            level = _plevel(row, col, val, deg)
            elim = select_eliminated(level, max_degree, n_valid=n)
        else:
            elim = select_fn(row, col, val, deg, n)
        out = schur_arrays(COO(row, col, val, n_cap, n_cap), deg, elim, n,
                           f_cap=n_cap, max_degree=max_degree,
                           out_capacity=e_cap + n_cap * w * w,
                           sentinel=n_cap)
        return elim, out

    return jax.jit(step)


def _build_agg(n_cap: int, e_cap: int, cfg, vote_factory=None):
    strength_fn = STRENGTH_METRICS[cfg.strength_metric]
    acfg = cfg.aggregation
    vote_w = cfg.setup_ell_width
    ell_sweeps = cfg.setup_ell_sweeps and cfg.matvec_backend != "coo"

    def step(row, col, val, deg, n, lam_v0):
        level = _plevel(row, col, val, deg)
        # ONE traced hybrid layout serves the whole step: the fused vote
        # reduction always, and (opt-in) the strength sweeps' SpMM.
        lay = ell_layout_traced(row, col, n_cap, vote_w)
        if ell_sweeps:
            # Attach the ELL twin BEFORE the strength sweeps, so setup's
            # dominant SpMV (the K damped-Jacobi relaxations) runs the
            # fused fixed-width path via matvec.level_spmm — not just the
            # post-setup solve. Execution-format change: summation order
            # differs from the COO segment-sum, hence the opt-in knob
            # (SetupConfig.setup_ell_sweeps).
            from repro.sparse.matvec import resolve_ell_mode

            ell = ELL(lay.col_table, lay.table(val), n_cap)
            rem = COO(lay.spill_row, lay.spill_col, lay.spill(val),
                      n_cap, n_cap)
            level = dataclasses.replace(
                level, ell=ell, ell_rem=rem,
                ell_mode=resolve_ell_mode(cfg.matvec_backend))
        strength = strength_fn(level, n_vectors=cfg.strength_vectors,
                               n_sweeps=cfg.strength_sweeps, seed=cfg.seed,
                               n_valid=n)
        # Quantised strengths in the hybrid layout, built once and reused
        # across every scanned vote round (the sq tables are round
        # invariants; only the state vector changes).
        sq = quantise_strength(strength, acfg)
        sq_table = lay.table(sq)
        sq_spill = lay.spill(sq)
        if vote_factory is None:
            def edge_reduce(state):
                return vote_edge_reduce(lay, sq_table, sq_spill, state,
                                        acfg)
        else:
            edge_reduce = vote_factory(lay, sq_table, sq_spill)
        aggs, _state = aggregate(level, None, acfg, n_valid=n,
                                 edge_reduce=edge_reduce)
        coarse_id, n_c, ok = renumber_device(aggs, n_valid=n)
        co_row, co_col, co_val, co_nnz = contract_arrays(
            level.adj, coarse_id, n_c, sentinel=n_cap)
        co_deg = jax.ops.segment_sum(co_val, co_row, num_segments=n_cap)
        # The power-iteration start vector rides in as an argument (see
        # estimate_lambda_max: drawn in-program it would be a trace-time
        # constant, and the batched vmapped program would fold its masked
        # reductions differently from this unbatched one).
        lam = estimate_lambda_max(level, n_valid=n, v0=lam_v0)
        return dict(coarse_id=coarse_id, n_c=n_c, ok=ok, co_row=co_row,
                    co_col=co_col, co_val=co_val, co_deg=co_deg,
                    co_nnz=co_nnz, lam=lam)

    return jax.jit(step)


def _build_rebucket(n_from: int, e_from: int, n_to: int, e_to: int):
    def step(row, col, val, deg):
        if e_to <= e_from:
            r, c, v = row[:e_to], col[:e_to], val[:e_to]
        else:
            pad = e_to - e_from
            r = jnp.concatenate([row, jnp.full((pad,), n_from, jnp.int32)])
            c = jnp.concatenate([col, jnp.full((pad,), n_from, jnp.int32)])
            v = jnp.concatenate([val, jnp.zeros((pad,), val.dtype)])
        r = jnp.where(r >= n_to, n_to, r).astype(jnp.int32)
        c = jnp.where(c >= n_to, n_to, c).astype(jnp.int32)
        return r, c, v, deg[:n_to]

    return jax.jit(step)


# ----------------------------------------------------------------------------
# Builder factory: the extension seam between the serial and distributed
# setups. The distributed subclass (repro.dist.setup.DistSuperstepBuilders)
# tags every registry key with its mesh and swaps the two semiring-SpMV
# hooks for shard_map programs over the 2D edge partition; everything else
# — the loop, bucketing, sync contract, wrap — is shared.
# ----------------------------------------------------------------------------

class SuperstepBuilders:
    """Per-bucket jitted super-step programs, registry-cached."""

    tag: tuple = ()          # extra registry-key components (dist: the mesh)

    def __init__(self, cfg):
        self.cfg = cfg

    # -- hooks the distributed subclass overrides ----------------------
    def select_fn(self, n_cap: int, e_cap: int):
        """Optional override of the Alg 1 selection reduction:
        ``(row, col, val, deg, n) -> elim`` or None for the serial
        ``select_eliminated``."""
        return None

    def vote_factory(self, n_cap: int, e_cap: int):
        """Optional override of the Alg 2 per-round edge ⊕:
        ``(layout, sq_table, sq_spill) -> (state -> (key, id))`` or None
        for the serial fused vote reduction."""
        return None

    # -- steps ----------------------------------------------------------
    # Every per-level program is addressed as ``(method, params)`` where
    # ``params`` is the bucket tuple. ``step`` resolves that address to a
    # registry-cached jitted program — unbatched (``batch=1``, the exact
    # programs the pre-plan loop built, same names and keys) or lifted
    # over a leading graph axis with ``jax.vmap`` for the batched driver
    # (registered under ``<name>@batch`` so compile accounting stays
    # per-rank). The named accessors below are kept as the readable
    # spelling for single-step callers.

    def _agg_key(self, n_cap: int, e_cap: int):
        cfg = self.cfg
        ell_sweeps = cfg.setup_ell_sweeps and cfg.matvec_backend != "coo"
        return self.tag + (n_cap, e_cap, cfg.strength_metric,
                           cfg.strength_vectors, cfg.strength_sweeps,
                           cfg.seed, cfg.aggregation, cfg.setup_ell_width,
                           ell_sweeps and cfg.matvec_backend)

    def _key(self, method: str, params: tuple):
        if method == "agg":
            return self._agg_key(*params)
        if method in ("elim", "elim_select", "elim_build"):
            return self.tag + params + (self.cfg.elim_max_degree,)
        return self.tag + params

    def _make(self, method: str, params: tuple):
        md = self.cfg.elim_max_degree
        if method == "probe":
            return _build_probe(*params)
        if method == "ingest":
            return _build_ingest(*params)
        if method == "ingest_fast":
            return _build_ingest_fast(*params)
        if method == "elim":
            n_cap, e_cap = params
            return _build_elim_fused(n_cap, e_cap, md,
                                     select_fn=self.select_fn(n_cap, e_cap))
        if method == "elim_select":
            n_cap, e_cap = params
            return _build_elim_select(n_cap, e_cap, md,
                                      select_fn=self.select_fn(n_cap, e_cap))
        if method == "elim_build":
            n_cap, e_cap, f_cap = params
            return _build_elim_build(n_cap, e_cap, f_cap, md)
        if method == "agg":
            n_cap, e_cap = params
            return _build_agg(n_cap, e_cap, self.cfg,
                              vote_factory=self.vote_factory(n_cap, e_cap))
        if method == "rebucket":
            return _build_rebucket(*params)
        raise KeyError(f"unknown super-step method {method!r}")

    def step(self, method: str, params: tuple, batch: int = 1):
        if batch == 1:
            if method == "probe":
                # plain jit, keyed on the raw capacity by shape; stays out
                # of the registry ledger like the pre-plan probe.
                return _ingest_probe
            return _step(method, self._key(method, params),
                         lambda: self._make(method, params))
        return _step(method + "@batch",
                     self._key(method, params) + ("batch", batch),
                     lambda: _batch_program(self._make(method, params),
                                            batch))

    def ingest(self, n_cap: int, e_cap: int):
        return self.step("ingest", (n_cap, e_cap))

    def ingest_fast(self, raw_cap: int, n_cap: int, e_cap: int):
        return self.step("ingest_fast", (raw_cap, n_cap, e_cap))

    def elim_select(self, n_cap: int, e_cap: int):
        return self.step("elim_select", (n_cap, e_cap))

    def elim_build(self, n_cap: int, e_cap: int, f_cap: int):
        return self.step("elim_build", (n_cap, e_cap, f_cap))

    def elim_fused(self, n_cap: int, e_cap: int):
        return self.step("elim", (n_cap, e_cap))

    def agg(self, n_cap: int, e_cap: int):
        return self.step("agg", (n_cap, e_cap))

    def rebucket(self, n_from: int, e_from: int, n_to: int, e_to: int):
        return self.step("rebucket", (n_from, e_from, n_to, e_to))


# ----------------------------------------------------------------------------
# Exact-shape wrapping (end of setup): plain slices, no super-step compiles.
# ----------------------------------------------------------------------------

def _exact_coarse(spec: dict) -> GraphLevel:
    n_c, nnz_c = spec["n_c"], spec["nnz_c"]
    out = spec["out"]
    # NO floor here: the bucket floor exists for super-step compile reuse
    # during setup; the wrapped solve-phase levels always get exact
    # power-of-two capacities (same as the eager path's _shrink) so the
    # per-level SpMV cost decays geometrically down the hierarchy and
    # solve-phase jit programs share bucket-shaped keys. Slice when the
    # carry is larger, pad with sentinels when bucket(nnz) exceeds the
    # carry (possible for elim levels, whose coalesce output length
    # e_cap + w²·f_cap is not itself a power of two).
    cap = bucket(max(nnz_c, 1))
    avail = int(out["co_row"].shape[0])
    take = min(cap, avail)          # coalesce output is padding-last
    r = jnp.minimum(out["co_row"][:take], n_c).astype(jnp.int32)
    c = jnp.minimum(out["co_col"][:take], n_c).astype(jnp.int32)
    v = out["co_val"][:take]
    if cap > avail:
        pad = cap - avail
        r = jnp.concatenate([r, jnp.full((pad,), n_c, jnp.int32)])
        c = jnp.concatenate([c, jnp.full((pad,), n_c, jnp.int32)])
        v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
    return GraphLevel(adj=COO(r, c, v, max(n_c, 1), max(n_c, 1)),
                      deg=out["co_deg"][:max(n_c, 1)])


def _wrap_elim(fine: GraphLevel, spec: dict) -> EliminationLevel:
    n, n_f, n_c = spec["n"], spec["n_f"], spec["n_c"]
    out = spec["out"]
    coarse = _exact_coarse(spec)
    pad = out["p_row"] >= n_f
    p_f = COO(jnp.where(pad, n_f, out["p_row"]).astype(jnp.int32),
              jnp.where(pad, n_f, out["p_col"]).astype(jnp.int32),
              out["p_val"], max(n_f, 1), max(n_c, 1))
    return EliminationLevel(
        fine=fine, coarse=coarse, elim_mask=spec["elim"][:n],
        c_index=out["c_index"][:n], f_index=out["f_index"][:n],
        f_vertices=out["f_vertices"][:max(n_f, 1)].astype(jnp.int32),
        p_f=p_f, inv_deg_f=out["inv_deg_f"][:max(n_f, 1)])


def _wrap_agg(fine: GraphLevel, spec: dict) -> AggregationLevel:
    coarse = _exact_coarse(spec)
    return AggregationLevel(fine=fine, coarse=coarse,
                            coarse_id=spec["out"]["coarse_id"][:spec["n"]])


# ----------------------------------------------------------------------------
# The setup loop.
# ----------------------------------------------------------------------------

def _batch_program(fn, batch: int):
    """Lift a single-graph super-step to a stacked batch of ``batch``.

    Takes/returns the single-graph signature with a leading graph axis on
    every argument and output. Two lowerings, picked per backend:

    * ``unroll`` (CPU) — trace ``fn`` once per member inside ONE jitted
      program. Each member keeps its exact unbatched HLO (bit-identical
      outputs by construction) and the members are data-independent
      subgraphs, so a multi-core host runtime executes them concurrently;
      the measured vmapped gather/scatter lowerings are ~1.3x slower than
      N unbatched runs on CPU, which this avoids.
    * ``vmap`` (accelerators) — one ``jax.vmap``-ped program whose batched
      ops fill the wide units. Requires the RNG-seeded λmax start vector
      to enter as a program *argument* (see ``estimate_lambda_max``) to
      stay bit-identical to the unbatched path.
    """
    if jax.default_backend() == "cpu":
        def run(*stacked):
            outs = [fn(*(a[i] for a in stacked)) for i in range(batch)]
            return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)

        return jax.jit(run)
    return jax.jit(jax.vmap(fn))


_LAM_V0: dict = {}


def _lam_seed_vector(n_cap: int):
    """The λmax power-iteration start vector for a vertex bucket, drawn
    once per capacity (deterministic: seed 0 is ``estimate_lambda_max``'s
    default) and fed to the agg step as a program argument."""
    v = _LAM_V0.get(n_cap)
    if v is None:
        v = _LAM_V0[n_cap] = jax.random.normal(jax.random.PRNGKey(0),
                                               (n_cap,))
    return v


def _validate_setup_cfg(cfg) -> None:
    floor = cfg.setup_bucket_floor
    if floor < 0 or (floor & (floor - 1)):
        # A non-power floor would produce mixed buckets (no reuse) and
        # hidden re-padding in the strength/λmax RNG shapes.
        raise ValueError(f"setup_bucket_floor must be 0 or a power of two, "
                         f"got {floor!r}")
    if cfg.elim_sizing not in ("conservative", "exact"):
        raise ValueError(f"elim_sizing must be 'conservative' or 'exact', "
                         f"got {cfg.elim_sizing!r}")


def _setup_plan(adj: COO, cfg):
    """The setup loop as a *plan*: a generator yielding execution
    requests, returning the finished ``Hierarchy`` via ``StopIteration``.

    Requests are ``("step", method, params, args)`` — run the registry
    program addressed by ``(method, params)`` on ``args`` — and
    ``("fetch", device_scalars)`` — one batched host sync. The driver
    sends the result back in. Keeping ALL device work and host syncs
    behind requests is what makes the loop batch-rank polymorphic: the
    single driver executes requests one plan at a time (the pre-plan
    behaviour, bit for bit), the batch driver stacks same-address step
    requests from N plans into one vmapped program and merges their
    fetches into one ``device_get`` per round.
    """
    from repro.core.hierarchy import Hierarchy, attach_ell_transfers

    floor = cfg.setup_bucket_floor
    n0 = adj.n_rows
    # Entry ingest. The probe (one batched scalar fetch) detects inputs
    # already in padding-last layout — any coalesce output qualifies —
    # and routes them through a jitted device-side compaction; only
    # arbitrary-order inputs fall back to the host-NumPy pass (one
    # full-array round-trip, counted in the sync ledger).
    probe = yield ("step", "probe", (int(adj.capacity),),
                   (adj.row, jnp.asarray(n0, jnp.int32)))
    nnz0, plast = yield ("fetch", tuple(probe))
    nnz0 = int(nnz0)
    n_cap, e_cap = bucket(n0, floor), bucket(max(nnz0, 1), floor)
    if bool(plast):
        row_d, col_d, val_d, deg_d = yield (
            "step", "ingest_fast", (int(adj.capacity), n_cap, e_cap),
            (adj.row, adj.col, adj.val, jnp.asarray(n0, jnp.int32)))
    else:
        row_h, col_h, val_h = (
            np.asarray(a) for a in
            (yield ("fetch", (adj.row, adj.col, adj.val))))
        mask = row_h < n0
        row_p = np.full(e_cap, n_cap, np.int32)
        col_p = np.full(e_cap, n_cap, np.int32)
        val_p = np.zeros(e_cap, val_h.dtype)
        row_p[:nnz0] = row_h[mask]
        col_p[:nnz0] = col_h[mask]
        val_p[:nnz0] = val_h[mask]
        row_d, col_d = jnp.asarray(row_p), jnp.asarray(col_p)
        val_d = jnp.asarray(val_p)
        deg_d = yield ("step", "ingest", (n_cap, e_cap),
                       (row_d, col_d, val_d))

    cur_n = n0
    n_d = jnp.asarray(cur_n, jnp.int32)
    specs: list = []

    def advance(out_row, out_col, out_val, out_deg, n_c, nnz_c):
        # A nested generator (entered with ``yield from``) so the
        # rebucket step routes through the driver like every other one.
        nonlocal row_d, col_d, val_d, deg_d, n_cap, e_cap, cur_n, n_d
        n_to, e_to = bucket(n_c, floor), bucket(max(nnz_c, 1), floor)
        e_from = int(out_row.shape[0])
        if (n_to, e_to) != (n_cap, e_from):
            out_row, out_col, out_val, out_deg = yield (
                "step", "rebucket", (n_cap, e_from, n_to, e_to),
                (out_row, out_col, out_val, out_deg))
        row_d, col_d, val_d, deg_d = out_row, out_col, out_val, out_deg
        n_cap, e_cap, cur_n = n_to, e_to, n_c
        n_d = jnp.asarray(cur_n, jnp.int32)

    while cur_n > cfg.coarsest_size and len(specs) < cfg.max_levels:
        progressed = False

        # --- low-degree elimination pass(es) ---------------------------
        for _ in range(cfg.elim_rounds_per_level):
            if cur_n <= cfg.coarsest_size:
                break
            if cfg.elim_sizing == "conservative":
                # Fused select+build; ONE batched decision fetch per elim
                # level. A rejected pass wastes one speculative build —
                # rejections are terminal in practice (the loop breaks).
                elim, out = yield ("step", "elim", (n_cap, e_cap),
                                   (row_d, col_d, val_d, deg_d, n_d))
                n_elim, nnz_c = yield ("fetch", (out["n_f"], out["co_nnz"]))
                n_elim, nnz_c = int(n_elim), int(nnz_c)
                if n_elim < max(cfg.elim_min_fraction * cur_n, 1) \
                        or n_elim == cur_n:
                    break
            else:
                elim, n_elim_d = yield ("step", "elim_select",
                                        (n_cap, e_cap),
                                        (row_d, col_d, val_d, deg_d, n_d))
                (n_elim,) = yield ("fetch", (n_elim_d,))  # decision fetch
                n_elim = int(n_elim)
                if n_elim < max(cfg.elim_min_fraction * cur_n, 1) \
                        or n_elim == cur_n:
                    break
                f_cap = bucket(n_elim, floor)
                out = yield ("step", "elim_build", (n_cap, e_cap, f_cap),
                             (row_d, col_d, val_d, deg_d, n_d, elim))
                (nnz_c,) = yield ("fetch", (out["co_nnz"],))  # sizing fetch
                nnz_c = int(nnz_c)
            specs.append(("elim", dict(n=cur_n, n_f=n_elim,
                                       n_c=cur_n - n_elim, nnz_c=nnz_c,
                                       elim=elim, out=out)))
            yield from advance(out["co_row"], out["co_col"], out["co_val"],
                               out["co_deg"], cur_n - n_elim, nnz_c)
            progressed = True

        if cur_n <= cfg.coarsest_size:
            break

        # --- aggregation level -----------------------------------------
        out = yield ("step", "agg", (n_cap, e_cap),
                     (row_d, col_d, val_d, deg_d, n_d,
                      _lam_seed_vector(n_cap)))
        # decision fetch: coarse size (ratio check), coarse nnz (the old
        # _shrink sync) and the renumbering invariant, in ONE device_get.
        n_c, nnz_c, ok = yield ("fetch", (out["n_c"], out["co_nnz"],
                                          out["ok"]))
        assert bool(ok), "aggregate pointers must hit roots"
        n_c, nnz_c = int(n_c), int(nnz_c)
        if n_c >= cur_n * cfg.min_coarsen_ratio:
            if not progressed:
                break                 # stuck: neither mechanism coarsens
            continue
        specs.append(("agg", dict(n=cur_n, n_c=n_c, nnz_c=nnz_c, out=out)))
        yield from advance(out["co_row"], out["co_col"], out["co_val"],
                           out["co_deg"], n_c, nnz_c)

    # --- exact-shape wrap + dense bottom solve --------------------------
    level = graph_from_adjacency(adj)
    transfers = []
    lam_maxes = []
    for kind, spec in specs:
        if kind == "elim":
            t = _wrap_elim(level, spec)
            lam_maxes.append(jnp.asarray(0.0))
        else:
            t = _wrap_agg(level, spec)
            lam_maxes.append(faults.site("setup.lambda_max",
                                         spec["out"]["lam"]))
        transfers.append(t)
        level = t.coarse

    from repro.core.hierarchy import coarse_inverse

    # ONE fetch (the sync-ledger contract): the alpha scalar plus the
    # coarse index arrays the nullspace/component analysis needs.
    alpha, row_h, col_h = yield ("fetch", (jnp.mean(level.deg),
                                           level.adj.row, level.adj.col))
    coarse_inv = coarse_inverse(level, float(alpha) or 1.0,
                                np.asarray(row_h), np.asarray(col_h))
    return Hierarchy(transfers=attach_ell_transfers(transfers, cfg),
                     lam_maxes=tuple(lam_maxes), coarse_inv=coarse_inv)


def _exec_request(steps: SuperstepBuilders, req):
    """Execute one plan request unbatched (the single-graph semantics)."""
    if req[0] == "fetch":
        return _fetch(*req[1])
    _, method, params, args = req
    return steps.step(method, params)(*args)


def build_hierarchy_superstep(adj: COO, cfg,
                              steps: SuperstepBuilders | None = None):
    """Compile-once device-resident setup. Same contract (and an
    equivalent hierarchy: level sizes, kinds, PCG iteration counts) as
    ``core.hierarchy.build_hierarchy_eager``.

    ``steps``: the super-step program factory; defaults to the serial
    :class:`SuperstepBuilders`. ``repro.dist.setup`` passes its
    mesh-tagged subclass, which runs the Alg 1/Alg 2 semiring reductions
    sharded over the 2D edge partition — the plan (including the
    per-level sync contract) is shared between the two.
    """
    _validate_setup_cfg(cfg)
    if steps is None:
        steps = SuperstepBuilders(cfg)
    plan = _setup_plan(adj, cfg)
    payload = None
    while True:
        try:
            req = plan.send(payload)
        except StopIteration as stop:
            return stop.value
        payload = _exec_request(steps, req)


def build_hierarchy_superstep_batch(adjs, cfg,
                                    steps: SuperstepBuilders | None = None
                                    ) -> list:
    """Drive N setup plans in lockstep rounds: one program, N hierarchies.

    Each round, requests for the same ``(step, bucket-key)`` address are
    stacked along a new leading graph axis and executed as ONE
    ``jax.vmap``-ped registry program, and every plan waiting on host
    scalars joins ONE batched ``device_get``. Per-graph level-advance
    decisions remain ordinary host control flow inside each plan, so
    every returned hierarchy is **bit-identical** to its single-graph
    ``build_hierarchy_superstep`` build. Graphs whose decisions diverge
    (extra elimination round, different bucket trajectory) drop out of
    the shared group for the affected rounds — they still build
    correctly, just without the batching win; same-family batches under
    a ``setup_bucket_floor`` stay grouped end to end.
    """
    adjs = list(adjs)
    _validate_setup_cfg(cfg)
    if steps is None:
        steps = SuperstepBuilders(cfg)
    plans = [_setup_plan(adj, cfg) for adj in adjs]
    out: list = [None] * len(plans)
    payload: list = [None] * len(plans)
    live = list(range(len(plans)))
    while live:
        reqs = {}
        nxt = []
        for i in live:
            try:
                reqs[i] = plans[i].send(payload[i])
                payload[i] = None
                nxt.append(i)
            except StopIteration as stop:
                out[i] = stop.value
        live = nxt

        # Every plan waiting on host scalars shares ONE batched fetch.
        fetchers = [i for i in live if reqs[i][0] == "fetch"]
        if fetchers:
            flat = [v for i in fetchers for v in reqs[i][1]]
            vals = _fetch(*flat)
            pos = 0
            for i in fetchers:
                k = len(reqs[i][1])
                payload[i] = tuple(vals[pos:pos + k])
                pos += k

        # Same-(method, params) step requests run as one vmapped program.
        groups: dict = {}
        for i in live:
            if reqs[i][0] == "step":
                _, method, params, _args = reqs[i]
                groups.setdefault((method, params), []).append(i)
        for (method, params), members in groups.items():
            if len(members) == 1:
                i = members[0]
                payload[i] = steps.step(method, params)(*reqs[i][3])
                continue
            n_args = len(reqs[members[0]][3])
            stacked = tuple(jnp.stack([reqs[i][3][a] for i in members])
                            for a in range(n_args))
            outs = steps.step(method, params, batch=len(members))(*stacked)
            for slot, i in enumerate(members):
                payload[i] = jax.tree_util.tree_map(
                    lambda x, s=slot: x[s], outs)
    return out
