"""Setup-phase benchmark: eager host-driven loop vs bucketed super-steps.

PR 3 moved the *solve* hot path onto the Pallas hybrid ELL+COO kernels, so
total time is dominated by the *setup* phase the paper spends most of its
effort on (Alg 1 elimination, Alg 2 aggregation, Galerkin contraction) —
the cost center LAMG also reports for aggregation-based Laplacian solvers.
This benchmark records the payoff of the compile-once restructuring
(``repro.core.setup_step``):

* wall time of ``build_hierarchy`` in both ``setup_mode``s, cold (first
  build in the process) and warm (a second build: the super-step path
  reuses every bucket-keyed compiled program; the eager path re-traces
  per exact level shape),
* host-sync counts: batched decision fetches for the super-step path vs
  ``jax.device_get`` round-trips of the eager path,
* the jit-cache hit/miss ledger across two *same-bucket* graphs (same
  topology, reseeded weights): the second graph must trigger **zero**
  new super-step compiles.

Running this module directly — or through ``benchmarks/run.py --only
setup`` — writes the stable-schema ``BENCH_setup.json`` at the repo root
so the setup-perf trajectory is recorded in-tree.
"""

from __future__ import annotations

import json
import os
import time

import jax

SCHEMA = "repro.bench.setup/v1"
ROOT_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_setup.json")


def _graphs(scale: float):
    from repro.graphs.generators import (barabasi_albert, ensure_connected,
                                         grid_2d)

    side = max(int(28 * (scale / 0.12) ** 0.5), 16)
    n_ba = max(int(1400 * scale / 0.12), 400)
    return [
        ("grid_2d", lambda seed=0: ensure_connected(
            *grid_2d(side, side, weighted=True, seed=seed))),
        ("barabasi_albert", lambda seed=0: ensure_connected(
            *barabasi_albert(n_ba, m=3, seed=seed, weighted=True))),
    ]


def _count_device_gets(fn):
    """Run ``fn`` with jax.device_get instrumented; return (result, count).

    This is how the *eager* path's host syncs are tallied — every one of
    its scalar decisions and array pulls goes through ``device_get``. The
    super-step path reports its own batched-fetch counter instead.
    """
    real = jax.device_get
    count = [0]

    def counting(x):
        count[0] += 1
        return real(x)

    jax.device_get = counting
    try:
        out = fn()
    finally:
        jax.device_get = real
    return out, count[0]


def _level_sig(h) -> list:
    from repro.core.hierarchy import hierarchy_stats

    return [[r["kind"], r["n"], r["nnz"]]
            for r in hierarchy_stats(h)["levels"]]


def bench_setup(scale: float = 0.12) -> dict:
    from repro.core import setup_step as ss
    from repro.core.hierarchy import (SetupConfig, build_hierarchy,
                                      build_hierarchy_eager)
    from repro.graphs.generators import to_laplacian_coo

    cfg_eager = SetupConfig(setup_mode="eager")
    cfg_super = SetupConfig()

    rows = []
    for name, gen in _graphs(scale):
        n, r, c, v = gen()
        adj = to_laplacian_coo(n, r, c, v)
        nnz = len(r)

        def eager():
            return build_hierarchy_eager(adj, cfg_eager)

        t0 = time.perf_counter()
        (h_eager, eager_syncs) = _count_device_gets(eager)
        eager_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        eager()
        eager_warm = time.perf_counter() - t0

        ss.clear_cache()
        ss.reset_counters()
        t0 = time.perf_counter()
        h_super = build_hierarchy(adj, cfg_super)
        super_cold = time.perf_counter() - t0
        cold_counters = ss.counters()

        ss.reset_counters()
        t0 = time.perf_counter()
        build_hierarchy(adj, cfg_super)
        super_warm = time.perf_counter() - t0
        warm_counters = ss.counters()

        rows.append(dict(
            graph=name, n=n, nnz=nnz,
            levels_match=_level_sig(h_eager) == _level_sig(h_super),
            eager_cold_s=round(eager_cold, 3),
            eager_warm_s=round(eager_warm, 3),
            superstep_cold_s=round(super_cold, 3),
            superstep_warm_s=round(super_warm, 3),
            speedup_cold=round(eager_cold / max(super_cold, 1e-9), 2),
            speedup_warm=round(eager_warm / max(super_warm, 1e-9), 2),
            host_syncs_eager=eager_syncs,
            host_syncs_superstep=warm_counters["host_syncs"],
            compiles_cold=sum(s["compiles"]
                              for s in cold_counters["steps"].values()),
            compiles_warm=sum(s["compiles"]
                              for s in warm_counters["steps"].values()),
        ))

    # --- zero-recompile check: a second same-bucket graph ----------------
    # Same topology, reseeded weights, and a bucket floor covering every
    # level, so both graphs' levels land in identical buckets (without a
    # floor, weight-dependent aggregation can push a deep level across a
    # power-of-two boundary — a new bucket legitimately compiles).
    import dataclasses

    name, gen = _graphs(scale)[0]
    n, r, c, v = gen(seed=0)
    n2, r2, c2, v2 = gen(seed=1)          # same topology, reseeded weights
    cfg_floor = dataclasses.replace(cfg_super, setup_bucket_floor=4096)
    ss.clear_cache()
    ss.reset_counters()
    build_hierarchy(to_laplacian_coo(n, r, c, v), cfg_floor)
    first = ss.counters()
    ss.reset_counters()
    build_hierarchy(to_laplacian_coo(n2, r2, c2, v2), cfg_floor)
    second = ss.counters()
    recompile = dict(
        graph=f"{name} (weights reseeded, setup_bucket_floor=4096)",
        first_build=first["steps"],
        second_build=second["steps"],
        second_build_compiles=sum(s["compiles"]
                                  for s in second["steps"].values()),
        zero_recompiles=all(s["compiles"] == 0
                            for s in second["steps"].values()),
    )

    return dict(
        schema=SCHEMA,
        generated_by="benchmarks/setup_bench.py",
        jax_backend=jax.default_backend(),
        note=("off-TPU wall times are CPU regression-tracking numbers; "
              "the compile/host-sync ledgers are backend-independent. "
              "host_syncs_superstep counts batched decision fetches "
              "(one device_get each); host_syncs_eager counts the eager "
              "loop's individual device_get round-trips."),
        graphs=rows,
        recompile_check=recompile,
        dist=bench_setup_dist(scale),
    )


def bench_setup_dist(scale: float = 0.12) -> dict:
    """Distributed setup: the shard_map super-step loop vs the
    level-at-a-time host-driven eager setup, on the DistLaplacianSolver
    path (degenerate mesh over the visible devices — the ledgers, not the
    wall times, are what transfer to real meshes).

    Reports per graph: cold/warm setup walls for both modes, the
    super-step decision-fetch ledger (the acceptance figure: <= 1 batched
    scalar fetch per constructed level, + the entry probe and the
    coarse-solve alpha), and the eager loop's device_get count for
    contrast.
    """
    import dataclasses

    from repro.core import setup_step as ss
    from repro.core.hierarchy import SetupConfig
    from repro.dist.solver import DistLaplacianSolver
    from repro.launch.mesh import make_mesh

    ndev = len(jax.devices())
    pr = max(d for d in range(1, int(ndev ** 0.5) + 1) if ndev % d == 0)
    mesh = make_mesh((pr, ndev // pr))
    cfg = SetupConfig()
    cfg_eager = dataclasses.replace(cfg, setup_mode="eager")
    kw = dict(dist_nnz_threshold=2000, max_dist_levels=2)

    rows = []
    for name, gen in _graphs(scale):
        n, r, c, v = gen()

        def eager_setup():
            return DistLaplacianSolver.setup(n, r, c, v, mesh,
                                             setup_config=cfg_eager, **kw)

        t0 = time.perf_counter()
        (_, eager_syncs) = _count_device_gets(eager_setup)
        eager_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        eager_setup()
        eager_warm = time.perf_counter() - t0

        ss.reset_counters()
        t0 = time.perf_counter()
        solver = DistLaplacianSolver.setup(n, r, c, v, mesh,
                                           setup_config=cfg, **kw)
        super_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        DistLaplacianSolver.setup(n, r, c, v, mesh, setup_config=cfg, **kw)
        super_warm = time.perf_counter() - t0
        counters = ss.counters()

        n_levels = len(solver.arrays.transfers) + \
            len(solver.coarse_h.transfers)
        # two builds since reset; per-build ledger is half of each count
        syncs_per_build = counters["host_syncs"] / 2
        # decision fetches = total minus the entry probe and the
        # coarse-solve alpha (one each per build)
        decisions = max(syncs_per_build - 2, 0)
        rows.append(dict(
            graph=name, n=n, nnz=len(r), n_levels=n_levels,
            eager_cold_s=round(eager_cold, 3),
            eager_warm_s=round(eager_warm, 3),
            superstep_cold_s=round(super_cold, 3),
            superstep_warm_s=round(super_warm, 3),
            speedup_cold=round(eager_cold / max(super_cold, 1e-9), 2),
            speedup_warm=round(eager_warm / max(super_warm, 1e-9), 2),
            host_syncs_eager=eager_syncs,
            host_syncs_superstep=syncs_per_build,
            decision_fetches_per_level=round(
                decisions / max(n_levels, 1), 3),
            sync_contract_met=decisions <= n_levels + 1,
            per_step=counters["steps"],
        ))

    return dict(
        mesh_shape=[pr, ndev // pr],
        note=("super-step dist setup: Alg 1 select and Alg 2 votes run "
              "as shard_map semiring reductions over device-side 2D edge "
              "blocks; decision_fetches_per_level counts batched scalar "
              "fetches per constructed level (contract: <= 1, plus one "
              "allowance per ratio-check rejection)."),
        graphs=rows,
    )


def write_root_json(out: dict, path: str = ROOT_JSON) -> str:
    path = os.path.abspath(path)
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=False)
        f.write("\n")
    return path


if __name__ == "__main__":
    out = bench_setup()
    print(json.dumps(out, indent=1))
    print("wrote", write_root_json(out))
