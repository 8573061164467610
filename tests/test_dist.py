"""Distributed 2D solver: correctness vs the single-device reference.

JAX locks the device count at first init, so multi-device cases run in
subprocesses with ``--xla_force_host_platform_device_count``. Each case
builds the same graph, solves with the 2D-partitioned shard_map solver on a
(pods ×) √P × √P mesh, and checks the result against the plain solver.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DRIVER = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(ndev)d"
    import numpy as np, jax, jax.numpy as jnp
    import jax.sharding as shd
    from repro.graphs.generators import barabasi_albert, grid_2d, ensure_connected, to_laplacian_coo
    from repro.core.graph import graph_from_adjacency
    from repro.dist.solver import DistLaplacianSolver
    from repro.core.hierarchy import SetupConfig

    kind = "%(kind)s"
    if kind == "ba":
        n, r, c, v = ensure_connected(*barabasi_albert(1200, m=3, seed=3, weighted=True))
    else:
        n, r, c, v = ensure_connected(*grid_2d(30, 30))

    mesh = jax.make_mesh(%(mesh_shape)s, %(mesh_axes)s,
                         axis_types=(shd.AxisType.Auto,) * len(%(mesh_axes)s))
    solver = DistLaplacianSolver.setup(
        n, r, c, v, mesh, SetupConfig(coarsest_size=64),
        dist_nnz_threshold=%(thresh)d, max_dist_levels=%(maxlev)d)

    rng = np.random.default_rng(0)
    b = rng.normal(size=n).astype(np.float32); b -= b.mean()
    x, norms = solver.solve(b, n_iters=25)

    level = graph_from_adjacency(to_laplacian_coo(n, r, c, v))
    res = np.asarray(b) - np.asarray(jax.device_get(level.laplacian_matvec(jnp.asarray(x))))
    out = dict(rel_residual=float(np.linalg.norm(res) / np.linalg.norm(b)),
               norm0=float(norms[0]), norm_last=float(norms[-1]),
               n_dist_levels=len(solver.level_meta),
               kinds=[m.kind for m in solver.level_meta])
    print("RESULT " + json.dumps(out))
""")


def run_case(ndev, mesh_shape, mesh_axes, kind="ba", thresh=100, maxlev=3):
    src = DRIVER % dict(ndev=ndev, mesh_shape=mesh_shape, mesh_axes=mesh_axes,
                        kind=kind, thresh=thresh, maxlev=maxlev)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", src], capture_output=True,
                          text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow  # each case is a fresh-process multi-device jit compile
class TestDistSolver:
    def test_2x2_matches_reference_ba(self):
        out = run_case(4, "(2, 2)", '("data", "model")')
        assert out["rel_residual"] < 1e-4, out
        assert out["n_dist_levels"] >= 1

    def test_2x2_grid_graph(self):
        out = run_case(4, "(2, 2)", '("data", "model")', kind="grid")
        assert out["rel_residual"] < 1e-4, out

    def test_multi_pod_2x2x2(self):
        """pod axis splits each block's edges; result must be identical."""
        out = run_case(8, "(2, 2, 2)", '("pod", "data", "model")')
        assert out["rel_residual"] < 1e-4, out

    def test_4x4_deeper_distribution(self):
        out = run_case(16, "(4, 4)", '("data", "model")', thresh=50, maxlev=2)
        assert out["rel_residual"] < 1e-4, out
        assert out["n_dist_levels"] == 2

    def test_single_device_degenerate(self):
        """1×1 mesh must reproduce the math with all collectives trivial."""
        out = run_case(1, "(1, 1)", '("data", "model")')
        assert out["rel_residual"] < 1e-4, out


class TestPartition:
    def test_partition_balance_and_roundtrip(self):
        from repro.dist.partition import (balance_report, pad_vector,
                                          partition_edges_2d, unpad_vector)
        from repro.graphs.generators import barabasi_albert, ensure_connected

        n, r, c, v = ensure_connected(*barabasi_albert(3000, m=5, seed=0))
        part = partition_edges_2d(n, r, c, v, 4, 4, pods=2)
        rep = balance_report(part)
        # random ordering keeps padded blocks balanced (paper §2.2)
        assert rep["imbalance"] < 1.6, rep
        assert 0.3 < rep["fill_fraction"] <= 1.0

        x = np.random.default_rng(1).normal(size=n).astype(np.float32)
        np.testing.assert_allclose(unpad_vector(part, pad_vector(part, x)), x)

    def test_partition_preserves_every_edge(self):
        from repro.dist.partition import partition_edges_2d
        from repro.graphs.generators import grid_2d

        n, r, c, v = grid_2d(12, 12)
        part = partition_edges_2d(n, r, c, v, 3, 3, random_ordering=False)
        total = 0.0
        valid = part.row_local < part.nb
        total = part.val[valid].sum()
        np.testing.assert_allclose(total, v.sum(), rtol=1e-6)
        assert valid.sum() == len(r)

    def test_random_ordering_improves_balance(self):
        from repro.dist.partition import partition_edges_2d
        from repro.graphs.generators import barabasi_albert

        n, r, c, v = barabasi_albert(4000, m=4, seed=2)
        p_no = partition_edges_2d(n, r, c, v, 4, 4, random_ordering=False)
        p_yes = partition_edges_2d(n, r, c, v, 4, 4, random_ordering=True)
        # BA ids are time-ordered (early vertices are hubs): blocked layout
        # without permutation concentrates edges in early blocks.
        assert p_yes.fill_fraction >= p_no.fill_fraction


# The scanned PCG's early exit, its spans and its host fetches, on a 2×2
# mesh of virtual devices: one subprocess per graph, read by the tests below.
EARLY_EXIT_SCRIPT = textwrap.dedent("""
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax, jax.numpy as jnp
    from repro import obs
    from repro.api import Problem, SolverOptions, setup
    from repro.core.hierarchy import SetupConfig
    from repro.core.krylov import GuardConfig
    from repro.dist.solver import DistLaplacianSolver, _pcg_block_step
    from repro.graphs.generators import (barabasi_albert, ensure_connected,
                                         grid_2d)
    from repro.launch.mesh import make_mesh

    TOL = 1e-6
    if "%(kind)s" == "ba":
        n, r, c, v = ensure_connected(*barabasi_albert(1200, m=3, seed=3,
                                                       weighted=True))
    else:
        n, r, c, v = ensure_connected(*grid_2d(30, 30))
    mesh = make_mesh((2, 2))
    solver = DistLaplacianSolver.setup(n, r, c, v, mesh,
                                       SetupConfig(coarsest_size=64),
                                       dist_nnz_threshold=100)
    rng = np.random.default_rng(0)
    B = rng.normal(size=(n, 2)).astype(np.float32)
    B -= B.mean(axis=0)
    B_pad = jnp.pad(jnp.asarray(B), ((0, solver.n_pad - n), (0, 0)))

    def bits(a):
        a = np.asarray(a)
        return a.view(np.uint32) if a.dtype == np.float32 else a

    def same(a, b):
        return bool(np.array_equal(bits(a), bits(b)))

    def fixed_scan(length, guard):
        # the chunk as a fixed-length scan of the same step
        def run(arrays, coarse_h, carry):
            matvec, precond = solver._operators(arrays, coarse_h)
            _, step = _pcg_block_step(matvec, precond, n, solver.n_pad, TOL,
                                      carry[-1], guard=guard)
            state, norms = jax.lax.scan(lambda s, _: step(s),
                                        tuple(carry[:-1]), None,
                                        length=length)
            return state + (carry[-1],), norms
        return jax.jit(run)

    out = dict(n_dist_levels=len(solver.level_meta))
    for name, guard in (("plain", None), ("guarded", GuardConfig())):
        init = jax.jit(solver.build_init_step(guard=guard))
        carry0 = init(solver.arrays, solver.coarse_h, B_pad)
        for length in (40, 2):
            loop = jax.jit(solver.build_chunk_step(length, tol=TOL,
                                                   guard=guard))
            ref = fixed_scan(length, guard)
            # two chunks in a row: the second starts where the first ended
            c1, c2, rows = carry0, carry0, []
            for _ in range(2):
                c1, ns1, steps = loop(solver.arrays, solver.coarse_h, c1)
                c2, ns2 = ref(solver.arrays, solver.coarse_h, c2)
                steps = int(steps)
                ns1, ns2 = np.asarray(ns1), np.asarray(ns2)
                rows.append(dict(
                    steps=steps, x=same(c1[0], c2[0]),
                    iters=same(c1[5], c2[5]), norms=same(ns1[:steps],
                                                         ns2[:steps]),
                    codes=guard is None or same(c1[6], c2[6]),
                    # a step past the exit changes no reported norm
                    tail_frozen=bool(steps == 0 or np.all(
                        bits(ns2[steps:]) == bits(ns2[steps - 1]))),
                    unwritten_zero=bool(np.all(ns1[steps:] == 0))))
            out[f"{name}_{length}"] = rows

    # one column through the chunked host loop, straight on the solver
    obs.reset()
    X, norms, iters = solver.solve_block(B[:, :1], n_iters=200, tol=TOL)
    out["block"] = dict(iters=np.asarray(iters).tolist(),
                        norm_rows=int(norms.shape[0]),
                        counters=obs.counters())

    # the same through the facade ("auto" picks dist for a mesh), counting
    # every device-to-host copy (a device_get of a device array); an
    # implicit copy would raise
    api = setup(Problem.from_edges(n, r, c, v),
                SolverOptions(coarsest_size=64, tol=TOL, fallback=False,
                              dist_nnz_threshold=100), mesh=mesh)
    api.solve(B[:, 0])
    obs.reset()
    copies = []
    device_get = jax.device_get

    def counted(x):
        if any(isinstance(a, jax.Array) for a in jax.tree.leaves(x)):
            copies.append(1)
        return device_get(x)

    jax.device_get = counted
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            _, res = api.solve(B[:, 0])
    finally:
        jax.device_get = device_get
    out["facade"] = dict(backend=api.backend, iters=int(res.iters),
                         copies=len(copies), counters=obs.counters())
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module", params=["ba", "grid"])
def early_exit(request):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-c", EARLY_EXIT_SCRIPT % dict(kind=request.param)],
        capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.slow  # one fresh 4-device process per graph, shared by the tests
class TestDistEarlyExit:
    @pytest.mark.parametrize("variant", ["plain", "guarded"])
    def test_loop_is_bitwise_a_fixed_scan_until_it_exits(self, early_exit,
                                                         variant):
        out = early_exit
        assert out["n_dist_levels"] >= 1
        long, short = out[f"{variant}_40"], out[f"{variant}_2"]
        # length 40: the first chunk converges and exits early, the
        # second has nothing left to do
        assert 0 < long[0]["steps"] < 40 and long[1]["steps"] == 0
        # length 2: both chunks run their full length
        assert long[0]["steps"] > 4
        assert [row["steps"] for row in short] == [2, 2]
        for row in long + short:
            assert row["x"] and row["iters"] and row["norms"], row
            assert row["codes"] and row["tail_frozen"], row
            assert row["unwritten_zero"], row

    def test_scan_steps_equal_pcg_iters_within_one_chunk(self, early_exit):
        out = early_exit["block"]
        c = out["counters"]
        (iters,) = out["iters"]
        assert 0 < iters < 16           # converges inside one chunk
        assert c["dist.chunks"] == 1
        assert c["dist.scan_steps"] == c["dist.pcg_iters"] == iters
        assert out["norm_rows"] == iters + 1

    def test_one_pcg_span_per_solve_over_init_and_chunks(self, early_exit):
        out = early_exit
        for c in (out["block"]["counters"], out["facade"]["counters"]):
            assert c["repro.pcg"] == 1
            assert c["repro.pcg>repro.dist.init"] == 1
            assert c["repro.pcg>repro.dist.chunk"] == c["dist.chunks"]
            assert c["repro.dist.init"] == 1
            assert c["repro.dist.chunk"] == c["dist.chunks"]
        assert out["facade"]["backend"] == "dist"
        assert out["facade"]["counters"]["repro.solve>repro.pcg"] == 1

    def test_every_solve_path_fetch_is_a_sync(self, early_exit):
        out = early_exit["facade"]
        c = out["counters"]
        # the r0 norms, one fetch per chunk, and the answer
        assert c["repro.pcg>repro.sync"] == 1 + c["dist.chunks"]
        assert c["repro.solve>repro.sync"] == 1
        assert c["repro.sync"] == out["copies"]
        assert c["dist.pcg_iters"] == out["iters"]
