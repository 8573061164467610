"""The benchmark's four-chip deployment (``bench/configs/rmat_g500_s19_2x2``)
against the plain references, on four virtual CPU devices.

The graph comes from the configuration's own generator block
(``bench/graphs.py``) at scale 10 instead of 19; the solver's options are
the configuration's. ``repro.api.setup`` with the default ``backend="auto"``
must pick the 2D-distributed solver on the 2×2 mesh that four devices give,
and each answer is compared with the ``serial_ref`` backend's and with the
float64 residual of ``bench/reference.py``, the check that decides the
cell's ``correct`` on the chip.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "bench", "configs", "rmat_g500_s19_2x2.json")
SCALE = 10
N_RHS = 3
TOL = 1e-6
# The cell's own limit (bench/workloads/rmat_dist.solve.json): a float32
# solve asked for 1e-6 reads at most 1.4e-6 in float64 on the chip.
MAX_REL_RESIDUAL = 1e-5
# Both backends build the same hierarchy (the distributed set-up makes the
# serial one's integer decisions, its floats equal to rounding) and run the
# same preconditioned CG, so their answers differ by float32 rounding
# carried through a few iterations: ~1e-7 of the answer's norm at this
# size, after removing the mean (a solution is defined up to a constant).
# Not the residual's 1e-6 scaled by the condition number, which two
# unrelated solvers would be allowed.
MAX_ANSWER_DIFF = 1e-5

SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.path.join(%(repo)r, "bench"))
    import numpy as np
    import graphs, reference
    from repro.api import Problem, SolverOptions, setup

    cfg = json.load(open(%(config)r))
    g = dict(cfg["graph"], scale=%(scale)d)
    n, rows, cols, vals = graphs.build(g, np.random.SeedSequence(g["seed"]))
    opts = SolverOptions(**cfg["solver"]["options"])
    problem = Problem.from_edges(n, rows, cols, vals)
    dist = setup(problem, opts)
    ref = setup(problem, opts, backend="serial_ref")
    lap = reference.laplacian(n, rows, cols, vals)
    stats = dist._handle.stats()
    out = dict(backend=dist.backend, mesh=stats.get("mesh_shape"), n=n,
               sharded=sum(lv["distributed"] for lv in stats["levels"]),
               solves=[])
    rng = np.random.default_rng(5)
    for _ in range(%(n_rhs)d):
        b = rng.standard_normal(n)
        b = (b - b.mean()).astype(np.float32)
        xd, rd = dist.solve(b, tol=%(tol)r)
        xs, rs = ref.solve(b, tol=%(tol)r)
        xd0, xs0 = xd - xd.mean(), xs - xs.mean()
        out["solves"].append(dict(
            status=str(rd.status), iters=int(rd.iters),
            ref_iters=int(rs.iters),
            residual=reference.rel_residual(lap, b, xd),
            ref_residual=reference.rel_residual(lap, b, xs),
            diff=float(np.linalg.norm(xd0 - xs0) / np.linalg.norm(xs0))))
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def deployment():
    src = SCRIPT % dict(repo=REPO, config=CONFIG, scale=SCALE, n_rhs=N_RHS,
                        tol=TOL)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", src], capture_output=True,
                          text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.slow  # a fresh 4-device process builds and solves two backends
def test_auto_resolves_to_dist_on_a_2x2_mesh(deployment):
    assert deployment["backend"] == "dist"
    assert deployment["mesh"] == {"data": 2, "model": 2}
    assert deployment["sharded"] >= 1
    assert deployment["n"] > 500            # the largest component


@pytest.mark.slow
@pytest.mark.parametrize("j", range(N_RHS))
def test_answer_meets_the_float64_check_and_matches_serial_ref(deployment,
                                                               j):
    s = deployment["solves"][j]
    assert s["status"] == "converged", s
    assert s["residual"] <= MAX_REL_RESIDUAL, s
    assert s["ref_residual"] <= MAX_REL_RESIDUAL, s
    assert s["diff"] <= MAX_ANSWER_DIFF, s


@pytest.mark.slow
@pytest.mark.parametrize("j", range(N_RHS))
def test_iterations_within_one_of_serial_ref(deployment, j):
    s = deployment["solves"][j]
    assert abs(s["iters"] - s["ref_iters"]) <= 1, s
