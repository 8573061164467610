"""Record ``data/spans.xplane.pb``, the chip trace that ``test_spans.py``
reads: three PCG iterations of a solve on an 8 x 8 grid through
``repro.api``, inside the harness's ``bench.window`` span, with the
program's own spans (1.6 MB on a v5e; a 16 x 16 grid at two iterations
comes to 1.9 MB).

    python3 bench/tests/record_spans_trace.py --out <dir>

It writes ``<dir>/spans.xplane.pb`` and ``<dir>/spans.json`` (the solve's
PCG iterations). Run it on a TPU, where the device planes hold
the programs; on a CPU the trace has no device plane.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SIDE, ITERS = 8, 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import numpy as np

    from repro.api import Problem, SolverOptions, setup
    from repro.graphs.generators import grid_2d

    n, rows, cols, vals = grid_2d(SIDE, SIDE, weighted=True, seed=1)
    solver = setup(Problem.from_edges(n, rows, cols, vals),
                   SolverOptions(coarsest_size=16, fallback=False))
    rng = np.random.default_rng(0)

    def rhs():
        b = rng.standard_normal(n).astype(np.float32)
        return b - b.mean()

    solver.solve(rhs(), tol=1e-12, max_iters=ITERS)           # compiles
    with tempfile.TemporaryDirectory() as tmp:
        opts = jax.profiler.ProfileOptions()     # as bench/run.py traces
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.solve"):
                _, res = solver.solve(rhs(), tol=1e-12, max_iters=ITERS)
        iters = [int(res.iters)]
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        os.makedirs(args.out, exist_ok=True)
        shutil.copy(path, os.path.join(args.out, "spans.xplane.pb"))
    with open(os.path.join(args.out, "spans.json"), "w") as f:
        json.dump(dict(iters=iters, side=SIDE,
                       device=jax.devices()[0].device_kind), f)
    print(json.dumps(dict(iters=iters, bytes=os.path.getsize(
        os.path.join(args.out, "spans.xplane.pb")))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
