"""Readings that the correctness check's limits are set from: the program
as it is, its control, and a solve stopped early.

    python3 bench/control.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] --variants program bf16@3 tol1e-4@3

One process builds the cell's hierarchy once, as ``bench/run.py`` does,
then runs the window of each variant on each seed (``@k``: on the first
``k`` seeds only) and compares its answers with ``run.compare``. The graph
is the configuration's, so a seed's requests are those of ``bench/run.py``
with that seed. It prints one JSON line per window: each request's
relative residual and the checks. Variants:

* ``program`` — the solver as it is (the lower reading);
* ``bf16`` — the control: every answer of ``Solver.solve`` rounded to
  bfloat16 where it is returned, the precision below the float32 the
  configurations state. The best answer a bfloat16 path can give is the
  exact answer rounded to bfloat16, so a limit that this fails is failed
  by any bfloat16 solve;
* ``tol<t>`` — the solver asked for the relative residual ``t`` in place of
  the traffic's: a solve stopped early.

The benchmark's own runs never run it; ``bench/tests/test_control.py``
runs it at a test size.
"""

from __future__ import annotations

import argparse
import json
import sys
from contextlib import contextmanager, nullcontext

import numpy as np

import run


def bf16(x) -> np.ndarray:
    import jax.numpy as jnp

    return np.asarray(x, np.float32).astype(jnp.bfloat16).astype(np.float32)


@contextmanager
def bf16_answers():
    """``repro.api.Solver.solve`` returning its answer in bfloat16."""
    from repro.api import facade

    solve = facade.Solver.solve

    def lowered(self, b, **kw):
        x, res = solve(self, b, **kw)
        return bf16(x), res

    facade.Solver.solve = lowered
    try:
        yield
    finally:
        facade.Solver.solve = solve


def parse_variant(text: str, n_seeds: int) -> tuple[str, int]:
    """``name`` or ``name@k``: the variant and how many seeds it runs."""
    name, _, k = text.partition("@")
    if name not in ("program", "bf16") and not name.startswith("tol"):
        raise ValueError(f"unknown variant {name!r}")
    return name, min(int(k), n_seeds) if k else n_seeds


def readings(cell, seeds, variants, seconds, require_tpu=True):
    """Yield one dict per (variant, seed) window; nothing where the cell
    may not run."""
    if run.start(cell, require_tpu) is None:
        return
    import load

    plan = [parse_variant(v, len(seeds)) for v in variants]
    loop = load.ClosedLoop(cell.config, cell.traffic, seeds[0], load.Spans())
    loop.prepare()
    tol = loop.tol
    for name, k in plan:
        loop.tol = float(name[3:]) if name.startswith("tol") else tol
        for seed in seeds[:k]:
            loop.seed = seed
            with bf16_answers() if name == "bf16" else nullcontext():
                records, _ = run.window(loop, seconds, loop.spans)
            failed, checks = run.compare(records, cell.limits)
            yield dict(workload=cell.name, variant=name, seed=seed,
                       correct=failed == 0,
                       rel_residuals=[r["rel_residual"] for r in records],
                       iters=[r.get("iters") for r in records],
                       checks=checks)
    loop.tol = tol


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=["bf16"])
    a = ap.parse_args(argv)
    n = 0
    for line in readings(run.Cell(a.workload), a.seeds, a.variants,
                         a.seconds):
        print(json.dumps(line), flush=True)
        n += 1
    return 0 if n else 1


if __name__ == "__main__":
    sys.exit(main())
