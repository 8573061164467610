"""Shared set-up of the harness tests: the benchmark's modules on the path,
and its cells cut to sizes a CPU test can hold."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# Sizes of the graphs at which the tests drive each configuration.
TINY = {"rmat": {"scale": 8}, "grid_2d": {"side": 16}}
CELLS = ("rmat19.solve", "grid256.solve")


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """Tests run on the CPU: keep their programs out of the checkout's
    compilation cache, which holds the chip's."""
    import run

    monkeypatch.setattr(run, "configure_jax", lambda: None)


def tiny_cell(name: str):
    import run

    cell = run.Cell(name)
    graph = cell.config["graph"]
    graph.update(TINY[graph["generator"]])
    return cell


def run_tiny(name: str, seed: int = 2**31 + 7, seconds: float = 0.01,
             trace: int = 0):
    """One harness run of a cut cell on the CPU, past the look for a TPU."""
    import run

    args = run.parse(["--workload", name, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)])
    return run.run(args, require_tpu=False, cell=tiny_cell(name))
