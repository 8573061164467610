"""The one traffic generator: a closed loop with one client that drives the
solver's public entry, ``repro.api.setup`` and ``Solver.solve``.

A traffic mix is a data file ``bench/traffic/<name>.json`` with the keys

* ``rhs_columns`` — right-hand sides per solve (1 gives an ``(n,)`` vector);
* ``tol`` — the relative residual the solver is asked for;
* ``warmup_solves`` — how many solves set-up runs on the built hierarchy,
  so that every program the window uses is compiled before it opens.

Set-up builds one graph and its hierarchy; every request is one solve
against it. The configuration's graph is drawn from its own ``graph.seed``
and is the same in every run, so every run builds the same hierarchy and
finds its programs in the compilation cache. Each warm-up and each request
right-hand side is drawn from the run's seed, from its own
``numpy.random.SeedSequence([seed, stream, index])``.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

import numpy as np

import graphs

# SeedSequence streams: one per kind of input, so that a request's data do
# not depend on how many warm-up draws came before it.
WARMUP_RHS, REQUEST_RHS = 1, 3


def stream(seed: int, kind: int, index: int = 0) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed) % 2**63, kind, index])


def rhs(n: int, columns: int, seq) -> np.ndarray:
    """A mean-free Gaussian right-hand side, float32, ``(n,)`` or
    ``(n, columns)``."""
    rng = np.random.default_rng(seq)
    b = rng.standard_normal((n,) if columns == 1 else (n, columns))
    return (b - b.mean(axis=0)).astype(np.float32)


class Spans:
    """Host spans of the harness, on the host clock and, while a profiler
    trace runs, in the trace too (``jax.profiler.TraceAnnotation``)."""

    def __init__(self):
        self.records: list = []

    @contextmanager
    def __call__(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))


class ClosedLoop:
    """Set-up and requests of one traffic mix on one configuration."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 spans: Spans):
        from repro.api import SolverOptions

        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.spans = spans
        self.options = SolverOptions(**config["solver"]["options"])
        self.tol = float(traffic["tol"])
        self.columns = int(traffic["rhs_columns"])
        self.solver = None
        self.graph = None

    def _solve(self, b):
        with self.spans("bench.solve"):
            return self.solver.solve(b, tol=self.tol)

    def prepare(self) -> None:
        """Set-up: build the graph and its hierarchy, and run every shape
        of the window once."""
        from repro.api import Problem, setup

        with self.spans("bench.setup"):
            self.graph = graphs.build(
                self.config["graph"],
                np.random.SeedSequence(self.config["graph"]["seed"]))
            with self.spans("bench.problem"):
                problem = Problem.from_edges(*self.graph)
            with self.spans("bench.hierarchy"):
                self.solver = setup(problem, self.options)
            for j in range(self.traffic["warmup_solves"]):
                self._solve(rhs(self.graph[0], self.columns,
                                stream(self.seed, WARMUP_RHS, j)))

    def request(self, i: int) -> dict:
        """Run request ``i`` and return its record. Its right-hand side is
        drawn before its clock starts; ``t0``/``t1`` bound the request."""
        g = self.graph
        b = rhs(g[0], self.columns, stream(self.seed, REQUEST_RHS, i))
        rec = dict(index=i, graph=g, b=b, x=None, error=None)
        t0 = time.perf_counter()
        try:
            with self.spans("bench.request"):
                x, res = self._solve(b)
            rec.update(x=x, iters=int(res.iters), status=str(res.status))
        except Exception as e:          # a failed request is counted, and
            rec["error"] = repr(e)      # the loop goes on
        rec["t0"], rec["t1"] = t0, time.perf_counter()
        rec["solve_s"] = rec["t1"] - t0
        return rec
