"""The solver's one tracing mechanism: spans, host fetches and counters.

* :class:`span` marks a layer boundary. While a ``jax.profiler`` trace
  runs, it is a ``TraceAnnotation`` on the host clock, so every device
  program launched inside it can be attributed to it; with no profiler
  running the annotation is inactive. It also counts its calls per name,
  and per ``"<parent>><name>"`` where another span is open around it on
  the same thread.
* :func:`fetch` is the one device-to-host copy of the solve path: a
  ``jax.device_get`` inside a ``repro.sync`` span, so each host sync is
  counted and shows in a trace.
* :func:`count`, :func:`counters` and :func:`reset` keep named counts
  (``repro.core.setup_step`` keeps its compile/call/sync ledger here;
  ``repro.core.cycles`` counts each trace of a level program as
  ``vcycle.compiles``).

Spans only observe: they record nothing while JAX traces a function (under
``jit``, ``vmap`` or a scan), so a compiled program holds no trace of them,
and they never touch the arrays they enclose.

Span names, each read by a benchmark metric (``PERF.md`` section 3):
``repro.solve`` (``Solver.solve``), ``repro.pcg`` (the eager Krylov loop),
``repro.spmv`` (``GraphLevel.laplacian_matvec``), ``repro.vcycle.L<k>``
and ``repro.vcycle.coarse`` (one multigrid level, the dense bottom solve),
``repro.sync`` (a host fetch), ``repro.certify`` (the float64 certificate)
and ``repro.setup.<step>`` (one set-up super-step dispatch). The dist
backend adds ``repro.dist.init`` and ``repro.dist.chunk`` (one launch of
its compiled PCG init and loop, under ``repro.pcg``) and
``repro.dist.setup.relabel`` / ``.superstep`` / ``.partition`` (its set-up
phases), and counts ``dist.chunks``, ``dist.scan_steps`` (PCG steps its
loop ran), ``dist.pcg_iters`` (each solve's largest column iteration
count) and ``dist.partition_host_bytes``.
"""

from __future__ import annotations

import collections
import functools
import threading

import jax
# JAX 0.9 has no public form of this check.
from jax._src.core import trace_state_clean

_LOCK = threading.Lock()
_COUNTS: collections.Counter = collections.Counter()
_OPEN = threading.local()           # .names: this thread's open spans


def _open_names() -> list:
    names = getattr(_OPEN, "names", None)
    if names is None:
        names = _OPEN.names = []
    return names


class span:
    """Context manager: a named span at a layer boundary (see module doc)."""

    __slots__ = ("name", "_annotation")

    def __init__(self, name: str):
        self.name = name
        self._annotation = None

    def __enter__(self):
        if not trace_state_clean():
            return self
        names = _open_names()
        with _LOCK:
            _COUNTS[self.name] += 1
            if names:
                _COUNTS[f"{names[-1]}>{self.name}"] += 1
        names.append(self.name)
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
            _open_names().pop()
        return False


def spanned(name: str):
    """Decorator: run the function inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def fetch(*arrays):
    """Copy device arrays to the host in one sync, inside a ``repro.sync``
    span. One argument gives its value (a pytree comes back whole); several
    give a tuple."""
    with span("repro.sync"):
        return jax.device_get(arrays[0] if len(arrays) == 1 else arrays)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    with _LOCK:
        _COUNTS[name] += n


def counters() -> dict:
    """A snapshot of every count: span calls by name, nested span calls by
    ``"<parent>><name>"``, and the named counters of :func:`count`."""
    with _LOCK:
        return dict(_COUNTS)


def reset(prefix: str = "") -> None:
    """Zero the counts whose name starts with ``prefix`` (all by default)."""
    with _LOCK:
        for name in [n for n in _COUNTS if n.startswith(prefix)]:
            del _COUNTS[name]
