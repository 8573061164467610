"""Reduction of a profiler trace to the program's own layers: device time,
device programs, host syncs and idle time by the ``repro.`` span that
launched them (``repro.obs`` spans: ``repro.pcg``, ``repro.spmv``,
``repro.vcycle.L<k>``, ``repro.sync``, ...).

Clock alignment. The device planes (``/device:TPU:<i>``) run on a clock of
their own. Each program on the line ``XLA Modules`` carries a ``run_id``,
and so does the host's ``DoEnqueueProgram`` event that launched it. The
device planes are shifted by the largest (enqueue - device start) over the
pairs, so that no program starts before its launch; the shift is reported
as ``offset_ms``.

Attribution. Each program, with its operations (line ``XLA Ops``), goes to
the innermost ``repro.`` span open at its launch, its enqueue instant where
it has one and its shifted start where it has none, on the host thread
that holds the harness's ``bench.window`` span. The path of open ``repro.``
spans is kept, outermost first, so that a reader can ask for a span and all
it encloses. Device time is clipped to the window, as ``bench/trace.py``
clips it.

A trace is parsed once per path and kept (the grid cell's is ~200 MB).
"""

from __future__ import annotations

import bisect
import glob
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
TRACES = os.path.join(os.path.dirname(BENCH), ".bench_cache", "trace")

DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ENQUEUE = "DoEnqueueProgram"
WINDOW_SPAN = "bench.window"
PREFIX = "repro."
PCG = "repro.pcg"
SPMV = "repro.spmv"
SYNC = "repro.sync"
VCYCLE = "repro.vcycle."

_SUMMARIES: dict = {}


def _run_id(ev):
    for name, value in ev.stats:
        if name == "run_id":
            return int(value)
    return None


def load(path: str):
    """Read an ``.xplane.pb`` into ``(planes, enqueues, events)``.

    ``planes``: per device plane, ``(modules, ops)``: programs as
    ``(start_ns, end_ns, run_id)`` and operations as ``(start_ns,
    end_ns)``. ``enqueues``: ``run_id`` -> host start of its
    ``DoEnqueueProgram``. ``events``: ``(start_ns, end_ns, name)`` of the
    ``repro.`` spans and the window span of the thread that holds it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes, enqueues, events = [], {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            modules, ops = [], []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules = [(ev.start_ns, ev.end_ns, _run_id(ev))
                               for ev in line.events]
                elif line.name == OPS_LINE:
                    ops = [(ev.start_ns, ev.end_ns) for ev in line.events]
            planes.append((modules, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                mine, window = [], False
                for ev in line.events:
                    name = ev.name
                    if name.startswith(PREFIX):
                        mine.append((ev.start_ns, ev.end_ns, name))
                    elif name == WINDOW_SPAN:
                        mine.append((ev.start_ns, ev.end_ns, name))
                        window = True
                    elif name == ENQUEUE:
                        rid = _run_id(ev)
                        if rid is not None:
                            enqueues.setdefault(rid, ev.start_ns)
                if window:
                    events.extend(mine)
    return planes, enqueues, events


def clock_offset(planes, enqueues) -> tuple[float, int]:
    """The shift, in ns, that puts no program before its enqueue, and the
    number of programs paired by ``run_id``."""
    lags = [enqueues[rid] - s for modules, _ in planes
            for s, _, rid in modules if rid in enqueues]
    return (max(lags) if lags else 0.0), len(lags)


def _sweep(events, w0, w1):
    """The window cut where the innermost ``repro.`` span changes, as
    ``(start, end, path)`` pieces (``path``: open span names, outermost
    first), and each span opened inside the window with the path around
    it, as ``(name, path)``."""
    pieces, opened = [], []
    stack: list = []                        # (end, name), innermost last
    t = w0

    def emit(upto):
        nonlocal t
        upto = min(upto, w1)
        if upto > t:
            path = tuple(n for _, n in stack)
            if pieces and pieces[-1][2] == path and pieces[-1][1] == t:
                pieces[-1] = (pieces[-1][0], upto, path)
            else:
                pieces.append((t, upto, path))
            t = upto

    for s, e, n in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        if w0 <= s < w1:
            opened.append((n, tuple(m for _, m in stack)))
        stack.append((e, n))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(w1)
    return pieces, opened


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(planes, enqueues, events) -> dict:
    """Totals over the window, averaged over the device planes.

    ``device_s``/``programs``: device time and programs by the path of
    ``repro.`` spans open at launch (``" > "``-joined, ``""`` for none);
    ``idle_s``: device idle time by the innermost span open on the host;
    ``opened``: spans opened in the window, by ``"<path> > <name>"``;
    ``op_s``/``attributed_s``: device operation time, all and under some
    ``repro.`` span; ``offset_ms`` and ``pairs`` of the alignment."""
    windows = [(s, e) for s, e, n in events if n == WINDOW_SPAN]
    if not windows:
        raise ValueError("trace holds no window span")
    w0, w1 = windows[0]
    offset, pairs = clock_offset(planes, enqueues)
    pieces, opened = _sweep([ev for ev in events if ev[2] != WINDOW_SPAN],
                            w0, w1)
    starts = [p[0] for p in pieces]

    def path_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return pieces[i][2] if 0 <= i and t < pieces[i][1] else ()

    device_s: dict = {}
    programs: dict = {}
    idle_s: dict = {}
    op_s = 0.0
    for modules, ops in planes:
        modules = sorted(modules)
        mstarts = [m[0] for m in modules]
        paths = []
        for s, _, rid in modules:
            launch = enqueues.get(rid, s + offset)
            path = " > ".join(path_at(launch))
            paths.append(path)
            if w0 <= launch < w1:
                programs[path] = programs.get(path, 0) + 1
        busy = []
        for s, e in ops:
            i = bisect.bisect_right(mstarts, s) - 1
            s, e = max(s + offset, w0), min(e + offset, w1)
            if e <= s:
                continue
            path = paths[i] if i >= 0 else ""
            device_s[path] = device_s.get(path, 0.0) + (e - s)
            op_s += e - s
            busy.append((s, e))
        edges = [w0] + [x for iv in _union(busy) for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            i = max(bisect.bisect_right(starts, g0) - 1, 0)
            while i < len(pieces) and pieces[i][0] < g1:
                s, e, path = pieces[i]
                cut = min(e, g1) - max(s, g0)
                if cut > 0:
                    key = path[-1] if path else ""
                    idle_s[key] = idle_s.get(key, 0.0) + cut
                i += 1
    k = max(len(planes), 1) * 1e9
    counts: dict = {}
    for name, path in opened:
        key = " > ".join(path + (name,))
        counts[key] = counts.get(key, 0) + 1
    return dict(
        window_s=(w1 - w0) / 1e9, offset_ms=offset / 1e6, pairs=pairs,
        modules=sum(len(m) for m, _ in planes),
        op_s=op_s / k, attributed_s=(op_s - device_s.get("", 0.0)) / k,
        device_s={p: v / k for p, v in device_s.items()},
        programs={p: v / max(len(planes), 1) for p, v in programs.items()},
        idle_s={p: v / k for p, v in idle_s.items()}, opened=counts)


def find(window_s: float) -> str | None:
    """The newest trace under ``.bench_cache/trace`` whose window lasts
    ``window_s`` (what ``bench/trace.py`` read from the same file)."""
    files = sorted(glob.glob(os.path.join(TRACES, "*", "plugins", "profile",
                                          "*", "*.xplane.pb")),
                   key=os.path.getmtime, reverse=True)
    for path in files:
        if abs(summarize(path)["window_s"] - window_s) <= 1e-6:
            return path
    return None


def summarize(path: str) -> dict:
    """``reduce(*load(path))``, once per path; logs the alignment."""
    if path not in _SUMMARIES:
        t0 = time.perf_counter()
        s = _SUMMARIES[path] = reduce(*load(path))
        share = 100.0 * s["attributed_s"] / s["op_s"] if s["op_s"] else 0.0
        print(f"bench: spans: offset_ms={s['offset_ms']:.4f} "
              f"pairs={s['pairs']}/{s['modules']} "
              f"attributed={share:.3f}% of {s['op_s']:.6f} s device op time "
              f"reduce_s={time.perf_counter() - t0:.2f} ({path})",
              file=sys.stderr, flush=True)
        inner: dict = {}
        for p, v in s["device_s"].items():
            key = p.rsplit(" > ", 1)[-1]
            inner[key] = inner.get(key, 0.0) + v
        for what, d in (("device_s", inner), ("idle_s", s["idle_s"])):
            print(f"bench: spans: {what} by innermost span " + " ".join(
                f"{k or '-'}={v:.4f}" for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])),
                file=sys.stderr, flush=True)
    return _SUMMARIES[path]


def for_record(record) -> tuple[dict, int] | None:
    """The span summary of a traced run's window and the PCG iterations of
    its requests; None where the run holds no trace, or the trace no
    ``repro.pcg`` span (a program without spans)."""
    trace = record.get("trace")
    if not trace:
        return None
    path = find(trace["window_s"])
    if path is None:
        return None
    s = summarize(path)
    iters = sum(r.get("iters") or 0 for r in record["requests"])
    if not iters or not any(key.endswith(PCG) for key in s["opened"]):
        return None
    return s, iters


def on_path(path: str, name: str) -> bool:
    """Whether a span path (``" > "``-joined) holds a span ``name``, or one
    starting with it where ``name`` ends in a dot."""
    parts = path.split(" > ")
    if name.endswith("."):
        return any(p.startswith(name) for p in parts)
    return name in parts
