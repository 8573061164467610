"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's device
numbers: busy and idle time, time per device operation, the share of
scatter operations, and idle gaps named by what the host was doing.

The trace is read with ``jax.profiler.ProfileData``. Device planes are
those named ``/device:TPU:<i>``. Their operations are the events of the
line ``XLA Ops``; each is labelled ``<program>/<instruction>``, the XLA
program (line ``XLA Modules``, without its hash) that ran it and the HLO
instruction's name. The solver runs eagerly, so most programs hold one
primitive and are named after it (``jit_scatter_add``, ``jit_gather``).

Host spans are the events of the host thread that holds the harness's
``TraceAnnotation`` spans (names starting with ``bench.``). The span
``bench.window`` bounds the measured window, and everything is clipped to
it. An idle gap is named by the innermost ``bench.`` span open in it and
by the innermost host event of that thread, when that is another.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def _union(intervals):
    """Merge ``(start, end)`` pairs; returns sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def op_label(module: str, hlo: str) -> str:
    """``<program>/<instruction>`` from a program's name and the HLO text
    of one of its instructions: ``jit_gather(123)`` and ``%fusion.2 =
    f32[8] fusion(...)`` give ``jit_gather/fusion.2``."""
    prog = re.sub(r"\(\d+\)$", "", module)
    inst = hlo.split(" = ", 1)[0].lstrip("%")
    return f"{prog}/{inst}"


def is_scatter(label: str, hlo: str) -> bool:
    """A scatter: an instruction of a scatter program (the eager
    ``segment_sum`` / ``.at[].add`` of the COO SpMV runs as
    ``jit_scatter_add``), or an HLO ``scatter`` instruction elsewhere."""
    return "scatter" in label or " scatter(" in hlo


def _events(line):
    for ev in line.events:
        s = float(ev.start_ns)
        yield s, s + float(ev.duration_ns), ev.name


def load(path: str):
    """Read an ``.xplane.pb`` into ``(devices, spans)``.

    ``devices``: one list per device plane of ``(start_ns, end_ns, label,
    is_scatter)`` operations. ``spans``: ``(start_ns, end_ns, name)`` of
    the events of the host thread that holds the harness's spans.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: list(_events(line)) for line in plane.lines}
            modules = sorted(lines.get(MODULES_LINE, []))
            starts = [m[0] for m in modules]
            ops = []
            for s, e, hlo in lines.get(OPS_LINE, []):
                i = bisect.bisect_right(starts, s) - 1
                label = op_label(modules[i][2] if i >= 0 else "", hlo)
                ops.append((s, e, label, is_scatter(label, hlo)))
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = list(_events(line))
                if any(n == WINDOW_SPAN for _, _, n in evs):
                    spans.extend(evs)
    return devices, spans


def reduce(devices, spans) -> dict:
    """Device numbers over the window, averaged over the device planes.

    Returns ``window_s``, ``busy_s`` (union of operation intervals),
    ``op_s`` (sum of operation durations), ``scatter_s``, ``device_ops``
    (the ``TOP`` operation labels by summed time) and ``idle_gaps`` (idle
    time summed by what the host was doing, the ``TOP`` largest)."""
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows or not any(devices):
        raise ValueError("trace holds no window span or no device operation")
    w0, w1 = windows[0]
    pieces = _pieces([sp for sp in spans if sp[2] != WINDOW_SPAN], w0, w1)
    starts = [p[0] for p in pieces]
    busy = op = scatter = 0.0
    by_op: dict = {}
    by_gap: dict = {}
    for ops in devices:
        clipped = [(max(s, w0), min(e, w1), n, sc) for s, e, n, sc in ops
                   if e > w0 and s < w1]
        merged = _union([(s, e) for s, e, _, _ in clipped])
        busy += sum(e - s for s, e in merged)
        for s, e, n, sc in clipped:
            op += e - s
            scatter += (e - s) if sc else 0.0
            by_op[n] = by_op.get(n, 0.0) + (e - s)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            i = max(bisect.bisect_right(starts, g0) - 1, 0)
            while i < len(pieces) and pieces[i][0] < g1:
                s, e, name = pieces[i]
                cut = min(e, g1) - max(s, g0)
                if cut > 0:
                    by_gap[name] = by_gap.get(name, 0.0) + cut
                i += 1
    k = len(devices) * 1e9
    top = lambda d: [[n, v / k] for n, v in          # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return dict(window_s=(w1 - w0) / 1e9, busy_s=busy / k, op_s=op / k,
                scatter_s=scatter / k, device_ops=top(by_op),
                idle_gaps=top(by_gap))


def _pieces(events, w0, w1):
    """Cut ``[w0, w1]`` where the host thread's innermost event changes.

    The events of one thread nest, so a sweep with a stack finds, for each
    piece, the innermost ``bench.`` span and the innermost event; the piece
    is named ``<span>`` or ``<span> > <event>``."""
    pieces = []
    stack: list = []                    # (end, name), innermost last
    t = w0

    def name():
        span = next((n for _, n in reversed(stack)
                     if n.startswith(SPAN_PREFIX)), "outside spans")
        inner = stack[-1][1] if stack else span
        return span if inner == span else f"{span} > {inner}"

    def emit(upto):
        nonlocal t
        upto = min(upto, w1)
        if upto > t:
            n = name()
            if pieces and pieces[-1][2] == n and pieces[-1][1] == t:
                pieces[-1] = (pieces[-1][0], upto, n)
            else:
                pieces.append((t, upto, n))
            t = upto

    for s, e, n in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((e, n))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(w1)
    return pieces


def summarize(log_dir: str) -> dict:
    return reduce(*load(find_xplane(log_dir)))
