"""Exposed collective time from a profiler trace: on each device plane, the
part of the measured window in which a collective operation runs and no
other operation does.

An operation is an event of the line ``XLA Ops`` of a ``/device:TPU:<i>``
plane, named by its HLO text, as ``bench/trace.py`` reads it. It is a
collective when its opcode, or the start of its instruction's name, is
``all-reduce``, ``all-gather``, ``reduce-scatter``, ``collective-permute``
or ``all-to-all`` (an async pair's ``-start`` and ``-done`` halves too), or
its name starts with ``psum`` (JAX's name for a ``psum``'s all-reduce).

A control-flow operation (opcode ``while``, ``conditional`` or ``call``)
spans the operations of its body on the same line, so it is not "another
operation": a loop that holds a collective does not hide it.

The device planes are shifted onto the host's clock by the offset
``bench/spans.py`` finds, and clipped to the ``bench.window`` span.
"""

from __future__ import annotations

import re

import spans

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
CONTROL = ("while", "conditional", "call")
COLLECTIVE, OTHER = "collective", "other"
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")


def kind(hlo: str) -> str:
    """``COLLECTIVE``, ``OTHER``, or for a control-flow operation
    ``"control"``, from an ``XLA Ops`` event's HLO text (``%name = shape
    opcode(operands), ...``)."""
    name, _, rest = hlo.partition(" = ")
    name = name.lstrip("%")
    op = _OPCODE.search(" " + rest)
    opcode = op.group(1) if op else ""
    if name.startswith("psum") or any(
            n.startswith(c) for n in (name, opcode) for c in COLLECTIVES):
        return COLLECTIVE
    return "control" if opcode in CONTROL else OTHER


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _minus(a, b):
    """Length of the union ``a`` less the union ``b`` (both merged)."""
    total, j = 0.0, 0
    for s, e in a:
        t = s
        while j < len(b) and b[j][1] <= t:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                total += b[k][0] - t
            t = max(t, b[k][1])
            k += 1
        if t < e:
            total += e - t
    return total


def exposed_s(ops, w0: float, w1: float) -> float:
    """Seconds of ``[w0, w1]`` (ns) in which an operation of ``ops``
    (``(start_ns, end_ns, kind)`` of one plane, on the window's clock) of
    kind ``COLLECTIVE`` runs and none of kind ``OTHER`` does."""
    clip = [(max(s, w0), min(e, w1), k) for s, e, k in ops
            if e > w0 and s < w1]
    coll = _union([(s, e) for s, e, k in clip if k == COLLECTIVE])
    other = _union([(s, e) for s, e, k in clip if k == OTHER])
    return _minus(coll, other) / 1e9


def load(path: str):
    """Read an ``.xplane.pb`` into ``(planes, window)``: per device plane,
    its operations as ``(start_ns, end_ns, kind)`` on the device
    clock, and the ``bench.window`` span ``(start_ns, end_ns)`` on the
    host's (None where the trace has none)."""
    from jax.profiler import ProfileData

    planes, window = [], None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(spans.DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == spans.OPS_LINE:
                    ops = [(float(ev.start_ns), float(ev.end_ns),
                            kind(ev.name)) for ev in line.events]
            planes.append(ops)
        elif plane.name.startswith("/host:") and window is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == spans.WINDOW_SPAN:
                        window = (float(ev.start_ns), float(ev.end_ns))
                        break
    return planes, window


def exposed_share(path: str) -> float | None:
    """The largest share over the device planes of the window in which a
    collective is exposed, %; None where the trace holds no window or no
    device plane."""
    planes, window = load(path)
    if window is None or not planes:
        return None
    offset = spans.summarize(path)["offset_ms"] * 1e6
    w0, w1 = window
    return max(100.0 * exposed_s([(s + offset, e + offset, k)
                                  for s, e, k in ops], w0, w1)
               / ((w1 - w0) / 1e9) for ops in planes)


def for_record(record) -> float | None:
    """``exposed_share`` of a traced run's trace; None for a run with no
    trace."""
    trace = record.get("trace")
    if not trace:
        return None
    path = spans.find(trace["window_s"])
    return None if path is None else exposed_share(path)
