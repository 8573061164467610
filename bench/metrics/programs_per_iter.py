"""programs_per_iter.*: device programs launched inside ``repro.pcg`` in
the traced window, per PCG iteration of its requests."""

import spans


def read(record):
    got = spans.for_record(record)
    if got is None:
        return None
    s, iters = got
    return sum(n for path, n in s["programs"].items()
               if spans.on_path(path, spans.PCG)) / iters
