"""vcycle_device_ms.*: device time of the programs launched inside a
``repro.vcycle.*`` span (the preconditioner, its SpMVs included) in the
traced window, ms per PCG iteration of its requests."""

import spans


def read(record):
    got = spans.for_record(record)
    if got is None:
        return None
    s, iters = got
    return 1e3 * sum(v for path, v in s["device_s"].items()
                     if spans.on_path(path, spans.VCYCLE)) / iters
