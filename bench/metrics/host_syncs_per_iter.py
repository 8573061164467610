"""host_syncs_per_iter.*: host fetches (``repro.sync`` spans) opened inside
``repro.pcg`` in the traced window, per PCG iteration of its requests."""

import spans


def read(record):
    got = spans.for_record(record)
    if got is None:
        return None
    s, iters = got
    return sum(n for key, n in s["opened"].items()
               if key.endswith(" > " + spans.SYNC)
               and spans.on_path(key, spans.PCG)) / iters
