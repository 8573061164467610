"""vcycle_idle_share.*: share of the traced window in which the device
idled while the host's innermost ``repro.`` span was a ``repro.vcycle.*``
level (its nested SpMVs and syncs not included), %."""

import spans


def read(record):
    got = spans.for_record(record)
    if got is None:
        return None
    s, _ = got
    return 100.0 * sum(v for span, v in s["idle_s"].items()
                       if span.startswith(spans.VCYCLE)) / s["window_s"]
