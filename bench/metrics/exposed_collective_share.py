"""exposed_collective_share.*: on each chip, the share of the traced window
in which a collective operation runs and no other operation does
(``bench/collectives.py``); the largest over the chips, %."""

import collectives


def read(record):
    return collectives.for_record(record)
