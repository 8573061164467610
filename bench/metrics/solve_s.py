"""solve_s.*: seconds per solve on a built hierarchy: the window's
request time over its requests (a request here is one solve)."""


def read(record):
    n = len(record["requests"])
    return record["window_s"] / n if n else None
