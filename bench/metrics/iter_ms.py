"""iter_ms.*: milliseconds per PCG iteration: the summed wall time of
the window's ``solve`` calls over their summed iterations."""


def read(record):
    done = [r for r in record["requests"] if "iters" in r and r["iters"]]
    its = sum(r["iters"] for r in done)
    return 1e3 * sum(r["solve_s"] for r in done) / its if its else None
