"""pcg_iters.*: PCG iterations per solve (``SolveResult.iters``), the
mean over the window's solves."""


def read(record):
    its = [r["iters"] for r in record["requests"] if "iters" in r]
    return sum(its) / len(its) if its else None
