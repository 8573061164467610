"""scan_steps_per_iter.*: PCG steps the dist backend's compiled loop ran
(counter ``dist.scan_steps``) over the steps its solves needed, each
solve's largest column iteration count (``dist.pcg_iters``), both counted
by ``repro.obs`` in the run's process since it started; 1 when no step is
wasted. None where the program keeps neither counter."""


def read(record):
    from repro import obs

    c = obs.counters()
    steps, iters = c.get("dist.scan_steps"), c.get("dist.pcg_iters")
    return steps / iters if steps and iters else None
