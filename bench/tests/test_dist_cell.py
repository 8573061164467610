"""``rmat_dist.solve`` cut to scale 8, end to end through ``run.run`` on
four virtual CPU devices (a fresh process: JAX fixes the device count when
it starts)."""

import json
import os
import subprocess
import sys
import textwrap

from conftest import BENCH, ROOT, TINY

SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [%(bench)r, os.path.join(%(root)r, "src")]
    import run

    run.configure_jax = lambda: None     # keep CPU programs out of the cache
    cell = run.Cell("rmat_dist.solve")
    cell.config["graph"].update(%(tiny)r)
    args = run.parse(["--workload", cell.name, "--seed", str(2**31 + 11),
                      "--seconds", "0.2", "--trace", "0"])
    res = run.run(args, require_tpu=False, cell=cell)
    res["scan_steps_per_iter"] = run.read_metric("scan_steps_per_iter.dist",
                                                 {})
    print("RESULT " + json.dumps(res))
""")


def test_the_cut_cell_runs_on_four_devices():
    src = SCRIPT % dict(bench=BENCH, root=ROOT, tiny=TINY["rmat"])
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", src], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    res = json.loads(line[-1][len("RESULT "):])
    assert res["device"]["count"] == 4
    assert res["correct"] and res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"solve_s.powerlaw", "setup_s"}
    # the compiled loop ran exactly the steps its solves needed
    assert res["scan_steps_per_iter"] == 1.0
