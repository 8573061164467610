"""``bench/run.py`` as a command: it refuses to run off the chip."""

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "grid256.solve",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_non_zero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_exits_non_zero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_unknown_workload_exits_non_zero():
    import run

    assert run.main(["--workload", "nope", "--seed", "1", "--seconds", "1",
                     "--trace", "0"]) != 0


def test_a_cut_cell_runs_end_to_end_on_the_cpu():
    from conftest import run_tiny

    res = run_tiny("grid256.solve", seconds=0.2)
    assert res["correct"] and res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"solve_s.mesh", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["device"]["count"] == 1
    json.dumps(res)
