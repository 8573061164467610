"""The check's control and the faults it must catch, at a test size on
the CPU: each run drives the whole harness past its look for a TPU."""

import numpy as np
import pytest

from conftest import CELLS, run_tiny


@pytest.mark.parametrize("name", CELLS)
def test_the_program_passes(name):
    res = run_tiny(name)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_bf16_answers_fail(name):
    import control

    with control.bf16_answers():
        res = run_tiny(name)
    assert not res["correct"]
    c = res["checks"]["max_rel_residual"]
    assert c["value"] > c["limit"]


def _unchanged(x):
    """The solve leaves its iterate at the zero it started from."""
    return np.zeros_like(x)


def _altered(x):
    """One entry of the answer altered where it is produced."""
    y = np.array(x, copy=True)
    y[np.argmax(np.abs(y))] *= -1
    return y


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_unchanged, _altered],
                         ids=["unchanged", "altered"])
def test_a_broken_answer_fails(name, fault, monkeypatch):
    from repro.api import facade

    solve = facade.Solver.solve

    def broken(self, b, **kw):
        x, res = solve(self, b, **kw)
        return fault(x), res

    monkeypatch.setattr(facade.Solver, "solve", broken)
    res = run_tiny(name)
    assert not res["correct"] and res["failed"] == res["attempted"]


@pytest.mark.parametrize("name", CELLS)
def test_a_wrong_operator_fails(name):
    """Stored edge weights corrupted at solve entry: PCG converges, to the
    wrong system's solution."""
    from repro.testing import Fault, FaultPlan, inject

    plan = FaultPlan({"sdc.edge_weights": Fault(mode="perturb",
                                                at_calls=None)})
    with inject(plan):
        res = run_tiny(name)
    assert plan.fired
    assert not res["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_readings_of_the_program_its_control_and_an_early_stop(name):
    import control
    from conftest import tiny_cell

    seeds = [2**31 + 11, 2**31 + 12]
    out = list(control.readings(tiny_cell(name), seeds,
                                ["program", "bf16@1", "tol1e-2@1"], 0.01,
                                require_tpu=False))
    assert [(o["variant"], o["seed"]) for o in out] == [
        ("program", seeds[0]), ("program", seeds[1]),
        ("bf16", seeds[0]), ("tol1e-2", seeds[0])]
    assert [o["correct"] for o in out] == [True, True, False, False]
    prog, early = out[0], out[3]
    assert len(prog["rel_residuals"]) == len(prog["iters"]) >= 1
    assert max(early["rel_residuals"]) > max(prog["rel_residuals"])
    assert sum(early["iters"]) < sum(prog["iters"][:len(early["iters"])])
