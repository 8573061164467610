"""``BENCHMARK.json`` and the files it names agree."""

import json
import os

import pytest

from conftest import BENCH, ROOT

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_names_files_that_exist(w):
    configs = {c["name"] for c in SPEC["configs"]}
    assert w["config"] in configs
    assert os.path.isfile(os.path.join(BENCH, "configs",
                                       w["config"] + ".json"))
    traffic = json.load(open(os.path.join(BENCH, "traffic",
                                          w["traffic"] + ".json")))
    assert traffic["tol"] > 0 and traffic["warmup_solves"] >= 1
    limits = json.load(open(os.path.join(BENCH, "workloads",
                                         w["name"] + ".json")))["limits"]
    assert limits["max_rel_residual"] > 0
    assert w["chips"] in (1, 4)


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_a_reader(m):
    import run

    name = m["name"]
    if not os.path.isfile(os.path.join(BENCH, "metrics", name + ".py")):
        name = name.split(".", 1)[0]
    assert callable(run.module("metrics", name).read)


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_matches_its_entry(c):
    path = os.path.join(ROOT, c["file"])
    assert path.startswith(os.path.join(ROOT, SPEC["paths"][0]) + os.sep)
    cfg = json.load(open(path))
    assert cfg["name"] == c["name"]
    assert sorted(cfg["reduced"]) == sorted(c["reduced"])


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_each_cell_reports_setup_another_metric_and_a_layer(w):
    import run

    cell = run.Cell(w["name"])
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


def test_per_layer_metrics_move_an_end_to_end_metric_of_their_cells():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    layers = open(os.path.join(ROOT, "PERF.md")).read()
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in moved.get("workloads", [w])
        assert f"| {m['layer']} |" in layers
