"""BENCHMARK.json against the files it names."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def _cells_of(metric):
    return set(metric.get("workloads")
               or [w["name"] for w in MANIFEST["workloads"]])


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    n4 = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert n4 <= max(1, len(MANIFEST["workloads"]) // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("w", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_every_cell_has_its_files(w):
    assert NAME.match(w["name"]) and len(w["why"]) <= 200
    cell = _load("cells", w["name"] + ".json")
    _load("traffic", w["traffic"] + ".json")
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "jobs", cell["kind"] + ".py"))
    config = next(c for c in MANIFEST["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        model_type = json.load(f)["model_type"]
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "model_types", model_type + ".py"))


@pytest.mark.parametrize("w", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_every_cell_reports_setup_another_metric_and_a_layer_metric(w):
    e2e = [m["name"] for m in MANIFEST["end_to_end"]
           if w["name"] in _cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(w["name"] in _cells_of(m) for m in MANIFEST["per_layer"])


@pytest.mark.parametrize("m", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_every_layer_metric_has_a_reader_and_moves_a_metric_of_its_cells(m):
    from benchmark import readers
    assert NAME.match(m["name"])
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    spec = _load("layer_metrics", m["name"] + ".json")
    assert spec["reducer"] in readers.REDUCERS and spec["reads"]
    moved = next(e for e in MANIFEST["end_to_end"] if e["name"] == m["moves"])
    assert _cells_of(m) <= _cells_of(moved)
    # a reader that finds nothing to read returns nothing
    assert readers.read(spec, {}) is None


def test_bounds_are_within_the_contract():
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
