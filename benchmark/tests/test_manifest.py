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


FAMILIES = ("train", "chat", "rollout", "serve")


def test_the_manifest_has_room_and_every_entry_names_its_cells():
    assert len(MANIFEST["per_layer"]) <= 100 < 128
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert len(set(names)) == len(names)
    for m in MANIFEST["per_layer"]:
        assert m.get("workloads"), m["name"]
    # a reader file for every entry and no file without one
    files = {f[:-len(".json")] for f in os.listdir(
        os.path.join(ROOT, "benchmark", "layer_metrics"))}
    assert files == set(names)


def test_no_two_reader_files_are_one_metric_under_two_names():
    """A metric measured in eight cells is ONE entry with eight cells.
    Reader files whose bodies are equal apart from ``reads`` may differ by
    FAMILY only (``.train`` / ``.chat`` / ``.rollout`` / ``.serve``: the
    cells that report the end-to-end metric each ``moves``), never by
    cell: a cell joins the families' lists and brings files only for
    readers whose body is its own (PERF.md section 7)."""
    folder = os.path.join(ROOT, "benchmark", "layer_metrics")
    by_body = {}
    for f in sorted(os.listdir(folder)):
        spec = _load("layer_metrics", f)
        spec.pop("reads")
        by_body.setdefault(json.dumps(spec, sort_keys=True), []).append(
            f[:-len(".json")])
    moves = {m["name"]: m["moves"] for m in MANIFEST["per_layer"]}
    for names in (v for v in by_body.values() if len(v) > 1):
        families = [n.rpartition(".")[2] for n in names]
        assert all(f in FAMILIES for f in families), names
        assert len(set(families)) == len(families), names
        # twins of a family each move another metric, but for the one
        # quantity every cell has under two families' lists: set-up
        moved = {moves[n] for n in names}
        assert len(moved) == len(names) or moved == {"setup_s"}, names


def test_no_stem_stands_under_two_model_suffixes():
    """What keeps the places under the guard free (PR 58): a kernel is
    read by ONE entry with the cells that run it. A suffix is a family
    (the cells that report the end-to-end metric the entry ``moves``) or
    the ONE configuration that has the mechanism; a second configuration
    with it turns that entry into the family's, its sizes into the cells'
    own files (``kernels``), and brings no second reader file."""
    models = {}
    for m in MANIFEST["per_layer"]:
        stem, _, suffix = m["name"].rpartition(".")
        if stem and suffix not in FAMILIES:
            models.setdefault(stem, []).append(suffix)
    assert not {s: v for s, v in models.items() if len(v) > 1}


MANY = [m["name"] for m in MANIFEST["per_layer"] if len(m["workloads"]) > 1]


@pytest.mark.parametrize("name", MANY)
def test_a_many_cell_reader_holds_no_cells_digits(name):
    """A reader file whose entry lists more than one cell names no
    kernel by its digits (``-bf16_1216_2048``: a traced name is a cell's,
    ``{cell.kernels.<family>.op}``) and no model's size as a literal: a
    cost's ``fixed`` holds switches, not numbers, ``per`` is 1 (one
    evaluation over the stretch's totals) or a key, and a ``calls_share``
    is a share of a step's own calls (a quarter of a train step's four
    flash calls is the least the programs give), never one of N steps or
    layers of one model (1/64, 1/128)."""
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        text = f.read()
    assert not re.search(r"-(bf16|f32)_\d", text)
    for k in json.loads(text).get("kernels", ()):
        for c in k["costs"]:
            assert all(isinstance(v, bool)
                       for v in c.get("fixed", {}).values()), c
            assert c.get("per", 1) == 1 or isinstance(c["per"], str), c
            assert c.get("calls_share", 1.0) >= 0.25, c


@pytest.mark.parametrize("name", [
    "refill_wall_share.rollout",
    "queue_wait_p90_ms.chat", "host_ms_per_step.chat",
    "decode_ms_per_step.rollout", "decode_ms_per_step.olmoe",
    "decode_ms_per_step.solar2", "decode_ms_per_step.pangu",
    "batch_occupancy.chat", "batch_occupancy.rollout"])
def test_the_outside_twins_are_gone(name):
    assert name not in [m["name"] for m in MANIFEST["per_layer"]]
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json"))


def test_a_family_lists_its_cells_once_and_in_the_manifests_order():
    order = [w["name"] for w in MANIFEST["workloads"]]
    for m in MANIFEST["per_layer"]:
        cells = m.get("workloads")
        if cells:
            assert cells == [c for c in order if c in cells], m["name"]
            assert len(set(cells)) == len(cells), m["name"]


def test_bounds_are_within_the_contract():
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
