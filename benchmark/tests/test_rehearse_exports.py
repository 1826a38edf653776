"""Each job kind exports what its cells' readers name: the ``--rehearse``
path of one cell a kind on the CPU (toy sizes, about 10 s a cell, the
traced stretch walked with no profiler under it), then every key a
per-layer reader of that cell names outside ``trace.*`` and ``peak.*``
(which only a chip run fills) must resolve in the job's observations.
The rot that left ``moe_ffn_share.*`` reading nothing for four PRs (a
reader naming what no job exports any more) then fails here."""

import functools

import pytest

from benchmark import readers, run
from benchmark.common import load_json, load_manifest

MANIFEST = load_manifest()
#: one rehearsed cell a job kind (the tier-1 twin runs these three)
REHEARSED = {"train": "train-1p3b-1chip", "open_loop": "serve-chat-steady",
             "closed_loop": "serve-offline-rollout"}


@functools.lru_cache(maxsize=None)
def _rehearse(cell):
    return run.run_cell(["--workload", cell, "--seed", "2147483659",
                         "--rehearse", "--trace", "1"])


def _missing(name, obs):
    """Keys the reader of ``name`` names outside ``trace.*`` / ``peak.*``
    that the observations do not hold."""
    spec = load_json("layer_metrics", name + ".json")
    return [(name, key) for key in readers.keys_of(spec)
            if key.split(".")[0] not in ("trace", "peak")
            and readers.lookup(obs, key) is None]


@pytest.mark.parametrize("cell", list(REHEARSED.values()))
def test_a_rehearsal_fills_every_key_its_cells_readers_name(cell, capsys):
    line, obs = _rehearse(cell)
    capsys.readouterr()
    assert line["correct"] and line["device"]["platform"] == "cpu"
    assert line["metrics"] == {} and "breakdown" not in line
    assert list(line)[-1] == "compared" and line["compared"]
    for pair in line["compared"].values():
        assert set(pair) == {"value", "limit"}
    names = [m["name"]
             for m in run._metrics_of(MANIFEST, "per_layer", cell)]
    assert len(names) >= 5
    assert not [miss for name in names for miss in _missing(name, obs)]
    # the engine's own totals come whole: a counter with no reader yet
    # is in the delta all the same
    if cell.startswith("serve"):
        for stretch in (obs, obs["traced"]):
            assert {"moe_rows_elsewhere", "state_slots_live",
                    "kv_write_runs"} <= set(stretch["pipeline"])
    else:
        assert obs["step_stats"]["steps"] == obs["steps"]


FAMILY_PAIRS = [(m["name"], cell) for m in MANIFEST["per_layer"]
                for cell in m.get("workloads", ()) if len(m["workloads"]) > 1]


@pytest.mark.parametrize("name,cell", FAMILY_PAIRS,
                         ids=[f"{n}-{c}" for n, c in FAMILY_PAIRS])
def test_a_familys_reader_finds_its_keys_in_every_cell_of_its_list(
        name, cell, capsys):
    """One entry a metric with its cells (PR 54): for every cell of a
    family's list the keys its reader names resolve in what that cell's
    job kind exports. The jobs export by kind and the engines' counters
    whole (``counters_delta``), so one rehearsed cell a kind stands for
    the kind: a key that resolves there resolves in every cell the kind
    runs, at nought where the model has no such layer. What a reader
    takes from the cell's own file (``cell.*``: its ``kernels`` block, PR
    58) is looked up in THAT cell's file, not in the stand-in's."""
    own = load_json("cells", cell + ".json")
    _line, obs = _rehearse(REHEARSED[own["kind"]])
    capsys.readouterr()
    assert not _missing(name, dict(obs, cell=own))
