"""Each job kind exports what its cells' readers name: the ``--rehearse``
path of one cell a kind on the CPU (toy sizes, about 10 s a cell, the
traced stretch walked with no profiler under it), then every key a
per-layer reader of that cell names outside ``trace.*`` and ``peak.*``
(which only a chip run fills) must resolve in the job's observations.
The rot that left ``moe_ffn_share.*`` reading nothing for four PRs (a
reader naming what no job exports any more) then fails here."""

import pytest

from benchmark import readers, run
from benchmark.common import load_json, load_manifest

MANIFEST = load_manifest()


@pytest.mark.parametrize("cell", ["train-1p3b-1chip", "serve-chat-steady",
                                  "serve-offline-rollout"])
def test_a_rehearsal_fills_every_key_its_cells_readers_name(cell, capsys):
    line, obs = run.run_cell(["--workload", cell, "--seed", "2147483659",
                              "--rehearse", "--trace", "1"])
    capsys.readouterr()
    assert line["correct"] and line["device"]["platform"] == "cpu"
    assert line["metrics"] == {} and "breakdown" not in line
    assert list(line)[-1] == "compared" and line["compared"]
    for pair in line["compared"].values():
        assert set(pair) == {"value", "limit"}
    names = [m["name"]
             for m in run._metrics_of(MANIFEST, "per_layer", cell)]
    assert len(names) >= 5
    missing = []
    for name in names:
        spec = load_json("layer_metrics", name + ".json")
        missing += [(name, key) for key in readers.keys_of(spec)
                    if key.split(".")[0] not in ("trace", "peak")
                    and readers.lookup(obs, key) is None]
    assert not missing
    # the engine's own totals come whole: a counter with no reader yet
    # is in the delta all the same
    if cell.startswith("serve"):
        for stretch in (obs, obs["traced"]):
            assert {"moe_rows_elsewhere", "state_slots_live",
                    "kv_write_runs"} <= set(stretch["pipeline"])
    else:
        assert obs["step_stats"]["steps"] == obs["steps"]
