"""The readers of the metrics PR 25 added, on hand-made observations.

All three read ``obs["pipeline"]``, the window's delta of
``engine.pipeline_stats`` that the open-loop job already takes: the
engine's new totals reach the readers with no edit to the harness."""

import json
import os

import pytest

from benchmark import program_spans, readers

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(name, obs):
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        return readers.read(json.load(f), obs)


PIPELINE = {"steps": 100, "plan_s": 0.05, "dispatch_s": 0.15,
            "commit_block_s": 2.3, "commit_apply_s": 0.07,
            "prefill_tokens_real": 900, "prefill_tokens_planned": 8192,
            "decode_slots_live": 3800, "decode_slots_planned": 6400}
# what the parent's engine leaves in the same dict
PARENT = {k: PIPELINE[k] for k in ("steps", "plan_s", "dispatch_s",
                                   "commit_block_s")}


def test_the_host_steps_own_work_apart_from_its_wait():
    obs = {"pipeline": PIPELINE}
    own = _read("host_self_ms_per_step.chat", obs)
    assert own == pytest.approx(2.7)
    # the wait in the readback (commit_block_s) is in no metric: it
    # shrinks when the device gets faster and grows when the host does
    # (host_ms_per_step.chat, which timed the layer from outside with the
    # wait in it, went with PR 54)
    assert own == pytest.approx(1e3 * (0.05 + 0.15 + 0.07) / 100)


@pytest.mark.parametrize("name,value", [
    ("prefill_useful_share.chat", 100 * 900 / 8192),
    ("bucket_occupancy.chat", 59.375),
])
def test_the_scheduler_shares(name, value):
    assert _read(name, {"pipeline": PIPELINE}) == pytest.approx(value)


@pytest.mark.parametrize("name", [
    "host_self_ms_per_step.chat", "prefill_useful_share.chat",
    "bucket_occupancy.chat"])
def test_a_parents_engine_leaves_nothing_to_read(name):
    assert _read(name, {"pipeline": PARENT}) is None
    assert _read(name, {}) is None


def test_the_command_line_prints_the_named_breakdown(capsys):
    trace = os.path.join(HERE, "fixtures", "v5e_serve_spans.xplane.pb")
    assert program_spans.main([trace]) == 0
    line = json.loads(capsys.readouterr().out)
    assert line["idle_by_phase"]["serve/dispatch"] > 0
    assert line["idle_gaps"][0][0] \
        == "all_gaps_under_decode_pipelined/serve/dispatch"
    assert line["clock_offset_s"] == pytest.approx(1.4e-3, abs=2e-4)
    assert program_spans.main([]) == 2
