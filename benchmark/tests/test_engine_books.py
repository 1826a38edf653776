"""The seven readers of the engines' closed books (PR 55; one for each
new total, the manifest's guard of 100 entries kept) on hand-made
observations: each gives the arithmetic its ``reads`` line states over
the window's delta of ``engine.pipeline_stats`` / ``engine.step_stats``,
returns nothing when its denominator is 0, and returns nothing on a
program that lacks the totals (the parent, which the driver runs under
these same files)."""

import json
import os

import pytest

from benchmark import readers

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def _spec(name):
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def _read(name, obs):
    return readers.read(_spec(name), obs)


# one window of a fused-loop cell: six rounds, a refill of four steps each
ROLLOUT = {"rounds": 6, "pipeline": {
    "put_s": 6.0, "decode_batch_s": 24.0, "decode_pipelined_s": 0.0,
    "admit_s": 0.03, "plan_s": 0.06, "plan_count_s": 0.012,
    "dispatch_s": 0.15, "commit_block_s": 5.4, "commit_apply_s": 0.06,
    "fused_stage_s": 0.018, "fused_dispatch_s": 0.03,
    "fused_readback_s": 23.7, "fused_count_s": 0.024, "fused_apply_s": 0.18,
    "prefill_steps": 72, "steps": 72, "fed_steps": 0}}
# one window of the open-loop cell
CHAT = {"pipeline": {
    "put_s": 8.0, "decode_pipelined_s": 32.0, "decode_batch_s": 0.0,
    "admit_s": 0.2, "plan_s": 4.0, "plan_count_s": 1.2, "dispatch_s": 6.0,
    "commit_block_s": 24.0, "commit_apply_s": 1.8, "steps": 4000,
    "fed_steps": 3000, "prefill_steps": 900}}
# 130 steps of 0.3 s
TRAIN = {"step_stats": {
    "steps": 130, "train_batch_s": 39.0, "stage_s": 0.13, "dispatch_s": 0.39,
    "device_wait_s": 38.22, "commit_apply_s": 0.13, "step_exit_s": 0.065}}

CASES = [
    ("refill_wait_ms_per_step.rollout", ROLLOUT, 1e3 * 5.4 / 72),
    ("engine_bracketed_share.rollout", ROLLOUT,
     100 * (0.03 + 0.06 + 0.15 + 5.4 + 0.06 + 0.018 + 0.03 + 23.7 + 0.024
            + 0.18) / 30.0),
    ("counters_host_ms_per_round.rollout", ROLLOUT,
     1e3 * (0.012 + 0.024) / 6),
    ("engine_bracketed_share.chat", CHAT,
     100 * (0.2 + 4.0 + 6.0 + 24.0 + 1.8) / 40.0),
    ("counters_host_ms_per_step.chat", CHAT, 1e3 * 1.2 / 4000),
    ("engine_bracketed_share.train", TRAIN,
     100 * (0.13 + 0.39 + 38.22 + 0.13 + 0.065) / 39.0),
    ("observer_ms_per_step.train", TRAIN, 0.5),
]
NAMES = [name for name, _, _ in CASES]


def _zeroed(obs, keys):
    out = {k: dict(v) if isinstance(v, dict) else v for k, v in obs.items()}
    for key in keys:
        head, _, leaf = key.rpartition(".")
        (out[head] if head else out)[leaf] = 0
    return out


@pytest.mark.parametrize("name,obs,value", CASES, ids=NAMES)
def test_a_reader_gives_the_arithmetic_of_its_reads_line(name, obs, value):
    assert _read(name, obs) == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("name,obs,_value", CASES, ids=NAMES)
def test_a_denominator_of_nought_gives_nothing(name, obs, _value):
    assert _read(name, _zeroed(obs, _spec(name)["den"])) is None
    assert _read(name, {}) is None


@pytest.mark.parametrize("name,obs,_value", CASES, ids=NAMES)
def test_a_program_without_the_totals_leaves_nothing_to_read(name, obs,
                                                             _value):
    """The parent's engines hold none of PR 55's keys: a reader that
    names one finds nothing and does not raise. One of the seven reads
    counters the parent has (``commit_block_s / prefill_steps``) and
    reads there as here."""
    new = {"put_s", "decode_pipelined_s", "decode_batch_s", "admit_s",
           "plan_count_s", "fused_stage_s", "fused_readback_s",
           "fused_count_s", "train_batch_s", "device_wait_s", "step_exit_s"}
    parent = {k: {leaf: v for leaf, v in stats.items() if leaf not in new}
              if isinstance(stats, dict) else stats
              for k, stats in obs.items()}
    old_only = name == "refill_wait_ms_per_step.rollout"
    assert (_read(name, parent) is not None) == old_only


def test_the_nested_bracket_is_no_addend():
    """``plan_count_s`` lies inside ``plan_s``: a share that added both
    would count the counters' arithmetic twice and could pass 100."""
    for name in ("engine_bracketed_share.rollout",
                 "engine_bracketed_share.chat"):
        assert "pipeline.plan_count_s" not in _spec(name)["num"]
    full = {"pipeline": dict(CHAT["pipeline"], admit_s=0.2, plan_s=4.0,
                             dispatch_s=6.0, commit_block_s=28.0,
                             commit_apply_s=1.8)}
    assert _read("engine_bracketed_share.chat", full) == pytest.approx(100.0)


@pytest.mark.parametrize("family,sibling", [
    ("rollout", "fused_host_ms_per_round.rollout"),
    ("chat", "host_self_ms_per_step.chat"),
    ("train", "host_launch_ms_per_step.train")])
def test_each_family_carries_its_siblings_cells(family, sibling):
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    mine = [n for n in NAMES if n.endswith("." + family)]
    assert len(mine) == {"rollout": 3, "chat": 2, "train": 2}[family]
    for name in mine:
        assert by_name[name]["workloads"] == by_name[sibling]["workloads"]
        assert by_name[name]["moves"] == by_name[sibling]["moves"]
        assert _spec(name)["reducer"] == "ratio"


def test_the_seven_are_the_manifests_last_entries():
    assert [m["name"] for m in MANIFEST["per_layer"][-7:]] == NAMES
