"""The stratified generator: same work under every seed, another order."""

import collections
import json
import os

import numpy as np
import pytest

from benchmark import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


SEGMENTS = [("ramp", 10.0), ("window", 40.0), ("tail", 12.0)]


def _schedule(seed, rate=8.0):
    return traffic.arrivals_schedule(_mix("chat-steady"), rate, SEGMENTS,
                                     seed, vocab=1000)


def _gaps(reqs, segment):
    due = [0.0] + [r.due_s for r in reqs]
    return [round(b - a, 9) for (a, b), r in zip(zip(due, due[1:]), reqs)
            if r.segment == segment]


def test_exact_counts_is_largest_remainder():
    assert traffic.exact_counts([0.30, 0.35, 0.25, 0.10], 320) \
        == [96, 112, 80, 32]
    # 7 items over thirds: 2.33 each, the odd one goes to the first
    assert traffic.exact_counts([1, 1, 1], 7) == [3, 2, 2]
    assert sum(traffic.exact_counts([0.3, 0.4, 0.2, 0.1], 13)) == 13


def test_gaps_are_the_exponentials_quantiles_and_fill_the_span():
    g = traffic.exponential_gaps(320, 40.0)
    assert g.sum() == pytest.approx(40.0)
    assert np.all(np.diff(g) > 0)
    # burstiness of an exponential: coefficient of variation near 1
    assert 0.9 < g.std() / g.mean() < 1.0


@pytest.mark.parametrize("segment", ["ramp", "window", "tail"])
def test_two_seeds_offer_the_same_multisets_in_another_order(segment):
    a, b = _schedule(1), _schedule(2 ** 31 + 11)
    sa = [r for r in a if r.segment == segment]
    sb = [r for r in b if r.segment == segment]
    count = collections.Counter
    assert count(len(r.prompt) for r in sa) == count(len(r.prompt) for r in sb)
    assert count(r.gen_len for r in sa) == count(r.gen_len for r in sb)
    assert sorted(_gaps(a, segment)) == sorted(_gaps(b, segment))
    assert [len(r.prompt) for r in sa] != [len(r.prompt) for r in sb]
    assert [r.gen_len for r in sa] != [r.gen_len for r in sb]
    assert _gaps(a, segment) != _gaps(b, segment)


def test_window_holds_rate_times_seconds_and_ends_on_its_end():
    reqs = _schedule(5)
    win = [r for r in reqs if r.segment == "window"]
    assert len(win) == 320
    assert win[-1].due_s == pytest.approx(50.0)
    assert all(10.0 < r.due_s <= 50.0 + 1e-9 for r in win)
    assert [r.uid for r in reqs] == list(range(len(reqs)))


def test_same_seed_gives_byte_identical_requests():
    dump = lambda reqs: json.dumps(  # noqa: E731
        [(r.uid, r.prompt, r.gen_len, r.due_s, r.segment) for r in reqs])
    assert dump(_schedule(2 ** 31 + 7)) == dump(_schedule(2 ** 31 + 7))
    mix = _mix("rollout-closed")
    one = traffic.closed_loop_requests(mix, 64, 9, 1000)
    two = traffic.closed_loop_requests(mix, 64, 9, 1000)
    assert [(r.prompt, r.gen_len) for r in one] \
        == [(r.prompt, r.gen_len) for r in two]


def test_first_wave_is_staggered_in_whole_quanta_and_keeps_totals():
    mix = _mix("rollout-closed")
    wave = traffic.first_wave(mix, 256, 64, 3, 1000)
    other = traffic.first_wave(mix, 256, 64, 4, 1000)
    assert len(wave) == 256 and all(r.uid < 0 for r in wave)
    assert all(r.gen_len % 64 == 0 and r.gen_len >= 64 for r in wave)
    # what left the output budget joined the prompt: totals are a mix pair
    totals = collections.Counter(len(r.prompt) + r.gen_len for r in wave)
    assert totals == collections.Counter(
        len(r.prompt) + r.gen_len for r in other)
    assert max(totals) <= 512 + 768
    # remaining budgets spread over every quantum of every output class
    remaining = collections.Counter(r.gen_len for r in wave)
    assert set(remaining) == {64 * k for k in range(1, 13)}
    assert remaining == collections.Counter(r.gen_len for r in other)


def test_mix_stats_matches_the_cell_files_worked_figures():
    s = traffic.mix_stats(_mix("rollout-closed"))
    assert s["mean_prompt"] == pytest.approx(384)
    assert s["mean_gen"] == pytest.approx(512)
    assert s["mean_live_context"] == pytest.approx(384 + 288)
    with open(os.path.join(HERE, "cells", "serve-offline-rollout.json")) as f:
        cell = json.load(f)
    pool, engine = cell["pool"], cell["engine"]
    assert pool["mean_live_context_tokens"] == 672
    assert pool["reserved_tokens"] \
        == engine["num_blocks"] * engine["block_size"]
    assert pool["reserved_bytes"] \
        == pool["reserved_tokens"] * pool["bytes_per_token"]
    assert pool["mean_bytes_in_use"] \
        == 672 * cell["clients"] * pool["bytes_per_token"]


def test_rollout_32k_is_one_class_and_its_stats_are_the_files_own():
    """ISSUE 54's mix: one prompt length, so the seed draws token ids and
    nothing else; mean live context 20,480 + 8,192 / 2."""
    mix = _mix("rollout-32k")
    s = traffic.mix_stats(mix)
    assert (s["mean_prompt"], s["mean_gen"], s["mean_live_context"]) \
        == (20480, 8192, 24576)
    assert {k: mix["mix_stats"][k] for k in s} == s
    a = traffic.closed_loop_requests(mix, 24, 1, 1000)
    b = traffic.closed_loop_requests(mix, 24, 2 ** 31 + 11, 1000)
    assert {(len(r.prompt), r.gen_len) for r in a + b} == {(20480, 8192)}
    assert a[0].prompt != b[0].prompt
    # the first wave: three clients at each phase of the output
    wave = traffic.first_wave(mix, 96, 256, 7, 1000)
    assert collections.Counter(r.gen_len for r in wave) \
        == {256 * k: 3 for k in range(1, 33)}
    assert {len(r.prompt) + r.gen_len for r in wave} == {28672}
