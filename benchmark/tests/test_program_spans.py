"""Naming the device's idle gaps by the program's own spans: hand-made
cases, then the recorded chip traces under ``benchmark/fixtures``."""

import os

import pytest

from benchmark import program_spans as ps
from benchmark import reduce_trace as rt

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")
DEV = "/device:TPU:0"


def _extra(ops, bench, program, modules=(), launches=None):
    return {"ops": {DEV: list(ops)}, "bench": sorted(bench),
            "program": sorted(program), "modules": {DEV: list(modules)},
            "launches": dict(launches or {})}


def test_a_gap_under_nested_spans_takes_the_innermost_of_each_kind():
    # window 0-10; the device runs 2-4 and 8-9. The host: put 0-5 holding
    # serve/put 0.5-4.5, which holds serve/plan 0.5-1 and serve/dispatch
    # 1-2; decode_pipelined 5-10 holding serve/commit_block 6-8.
    extra = _extra(
        ops=[(2.0, 4.0), (8.0, 9.0)],
        bench=[(0.0, 10.0, "window"), (0.0, 5.0, "put"),
               (5.0, 10.0, "decode_pipelined")],
        program=[(0.5, 4.5, "serve/put"), (0.5, 1.0, "serve/plan"),
                 (1.0, 2.0, "serve/dispatch"),
                 (6.0, 8.0, "serve/commit_block")])
    out = ps.name_gaps(extra)
    names = out["trace"]["idle_by_name"]
    # the gaps 0-2 and 4-8 are cut at every span boundary inside them
    assert names["put"] == pytest.approx(0.5 + 0.5)        # 0-0.5, 4.5-5
    assert names["put/serve/plan"] == pytest.approx(0.5)
    assert names["put/serve/dispatch"] == pytest.approx(1.0)
    assert names["put/serve/put"] == pytest.approx(0.5)    # 4-4.5
    assert names["decode_pipelined"] == pytest.approx(1.0 + 1.0)
    assert names["decode_pipelined/serve/commit_block"] \
        == pytest.approx(2.0)
    # the printed list: the largest sums, then the largest single gaps,
    # each under the name that covers most of it
    printed = out["breakdown"]["idle_gaps"]
    assert printed[0] == ["all_gaps_under_decode_pipelined",
                          pytest.approx(2.0)] \
        or printed[0] == ["all_gaps_under_decode_pipelined/serve/"
                          "commit_block", pytest.approx(2.0)]
    assert ["one_gap_under_decode_pipelined/serve/commit_block",
            pytest.approx(4.0)] in printed
    by_phase = out["trace"]["idle_by_phase"]
    assert by_phase["serve/plan"] == pytest.approx(0.5)
    assert by_phase["serve/dispatch"] == pytest.approx(1.0)
    assert by_phase["serve/commit_block"] == pytest.approx(2.0)
    # 0-0.5 and 4.5-6 and 9-10: no program span covers them
    assert by_phase["none"] == pytest.approx(0.5 + 1.5 + 1.0)
    # every idle second has exactly one name
    assert sum(by_phase.values()) == pytest.approx(10.0 - 3.0)
    assert sum(out["trace"]["idle_by_name"].values()) == pytest.approx(7.0)


def test_a_gap_under_no_span_and_a_trace_without_program_spans():
    extra = _extra(ops=[(1.0, 2.0)],
                   bench=[(0.0, 4.0, "window"), (0.5, 2.5, "train_batch")],
                   program=[])
    out = ps.name_gaps(extra)
    gaps = dict(map(tuple, out["breakdown"]["idle_gaps"]))
    assert gaps["all_gaps_under_train_batch"] == pytest.approx(1.0)
    assert gaps["all_gaps_under_none"] == pytest.approx(2.0)
    # nothing to read for a ratio over a program phase: the parent's case
    assert out["trace"]["idle_by_phase"] == {"none": pytest.approx(3.0)}
    assert out["trace"]["clock_offset_s"] is None
    # only the harness's own loop counts as named here
    assert out["trace"]["idle_named_share"] == pytest.approx(2.0 / 3.0)


def test_idle_under_the_harnesss_own_span_is_named_and_inside_a_call_not():
    # window 0-10, device busy 1-2 and 8-9. decode_batch 0.5-3 holds
    # serve/fused_apply 2-2.5; harvest 3-5 holds no program span; put 5-9
    # holds serve/plan 6-7. Idle: 0-0.5 none, 0.5-1 decode_batch (inside
    # the call, no bracket: unnamed), 2-2.5 fused_apply, 2.5-3
    # decode_batch (unnamed), 3-5 harvest, 5-6 put (unnamed), 6-7 plan,
    # 7-8 put (unnamed), 9-10 none
    extra = _extra(
        ops=[(1.0, 2.0), (8.0, 9.0)],
        bench=[(0.0, 10.0, "window"), (0.5, 3.0, "decode_batch"),
               (3.0, 5.0, "harvest"), (5.0, 9.0, "put")],
        program=[(2.0, 2.5, "serve/fused_apply"), (6.0, 7.0, "serve/plan")])
    out = ps.name_gaps(extra)["trace"]
    assert out["idle_by_name"]["harvest"] == pytest.approx(2.0)
    assert out["idle_by_name"]["put"] == pytest.approx(2.0)
    assert sum(out["idle_by_name"].values()) == pytest.approx(8.0)
    assert out["idle_named_share"] == pytest.approx((8.0 - 3.0) / 8.0)


def test_a_phase_without_idle_reads_zero_not_nothing():
    extra = _extra(ops=[(0.0, 4.0)], bench=[(0.0, 4.0, "window")],
                   program=[(1.0, 2.0, "serve/plan")])
    assert ps.name_gaps(extra)["trace"]["idle_by_phase"] \
        == {"serve/plan": 0.0}


def test_the_clock_offset_comes_from_causality_and_only_names_the_gaps():
    # the device's clock reads 1.0 behind the host's: runs launched at
    # host 3.0 and 6.0 appear to start at 2.05 and 5.2 (launch latencies
    # 0.05 and 0.2). The least shift that lets no run precede its launch
    # is 0.95. The device's idle gap 4-5.2 is host time 4.95-6.15.
    extra = _extra(
        ops=[(2.05, 4.0), (5.2, 7.0)],
        bench=[(2.0, 9.0, "window"), (2.5, 5.5, "put"),
               (5.5, 8.5, "decode_pipelined")],
        program=[(5.6, 6.1, "serve/dispatch")],
        modules=[(2.05, 4.0, "jit__step_greedy(123)", 7),
                 (5.2, 7.0, "jit__step_greedy_fb(456)", 8)],
        launches={(0, 7): 3.0, (0, 8): 6.0})
    out = ps.name_gaps(extra)
    assert out["trace"]["clock_offset_s"] == pytest.approx(0.95)
    names = out["trace"]["idle_by_name"]
    # 4.95-5.5 under put, 5.5-5.6 under decode_pipelined, 5.6-6.1 under
    # its dispatch, 6.1-6.15 under decode_pipelined again; the gaps 2-2.05
    # and 7-9 are host time 2.95-3 (put) and 7.95-9.95 (decode_pipelined
    # until 8.5, then the harness's own loop)
    assert names["put"] == pytest.approx(0.05 + 0.55)
    assert names["decode_pipelined/serve/dispatch"] == pytest.approx(0.5)
    assert names["decode_pipelined"] == pytest.approx(0.15 + 0.55)
    assert names["none"] == pytest.approx(1.45)
    # the sums are those of the clocks as recorded: 9 - 2 - busy
    assert sum(names.values()) == pytest.approx(7.0 - 1.95 - 1.8)
    programs = out["breakdown"]["device_programs"]
    assert programs == [["jit__step_greedy", pytest.approx(1.95), 1],
                        ["jit__step_greedy_fb", pytest.approx(1.8), 1]]


def test_on_several_devices_the_first_ones_offset_places_its_gaps():
    extra = _extra(ops=[(1.0, 2.0)], bench=[(0.0, 4.0, "window")],
                   program=[(2.4, 2.6, "train/dispatch")],
                   modules=[(1.0, 2.0, "jit_step_fn(1)", 5)],
                   launches={(0, 5): 1.25, (1, 5): 1.25})
    extra["ops"]["/device:TPU:1"] = [(0.5, 1.5)]
    extra["modules"]["/device:TPU:1"] = [(0.5, 1.5, "jit_step_fn(1)", 5)]
    out = ps.name_gaps(extra)
    # device 1's clock is 0.75 behind; device 0's 0.25, and its gap 2-4
    # is host time 2.25-4.25
    assert out["trace"]["clock_offset_s"] == pytest.approx(0.25)
    assert out["trace"]["idle_by_phase"]["train/dispatch"] \
        == pytest.approx(0.2)
    assert out["breakdown"]["device_programs"] \
        == [["jit_step_fn", pytest.approx(2.0), 2]]


def test_the_old_recorded_trace_reads_as_before_with_the_gaps_placed():
    """``v5e_matmul_loop.xplane.pb`` holds no program span: the names are
    the benchmark's own, the idle seconds those ``reduce_trace`` finds,
    and the 1.5 ms that trace's docstring speaks of is now measured."""
    path = os.path.join(FIXTURES, "v5e_matmul_loop.xplane.pb")
    out = ps.read(path)
    old = rt.reduce(rt.load(path))
    assert out["trace"]["clock_offset_s"] == pytest.approx(1.482e-3,
                                                           abs=2e-5)
    names = out["trace"]["idle_by_name"]
    assert sum(names.values()) \
        == pytest.approx(old["window_s"] - old["busy_s"], rel=1e-9)
    # with the gaps placed on the host's clock the four matmuls of a
    # round lie under bench:work and the sleeps hold only sleeping
    assert names["sleep"] == pytest.approx(0.1029, abs=1e-3)
    assert names["work"] == pytest.approx(0.0040, abs=5e-4)
    assert out["breakdown"]["device_programs"] \
        == [["jit_bench_fixture_matmul", pytest.approx(old["busy_s"],
                                                       rel=1e-3), 16]]
    assert set(out["trace"]["idle_by_phase"]) == {"none"}


def test_the_recorded_v5e_serve_trace():
    """``fixtures/v5e_serve_spans.xplane.pb`` (my chip run, PR 25, TPU v5
    lite; ``record_serve_spans.py``): a one-layer toy decoder behind the
    real engine. Under ``bench:window``: a ``put`` of two prompts (two
    prefill steps), a four-step ``decode_pipelined`` burst, a 5 ms
    sleep, a second burst, two flushes (their ``serve/flush`` spans
    were cut from the file when review deleted that bracket; the 40
    us that idled under them now read ``none``). The device is busy 0.17 ms of the 29.4 ms:
    nearly all of it is launch time, which is what the names must
    say."""
    path = os.path.join(FIXTURES, "v5e_serve_spans.xplane.pb")
    assert os.path.getsize(path) < 100 * 1024
    extra = ps.load(path)
    spans = {}
    for _s, _e, name in extra["program"]:
        spans[name] = spans.get(name, 0) + 1
    assert spans == {"serve/put": 1, "serve/decode_pipelined": 2,
                     "serve/plan": 10,
                     "serve/dispatch": 10, "serve/commit_block": 10,
                     "serve/commit_apply": 10}
    # every program run has its launch: ten run ids, in order
    runs = extra["modules"][DEV]
    assert [r[3] for r in runs] == list(range(192, 202))
    assert set(extra["launches"]) == {(0, r) for r in range(192, 202)}

    out = ps.name_gaps(extra)
    trace, printed = out["trace"], out["breakdown"]
    # the clock offset: no run starts before its launch, one starts with it
    offset = trace["clock_offset_s"]
    assert offset == pytest.approx(1.4088e-3, abs=1e-6)
    lags = [r[0] + offset - extra["launches"][(0, r[3])] for r in runs]
    assert min(lags) == pytest.approx(0.0, abs=1e-12)
    assert all(lag >= 0.0 for lag in lags)

    # the sums are reduce_trace's own: the offset moved no second
    old = rt.reduce(rt.load(path))
    idle_s = old["window_s"] - old["busy_s"]
    assert old["window_s"] == pytest.approx(0.029417897, abs=1e-8)
    assert sum(trace["idle_by_phase"].values()) \
        == pytest.approx(idle_s, rel=1e-9)
    assert sum(trace["idle_by_name"].values()) \
        == pytest.approx(idle_s, rel=1e-9)

    # innermost naming: the device waits while the host launches
    by_phase = trace["idle_by_phase"]
    assert by_phase["serve/dispatch"] == pytest.approx(0.016369, abs=2e-6)
    assert by_phase["serve/commit_block"] == pytest.approx(0.003479,
                                                           abs=2e-6)
    assert by_phase["serve/plan"] == pytest.approx(0.000676, abs=2e-6)
    assert by_phase["none"] == pytest.approx(0.006926, abs=2e-6)
    assert set(by_phase) == set(spans) | {"none"}
    names = trace["idle_by_name"]
    assert names["decode_pipelined/serve/dispatch"] \
        == pytest.approx(0.014453, abs=2e-6)
    assert names["put/serve/dispatch"] == pytest.approx(0.001916, abs=2e-6)
    assert names["sleep"] == pytest.approx(0.005430, abs=2e-6)
    # reduce_trace, without the offset and at call granularity, files
    # 8.1 ms under the 5.4 ms sleep: the burst's tail slid into it
    assert dict(map(tuple, old["idle_gaps"]))["all_gaps_under_sleep"] \
        == pytest.approx(0.008130, abs=2e-6)
    assert printed["idle_gaps"][0] == [
        "all_gaps_under_decode_pipelined/serve/dispatch",
        pytest.approx(0.014453, abs=2e-6)]
    # unnamed is only what idles inside a call but outside every bracket
    # of the program's own (3 us in put, 20 us in decode_pipelined): the
    # sleep is the harness's own code (no program span opens under it)
    # and counts as named since PR 37; without it the share read 0.8135
    assert trace["idle_named_share"] == pytest.approx(0.99919, abs=1e-4)
    assert (1 - trace["idle_named_share"]) * idle_s == pytest.approx(
        names["put"] + names["decode_pipelined"], rel=1e-6)

    # programs, from the module line: 2 prefill + 2 unfed decode steps
    # run _step_greedy, the 6 fed steps _step_greedy_fb
    assert printed["device_programs"] == [
        ["jit__step_greedy_fb", pytest.approx(100.49e-6, rel=1e-3), 6],
        ["jit__step_greedy", pytest.approx(74.76e-6, rel=1e-3), 4]]
