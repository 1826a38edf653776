"""The trace reduction: interval algebra on hand-made traces, then the
recorded TPU trace under ``benchmark/fixtures``."""

import os

import pytest

from benchmark import reduce_trace as rt

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures", "v5e_matmul_loop.xplane.pb")


def test_union_and_subtract():
    assert rt.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert rt.total([(0, 2), (3, 4)]) == 3
    assert rt.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) \
        == [(0, 1), (2, 4), (6, 9)]
    assert rt.subtract([(0, 1), (5, 6)], [(0, 1)]) == [(5, 6)]


def test_leaves_drop_the_parents_of_nested_events():
    events = [(0.0, 10.0, "while"), (1.0, 2.0, "fusion.1"),
              (3.0, 4.0, "fusion.2"), (11.0, 12.0, "copy.3")]
    assert [n for _, _, n in rt.leaves(events)] \
        == ["fusion.1", "fusion.2", "copy.3"]


def test_stable_names_drop_the_compilers_numbering():
    assert rt.stable_name("fusion.123") == "fusion"
    assert rt.stable_name("_step_greedy_fb") == "_step_greedy_fb"
    assert rt.stable_name(
        "%fusion.340 = bf16[16,512,8960]{2,1,0:T(8,128)(2,1)} fusion(bf16["
        "1536,8960]{1,0} %custom-call.128), kind=kOutput") \
        == "fusion-bf16_16_512_8960"
    assert rt.stable_name(
        "%convolution_reduce_fusion = (bf16[64]{0:T(256)}, s32[64]{0}) "
        "fusion(bf16[151936,1536]{1,0} %p)") \
        == "convolution_reduce_fusion-bf16_64"
    assert rt.stable_name("%copy-done = bf16[2048,2048]{1,0} copy-done("
                          "(bf16[2048,2048]) %copy-start)") \
        == "copy-done-bf16_2048_2048"


def _hand_trace():
    # one device, window 0..10 s: ops busy 1-3, 3-4, 6-9 (a collective
    # 6-8 of which 7-8 overlaps compute), idle 0-1, 4-6, 9-10
    dev = [(1.0, 3.0, "fusion.1"), (3.0, 4.0, "custom-call.2"),
           (6.0, 8.0, "%all-gather.3 = bf16[8,4]{1,0} all-gather(%p)"),
           (7.0, 9.0, "fusion.4")]
    spans = [(0.0, 10.0, "window"), (0.5, 4.5, "train_batch"),
             (4.6, 5.9, "put")]
    return {"devices": {"/device:TPU:0": dev}, "spans": spans}


def test_reduce_a_hand_made_trace():
    r = rt.reduce(_hand_trace())
    assert r["window_s"] == 10.0
    assert r["busy_s"] == pytest.approx(6.0)        # 1-4 and 6-9
    assert r["collective_s"] == pytest.approx(2.0)
    assert r["exposed_collective_s"] == pytest.approx(1.0)   # 6-7
    assert r["ops"]["fusion"] == pytest.approx(4.0)
    assert r["op_counts"]["fusion"] == 2
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["all_gaps_under_train_batch"] == pytest.approx(1.0)  # 0-1
    assert gaps["all_gaps_under_put"] == pytest.approx(2.0)          # 4-6
    assert gaps["all_gaps_under_none"] == pytest.approx(1.0)         # 9-10
    assert rt.kernel_seconds(r, r"^custom-call") == (pytest.approx(1.0), 1)


def test_an_asynchronous_collective_is_exposed_only_where_nothing_computes():
    # all-gather in flight 2-6 on the async line; compute 1-3 and 5-7; the
    # operation line shows only its launch and the wait 4-5
    trace = {"devices": {"/device:TPU:0": [
        (1.0, 3.0, "fusion.1"),
        (2.0, 2.0, "%all-gather-start.1 = (f32[4]) all-gather-start(%p)"),
        (4.0, 5.0, "%all-gather-done.1 = f32[4]{0} all-gather-done(%s)"),
        (5.0, 7.0, "fusion.2")]},
        "async": {"/device:TPU:0": [
            (2.0, 6.0, "%all-gather-start.1 = (f32[4]) all-gather-start(%p)")]},
        "spans": [(0.0, 8.0, "window")]}
    r = rt.reduce(trace)
    assert r["collective_s"] == pytest.approx(4.0)            # 2-6
    assert r["exposed_collective_s"] == pytest.approx(2.0)    # 3-5
    assert r["busy_s"] == pytest.approx(5.0)                  # 1-3, 4-7


def test_a_trace_with_no_device_operation_is_refused():
    with pytest.raises(ValueError):
        rt.reduce({"devices": {}, "spans": []})


def test_the_recorded_v5e_trace():
    """``fixtures/v5e_matmul_loop.xplane.pb`` (my chip run, PR 24, TPU v5
    lite): five rounds of four ``tanh(x @ x)`` on bf16[2048, 2048] under
    ``bench:work``, each followed by a 20 ms sleep under ``bench:sleep``,
    all under ``bench:window``. The device's clock reads ~1.5 ms behind
    the host's in this trace, so the first round's four operations fall
    just before the window's start: 16 of the 20 are inside."""
    r = rt.reduce(rt.load(FIXTURE))
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(0.110153, abs=1e-6)
    name = "convolution_tanh_fusion-bf16_2048_2048"
    assert r["op_counts"][name] == 16
    # one matmul of 2 * 2048^3 FLOPs in ~91 us: 189 TFLOP/s of the 197
    per_call = r["ops"][name] / 16
    assert 2 * 2048 ** 3 / per_call == pytest.approx(189e12, rel=0.02)
    assert r["busy_s"] == pytest.approx(0.0016393, rel=1e-3)
    assert r["busy_s"] < sum(r["ops"].values()) + 1e-9
    assert r["collective_s"] == 0.0
    gaps = dict(map(tuple, r["idle_gaps"][:1]))
    # the device idles through the five sleeps, and the reduction says so
    assert gaps["all_gaps_under_sleep"] == pytest.approx(0.1085, abs=1e-3)
    assert r["busy_s"] + sum(v for k, v in r["idle_gaps"]
                             if k.startswith("all_gaps")) \
        == pytest.approx(r["window_s"], rel=1e-6)
    assert rt.kernel_seconds(r, "^convolution_tanh_fusion")[1] == 16
