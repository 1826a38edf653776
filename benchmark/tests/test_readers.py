"""The reducers of the per-layer readers, on hand-made observations."""

import json
import os

import pytest

from benchmark import kernel_cost, readers

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec(name):
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_percentile_share_ratio_and_value():
    obs = {"gen_late_s": [0.001 * i for i in range(101)],
           "met_limits": [True, True, False, True],
           "pipeline": {"decode_slots_live": 30, "decode_slots_planned": 40},
           "setup": {"programs": 9, "compile_s": 4.0, "trace_lower_s": 18.0,
                     "phase_s": {"import": 12.0},
                     "at_window_open": {"compile_s": 2.5}}}
    assert readers.read(_spec("gen_late_p99_ms"), obs) == pytest.approx(99.0)
    assert readers.read(_spec("goodput_share.chat"), obs) == 75.0
    assert readers.read(_spec("bucket_occupancy.chat"), obs) == 75.0
    assert readers.read(_spec("setup_programs.serve"), obs) == 9.0
    # set-up by phase (PR 54): the phase clock and JAX's own events
    assert readers.read(_spec("setup_import_s"), obs) == 12.0
    assert readers.read(_spec("setup_compile_s"), obs) == 2.5
    assert readers.read(_spec("setup_trace_lower_s.serve"), obs) \
        == readers.read(_spec("setup_trace_lower_s.train"), obs) == 18.0


def test_paged_roofline_is_least_time_over_kernel_time():
    # 28 layers, 100 decode steps of 128 sequences at 672 tokens: the K and
    # V rows of a layer are read once a step
    ctx_tokens = 100 * 128 * 672
    att = {"q_heads": 12, "kv_heads": 2, "head_dim": 128, "kv_row": 256,
           "layers": 28}
    peak = kernel_cost.peaks("TPU v5 lite")
    least = 28 * 2.0 * ctx_tokens * 2 * 128 * 2 / peak["hbm_bytes_per_s"]
    obs = {"peak": peak, "attention": att,
           # the layers that keep K/V are the cell's to state (PR 58)
           "cell": {"kernels": {"paged_attn": {"layers": 28}}},
           "traced": {"decode_context_tokens": ctx_tokens},
           "trace": {"n_devices": 1,
                     "ops": {"closed_call-bf16_128_12_256": 4 * least,
                             "fusion-bf16_128_12_256": 9.0},
                     "op_counts": {"closed_call-bf16_128_12_256": 2800,
                                   "fusion-bf16_128_12_256": 5}}}
    assert readers.read(_spec("paged_attn_roofline.rollout"), obs) \
        == pytest.approx(25.0)
    # no such kernel in the trace: nothing to read, nothing reported
    obs["trace"]["ops"] = {"fusion-bf16_128_12_256": 9.0}
    obs["trace"]["op_counts"] = {"fusion-bf16_128_12_256": 5}
    assert readers.read(_spec("paged_attn_roofline.rollout"), obs) is None


def test_flash_roofline_counts_two_forwards_and_one_backward_per_four_calls():
    att = {"batch": 2, "heads": 16, "seq": 2048, "head_dim": 128}
    peak = kernel_cost.peaks("TPU v5 lite")
    fwd = kernel_cost.roofline_seconds(
        kernel_cost.flash_attention_cost(2, 16, 2048, 128), peak)["seconds"]
    bwd = kernel_cost.roofline_seconds(kernel_cost.flash_attention_cost(
        2, 16, 2048, 128, backward=True), peak)["seconds"]
    calls = 96 * 3                       # 24 layers x 4 kernels x 3 steps
    least = 72 * (2 * fwd + bwd)
    obs = {"peak": peak, "attention": att,
           "trace": {"n_devices": 1,
                     "ops": {"attn-bf16_2_16_2048_128": 2 * least},
                     "op_counts": {"attn-bf16_2_16_2048_128": calls}}}
    assert readers.read(_spec("flash_attn_roofline.train"), obs) \
        == pytest.approx(50.0)
    # on four chips a device's share: the same calls and seconds on each
    # of four devices read the same 50 % (a chip's batch is 2 there too)
    four = {"peak": peak, "attention": att,
            "trace": {"n_devices": 4,
                      "ops": {"attn-bf16_2_16_2048_128": 4 * 2 * least},
                      "op_counts": {"attn-bf16_2_16_2048_128": 4 * calls}}}
    assert readers.read(_spec("flash_attn_roofline.train"), four) \
        == pytest.approx(50.0)
