"""``moe_cost.grouped_moe_ffn_cost`` against hand-worked numbers at the
shapes of ``serve-olmoe-rollout`` (hidden 2048, 64 experts of width 1024,
8 experts a token, bfloat16) and, at ``matrices=2``, of
``serve-nemotron3-nano-rollout-long``'s ungated experts (hidden 2688,
published width 1856; until PR 58 ``ssm_cost.ungated_ffn_cost``)."""

import pytest

from benchmark import kernel_cost, moe_cost

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
HIDDEN, WIDTH, EXPERTS = 2048, 1024, 64
ONE_MATRIX = HIDDEN * WIDTH * 2            # bytes: 4,194,304


@pytest.mark.parametrize("rows, hit, flops, nbytes, bound", [
    # a decode step: 32 sequences x 8 experts
    (256, 63, 6 * 256 * HIDDEN * WIDTH,
     3 * 63 * ONE_MATRIX + 2 * 256 * HIDDEN * 2, "memory"),
    # a refill step: [16, 512] positions x 8 experts, every expert hit
    (65536, 64, 6 * 65536 * HIDDEN * WIDTH,
     3 * 64 * ONE_MATRIX + 2 * 65536 * HIDDEN * 2, "compute"),
], ids=["decode-256-rows", "refill-65536-rows"])
def test_cost_and_bound_at_the_cells_shapes(rows, hit, flops, nbytes, bound):
    cost = moe_cost.grouped_moe_ffn_cost(rows, hit, HIDDEN, WIDTH, 2)
    assert cost == {"flops": float(flops), "bytes": float(nbytes)}
    assert kernel_cost.roofline_seconds(cost, V5E)["bound"] == bound


def test_decode_is_the_weights_and_refill_is_the_matmuls():
    dec = moe_cost.grouped_moe_ffn_cost(256, 63, HIDDEN, WIDTH)
    # 792.7 MB of expert matrices, 2.1 MB of rows: 0.970 ms at 819 GB/s
    assert dec["bytes"] == 792_723_456 + 2_097_152
    assert kernel_cost.roofline_seconds(dec, V5E)["seconds"] == \
        pytest.approx(0.9705e-3, rel=1e-3)
    ref = moe_cost.grouped_moe_ffn_cost(65536, 64, HIDDEN, WIDTH)
    # 0.8246 TFLOP: 4.186 ms at 197 TFLOP/s against 1.639 ms of bytes
    assert ref["flops"] == pytest.approx(8.2463e11, rel=1e-4)
    assert kernel_cost.roofline_seconds(ref, V5E)["seconds"] == \
        pytest.approx(4.186e-3, rel=1e-3)


def test_expected_experts_hit():
    # 256 uniform rows reach all but about one of 64 experts
    assert moe_cost.expected_experts_hit(256, EXPERTS) == \
        pytest.approx(62.86, abs=0.01)
    assert moe_cost.expected_experts_hit(65536, EXPERTS) == \
        pytest.approx(64.0)


def test_roofline_share_of_a_measured_time():
    # 8 layers that took twice their least time read 50 %
    least = kernel_cost.roofline_seconds(
        moe_cost.grouped_moe_ffn_cost(256, 63, HIDDEN, WIDTH), V5E)
    got = moe_cost.roofline_share(
        2 * 8 * least["seconds"], 8, V5E, rows=256, experts_hit=63,
        hidden=HIDDEN, width=WIDTH)
    assert got["share"] == pytest.approx(50.0)
    assert got["bound"] == "memory"


NEMOTRON = dict(hidden=2688, width=1856)


def test_the_ungated_experts_cost_by_hand():
    """TWO matrices an expert hit at the published width 1856: 19.96 MB
    an expert, 1.28 GB a layer and step when all 64 are hit; two thirds of
    what the three-matrix count gives for the same shape."""
    c = moe_cost.grouped_moe_ffn_cost(rows=768, experts_hit=64, matrices=2,
                                      **NEMOTRON)
    assert c["flops"] == 4.0 * 768 * 2688 * 1856
    assert c["bytes"] == 2 * 64 * 2688 * 1856 * 2 + 2 * 768 * 2688 * 2
    assert c["bytes"] == pytest.approx(1.2853e9, rel=1e-3)
    assert kernel_cost.roofline_seconds(c, V5E)["bound"] == "memory"
    three = moe_cost.grouped_moe_ffn_cost(rows=768, experts_hit=64,
                                          **NEMOTRON)
    assert three["flops"] == 1.5 * c["flops"]
    assert three["bytes"] / c["bytes"] == pytest.approx(1.5, rel=5e-3)


@pytest.mark.parametrize("rows, hit", [
    (768, 64), (768.0, 63.2), (5 * 128 * 768.0, 5 * 128 * 64.0),
    (196608.0, 40448.0), (1.0, 1.0)])
def test_two_matrices_count_what_the_retired_ungated_cost_counted(rows, hit):
    """``ssm_cost.ungated_ffn_cost`` as it stood until PR 58, to the
    digit: the same floats in the same order, so the reading of
    ``grouped_moe_roofline`` on Nemotron's cell did not move."""
    was = {"flops": 4.0 * rows * 2688 * 1856,
           "bytes": float(2 * hit * 2688 * 1856 * 2 + 2 * rows * 2688 * 2)}
    assert moe_cost.grouped_moe_ffn_cost(rows, hit, matrices=2,
                                         **NEMOTRON) == was
    # and three matrices is the default every SwiGLU cell reads by
    assert moe_cost.grouped_moe_ffn_cost(rows, hit, **NEMOTRON) \
        == moe_cost.grouped_moe_ffn_cost(rows, hit, matrices=3, **NEMOTRON)


def test_the_three_matrix_count_would_pass_100_on_two_matrix_experts():
    """Why Nemotron's cell states ``matrices`` 2: a kernel at 92 % of the
    two-matrix bound reads 138 % against the three-matrix one, which the
    driver refuses as an impossible reading."""
    two = kernel_cost.roofline_seconds(moe_cost.grouped_moe_ffn_cost(
        rows=768, experts_hit=64, matrices=2, **NEMOTRON), V5E)["seconds"]
    three = kernel_cost.roofline_seconds(moe_cost.grouped_moe_ffn_cost(
        rows=768, experts_hit=64, **NEMOTRON), V5E)["seconds"]
    took = two / 0.92
    assert 100 * three / took == pytest.approx(138.0, abs=0.6)
