"""``linear_attn_cost`` against hand-worked numbers at the shapes of
``serve-solar2-rollout`` (64 heads, key and value width 128, float32
state)."""

import pytest

from benchmark import kernel_cost, linear_attn_cost as lac

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
HEADS, DK, DV = 64, 128, 128
STATE = HEADS * DK * DV                    # elements a sequence: 1,048,576


@pytest.mark.parametrize("seqs", [1, 16, 128])
def test_decode_reads_and_writes_each_state_once(seqs):
    cost = lac.kda_decode_cost(seqs, HEADS, DK, DV)
    vectors = seqs * HEADS * (3 * DK + 2 * DV + 1)
    assert cost == {"flops": 7.0 * seqs * STATE,
                    "bytes": 2.0 * seqs * STATE * 4 + vectors * 4}
    assert kernel_cost.roofline_seconds(cost, V5E)["bound"] == "memory"


def test_decode_at_the_cells_batch_is_1_3_ms_a_layer():
    # 128 sequences: 1.074 GB of state read and written + 21 MB of vectors
    cost = lac.kda_decode_cost(128, HEADS, DK, DV)
    assert cost["bytes"] == 2 * 536_870_912 + 21_004_288
    assert kernel_cost.roofline_seconds(cost, V5E)["seconds"] == \
        pytest.approx(1.3367e-3, rel=1e-3)
    # a bfloat16 state would halve the state's part
    half = lac.kda_decode_cost(128, HEADS, DK, DV, state_itemsize=2)
    assert half["bytes"] == 536_870_912 + 21_004_288


@pytest.mark.parametrize("tokens, seqs, bound", [
    (2048, 4, "memory"),       # a [4, 512] refill step
    (64, 1, "memory"),         # one chunk of one sequence
], ids=["refill-2048", "one-chunk"])
def test_prefill_counts_the_chunks_matmuls(tokens, seqs, bound):
    cost = lac.kda_prefill_cost(tokens, HEADS, DK, DV, chunk=64,
                                sequences=seqs)
    per_chunk = (4 * 64 * 64 * DK + 64 * 64 * (DK + DV)
                 + 6 * 64 * DK * DV + 2 * 64 * 64 * DV)
    assert cost["flops"] == per_chunk * HEADS * tokens / 64
    assert cost["bytes"] == tokens * HEADS * (3 * DK + 2 * DV + 1) * 4 \
        + 2 * seqs * STATE * 4
    assert kernel_cost.roofline_seconds(cost, V5E)["bound"] == bound


def test_prefill_flops_grow_with_the_chunk_and_bytes_do_not():
    a = lac.kda_prefill_cost(2048, HEADS, DK, DV, chunk=64)
    b = lac.kda_prefill_cost(2048, HEADS, DK, DV, chunk=128)
    assert b["flops"] > a["flops"] and b["bytes"] == a["bytes"]


def test_roofline_share_of_a_measured_time():
    # 3 layers that took twice their least time read 50 %
    cost = lac.kda_decode_cost(128, HEADS, DK, DV)
    least = kernel_cost.roofline_seconds(cost, V5E)
    got = lac.roofline_share(2 * 3 * least["seconds"], 3, V5E, cost)
    assert got["share"] == pytest.approx(50.0)
    assert got["bound"] == "memory"
