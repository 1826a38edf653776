"""``benchmark/selective_scan_cost.py`` on round numbers: what the Mamba-1
selective scan must compute and move, whatever layout holds it."""

from benchmark import kernel_cost, selective_scan_cost as cost

PEAK = kernel_cost.peaks("TPU v5 lite")


def test_a_decode_token_reads_and_writes_each_state_once():
    c = cost.mamba1_decode_cost(sequences=10, channels=100, state=10)
    assert c["flops"] == 9 * 10 * 100 * 10
    # the state twice at 4 B; x, dt, y a channel and B, C a state once
    assert c["bytes"] == 2 * 10000 * 4 + 10 * (300 + 20) * 4
    half = cost.mamba1_decode_cost(sequences=10, channels=100, state=10,
                                   state_itemsize=2)
    assert half["bytes"] == c["bytes"] - 2 * 10000 * 2


def test_the_cells_decode_call_is_memory_bound():
    c = cost.mamba1_decode_cost(sequences=256, channels=5120, state=16)
    assert c["bytes"] == 2 * 256 * 81920 * 4 + 256 * 15392 * 4 == 183533568
    least = kernel_cost.roofline_seconds(c, PEAK)
    assert least["bound"] == "memory" and 2.2e-4 < least["seconds"] < 2.3e-4


def test_a_prefilled_position_moves_its_vectors_and_a_row_its_state():
    p = cost.mamba1_prefill_cost(tokens=1000, sequences=4, channels=100,
                                 state=10)
    assert p["flops"] == 9 * 1000 * 100 * 10
    assert p["bytes"] == 1000 * 320 * 4 + 2 * 4 * 1000 * 4
    # no sequences stated: the vectors alone
    assert cost.mamba1_prefill_cost(tokens=1000, sequences=0, channels=100,
                                    state=10)["bytes"] == 1000 * 320 * 4
    # a [4, 512] step of the cell: 126 MB of float32 vectors a layer
    step = cost.mamba1_prefill_cost(tokens=2048, sequences=4, channels=5120,
                                    state=16)
    assert step["bytes"] == 2048 * 15392 * 4 + 8 * 81920 * 4
    assert kernel_cost.roofline_seconds(step, PEAK)["bound"] == "memory"
