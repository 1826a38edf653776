"""``flash_window_cost``: the pairs a windowed causal mask lets through,
against a brute-force mask, and ``window >= seq`` against the full causal
call's cost (``kernel_cost.flash_attention_cost``)."""

import numpy as np
import pytest

from benchmark import kernel_cost
from benchmark.flash_window_cost import (visible_pairs,
                                         windowed_flash_attention_cost)


@pytest.mark.parametrize("seq,window", [(64, 16), (64, 1), (64, 63), (64, 64),
                                        (64, 200), (100, 7), (8192, 2048)])
def test_visible_pairs_is_the_masks_count(seq, window):
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    assert visible_pairs(seq, window) == int(
        ((j <= i) & (i - j < window)).sum())
    assert visible_pairs(seq, window) == sum(
        min(n + 1, window) for n in range(seq))


@pytest.mark.parametrize("backward", [False, True])
def test_no_window_is_the_full_causal_calls_cost(backward):
    full = kernel_cost.flash_attention_cost(2, 32, 8192, 128,
                                            backward=backward)
    for window in (None, 8192, 10 ** 6):
        mine = windowed_flash_attention_cost(2, 32, 8192, 128, window,
                                             backward=backward)
        assert mine["bytes"] == full["bytes"]
        # kernel_cost halves the square; the triangle holds its diagonal
        assert mine["flops"] == pytest.approx(full["flops"], rel=2e-4)
        assert mine["flops"] >= full["flops"]
    cut = windowed_flash_attention_cost(2, 32, 8192, 128, 2048,
                                        backward=backward)
    assert cut["flops"] / mine["flops"] == pytest.approx(
        visible_pairs(8192, 2048) / visible_pairs(8192, None))
    assert 0.43 < cut["flops"] / mine["flops"] < 0.44
