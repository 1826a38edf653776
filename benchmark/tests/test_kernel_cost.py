"""FLOP and byte functions against hand-worked shapes."""

import pytest

from benchmark import kernel_cost


def test_flash_forward_of_the_1p3b_step():
    # [2, 2048, 16, 128] causal: QK^T and PV are each
    # 2*B*H*T*T*D = 2*2*16*2048*2048*128 = 34,359,738,368 FLOPs, halved
    c = kernel_cost.flash_attention_cost(2, 16, 2048, 128)
    assert c["flops"] == 2 * 34_359_738_368 / 2
    # q, k, v, o: 4 tensors of 2*16*2048*128 bf16 elements
    assert c["bytes"] == 4 * 8_388_608 * 2


def test_flash_backward_is_five_matmuls_and_eight_tensors():
    f = kernel_cost.flash_attention_cost(2, 16, 2048, 128)
    b = kernel_cost.flash_attention_cost(2, 16, 2048, 128, backward=True)
    assert b["flops"] == pytest.approx(2.5 * f["flops"])
    assert b["bytes"] == 2 * f["bytes"]


def test_paged_decode_reads_every_live_row_once():
    # 256 sequences at 672 tokens, 12 q / 2 kv heads of 128, bf16:
    # K and V rows: 2 * 172,032 * 2 * 128 * 2 B = 176,160,768 B
    c = kernel_cost.paged_decode_attention_cost(256 * 672, 12, 2, 128)
    assert c["bytes"] == 176_160_768
    # per query head a dot and a weighted sum over the context
    assert c["flops"] == 4 * 256 * 672 * 12 * 128


def test_roofline_says_which_limit_binds():
    peak = kernel_cost.peaks("TPU v5 lite")
    decode = kernel_cost.paged_decode_attention_cost(256 * 672, 12, 2, 128)
    r = kernel_cost.roofline_seconds(decode, peak)
    assert r["bound"] == "memory"
    assert r["seconds"] == pytest.approx(176_160_768 / 819e9)
    flash = kernel_cost.flash_attention_cost(2, 16, 2048, 128)
    r = kernel_cost.roofline_seconds(flash, peak)
    assert r["bound"] == "compute"
    assert r["seconds"] == pytest.approx(34_359_738_368 / 197e12)


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(KeyError):
        kernel_cost.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        kernel_cost.peaks("_source")
