"""The latent-attention cost functions, the roofline reader that stands in
for them until ``readers.r_roofline`` resolves ``mla_cost``, and the
``rollout-long`` mix the cell's arithmetic quotes."""

import json
import os

import pytest

from benchmark import kernel_cost, mla_cost, readers, traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAK = kernel_cost.peaks("TPU v5 lite")


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def test_decode_cost_reads_a_row_once_and_sits_on_the_ridge():
    c = mla_cost.mla_decode_attention_cost(1000.0, 128, 512, 64)
    assert c["bytes"] == 1000 * 576 * 2            # key and value at once
    assert c["flops"] == 2 * 1000 * 128 * (576 + 512)
    t_bytes = c["bytes"] / PEAK["hbm_bytes_per_s"] / 1000
    t_flops = c["flops"] / PEAK["bf16_flops_per_s"] / 1000
    assert t_bytes == pytest.approx(1.407e-9, rel=1e-3)
    assert t_flops == pytest.approx(1.414e-9, rel=1e-3)
    # the stand-in (kv_heads 1, head_dim 288) counts the same bytes and
    # fewer FLOPs: its roofline is 0.5 % low and never high
    s = kernel_cost.paged_decode_attention_cost(1000.0, 128, 1, 288)
    assert s["bytes"] == c["bytes"] and s["flops"] < c["flops"]
    low = kernel_cost.roofline_seconds(s, PEAK)["seconds"]
    true = kernel_cost.roofline_seconds(c, PEAK)["seconds"]
    assert 0.99 < low / true <= 1.0
    # the config's own kv_heads x head_dim would count the row twice
    twice = kernel_cost.paged_decode_attention_cost(1000.0, 128, 1, 576)
    assert twice["bytes"] == 2 * c["bytes"]


@pytest.mark.parametrize("ctx, absorbed, expanded", [
    (0, 36.6e9, 27.9e9), (1536, 255.6e9, 143.9e9), (5632, 839.7e9, 453.1e9)])
def test_prefill_cost_absorbed_and_expanded(ctx, absorbed, expanded):
    """A 512-token chunk of one sequence (PERF.md, PR 34, quotes these)."""
    a = mla_cost.mla_prefill_attention_cost(512, ctx, 128, 512, 64)
    e = mla_cost.mla_prefill_attention_cost(512, ctx, 128, 512, 64,
                                            nope=128, v_dim=128)
    assert a["flops"] == pytest.approx(absorbed, rel=2e-3)
    assert e["flops"] == pytest.approx(expanded, rel=2e-3)
    assert a["bytes"] == e["bytes"] == (ctx + 512) * 1152


def test_mla_roofline_reader_counts_one_layers_rows_a_call():
    # one traced round of 128 steps over 5 layers: 640 calls
    ctx_tokens = 128 * 415_000
    # the reader stands on the absorbed form's true count (PR 37): at 128
    # heads its FLOPs bound a row by a hair, 0.5 % over reading it once
    row = kernel_cost.roofline_seconds(
        mla_cost.mla_decode_attention_cost(1.0, 128, 512, 64), PEAK)
    assert row["bound"] == "compute"
    assert row["seconds"] == pytest.approx(
        1.005 * 1152 / PEAK["hbm_bytes_per_s"], rel=1e-3)
    least = 5 * ctx_tokens * row["seconds"]
    name = "mla_decode_attention-bf16_128_128_512"
    obs = {"peak": PEAK,
           "cell": _load("cells", "serve-pangu-rollout-long.json"),
           "attention": {"q_heads": 128, "kv_heads": 1,
                                       "head_dim": 576, "kv_row": 576,
                                       "layers": 5},
           "traced": {"decode_context_tokens": ctx_tokens},
           "trace": {"n_devices": 1, "busy_s": 8 * least,
                     "ops": {name: 2 * least, "fusion-bf16_4_512": 1.0},
                     "op_counts": {name: 640, "fusion-bf16_4_512": 9}}}
    spec = _load("layer_metrics", "mla_attn_roofline.rollout.json")
    assert readers.read(spec, obs) == pytest.approx(50.0)
    assert readers.read(_load("layer_metrics", "mla_attn_share.rollout.json"),
                        obs) == pytest.approx(25.0)
    # a program without the kernel: nothing to read, nothing reported
    obs["trace"]["ops"].pop(name)
    obs["trace"]["op_counts"].pop(name)
    assert readers.read(spec, obs) is None
    assert readers.read(_load("layer_metrics", "mla_attn_share.rollout.json"),
                        obs) is None


def test_rollout_long_mix_and_first_wave_cover_every_phase():
    mix = _load("traffic", "rollout-long.json")
    stats = traffic.mix_stats(mix)
    for key, val in stats.items():
        assert mix["mix_stats"][key] == pytest.approx(val)
    assert mix["mix_stats"]["longest"] == max(mix["prompt_lens"]) \
        + max(mix["gen_lens"])
    cell = _load("cells", "serve-pangu-rollout-long.json")
    quantum = cell["engine"]["decode_loop_steps"]
    assert all(g % quantum == 0 for g in mix["gen_lens"])
    assert quantum <= cell["engine"]["block_size"]
    wave = traffic.first_wave(mix, cell["clients"], quantum, 1, 19200)
    # every phase of every (prompt, output) class has a client
    left = {}
    for r in wave:
        left.setdefault(len(r.prompt) + r.gen_len, set()).add(r.gen_len)
    assert {total: len(v) for total, v in left.items()} \
        == {3072: 16, 4096: 16, 5120: 32, 6144: 32}
    # and the pool holds the steady state with room for the refills
    eng = cell["engine"]
    assert mix["mix_stats"]["longest"] <= eng["max_blocks_per_seq"] \
        * eng["block_size"]
    live = cell["clients"] * stats["mean_live_context"]
    assert live < 0.9 * eng["num_blocks"] * eng["block_size"]
    assert cell["pool"]["reserved_bytes"] == eng["num_blocks"] \
        * eng["block_size"] * cell["pool"]["bytes_per_token"]


def test_the_cells_check_reads_rows_a_flush_wrote_in_a_block_taken_late():
    """``check_streams`` compares the FIRST ``correct.tokens`` served
    tokens of the shortest prompts. A prompt is prefilled up to its last
    token, so a stream's first ``decode_loop_steps`` tokens come from the
    first fused loop and read prefilled rows and the ring only. Only the
    tokens after them read rows ``_flush_latent`` wrote; a block-aligned
    prompt puts those rows in a block ``ensure_blocks`` took while
    decoding."""
    cell = _load("cells", "serve-pangu-rollout-long.json")
    mix = _load("traffic", "rollout-long.json")
    eng, n = cell["engine"], cell["correct"]["tokens"]
    loop, block = eng["decode_loop_steps"], eng["block_size"]
    assert n > 2 * loop                    # two flushes crossed
    assert min(mix["prompt_lens"]) % block == 0
    # every request's stream is that long
    assert n <= min(mix["gen_lens"])
