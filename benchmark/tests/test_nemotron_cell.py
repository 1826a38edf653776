"""The cell ``serve-nemotron3-nano-rollout-long``, its ``kernels`` block
(PR 58: the five ``.nemotron`` reader files became entries of the cell's
own file) and the families' readers that list it (``.rollout`` /
``.serve``): the job exports every key they name (a ``--rehearse`` walk of
the cell on the CPU, toy sizes), each counter reader on hand-made
observations, the state update's cost by hand (the ungated experts' is
``test_moe_cost.py``'s, ``matrices=2``), and the roofline readers
against a hand-made trace that carries the kernel names the v5e compile
gives at the published widths (``tests/unit/test_nemotron_h.py`` has the
model; a time comes only from a chip run). Nothing here looks at where in
``BENCHMARK.json``'s lists the entries stand."""

import pytest

from benchmark import kernel_cost, moe_cost, readers, run, ssm_cost
from benchmark.common import load_json, load_manifest

CELL = "serve-nemotron3-nano-rollout-long"
CONFIG = "nemotron-3-nano-30b-a3b"
MANIFEST = load_manifest()
PEAK = kernel_cost.peaks("TPU v5 lite")
NAMES = [m["name"] for m in run._metrics_of(MANIFEST, "per_layer", CELL)]
KERNEL_READERS = sorted(
    n for n in NAMES
    if any(k.startswith("cell.kernels.")
           for k in readers.keys_of(load_json("layer_metrics",
                                              n + ".json"))))


def _spec(name):
    return load_json("layer_metrics", name + ".json")


def test_the_manifest_gives_the_cell_its_metrics():
    # no reader file of its own: five families' readers take the
    # kernels' names and sizes from the cell's file
    assert KERNEL_READERS == [
        "grouped_ffn_share.rollout", "grouped_moe_roofline.rollout",
        "paged_attn_roofline.rollout", "ssm_roofline.rollout",
        "state_update_share.rollout"]
    assert all(n.rpartition(".")[2] in ("rollout", "serve") or "." not in n
               for n in NAMES)
    kernels = load_json("cells", CELL + ".json")["kernels"]
    assert sorted(kernels) == ["grouped_ffn", "paged_attn", "state_update"]
    # two matrices an expert at the PUBLISHED width; 2 of 13 layers keep K/V
    assert (kernels["grouped_ffn"]["matrices"],
            kernels["grouped_ffn"]["width"]) == (2, 1856)
    assert kernels["paged_attn"]["layers"] == 2
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "rollout-long", 1)
    e2e = [m["name"] for m in run._metrics_of(MANIFEST, "end_to_end", CELL)]
    assert sorted(e2e) == ["serve_tok_s", "setup_s"]
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert sorted(cfg["reduced"]) == sorted(
        load_json("configs", CONFIG + ".json")["reduced"])
    # the engine is serve-kimi-linear-rollout-long's but for the twice as
    # many slots the constant state leaves room for, at the same blocks a
    # sequence; the traffic file is the same
    mine, kimi = (load_json("cells", c + ".json")
                  for c in (CELL, "serve-kimi-linear-rollout-long"))
    differ = {k for k in kimi["engine"]
              if mine["engine"][k] != kimi["engine"][k]}
    assert differ == {"max_seqs", "num_blocks"}
    for key in ("clients", "planned_requests"):
        assert mine[key] == 2 * kimi[key], key
    assert mine["engine"]["max_seqs"] == mine["clients"] == 256
    assert mine["engine"]["num_blocks"] == 15 * 256
    for key in ("kind", "admit_max", "trace_rounds"):
        assert mine[key] == kimi[key], key
    assert (mine["correct"]["sequences"], mine["correct"]["tokens"]) \
        == (4, 320)
    pool = mine["pool"]
    assert pool["state_pool_bytes"] == 257 * pool["state_bytes_per_sequence"]
    assert pool["reserved_bytes"] \
        == pool["bytes_per_token"] * 3840 * 256 == 2013265920


def test_a_rehearsal_fills_every_key_the_nemotron_readers_name(capsys):
    line, obs = run.run_cell(["--workload", CELL, "--seed", "2147483659",
                              "--rehearse", "--trace", "1"])
    capsys.readouterr()
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}
    assert line["checks"]["no_compile_in_window"]
    assert line["checks"]["every_slot_live"]
    missing = []
    for name in NAMES:
        missing += [(name, key) for key in readers.keys_of(_spec(name))
                    if key.split(".")[0] not in ("trace", "peak")
                    and readers.lookup(obs, key) is None]
    assert not missing
    # the state pool's counters and the paged planes' fill in the one run,
    # under the window and under the traced stretch; the latent rows' stay 0
    for stretch in (obs, obs["traced"]):
        p = stretch["pipeline"]
        for key in ("decode_kv_rows_live", "decode_kv_rows_fetched",
                    "kv_bytes_live", "state_slots_live", "state_bytes_live",
                    "moe_rows_routed"):
            assert p[key] > 0, key
        assert p["latent_rows_live"] == p["latent_bytes_live"] == 0
        # two softmax layers, K and V, 2 kv heads of 16, float32
        assert p["kv_bytes_live"] \
            == p["decode_kv_rows_live"] * 2 * 2 * 2 * 16 * 4
    assert obs["attention"]["q_heads"] == 4       # the toy's


PIPELINE = {
    "prefill_tokens_real": 900, "prefill_tokens_planned": 2048,
    "fused_dispatch_s": 0.030, "fused_apply_s": 0.012,
    "put_s": 8.0, "decode_batch_s": 32.0,
    "decode_kv_rows_live": 800, "decode_kv_rows_fetched": 1000,
    "kv_bytes_live": 1_000_000, "state_bytes_live": 3_000_000,
    "latent_bytes_live": 0,
    "moe_rows_routed": 1000, "moe_rows_hottest": 1300,
    "moe_experts_hit": 5000, "moe_expert_reads": 5010,
    "moe_prefill_tokens": 4000, "moe_prefill_kernel_tokens": 4000}
OBS = {"pipeline": PIPELINE, "rounds": 12, "window_s": 40.0,
       "memory_peak_bytes": 13.6e9,
       "trace": {"window_s": 4.0, "idle_s": 0.1, "busy_s": 3.9}}


@pytest.mark.parametrize("name, want", [
    ("state_cache_share.rollout", 75.0),
    ("decode_live_rows_share.rollout", 80.0),
    ("expert_imbalance.rollout", 1.3),
    ("moe_reads_per_hit.rollout", 1.002),
    ("prefill_useful_share.rollout", 100 * 900 / 2048),
    ("fused_host_ms_per_round.rollout", 3.5),
    ("refill_call_share.rollout", 20.0),
    ("device_idle_share.rollout", 2.5),
    ("peak_hbm_gb.rollout", 13.6),
    ("moe_prefill_kernel_share.rollout", 100.0)])
def test_counter_readers(name, want):
    assert name in NAMES            # the family's list holds this cell
    assert readers.read(_spec(name), OBS) == pytest.approx(want)
    assert readers.read(_spec(name), {}) is None


def test_the_state_updates_cost_by_hand():
    """256 sequences x 64 heads of [64, 128] float32: 2.10 MB a sequence
    and layer each way, 0.54 GB read and as much written a call, and the
    vectors a thirtieth of a per cent beside them."""
    c = ssm_cost.mamba2_decode_cost(256, 64, 64, 128)
    state = 256 * 64 * 64 * 128
    assert c["flops"] == 7.0 * state
    assert c["bytes"] == 2 * state * 4 + 256 * 64 * (2 * 64 + 2 * 128 + 1) * 4
    assert c["bytes"] / 256 == pytest.approx(2 * 2.097e6 + 98.6e3, rel=1e-3)
    least = kernel_cost.roofline_seconds(c, PEAK)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(1.342e-3, rel=1e-2)
    # linear in the sequences; a bfloat16 state would halve it
    assert ssm_cost.mamba2_decode_cost(128, 64, 64, 128)["bytes"] * 2 \
        == c["bytes"]
    half = ssm_cost.mamba2_decode_cost(256, 64, 64, 128, state_itemsize=2)
    assert half["bytes"] == c["bytes"] - state * 4


KERNELS = {
    "ssm": "mamba2_decode_state_update-f32_257_64_64_128",
    "paged_attn": "closed_call-bf16_256_32_256",
    "grouped_moe": "grouped_ffn_decode-bf16_2496_2688"}


@pytest.mark.parametrize("metric", sorted(KERNELS))
def test_roofline_readers_match_the_compiled_names_and_stay_under_100(metric):
    """A trace whose kernels took exactly twice their least time reads 50 %
    through each reader, by the counts the traced stretch itself reports:
    6 state-space, 2 softmax and 5 sparse layers x 128 steps."""
    layers = {"ssm": 6, "paged_attn": 2, "grouped_moe": 5}[metric]
    calls = layers * 128
    ctx = 256 * 128 * 3243.0                   # a round's context tokens
    hit, rows = 5 * 128 * 64.0, 5 * 128 * 768.0
    cost = {
        "ssm": calls * kernel_cost.roofline_seconds(
            ssm_cost.mamba2_decode_cost(256, 64, 64, 128), PEAK)["seconds"],
        "paged_attn": layers * kernel_cost.roofline_seconds(
            kernel_cost.paged_decode_attention_cost(ctx, 32, 2, 128),
            PEAK)["seconds"],
        "grouped_moe": kernel_cost.roofline_seconds(
            moe_cost.grouped_moe_ffn_cost(rows=rows, experts_hit=hit,
                                          hidden=2688, width=1856,
                                          matrices=2),
            PEAK)["seconds"]}[metric]
    name = KERNELS[metric]
    obs = {"peak": PEAK, "cell": load_json("cells", CELL + ".json"),
           "attention": {"q_heads": 32, "kv_heads": 2, "head_dim": 128,
                         "kv_row": 256, "layers": 13},
           "traced": {"decode_context_tokens": ctx,
                      "pipeline": {"moe_rows_routed": rows,
                                   "moe_experts_hit": hit}},
           "trace": {"n_devices": 1, "busy_s": 10 * cost,
                     "ops": {name: 2 * cost, "fusion.1": 8 * cost},
                     "op_counts": {name: calls, "fusion.1": 5}}}
    roofline = {"ssm": "ssm_roofline", "paged_attn": "paged_attn_roofline",
                "grouped_moe": "grouped_moe_roofline"}[metric]
    got = readers.read(_spec(roofline + ".rollout"), obs)
    assert got == pytest.approx(50.0, rel=1e-6)
    share = {"ssm": "state_update_share", "grouped_moe": "grouped_ffn_share"}
    if metric in share:
        assert readers.read(_spec(share[metric] + ".rollout"), obs) \
            == pytest.approx(20.0)
    # another model's kernel names are not matched: Kimi's state update,
    # its grouped kernel's shape
    other = dict(obs, trace=dict(obs["trace"], ops={
        "kda_decode_state_update-f32_129_32_128_128": 1.0,
        "grouped_ffn_decode-bf16_1984_2304": 1.0},
        op_counts={"kda_decode_state_update-f32_129_32_128_128": 1,
                   "grouped_ffn_decode-bf16_1984_2304": 1}))
    assert readers.read(_spec(roofline + ".rollout"), other) is None
