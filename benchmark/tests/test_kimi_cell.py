"""The cell ``serve-kimi-linear-rollout-long``, its ``kernels`` block (PR
58: the seven ``.kimi`` reader files became entries of the cell's own
file) and the families' readers that list it (``.rollout`` / ``.serve``):
the job exports every key they name (a ``--rehearse`` walk of the cell on
the CPU, toy sizes), each counter reader on hand-made observations, the two
cost functions at the configuration's 32 heads, and the roofline readers
against a hand-made trace that carries the kernel names the v5e compile
gives at the published widths (``tests/unit/test_kimi_linear.py`` has the
model; a time comes only from a chip run). Nothing here looks at where in
``BENCHMARK.json``'s lists the entries stand."""

import pytest

from benchmark import (kernel_cost, linear_attn_cost, mla_cost, moe_cost,
                       readers, run)
from benchmark.common import load_json, load_manifest

CELL = "serve-kimi-linear-rollout-long"
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


def test_the_manifest_gives_the_cell_its_metrics_and_nothing_else_moved():
    # no reader file of its own: the kernels' names and sizes stand in
    # the cell's file, and seven families' readers take them from there
    assert KERNEL_READERS == [
        "grouped_ffn_share.rollout", "grouped_moe_roofline.rollout",
        "linear_attn_prefill_roofline.rollout",
        "linear_attn_roofline.rollout", "mla_attn_roofline.rollout",
        "mla_attn_share.rollout", "state_update_share.rollout"]
    assert all(n.rpartition(".")[2] in ("rollout", "serve") or "." not in n
               for n in NAMES)
    assert sorted(load_json("cells", CELL + ".json")["kernels"]) == [
        "grouped_ffn", "linear_attn_prefill", "mla", "state_update"]
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("kimi-linear-48b-a3b", "rollout-long", 1)
    e2e = [m["name"] for m in run._metrics_of(MANIFEST, "end_to_end", CELL)]
    assert sorted(e2e) == ["serve_tok_s", "setup_s"]
    # the engine is serve-pangu-rollout-long's: the two cells differ by
    # the model alone
    mine, pangu = (load_json("cells", c + ".json")
                   for c in (CELL, "serve-pangu-rollout-long"))
    for key in ("kind", "engine", "clients", "planned_requests",
                "admit_max", "trace_rounds"):
        assert mine[key] == pangu[key], key
    assert (mine["correct"]["sequences"], mine["correct"]["tokens"]) \
        == (4, 320)


def test_a_rehearsal_fills_every_key_the_kimi_readers_name(capsys):
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
    # both families of counters fill in the one run, under the window and
    # under the traced stretch; the K/V rows' stay 0
    for stretch in (obs, obs["traced"]):
        p = stretch["pipeline"]
        for key in ("latent_rows_live", "latent_rows_fetched",
                    "latent_bytes_live", "state_slots_live",
                    "state_bytes_live", "moe_rows_routed"):
            assert p[key] > 0, key
        assert p["decode_kv_rows_live"] == p["decode_kv_rows_fetched"] == 0
    assert obs["attention"]["q_heads"] == 4       # the toy's


PIPELINE = {
    "prefill_tokens_real": 900, "prefill_tokens_planned": 2048,
    "fused_dispatch_s": 0.030, "fused_apply_s": 0.012,
    "put_s": 8.0, "decode_batch_s": 32.0,
    "latent_rows_live": 800, "latent_rows_fetched": 1000,
    "latent_bytes_live": 1_000_000, "state_bytes_live": 3_000_000,
    "kv_bytes_live": 0,
    "moe_rows_routed": 1000, "moe_rows_hottest": 1300,
    "moe_experts_hit": 5000, "moe_expert_reads": 5010,
    "moe_prefill_tokens": 4000, "moe_prefill_kernel_tokens": 4000,
    "linear_attn_prefill_tokens": 4000,
    "linear_attn_prefill_kernel_tokens": 3000}
OBS = {"pipeline": PIPELINE, "rounds": 12, "window_s": 40.0,
       "memory_peak_bytes": 11.3e9,
       "trace": {"window_s": 4.0, "idle_s": 0.1, "busy_s": 3.9}}


@pytest.mark.parametrize("name, want", [
    ("state_cache_share.rollout", 75.0),
    ("latent_live_rows_share.rollout", 80.0),
    ("expert_imbalance.rollout", 1.3), ("moe_reads_per_hit.rollout", 1.002),
    ("prefill_useful_share.rollout", 100 * 900 / 2048),
    ("fused_host_ms_per_round.rollout", 3.5),
    ("refill_call_share.rollout", 20.0), ("device_idle_share.rollout", 2.5),
    ("peak_hbm_gb.rollout", 11.3),
    ("moe_prefill_kernel_share.rollout", 100.0),
    ("linear_attn_prefill_kernel_share.rollout", 75.0)])
def test_counter_readers(name, want):
    assert name in NAMES            # the family's list holds this cell
    assert readers.read(_spec(name), OBS) == pytest.approx(want)
    assert readers.read(_spec(name), {}) is None


def test_cost_functions_at_32_heads():
    """The latent decode kernel at 32 heads is bound by bytes alone (at
    Pangu's 128 it sits on the ridge); the state update moves 2.1 MB a
    sequence and layer each way."""
    c = mla_cost.mla_decode_attention_cost(1000.0, 32, 512, 64)
    assert c["bytes"] == 1000 * 576 * 2
    assert c["flops"] == 2 * 1000 * 32 * (576 + 512)
    least = kernel_cost.roofline_seconds(c, PEAK)
    assert least["bound"] == "memory"
    assert least["seconds"] / 1000 == pytest.approx(1.407e-9, rel=1e-3)
    assert c["flops"] / PEAK["bf16_flops_per_s"] / 1000 \
        == pytest.approx(0.354e-9, rel=2e-3)
    k = linear_attn_cost.kda_decode_cost(128, 32, 128, 128)
    state = 128 * 32 * 128 * 128
    assert k["flops"] == 7.0 * state
    assert k["bytes"] == 2 * state * 4 + 128 * 32 * (5 * 128 + 1) * 4
    assert k["bytes"] / 128 == pytest.approx(2 * 2.097e6 + 82e3, rel=1e-2)
    assert kernel_cost.roofline_seconds(k, PEAK)["bound"] == "memory"
    # half of solar-open2-250b's 64 heads, to the byte
    k64 = linear_attn_cost.kda_decode_cost(128, 64, 128, 128)
    assert k64["bytes"] == 2 * k["bytes"]


KERNELS = {
    "linear_attn": "kda_decode_state_update-f32_129_32_128_128",
    "mla_attn": "mla_decode_attention-bf16_128_32_512",
    "grouped_moe": "grouped_ffn_decode-bf16_1984_2304"}


@pytest.mark.parametrize("metric", sorted(KERNELS))
def test_roofline_readers_match_the_compiled_names_and_stay_under_100(metric):
    """A trace whose kernels took exactly twice their least time reads 50 %
    through each reader, by the counts the traced stretch itself reports:
    6 recurrent, 2 latent and 7 sparse layers x 128 steps."""
    layers = {"linear_attn": 6, "mla_attn": 2, "grouped_moe": 7}[metric]
    calls = layers * 128
    ctx = 128 * 128 * 3243.0                   # a round's context tokens
    hit, rows = 7 * 128 * 62.0, 7 * 128 * 256.0
    cost = {
        "linear_attn": calls * kernel_cost.roofline_seconds(
            linear_attn_cost.kda_decode_cost(128, 32, 128, 128),
            PEAK)["seconds"],
        "mla_attn": layers * kernel_cost.roofline_seconds(
            mla_cost.mla_decode_attention_cost(ctx, 32, 512, 64),
            PEAK)["seconds"],
        "grouped_moe": kernel_cost.roofline_seconds(
            moe_cost.grouped_moe_ffn_cost(rows=rows, experts_hit=hit,
                                          hidden=2304, width=1024),
            PEAK)["seconds"]}[metric]
    name = KERNELS[metric]
    obs = {"peak": PEAK, "attention": {"q_heads": 32},
           "cell": load_json("cells", CELL + ".json"),
           "traced": {"decode_context_tokens": ctx,
                      "pipeline": {"moe_rows_routed": rows,
                                   "moe_experts_hit": hit}},
           "trace": {"n_devices": 1, "busy_s": 10 * cost,
                     "ops": {name: 2 * cost, "fusion.1": 8 * cost},
                     "op_counts": {name: calls, "fusion.1": 5}}}
    got = readers.read(_spec(f"{metric}_roofline.rollout"), obs)
    assert got == pytest.approx(50.0, rel=1e-6)
    share = {"linear_attn": "state_update_share", "mla_attn": "mla_attn_share",
             "grouped_moe": "grouped_ffn_share"}[metric]
    assert readers.read(_spec(share + ".rollout"), obs) \
        == pytest.approx(20.0)
    # another model's kernel names are not matched
    other = dict(obs, trace=dict(obs["trace"], ops={"x": 1.0},
                                 op_counts={"x": 1}))
    assert readers.read(_spec(f"{metric}_roofline.rollout"), other) is None
