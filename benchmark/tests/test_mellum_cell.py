"""The cell ``serve-mellum2-rollout-long``, its ``.mellum2`` readers (the
window's, which one configuration has), its ``kernels`` block (PR 58: the
grouped kernel's two ``.mellum2`` files became an entry of the cell's own
file) and the families' readers that list it (``.rollout`` / ``.serve``):
the job exports every key they name (a ``--rehearse`` walk of the cell on
the CPU, toy sizes), each counter reader on hand-made observations,
``window_attn_cost.mixed_decode_attention_cost`` by hand (with the case
that a whole-chain read scores under 100 %), and the roofline readers
against a hand-made trace that carries the kernel names the v5e compile
gives at the published widths (``tests/unit/test_mellum.py`` has the model;
a time comes only from a chip run). Nothing here looks at where in
``BENCHMARK.json``'s lists the entries stand."""

import pytest

from benchmark import kernel_cost, moe_cost, readers, run, window_attn_cost
from benchmark.common import load_json, load_manifest

CELL = "serve-mellum2-rollout-long"
CONFIG = "mellum2-12b-a2.5b"
MANIFEST = load_manifest()
PEAK = kernel_cost.peaks("TPU v5 lite")
NAMES = [m["name"] for m in run._metrics_of(MANIFEST, "per_layer", CELL)]
OWN = sorted(n for n in NAMES if n.endswith(".mellum2"))


def _spec(name):
    return load_json("layer_metrics", name + ".json")


def test_the_manifest_gives_the_cell_its_metrics():
    assert OWN == [
        "paged_attn_roofline.mellum2", "paged_attn_share.mellum2",
        "window_cache_share.mellum2", "window_live_rows_share.mellum2",
        "window_scored_share.mellum2"]
    assert all(n.rpartition(".")[2] in ("rollout", "serve") or "." not in n
               for n in NAMES if n not in OWN)
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "rollout-long", 1)
    assert len(cell["why"]) <= 200
    e2e = [m["name"] for m in run._metrics_of(MANIFEST, "end_to_end", CELL)]
    assert sorted(e2e) == ["serve_tok_s", "setup_s"]
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert sorted(cfg["reduced"]) == sorted(
        load_json("configs", CONFIG + ".json")["reduced"])
    assert len(cfg["why"]) <= 200
    # the engine is serve-nemotron3-nano-rollout-long's to the key, and so
    # are the clients, the plan and the traffic file
    mine, nemo = (load_json("cells", c + ".json")
                  for c in (CELL, "serve-nemotron3-nano-rollout-long"))
    assert mine["engine"] == nemo["engine"]
    for key in ("kind", "clients", "planned_requests", "admit_max",
                "trace_rounds"):
        assert mine[key] == nemo[key], key
    assert mine["rehearse"]["engine"] == nemo["rehearse"]["engine"]
    # the check reads past four flushes and past the window pool's first
    # wrap: a 1,024-token prompt passes row 1,536 at its 512th token
    assert (mine["correct"]["sequences"], mine["correct"]["tokens"]) \
        == (4, 640)
    pool, eng = mine["pool"], mine["engine"]
    assert pool["reserved_bytes"] \
        == pool["bytes_per_token"] * 3840 * 256 == 4026531840
    R = pool["window_blocks_per_slot"]
    assert R == -(-(1024 - 1 + eng["chunk_size"]) // eng["block_size"]) == 6
    assert 1024 + 640 > R * eng["block_size"]
    assert pool["window_bytes_per_slot"] \
        == 6 * 2 * R * eng["block_size"] * 512 * 2
    assert pool["window_pool_bytes"] \
        == (eng["max_seqs"] + 1) * pool["window_bytes_per_slot"]


def test_a_rehearsal_fills_every_key_the_mellum2_readers_name(capsys):
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
    # both pools' counters in the one run, under the window and under the
    # traced stretch; the state's and the latent rows' stay 0
    for stretch in (obs, obs["traced"]):
        p = stretch["pipeline"]
        for key in ("decode_kv_rows_live", "decode_kv_rows_fetched",
                    "kv_bytes_live", "window_rows_live",
                    "window_rows_fetched", "window_bytes_live",
                    "moe_rows_routed"):
            assert p[key] > 0, key
        assert p["latent_rows_live"] == p["state_bytes_live"] == 0
        # two full layers, K and V, 2 kv heads of 16, float32; six window
        # layers (the toy keeps the published depth and window)
        assert p["kv_bytes_live"] \
            == p["decode_kv_rows_live"] * 2 * 2 * 2 * 16 * 4
        assert p["window_bytes_live"] \
            == p["window_rows_live"] * 6 * 2 * 2 * 16 * 4
        assert p["window_rows_live"] <= p["window_rows_fetched"]
        # every live context is past the window: a layer's must-read rows
        # are the window's less the loop's own, 1023 - t at step t
        steps = stretch["decode_steps"] * 8
        assert p["window_rows_live"] == steps * (1023 - 63.5)
    assert obs["attention"]["q_heads"] == 4       # the toy's


PIPELINE = {
    "prefill_tokens_real": 900, "prefill_tokens_planned": 2048,
    "fused_dispatch_s": 0.030, "fused_apply_s": 0.012,
    "put_s": 8.0, "decode_batch_s": 32.0,
    "decode_kv_rows_live": 800, "decode_kv_rows_fetched": 1000,
    "kv_bytes_live": 3_000_000, "window_rows_live": 900,
    "window_rows_fetched": 1200, "window_rows_scored": 1200,
    "window_bytes_live": 1_000_000,
    "moe_rows_routed": 1000, "moe_rows_hottest": 1300,
    "moe_experts_hit": 5000, "moe_expert_reads": 5010,
    "moe_prefill_tokens": 4000, "moe_prefill_kernel_tokens": 4000}
OBS = {"pipeline": PIPELINE, "rounds": 12, "window_s": 40.0,
       "memory_peak_bytes": 14.2e9,
       "trace": {"window_s": 4.0, "idle_s": 0.1, "busy_s": 3.9}}


@pytest.mark.parametrize("name, want", [
    ("window_cache_share.mellum2", 25.0),
    ("window_live_rows_share.mellum2", 75.0),
    ("window_scored_share.mellum2", 75.0),
    ("decode_live_rows_share.rollout", 80.0),
    ("expert_imbalance.rollout", 1.3),
    ("moe_reads_per_hit.rollout", 1.002),
    ("prefill_useful_share.rollout", 100 * 900 / 2048),
    ("fused_host_ms_per_round.rollout", 3.5),
    ("refill_call_share.rollout", 20.0),
    ("device_idle_share.rollout", 2.5),
    ("peak_hbm_gb.rollout", 14.2),
    ("moe_prefill_kernel_share.rollout", 100.0)])
def test_counter_readers(name, want):
    assert name in NAMES            # the family's list holds this cell
    assert readers.read(_spec(name), OBS) == pytest.approx(want)
    assert readers.read(_spec(name), {}) is None


def test_the_mixed_attention_cost_by_hand():
    """256 sequences at the mean live context 3,243: a full layer reads
    830.2k rows, a window layer 256 x 1,023; K and V rows of 4 heads x 128
    in bfloat16 are 2,048 B: 3.40 GB over the 2 full layers and 3.22 GB
    over the 6 window layers, ISSUE 48's 6.62 GB a step."""
    full, window = 256 * 3243.0, 256 * 1023.0
    c = window_attn_cost.mixed_decode_attention_cost(
        full, window, 2, 6, 32, 4, 128)
    rows = 2 * full + 6 * window
    assert c["bytes"] == 2 * rows * 4 * 128 * 2 == rows * 2048
    assert c["flops"] == 4 * rows * 32 * 128
    assert 2 * full * 2048 == pytest.approx(3.40e9, rel=2e-3)
    assert 6 * window * 2048 == pytest.approx(3.22e9, rel=2e-3)
    assert kernel_cost.roofline_seconds(c, PEAK)["bound"] == "memory"
    # one kind alone is kernel_cost's own count, a layer at a time
    one = kernel_cost.paged_decode_attention_cost(full, 32, 4, 128)
    assert window_attn_cost.mixed_decode_attention_cost(
        full, 0.0, 1, 6, 32, 4, 128) == one
    # linear in each kind's rows and layers
    assert window_attn_cost.mixed_decode_attention_cost(
        full, window, 4, 12, 32, 4, 128)["bytes"] == 2 * c["bytes"]


def test_a_whole_chain_read_scores_under_100():
    """A program that streamed a window layer's WHOLE chain at the bytes'
    bound would take the time of 8 full layers; against the rows a window
    layer MUST read it reads 49 %, never more than 100."""
    full, window = 256 * 3243.0, 256 * 1023.0
    must = kernel_cost.roofline_seconds(
        window_attn_cost.mixed_decode_attention_cost(
            full, window, 2, 6, 32, 4, 128), PEAK)["seconds"]
    took = kernel_cost.roofline_seconds(
        window_attn_cost.mixed_decode_attention_cost(
            full, full, 2, 6, 32, 4, 128), PEAK)["seconds"]
    assert 100 * must / took == pytest.approx(48.7, abs=0.2)


KERNELS = {"paged_attn": "closed_call-bf16_256_32_512",
           "grouped_moe": "grouped_ffn_decode-bf16_3040_2304"}


@pytest.mark.parametrize("metric", sorted(KERNELS))
def test_roofline_readers_match_the_compiled_names_and_stay_under_100(metric):
    """A trace whose kernels took exactly twice their least time reads 50 %
    through each reader, by the counts the traced stretch itself reports:
    8 attention calls (2 full, 6 window) and 8 sparse layers x 128 steps."""
    calls = 8 * 128
    full, window = 128 * 256 * 3243.0, 128 * 256 * 959.5
    hit, rows = 8 * 128 * 32.0, 8 * 128 * 1024.0
    cost = {
        "paged_attn": kernel_cost.roofline_seconds(
            window_attn_cost.mixed_decode_attention_cost(
                full, window, 2, 6, 32, 4, 128), PEAK)["seconds"],
        "grouped_moe": kernel_cost.roofline_seconds(
            moe_cost.grouped_moe_ffn_cost(rows=rows, experts_hit=hit,
                                          hidden=2304, width=896),
            PEAK)["seconds"]}[metric]
    name = KERNELS[metric]
    obs = {"peak": PEAK, "cell": load_json("cells", CELL + ".json"),
           "traced": {"pipeline": {"decode_kv_rows_live": full,
                                   "window_rows_live": window,
                                   "moe_rows_routed": rows,
                                   "moe_experts_hit": hit}},
           "trace": {"n_devices": 1, "busy_s": 10 * cost,
                     "ops": {name: 2 * cost, "fusion.1": 8 * cost},
                     "op_counts": {name: calls, "fusion.1": 5}}}
    roofline = {"paged_attn": "paged_attn_roofline",
                "grouped_moe": "grouped_moe_roofline"}[metric]
    share = {"paged_attn": "paged_attn_share",
             "grouped_moe": "grouped_ffn_share"}[metric]
    # the window's readers are this configuration's own; the grouped
    # kernel's are the family's, with the cell's name and sizes
    suffix = {"paged_attn": ".mellum2", "grouped_moe": ".rollout"}[metric]
    roofline, share = roofline + suffix, share + suffix
    assert readers.read(_spec(roofline), obs) \
        == pytest.approx(50.0, rel=1e-6)
    assert readers.read(_spec(share), obs) == pytest.approx(20.0)
    # another model's kernel names are not matched: Nemotron's decode
    # kernel over its 256-lane row, Kimi's grouped kernel's shape
    other = dict(obs, trace=dict(obs["trace"], ops={
        "closed_call-bf16_256_32_256": 1.0,
        "grouped_ffn_decode-bf16_1984_2304": 1.0},
        op_counts={"closed_call-bf16_256_32_256": 1,
                   "grouped_ffn_decode-bf16_1984_2304": 1}))
    assert readers.read(_spec(roofline), other) is None
    # and a parent that has no such counter gives the reader nothing to
    # read: the line then leaves the metric out
    bare = dict(obs, traced={"pipeline": {"decode_kv_rows_live": full,
                                          "moe_rows_routed": rows}})
    assert readers.read(_spec(roofline), bare) is None
