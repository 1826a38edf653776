"""The cell ``serve-lfm2-rollout-long``, its four ``.lfm2`` readers (the
convolution's, which one configuration's cell reads), its ``kernels``
block and the families' readers that list it (``.rollout`` / ``.serve``):
the job exports every key they name (a ``--rehearse`` walk of the cell on
the CPU, toy sizes), each counter reader on hand-made observations,
``short_conv_cost.decode_step_cost`` by hand, and the roofline reader
against a hand-made trace that carries the kernel name the v5e compile
gives at the published widths (``tests/unit/test_tpu_compile.py`` holds
that name; ``tests/unit/test_lfm2.py`` has the model; a time comes only
from a chip run). Nothing here looks at where in ``BENCHMARK.json``'s
lists the entries stand."""

import pytest

from benchmark import kernel_cost, readers, run, short_conv_cost
from benchmark.common import load_json, load_manifest

CELL = "serve-lfm2-rollout-long"
CONFIG = "lfm2-24b-a2b"
MANIFEST = load_manifest()
PEAK = kernel_cost.peaks("TPU v5 lite")
NAMES = [m["name"] for m in run._metrics_of(MANIFEST, "per_layer", CELL)]
OWN = sorted(n for n in NAMES if n.endswith(".lfm2"))
#: the heirs ``test_family_readers.py`` pins to PR 58's lists: this cell
#: runs their kernels and cannot join them without an edit to that test
#: (PERF.md section 7 has the row for the next ``benchmark`` PR)
PINNED = ("grouped_moe_roofline.rollout", "grouped_ffn_share.rollout",
          "paged_attn_roofline.rollout")


def _spec(name):
    return load_json("layer_metrics", name + ".json")


def test_the_manifest_gives_the_cell_its_metrics():
    assert OWN == ["conv_in_place_share.lfm2", "conv_mixer_share.lfm2",
                   "short_conv_roofline.lfm2", "short_conv_share.lfm2"]
    assert all(n.rpartition(".")[2] in ("rollout", "serve") or "." not in n
               for n in NAMES if n not in OWN)
    assert len(NAMES) >= 25 and not set(PINNED) & set(NAMES)
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
    # the engine is serve-kimi-linear-rollout-long's to the key, and so
    # are the clients, the plan and the traffic file: the long cells
    # differ by the model alone
    mine, kimi = (load_json("cells", c + ".json")
                  for c in (CELL, "serve-kimi-linear-rollout-long"))
    assert mine["engine"] == kimi["engine"]
    for key in ("kind", "clients", "planned_requests", "admit_max",
                "trace_rounds"):
        assert mine[key] == kimi[key], key
    assert mine["rehearse"]["engine"] == kimi["rehearse"]["engine"]
    # the check reads past two flushes of the 128-step loop
    assert (mine["correct"]["sequences"], mine["correct"]["tokens"]) \
        == (4, 320)
    pool, eng = mine["pool"], mine["engine"]
    # 2 K/V layers x K and V x 8 kv heads x 64 lanes x 2 B
    assert pool["bytes_per_token"] == 2 * 2 * 8 * 64 * 2 == 4096
    assert pool["reserved_bytes"] == pool["bytes_per_token"] \
        * eng["num_blocks"] * eng["block_size"] == 2013265920
    # 7 conv layers x 2 carried inputs x 2,048 lanes x 2 B = 56 KB a slot
    k = mine["kernels"]["short_conv"]
    assert pool["state_bytes_per_sequence"] \
        == k["layers"] * (k["taps"] - 1) * k["width"] * 2 == 57344
    assert pool["state_pool_bytes"] \
        == (eng["max_seqs"] + 1) * pool["state_bytes_per_sequence"]
    assert set(mine["kernels"]) == {"short_conv"}


def test_a_rehearsal_fills_every_key_the_lfm2_readers_name(capsys):
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
    for stretch in (obs, obs["traced"]):
        p = stretch["pipeline"]
        steps = stretch["decode_steps"]
        # 8 toy clients live every step; 7 conv layers at the published
        # depth; none in place on a CPU
        assert p["state_slots_live"] == 8 * steps
        assert p["conv_steps"] == 7 * steps and p["conv_steps_in_place"] == 0
        # a slot holds 7 layers x 2 carried inputs x 64 lanes, float32
        assert p["state_bytes_live"] \
            == p["state_slots_live"] * 7 * 2 * 64 * 4
        # two attention layers keep K and V: 2 kv heads of 16, float32
        assert p["kv_bytes_live"] \
            == p["decode_kv_rows_live"] * 2 * 2 * 2 * 16 * 4 > 0
        assert p["latent_rows_live"] == p["window_rows_live"] == 0
        assert p["linear_attn_prefill_tokens"] == 0
        assert p["moe_rows_routed"] == 8 * steps * 8 * 2    # all held
        assert p["moe_rows_elsewhere"] == 0
    assert readers.read(_spec("conv_in_place_share.lfm2"), obs) == 0.0
    # the carried inputs beside the K/V rows: a sliver
    assert 0 < readers.read(_spec("state_cache_share.rollout"), obs) < 5
    assert obs["attention"]["q_heads"] == 4       # the toy's


def test_the_convolutions_cost_by_hand():
    """128 live rows through one layer at the published width: a row's two
    carried inputs of 2,048 bfloat16 lanes read and written (16 KB), its
    2,048 float32 inputs read and outputs written (16 KB): 32 KB a row,
    4 MB a call, 5 us at the chip's 819 GB/s; 12,288 FLOPs a row."""
    c = short_conv_cost.decode_step_cost(128, 2048, 3)
    assert c["bytes"] == 128 * (2 * 2 * 2048 * 2 + 2 * 2048 * 4) \
        == 128 * 32768
    assert c["flops"] == 128 * 2 * 3 * 2048
    r = kernel_cost.roofline_seconds(c, PEAK)
    assert r["bound"] == "memory"
    assert r["seconds"] == pytest.approx(5.12e-6, rel=0.01)
    # linear in the rows: one evaluation over a stretch's row-steps
    assert short_conv_cost.decode_step_cost(128 * 128, 2048, 3)["bytes"] \
        == 128 * c["bytes"]
    # KDA's shape (Kimi's cell): four taps, 12,288 lanes
    k = short_conv_cost.decode_step_cost(128, 12288, 4)
    assert k["bytes"] == 128 * (2 * 3 * 12288 * 2 + 2 * 12288 * 4)
    # a float32 pool (a rehearsal's) carries twice the bytes
    assert short_conv_cost.decode_step_cost(
        1, 2048, 3, pool_itemsize=4)["bytes"] == 2 * 2 * 2048 * 4 + 16384


PIPELINE = {
    "prefill_tokens_real": 900, "prefill_tokens_planned": 2048,
    "fused_dispatch_s": 0.030, "fused_apply_s": 0.012,
    "put_s": 8.0, "decode_batch_s": 32.0,
    "decode_kv_rows_live": 800, "decode_kv_rows_fetched": 1000,
    "kv_bytes_live": 3_980_000, "latent_bytes_live": 0,
    "state_bytes_live": 20_000, "state_slots_live": 16384,
    "conv_steps": 896, "conv_steps_in_place": 672,
    "moe_rows_routed": 1000, "moe_rows_hottest": 1300,
    "moe_experts_hit": 5000, "moe_expert_reads": 5010,
    "moe_prefill_tokens": 4000, "moe_prefill_kernel_tokens": 4000}
OBS = {"pipeline": PIPELINE, "rounds": 12, "window_s": 40.0,
       "memory_peak_bytes": 12.6e9,
       "trace": {"window_s": 4.0, "idle_s": 0.1, "busy_s": 3.9,
                 "regions": {"conv_mixer": 0.39, "ffn_dense": 0.078}}}


@pytest.mark.parametrize("name, want", [
    ("conv_in_place_share.lfm2", 75.0),
    ("conv_mixer_share.lfm2", 10.0),
    ("state_cache_share.rollout", 0.5),
    ("ffn_dense_share.rollout", 2.0),
    ("decode_live_rows_share.rollout", 80.0),
    ("expert_imbalance.rollout", 1.3),
    ("moe_reads_per_hit.rollout", 1.002),
    ("prefill_useful_share.rollout", 100 * 900 / 2048),
    ("fused_host_ms_per_round.rollout", 3.5),
    ("refill_call_share.rollout", 20.0),
    ("device_idle_share.rollout", 2.5),
    ("peak_hbm_gb.rollout", 12.6),
    ("moe_prefill_kernel_share.rollout", 100.0)])
def test_counter_readers(name, want):
    assert name in NAMES            # the family's list holds this cell
    assert readers.read(_spec(name), OBS) == pytest.approx(want)
    assert readers.read(_spec(name), {}) is None


def test_a_parent_without_the_counter_or_the_region_gives_nothing_to_read():
    """The parent of PR 59 counts ``conv_steps_in_place`` and not
    ``conv_steps``, and its programs have no ``conv_mixer`` region: the
    two readers return nothing and do not raise."""
    p = {k: v for k, v in PIPELINE.items() if k != "conv_steps"}
    assert readers.read(_spec("conv_in_place_share.lfm2"),
                        dict(OBS, pipeline=p)) is None
    bare = dict(OBS, trace=dict(OBS["trace"], regions={"ffn_dense": 0.1}))
    assert readers.read(_spec("conv_mixer_share.lfm2"), bare) is None
    assert readers.read(_spec("conv_mixer_share.lfm2"),
                        dict(OBS, trace={"busy_s": 3.9})) is None


def test_the_roofline_reader_matches_the_compiled_name_and_stays_under_100():
    """A trace whose convolution calls took exactly twenty times their
    least time reads 5 % through the reader, by the counts the traced
    stretch itself reports: 7 layers x 128 steps of 128 live rows."""
    cell = load_json("cells", CELL + ".json")
    name = cell["kernels"]["short_conv"]["op"]
    assert name == "short_conv_decode_step-bf16_7_129_32_128"
    steps, rows, layers = 128, 128, 7
    cost = layers * kernel_cost.roofline_seconds(
        short_conv_cost.decode_step_cost(rows * steps, 2048, 3),
        PEAK)["seconds"]
    obs = {"peak": PEAK, "cell": cell,
           "traced": {"pipeline": {"state_slots_live": rows * steps}},
           "trace": {"n_devices": 1, "busy_s": 200 * cost,
                     "ops": {name: 20 * cost, "fusion.1": 180 * cost},
                     "op_counts": {name: layers * steps, "fusion.1": 5}}}
    assert readers.read(_spec("short_conv_roofline.lfm2"), obs) \
        == pytest.approx(5.0, rel=1e-6)
    assert readers.read(_spec("short_conv_share.lfm2"), obs) \
        == pytest.approx(10.0)
    # two traced rounds: twice the calls, twice the row-steps, one reading
    two = dict(obs, traced={"pipeline": {"state_slots_live":
                                         2 * rows * steps}},
               trace=dict(obs["trace"], ops={name: 40 * cost},
                          op_counts={name: 2 * layers * steps}))
    assert readers.read(_spec("short_conv_roofline.lfm2"), two) \
        == pytest.approx(5.0, rel=1e-6)
    # another model's convolution is not matched: Kimi's pool
    other = dict(obs, trace=dict(obs["trace"], ops={
        "short_conv_decode_step-bf16_6_129_288_128": 1.0},
        op_counts={"short_conv_decode_step-bf16_6_129_288_128": 1}))
    assert readers.read(_spec("short_conv_roofline.lfm2"), other) is None
    assert readers.read(_spec("short_conv_share.lfm2"), other) is None
    # a run without the counter, a cell without the block: nothing
    assert readers.read(_spec("short_conv_roofline.lfm2"),
                        dict(obs, traced={"pipeline": {}})) is None
    assert readers.read(_spec("short_conv_roofline.lfm2"),
                        dict(obs, cell={"kernels": {}})) is None
