"""The cell ``serve-olmo-hybrid-rollout-long``, its three ``.olmo_hybrid``
readers, its ``kernels`` block and the families' readers that list it
(``.rollout`` / ``.serve``): the job exports every key they name (a
``--rehearse`` walk of the cell on the CPU, toy sizes), the two cost
functions of ``gdn_cost.py`` by hand, each reader on hand-made
observations (and nothing where the program has no such counter, as the
parent of PR 65 has not), and the roofline readers against a hand-made
trace that carries the kernel names the v5e compile gives at the published
widths (``tests/unit/test_tpu_compile_state.py`` holds those names;
``tests/unit/test_olmo_hybrid.py`` has the model; a time comes only from a
chip run). Nothing here looks at where in ``BENCHMARK.json``'s lists the
entries stand."""

from benchmark import gdn_cost, kernel_cost, readers, run
from benchmark.common import load_json, load_manifest

CELL = "serve-olmo-hybrid-rollout-long"
CONFIG = "olmo-hybrid-7b"
MANIFEST = load_manifest()
PEAK = kernel_cost.peaks("TPU v5 lite")
NAMES = [m["name"] for m in run._metrics_of(MANIFEST, "per_layer", CELL)]
OWN = sorted(n for n in NAMES if n.endswith(".olmo_hybrid"))
#: the heirs ``test_family_readers.py`` pins to PR 58's lists: this cell
#: runs the paged decode kernel and a delta rule's two kernels and cannot
#: join them without an edit to that test (PERF.md section 7 has the rows
#: for the next ``benchmark`` PR)
PINNED = ("paged_attn_roofline.rollout", "linear_attn_roofline.rollout",
          "linear_attn_prefill_roofline.rollout",
          "state_update_share.rollout")


def _spec(name):
    return load_json("layer_metrics", name + ".json")


def test_the_manifest_gives_the_cell_its_metrics():
    assert OWN == ["gdn_prefill_roofline.olmo_hybrid",
                   "gdn_state_roofline.olmo_hybrid",
                   "state_padding_share.olmo_hybrid"]
    assert all(n.rpartition(".")[2] in ("rollout", "serve") or "." not in n
               for n in NAMES if n not in OWN)
    assert len(NAMES) == 22 and not set(PINNED) & set(NAMES)
    # a dense model: none of the sparse layers' entries
    assert not [n for n in NAMES if n.startswith(("moe_", "expert_"))]
    for name in ("decode_live_rows_share.rollout",
                 "kv_write_rows_per_run.rollout",
                 "linear_attn_prefill_kernel_share.rollout",
                 "state_cache_share.rollout", "ffn_dense_share.rollout",
                 "region_named_share.rollout", "peak_hbm_gb.rollout"):
        assert name in NAMES, name
    for m in MANIFEST["per_layer"]:
        if m["name"] in OWN:
            assert (m["moves"], m["workloads"], m["unit"], m["layer"]) \
                == ("serve_tok_s", [CELL], "%", "kernels")
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "rollout-long", 1)
    assert len(cell["why"]) <= 200
    e2e = [m["name"] for m in run._metrics_of(MANIFEST, "end_to_end", CELL)]
    assert sorted(e2e) == ["serve_tok_s", "setup_s"]
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert sorted(cfg["reduced"]) == sorted(
        load_json("configs", CONFIG + ".json")["reduced"]) \
        == ["layer_types", "num_hidden_layers"]
    assert len(cfg["why"]) <= 200


def test_the_cells_file_is_issue_65s_cell():
    mine = load_json("cells", CELL + ".json")
    eng = mine["engine"]
    assert (mine["kind"], mine["clients"], mine["planned_requests"],
            mine["admit_max"], mine["trace_rounds"]) \
        == ("closed_loop", 64, 384, 16, 1)
    assert (eng["max_seqs"], eng["chunk_size"], eng["block_size"],
            eng["max_blocks_per_seq"], eng["decode_loop_steps"],
            eng["dtype"], eng["kv_cache_dtype"], eng["max_batch_tokens"]) \
        == (64, 512, 256, 24, 256, "bfloat16", "auto", 8192)
    # the stated ladder: 960 blocks, no fewer than 912
    assert 912 <= eng["num_blocks"] <= 960
    # the traffic is serve-kimi-linear-rollout-long's file, as it is; its
    # outputs are whole loops of 256 steps, 8 and 16 of them
    traffic = load_json("traffic", "rollout-long.json")
    assert [g // eng["decode_loop_steps"] for g in traffic["gen_lens"]] \
        == [8, 16]
    assert all(g % eng["decode_loop_steps"] == 0
               for g in traffic["gen_lens"])
    # the check reads past the first flush of the 256-step loop
    assert mine["correct"]["sequences"] == 4
    assert mine["correct"]["tokens"] >= 320
    pool = mine["pool"]
    # 2 full layers x K and V x 30 heads x 128 lanes x 2 B
    assert pool["bytes_per_token"] == 2 * 2 * 30 * 128 * 2 == 30720
    assert pool["reserved_bytes"] == pool["bytes_per_token"] \
        * eng["num_blocks"] * eng["block_size"]
    # 6 layers x (30 x 96 x 192 floats + 3 taps x 11,520 channels x 2 B)
    k = mine["kernels"]["gdn_state"]
    assert pool["state_bytes_per_sequence"] == 6 * (
        k["heads"] * k["d_k"] * k["d_v"] * 4 + 3 * 11520 * 2) == 13685760
    # as stored: the convolution's pool is 12,288 wide
    assert pool["state_pool_bytes"] == (eng["max_seqs"] + 1) * 6 * (
        k["heads"] * k["d_k"] * k["d_v"] * 4 + 3 * 12288 * 2)
    assert all(len(mine[key]["why"]) > 200 for key in ("correct",)) \
        and len(mine["why"]) > 200 and len(pool["worked"]) > 200


def test_the_kernels_block_is_read_and_whole():
    block = load_json("cells", CELL + ".json")["kernels"]
    assert set(block) == {"gdn_state", "gdn_prefill"}
    named = set()
    for name in NAMES:
        named |= {k for k in readers.keys_of(_spec(name))
                  if k.startswith("cell.kernels.")}
    stated = {f"cell.kernels.{family}.{k}" for family, entry in block.items()
              for k in entry if k != "why"}
    assert stated == named
    assert all(entry["why"] for entry in block.values())
    assert block["gdn_state"] == dict(
        block["gdn_state"], sequences=64, heads=30, d_k=96, d_v=192,
        op="gdn_decode_state_update-f32_65_96_5760")
    assert block["gdn_prefill"] == dict(
        block["gdn_prefill"], heads=30, d_k=96, d_v=192, layers=6,
        op="gdn_chunk_prefill-f32_4_30_512_192")


def test_the_configuration_keeps_every_width_and_states_its_cut():
    cfg = load_json("configs", CONFIG + ".json")
    published = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_attention_heads": 30, "num_key_value_heads": 30,
        "hidden_act": "silu", "max_position_embeddings": 65536,
        "attention_bias": False, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": False, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    assert {k: cfg[k] for k in published} == published
    assert set(cfg["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert cfg["num_hidden_layers"] == 8
    assert cfg["num_hidden_layers_published"] == 32
    # two WHOLE periods: the guide's floor is one, and four layers
    assert cfg["layer_types"] == (["linear_attention"] * 3
                                  + ["full_attention"]) * 2
    assert cfg["chips_sharing_a_layer"] == 1
    assert cfg["parameters"] == 2435748072           # 4.87 GB in bfloat16
    assert len(cfg["assumed"]) >= 8 and cfg["deployment"]


def test_the_cost_functions_against_hand_counts():
    # one decode token, 64 sequences, 30 heads of 96 x 192
    c = gdn_cost.gdn_decode_cost(sequences=64, heads=30, d_k=96, d_v=192)
    state = 64 * 30 * 96 * 192
    assert state == 35389440
    assert c["flops"] == 7 * state
    # the state twice, q k (96 each), v o (192 each), decay and step size
    assert c["bytes"] == 2 * state * 4 + 64 * 30 * (2 * 96 + 2 * 192 + 2) * 4
    assert c["bytes"] == 283115520 + 4439040
    # bytes-bound by two orders: 0.35 ms of 819 GB/s
    least = kernel_cost.roofline_seconds(c, PEAK)
    assert least["bound"] == "memory" and 3.4e-4 < least["seconds"] < 3.6e-4
    # one [4, 512] step's worth of real positions: 32 chunks of 64
    p = gdn_cost.gdn_prefill_cost(tokens=2048, heads=30, d_k=96, d_v=192,
                                  sequences=4)
    per_chunk = 4 * 64 * 64 * 96 + 64 * 64 * 288 + 6 * 64 * 96 * 192 \
        + 2 * 64 * 64 * 192
    assert per_chunk == 11403264
    assert p["flops"] == per_chunk * 30 * 32
    assert p["bytes"] == 2048 * 30 * 578 * 4 + 2 * 4 * 30 * 96 * 192 * 4
    # 142 MB of float32 operands against 11 GFLOP at the bfloat16 peak
    assert kernel_cost.roofline_seconds(p, PEAK)["bound"] == "memory"
    # ONE decay a head where the channel form moves one a channel
    from benchmark import linear_attn_cost
    kda = linear_attn_cost.kda_prefill_cost(tokens=2048, heads=30, d_k=96,
                                            d_v=192, sequences=4)
    assert kda["flops"] == p["flops"]
    assert kda["bytes"] - p["bytes"] == 2048 * 30 * (96 - 1) * 4


def _obs(**pipeline):
    cell = load_json("cells", CELL + ".json")
    state, chunk = (cell["kernels"][k]["op"]
                    for k in ("gdn_state", "gdn_prefill"))
    # 256 steps x 6 layers of the update at 0.5 ms, 5 refill steps x 6
    # layers of the chunk kernel at 2 ms
    return {"cell": cell, "peak": PEAK,
            "pipeline": pipeline,
            "traced": {"pipeline": {
                "linear_attn_prefill_kernel_tokens": 9216.0,
                "prefill_rows": 6.0}},
            "trace": {"n_devices": 1, "busy_s": 4.0,
                      "ops": {state: 256 * 6 * 0.5e-3, chunk: 30 * 2e-3},
                      "op_counts": {state: 256 * 6, chunk: 30}}}


def test_the_three_readers_on_hand_made_observations():
    obs = _obs(state_bytes_resident=4000.0, state_bytes_padding=1000.0,
               state_bytes_live=3000.0)
    assert readers.read(_spec("state_padding_share.olmo_hybrid"), obs) \
        == 25.0
    whole = _obs(state_bytes_resident=3000.0, state_bytes_padding=0.0)
    assert readers.read(_spec("state_padding_share.olmo_hybrid"), whole) \
        == 0.0
    least = kernel_cost.roofline_seconds(gdn_cost.gdn_decode_cost(
        sequences=64, heads=30, d_k=96, d_v=192), PEAK)["seconds"]
    got = readers.read(_spec("gdn_state_roofline.olmo_hybrid"), obs)
    assert abs(got - 100 * least / 0.5e-3) < 1e-9 and 60 < got < 80
    least = kernel_cost.roofline_seconds(gdn_cost.gdn_prefill_cost(
        tokens=9216.0, heads=30, d_k=96, d_v=192, sequences=6.0),
        PEAK)["seconds"]
    got = readers.read(_spec("gdn_prefill_roofline.olmo_hybrid"), obs)
    assert abs(got - 100 * 6 * least / 60e-3) < 1e-9 and 0 < got < 100


def test_a_program_without_the_counters_or_the_kernels_reads_nothing():
    """The parent of PR 65 builds no such model; whatever it ran, these
    readers find nothing there and the line leaves the metrics out."""
    obs = _obs(state_bytes_live=3000.0)
    assert readers.read(_spec("state_padding_share.olmo_hybrid"), obs) \
        is None
    bare = dict(obs, trace={"n_devices": 1, "busy_s": 4.0, "ops": {},
                            "op_counts": {}})
    for name in OWN:
        assert readers.read(_spec(name), bare) is None
        assert readers.read(_spec(name), {}) is None
