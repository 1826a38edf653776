"""The cell ``serve-jamba2-rollout-long``, its three ``.jamba2`` readers,
its ``kernels`` block and the families' readers that list it (``.rollout``
/ ``.serve``): the cell's files load and say what ISSUE 68 asked, each
reader on hand-made observations (and nothing where the program has no such
kernel, as the parent of PR 68 has not), and the roofline readers against
a hand-made trace that carries the kernel names the v5e compile gives at
the published widths (``tests/unit/test_tpu_compile_state.py`` holds those
names; ``tests/unit/test_jamba.py`` has the model and
``test_selective_scan_cost.py`` the cost functions; a time comes only from
a chip run). Nothing here looks at where in ``BENCHMARK.json``'s lists the
entries stand."""

from benchmark import kernel_cost, readers, run, selective_scan_cost
from benchmark.common import load_json, load_manifest

CELL = "serve-jamba2-rollout-long"
CONFIG = "jamba2-3b"
MANIFEST = load_manifest()
PEAK = kernel_cost.peaks("TPU v5 lite")
NAMES = [m["name"] for m in run._metrics_of(MANIFEST, "per_layer", CELL)]
OWN = sorted(n for n in NAMES if n.endswith(".jamba2"))
#: the heirs ``test_family_readers.py`` pins to PR 58's lists: this cell
#: runs the paged decode kernel and a state update and cannot join them
#: without an edit to that test (PERF.md section 7 has the rows for the
#: next ``benchmark`` PR)
PINNED = ("paged_attn_roofline.rollout", "state_update_share.rollout",
          "ssm_roofline.rollout")


def _spec(name):
    return load_json("layer_metrics", name + ".json")


def test_the_manifest_gives_the_cell_its_metrics():
    assert OWN == ["selective_scan_prefill_roofline.jamba2",
                   "selective_scan_roofline.jamba2",
                   "selective_scan_share.jamba2"]
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
            assert (m["moves"], m["workloads"], m["unit"], m["layer"],
                    m["source"]) == ("serve_tok_s", [CELL], "%", "kernels",
                                     "device_trace")
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "rollout-long", 1)
    assert len(cell["why"]) <= 200
    e2e = [m["name"] for m in run._metrics_of(MANIFEST, "end_to_end", CELL)]
    assert sorted(e2e) == ["serve_tok_s", "setup_s"]
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == [] \
        and load_json("configs", CONFIG + ".json")["reduced"] == {}
    assert len(cfg["why"]) <= 200


def test_the_cells_file_is_issue_68s_cell():
    mine = load_json("cells", CELL + ".json")
    eng = mine["engine"]
    # the engine of serve-nemotron3-nano-rollout-long to the key
    assert eng == load_json(
        "cells", "serve-nemotron3-nano-rollout-long.json")["engine"]
    assert (mine["kind"], mine["clients"], mine["planned_requests"],
            mine["admit_max"], mine["trace_rounds"]) \
        == ("closed_loop", 256, 1536, 16, 1)
    assert (eng["max_seqs"], eng["chunk_size"], eng["block_size"],
            eng["num_blocks"], eng["max_blocks_per_seq"],
            eng["decode_loop_steps"], eng["dtype"], eng["max_batch_tokens"]) \
        == (256, 512, 256, 3840, 24, 128, "bfloat16", 8192)
    # the traffic file is the other long cells', as it is; its outputs
    # are whole loops of 128 steps
    traffic = load_json("traffic", "rollout-long.json")
    assert all(g % eng["decode_loop_steps"] == 0
               for g in traffic["gen_lens"])
    # the check reads past the second flush of the 128-step loop
    assert mine["correct"]["sequences"] == 4
    assert mine["correct"]["tokens"] == 320 > 2 * eng["decode_loop_steps"]
    pool = mine["pool"]
    # 2 attention layers x K and V x ONE kv head x 128 lanes x 2 B
    assert pool["bytes_per_token"] == 2 * 2 * 1 * 128 * 2 == 1024
    assert pool["reserved_bytes"] == pool["bytes_per_token"] \
        * eng["num_blocks"] * eng["block_size"]
    # 26 layers x (16 x 5,120 floats + 3 taps x 5,120 channels x 2 B)
    k = mine["kernels"]["selective_scan"]
    state = k["channels"] * k["state"] * 4
    assert pool["state_bytes_per_sequence"] == 26 * (state + 3 * 5120 * 2) \
        == 9318400
    # as stored: the convolution's pool is 6,144 wide
    assert pool["state_pool_bytes"] == (eng["max_seqs"] + 1) * 26 * (
        state + 3 * 6144 * 2)
    assert all(len(mine[key]["why"]) > 200 for key in ("correct",)) \
        and len(mine["why"]) > 200 and len(pool["worked"]) > 200


def test_the_kernels_block_is_read_and_whole():
    block = load_json("cells", CELL + ".json")["kernels"]
    assert set(block) == {"selective_scan", "selective_scan_prefill"}
    named = set()
    for name in NAMES:
        named |= {k for k in readers.keys_of(_spec(name))
                  if k.startswith("cell.kernels.")}
    stated = {f"cell.kernels.{family}.{k}" for family, entry in block.items()
              for k in entry if k != "why"}
    assert stated == named
    assert all(entry["why"] for entry in block.values())
    assert block["selective_scan"] == dict(
        block["selective_scan"], sequences=256, channels=5120, state=16,
        op="mamba1_decode_state_update-f32_257_16_5120")
    assert block["selective_scan_prefill"] == dict(
        block["selective_scan_prefill"], channels=5120, state=16, layers=26,
        op="mamba1_chunk_scan-f32_257_16_5120")


def test_the_configuration_is_the_published_one_whole():
    cfg = load_json("configs", CONFIG + ".json")
    published = {
        "model_type": "jamba", "vocab_size": 65536, "hidden_size": 2560,
        "intermediate_size": 8192, "num_hidden_layers": 28,
        "num_attention_heads": 20, "num_key_value_heads": 1,
        "attn_layer_period": 14, "attn_layer_offset": 7, "num_experts": 1,
        "mamba_expand": 2, "mamba_d_state": 16, "mamba_d_conv": 4,
        "mamba_dt_rank": 160, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": True, "hidden_act": "silu"}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == {}
    assert cfg["parameters"] == 3029337472           # 6.06 GB in bfloat16
    assert len(cfg["assumed"]) >= 8 and cfg["deployment"]
    assert cfg["rehearse"]["num_hidden_layers"] == 4


def _obs(**pipeline):
    cell = load_json("cells", CELL + ".json")
    step, chunk = (cell["kernels"][k]["op"]
                   for k in ("selective_scan", "selective_scan_prefill"))
    # 128 steps x 26 layers of the update at 0.3 ms, 8 refill steps x 26
    # layers of the chunk scan at 1 ms
    return {"cell": cell, "peak": PEAK, "pipeline": pipeline,
            "traced": {"pipeline": {
                "linear_attn_prefill_kernel_tokens": 16000.0,
                "prefill_rows": 11.0}},
            "trace": {"n_devices": 1, "busy_s": 4.0,
                      "ops": {step: 128 * 26 * 0.3e-3, chunk: 208 * 1e-3},
                      "op_counts": {step: 128 * 26, chunk: 208}}}


def test_the_three_readers_on_hand_made_observations():
    obs = _obs()
    least = kernel_cost.roofline_seconds(
        selective_scan_cost.mamba1_decode_cost(
            sequences=256, channels=5120, state=16), PEAK)["seconds"]
    got = readers.read(_spec("selective_scan_roofline.jamba2"), obs)
    assert abs(got - 100 * least / 0.3e-3) < 1e-9 and 70 < got < 80
    least = kernel_cost.roofline_seconds(
        selective_scan_cost.mamba1_prefill_cost(
            tokens=16000.0, sequences=11.0, channels=5120, state=16),
        PEAK)["seconds"]
    got = readers.read(_spec("selective_scan_prefill_roofline.jamba2"), obs)
    assert abs(got - 100 * 26 * least / 208e-3) < 1e-9 and 0 < got < 100
    got = readers.read(_spec("selective_scan_share.jamba2"), obs)
    assert abs(got - 100 * (128 * 26 * 0.3e-3 + 208e-3) / 4.0) < 1e-9


def test_a_program_without_the_kernels_reads_nothing():
    """The parent of PR 68 builds no such model; whatever it ran, these
    readers find nothing there and the line leaves the metrics out."""
    bare = dict(_obs(), trace={"n_devices": 1, "busy_s": 4.0, "ops": {},
                               "op_counts": {}})
    for name in OWN:
        assert readers.read(_spec(name), bare) is None
        assert readers.read(_spec(name), {}) is None
