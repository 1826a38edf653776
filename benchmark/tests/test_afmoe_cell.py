"""``train-trinity-mini-8k-1chip``: its manifest entries and files, the
configuration against the catalog row's keys as far as this repository can
hold them, the rehearsal of its job kind on the CPU (toy sizes), and its
readers on the rehearsal's observations and on made-up trace numbers."""

import functools
import json
import os

import pytest

from benchmark import readers, run
from benchmark.common import load_json, load_manifest

CELL = "train-trinity-mini-8k-1chip"
CONFIG = "trinity-mini-26b-a3b"
MANIFEST = load_manifest()
NEW = ["mfu.train_sparse", "flash_window_roofline.train",
       "attn_window_share.train", "moe_experts_scoped_share.train",
       "moe_route_share.train", "moe_shared_share.train",
       "expert_imbalance.train", "moe_rows_here_share.train"]
JOINED = ["peak_hbm_gb.train", "device_idle_share.train",
          "host_launch_ms_per_step.train", "region_named_share.train",
          "optimizer_share.train", "loss_share.train",
          "ffn_dense_share.train", "flash_score_area_share.train",
          "engine_bracketed_share.train", "observer_ms_per_step.train",
          "setup_trace_lower_s.train", "setup_import_s", "setup_compile_s"]


def test_the_manifests_entries():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert entry == MANIFEST["workloads"][-1] and entry["chips"] == 1
    assert (entry["config"], entry["traffic"]) == (CONFIG,
                                                   "pretrain-8k-sparse")
    config = MANIFEST["configs"][-1]
    assert config["name"] == CONFIG and config["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"]
    mine = [m["name"] for m in MANIFEST["per_layer"]
            if CELL in m["workloads"]]
    assert sorted(mine) == sorted(NEW + JOINED)
    # the eight new ones are the manifest's last, the cell's alone
    assert [m["name"] for m in MANIFEST["per_layer"][-8:]] == NEW
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_tok_s"
               for m in MANIFEST["per_layer"][-8:])
    for absent in ("mfu.train", "flash_attn_roofline.train"):
        assert absent not in mine
    e2e = {m["name"] for m in MANIFEST["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"train_tok_s", "setup_s"}


def test_the_configuration_keeps_every_width_and_states_its_cut():
    cfg = load_json("configs", CONFIG + ".json")
    published = {"hidden_size": 2048, "intermediate_size": 6144,
                 "moe_intermediate_size": 1024, "head_dim": 128,
                 "num_attention_heads": 32, "num_key_value_heads": 4,
                 "num_experts_per_tok": 8, "sliding_window": 2048,
                 "route_scale": 2.826, "rms_norm_eps": 1e-05,
                 "rope_theta": 10000, "num_shared_experts": 1,
                 "load_balance_coeff": 0.001, "global_attn_every_n_layers": 4,
                 "max_position_embeddings": 131072, "model_type": "afmoe",
                 "score_func": "sigmoid", "route_norm": True,
                 "mup_enabled": True, "tie_word_embeddings": False}
    assert {k: cfg[k] for k in published} == published
    assert set(cfg["reduced"]) == {"num_hidden_layers", "layer_types",
                                   "num_dense_layers", "num_experts",
                                   "vocab_size"}
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"]) == (5, 1, 16, 25024)
    assert cfg["layer_types"] == ["sliding_attention"] * 4 \
        + ["full_attention"]
    assert (cfg["num_experts_published"], cfg["vocab_size_published"],
            cfg["chips_sharing_a_layer"]) == (128, 200192, 8)
    # the guide's floors: a whole period, >= 8 experts, >= 1/8 vocabulary
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["vocab_size_published"]
    assert len(cfg["assumed"]) >= 10 and "ep = 8" in cfg["deployment"]


def test_the_tree_is_the_byte_count_the_file_states():
    import jax
    import numpy as np
    from benchmark.model_types import afmoe as mt
    from deepspeed_tpu.models.afmoe import param_counts
    model_cfg = mt.model_config(load_json("configs", CONFIG + ".json"),
                                "float32")
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        mt.param_shapes(model_cfg)))
    assert n == param_counts(model_cfg)[0] == 705_474_304
    # one routed row a token and sparse layer: the dense path + 4 experts
    assert mt.active_params(model_cfg, 4.0) == pytest.approx(277.6e6,
                                                             rel=5e-3)


@functools.lru_cache(maxsize=None)
def _rehearse():
    return run.run_cell(["--workload", CELL, "--seed", "3000000019",
                         "--rehearse", "--trace", "1"])


def test_the_rehearsal_fills_every_key_the_cells_readers_name(capsys):
    line, obs = _rehearse()
    capsys.readouterr()
    assert line["correct"] and line["device"]["platform"] == "cpu"
    assert line["metrics"] == {} and list(line)[-1] == "compared"
    assert set(line["checks"]) >= {
        "first_loss_matches_reference", "first_step_matches_plain_adamw",
        "step_gradient_matches_reference", "forward_matches_reference",
        "bias_moves_by_the_rule", "no_compile_in_window"}
    # the deciding numbers are the ENGINE's own first step's, in float32
    # on the CPU: a state left unchanged would read 1
    assert 0 < line["compared"]["step_update_gap"]["value"] < 1e-3
    assert 0 < line["compared"]["optimizer_gap"]["value"] < 1e-3
    assert line["compared"]["step_grad_min_cosine"]["value"] > 0.9999
    for name in NEW + JOINED:
        spec = load_json("layer_metrics", name + ".json")
        missing = [key for key in readers.keys_of(spec)
                   if key.split(".")[0] not in ("trace", "peak")
                   and readers.lookup(obs, key) is None]
        assert not missing, (name, missing)
    stats = obs["step_stats"]
    assert stats["steps"] == obs["steps"]
    rows = stats["moe_rows_routed"] + stats["moe_rows_elsewhere"]
    # toy: a dense layer and 2 sparse ones, top-4 of 16, 4 held
    assert rows == obs["tokens"] * 2 * 4
    assert stats["moe_rows_hottest"] >= stats["moe_rows_routed"] > 0
    assert obs["attention"]["window_layers"] == 2
    assert obs["attention"]["full_layers"] == 1


def test_the_new_readers_on_the_rehearsals_books_and_a_made_up_trace(capsys):
    _line, obs = _rehearse()
    capsys.readouterr()
    read = lambda name, o: readers.read(                    # noqa: E731
        load_json("layer_metrics", name + ".json"), o)
    stats = obs["step_stats"]
    assert read("expert_imbalance.train", obs) == pytest.approx(
        stats["moe_rows_hottest"] / stats["moe_rows_routed"])
    assert 0 < read("moe_rows_here_share.train", obs) < 100
    # no chip, no peak: a share of a peak has nothing to read
    assert read("mfu.train_sparse", obs) is None
    assert read("flash_window_roofline.train", obs) is None
    from benchmark import kernel_cost
    from benchmark.flash_window_cost import windowed_flash_attention_cost
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    att = {"batch": 2, "heads": 32, "kv_heads": 4, "seq": 8192,
           "head_dim": 128, "window": 2048}

    def least(window):
        s = lambda b: kernel_cost.roofline_seconds(         # noqa: E731
            windowed_flash_attention_cost(2, 32, 8192, 128, window,
                                          backward=b), peak)["seconds"]
        return 2 * s(False) + s(True)       # a layer's four calls

    ops = {"attn_w2048-bf16_2_32_8192_128": (3, 0.75),   # fwd x2, dq
           "attn_w2048-bf16_2_4_8192_128": (1, 0.25),    # dk/dv
           "attn-bf16_2_32_8192_128": (3, 0.75),
           "attn-bf16_2_4_8192_128": (1, 0.25)}
    took = {k: 2 * share * least(2048 if "_w" in k else None)
            for k, (_, share) in ops.items()}
    made = {"attention": att, "peak": peak, "trace": {
        "n_devices": 1, "ops": took,
        "op_counts": {k: n for k, (n, _) in ops.items()}}}
    assert read("flash_window_roofline.train", made) == pytest.approx(50.0)
    made["active_model_flops_per_s_chip"] = 0.25 * 197e12
    assert read("mfu.train_sparse", made) == pytest.approx(25.0)
    made["trace"].update(busy_s=2.0, regions={"moe_experts": 0.5})
    assert read("moe_experts_scoped_share.train", made) == pytest.approx(25.0)
    assert read("moe_route_share.train", made) is None


def test_the_planted_faults_fail_by_the_first_steps_own_numbers(capsys):
    """Through the harness, on the CPU at toy size: the sound step passes
    the limits the traffic file sets on the engine's first step; a step on
    half the batch, and a state left unchanged (which reads exactly 1),
    fail them."""
    import json
    from benchmark.jobs import train_sparse
    tol = load_json("traffic", "pretrain-8k-sparse.json")["tolerances"]
    assert train_sparse.controls([
        "--workload", CELL, "--seed", "3000000019", "--rehearse",
        "--only", "right,half_batch,unchanged_state"]) == 0
    found = {d["control"]: d for d in (
        json.loads(line) for line in capsys.readouterr().out.splitlines()
        if line.startswith('{"control"'))}

    def passes(d):
        return (d["step_update_gap"] <= tol["step_update_gap"]
                and d["optimizer_gap"] <= tol["optimizer_gap"]
                and d["step_grad_min_cosine"] >= tol["step_grad_min_cosine"]
                and d["step_grad_norm_gap"] <= tol["step_grad_norm_gap"])

    assert passes(found["right"])
    assert not passes(found["half_batch"])
    assert found["unchanged_state"]["step_update_gap"] == pytest.approx(1.0)
    assert not passes(found["unchanged_state"])
