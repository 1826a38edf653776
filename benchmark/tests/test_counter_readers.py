"""The readers of PR 37, which read what the program already counts: each
on hand-made observations, the cost functions found by name, the stage
series of the open loop, and the one reading of a profile."""

import json
import os
import types

import pytest

from benchmark import (kernel_cost, linear_attn_cost, mla_cost, moe_cost,
                       readers, reduce_trace, run)
from benchmark.common import counters_delta, load_json
from benchmark.jobs import open_loop

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAK = kernel_cost.peaks("TPU v5 lite")


def _spec(name):
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        return json.load(f)


PIPELINE = {
    "prefill_tokens_real": 900, "prefill_tokens_planned": 2048,
    "prefill_steps": 8, "prefill_rows": 10,
    "fused_dispatch_s": 0.030, "fused_apply_s": 0.012,
    "kv_write_rows": 4096, "kv_write_runs": 64,
    "decode_kv_rows_live": 950, "decode_kv_rows_fetched": 1000,
    "latent_rows_live": 800, "latent_rows_fetched": 1000,
    "moe_rows_routed": 1000, "moe_rows_hottest": 1300,
    "moe_experts_hit": 5000, "moe_expert_reads": 5010,
    "moe_prefill_tokens": 4000, "moe_prefill_kernel_tokens": 3000,
    "linear_attn_prefill_tokens": 4000,
    "linear_attn_prefill_kernel_tokens": 4000,
    "sparse_select_queries": 8000, "sparse_select_kernel_queries": 6000,
    "window_rows_live": 900, "window_rows_scored": 1200}
OBS = {"pipeline": PIPELINE, "rounds": 12,
       "step_stats": {"steps": 100, "stage_s": 0.1, "dispatch_s": 0.4,
                      "commit_apply_s": 0.15,
                      "flash_score_elems_computed": 1166,
                      "flash_score_elems_needed": 1000},
       "door_wait_s": [0.001 * i for i in range(101)],
       "sched_wait_s": [0.0002] * 11, "prefill_s": [0.02 * i for i in
                                                    range(11)],
       "group_wait_s": [0.0, 0.1],
       "trace": {"window_s": 4.0,
                 "idle_by_phase": {"serve/plan": 0.04, "serve/dispatch": 0.2,
                                   "serve/commit_block": 0.1, "none": 0.3}}}

CASES = [
    ("door_wait_p90_ms.chat", 90.0), ("sched_wait_p90_ms.chat", 0.2),
    ("prefill_p90_ms.chat", 180.0), ("group_wait_p90_ms.chat", 90.0),
    ("launch_bubble_share.chat", 6.0),
    ("prefill_rows_per_step.chat", 1.25),
    ("kv_write_rows_per_run.chat", 64.0),
    ("kv_write_rows_per_run.rollout", 64.0),
    ("latent_live_rows_share.rollout", 80.0),
    ("host_launch_ms_per_step.train", 6.5),
    ("decode_live_rows_share.chat", 95.0),
    ("decode_live_rows_share.rollout", 95.0),
    ("prefill_useful_share.rollout", 100 * 900 / 2048),
    ("fused_host_ms_per_round.rollout", 3.5),
    ("expert_imbalance.rollout", 1.3), ("moe_reads_per_hit.rollout", 1.002),
    # the counters PR 39-53 left without a reader (PR 54)
    ("flash_score_area_share.train", 1.166),
    ("window_scored_share.mellum2", 75.0),
    ("moe_prefill_kernel_share.rollout", 75.0),
    ("linear_attn_prefill_kernel_share.rollout", 100.0),
    ("sparse_select_kernel_share.sala", 75.0),
]


@pytest.mark.parametrize("name,value", CASES, ids=[c[0] for c in CASES])
def test_a_reader_of_the_programs_own_counts(name, value):
    spec = _spec(name)
    assert readers.read(spec, OBS) == pytest.approx(value)
    # a program (or a job) that leaves the key out: nothing to read
    assert readers.read(spec, {}) is None
    assert readers.read(spec, {"pipeline": {}, "step_stats": {},
                               "trace": {"window_s": 4.0,
                                         "idle_by_phase": {"none": 1.0}}}) \
        is None
    # and every key it names is one the hand-made observations hold
    assert all(readers.lookup(OBS, k) is not None
               for k in readers.keys_of(spec))


def test_a_counter_that_stayed_at_nought_is_nothing_to_read():
    # a program on the ragged_dot path counts no visit and no hit
    obs = {"pipeline": dict(PIPELINE, moe_experts_hit=0, moe_expert_reads=0)}
    assert readers.read(_spec("moe_reads_per_hit.rollout"), obs) is None
    # and a model with no such layer reports no kernel share of 0 / 0
    none = {"pipeline": dict(PIPELINE, moe_prefill_tokens=0,
                             moe_prefill_kernel_tokens=0)}
    assert readers.read(_spec("moe_prefill_kernel_share.rollout"),
                        none) is None


def test_the_delta_takes_every_number_and_nothing_else():
    then = {"steps": 3, "plan_s": 0.5, "name": "x"}
    now = {"steps": 10, "plan_s": 0.75, "name": "x", "added_later": 7,
           "table": [1, 2]}
    assert counters_delta(now, then) == {"steps": 7, "plan_s": 0.25,
                                         "added_later": 7}


# ------------------------ cost functions by name ------------------------ #

@pytest.mark.parametrize("name,function", [
    ("paged_decode_attention_cost", kernel_cost.paged_decode_attention_cost),
    ("flash_attention_cost", kernel_cost.flash_attention_cost),
    ("moe_cost.grouped_moe_ffn_cost", moe_cost.grouped_moe_ffn_cost),
    ("linear_attn_cost.kda_decode_cost", linear_attn_cost.kda_decode_cost),
    ("linear_attn_cost.kda_prefill_cost", linear_attn_cost.kda_prefill_cost),
    ("mla_cost.mla_decode_attention_cost",
     mla_cost.mla_decode_attention_cost),
    ("kernel_cost.flash_attention_cost", kernel_cost.flash_attention_cost),
])
def test_a_cost_function_is_found_by_name(name, function):
    assert readers.cost_function(name) is function


@pytest.mark.parametrize("name", [
    "no_such_cost", "moe_cost.no_such_cost", "nowhere_cost.f",
    "readers.read", "os.system", "jobs.train.run"])
def test_an_unknown_cost_raises(name):
    with pytest.raises(KeyError):
        readers.cost_function(name)


def test_every_roofline_reader_names_a_cost_that_exists():
    folder = os.path.join(HERE, "layer_metrics")
    seen = 0
    for f in sorted(os.listdir(folder)):
        spec = _spec(f[:-len(".json")])
        for k in spec.get("kernels", ()):
            for c in k["costs"]:
                assert callable(readers.cost_function(c["cost"])), f
                seen += 1
    assert seen >= 12          # one a kernel family since PR 58


def _cell(name):
    return load_json("cells", name + ".json")


@pytest.mark.parametrize("cell,shape,hidden,width", [
    ("serve-olmoe-rollout", "1216_2048", 2048, 1024),
    ("serve-solar2-rollout", "1616_4096", 4096, 1280),
    ("serve-pangu-rollout-long", "1136_7680", 7680, 2048)])
def test_grouped_roofline_is_one_evaluation_over_the_traced_totals(
        cell, shape, hidden, width):
    # 64 steps x 8 sparse layers, 256 routed rows and ~60 experts hit each
    calls, rows, hit = 512, 512 * 256, 512 * 60
    least = kernel_cost.roofline_seconds(moe_cost.grouped_moe_ffn_cost(
        rows, hit, hidden, width), PEAK)
    assert least["bound"] == "memory"
    name = f"grouped_ffn_decode-bf16_{shape}"
    obs = {"peak": PEAK, "cell": _cell(cell),
           "traced": {"pipeline": {"moe_rows_routed": rows,
                                   "moe_experts_hit": hit}},
           # the window's own totals must not be what it counts
           "pipeline": {"moe_rows_routed": 50 * rows,
                        "moe_experts_hit": 50 * hit},
           "trace": {"n_devices": 1,
                     "ops": {name: least["seconds"] / 0.925,
                             "grouped_ffn_decode-bf16_18880_4096": 1.0},
                     "op_counts": {
                         name: calls,
                         "grouped_ffn_decode-bf16_18880_4096": 16}}}
    spec = _spec("grouped_moe_roofline.rollout")
    assert readers.read(spec, obs) == pytest.approx(92.5)
    # the share is the by-hand one of moe_cost.roofline_share
    by_hand = moe_cost.roofline_share(
        least["seconds"] / 0.925, calls, PEAK, rows=rows / calls,
        experts_hit=hit / calls, hidden=hidden, width=width)
    assert by_hand["share"] == pytest.approx(92.5)
    # a job that exports no traced counters, or a decode loop without
    # the kernel (the ragged_dot path): nothing to read
    assert readers.read(spec, dict(obs, traced={})) is None
    assert readers.read(spec, dict(obs, trace={
        "n_devices": 1, "ops": {"ragged-dot-none-bf16_256_1024": 1.0},
        "op_counts": {"ragged-dot-none-bf16_256_1024": 9}})) is None
    # counters at nought under a kernel that ran: no share of 0
    assert readers.read(spec, dict(obs, traced={"pipeline": {
        "moe_rows_routed": 0, "moe_experts_hit": 0}})) is None


def test_linear_attn_roofline_counts_one_cost_a_call():
    call = kernel_cost.roofline_seconds(
        linear_attn_cost.kda_decode_cost(128, 64, 128, 128), PEAK)
    assert call["bound"] == "memory"
    name = "kda_decode_state_update-f32_129_64_128_128"
    obs = {"peak": PEAK, "cell": _cell("serve-solar2-rollout"),
           "trace": {"n_devices": 1,
                     "ops": {name: 192 * call["seconds"] / 0.8,
                             "custom-call-f32_4_64_1_64_64": 0.03},
                     "op_counts": {name: 192,
                                   "custom-call-f32_4_64_1_64_64": 48}}}
    assert readers.read(_spec("linear_attn_roofline.rollout"), obs) \
        == pytest.approx(80.0)


@pytest.mark.parametrize("cell,heads,layers", [
    ("serve-solar2-rollout", 64, 3),
    ("serve-kimi-linear-rollout-long", 32, 6)])
def test_linear_attn_prefill_roofline_is_one_evaluation_a_layer(
        cell, heads, layers):
    """A traced round of 40 [4, 512] refill steps whose three rows hold
    61,437 real positions: the chunk kernel's work is those positions in
    chunks of 64 and three states read and written, once a KDA layer; a
    kernel that took five times that reads 20 %, and the padded quarter of
    the steps is no work."""
    tokens, rows = 61437.0, 3.0
    least = kernel_cost.roofline_seconds(linear_attn_cost.kda_prefill_cost(
        tokens, heads, 128, 128, sequences=rows), PEAK)
    # q, k, decay, v, output in float32 outweigh the chunk's matmuls
    assert least["bound"] == "memory"
    name = f"kda_chunk_prefill-f32_4_512_{heads}_128"
    obs = {"peak": PEAK, "cell": _cell(cell),
           "traced": {"pipeline": {
               "linear_attn_prefill_kernel_tokens": tokens,
               "prefill_rows": rows}},
           "trace": {"n_devices": 1,
                     "ops": {name: 5 * layers * least["seconds"],
                             "kda_decode_state_update-f32_129_64_128_128":
                                 1.0},
                     "op_counts": {
                         name: 40 * layers,
                         "kda_decode_state_update-f32_129_64_128_128": 9}}}
    spec = _spec("linear_attn_prefill_roofline.rollout")
    assert readers.read(spec, obs) == pytest.approx(20.0)
    # the other cell's kernel name is not matched, and a stretch whose
    # refill admitted nothing counts no work: no 0 %
    other = "serve-kimi-linear-rollout-long" if "solar2" in cell \
        else "serve-solar2-rollout"
    assert readers.read(spec, dict(obs, cell=_cell(other))) is None
    assert readers.read(spec, dict(obs, traced={"pipeline": {
        "linear_attn_prefill_kernel_tokens": 0.0,
        "prefill_rows": 0.0}})) is None
    assert readers.read(spec, dict(obs, traced={})) is None


def test_per_may_be_a_number_or_a_key():
    spec = {"reducer": "roofline", "kernels": [{
        "pattern": "^k$", "costs": [{
            "cost": "paged_decode_attention_cost",
            "args": {"context_tokens": "n"},
            "fixed": {"q_heads": 12, "kv_heads": 2, "head_dim": 128},
            "per": 3}]}]}
    one = kernel_cost.roofline_seconds(
        kernel_cost.paged_decode_attention_cost(1e6, 12, 2, 128), PEAK)
    obs = {"peak": PEAK, "n": 1e6, "times": 3,
           "trace": {"n_devices": 1, "ops": {"k": 6 * one["seconds"]},
                     "op_counts": {"k": 5}}}
    assert readers.read(spec, obs) == pytest.approx(50.0)
    spec["kernels"][0]["costs"][0]["per"] = "times"
    assert readers.read(spec, obs) == pytest.approx(50.0)
    assert readers.keys_of(spec) == ["n", "times"]
    assert readers.read(spec, dict(obs, times=None)) is None


# ------------------- the open loop's stage series ------------------- #

def test_the_open_loop_splits_a_first_token_by_the_engines_stamps():
    reqs = [types.SimpleNamespace(uid=u, segment=seg, due_s=due,
                                  prompt=[1] * 8, gen_len=2)
            for u, seg, due in ((0, "ramp", 0.0), (1, "window", 1.0),
                                (2, "window", 2.0), (3, "window", 3.0))]
    ctx = types.SimpleNamespace(param=lambda key: {
        "ttft_s": 1.0, "ttft_s_per_prompt_token": 0.0, "tpot_s": 1.0})
    engine = types.SimpleNamespace(config=types.SimpleNamespace(max_seqs=4))
    loop = open_loop.OpenLoop(ctx, engine, reqs, 8, 4)
    loop.t0 = 100.0
    # uid 1: due 101.0, put 101.03, scheduled 101.031, token 101.05,
    # host-visible with its put group at 101.09
    loop.stamps[1] = (101.03, 101.031, 101.05)
    loop.t_first[1], loop.t_last[1] = 101.09, 101.2
    loop.streams[1] = [5, 6]
    # uid 2: admitted, scheduled, no token yet; uid 3: never admitted
    loop.stamps[2] = (102.2, 102.25, None)
    loop.offered = {1: 101.0, 2: 102.0}
    s = loop.sample()
    assert s["n"] == 3 and s["failed"] == 2
    assert s["door_wait_s"] == pytest.approx([0.03, 0.2])
    assert s["sched_wait_s"] == pytest.approx([0.001, 0.05])
    assert s["prefill_s"] == pytest.approx([0.019])
    assert s["group_wait_s"] == pytest.approx([0.04])
    # the stages of a finished request add up to its first-token time
    assert sum(s[k][0] for k in ("door_wait_s", "sched_wait_s", "prefill_s",
                                 "group_wait_s")) \
        == pytest.approx(s["ttft_s"][0])


# --------------------- one reading of the profile --------------------- #

@pytest.mark.parametrize("fixture", ["v5e_serve_spans.xplane.pb",
                                     "v5e_matmul_loop.xplane.pb"])
def test_the_merged_reading_names_the_gaps_and_moves_no_sum(fixture):
    path = os.path.join(HERE, "fixtures", fixture)
    trace, breakdown = run.read_trace(path)
    alone = reduce_trace.reduce(reduce_trace.load(path))
    # what reduce_trace computes is what it computes alone, to the byte
    for key in ("window_s", "busy_s", "n_devices", "ops", "op_counts",
                "device_ops", "collective_s", "exposed_collective_s"):
        assert json.dumps(trace[key]) == json.dumps(alone[key]), key
    assert breakdown["device_ops"] == alone["device_ops"]
    assert set(breakdown) == {"device_ops", "idle_gaps"}
    assert len(breakdown["idle_gaps"]) <= 10
    # every idle second has one name, and the names sum to idle_s
    assert trace["idle_s"] == alone["window_s"] - alone["busy_s"]
    for key in ("idle_by_name", "idle_by_phase"):
        assert sum(trace[key].values()) == pytest.approx(trace["idle_s"],
                                                         rel=1e-9)
    assert 0.0 < trace["idle_named_share"] <= 1.0
    assert trace["clock_offset_s"] > 0 and trace["device_programs"]


def test_the_serve_fixtures_gaps_carry_the_programs_span():
    trace, breakdown = run.read_trace(os.path.join(
        HERE, "fixtures", "v5e_serve_spans.xplane.pb"))
    assert breakdown["idle_gaps"][0][0] \
        == "all_gaps_under_decode_pipelined/serve/dispatch"
    obs = {"trace": trace}
    bubble = readers.read(_spec("launch_bubble_share.chat"), obs)
    assert bubble == pytest.approx(100 * (0.016369 + 0.000676) / 0.029418,
                                   rel=1e-3)
    # a trace without the program's spans leaves that reader silent
    old, _ = run.read_trace(os.path.join(HERE, "fixtures",
                                         "v5e_matmul_loop.xplane.pb"))
    assert readers.read(_spec("launch_bubble_share.chat"),
                        {"trace": old}) is None


def test_the_regions_reach_the_traced_reading_and_no_untraced_run():
    """``read_trace`` adds the regions' keys (PR 54), addressed as the
    ``*_share`` readers over regions address them; the module is imported
    inside ``read_trace`` alone, so an untraced run never loads it."""
    import subprocess
    import sys
    trace, _ = run.read_trace(os.path.join(HERE, "fixtures",
                                           "v5e_regions.xplane.pb"))
    obs = {"trace": trace}
    assert readers.read(_spec("region_named_share.train"), obs) \
        == pytest.approx(100 * trace["region_named_share"])
    want = 100 * (trace["regions"]["optimizer"]
                  + trace["regions"]["grad_clip"]) / trace["busy_s"]
    assert readers.read(_spec("optimizer_share.train"), obs) \
        == pytest.approx(want) and want > 0
    assert trace["regions_read_s"] > 0
    # a profile of a program without regions: the readers stay silent
    old, _ = run.read_trace(os.path.join(HERE, "fixtures",
                                         "v5e_matmul_loop.xplane.pb"))
    for name in ("region_named_share.rollout", "ffn_dense_share.chat",
                 "attn_select_share.sala"):
        assert readers.read(_spec(name), {"trace": old}) is None
    code = ("import sys; import benchmark.run, benchmark.readers, "
            "benchmark.jobs.closed_loop; "
            "assert 'benchmark.regions' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=os.path.dirname(HERE))
