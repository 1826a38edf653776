"""The cell ``serve-minicpm-sala-rollout-32k`` and its readers: the seven
``.sala`` files whose bodies are its own (the block selection and the
sparse kernels, which one configuration has), its ``kernels`` block (PR
58: the Lightning layers' state update is Nemotron's kernel, and its two
``.sala`` files became an entry of the cell's own file) and the families'
readers that list it (``.rollout`` / ``.serve``). The engine counts every
key the readers name
(a toy engine, the job's own delta), each counter reader on hand-made
observations, the mix's equal rounds under ten seeds, the cost
functions of ``sparse_attn_cost.py`` by hand, the roofline readers against
a hand-made trace that carries the kernel names the v5e compile gives at
the published widths, a rehearsal of the cell (fill, one round, the traced
stretch), and ``mix_stats`` of ``rollout-32k`` against what the mix file
quotes (``tests/unit/test_minicpm_sala.py`` has the model; a time
comes only from a chip run). Nothing here looks at where in
``BENCHMARK.json``'s lists the entries stand."""

import pytest

from benchmark import kernel_cost, readers, run, sparse_attn_cost, ssm_cost
from benchmark.common import load_json, load_manifest
from benchmark.traffic import closed_loop_requests, first_wave, mix_stats

CELL = "serve-minicpm-sala-rollout-32k"
CONFIG = "minicpm-sala-9b"
MANIFEST = load_manifest()
PEAK = kernel_cost.peaks("TPU v5 lite")
NAMES = [m["name"] for m in run._metrics_of(MANIFEST, "per_layer", CELL)]
SALA = [n for n in NAMES if n.endswith(".sala")]


def _spec(name):
    return load_json("layer_metrics", name + ".json")


def test_the_manifest_gives_the_cell_its_metrics():
    assert sorted(SALA) == [
        "attn_select_share.sala", "sparse_attn_roofline.sala",
        "sparse_attn_share.sala", "sparse_prefill_roofline.sala",
        "sparse_prefill_visit_ratio.sala", "sparse_read_share.sala",
        "sparse_select_kernel_share.sala"]
    # no metric under another model's suffix; the families that list it
    family = [n for n in NAMES if n not in SALA]
    assert all(n.rpartition(".")[2] in ("rollout", "serve") or "." not in n
               for n in family)
    assert {"device_idle_share.rollout", "peak_hbm_gb.rollout",
            "prefill_useful_share.rollout", "refill_call_share.rollout",
            "ssm_roofline.rollout", "state_update_share.rollout",
            "fused_host_ms_per_round.rollout", "state_cache_share.rollout",
            "region_named_share.rollout"} <= set(family)
    assert len(cell_why()) <= 200
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "rollout-32k", 1)
    e2e = [m["name"] for m in run._metrics_of(MANIFEST, "end_to_end", CELL)]
    assert sorted(e2e) == ["serve_tok_s", "setup_s"]
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    file = load_json("configs", CONFIG + ".json")
    assert sorted(cfg["reduced"]) == sorted(file["reduced"]) \
        == ["mixer_types", "num_hidden_layers"]
    assert file["num_hidden_layers_published"] == 32 and file["assumed"]
    mine = load_json("cells", CELL + ".json")
    eng = mine["engine"]
    assert (mine["clients"], eng["max_seqs"], eng["decode_loop_steps"],
            eng["chunk_size"], eng["block_size"],
            eng["max_blocks_per_seq"]) == (96, 96, 256, 512, 256, 160)
    assert (mine["correct"]["sequences"], mine["correct"]["tokens"]) \
        == (4, 512)
    pool = mine["pool"]
    assert pool["reserved_bytes"] == pool["bytes_per_token"] \
        * eng["num_blocks"] * 256
    assert pool["state_pool_bytes"] == 97 * pool["state_bytes_per_sequence"]


def cell_why():
    return next(w for w in MANIFEST["workloads"] if w["name"] == CELL)["why"]


def test_the_mix_is_what_the_issue_named_and_quotes_its_own_numbers():
    mix = load_json("traffic", "rollout-32k.json")
    assert (mix["prompt_lens"], mix["prompt_shares"], mix["gen_lens"]) \
        == ([20480], [1], [8192])
    # ISSUE 54's table: ONE prompt class, the block of 12 left as it was
    assert mix["shuffle_block"] == load_json(
        "traffic", "rollout-long.json")["shuffle_block"] == 12
    stats = mix_stats(mix)
    for key, val in stats.items():
        assert mix["mix_stats"][key] == pytest.approx(val), key
    assert mix["mix_stats"]["mean_live_context"] == 24576
    assert mix["mix_stats"]["longest"] == 20480 + 8192
    # three clients at each of the 32 phases of an 8,192-token output:
    # every round 3 finish and 3 prompts refill
    wave = first_wave(mix, 96, 256, seed=1, vocab=1000)
    remaining = sorted(r.gen_len for r in wave)
    assert remaining == sorted([256 * (k + 1) for k in range(32)] * 3)
    live = sum(len(r.prompt) for r in wave)
    assert live == 96 * 20480 + 3 * 256 * sum(range(32)) == 2347008
    assert min(len(r.prompt) for r in wave) >= 20480 > 8192
    # the first wave fits the pool with each sequence's last block partly
    # filled, at its start and at the end of a loop (+ 256 a sequence)
    eng = load_json("cells", CELL + ".json")["engine"]
    blocks = sum(-(-(len(r.prompt) + 256) // eng["block_size"])
                 for r in wave)
    assert blocks == 9264 <= eng["num_blocks"]
    assert max(len(r.prompt) + r.gen_len for r in wave) \
        == eng["max_blocks_per_seq"] * eng["block_size"] - 12288


@pytest.mark.parametrize("seed", [0, 1, 7, 2147483659, 2971000003,
                                  3205000057, 3616000063, 4027000079,
                                  4294967295, 5400000101])
def test_every_round_refills_three_equal_prompts(seed):
    """The closed loop hands the finished slots the next requests of the
    seed's list, three a round: under every seed each is 20,480 tokens in
    and 8,192 out, so a round is 40 [4, 512] steps with 3 rows of 4 real
    (prefill_useful_share 75 %); only the token ids are the seed's."""
    mix = load_json("traffic", "rollout-32k.json")
    cell = load_json("cells", CELL + ".json")
    reqs = closed_loop_requests(mix, cell["planned_requests"], seed, 1000)
    assert len(reqs) == 288
    chunk = cell["engine"]["chunk_size"]
    for i in range(0, len(reqs), 3):
        a_round = reqs[i:i + 3]
        assert [(len(r.prompt), r.gen_len) for r in a_round] \
            == [(20480, 8192)] * 3
        # prefilled up to its last token, which the loop feeds
        steps = max(-(-(len(r.prompt) - 1) // chunk) for r in a_round)
        real = sum(len(r.prompt) - 1 for r in a_round)
        assert steps == 40 and real / (steps * 4 * chunk) \
            == pytest.approx(0.75, abs=1e-4)
    other = closed_loop_requests(mix, 3, seed + 1, 1000)
    assert other[0].prompt != reqs[0].prompt


def test_an_engine_counts_every_key_the_readers_name():
    """Every key a reader of the cell names outside ``trace.*`` and
    ``peak.*`` is one of the job's observations: the closed-loop job's own
    keys, or a counter of ``engine.pipeline_stats`` (whose delta the job
    exports whole, for the window and for the traced stretch)."""
    import jax.numpy as jnp
    from benchmark.model_types import minicpm_sala as mt
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)
    from deepspeed_tpu.models.minicpm_sala import MiniCPMSALAConfig
    cfg = MiniCPMSALAConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    eng = InferenceEngineV2(cfg, mt.init_params(cfg, 0),
                            RaggedInferenceConfig(
        max_seqs=2, chunk_size=32, block_size=16, num_blocks=40,
        max_blocks_per_seq=20, decode_loop_steps=8, dtype="float32"))
    eng.put([0], [list(range(100))], _greedy=True)
    eng.decode_batch([0], [5], 8)
    job = {"window_s", "rounds", "memory_peak_bytes"}
    own = {"cell": load_json("cells", CELL + ".json")}
    missing = []
    for name in NAMES:
        for key in readers.keys_of(_spec(name)):
            head, _, rest = key.partition(".")
            if head in ("trace", "peak", "setup") or key in job:
                continue
            if head == "cell":          # the cell's own file states it
                assert readers.lookup(own, key) is not None, (name, key)
                continue
            counter = key.replace("traced.", "").replace("pipeline.", "")
            if counter not in eng.pipeline_stats:
                missing.append((name, key))
    assert not missing
    st = eng.pipeline_stats
    assert st["sparse_rows_selected"] > 0 and st["state_bytes_live"] > 0 \
        and st["sparse_prefill_blocks_visited"] > 0


OBS = {"pipeline": {
    "sparse_rows_selected": 2 * 4096.0 * 96 * 256,
    "sparse_rows_live": 2 * 24576.0 * 96 * 256,
    "sparse_prefill_blocks_selected": 1000.0,
    "sparse_prefill_blocks_visited": 3500.0,
    "sparse_select_queries": 8000, "sparse_select_kernel_queries": 8000,
    "prefill_tokens_real": 61437, "prefill_tokens_planned": 81920,
    "fused_dispatch_s": 0.030, "fused_apply_s": 0.012,
    "put_s": 17.4, "decode_batch_s": 22.6,
    "state_bytes_live": 1_000_000, "kv_bytes_live": 3_000_000,
    "latent_bytes_live": 0},
    "rounds": 6, "window_s": 40.0,
    "memory_peak_bytes": 12.53e9,
    "trace": {"window_s": 6.7, "idle_s": 0.0335, "busy_s": 6.6665}}


@pytest.mark.parametrize("name, want", [
    ("sparse_read_share.sala", 100 * 4096 / 24576),
    ("sparse_prefill_visit_ratio.sala", 3.5),
    ("sparse_select_kernel_share.sala", 100.0),
    ("state_cache_share.rollout", 25.0),
    ("prefill_useful_share.rollout", 100 * 61437 / 81920),
    ("fused_host_ms_per_round.rollout", 7.0),
    ("refill_call_share.rollout", 43.5),
    ("device_idle_share.rollout", 0.5),
    ("peak_hbm_gb.rollout", 12.53)])
def test_counter_readers(name, want):
    assert name in NAMES
    assert readers.read(_spec(name), OBS) == pytest.approx(want)
    assert readers.read(_spec(name), {}) is None


def test_the_sparse_decode_cost_by_hand():
    """96 sequences x 2 kv heads x 64 blocks of 64 rows, K and V at 128
    lanes of bfloat16: 4.19 MB a sequence and layer, 0.40 GB a layer and
    step, 0.81 GB over the two layers whatever the context; a dense call
    at the mix's mean context would read 6 times that."""
    rows = 96 * 2 * 4096.0
    c = sparse_attn_cost.sparse_decode_attention_cost(rows, 2, 16, 128)
    assert c["bytes"] == 2 * rows * 2 * 128 * 2
    assert c["bytes"] == pytest.approx(0.8053e9, rel=1e-3)
    assert c["flops"] == 4 * rows * 2 * 16 * 128
    least = kernel_cost.roofline_seconds(c, PEAK)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(0.983e-3, rel=1e-2)
    dense = kernel_cost.paged_decode_attention_cost(
        96 * 24576.0, 32, 2, 128)
    assert 2 * dense["bytes"] / c["bytes"] == pytest.approx(6.0, rel=1e-2)
    # linear in the rows and the layers
    one = sparse_attn_cost.sparse_decode_attention_cost(rows / 2, 1, 16, 128)
    assert one["bytes"] * 4 == c["bytes"]


def test_the_sparse_prefill_cost_by_hand():
    """A refill chunk of 2,048 queries x 2 kv heads x 64 blocks x 2
    layers: 1.1 TFLOP, 5.6 ms at the bf16 peak; compute-bound."""
    blocks = 2048 * 2 * 64 * 2.0
    c = sparse_attn_cost.sparse_prefill_attention_cost(blocks, 64, 16, 128)
    assert c["flops"] == 4 * blocks * 64 * 16 * 128
    assert c["flops"] == pytest.approx(2.749e11, rel=1e-3)
    least = kernel_cost.roofline_seconds(c, PEAK)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(
        c["flops"] / PEAK["bf16_flops_per_s"])


def test_the_lightning_updates_cost_by_hand():
    """96 sequences x 32 heads of [128, 128] float32: 2.10 MB a sequence
    and layer each way, 0.40 GB a call: Nemotron's update at other
    numbers, the same function."""
    c = ssm_cost.mamba2_decode_cost(96, 32, 128, 128)
    state = 96 * 32 * 128 * 128
    assert c["bytes"] == 2 * state * 4 + 96 * 32 * (4 * 128 + 1) * 4
    assert c["bytes"] / 96 == pytest.approx(2 * 2.097e6 + 65.7e3, rel=1e-3)
    assert kernel_cost.roofline_seconds(c, PEAK)["seconds"] \
        == pytest.approx(0.4995e-3, rel=1e-2)


KERNELS = {
    "sparse_attn": "sparse_decode-bf16_96_32_256",
    "sparse_prefill": "sparse_prefill-bf16_4_18432_128",
    "linear_attn": "mamba2_decode_state_update-f32_97_32_128_128"}


@pytest.mark.parametrize("metric", sorted(KERNELS))
def test_roofline_readers_match_the_compiled_names_and_stay_under_100(metric):
    """A trace whose kernels took exactly twice their least time reads 50 %
    through each reader, by the counts the traced stretch itself reports:
    2 sparse and 6 Lightning layers x 256 steps, 32 refill steps."""
    rows = 96 * 2 * 4096.0 * 256
    blocks = 65536 * 2 * 64 * 2.0
    cost = {
        "sparse_attn": kernel_cost.roofline_seconds(
            sparse_attn_cost.sparse_decode_attention_cost(rows, 2, 16, 128),
            PEAK)["seconds"],
        "sparse_prefill": kernel_cost.roofline_seconds(
            sparse_attn_cost.sparse_prefill_attention_cost(
                blocks, 64, 16, 128), PEAK)["seconds"],
        "linear_attn": 6 * 256 * kernel_cost.roofline_seconds(
            ssm_cost.mamba2_decode_cost(96, 32, 128, 128),
            PEAK)["seconds"]}[metric]
    calls = {"sparse_attn": 2 * 256, "sparse_prefill": 2 * 32,
             "linear_attn": 6 * 256}[metric]
    name = KERNELS[metric]
    obs = {"peak": PEAK, "cell": load_json("cells", CELL + ".json"),
           "traced": {"pipeline": {
               "sparse_rows_selected": rows,
               "sparse_prefill_blocks_selected": blocks}},
           "trace": {"n_devices": 1, "busy_s": 10 * cost,
                     "ops": {name: 2 * cost, "fusion.1": 8 * cost},
                     "op_counts": {name: calls, "fusion.1": 5}}}
    # the Lightning layers' update is the state-space family's kernel
    roofline = {"linear_attn": "ssm_roofline.rollout"}.get(
        metric, metric + "_roofline.sala")
    got = readers.read(_spec(roofline), obs)
    assert got == pytest.approx(50.0, rel=1e-6)
    share = {"sparse_attn": "sparse_attn_share.sala",
             "linear_attn": "state_update_share.rollout"}.get(metric)
    if share:
        assert readers.read(_spec(share), obs) == pytest.approx(20.0)
    # another model's kernels are not matched: the dense decode kernel at
    # this geometry, Nemotron's state update
    other = dict(obs, trace=dict(obs["trace"], ops={
        "closed_call-bf16_96_32_256": 1.0,
        "mamba2_decode_state_update-f32_257_64_64_128": 1.0},
        op_counts={"closed_call-bf16_96_32_256": 1,
                   "mamba2_decode_state_update-f32_257_64_64_128": 1}))
    assert readers.read(_spec(roofline), other) is None
