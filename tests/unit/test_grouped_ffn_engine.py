"""The two counters the fused loop carries for the grouped expert kernel
(``ops/kernels/grouped_ffn.py``) and the prefill steps' token count, on toy
engines of the families that take it (the kernel interpreted on the CPU);
the kernel alone is in ``test_grouped_ffn.py``, its layout in
``test_grouped_ffn_layout.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.kernels import grouped_ffn as gf


def _closed_form(per_step_sizes):
    """(hit, reads) over a list of per-(step, layer) held group sizes."""
    hit = sum(int((s > 0).sum()) for s in per_step_sizes)
    reads = sum(int(gf.streams(jnp.asarray(s), gf.ROW_TILE).sum())
                for s in per_step_sizes)
    return hit, reads


def _spy_on_layouts(monkeypatch):
    """Record the held group sizes of every sparse layer a traced program
    runs, through ``jax.debug.callback`` (the routing the program itself
    computes is what the closed form is over)."""
    seen = []
    real = gf.group_layout

    def spying(eid, groups, *tiling):
        out = real(eid, groups, *tiling)
        jax.debug.callback(lambda s: seen.append(np.asarray(s)), out[3])
        return out
    monkeypatch.setattr(gf, "group_layout", spying)
    return seen


def _olmoe_engine():
    from tests.unit.test_olmoe import make_engine, tiny_cfg, tiny_params
    cfg = tiny_cfg(2)
    return make_engine(cfg, tiny_params(cfg)), 64


def _olmoe_hot_engine():
    """Twenty sequences whose router is silent (a zero gate: every row
    ties, and the top-k of a tie is the first k experts), so each step
    routes its 20+ rows to the same two experts: groups of two row tiles."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)
    from tests.unit.test_olmoe import tiny_cfg, tiny_params
    cfg = tiny_cfg(2)
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.zeros_like(leaf)
        if "'gate'" in jax.tree_util.keystr(path) else leaf,
        tiny_params(cfg))
    return InferenceEngineV2(cfg, params, RaggedInferenceConfig(
        max_seqs=24, chunk_size=8, block_size=8, num_blocks=96,
        max_blocks_per_seq=4, decode_loop_steps=4, dtype="float32")), 64


def _solar_engine():
    from benchmark.model_types import solar_open2 as mt
    from tests.unit.test_solar_open2 import engine, tiny
    cfg = tiny()
    return engine(cfg, mt.init_params(cfg, 3)), 512


def _dense_engine():
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)
    from deepspeed_tpu.models.llama import Llama, LlamaConfig
    cfg = LlamaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    params = Llama(cfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    return InferenceEngineV2(cfg, params, RaggedInferenceConfig(
        max_seqs=4, chunk_size=16, block_size=8, num_blocks=32,
        max_blocks_per_seq=8, decode_loop_steps=4, dtype="float32")), 64


@pytest.mark.parametrize("family", ["olmoe", "solar_open2", "dense",
                                    "olmoe-one-hot-pair"])
def test_fused_loop_counts_experts_hit_and_reads(family, monkeypatch):
    """After a ``decode_batch`` through the (interpreted) kernel,
    ``moe_experts_hit`` and ``moe_expert_reads`` are the closed form over
    the routing the program computed: held groups with a row, and the
    kernel's visits to them (streams of an expert's matrices: one a group
    within 128 rows, however many row tiles), summed over sparse layers
    and steps. A model with no routed expert leaves both at 0, and so
    does the ``ragged_dot`` path."""
    build = {"olmoe": _olmoe_engine, "solar_open2": _solar_engine,
             "dense": _dense_engine,
             "olmoe-one-hot-pair": _olmoe_hot_engine}[family]
    eng, vocab = build()
    rng = np.random.default_rng(2)
    uids = list(range(20 if family == "olmoe-one-hot-pair" else 3))
    prompts = [rng.integers(1, vocab, 5 + i % 3).tolist() for i in uids]
    first = eng.put(uids, prompts, _greedy=True)
    # the CPU default is ragged_dot: a loop on it counts nothing
    eng.decode_batch(uids, [first[u] for u in uids], 2)
    stats = eng.pipeline_stats
    assert stats["moe_experts_hit"] == stats["moe_expert_reads"] == 0
    if family == "dense":
        assert stats["moe_rows_routed"] == 0
        return
    routed_before = stats["moe_rows_routed"]
    assert routed_before > 0
    # steer the choice from the test, as test_tpu_compile steers the
    # backend: the program has no option for it
    monkeypatch.setattr(gf, "kernel_impl", lambda *a: "interpret")
    seen = _spy_on_layouts(monkeypatch)
    jax.clear_caches()
    eng2, _ = build()
    first = eng2.put(uids, prompts, _greedy=True)
    seen.clear()                       # the prefill steps are not counted
    toks = eng2.decode_batch(uids, [first[u] for u in uids], 4)
    jax.effects_barrier()
    stats = eng2.pipeline_stats
    layers = eng2.runner.model_cfg.num_layers
    assert len(seen) == 4 * layers
    assert (stats["moe_experts_hit"], stats["moe_expert_reads"]) \
        == _closed_form(seen)
    assert 0 < stats["moe_experts_hit"] <= stats["moe_expert_reads"]
    if family == "olmoe-one-hot-pair":
        # 20+ rows on each of two experts, two row tiles a group: one
        # stream each all the same
        assert all(sorted(s)[-2:] == [max(s)] * 2 and max(s) > gf.ROW_TILE
                   and sum(s) == 2 * max(s) for s in map(list, seen))
        assert stats["moe_expert_reads"] == stats["moe_experts_hit"] \
            == 2 * len(seen)
    # the same tokens as the ragged_dot loop decodes
    eng3, _ = build()
    monkeypatch.undo()
    jax.clear_caches()
    f3 = eng3.put(uids, prompts, _greedy=True)
    want = eng3.decode_batch(uids, [f3[u] for u in uids], 4)
    assert {u: list(map(int, t)) for u, t in toks.items()} \
        == {u: list(map(int, t)) for u, t in want.items()}


@pytest.mark.parametrize("family", ["olmoe", "solar_open2", "dense"])
def test_prefill_steps_count_tokens_through_the_kernel(family, monkeypatch):
    """``moe_prefill_tokens`` counts the real positions of every prefill
    step of a model with routed experts and ``moe_prefill_kernel_tokens``
    those of them in steps whose shape took the grouped kernel, by the
    choice the runner itself makes: none on the CPU, where the steps run
    ``ragged_dot``; all of them once the choice says so, and the steps
    then do run the kernel and serve the same first tokens. A dense
    model counts neither."""
    build = {"olmoe": _olmoe_engine, "solar_open2": _solar_engine,
             "dense": _dense_engine}[family]
    eng, vocab = build()
    rng = np.random.default_rng(3)
    uids = [0, 1, 2]
    prompts = [rng.integers(1, vocab, 5 + i).tolist() for i in uids]
    want = eng.put(uids, prompts, _greedy=True)
    stats = eng.pipeline_stats
    real = sum(map(len, prompts))
    assert stats["prefill_tokens_real"] == real
    assert stats["moe_prefill_kernel_tokens"] == 0
    assert stats["moe_prefill_tokens"] == (0 if family == "dense" else real)
    # a one-token step is a decode step: not a prefill token
    eng.put(uids, [[want[u]] for u in uids], _greedy=True)
    assert eng.pipeline_stats["moe_prefill_tokens"] \
        == stats["moe_prefill_tokens"]
    if family == "dense":
        return
    monkeypatch.setattr(gf, "kernel_impl", lambda *a: "interpret")
    seen = _spy_on_layouts(monkeypatch)
    jax.clear_caches()
    eng2, _ = build()
    got = eng2.put(uids, prompts, _greedy=True)
    jax.effects_barrier()
    stats = eng2.pipeline_stats
    assert stats["moe_prefill_tokens"] \
        == stats["moe_prefill_kernel_tokens"] == real
    # the steps the counter spoke for went through the kernel's layout
    assert len(seen) == stats["prefill_steps"] \
        * eng2.runner.model_cfg.num_layers > 0
    assert got == want
    monkeypatch.undo()
    jax.clear_caches()
