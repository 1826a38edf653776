"""The serve programs of the families with a state pool or a latent plane
(Solar-Open2, Kimi-Linear, openPangu): decode loop, flush, refill step and
the short convolution's decode step compiled for the TPU v5e with no chip
attached (see ``test_tpu_compile.py``)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import RaggedInferenceConfig
from tpu_compile_common import (
    _conv_pool_moves, _mosaic_call_names, _scoped_vmem,
    described_chips_programs_stay_out_of_the_cache, one_chip)


def test_solar2_decode_loop_keeps_one_copy_of_the_state(one_chip,
                                                        monkeypatch):
    """The fused decode loop of ``serve-solar2-rollout`` at the published
    widths (one period of four layers, 128 slots), from shapes alone: the
    recurrent state enters donated and comes back aliased, the three KDA
    layers update it through the in-place Mosaic call, no operation
    copies a state-shaped value and the loop's temporaries stay under one
    layer's plane of the state."""
    import json
    import os
    import re

    import deepspeed_tpu.ops.kernels as kernels
    from benchmark.model_types import solar_open2 as mt
    from deepspeed_tpu.inference.v2.kv_quant import KVPool
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    from deepspeed_tpu.ops.kernels import short_conv
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "solar-open2-250b.json")) as f:
        mcfg = mt.model_config(json.load(f))
    slots, block = 128, 640
    runner = LlamaRaggedRunner(mcfg, RaggedInferenceConfig(
        max_seqs=slots, chunk_size=512, block_size=block, num_blocks=260,
        max_blocks_per_seq=2, decode_loop_steps=64, dtype="bfloat16",
        attention_impl="paged_flash"))
    assert (runner.kv_layers, runner.state_spec["layers"]) == (1, 3)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: mt.init_params(mcfg, 0)))
    planes = spec((1, 2, 261 * block, 8 * 128), jnp.bfloat16)
    state = tuple(spec((slots + 1, 64, 128, 128), jnp.float32)
                  for _ in range(3))
    # a slot's [3, 24576] carried inputs as 576 rows of 128 lanes
    conv = spec((3, slots + 1, 576, 128), jnp.bfloat16)
    assert conv.shape == short_conv.pool_shape(3, slots + 1, 4, 3 * 64 * 128)
    i32 = functools.partial(spec, dtype=jnp.int32)
    f32 = functools.partial(spec, dtype=jnp.float32)
    exe = runner._decode_loop_ring.trace(
        params, KVPool(planes, None, None, None), (state, conv),
        i32((slots,)), i32((slots,)), i32((slots,)), i32((slots,)),
        i32((slots, 2)), i32((1,)), f32((1,)), i32((1,)), f32((1,)),
        i32((1, 1)), n=64, mode="greedy", cand=1, eos_id=-1,
        feed="self").lower(lowering_platforms=("tpu",)).compile()
    hlo = exe.as_text()
    mem = exe.memory_analysis()
    state_bytes = 3 * (slots + 1) * 64 * 128 * 128 * 4
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 3
    shaped = r"f32\[%d,64,128,128\]" % (slots + 1)
    made = re.findall(r"= %s\S* ([\w\-]+)\(" % shaped, hlo)
    assert set(made) <= {"parameter", "get-tuple-element"}, set(made)
    # the decode kernel of the softmax layer; a KDA layer's short
    # convolution in place on the pool of carried inputs, which no gather
    # or scatter of XLA's touches any more, and its state update
    from collections import Counter
    assert Counter(_mosaic_call_names(hlo)) == {
        "closed_call": 1, "short_conv_decode_step": 3,
        "kda_decode_state_update": 3, "grouped_ffn_decode": 4}
    assert len(re.findall(
        r"%short_conv_decode_step[\w\-.]* = \(bf16\[3,129,576,128\]",
        hlo)) == 3
    assert not _conv_pool_moves(hlo, 576)
    # and the grouped expert kernel once a layer, over 101 row tiles of
    # 16 where ragged-dot was handed all 1,024 routed rows three times
    from deepspeed_tpu.ops.kernels.grouped_ffn import ROW_TILE, visits_bound
    rows = slots * mcfg.experts_top_k
    assert "ragged-dot" not in hlo
    padded = visits_bound(rows, mcfg.held) * ROW_TILE
    assert len(re.findall(
        r"%%grouped_ffn_decode[\w\-.]* = bf16\[%d,4096\]" % padded,
        hlo)) >= 4


@pytest.mark.parametrize("S, W, dtype, bias", [
    (16, 24576, jnp.bfloat16, False),     # a per-step bucket: ONE grid step
    (24, 6144, jnp.bfloat16, True),       # rows no multiple of 16: 8 a step
    (512, 12288, jnp.bfloat16, False),    # the largest slot bucket
    (16, 1024, jnp.float32, False),       # a float32 pool: taps of 8 rows
], ids=["one-step", "eight-rows", "bucket-512", "float32-pool"])
def test_short_conv_decode_step_compiles_off_the_cells_shapes(
        one_chip, S, W, dtype, bias):
    """The in-place short convolution alone, at the shapes a per-step
    decode (``decode_pipelined``: slot buckets of 16 to 512 rows) hands it
    and the cells' fused loops do not: every shape ``decode_uses_kernel``
    admits has to compile for the v5e, with the pool aliased."""
    from deepspeed_tpu.ops.kernels import short_conv
    assert short_conv.decode_uses_kernel(S, W, dtype, backend="tpu")

    def spec(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = spec(short_conv.pool_shape(2, S + 1, 4, W), dtype)
    exe = jax.jit(short_conv.short_conv_decode_step, donate_argnums=0).trace(
        pool, spec(()), spec((S,)), spec((S, W), jnp.float32),
        spec((4, W), jnp.float32),
        spec((W,), jnp.float32) if bias else None, spec((S,), jnp.bool_),
        spec((S,), jnp.bool_)).lower(lowering_platforms=("tpu",)).compile()
    assert _mosaic_call_names(exe.as_text()) == ["short_conv_decode_step"]
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * (S + 1) * 3 * W \
        * jnp.dtype(dtype).itemsize
    assert mem.temp_size_in_bytes < S * W * 4


def test_kimi_decode_loop_runs_the_short_conv_in_place(one_chip,
                                                        monkeypatch):
    """The fused 128-step decode loop of ``serve-kimi-linear-rollout-long``
    at the published widths and the cell's pool, from shapes alone: six
    KDA layers, each its short convolution and its state update in place
    (the names and shapes the ``.kimi`` readers match unchanged beside
    the new call), two latent layers in the latent decode kernel, and no
    gather, scatter or copy of XLA's on the pool of carried inputs."""
    import json
    import os
    import re
    from collections import Counter

    import deepspeed_tpu.ops.kernels as kernels
    from benchmark.model_types import kimi_linear as mt
    from deepspeed_tpu.inference.v2.kv_quant import KVPool
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        mcfg = mt.model_config(json.load(f))
    with open(os.path.join(root, "benchmark", "cells",
                           "serve-kimi-linear-rollout-long.json")) as f:
        eng = json.load(f)["engine"]
    runner = LlamaRaggedRunner(mcfg, RaggedInferenceConfig(**eng))
    slots, block, blocks, maxb = (eng["max_seqs"], eng["block_size"],
                                  eng["num_blocks"],
                                  eng["max_blocks_per_seq"])
    assert runner.state_spec == {
        "kind": "kda", "layers": 6, "heads": 32, "d_v": 128, "d_k": 128,
        "taps": 4, "conv_width": 12288}

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: mt.init_params(mcfg, 0)))
    state = tuple(spec((slots + 1, 32, 128, 128), jnp.float32)
                  for _ in range(6))
    conv = spec((6, slots + 1, 288, 128), jnp.bfloat16)
    planes = spec((runner.kv_layers, runner.kv_planes, (blocks + 1) * block,
                   runner.kv_heads * runner.head_dim), jnp.bfloat16)
    f32 = functools.partial(spec, dtype=jnp.float32)
    hlo = runner._decode_loop_ring.trace(
        params, KVPool(planes, None, None, None), (state, conv),
        spec((slots,)), spec((slots,)), spec((slots,)), spec((slots,)),
        spec((slots, maxb)), spec((1,)), f32((1,)), spec((1,)), f32((1,)),
        spec((1, 1)), n=eng["decode_loop_steps"], mode="greedy", cand=1,
        eos_id=-1, feed="self").lower(
            lowering_platforms=("tpu",)).compile().as_text()
    assert Counter(_mosaic_call_names(hlo)) == {
        "short_conv_decode_step": 6, "kda_decode_state_update": 6,
        "grouped_ffn_decode": 7, "mla_decode_attention": 2}
    assert len(re.findall(
        r"%%short_conv_decode_step[\w\-.]* = \(bf16\[6,%d,288,128\]"
        % (slots + 1), hlo)) == 6
    assert len(re.findall(
        r"%%kda_decode_state_update[\w\-.]* = \(f32\[%d,32,128,128\]"
        % (slots + 1), hlo)) == 6
    assert not _conv_pool_moves(hlo, 288)


def test_pangu_decode_loop_and_flush_compile_over_the_latent_plane(
        one_chip, monkeypatch):
    """The fused decode loop and the flush of ``serve-pangu-rollout-long``
    (128 slots, one 640-lane latent plane a layer, 256-token blocks, 128
    steps a loop) at the published widths and one dense + one sparse
    layer, from shapes alone: one latent decode kernel a layer (a Mosaic
    call named ``mla_decode_attention``: Mosaic takes its DMAs and its
    VMEM), one grouped expert kernel for the sparse layer, and a flush
    that updates the donated one-plane pool IN PLACE: its temporaries stay
    under a tenth of the pool (the scatter over all layers at once, which
    the K/V pools keep, holds the pool twice more: 5.2 GB at this cell's
    3.15 GB pool, PERF.md PR 34)."""
    import dataclasses
    import re

    import deepspeed_tpu.ops.kernels as kernels
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    from deepspeed_tpu.models.pangu_ultra_moe import (PanguUltraMoE,
                                                      PanguUltraMoEConfig)
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, block, maxb, blocks, steps = 128, 256, 24, 1920, 128
    mcfg = PanguUltraMoEConfig(
        vocab_size=19200, max_seq_len=131072, num_layers=2, num_heads=128,
        num_kv_heads=1, hidden_size=7680, intermediate_size=2048,
        shared_expert_size=2048, num_experts=256, experts_top_k=8,
        experts_held=8, layer_kinds=("mla", "mla"),
        ffn_kinds=("dense", "moe"), rope_theta=25.6e6,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    icfg = RaggedInferenceConfig(
        max_seqs=slots, chunk_size=512, block_size=block, num_blocks=blocks,
        max_blocks_per_seq=maxb, decode_loop_steps=steps, dtype="bfloat16",
        attention_impl="paged_flash")
    runner = LlamaRaggedRunner(mcfg, icfg)
    assert (runner.kv_planes, runner.kv_heads, runner.head_dim) == (1, 1, 640)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype), jax.eval_shape(
            lambda k: PanguUltraMoE(mcfg).init(
                k, jnp.zeros((1, 8), jnp.int32))["params"],
            jax.random.PRNGKey(0)))
    pool = spec((2, 1, (blocks + 1) * block, 640), jnp.bfloat16)
    i32 = functools.partial(spec, dtype=jnp.int32)
    f32 = functools.partial(spec, dtype=jnp.float32)
    exe = runner._decode_loop_ring.trace(
        params, pool, None, None, i32((slots,)), i32((slots,)),
        i32((slots,)), i32((slots, maxb)), i32((1,)), f32((1,)), i32((1,)),
        f32((1,)), i32((1, 1)), n=steps, mode="greedy", cand=1, eos_id=-1,
        feed="self").lower(lowering_platforms=("tpu",)).compile()
    hlo = exe.as_text()
    assert len(re.findall(
        r"%mla_decode_attention[\w\-.]* = bf16\[128,128,512\]", hlo)) >= 2
    assert len(re.findall(
        r"%grouped_ffn_decode[\w\-.]* = bf16\[1136,7680\]", hlo)) >= 1
    # nothing of the pool's size is built beside it in the loop
    pool_bytes = 2 * (blocks + 1) * block * 640 * 2
    assert exe.memory_analysis().temp_size_in_bytes < pool_bytes // 2
    ring = spec((2, 1, slots, steps, 640), jnp.bfloat16)
    flush = runner._flush_ring.trace(
        pool, ring, i32((slots, maxb)), i32((slots,)),
        i32((slots,))).lower(lowering_platforms=("tpu",)).compile()
    mem = flush.memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 10


@pytest.mark.parametrize(
    "name, family, layers, heads, planes, row, block, blocks, maxb, "
    "experts", [
        ("solar-open2-250b", "solar_open2", 3, 64, (1, 2), 1024, 640, 260,
         2, (18880, 4096, 1280)),
        ("kimi-linear-48b-a3b", "kimi_linear", 6, 32, (2, 1), 640, 256,
         1920, 24, (20416, 2304, 1024)),
    ], ids=["solar2", "kimi"])
def test_refill_step_runs_the_chunk_kernel_of_the_delta_rule(
        one_chip, monkeypatch, name, family, layers, heads, planes, row,
        block, blocks, maxb, experts):
    """The [4, 512] prefill step of the two cells with recurrent layers,
    at their cut, from shapes alone: every KDA layer runs the Pallas chunk
    kernel under its own name (which the decode update's readers do not
    match), traced and lowered ONCE for all of them, and XLA's batched
    triangular solve is gone from the program. Its routed experts run the
    grouped kernel at a 64-row tile, under the name and padded row count
    the cells' refill lines are read by."""
    import importlib
    import json
    import os
    import re

    import deepspeed_tpu.ops.kernels as kernels
    from deepspeed_tpu.inference.v2.kv_quant import KVPool
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    from deepspeed_tpu.inference.v2.model_runner import RaggedBatch
    mt = importlib.import_module(f"benchmark.model_types.{family}")
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           name + ".json")) as f:
        mcfg = mt.model_config(json.load(f))
    slots = 128
    runner = LlamaRaggedRunner(mcfg, RaggedInferenceConfig(
        max_seqs=slots, chunk_size=512, block_size=block,
        num_blocks=blocks, max_blocks_per_seq=maxb, decode_loop_steps=64,
        dtype="bfloat16", attention_impl="paged_flash"))
    assert (runner.state_spec["layers"], runner.state_spec["heads"]) \
        == (layers, heads)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: mt.init_params(mcfg, 0)))
    state = tuple(spec((slots + 1, heads, 128, 128), jnp.float32)
                  for _ in range(layers))
    conv = spec((layers, slots + 1, 9 * heads, 128), jnp.bfloat16)
    lowered = runner._step_greedy.trace(
        params, KVPool(spec(planes + ((blocks + 1) * block, row),
                            jnp.bfloat16), None, state, conv),
        RaggedBatch(spec((4, 512)), spec((4,)), spec((4,)), spec((4, maxb)),
                    spec((4,)))).lower(lowering_platforms=("tpu",))
    # one lowering a KIND of kernel in the step, whatever the depth: the
    # chunk kernel's launcher is ONE function all the KDA layers call
    text = lowered.as_text()
    assert len(re.findall(r"func\.func private @kda_chunk_prefill\b",
                          text)) == 1
    assert text.count("stablehlo.custom_call @tpu_custom_call") <= 4
    assert "triangular_solve" not in text
    hlo = lowered.compile().as_text()
    names = _mosaic_call_names(hlo)
    assert names.count("kda_chunk_prefill") == layers, names
    # a prefill chunk keeps the decode step's two kernels off its path
    assert not any(re.match(r"^(kda_decode_state_update|short_conv)", n)
                   for n in names)
    assert "riangular" not in hlo
    from deepspeed_tpu.ops.kernels import grouped_ffn
    padded, width, inner = experts
    assert re.search(r"%%grouped_ffn_decode[\w\-.]* = bf16\[%d,%d\]"
                     % (padded, width), hlo) and "ragged-dot" not in hlo
    # at the refill's 64-row tile the call asks VMEM for a 128-row span
    tile = 64
    assert {a for a, _ in _scoped_vmem(hlo, "grouped_ffn_decode")} == {
        grouped_ffn.vmem_need(tile, padded // tile, width, inner, 2, True)}


def test_olmo_hybrid_loop_and_refill_step_compile_at_the_published_widths(
        one_chip, monkeypatch):
    """The fused 256-step decode loop and the [4, 512] refill step of
    ``serve-olmo-hybrid-rollout-long`` at the published widths, the
    cell's cut (8 layers) and the cell's pool (7.56 GB of 3,840-lane K/V
    rows beside 0.89 GB of state), from shapes alone. The loop: six
    delta-rule layers with ONE decay a head, each its short convolution
    (11,520 channels in a 12,288-wide pool) and its state update in place
    on a pool ``[65, 96, 5760]`` that tiles whole, two 30-head MHA layers
    in the paged decode kernel, nothing state-shaped copied. The refill
    step: the scalar-decay chunk kernel six times from ONE lowering, the
    BlockSpec attention kernel twice, and the pool written in windows the
    compiler's gather takes whole: at 256-row windows of 3,840 lanes it
    re-laid the pool (5.6 GB of temporaries, and no room for them)."""
    import json
    import os
    import re
    from collections import Counter

    import deepspeed_tpu.ops.kernels as kernels
    from benchmark.model_types import olmo_hybrid as mt
    from deepspeed_tpu.inference.v2.kv_quant import KVPool
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    from deepspeed_tpu.inference.v2.model_runner import RaggedBatch
    from deepspeed_tpu.ops.kernels import short_conv
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        mcfg = mt.model_config(json.load(f))
    with open(os.path.join(root, "benchmark", "cells",
                           "serve-olmo-hybrid-rollout-long.json")) as f:
        eng = json.load(f)["engine"]
    runner = LlamaRaggedRunner(mcfg, RaggedInferenceConfig(
        **eng, attention_impl="paged_flash"))
    slots, block, blocks, maxb, steps = (
        eng["max_seqs"], eng["block_size"], eng["num_blocks"],
        eng["max_blocks_per_seq"], eng["decode_loop_steps"])
    assert runner.state_spec == {
        "kind": "gdn", "layers": 6, "heads": 30, "d_v": 192, "d_k": 96,
        "taps": 4, "conv_width": 12288, "conv_channels": 11520,
        "state_shape": (96, 5760)}
    assert (runner.kv_layers, runner.kv_heads, runner.head_dim) \
        == (2, 30, 128)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: mt.init_params(mcfg, 0)))
    state = tuple(spec((slots + 1, 96, 5760), jnp.float32)
                  for _ in range(6))
    conv = spec(short_conv.pool_shape(6, slots + 1, 4, 12288), jnp.bfloat16)
    assert conv.shape == (6, slots + 1, 288, 128)
    planes = spec((2, 2, (blocks + 1) * block, 3840), jnp.bfloat16)
    f32 = functools.partial(spec, dtype=jnp.float32)
    exe = runner._decode_loop_ring.trace(
        params, KVPool(planes, None, None, None), (state, conv),
        spec((slots,)), spec((slots,)), spec((slots,)), spec((slots,)),
        spec((slots, maxb)), spec((1,)), f32((1,)), spec((1,)), f32((1,)),
        spec((1, 1)), n=steps, mode="greedy", cand=1, eos_id=-1,
        feed="self").lower(lowering_platforms=("tpu",)).compile()
    hlo = exe.as_text()
    assert Counter(_mosaic_call_names(hlo)) == {
        "short_conv_decode_step": 6, "gdn_decode_state_update": 6,
        "closed_call": 2}
    assert len(re.findall(
        r"%%gdn_decode_state_update[\w\-.]* = \(f32\[%d,96,5760\]"
        % (slots + 1), hlo)) == 6
    assert not _conv_pool_moves(hlo, 288)
    mem = exe.memory_analysis()
    state_bytes = 6 * (slots + 1) * 96 * 5760 * 4
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 6
    made = re.findall(r"= f32\[%d,96,5760\]\S* ([\w\-]+)\(" % (slots + 1),
                      hlo)
    assert set(made) <= {"parameter", "get-tuple-element"}, set(made)

    lowered = runner._step_greedy.trace(
        params, KVPool(planes, None, state, conv),
        RaggedBatch(spec((4, 512)), spec((4,)), spec((4,)), spec((4, maxb)),
                    spec((4,)))).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert len(re.findall(r"func\.func private @gdn_chunk_prefill\b",
                          text)) == 1
    assert "triangular_solve" not in text
    exe = lowered.compile()
    names = Counter(_mosaic_call_names(exe.as_text()))
    assert names["gdn_chunk_prefill"] == 6 and len(names) == 2, names
    assert sum(names.values()) == 8          # and the two attention layers
    # the pool is written in place: no temporary near the pool's size
    assert exe.memory_analysis().temp_size_in_bytes < 1 << 30


def test_jamba_loop_and_refill_step_compile_whole_at_the_published_widths(
        one_chip, monkeypatch):
    """The fused 128-step decode loop and the [4, 512] refill step of
    ``serve-jamba2-rollout-long`` at the published widths, ALL 28 layers
    and the cell's pools (2.4 GB of [16, 5120] float32 states and carried
    inputs for 257 slots beside 1.0 GB of 128-lane K/V rows), from shapes
    alone. The loop: 26 Mamba-1 layers, each its short convolution (5,120
    channels in a 6,144-wide pool) and its selective-scan update in place
    on a pool ``[257, 16, 5120]`` that tiles whole, two multi-query layers
    (20 query heads on ONE K/V head: rows of exactly 128 lanes) in the
    paged decode kernel, nothing state-shaped copied. The refill step:
    the chunk scan 26 times from ONE lowering, in place over the same
    pool, and the BlockSpec attention kernel twice at (20, 1, 128)."""
    import json
    import os
    import re
    from collections import Counter

    import deepspeed_tpu.ops.kernels as kernels
    from benchmark.model_types import jamba as mt
    from deepspeed_tpu.inference.v2.kv_quant import KVPool
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    from deepspeed_tpu.inference.v2.model_runner import RaggedBatch
    from deepspeed_tpu.ops.kernels import short_conv
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "jamba2-3b.json")) as f:
        mcfg = mt.model_config(json.load(f))
    with open(os.path.join(root, "benchmark", "cells",
                           "serve-jamba2-rollout-long.json")) as f:
        cell = json.load(f)
    eng = cell["engine"]
    runner = LlamaRaggedRunner(mcfg, RaggedInferenceConfig(
        **eng, attention_impl="paged_flash"))
    slots, block, blocks, maxb, steps = (
        eng["max_seqs"], eng["block_size"], eng["num_blocks"],
        eng["max_blocks_per_seq"], eng["decode_loop_steps"])
    assert runner.state_spec["state_shape"] == (16, 5120)
    assert (runner.kv_layers, runner.kv_heads, runner.head_dim,
            mcfg.num_heads) == (2, 1, 128, 20)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: mt.init_params(mcfg, 0)))
    pool = (slots + 1, 16, 5120)
    state = tuple(spec(pool, jnp.float32) for _ in range(26))
    conv = spec(short_conv.pool_shape(26, slots + 1, 4, 6144), jnp.bfloat16)
    planes = spec((2, 2, (blocks + 1) * block, 128), jnp.bfloat16)
    f32 = functools.partial(spec, dtype=jnp.float32)
    exe = runner._decode_loop_ring.trace(
        params, KVPool(planes, None, None, None), (state, conv),
        spec((slots,)), spec((slots,)), spec((slots,)), spec((slots,)),
        spec((slots, maxb)), spec((1,)), f32((1,)), spec((1,)), f32((1,)),
        spec((1, 1)), n=steps, mode="greedy", cand=1, eos_id=-1,
        feed="self").lower(lowering_platforms=("tpu",)).compile()
    hlo = exe.as_text()
    assert Counter(_mosaic_call_names(hlo)) == {
        "short_conv_decode_step": 26, "mamba1_decode_state_update": 26,
        "closed_call": 2}
    # the names the cell's ``kernels`` block gives the benchmark's readers
    shaped = "f32_%d_16_5120" % (slots + 1)
    assert cell["kernels"]["selective_scan"]["op"] \
        == "mamba1_decode_state_update-" + shaped
    assert cell["kernels"]["selective_scan_prefill"]["op"] \
        == "mamba1_chunk_scan-" + shaped
    assert len(re.findall(
        r"%%mamba1_decode_state_update[\w\-.]* = \(f32\[%d,16,5120\]"
        % (slots + 1), hlo)) == 26
    assert not _conv_pool_moves(hlo, 144)
    mem = exe.memory_analysis()
    state_bytes = 26 * (slots + 1) * 16 * 5120 * 4
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 8
    made = re.findall(r"= f32\[%d,16,5120\]\S* ([\w\-]+)\(" % (slots + 1),
                      hlo)
    assert set(made) <= {"parameter", "get-tuple-element"}, set(made)

    lowered = runner._step_greedy.trace(
        params, KVPool(planes, None, state, conv),
        RaggedBatch(spec((4, 512)), spec((4,)), spec((4,)), spec((4, maxb)),
                    spec((4,)))).lower(lowering_platforms=("tpu",))
    assert len(re.findall(r"func\.func private @mamba1_chunk_scan\b",
                          lowered.as_text())) == 1
    exe = lowered.compile()
    hlo = exe.as_text()
    names = Counter(_mosaic_call_names(hlo))
    assert names["mamba1_chunk_scan"] == 26 and len(names) == 2, names
    assert sum(names.values()) == 28         # and the two attention layers
    assert len(re.findall(
        r"%%mamba1_chunk_scan[\w\-.]* = \(f32\[%d,16,5120\]" % (slots + 1),
        hlo)) == 26
    # every pool is written in place: weights 6.06 GB + pools 3.45 GB of
    # arguments, and no temporary near a pool's size
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < 1 << 29
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 11 << 30
