"""The serve step programs, compiled for the TPU v5e in this process with
no chip attached (the TPU compiler, Mosaic included, ships with libtpu).

What only the chip's compiler can say about a structure: whether XLA
materializes anything of a KV-pool plane's size around the paged-attention
kernel. A compile that passes is not a chip run; no time comes from here.

The topology is described inside a fixture, never at import: one process
at a time may load libtpu, and xdist workers import every test file.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.analysis import serve_program_calls
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceConfig)

#: the serve-chat-steady pool's geometry (benchmark/cells): 388 + 1 blocks
#: of 256 tokens, rows of 2 kv heads x 128
BLOCK, BLOCKS, KV_HEADS, HEAD_DIM = 256, 388, 2, 128
SLOTS = (BLOCKS + 1) * BLOCK
PLANE = f"bf16[1,1,{SLOTS},{KV_HEADS * HEAD_DIM}]"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def engine():
    """Two llama layers at the cell's KV row width, on the paged layout.
    Built on the CPU over a pool of a few blocks; the programs are lowered
    below against the cell's pool shape."""
    from deepspeed_tpu.models.llama import Llama, LlamaConfig
    mcfg = LlamaConfig.tiny(
        hidden_size=512, num_heads=4, num_kv_heads=KV_HEADS,
        intermediate_size=1024, max_seq_len=2048, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, attention_impl="xla")
    assert mcfg.head_dim == HEAD_DIM
    params = Llama(mcfg).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32))["params"]
    return InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
        max_seqs=16, chunk_size=256, block_size=BLOCK, num_blocks=12,
        max_blocks_per_seq=6, dtype="bfloat16", decode_loop_steps=8,
        attention_impl="paged_flash"))


def _mosaic_call_names(hlo):
    """Names of the compiled text's Mosaic calls, XLA's numbering cut."""
    import re
    return [re.sub(r"\.\d+$", "", name) for name in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)]


def _scoped_vmem(hlo, name):
    """(asked, used) bytes of scoped VMEM of every Mosaic call ``name`` in
    the compiled text: the call's ``vmem_limit_bytes`` and what Mosaic
    laid out under it."""
    import re
    size = r'scoped_memory_configs":\[\{[^\]]*?"size":"(\d+)"'
    calls = [line for line in hlo.splitlines() if re.match(
        r"\s*(ROOT )?%%%s[\w\-.]* = [^\n]*\"tpu_custom_call\"" % name, line)]
    return [(int(re.search('"' + size, line).group(1)),
             int(re.search('"used_' + size, line).group(1)))
            for line in calls]


def _conv_pool_moves(hlo, pool_rows):
    """XLA's gathers and scatters (and copies) of the pool of carried
    convolution inputs, whose slots are ``pool_rows`` rows of 128 lanes, in
    the compiled text."""
    import re
    shaped = r"bf16\[\d+,\d+,%d,128\]" % pool_rows
    return [line.strip()[:120] for line in hlo.splitlines()
            if re.search(shaped, line)
            and re.search(r" (gather|scatter|copy)\(", line)]


def test_the_flash_kernels_keep_the_name_their_roofline_reader_matches(
        one_chip, monkeypatch):
    """``flash_attn_roofline.train`` matches ``attn-bf16_<B>_<H>_<T>_<D>``:
    the flash calls name themselves ``attn`` (their launchers are inner
    jits, whose own names an unnamed call would take) under the
    ``attn_core`` region. Forward, backward and the recompute, from shapes
    alone."""
    import deepspeed_tpu.ops.kernels as kernels
    from deepspeed_tpu.models.gpt2 import CausalSelfAttention, GPT2Config
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    cfg = GPT2Config(num_heads=4, hidden_size=512, attention_impl="flash",
                     dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    attn = CausalSelfAttention(cfg, name="attn")
    x = jnp.zeros((2, 1024, 512), jnp.bfloat16)
    params = jax.eval_shape(attn.init, jax.random.PRNGKey(0), x)

    def loss(params, x):
        y = jax.checkpoint(lambda p, x: attn.apply(p, x))(params, x)
        return y.astype(jnp.float32).sum()

    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (params, x))
    hlo = jax.jit(jax.grad(loss)).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    names = _mosaic_call_names(hlo)
    assert len(names) >= 3 and set(names) == {"attn"}, names
    assert "rg.attn_core" in hlo        # and the region is on its path


def test_the_flash_kernels_compile_at_the_train_cells_shape(one_chip,
                                                           monkeypatch):
    """``[2, 16, 2048, 128]`` at 1024-blocks, as both train cells run it
    (benchmark/configs/gpt-1p3b.json): each kernel carries an interior body
    and a diagonal body unrolled over its strips, and Mosaic still fits
    them in VMEM. Under ``jax.checkpoint`` a layer has exactly four calls,
    all named ``attn`` (forward, the forward again in the backward, dq,
    dk/dv): ``flash_attn_roofline.train`` divides by that count."""
    import deepspeed_tpu.ops.kernels as kernels
    from deepspeed_tpu.models.gpt2 import CausalSelfAttention, GPT2Config
    from deepspeed_tpu.ops.kernels.flash_attention import take_causal_plans
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    cfg = GPT2Config(num_heads=16, hidden_size=2048, attention_impl="flash",
                     flash_block_q=1024, flash_block_k=1024,
                     dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    attn = CausalSelfAttention(cfg, name="attn")
    x = jnp.zeros((2, 2048, 2048), jnp.bfloat16)
    params = jax.eval_shape(attn.init, jax.random.PRNGKey(0), x)

    def loss(params, x):
        y = jax.checkpoint(lambda p, x: attn.apply(p, x))(params, x)
        return (y.astype(jnp.float32) ** 2).sum()

    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (params, x))
    take_causal_plans()
    hlo = jax.jit(jax.value_and_grad(loss)).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert _mosaic_call_names(hlo) == ["attn"] * 4
    (b, h, plan), = take_causal_plans()
    assert (b, h) == (2, 16)
    assert (plan["interior"], plan["sub_tiled"], plan["general"]) == (1, 2, 0)


def test_unrolled_layers_lower_the_flash_kernels_once(one_chip):
    """The kernels' launchers are inner jits: a model's unrolled layers
    call them with the same shapes, so one program holds ONE lowered copy
    of each kernel call (the forward, its recompute under
    ``jax.checkpoint``, dq, dk/dv) whatever its depth, where every layer
    used to trace and lower its own four (8 s of a warm start at 24 layers
    with three bodies a kernel). Lowering only: no compile."""
    from deepspeed_tpu.ops.kernels.flash_attention import flash_attention

    def loss(q, k, v):
        for _ in range(3):
            q = jax.checkpoint(functools.partial(
                flash_attention, causal=True, interpret=False,
                block_q=256, block_k=256))(q, k, v)
        return q.astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((1, 512, 2, 128), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(x, x, x).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 4


@pytest.mark.parametrize(
    "program", ["step_greedy", "step_greedy_fb", "decode_loop"])
def test_no_pool_plane_is_materialized(one_chip, engine, program,
                                       monkeypatch):
    import deepspeed_tpu.ops.kernels as kernels
    # the kernels ask the default backend (the CPU here) whether to
    # interpret: steer that from the test, the program has no option for it
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    fn, args, static = serve_program_calls(engine, (program,))[program]
    pool = engine._kv_data

    def spec(x):
        shape = x.shape
        if x is pool:
            shape = shape[:2] + (SLOTS,) + shape[3:]
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=one_chip)

    args = jax.tree_util.tree_map(spec, args)
    exe = fn.trace(*args, **static).lower(
        lowering_platforms=("tpu",)).compile()
    hlo = exe.as_text()
    assert hlo.count("tpu_custom_call") >= engine.runner.num_layers
    # an unnamed Pallas call is named in a profile after its innermost
    # scope: the decode kernel keeps the name ``paged_attn_roofline.*``
    # match, whatever regions (telemetry/trace.py) are opened around it
    assert set(_mosaic_call_names(hlo)) == {"closed_call"}
    assert PLANE not in hlo, \
        f"{program}: XLA slices a K or V plane out of the pool"
    # and nothing else of that size is kept beside the (aliased) pool
    plane_bytes = SLOTS * KV_HEADS * HEAD_DIM * 2
    assert exe.memory_analysis().temp_size_in_bytes < plane_bytes


def test_olmoe_decode_loop_compiles_without_an_expert_by_rows_temporary(
        one_chip, monkeypatch):
    """The fused decode loop of ``serve-olmoe-rollout`` (32 slots, KV rows
    of 16 heads x 128 = 2048 lanes, two 640-token blocks a sequence, 64
    experts of width 1024 top-8) at the published widths and two layers,
    from shapes alone: the paged kernel takes the 2048-lane tiles, the
    routed experts' feed-forward is ONE Mosaic call a layer (the grouped
    kernel over 76 row tiles of 16: no ragged-dot is left at the decode
    shape), and nothing of [experts, rows, width] shape is built around
    it."""
    import re

    import deepspeed_tpu.ops.kernels as kernels
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    from deepspeed_tpu.models.mixtral import Mixtral, MixtralConfig
    from deepspeed_tpu.ops.kernels.grouped_ffn import ROW_TILE, visits_bound
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, block, layers, experts, top_k = 32, 640, 2, 64, 8
    mcfg = MixtralConfig(
        vocab_size=50304, max_seq_len=4096, num_layers=layers, num_heads=16,
        num_kv_heads=16, hidden_size=2048, intermediate_size=1024,
        num_experts=experts, experts_top_k=top_k, norm_topk_prob=False,
        qk_norm=True, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    icfg = RaggedInferenceConfig(
        max_seqs=slots, chunk_size=512, block_size=block, num_blocks=68,
        max_blocks_per_seq=2, decode_loop_steps=64, dtype="bfloat16",
        attention_impl="paged_flash")
    runner = LlamaRaggedRunner(mcfg, icfg)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, jnp.bfloat16), jax.eval_shape(
            lambda k: Mixtral(mcfg).init(
                {"params": k, "gating": k},
                jnp.zeros((1, 8), jnp.int32))["params"],
            jax.random.PRNGKey(0)))
    kv_row = mcfg.num_kv_heads * mcfg.head_dim
    pool = spec((layers, 2, 69 * block, kv_row), jnp.bfloat16)
    i32 = functools.partial(spec, dtype=jnp.int32)
    f32 = functools.partial(spec, dtype=jnp.float32)
    exe = runner._decode_loop_ring.trace(
        params, pool, None, None, i32((slots,)), i32((slots,)),
        i32((slots,)), i32((slots, 2)), i32((1,)), f32((1,)), i32((1,)),
        f32((1,)),
        i32((1, 1)), n=64, mode="greedy", cand=1, eos_id=-1,
        feed="self").lower(lowering_platforms=("tpu",)).compile()
    hlo = exe.as_text()
    rows = slots * top_k
    assert "ragged-dot" not in hlo
    padded = visits_bound(rows, experts) * ROW_TILE
    assert len(re.findall(
        r"%%grouped_ffn_decode[\w\-.]* = bf16\[%d,2048\]" % padded,
        hlo)) >= layers
    # one paged-attention kernel a layer beside them
    assert hlo.count("tpu_custom_call") >= 2 * layers
    wide = re.findall(r"(?:bf16|f32)\[%d,%d,(?:2048|1024)\]"
                      % (experts, rows), hlo)
    assert not wide, f"an [experts, rows, width] temporary: {set(wide)}"
    # and the loop's temporaries stay far under one expert stack
    assert exe.memory_analysis().temp_size_in_bytes \
        < experts * 2048 * 1024 * 2
    # the [4, 512] refill step, 256 rows an expert (the chip's ridge): the
    # same kernel at a 128-row tile under the 512-row span, the VMEM that
    # span's rows and sums need, and no ragged-dot
    from deepspeed_tpu.inference.v2.model_runner import RaggedBatch
    from deepspeed_tpu.ops.kernels import grouped_ffn
    hlo = runner._step_greedy.trace(
        params, pool, RaggedBatch(i32((4, 512)), i32((4,)), i32((4,)),
                                  i32((4, 2)))).lower(
                                      lowering_platforms=("tpu",)
                                  ).compile().as_text()
    assert "ragged-dot" not in hlo
    assert len(re.findall(
        r"%grouped_ffn_decode[\w\-.]* = bf16\[24448,2048\]", hlo)) == layers
    assert {a for a, _ in _scoped_vmem(hlo, "grouped_ffn_decode")} == {
        grouped_ffn.vmem_need(128, 191, 2048, 1024, 2, True, 512)}


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


#: serve-offline-rollout's pool (benchmark/cells): 260 + 1 blocks of 640
#: tokens, two a sequence, rows of 2 kv heads x 128; 128 clients, a
#: 64-step ring
ROLLOUT_BLOCK, ROLLOUT_ROW = 640, 256


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


def _rollout_runner(layers, clients):
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    from deepspeed_tpu.models.llama import LlamaConfig
    mcfg = LlamaConfig.tiny(
        hidden_size=512, num_heads=4, num_kv_heads=2, num_layers=layers,
        intermediate_size=1024, max_seq_len=2048, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, attention_impl="xla")
    return mcfg, LlamaRaggedRunner(mcfg, RaggedInferenceConfig(
        max_seqs=clients, chunk_size=512, block_size=ROLLOUT_BLOCK,
        num_blocks=2 * clients + 4, max_blocks_per_seq=2, dtype="bfloat16",
        decode_loop_steps=64, attention_impl="paged_flash"))


def _flush_at(runner, layers, clients, one_chip):
    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    slots = (2 * clients + 5) * ROLLOUT_BLOCK
    pool = spec((layers, 2, slots, ROLLOUT_ROW), jnp.bfloat16)
    ring = spec((64, layers, 2, clients, ROLLOUT_ROW), jnp.bfloat16)
    exe = runner._flush_ring.trace(
        pool, ring, spec((clients, 2)), spec((clients,)),
        spec((clients,))).lower(lowering_platforms=("tpu",)).compile()
    return exe, layers * 2 * slots * ROLLOUT_ROW * 2


def test_rollout_prefill_step_and_flush_store_rows_in_place(one_chip,
                                                            monkeypatch):
    """A [4, 512] prefill step and the ring's flush at
    ``serve-offline-rollout``'s pool geometry (two layers), from shapes
    alone: the donated pool comes back aliased, nothing of a pool plane's
    size is kept beside it and no operation copies the pool (the flush's
    scatter over all layers at once held it twice more: 0.0287 s a round
    and the refusal at 256 clients, PERF.md PR 36)."""
    import re

    import deepspeed_tpu.ops.kernels as kernels
    from deepspeed_tpu.inference.v2.model_runner import RaggedBatch
    from deepspeed_tpu.models.llama import Llama
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layers, clients = 2, 128
    mcfg, runner = _rollout_runner(layers, clients)
    flush, pool_bytes = _flush_at(runner, layers, clients, one_chip)
    plane_bytes = pool_bytes // (layers * 2)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype), jax.eval_shape(
            lambda k: Llama(mcfg).init(
                k, jnp.zeros((1, 8), jnp.int32))["params"],
            jax.random.PRNGKey(0)))
    pool = spec((layers, 2, pool_bytes // (layers * 2 * ROLLOUT_ROW * 2),
                 ROLLOUT_ROW), jnp.bfloat16)
    step = runner._step_greedy.trace(params, pool, RaggedBatch(
        spec((4, 512)), spec((4,)), spec((4,)), spec((4, 2)))).lower(
            lowering_platforms=("tpu",)).compile()
    for name, exe in (("flush", flush), ("prefill step", step)):
        mem = exe.memory_analysis()
        assert mem.alias_size_in_bytes >= pool_bytes, name
        assert mem.temp_size_in_bytes < plane_bytes, name
        copies = re.findall(r"= bf16\[%d,2,\d+,(?:\d+,)?%d\]\S* copy\("
                            % (layers, ROLLOUT_ROW), exe.as_text())
        assert not copies, f"{name}: the pool is copied: {copies[:2]}"


def test_flush_compiles_at_256_clients(one_chip):
    """The flush ISSUE 24's 256-client cell was halved for: a 9.4 GB pool
    (28 layers) and its 0.47 GB ring. The all-layers scatter was refused
    there ("Used 18.55G of 15.75G hbm"); the writer's flush compiles, the
    pool aliased and no temporary of a plane's size."""
    layers, clients = 28, 256
    _, runner = _rollout_runner(layers, clients)
    flush, pool_bytes = _flush_at(runner, layers, clients, one_chip)
    assert pool_bytes > 9.4e9
    mem = flush.memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // (layers * 2)


def test_nemotron_loop_and_refill_compile_at_256_clients(one_chip,
                                                         monkeypatch):
    """The fused 128-step decode loop and the [4, 512] refill step of
    ``serve-nemotron3-nano-rollout-long`` at the published widths and the
    cell's 256-client pool, from shapes alone: every Mamba-2 layer updates
    its OBLONG state through the in-place Mosaic call (whose name and
    output shape ``ssm_roofline.rollout`` matches through the cell's
    ``kernels.state_update.op``), the ungated experts
    of width 1856 (stored 1920) run in the grouped kernel and not in
    ``ragged-dot``, the softmax layers in the paged decode kernel at 16
    queries a kv head, the state enters donated and comes back aliased,
    and the refill step's SSD form is plain XLA."""
    import json
    import os
    import re
    from collections import Counter

    import deepspeed_tpu.ops.kernels as kernels
    from benchmark.model_types import nemotron_h as mt
    from deepspeed_tpu.inference.v2.kv_quant import KVPool
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    from deepspeed_tpu.inference.v2.model_runner import RaggedBatch
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        mcfg = mt.model_config(json.load(f))
    with open(os.path.join(root, "benchmark", "cells",
                           "serve-nemotron3-nano-rollout-long.json")) as f:
        eng = json.load(f)["engine"]
    runner = LlamaRaggedRunner(mcfg, RaggedInferenceConfig(**eng))
    slots, block, blocks, maxb = (eng["max_seqs"], eng["block_size"],
                                  eng["num_blocks"],
                                  eng["max_blocks_per_seq"])
    assert (slots, blocks) == (256, 3840)
    assert runner.state_spec == {
        "kind": "mamba2", "layers": 6, "heads": 64, "d_v": 64, "d_k": 128,
        "taps": 4, "conv_width": 6144}
    assert (runner.kv_layers, runner.kv_heads, runner.head_dim) \
        == (2, 2, 128)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: mt.init_params(mcfg, 0)))
    assert params["layer_1"]["moe"]["wi"].shape == (64, 2688, 1920)
    state = tuple(spec((slots + 1, 64, 64, 128), jnp.float32)
                  for _ in range(6))
    conv = spec((6, slots + 1, 144, 128), jnp.bfloat16)
    planes = spec((2, 2, (blocks + 1) * block, 256), jnp.bfloat16)
    f32 = functools.partial(spec, dtype=jnp.float32)
    exe = runner._decode_loop_ring.trace(
        params, KVPool(planes, None, None, None), (state, conv),
        spec((slots,)), spec((slots,)), spec((slots,)), spec((slots,)),
        spec((slots, maxb)), spec((1,)), f32((1,)), spec((1,)), f32((1,)),
        spec((1, 1)), n=128, mode="greedy", cand=1, eos_id=-1,
        feed="self").lower(lowering_platforms=("tpu",)).compile()
    hlo = exe.as_text()
    assert Counter(_mosaic_call_names(hlo)) == {
        "short_conv_decode_step": 6, "mamba2_decode_state_update": 6,
        "grouped_ffn_decode": 5, "closed_call": 2}
    assert "ragged-dot" not in hlo
    # the short convolution in place: XLA neither gathers nor scatters
    # (nor copies) the pool of carried inputs
    assert len(re.findall(
        r"%short_conv_decode_step[\w\-.]* = \(bf16\[6,257,144,128\]",
        hlo)) == 6
    assert not _conv_pool_moves(hlo, 144)
    # the names and shapes the .nemotron readers match
    assert len(re.findall(
        r"%mamba2_decode_state_update[\w\-.]* = \(f32\[257,64,64,128\]",
        hlo)) == 6
    assert len(re.findall(
        r"%grouped_ffn_decode[\w\-.]* = bf16\[2496,2688\]", hlo)) == 5
    assert len(re.findall(
        r"%closed_call[\w\-.]* = bf16\[256,32,256\]", hlo)) == 2
    # the grouped kernel with its span branches (operands of 16 to 128
    # rows) lies inside the VMEM its call asks for, and the asking counts
    # the tallest span's rows, not one tile's
    from deepspeed_tpu.ops.kernels import grouped_ffn
    # (the TPU compiler refuses a Mosaic call whose scratch and stack pass
    # its limit, so the loop's compile above is the first half of this;
    # inside a program XLA adds its own operand prefetches to the call's
    # "used" figure, so Mosaic's own is read from the call compiled alone)
    asked = grouped_ffn.vmem_need(16, 2496 // 16, 2688, 1920, 2, False)
    assert [a for a, _ in _scoped_vmem(hlo, "grouped_ffn_decode")] \
        == [asked] * 5
    assert asked - grouped_ffn.vmem_need(16, 1, 2688, 1920, 2, False) \
        == (128 - 16) * (2 * 2688 * 2 + (2 * 1920 + 2 * 2688) * 4)
    bf16 = functools.partial(spec, dtype=jnp.bfloat16)
    alone = jax.jit(functools.partial(
        grouped_ffn.grouped_ffn_decode, activation=jax.nn.relu)).trace(
            bf16((2496, 2688)), (spec((156,)),) * 3, spec((1,)),
            (bf16((64, 2688, 1920)), bf16((64, 1920, 2688)))).lower(
                lowering_platforms=("tpu",)).compile().as_text()
    (asked_alone, used), = _scoped_vmem(alone, "grouped_ffn_decode")
    assert asked_alone == asked and 0 < used <= asked
    mem = exe.memory_analysis()
    state_bytes = 6 * (slots + 1) * 64 * 64 * 128 * 4
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 6
    made = re.findall(r"= f32\[257,64,64,128\]\S* ([\w\-]+)\(", hlo)
    assert set(made) <= {"parameter", "get-tuple-element"}, set(made)
    # the refill step: experts at a 128-row tile in the same kernel (the
    # span cap: a visit is one tile there), the chunked SSD form without a
    # kernel of its own
    hlo = runner._step_greedy.trace(
        params, KVPool(planes, None, state, conv),
        RaggedBatch(spec((4, 512)), spec((4,)), spec((4,)), spec((4, maxb)),
                    spec((4,)))).lower(
                        lowering_platforms=("tpu",)).compile().as_text()
    names = Counter(_mosaic_call_names(hlo))
    assert names["grouped_ffn_decode"] == 5 and "ragged-dot" not in hlo
    assert not any(n.startswith(("mamba2", "short_conv")) for n in names), \
        names


def test_mellum_loop_flush_and_refill_compile_at_256_clients(one_chip,
                                                            monkeypatch):
    """The fused 128-step decode loop, its flush and the [4, 512] refill
    step of ``serve-mellum2-rollout-long`` at the published widths and the
    cell's 256-client pools, from shapes alone: all eight attention layers
    (two over the paged pool, six over the window pool of R = 6 blocks a
    slot) run the ONE decode kernel at 8 queries a kv head over a 512-lane
    row (the name and shape ``paged_attn_roofline.mellum2`` matches), the
    experts of 7 lane groups run in the grouped kernel at the shape
    ``grouped_moe_roofline.rollout`` matches through the cell's
    ``kernels.grouped_ffn.op``, the flush updates BOTH
    donated pools in place, and the refill step's attention calls trace
    under the two regions."""
    import json
    import os
    import re
    from collections import Counter

    import deepspeed_tpu.ops.kernels as kernels
    from benchmark.model_types import mellum as mt
    from deepspeed_tpu.inference.v2.kv_cache import window_blocks
    from deepspeed_tpu.inference.v2.kv_quant import KVPool
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    from deepspeed_tpu.inference.v2.model_runner import RaggedBatch
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "mellum2-12b-a2.5b.json")) as f:
        mcfg = mt.model_config(json.load(f))
    with open(os.path.join(root, "benchmark", "cells",
                           "serve-mellum2-rollout-long.json")) as f:
        cell = json.load(f)
    eng = cell["engine"]
    icfg = RaggedInferenceConfig(**eng)
    runner = LlamaRaggedRunner(mcfg, icfg)
    slots, block, blocks, maxb = (eng["max_seqs"], eng["block_size"],
                                  eng["num_blocks"],
                                  eng["max_blocks_per_seq"])
    assert (slots, blocks) == (256, 3840)
    assert runner.window_spec == {
        "layers": 6, "window": 1024,
        "ring_of": {False: (3, 7), True: (0, 1, 2, 4, 5, 6)}}
    assert (runner.kv_layers, runner.kv_heads, runner.head_dim) \
        == (2, 4, 128)
    R = window_blocks(1024, icfg)
    assert R == cell["pool"]["window_blocks_per_slot"] == 6

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: mt.init_params(mcfg, 0)))
    assert params["layer_1"]["moe"]["wi_gate"].shape == (32, 2304, 896)
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert abs(weights / 3.966e9 - 1) < 1e-3
    planes = spec((2, 2, (blocks + 1) * block, 512), jnp.bfloat16)
    window = spec((6, 2, (slots + 1) * R * block, 512), jnp.bfloat16)
    pools = 2 * (planes.size + window.size)
    assert window.size * 2 == cell["pool"]["window_pool_bytes"]
    kv = KVPool(planes, None, None, None, window)
    f32 = functools.partial(spec, dtype=jnp.float32)
    exe = runner._decode_loop_ring.trace(
        params, kv, None, spec((slots,)), spec((slots,)), spec((slots,)),
        spec((slots,)), spec((slots, maxb)), spec((1,)), f32((1,)),
        spec((1,)), f32((1,)), spec((1, 1)), n=128, mode="greedy", cand=1,
        eos_id=-1, feed="self").lower(lowering_platforms=("tpu",)).compile()
    hlo = exe.as_text()
    assert Counter(_mosaic_call_names(hlo)) == {
        "closed_call": 8, "grouped_ffn_decode": 8}
    assert "ragged-dot" not in hlo
    assert len(re.findall(
        r"%closed_call[\w\-.]* = bf16\[256,32,512\]", hlo)) == 8
    assert len(re.findall(
        r"%grouped_ffn_decode[\w\-.]* = bf16\[3040,2304\]", hlo)) == 8
    mem = exe.memory_analysis()
    ring = 128 * 8 * 2 * slots * 512 * 2
    assert mem.temp_size_in_bytes < ring // 2
    # weights + both pools + the ring + temporaries under the chip's 15.75
    assert weights + pools + ring + mem.temp_size_in_bytes < 14.0e9
    # the flush: both donated pools aliased; beside them the ring re-laid
    # once for its two loops and a layer's rows
    flush = runner._flush_ring.trace(
        kv, spec((128, 8, 2, slots, 512), jnp.bfloat16),
        spec((slots, maxb)), spec((slots,)), spec((slots,)),
        spec((slots,))).lower(lowering_platforms=("tpu",)).compile()
    mem = flush.memory_analysis()
    assert mem.alias_size_in_bytes == pools
    assert mem.temp_size_in_bytes < 2 * ring
    assert weights + pools + ring + mem.temp_size_in_bytes < 14.6e9
    # the refill step: the BlockSpec kernel a layer, six under the window
    # region; the experts at the ridge (256 rows an expert) in the grouped
    # kernel too, at a 128-row tile under the 512-row span: another shape
    # than the loop's call, which the cell's roofline reader matches
    step = runner._step_greedy.trace(
        params, kv, RaggedBatch(spec((4, 512)), spec((4,)), spec((4,)),
                                spec((4, maxb)), spec((4,)))).lower(
                                    lowering_platforms=("tpu",)).compile()
    hlo = step.as_text()
    names = Counter(_mosaic_call_names(hlo))
    assert names == {"rg.attn_window": 6, "rg.attn_core": 2,
                     "grouped_ffn_decode": 8}
    assert "ragged-dot" not in hlo
    assert len(re.findall(
        r"%grouped_ffn_decode[\w\-.]* = bf16\[20352,2304\]", hlo)) == 8
    from deepspeed_tpu.ops.kernels import grouped_ffn
    asked = grouped_ffn.vmem_need(128, 159, 2304, 896, 2, True, 512)
    assert {a for a, _ in _scoped_vmem(hlo, "grouped_ffn_decode")} \
        == {asked} and 20e6 < asked < 27e6
    assert step.memory_analysis().alias_size_in_bytes == pools


def test_sala_loop_flush_and_refill_compile_at_96_clients(one_chip,
                                                          monkeypatch):
    """The fused 256-step decode loop, its flush and the [4, 512] refill
    step of ``serve-minicpm-sala-rollout-32k`` at the published widths and
    the cell's pool (10,300 blocks, contexts to 40,960), from shapes alone:
    every block-selected layer holds BOTH decode kernels under a
    ``lax.cond`` each (the sparse one, named ``sparse_decode`` as
    ``sparse_attn_roofline.sala`` matches it, behind the selection's
    ``block_select``, and the paged pool's own for sequences below
    ``dense_len``), every Lightning layer updates its
    state through the in-place Mosaic call at [97, 32, 128, 128], the
    state enters donated and comes back aliased, no program copies a
    plane of the pool or of the compressed keys out (the temporaries stay
    under a plane's bytes), and the refill step holds the block-union
    kernel and the selection's beside the BlockSpec paged kernel."""
    import json
    import os
    import re
    from collections import Counter

    import deepspeed_tpu.ops.kernels as kernels
    from benchmark.model_types import minicpm_sala as mt
    from deepspeed_tpu.inference.v2.kv_quant import KVPool
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    from deepspeed_tpu.inference.v2.model_runner import RaggedBatch
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "minicpm-sala-9b.json")) as f:
        mcfg = mt.model_config(json.load(f))
    with open(os.path.join(root, "benchmark", "cells",
                           "serve-minicpm-sala-rollout-32k.json")) as f:
        eng = json.load(f)["engine"]
    runner = LlamaRaggedRunner(mcfg, RaggedInferenceConfig(**eng))
    slots, block, blocks, maxb = (eng["max_seqs"], eng["block_size"],
                                  eng["num_blocks"],
                                  eng["max_blocks_per_seq"])
    assert (slots, blocks, maxb) == (96, 10300, 160)
    assert runner.state_spec == {
        "kind": "lightning", "layers": 6, "heads": 32, "d_v": 128,
        "d_k": 128, "taps": 0, "conv_width": 0}
    assert runner.index_spec == {"layers": 2, "stride": 16,
                                 "pool_layers": (0, 1)}
    assert (runner.kv_layers, runner.kv_heads, runner.head_dim) \
        == (2, 2, 128)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: mt.init_params(mcfg, 0)))
    assert params["lm_head"]["kernel"].shape == (4096, 73448)
    rows = (blocks + 1) * block
    state = tuple(spec((slots + 1, 32, 128, 128), jnp.float32)
                  for _ in range(6))
    planes = spec((2, 2, rows, 256), jnp.bfloat16)
    index = spec((2, rows // 16, 256), jnp.bfloat16)
    counts = spec((2,))
    plane_bytes = rows * 256 * 2
    f32 = functools.partial(spec, dtype=jnp.float32)
    exe = runner._decode_loop_ring.trace(
        params, KVPool(planes, None, None, None, None, index, counts),
        (state, None), spec((slots,)), spec((slots,)), spec((slots,)),
        spec((slots,)), spec((slots, maxb)), spec((1,)), f32((1,)),
        spec((1,)), f32((1,)), spec((1, 1)), n=256, mode="greedy", cand=1,
        eos_id=-1, feed="self").lower(lowering_platforms=("tpu",)).compile()
    hlo = exe.as_text()
    assert Counter(_mosaic_call_names(hlo)) == {
        "mamba2_decode_state_update": 6, "sparse_decode": 2,
        "block_select": 2, "closed_call": 2}
    # the names and shapes the .sala readers match
    assert len(re.findall(
        r"%mamba2_decode_state_update[\w\-.]* = \(f32\[97,32,128,128\]",
        hlo)) == 6
    assert len(re.findall(
        r"%sparse_decode[\w\-.]* = bf16\[96,32,256\]", hlo)) == 2
    # the selection's scores are ``block_select``'s: no gathered plane and
    # no score a query head is left in the program
    big = (r"bf16\[(96,2560,256|15360,16,256)\]"
           r"|f32\[[\d,]*(32,2560|2,16,25(60|59))\]")
    assert not re.search(big, hlo), re.findall(big, hlo)[:4]
    mem = exe.memory_analysis()
    state_bytes = 6 * (slots + 1) * 32 * 128 * 128 * 4
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < plane_bytes
    made = re.findall(r"= f32\[97,32,128,128\]\S* ([\w\-]+)\(", hlo)
    assert set(made) <= {"parameter", "get-tuple-element"}, set(made)
    # the flush: the pool and the compressed keys updated in place
    ring = spec((256, 2, 2, slots, 256), jnp.bfloat16)
    flush = runner._flush_ring.trace(
        KVPool(planes, None, None, None, None, index, counts), ring,
        spec((slots, maxb)), spec((slots,)), spec((slots,))).lower(
            lowering_platforms=("tpu",)).compile()
    mem = flush.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * plane_bytes + 2 * plane_bytes // 16
    assert mem.temp_size_in_bytes < plane_bytes // 8
    # the refill step: the block-union kernel a sparse layer, beside the
    # paged pool's own for chunks below dense_len; the chunked recurrence
    # without a kernel of its own
    step = runner._step_greedy.trace(
        params, KVPool(planes, None, state, None, None, index, counts),
        RaggedBatch(spec((4, 512)), spec((4,)), spec((4,)), spec((4, maxb)),
                    spec((4,)))).lower(
                        lowering_platforms=("tpu",)).compile()
    hlo = step.as_text()
    names = Counter(_mosaic_call_names(hlo))
    assert names["sparse_prefill"] == 2 and names["block_select"] == 2 \
        and len(names) == 3, names
    assert len(re.findall(
        r"%sparse_prefill[\w\-.]* = bf16\[4,18432,128\]", hlo)) == 2
    assert not re.search(big, hlo), re.findall(big, hlo)[:4]
    assert step.memory_analysis().temp_size_in_bytes < plane_bytes


def test_lfm2_loop_flush_and_refill_compile_at_128_clients(one_chip,
                                                          monkeypatch):
    """The fused 128-step decode loop, its flush and the [4, 512] refill
    step of ``serve-lfm2-rollout-long`` at the published widths and the
    cell's 128-client pools, from shapes alone: the seven gated
    short-convolution layers run the ONE in-place convolution call at
    three taps over a pool that has NO state part (the name and shape the
    cell's ``kernels.short_conv.op`` states), the two attention layers
    run the decode kernel at heads of 64 lanes (four query heads a kv
    head over a 512-lane row: ``_decode_kernel``'s, not the BlockSpec
    kernel's), all 64 held experts of 12 lane groups run in the grouped
    kernel, and the weights are the configuration file's count."""
    import json
    import os
    import re
    from collections import Counter

    import deepspeed_tpu.ops.kernels as kernels
    from benchmark.model_types import lfm2_moe as mt
    from deepspeed_tpu.inference.v2.kv_quant import KVPool
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    from deepspeed_tpu.inference.v2.model_runner import RaggedBatch
    from deepspeed_tpu.models.lfm2 import param_counts
    from deepspeed_tpu.ops.kernels.short_conv import pool_shape
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2-24b-a2b.json")) as f:
        config = json.load(f)
    mcfg = mt.model_config(config)
    with open(os.path.join(root, "benchmark", "cells",
                           "serve-lfm2-rollout-long.json")) as f:
        cell = json.load(f)
    eng = cell["engine"]
    runner = LlamaRaggedRunner(mcfg, RaggedInferenceConfig(**eng))
    slots, block, blocks, maxb = (eng["max_seqs"], eng["block_size"],
                                  eng["num_blocks"],
                                  eng["max_blocks_per_seq"])
    assert (slots, blocks) == (128, 1920)
    assert runner.state_spec == {"kind": "conv", "layers": 7, "heads": 0,
                                 "taps": 3, "conv_width": 2048}
    assert (runner.kv_layers, runner.kv_heads, runner.head_dim) \
        == (2, 8, 64)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: mt.init_params(mcfg, 0)))
    assert params["layer_1"]["moe"]["wi_gate"].shape == (64, 2048, 1536)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n_params == param_counts(mcfg)[0] == config["parameters"]
    assert abs(n_params / 5.178e9 - 1) < 5e-3          # ISSUE 59's count
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    planes = spec((2, 2, (blocks + 1) * block, 512), jnp.bfloat16)
    conv = spec(pool_shape(7, slots + 1, 3, 2048), jnp.bfloat16)
    assert conv.shape == (7, 129, 32, 128)
    assert conv.size * 2 == cell["pool"]["state_pool_bytes"]
    kv = KVPool(planes, None, None, conv)
    f32 = functools.partial(spec, dtype=jnp.float32)
    exe = runner._decode_loop_ring.trace(
        params, kv._replace(conv=None), (None, conv), spec((slots,)),
        spec((slots,)), spec((slots,)), spec((slots,)), spec((slots, maxb)),
        spec((1,)), f32((1,)), spec((1,)), f32((1,)), spec((1, 1)), n=128,
        mode="greedy", cand=1, eos_id=-1,
        feed="self").lower(lowering_platforms=("tpu",)).compile()
    hlo = exe.as_text()
    assert Counter(_mosaic_call_names(hlo)) == {
        "closed_call": 2, "grouped_ffn_decode": 8,
        "short_conv_decode_step": 7}
    assert "ragged-dot" not in hlo
    # the decode kernel's output [slots, q heads, kv heads x head_dim]: 32
    # heads of 64 lanes over a 512-lane K/V row
    assert len(re.findall(
        r"%closed_call[\w\-.]* = bf16\[128,32,512\]", hlo)) == 2
    # the name the trace prints is the cell's own
    op = cell["kernels"]["short_conv"]["op"]
    assert op == "short_conv_decode_step-bf16_7_129_32_128"
    assert len(re.findall(
        r"%short_conv_decode_step[\w\-.]* = \(bf16\[7,129,32,128\]",
        hlo)) == 7
    assert not _conv_pool_moves(hlo, 129)
    mem = exe.memory_analysis()
    ring = 128 * 2 * 2 * slots * 512 * 2
    pools = 2 * (planes.size + conv.size)
    assert weights + pools + ring + mem.temp_size_in_bytes < 13.5e9
    flush = runner._flush_ring.trace(
        kv._replace(conv=None), spec((128, 2, 2, slots, 512), jnp.bfloat16),
        spec((slots, maxb)), spec((slots,)),
        spec((slots,))).lower(lowering_platforms=("tpu",)).compile()
    assert flush.memory_analysis().alias_size_in_bytes == 2 * planes.size
    # the refill step: the BlockSpec kernel an attention layer, the
    # convolution as gather / convolve / scatter (no decode call), the
    # experts in the grouped kernel at the 128-row tile
    step = runner._step_greedy.trace(
        params, kv, RaggedBatch(spec((4, 512)), spec((4,)), spec((4,)),
                                spec((4, maxb)), spec((4,)))).lower(
                                    lowering_platforms=("tpu",)).compile()
    hlo = step.as_text()
    assert Counter(_mosaic_call_names(hlo)) == {
        "rg.attn_core": 2, "grouped_ffn_decode": 8}
    assert "ragged-dot" not in hlo
    assert step.memory_analysis().alias_size_in_bytes == pools
    assert weights + pools + ring \
        + step.memory_analysis().temp_size_in_bytes < 15.0e9


# --------------------------------------------------------------------------- #
# the train engine's ZeRO-3 step over the four chips of a v5e:2x2 (ISSUE 60)
# --------------------------------------------------------------------------- #


def _collectives(hlo):
    """(kind, how, result elements) of every weight-sized (a million
    elements and more) collective the ENTRY computation of a scheduled
    TPU text runs. ``how``: ``sync`` an instruction of the entry
    computation itself (the TensorCore waits for it), ``kernel`` the TPU's
    fused all-reduce-scatter (a reduce-scatter: its result is a shard;
    synchronous as well), ``async`` inside an asynchronous collective
    fusion (counted once, at the fusion that starts it)."""
    import re
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            cur = "ENTRY" if head.group(1) else head.group(2)
            comps[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)

    def elements(text):
        return max((int(np.prod([int(d) for d in dims.split(",") if d]))
                    for dims in re.findall(r"\w+\[([\d,]*)\]", text)),
                   default=0)

    op = re.compile(r"= (.*?) (all-reduce|all-gather|reduce-scatter)"
                    r"(-start)?\(")
    inner = {name: [m for m in map(op.search, lines) if m]
             for name, lines in comps.items() if name != "ENTRY"}
    out = []
    for line in comps["ENTRY"]:
        m = op.search(line)
        if m:
            out.append((m.group(2), "async" if m.group(3) else "sync",
                        elements(m.group(1))))
            continue
        call = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
        for m in inner.get(call.group(1), []) if call else []:
            callee = call.group(1)
            if callee.startswith("all-reduce-scatter"):
                out.append(("reduce-scatter", "kernel",
                            elements(line.split(" fusion(")[0])))
            elif callee.startswith("async_collective_fusion"):
                out.append((m.group(2), "async", elements(m.group(1))))
    return [c for c in out if c[2] >= 1_000_000]


def test_the_zero3_step_compiles_with_its_collectives_written_out(
        monkeypatch):
    """Two layers of the cell's model (benchmark/configs/gpt-1p3b.json at
    a cut vocabulary) through the ENGINE's own stage-3 step builder, for
    the four chips of a v5e:2x2, micro-batch 2 x 2048 a chip: the flash
    kernels compile inside the seam (a Mosaic call refuses a context with
    an automatic axis), every sharded leaf's gradient is a reduce-scatter
    (no weight-sized all-reduce in the entry computation), and the count
    of SYNCHRONOUS weight-sized gathers and reduce-scatters is what
    PERF.md section 5 (PR 60) reads: a later change that folds an
    asynchronous one back fails here, with no chip."""
    import functools

    from jax.experimental import topologies
    from jax.sharding import NamedSharding

    import deepspeed_tpu as dstpu
    import deepspeed_tpu.ops.kernels as kernels
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.models.gpt2 import GPT2Config, make_model
    from deepspeed_tpu.runtime.engine import Engine
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda *a: 4)
    # the state stays where it was made: nothing can be put on a chip
    # that is only described
    monkeypatch.setattr(Engine, "_place_state", lambda self, state: state)

    layers = 2
    cfg = GPT2Config(vocab_size=8192, max_seq_len=2049, num_layers=layers,
                     num_heads=16, hidden_size=2048, mlp_ratio=4,
                     param_dtype=jnp.bfloat16, remat=True,
                     remat_policy="qkv_out", flash_block_q=1024,
                     flash_block_k=1024)
    _, init_fn, loss_fn = make_model(cfg)
    params = jax.jit(functools.partial(init_fn, batch_size=1, seq_len=64))(
        jax.random.PRNGKey(0))
    mesh = {"data": 4}
    engine, _, _, _ = dstpu.initialize(
        loss_fn=loss_fn, params=params,
        topology=dstpu.build_mesh(MeshConfig(**mesh), devices=topo.devices),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1, "bf16": {"enabled": True},
                "data_types": {"grad_accum_dtype": "bfloat16"},
                "gradient_clipping": 1.0, "steps_per_print": 1000000,
                "optimizer": {"type": "AdamW", "params": {
                    "lr": 3e-4, "moment_dtype": "bfloat16"}},
                "zero_optimization": {"stage": 3}, "mesh": mesh})
    state = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=s),
        engine.state, engine._state_shardings)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (engine.config.train_batch_size, 2049), jnp.int32,
        sharding=engine.topology.batch_sharding())}
    hlo = engine._train_step.trace(state, batch).lower(
        lowering_platforms=("tpu",)).compile().as_text()

    assert set(_mosaic_call_names(hlo)) == {"attn"}
    found = _collectives(hlo)
    kinds = {}
    for kind, how, _ in found:
        kinds[kind, how] = kinds.get((kind, how), 0) + 1
    # a gradient leaves as a shard: four kernels a layer and the token
    # embedding, none as an all-reduce of the leaf. The one all-reduce is
    # the position table's: 2049 rows are no whole number of sublane
    # tiles, and the compiler legalizes that reduce-scatter into an
    # all-reduce (8 MB a step, combined with the biases' and norms' psums)
    assert [c for c in found if c[0] == "all-reduce"] \
        == [("all-reduce", "sync", 2049 * 2048)], found
    assert kinds.get(("reduce-scatter", "sync"), 0) \
        + kinds.get(("reduce-scatter", "kernel"), 0) == 4 * layers + 1, kinds
    # the backward's re-gathers ride asynchronous fusions but for the
    # recompute's c_fc, the first weight a layer's backward needs (the
    # parent: three a layer in the backward and one in the forward); in
    # front of the model wte, wpe and the first layer's c_attn
    assert kinds.get(("all-gather", "sync"), 0) == layers + 3, kinds
    assert kinds.get(("all-gather", "async"), 0) >= 7 * layers - 1, kinds


def test_the_trinity_cells_train_step_compiles_under_the_chips_memory(
        monkeypatch):
    """The step of ``train-trinity-mini-8k-1chip`` as its job builds it
    (benchmark/configs/trinity-mini-26b-a3b.json at published widths: 5
    layers, 16 of 128 experts held, 1/8 of the vocabulary; micro-batch 2 x
    8,192, float32 master / moments / gradients, bf16 compute, remat a
    layer) through the ENGINE's own step builder, for one v5e chip: it fits
    under 15.75 GB, its flash calls are the WINDOW kernel on the four
    sliding layers and today's on the full one (four calls a layer:
    forward, its recompute, dq, dk/dv: what ``flash_window_roofline.train``
    divides by), and the experts' grouped products are there in both
    passes. The engine is built over a toy tree of the same STRUCTURE (a
    described chip holds no array) and its step traced at the real
    shapes."""
    import dataclasses
    import json
    import os

    from jax.experimental import topologies

    import deepspeed_tpu as dstpu
    import deepspeed_tpu.ops.kernels as kernels
    from benchmark.model_types import afmoe as mt
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.models.afmoe import make_model
    from deepspeed_tpu.ops.kernels.flash_attention import take_causal_plans
    from deepspeed_tpu.runtime.engine import Engine
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "trinity-mini-26b-a3b.json")) as f:
        full = mt.model_config(json.load(f), "float32")
    with open(os.path.join(root, "benchmark", "traffic",
                           "pretrain-8k-sparse.json")) as f:
        job = json.load(f)
    toy = dataclasses.replace(
        full, vocab_size=64, hidden_size=16, num_heads=2, num_kv_heads=1,
        attn_head_dim=8, intermediate_size=16, moe_intermediate_size=8,
        num_experts=8, experts_held=2, attention_impl="xla")
    params = make_model(toy)[1](jax.random.PRNGKey(0), 1, 8)
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    monkeypatch.setattr(Engine, "_place_state", lambda self, state: state)
    engine, _, _, _ = dstpu.initialize(
        loss_fn=make_model(full)[2], params=params,
        topology=dstpu.build_mesh(MeshConfig(**job["mesh"]),
                                  devices=topo.devices[:1]),
        config=dict(job["ds_config"], mesh=job["mesh"]))

    # every params-shaped subtree of the state (the master, the moments)
    # at the real shapes; whatever else it holds as it is
    real = jax.tree_util.tree_leaves(mt.param_shapes(full))
    toy_shapes = [p.shape for p in jax.tree_util.tree_leaves(params)]

    def at_real_shapes(sub):
        leaves, treedef = jax.tree_util.tree_flatten(sub)
        if [np.shape(x) for x in leaves] == toy_shapes:
            leaves = [jax.ShapeDtypeStruct(r.shape, x.dtype, sharding=one)
                      for r, x in zip(real, leaves)]
            return jax.tree_util.tree_unflatten(treedef, leaves)
        if isinstance(sub, dict):
            return {k: at_real_shapes(v) for k, v in sub.items()}
        if isinstance(sub, (tuple, list)) and not hasattr(sub, "shape"):
            vals = [at_real_shapes(v) for v in sub]
            return type(sub)(*vals) if hasattr(sub, "_fields") \
                else type(sub)(vals)
        return jax.ShapeDtypeStruct(np.shape(sub), sub.dtype, sharding=one)

    state = at_real_shapes(engine.state)
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(state.params))
    assert 705e6 < n < 706e6
    B = engine.config.train_batch_size
    batch = {"tokens": jax.ShapeDtypeStruct((B, full.max_seq_len), jnp.int32,
                                            sharding=one)}
    take_causal_plans()
    exe = engine._train_step.trace(state, batch).lower(
        lowering_platforms=("tpu",)).compile()
    mem = exe.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert 4e9 < total < 15.75e9, total
    names = _mosaic_call_names(exe.as_text())
    window = f"attn_w{full.sliding_window}"
    assert names.count(window) == 4 * 4 and names.count("attn") == 4 * 1
    assert sum(n.startswith("ragged-dot") for n in names) >= 4 * 9
    plans = take_causal_plans()             # one a layer's call
    assert {(b, h) for b, h, _ in plans} == {(B, 32)}
    assert sorted((plan["edge"], plan["skipped"]) for _, _, plan in plans) \
        == [(0, 28)] + [(6, 43)] * 4
