"""The serve programs of the families whose cells serve hundreds of clients
(Nemotron-H, Mellum, MiniCPM-SALA, LFM2): fused loop, flush and refill step
compiled for the TPU v5e at the cell's client count with no chip attached
(see ``test_tpu_compile.py``)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2 import RaggedInferenceConfig
from tpu_compile_common import (
    _conv_pool_moves, _mosaic_call_names, _scoped_vmem,
    described_chips_programs_stay_out_of_the_cache, one_chip)


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
