"""The serve step programs, compiled for the TPU v5e in this process with
no chip attached (the TPU compiler, Mosaic included, ships with libtpu).

What only the chip's compiler can say about a structure: whether XLA
materializes anything of a KV-pool plane's size around the paged-attention
kernel. A compile that passes is not a chip run; no time comes from here.

This file holds the llama pool's programs, the flash kernels, OLMoE's loop
and the rollout's flush; the families with a state pool or a latent plane
are in ``test_tpu_compile_state.py``, the hundreds-of-clients cells in
``test_tpu_compile_clients.py``, the train steps in
``test_tpu_compile_train.py`` (a file is what tier-1 schedules, and the
whole was its heaviest), the topology and the readers of a compiled text in
``tpu_compile_common.py``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.analysis import serve_program_calls
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceConfig)
from tpu_compile_common import (
    _mosaic_call_names, _scoped_vmem,
    described_chips_programs_stay_out_of_the_cache, one_chip)

#: the serve-chat-steady pool's geometry (benchmark/cells): 388 + 1 blocks
#: of 256 tokens, rows of 2 kv heads x 128
BLOCK, BLOCKS, KV_HEADS, HEAD_DIM = 256, 388, 2, 128
SLOTS = (BLOCKS + 1) * BLOCK
PLANE = f"bf16[1,1,{SLOTS},{KV_HEADS * HEAD_DIM}]"


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


#: serve-offline-rollout's pool (benchmark/cells): 260 + 1 blocks of 640
#: tokens, two a sequence, rows of 2 kv heads x 128; 128 clients, a
#: 64-step ring
ROLLOUT_BLOCK, ROLLOUT_ROW = 640, 256


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


# --------------------------------------------------------------------------- #
# the train engine's ZeRO-3 step over the four chips of a v5e:2x2 (ISSUE 60)
# --------------------------------------------------------------------------- #


