"""The TRAIN engine's step programs compiled for the TPU v5e with no chip
attached (see ``test_tpu_compile.py``): ZeRO-3's step over the four chips
of a v5e:2x2 (ISSUE 60) and the sparse train cell's step on one (ISSUE 61)."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from tpu_compile_common import (
    _mosaic_call_names, _mosaic_grids, _xla_remats,
    described_chips_programs_stay_out_of_the_cache, one_chip)


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
    sliding layers and today's on the full one (THREE calls a layer:
    forward, dq, dk/dv; the layer's recompute keeps the forward's output
    and row sums, ISSUE 69, where ``flash_window_roofline.train`` still
    divides by four), and the experts' grouped products are there in both
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
    text = exe.as_text()
    # what the memory costs in the compiler's OWN recomputation: 23
    # instructions before ISSUE 69 at 14.35 GB, 33 with the five layers'
    # ``o`` and ``lse`` kept at 14.55 GB (four layers' shared ``up_proj``,
    # ``k_proj``, router product and ``lse`` column a second time). A
    # change that spends memory sees here what XLA buys back with it
    assert len(_xla_remats(text)) <= 33, _xla_remats(text)
    names = _mosaic_call_names(text)
    window = f"attn_w{full.sliding_window}"
    assert names.count(window) == 4 * 3 and names.count("attn") == 3 * 1
    assert sum(n.startswith("ragged-dot") for n in names) >= 4 * 9
    # ISSUE 64: a sparse layer's rows rejoin their tokens through the
    # combine kernel, in each branch of its ``cond``: forward, the layer's
    # recompute, and in the backward for the tokens' and the weights'
    # cotangents; the third forward the checkpointed branch runs inside the
    # backward keeps none (nobody reads its output)
    assert names.count("moe_combine") == 4 * 2 * 4
    # ISSUE 67: a layer's QK-norm and rotary code are ONE call a pass
    # (forward, the layer's recompute, backward), and nothing of float32
    # at the size of ``q`` is left under the projections' region
    assert names.count("qk_norm_rope") == 3 * 5
    assert not [line for line in text.splitlines()
                if "rg.attn_proj" in line
                and re.search(r"= f32\[%d,8192,32,128\]" % B, line)]
    plans = take_causal_plans()             # one a layer's call
    assert {(b, h) for b, h, _ in plans} == {(B, 32)}
    assert sorted((plan["edge"], plan["skipped"]) for _, _, plan in plans) \
        == [(0, 28)] + [(6, 43)] * 4
    # ISSUE 66: a sliding layer's three kernels walk 8 x 3 grid steps a q
    # head where each walked 8 x 8 (21 of them run a body either way); the
    # full layer's keep the square. And Mosaic took those grids: forward
    # and dq at 3 key blocks a query block, dk/dv at 3 query blocks a q
    # head of a KV head's 8
    assert sorted((plan["steps"], plan["steps_run"])
                  for _, _, plan in plans) == [(72, 63)] * 4 + [(192, 108)]
    assert sorted(_mosaic_grids(text, window)) \
        == [(B, 4, 8, 8 * 3)] * 4 + [(B, 32, 8, 3)] * 4 * 2
    assert sorted(_mosaic_grids(text, "attn")) \
        == [(B, 4, 8, 8 * 8)] + [(B, 32, 8, 8)] * 2


@pytest.mark.parametrize("S,n,M,rows,dtype", [
    (16384, 16, 2048, 32768, jnp.bfloat16),     # the cell's bound branch
    (16384, 16, 2048, 131072, jnp.bfloat16),    # its full branch
    (16384, 16, 128, 32768, jnp.float32),       # the weights' cotangent
    (16384, 128, 2048, 131072, jnp.bfloat16),   # a whole layer (moe/layer)
    (4096, 8, 4096, 8192, jnp.float32),         # mixtral's widths, float32
])
def test_the_combine_kernel_compiles_at_the_calls_it_takes(
        one_chip, S, n, M, rows, dtype):
    """``moe_combine`` (ISSUE 64) through Mosaic at the sparse train
    cell's sizes and at the other callers' widths: unaligned copies,
    float32 products at HIGHEST and the tiles' VMEM are refused here, not
    on the chip. The call keeps the default scoped VMEM (16 MB): its tiles
    follow the width."""
    from deepspeed_tpu.ops.kernels import moe_combine as mc
    assert mc.fits(S, n, M, rows, dtype)

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    exe = jax.jit(lambda ys, w, row, sizes: mc.moe_combine(
        ys, w, row, sizes, dtype)).trace(
            spec((rows, M), dtype), spec((S, n), jnp.float32),
            spec((S, n), jnp.int32), spec((n,), jnp.int32)).lower(
                lowering_platforms=("tpu",)).compile()
    text = exe.as_text()
    assert _mosaic_call_names(text) == ["moe_combine"]
    call, = (line for line in text.splitlines()
             if '"tpu_custom_call"' in line)
    used = re.search(
        r'"used_scoped_memory_configs":\[\{[^\]]*?"size":"(\d+)"', call)
    assert 1 << 20 < int(used.group(1)) <= 16 << 20


@pytest.mark.parametrize("rotate", [True, False], ids=["sliding", "full"])
def test_the_qk_norm_rope_kernel_compiles_at_the_cells_calls(one_chip,
                                                             rotate):
    """``qk_norm_rope`` (ISSUE 67) through Mosaic at the sparse train
    cell's two calls, a sliding layer's (with the table) and a full
    layer's (without): forward and backward are one call each, named so,
    rows ``[B, T, H * D]`` in and head-major ``[B, H, T, D]`` out (the
    backward the other way), a grid step 256 rows of ``q``'s 4,096
    bfloat16 lanes, batch innermost, under the default scoped VMEM (16
    MB)."""
    from deepspeed_tpu.ops.kernels import qk_norm_rope as qn
    B, T, H, KV, D = 2, 8192, 32, 4, 128
    assert qn.fits(T, H, D, jnp.bfloat16)

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def both(q, k, q_scale, k_scale, table, dq, dk):
        out, vjp = jax.vjp(
            lambda *a: qn._qk_norm_rope("pallas", 1e-5, *a, table),
            q, k, q_scale, k_scale)
        return out, vjp((dq, dk))

    q, k = spec((B, T, H * D), jnp.bfloat16), spec((B, T, KV * D),
                                                   jnp.bfloat16)
    dq, dk = spec((B, H, T, D), jnp.bfloat16), spec((B, KV, T, D),
                                                    jnp.bfloat16)
    scale = spec((D,), jnp.float32)
    table = (spec((T, D), jnp.float32),) * 2 if rotate else None
    text = jax.jit(both).trace(q, k, scale, scale, table, dq, dk).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert _mosaic_call_names(text) == ["qk_norm_rope"] * 2
    assert _mosaic_grids(text, "qk_norm_rope") == [(T // 256, B)] * 2
    used = [int(re.search(
        r'"used_scoped_memory_configs":\[\{[^\]]*?"size":"(\d+)"',
        line).group(1)) for line in text.splitlines()
        if '"tpu_custom_call"' in line]
    assert len(used) == 2 and all(1 << 20 < n <= 16 << 20 for n in used)
