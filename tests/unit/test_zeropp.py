"""ZeRO++ (qwZ/qgZ/hpZ) and MiCS — reference parity: tests/unit/runtime/zero/
test_zeropp.py (hpZ/qwZ/qgZ train steps) and runtime/zero/mics.py behavior."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu as dstpu
from deepspeed_tpu.config.config import Config
from deepspeed_tpu.models.gpt2 import GPT2Config, make_model
from deepspeed_tpu.parallel.topology import build_mesh
from deepspeed_tpu.config.config import MeshConfig
from deepspeed_tpu.runtime.zero.quantized_collectives import (
    _make_param_gather, _make_replicated_prep, shard_map, strip_to_manual)
from deepspeed_tpu.runtime.zero.sharding import ZeroShardingPlan


def _gpt2_setup(seed=0):
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    model, init_fn, loss_fn = make_model(cfg)
    params = init_fn(jax.random.PRNGKey(seed), batch_size=2, seq_len=16)
    return loss_fn, params


def _engine(loss_fn, params, zero_extra=None, seed=7):
    # threshold 0: the tiny model's params are all <100k, so the default
    # persistence threshold would leave everything replicated and the
    # quantized gather path untested
    zopt = {"stage": 3, "stage3_param_persistence_threshold": 0}
    zopt.update(zero_extra or {})
    engine, _, _, _ = dstpu.initialize(
        loss_fn=loss_fn, params=params, config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
            "zero_optimization": zopt,
            "seed": seed,
        })
    return engine


def _batches(n, steps=5, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(steps):
        starts = rng.integers(0, 64, size=(n,))
        seq = (starts[:, None] + np.arange(17)[None, :]) % 64
        yield {"tokens": jnp.asarray(seq, jnp.int32)}


class TestQuantizedCollectives:
    """Per-device collective building blocks inside shard_map."""

    def test_gather_roundtrip_and_grad(self, devices8):
        mesh = Mesh(np.array(devices8).reshape(8), axis_names=("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 32), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (16, 32), jnp.float32)
        spec = P("data", None)

        # quant tolerances are relative to the tensor's max magnitude
        # (per-row int8 scale => error up to absmax/254 per contribution)
        for wb, gb, fwd_rtol, bwd_rtol in [
            (8, None, 1e-2, 1e-6),   # qwZ only: exact reduce-scatter
            (None, 8, 1e-6, 3e-2),   # qgZ only: exact gather
            (8, 8, 1e-2, 3e-2),
            (4, None, 2e-1, 1e-6),
        ]:
            gather = _make_param_gather(0, ("data",), 8, wb, gb)

            def local(xl, wl):
                full = gather(xl)
                # per-rank objective; total = sum over ranks
                return ((full * wl) ** 2).sum() / 8.0

            loss_and_grad = shard_map(
                jax.value_and_grad(local), mesh,
                in_specs=(spec, P()), out_specs=(P(), spec),
                axis_names=("data",))
            _, g = jax.jit(loss_and_grad)(x, w)

            full = shard_map(gather, mesh, in_specs=(spec,), out_specs=P(),
                             axis_names=("data",))(x)
            fwd_err = float(jnp.abs(full - x).max())
            assert fwd_err < fwd_rtol * float(jnp.abs(x).max()) + 1e-6, (wb, gb)

            # reference grad computed on the dequantized forward value
            gref = jax.grad(lambda xv: ((xv * w) ** 2).sum())(full)
            bwd_err = float(jnp.abs(g - gref).max())
            assert bwd_err < bwd_rtol * float(jnp.abs(gref).max()) + 1e-5, (wb, gb)

    def test_replicated_prep_psum_grad(self, devices8):
        mesh = Mesh(np.array(devices8).reshape(8), axis_names=("data",))
        prep = _make_replicated_prep(("data",))
        x = jnp.ones((4,), jnp.float32)
        b = jnp.arange(8.0).reshape(8, 1) * jnp.ones((8, 4))

        def local(xl, bl):
            return (prep(xl) * bl).sum()

        g = shard_map(jax.grad(local), mesh,
                      in_specs=(P(), P("data")), out_specs=P(),
                      axis_names=("data",))(x, b)
        # grad = psum of per-rank b rows = column sums of b
        np.testing.assert_allclose(np.asarray(g), np.asarray(b.sum(0)), rtol=1e-6)

    def test_strip_to_manual(self):
        assert strip_to_manual(P("model", "data"), ("data",), 2) == P(None, "data")
        assert strip_to_manual(P(("seq", "data")), ("data",), 1) == P()
        assert strip_to_manual(None, ("data",), 3) == P()


class TestZeroPlusPlus:
    """Engine end-to-end with quantized collectives."""

    @pytest.mark.parametrize("zero_extra", [
        {"zero_quantized_weights": True},
        {"zero_quantized_gradients": True},
        {"zero_quantized_weights": True, "zero_quantized_gradients": True},
    ])
    def test_qwz_qgz_training_matches_baseline(self, devices8, zero_extra):
        loss_fn, params = _gpt2_setup()
        base = _engine(loss_fn, params)
        quant = _engine(loss_fn, params, zero_extra)

        base_losses, quant_losses = [], []
        for b in _batches(16, steps=5):
            base_losses.append(float(base.train_batch(b)))
        for b in _batches(16, steps=5):
            quant_losses.append(float(quant.train_batch(b)))

        # both must learn; int8 comm noise shifts losses only slightly
        assert quant_losses[-1] < quant_losses[0]
        assert abs(quant_losses[-1] - base_losses[-1]) < 0.25 * base_losses[-1]

    def test_stage2_falls_back(self, devices8):
        loss_fn, params = _gpt2_setup()
        engine = _engine(loss_fn, params,
                         {"stage": 2, "zero_quantized_weights": True})
        for b in _batches(16, steps=2):
            loss = float(engine.train_batch(b))
        assert np.isfinite(loss)


class TestHpzMics:
    def test_hpz_param_axes(self, devices8):
        topo = build_mesh(MeshConfig(data=8), inner_shard_size=2)
        assert topo.axis_size("data") == 4
        assert topo.axis_size("data_inner") == 2
        assert topo.dp_world_size == 8
        from deepspeed_tpu.config.config import ZeroConfig
        plan = ZeroShardingPlan(
            ZeroConfig(stage=3, zero_hpz_partition_size=2), topo)
        assert plan.param_axes == ("data_inner",)
        assert set(plan.zero_axes) == {"data", "data_inner"}
        # params shard 2-way (secondary partition), opt-state 8-way
        # (param must exceed stage3_param_persistence_threshold to shard)
        big = {"w": jnp.zeros((512, 256))}
        ps = plan.param_specs(big)["w"]
        assert any("data_inner" in ((e,) if isinstance(e, str) else tuple(e))
                   for e in ps if e is not None)
        assert not any(
            "data" in ((e,) if isinstance(e, str) else tuple(e))
            for e in ps if e is not None)
        os_ = plan.opt_state_specs(big)["w"]
        flat = [a for e in os_ if e is not None
                for a in ((e,) if isinstance(e, str) else tuple(e))]
        assert set(flat) == {"data", "data_inner"}

    def test_mics_all_inner(self, devices8):
        topo = build_mesh(MeshConfig(data=8), inner_shard_size=4)
        from deepspeed_tpu.config.config import ZeroConfig
        plan = ZeroShardingPlan(ZeroConfig(stage=3, mics_shard_size=4), topo)
        assert plan.param_axes == ("data_inner",)
        assert plan.zero_axes == ("data_inner",)
        assert plan.n_shards == 4

    @pytest.mark.parametrize("zero_extra", [
        {"zero_hpz_partition_size": 2},
        {"mics_shard_size": 2},
        {"stage": 1, "mics_shard_size": 4},
    ])
    def test_training_with_inner_sharding(self, devices8, zero_extra):
        loss_fn, params = _gpt2_setup()
        engine = _engine(loss_fn, params, zero_extra)
        losses = [float(engine.train_batch(b)) for b in _batches(16, steps=4)]
        assert losses[-1] < losses[0]
        assert all(np.isfinite(l) for l in losses)

    def test_hpz_matches_plain_stage3(self, devices8):
        loss_fn, params = _gpt2_setup()
        base = _engine(loss_fn, params)
        hpz = _engine(loss_fn, params, {"zero_hpz_partition_size": 2})
        for b in _batches(16, steps=3):
            bl = float(base.train_batch(b))
        for b in _batches(16, steps=3):
            hl = float(hpz.train_batch(b))
        # hpZ changes communication pattern, not math
        assert abs(bl - hl) < 1e-3 * max(1.0, abs(bl))


def test_fused_xent_inside_manual_seam(devices8):
    """xent_impl='fused' composed with the ZeRO++ manual shard_map seam:
    the loss path must detect the manual axes (abstract mesh) and run the
    kernel plainly on the per-rank shard instead of nesting a second
    shard_map over 'data'. Loss trajectory must track the chunked path."""
    from deepspeed_tpu.parallel import topology as topo_mod
    losses = {}
    for impl in ("chunked", "fused"):
        topo_mod._TOPOLOGY = None
        cfg = GPT2Config.tiny(dtype=jnp.float32, xent_impl=impl)
        model, init_fn, loss_fn = make_model(cfg)
        params = init_fn(jax.random.PRNGKey(0), batch_size=2, seq_len=16)
        engine = _engine(loss_fn, params,
                         {"zero_quantized_gradients": True})
        tr = [float(engine.train_batch(b)) for b in _batches(
            engine.config.train_batch_size)]
        losses[impl] = tr
        assert all(np.isfinite(tr))
        assert tr[-1] < tr[0]
    np.testing.assert_allclose(losses["chunked"], losses["fused"],
                               rtol=0.05)


# --------------------------------------------------------------------------- #
# plain stage 3 through the explicit seam (ISSUE 60)
# --------------------------------------------------------------------------- #


def _seam_engine(zero, mesh=None, inner=None, devices=None, dtype=jnp.float32,
                 bf16=False, seed=0, extra=None, model=None):
    """Tiny GPT-2 (remat ``qkv_out``, as the benchmark's cells run it) with
    a persistence threshold of 1000 elements: biases and norms stay
    replicated, ``c_attn`` / ``c_fc`` / attention ``c_proj`` shard on
    dimension 1, ``mlp/c_proj`` and the embeddings on dimension 0."""
    from deepspeed_tpu.parallel import topology as topo_mod
    topo_mod._TOPOLOGY = None
    cfg = GPT2Config.tiny(dtype=dtype, remat=True, remat_policy="qkv_out")
    _, init_fn, loss_fn = model or make_model(cfg)
    params = init_fn(jax.random.PRNGKey(seed), batch_size=2, seq_len=16)
    kw = {}
    if mesh or inner or devices:
        devices = devices or jax.devices()
        kw["topology"] = build_mesh(
            MeshConfig(**(mesh or {"data": len(devices)})), devices=devices,
            **({"inner_shard_size": inner} if inner else {}))
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "zero_optimization": dict(zero,
                                  stage3_param_persistence_threshold=1000),
        "seed": 7, **(extra or {})}
    if bf16:
        config["bf16"] = {"enabled": True}
    engine, _, _, _ = dstpu.initialize(loss_fn=loss_fn, params=params,
                                       config=config, **kw)
    return engine


def _step_jaxpr(engine):
    batch = {"tokens": jnp.zeros((engine.config.train_batch_size, 17),
                                 jnp.int32)}
    return str(engine._train_step.trace(engine.state, batch).jaxpr)


class TestPlainStage3Seam:
    @pytest.mark.parametrize("bf16,rtol", [(False, 1e-5), (True, 2e-2)])
    def test_follows_stage0(self, devices8, bf16, rtol):
        """Stage 3 through the seam is stage 0's arithmetic: the same
        losses to float32 rounding, and to bfloat16's when the compute
        (and so the gathers and the reduce-scatters) is bfloat16."""
        losses = {}
        for stage in (0, 3):
            engine = _seam_engine({"stage": stage}, bf16=bf16)
            losses[stage] = [float(engine.train_batch(b))
                             for b in _batches(16, steps=5)]
            specs = jax.tree_util.tree_map(
                lambda sh: sh.spec, engine._state_shardings.params)
        np.testing.assert_allclose(losses[3], losses[0], rtol=rtol)
        assert losses[3][-1] < losses[3][0]
        # the tree holds what the seam has to tell apart
        assert specs["h_0"]["attn"]["c_attn"]["kernel"] == P(None, "data")
        assert specs["h_0"]["mlp"]["c_proj"]["kernel"] == P("data", None)
        assert specs["h_0"]["mlp"]["c_fc"]["bias"] == P()
        # as the step's trace showed them: the kernels inside their
        # layers, the two embeddings in front of the model
        assert engine.step_stats["zero_manual_leaves"] == 5 * 8
        assert engine.step_stats["zero_held_leaves"] == 5 * 2
        assert engine.step_stats["zero_auto_leaves"] == 0

    def test_one_reduce_scatter_a_sharded_leaf_and_no_full_psum(self,
                                                                  devices8):
        import re
        engine = _seam_engine({"stage": 3})
        text = _step_jaxpr(engine)
        shapes = {"[%s]" % ",".join(map(str, p.shape))
                  for p, sh in zip(
                      jax.tree_util.tree_leaves(engine.state.params),
                      jax.tree_util.tree_leaves(
                          engine._state_shardings.params))
                  if sh.spec != P()}
        assert len(shapes) == 6       # 4 kernels a layer, wte, wpe
        assert len(re.findall(r"= reduce_scatter\[", text)) == 10
        summed = re.findall(r"f32(\[[\d,]*\]) = psum\[", text)
        assert summed and not shapes & set(summed), summed

    @pytest.mark.parametrize("bits,first", [(None, "convert_element_type"),
                                            (8, "all_gather")])
    def test_the_cast_follows_quantized_collectives_and_leads_plain_ones(
            self, devices8, bits, first):
        """float32 parameters under bfloat16 compute: plain stage 3 moves
        the compute dtype's bytes (cast, then gather), while qwZ quantizes
        the parameter's own float32 values (gather, then cast), as before
        the seam served plain stage 3."""
        from deepspeed_tpu.runtime.zero.quantized_collectives import (
            prep_params, shard_map)
        mesh = Mesh(np.array(devices8), axis_names=("data",))
        spec = {"w": P("data", None)}
        text = str(jax.make_jaxpr(shard_map(
            lambda p: prep_params(p, spec, ("data",), 8, bits, None,
                                  lambda x: x.astype(jnp.bfloat16)),
            mesh, in_specs=(spec,), out_specs={"w": P()},
            axis_names=("data",)))({"w": jnp.ones((64, 32), jnp.float32)}))
        cast = text.index("new_dtype=bfloat16")
        gather = text.index("all_gather[")
        assert (cast < gather) == (first == "convert_element_type"), text
        assert ("bf16[64,32] = all_gather" in text) == (bits is None)

    def test_a_layers_gathers_in_front_of_the_model_are_dead(self, devices8):
        """``prep_params`` gathers the whole tree; the kernels the blocks
        gather for themselves must not be gathered a third time: forward
        and recompute a block's kernel, once wte and wpe."""
        import collections
        import re
        engine = _seam_engine({"stage": 3})
        batch = {"tokens": jnp.zeros((engine.config.train_batch_size, 17),
                                     jnp.int32)}
        hlo = engine._train_step.lower(engine.state, batch).compile() \
            .as_text()
        gathered = collections.Counter(re.findall(
            r"= f32\[(\d+,\d+)\]\S* all-gather(?:-start)?\(", hlo))
        assert set(gathered) == {"64,192", "64,64", "64,256", "256,64",
                                 "128,64", "512,64"}, gathered
        assert gathered["128,64"] == gathered["512,64"] == 1
        assert all(n <= 2 * 2 for n in gathered.values()), gathered

    @pytest.mark.parametrize("zero,kw", [
        ({"stage": 0}, {}), ({"stage": 1}, {}), ({"stage": 2}, {}),
        ({"stage": 3}, {"devices": 1}),
        ({"stage": 3, "zero_hpz_partition_size": 2}, {"inner": 2}),
        ({"stage": 3, "mics_shard_size": 2}, {"inner": 2}),
        ({"stage": 3}, {"mesh": {"data": 4, "seq": 2}}),
    ], ids=["stage0", "stage1", "stage2", "stage3-one-device", "hpz", "mics",
            "seq-fused"])
    def test_the_other_paths_stay_declarative(self, devices8, zero, kw):
        """The seam is taken on what the plan and the mesh show; every
        other configuration keeps the step program it had (their jaxpr
        hashes against the parent commit are in CHANGES.md, PR 60)."""
        kw = dict(kw)
        if "devices" in kw:
            kw["devices"] = jax.devices()[:kw["devices"]]
        engine = _seam_engine(zero, **kw)
        text = _step_jaxpr(engine)
        assert "shard_map" not in text and "reduce_scatter" not in text
        engine.train_batch(next(_batches(engine.config.train_batch_size,
                                         steps=1)))
        assert engine.step_stats["zero_manual_leaves"] == 0
        assert engine.step_stats["zero_held_leaves"] == 0

    def test_a_model_whose_layers_gather_nothing_keeps_the_partitioner(
            self, devices8, caplog):
        """GPT-Neo builds its blocks without ``layer_class``: under the
        seam every gathered weight would live from its forward use to its
        backward one. The step's trace shows no layer taking its own, so
        the step is the declarative one (nothing of the seam is lowered),
        says so, and counts every sharded leaf as the partitioner's."""
        from deepspeed_tpu.models import gpt_neo
        model = gpt_neo.make_model(gpt_neo.GPTNeoConfig.tiny(
            dtype=jnp.float32))
        from deepspeed_tpu.utils.logging import logger
        losses = {}
        logger.addHandler(caplog.handler)   # the logger does not propagate
        try:
            for stage in (0, 3):
                engine = _seam_engine({"stage": stage}, model=model)
                losses[stage] = [float(engine.train_batch(b))
                                 for b in _batches(16, steps=3)]
        finally:
            logger.removeHandler(caplog.handler)
        np.testing.assert_allclose(losses[3], losses[0], rtol=1e-5)
        assert any("no layer of this model gathers its own" in r.getMessage()
                   and r.levelname == "WARNING" for r in caplog.records)
        sharded = sum(sh.spec != P() for sh in jax.tree_util.tree_leaves(
            engine._state_shardings.params))
        assert sharded and engine.step_stats == dict(
            engine.step_stats, zero_manual_leaves=0, zero_held_leaves=0,
            zero_auto_leaves=3 * sharded)
        batch = {"tokens": jnp.zeros((16, 17), jnp.int32)}
        lowered = engine._train_step.lower(engine.state, batch).as_text()
        assert "manual_computation" not in lowered \
            and "reduce_scatter" not in lowered

    def test_a_leaf_the_loss_function_changed_is_not_gathered_again(
            self, devices8):
        """Compression (here pruning of the MLPs, with a straight-through
        gradient) makes new values of the gathered leaves before the model
        sees them: a layer gathers again only what ARRIVES as the seam's
        own gathered value, so stage 3 with compression follows stage 0
        with compression, the pruned kernels gathered in front."""
        extra = {"compression_training": {"sparse_pruning": {
            "shared_parameters": {"enabled": True, "schedule_offset": 1,
                                  "dense_ratio": 0.5},
            "different_groups": {"g": {"params": {}, "modules": ["mlp"]}}}}}
        losses = {}
        for stage in (0, 3):
            engine = _seam_engine({"stage": stage}, extra=extra)
            assert engine._compression is not None
            losses[stage] = [float(engine.train_batch(b))
                             for b in _batches(16, steps=4)]
        np.testing.assert_allclose(losses[3], losses[0], rtol=1e-5)
        dense = _seam_engine({"stage": 3})
        assert not np.allclose(
            [float(dense.train_batch(b)) for b in _batches(16, steps=4)],
            losses[3], rtol=1e-3)         # the pruning was there to lose
        # the attention kernels inside their layers; the MLPs' and the
        # embeddings held
        assert engine.step_stats["zero_manual_leaves"] == 4 * 4
        assert engine.step_stats["zero_held_leaves"] == 4 * 6

    def test_another_family_gathers_in_its_layers_too(self, devices8):
        """Every model of the zoo builds its layers through
        ``_lm_utils.layer_class``: Llama under the seam follows stage 0,
        and no block kernel is gathered more than twice a layer (forward
        and recompute: the gather in front of the model is dead)."""
        import collections
        import re

        from deepspeed_tpu.models import llama
        from deepspeed_tpu.parallel import topology as topo_mod
        cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, remat=True)
        _, init_fn, loss_fn = llama.make_model(cfg)
        params = init_fn(jax.random.PRNGKey(0), batch_size=2, seq_len=16)
        losses = {}
        for stage in (0, 3):
            topo_mod._TOPOLOGY = None
            engine, _, _, _ = dstpu.initialize(
                loss_fn=loss_fn, params=params, config={
                    "train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
                    "zero_optimization": {
                        "stage": stage,
                        "stage3_param_persistence_threshold": 1000},
                    "seed": 7})
            losses[stage] = [float(engine.train_batch(b))
                             for b in _batches(16, steps=3)]
        np.testing.assert_allclose(losses[3], losses[0], rtol=1e-5)
        batch = {"tokens": jnp.zeros((16, 17), jnp.int32)}
        hlo = engine._train_step.lower(engine.state, batch).compile() \
            .as_text()
        gathered = collections.Counter(re.findall(
            r"= f32\[(\d+,\d+)\]\S* all-gather(?:-start)?\(", hlo))
        specs = jax.tree_util.tree_flatten_with_path(
            jax.tree_util.tree_map(lambda sh: sh.spec,
                                   engine._state_shardings.params))[0]
        in_layers = sum(spec != P() and "layer_" in str(path[0])
                        for path, spec in specs)
        outside = sum(spec != P() and "layer_" not in str(path[0])
                      for path, spec in specs)
        assert in_layers and outside
        assert 0 < sum(gathered.values()) <= 2 * in_layers + outside, gathered
