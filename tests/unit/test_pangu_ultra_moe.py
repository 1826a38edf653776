"""openPangu-Ultra-MoE (latent attention over a one-plane paged cache, a
dense layer before the sparse ones, sandwich norms, a share of the experts)
through the normal engine, at a small size on the CPU: hidden 64, 8 heads of
16 + 8 over a 128 + 8 lane latent row, 8 experts of which a share holds 4,
top-2, 3 layers (one dense, two sparse). Logits against the plain reference
(``benchmark/reference/pangu_ultra_moe.py``), the absorbed form against the
expanded one, the decode kernel against plain absorbed attention, the share
rule of the model-configs guide, the latent pool's shape and bytes, the MTP
module, the loader's names, and every refusal a latent cache makes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmark.model_types import pangu_ultra_moe as mt
from benchmark.reference import pangu_ultra_moe as reference
from deepspeed_tpu.inference.v2 import RaggedInferenceConfig
from deepspeed_tpu.inference.v2.model_runner import RaggedBatch
from deepspeed_tpu.models.pangu_ultra_moe import (PanguUltraMoE,
                                                  PanguUltraMoEConfig,
                                                  param_counts)
from deepspeed_tpu.models.registry import config_from_hf
from family_harness import prompt_of

CONFIG = "openpangu-ultra-moe-718b.json"


def tiny(**kw):
    kw.setdefault("experts_held", 4)
    kw.setdefault("experts_first", 2)
    return PanguUltraMoEConfig.tiny(dtype=jnp.float32,
                                    param_dtype=jnp.float32, **kw)


#: float32 engine against a float32 reference at highest precision: what
#: is left is the order of the sums (the absorbed products against the
#: expanded ones, the grouped matmul against the dense mask), a few 1e-6
#: on logits of size 4
FAMILY = H.Family(mt, tiny, tol=2e-4)
TOL = FAMILY.tol
engine = FAMILY.engine


@pytest.fixture(scope="module")
def model():
    return FAMILY.model()


# ------------------------- (a) engine vs reference ------------------------ #


@H.chunk_and_decode
def test_engine_logits_match_the_reference(model, chunk, decode):
    """A 37-token prompt (three 16-token latent blocks) prefilled in one
    chunk or in three, 8 tokens decoded through the fused loop (its ring,
    then the flush into the pool) or step by step, then one more position's
    logits: each against the reference's forward pass over the whole
    sequence (expanded attention, no cache)."""
    prompt = prompt_of(37)
    stats = FAMILY.serve_against_reference(model, chunk,
                                           decode).pipeline_stats
    assert stats["mla_prefill_tokens"] == len(prompt)
    # 8 decode steps (the fused loop counts the 37 rows settled at its
    # entry: its own ride the ring; a step alone its own row too), then
    # the one-token step that read the logits above over 46 rows
    live = (sum(range(38, 46)) if decode == "pipelined" else 8 * 37) + 46
    assert stats["latent_rows_live"] == live
    assert stats["latent_rows_fetched"] >= live
    assert stats["latent_bytes_live"] == live * 3 * (128 + 8) * 4
    # one pair of row counters a model: the K/V kernel never ran
    assert stats["decode_kv_rows_live"] == 0
    assert stats["decode_kv_rows_fetched"] == 0
    if decode == "fused":
        # 8 steps x 2 sparse layers x top-2, split with the other shares
        assert stats["moe_rows_routed"] + stats["moe_rows_elsewhere"] == 32
        assert stats["moe_rows_elsewhere"] > 0


def test_flax_model_and_runner_read_one_tree(model):
    FAMILY.flax_model_reads_the_runners_tree(PanguUltraMoE, model)


# ----------------------- (b) absorbed == expanded ------------------------ #


def test_absorbed_attention_is_the_expanded_attention(model):
    """The runner's mixer (W_UK in the query, W_UV on the output, scores
    and values over the cached latent rows) against the reference's
    per-head keys and values, on the same inputs: a 20-token chunk after
    nothing, then a second chunk over the rows the first left."""
    from deepspeed_tpu.inference.v2.kv_cache import BlockedKVCache
    from deepspeed_tpu.inference.v2.llama_runner import _mla_mixer
    cfg, params = model
    p = params["layer_1"]["attn"]
    icfg = RaggedInferenceConfig(max_seqs=2, chunk_size=32, block_size=16,
                                 num_blocks=8, max_blocks_per_seq=4,
                                 dtype="float32")
    pool = BlockedKVCache(icfg, 3, 1, cfg.latent_row, dtype=jnp.float32,
                          planes=1).pool
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 64))
    tables = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    outs = []
    with jax.default_matmul_precision("highest"):
        for lo, n in ((0, 20), (20, 20)):
            batch = RaggedBatch(jnp.zeros((2, 20), jnp.int32),
                                jnp.full((2,), lo, jnp.int32),
                                jnp.full((2,), n, jnp.int32), tables)
            pos = lo + jnp.broadcast_to(jnp.arange(20), (2, 20))
            pool, y = _mla_mixer(p, h[:, lo:lo + n], pool, 1, batch, cfg,
                                 icfg, pos, jnp.ones((2, 20), bool),
                                 jnp.float32)
            outs.append(y)
        dims = mt.reference_dims(cfg)
        want = reference._attention(
            p, h, **{k: dims[k] for k in ("num_heads", "nope", "rope",
                                          "v_dim", "rank", "rope_theta",
                                          "rms_eps")})
    assert float(jnp.abs(jnp.concatenate(outs, 1) - want).max()) < 1e-5
    # only plane 1 of the one-plane pool was written, 40 rows a sequence
    assert float(jnp.abs(pool[0]).max()) == 0.0
    assert int((jnp.abs(pool[1, 0]).max(-1) > 0).sum()) == 80


@pytest.mark.parametrize("ring", [False, True], ids=["pool", "pool+ring"])
def test_decode_kernel_is_plain_absorbed_attention(ring):
    """``mla_decode_attention`` (interpreted) against the gathered,
    masked softmax: scattered block tables, lengths at and around tile
    edges, an idle slot, and the fused loop's ring."""
    from deepspeed_tpu.ops.kernels.mla_attention import (
        decode_rows_fetched, mla_attention_reference, mla_decode_attention)
    rng = np.random.default_rng(0)
    S, H, W, LAT, bs, maxb, L, R = 8, 8, 256, 128, 128, 3, 2, 8
    nb = S * maxb
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    pool, q = f(L, 1, (nb + 1) * bs, W), f(S, H, W)
    tables = jnp.asarray(rng.permutation(nb).reshape(S, maxb), jnp.int32)
    lens = jnp.asarray([0, 1, 127, 128, 129, 300, 384, 200], jnp.int32)
    rc = 5 if ring else 0
    rk = f(L, 1, S, R, W) if ring else None
    got = mla_decode_attention(q, pool, rk, tables, lens,
                               jnp.asarray(rc), jnp.asarray([1, 1]),
                               block_size=bs, latent=LAT, sm_scale=0.1,
                               interpret=True)
    j = jnp.arange(maxb * bs)
    rows = pool[1, 0][tables[:, j // bs] * bs + j % bs]
    mask = j[None, None, :] < lens[:, None, None]
    if ring:
        rows = jnp.concatenate([rows, rk[1, 0]], 1)
        live = (jnp.arange(R) < rc)[None, None, :] \
            & (lens > 0)[:, None, None]
        mask = jnp.concatenate([mask, jnp.broadcast_to(live, (S, 1, R))], 2)
    want = mla_attention_reference(q[:, None], rows, mask, LAT, 0.1)[:, 0]
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert float(jnp.abs(got[0]).max()) == 0.0          # the idle slot
    assert [decode_rows_fetched(n, bs) for n in (0, 1, 128, 129)] \
        == [0, 128, 128, 256]


def test_engine_through_the_kernels_matches_the_reference(model):
    """The same engine with the Pallas paths forced (interpreted here):
    the prefill chunks through the paged kernel with the ONE plane as its
    K and its V operand, the decode steps through the latent decode
    kernel, per step and in the fused loop over its ring."""
    FAMILY.serve_through_the_kernels(model)


# ------------------------------ (c) shares ------------------------------- #


def test_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts of all the shares, plus the
    shared expert and the dense parts once, equal the uncut reference's
    layer: in the engine's sparse block and in the reference alike."""
    from deepspeed_tpu.inference.v2.llama_runner import _moe_mlp
    whole_cfg = tiny(experts_held=None, experts_first=0)
    whole = mt.init_params(whole_cfg, 11)["layer_1"]
    h = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 64))
    shared = ("shared_gate_proj", "shared_up_proj", "shared_down_proj")

    def share(first, held):
        return H.share_of(whole_cfg, whole["moe"], first, held)

    with jax.default_matmul_precision("highest"):
        uncut = reference._sparse_mlp(whole["moe"], h, top_k=2, first=0,
                                      scaling=2.5) \
            + reference._swiglu(whole, h, shared)
        parts, refs = [], []
        for first, held in ((0, 2), (2, 4), (6, 2)):
            cfg, p = share(first, held)
            parts.append(_moe_mlp(p, h, cfg, jnp.float32)[0])
            refs.append(reference._sparse_mlp(p, h, top_k=2, first=first,
                                              scaling=2.5))
        once = reference._swiglu(whole, h, shared)
    H.shares_add_up(parts, refs, uncut, once)


# ----------------------------- (d) latent pool --------------------------- #


def test_latent_pool_is_one_plane_of_stored_rows(model):
    cfg, params = model
    eng = engine(cfg, params)
    cache = eng.kv_cache
    # 3 layers, ONE plane, 24 blocks + the trash block, 128 + 8 lanes
    # stored in 256 (whole 128-lane groups, the tail zero)
    assert cache.data.shape == (3, 1, 25 * 16, 256)
    assert cache.data.dtype == jnp.float32 and cache.scales is None
    assert cache.kv_bytes_per_token() == 3 * 256 * 4
    assert cache.memory_bytes() == 3 * 25 * 16 * 256 * 4
    assert mt.kv_bytes_per_token(cfg, 4) == 3 * 136 * 4
    assert eng.state.kv_memory_report()["kv_bytes_per_token"] == 3072
    eng.put([1], [prompt_of(20)])
    rows = np.asarray(eng._kv_data)
    assert np.abs(rows[..., 136:]).max() == 0.0         # the zero tail
    # 20 rows a layer (padded positions went to the trash block's last row)
    assert (np.abs(rows[:, :, :24 * 16]).max(-1) > 0).sum() == 3 * 20


def test_block_copy_moves_the_one_plane(model):
    cfg, params = model
    eng = engine(cfg, params)
    eng.put([1], [prompt_of(16)])
    src = eng.state.sequences[1].kv_blocks[0]
    dst = next(b for b in range(24) if b != src)
    pool = np.asarray(eng.kv_cache.copy_block(eng._kv_data, src, dst))
    assert np.abs(pool[:, :, src * 16:src * 16 + 16]).max() > 0
    assert np.array_equal(pool[:, :, dst * 16:dst * 16 + 16],
                          pool[:, :, src * 16:src * 16 + 16])


def test_pause_and_resume_carry_the_latent_rows(model):
    """Offload and restore through the one pool value: a sequence paused
    to the host and resumed into other blocks decodes as if never paused."""
    cfg, params = model
    prompt = prompt_of(30, seed=2)

    def run(pause):
        eng = engine(cfg, params)
        tok = int(np.argmax(np.asarray(eng.put([5], [prompt])[5])))
        out = list(eng.decode_batch([5], [tok], 4)[5])
        if pause:
            eng.pause(5)
            eng.put([6], [prompt_of(40, seed=9)])    # takes the freed blocks
            eng.resume(5)
        return out + list(eng.decode_batch([5], [int(out[-1])], 4)[5])

    assert run(True) == run(False)


# ------------------------------ (e) refusals ----------------------------- #


@pytest.mark.parametrize("feature, kw", H.CONSTRUCTION_REFUSALS)
def test_construction_refuses_what_the_latent_plane_cannot_do(model, feature,
                                                              kw):
    said = FAMILY.refusal(model, feature, kw, None)
    assert feature in said and "'mla'" in said


@pytest.mark.parametrize("call", [
    "handoff_out", "handoff_in", "drain", "replay", "attach_draft",
    "decode_spec"])
def test_calls_refuse_what_the_latent_plane_cannot_do(model, call):
    """``attach_draft`` is also how an MTP draft would arrive: speculation
    with one refuses by name until the loop hands back its last hidden
    state (PERF.md section 7)."""
    said = FAMILY.refusal(model, call, {}, H.CALL_ARGS[call])
    assert call in said and "'mla'" in said


def test_latent_layers_do_not_mix_with_other_kinds(model):
    """``"mla"`` beside ``"attn"``: planes of two kinds in one pool array.
    Beside ``"kda"`` latent layers do stand (one plane and a state pool:
    ``test_kimi_linear.py::test_one_cache_value_holds_a_latent_plane_and_
    a_state_pool``)."""
    cfg, params = model
    mixed = dataclasses.replace(cfg, layer_kinds=("mla", "attn", "mla"))
    with pytest.raises(ValueError, match="do not mix with softmax"):
        engine(mixed, params)


# ------------------- (f) the other families' programs -------------------- #


def _family(name):
    from deepspeed_tpu.models.llama import Llama, LlamaConfig
    from deepspeed_tpu.models.mixtral import Mixtral, MixtralConfig
    from deepspeed_tpu.models.solar_open2 import SolarOpen2, SolarOpen2Config
    if name == "qwen2":
        cfg = LlamaConfig.tiny(qkv_bias=True, tie_embeddings=True,
                               dtype=jnp.float32)
        return cfg, Llama(cfg), lambda k: k
    if name == "olmoe":
        cfg = MixtralConfig.tiny(qk_norm=True, norm_topk_prob=False,
                                 dtype=jnp.float32)
        return cfg, Mixtral(cfg), lambda k: {"params": k, "gating": k}
    cfg = SolarOpen2Config.tiny(experts_held=4, dtype=jnp.float32,
                                param_dtype=jnp.float32)
    return cfg, SolarOpen2(cfg), lambda k: k


@pytest.mark.parametrize("name", ["qwen2", "olmoe", "solar_open2"])
def test_other_families_lower_to_the_programs_they_were(name):
    """A family that says nothing of feed-forward kinds, sandwich norms or
    planes lowers, step and fused loop and flush, to the same text as one
    that spells the defaults out (all layers one feed-forward kind, no
    branch norms): the per-layer lists add no operation to the four cells
    that were there. (Against the parent commit's text the same twelve
    programs were compared once, by hand: PERF.md, PR 34.)"""
    from deepspeed_tpu.inference.v2.kv_cache import BlockedKVCache
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    cfg, net, rngs = _family(name)
    moe = hasattr(cfg, "num_experts")
    kinds = getattr(cfg, "layer_kinds", None) or ("attn",) * cfg.num_layers
    spelt = type("Spelt", (type(cfg),), dict(
        layer_kinds=kinds, sandwich_norm=False,
        ffn_kinds=("moe" if moe else "dense",) * len(kinds)))
    spelt_cfg = object.__new__(spelt)
    spelt_cfg.__dict__.update(cfg.__dict__)
    icfg = RaggedInferenceConfig(max_seqs=4, chunk_size=16, block_size=16,
                                 num_blocks=12, max_blocks_per_seq=3,
                                 decode_loop_steps=4, dtype="float32")
    params = jax.eval_shape(lambda k: net.init(
        rngs(k), jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    i32 = lambda *s: jnp.zeros(s, jnp.int32)            # noqa: E731

    def texts(c):
        r = LlamaRaggedRunner(c, icfg)
        assert r.kv_planes == 2
        pool = BlockedKVCache(icfg, r.kv_layers, r.kv_heads, r.head_dim,
                              dtype=jnp.float32, state_spec=r.state_spec,
                              planes=r.kv_planes).pool
        ss = i32(4) if r.state_spec else None
        out = [r._step_greedy.lower(
            params, pool, RaggedBatch(i32(4, C), i32(4), i32(4), i32(4, 3),
                                      ss)).as_text() for C in (1, 16)]
        lin, kvd = None, pool
        if r.state_spec is not None:
            lin = (pool.state, pool.conv)
            kvd = pool._replace(state=None, conv=None)
        out.append(r._decode_loop_ring.lower(
            params, kvd, lin, ss, i32(4), i32(4), i32(4), i32(4, 3), i32(1),
            jnp.zeros((1,)), i32(1), jnp.ones((1,)), i32(1, 1), n=4,
            mode="greedy", cand=1, eos_id=-1, feed="self").as_text())
        ring = jnp.zeros((4, r.kv_layers, 2, 4, r.kv_heads * r.head_dim))
        out.append(r._flush_ring.lower(kvd, ring, i32(4, 3), i32(4),
                                       i32(4)).as_text())
        return out

    assert texts(cfg) == texts(spelt_cfg)


# -------------------------------- (g) MTP -------------------------------- #


def test_mtp_logits_match_the_reference():
    """The module in the model tree against ``reference.mtp_logits`` on the
    reference's own hidden stream: row t scores token t + 2."""
    cfg = tiny(nextn_layers=1)
    params = mt.init_params(cfg, 5)
    toks = jnp.asarray([prompt_of(14, seed=1), prompt_of(14, seed=2)])
    dims = mt.reference_dims(cfg)
    with jax.default_matmul_precision("highest"):
        main, got = PanguUltraMoE(cfg).apply({"params": params}, toks,
                                             mtp=True)
        hidden = reference.hidden_states(params, toks, **dims)
        want = reference.mtp_logits(params, hidden[:, :-1], toks[:, 1:],
                                    **dims)
    assert got.shape == (2, 13, 512)
    assert float(jnp.abs(got - want).max()) < TOL
    # and the main model's logits take no notice of the module
    at = jnp.broadcast_to(jnp.arange(14), (2, 14))
    with jax.default_matmul_precision("highest"):
        plain = reference.logits(params, toks, at, **dims)
    assert float(jnp.abs(main - plain).max()) < TOL
    assert float(jnp.abs(got - plain[:, :-1]).max()) > 0.1


# ------------------------- (h) registry and loader ----------------------- #


def _published():
    return H.published(CONFIG, (
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"))


def test_config_from_hf_layer_lists_and_parameter_counts():
    arch, cfg = config_from_hf(_published())
    assert arch == "pangu_ultra_moe" and isinstance(cfg, PanguUltraMoEConfig)
    assert cfg.layer_kinds == ("mla",) * 61
    assert cfg.ffn_kinds == ("dense",) * 3 + ("moe",) * 58
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.latent_row) == (128, 1, 576, 640)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (cfg.hidden_size, cfg.dense_intermediate_size,
            cfg.intermediate_size) == (7680, 18432, 2048)
    assert (cfg.num_experts, cfg.held, cfg.experts_top_k) == (256, 256, 8)
    assert cfg.sandwich_norm and cfg.routed_scaling == 2.5
    assert cfg.rope_theta == 25.6e6 and cfg.nextn_layers == 1
    total, active = param_counts(cfg)
    assert abs(total / 718e9 - 1) < 0.01           # the published 718B
    assert abs(active / 39e9 - 1) < 0.05           # ... -A39B


def test_the_benchmarks_cut_is_a_share_of_the_published_model():
    d = H.benchmark_config(CONFIG)
    cfg = mt.model_config(d)
    assert cfg.ffn_kinds == ("dense", "moe", "moe", "moe", "moe")
    assert (cfg.num_experts, cfg.held, cfg.vocab_size) == (256, 8, 19200)
    total, _ = param_counts(cfg)
    assert abs(total / 3.41e9 - 1) < 0.005         # 6.82 GB in bfloat16
    assert mt.kv_bytes_per_token(cfg) == 5760      # 5 x 576 lanes x 2 B
    # every catalog key is carried, the widths unchanged
    cat = H.catalog_row("openPangu-Ultra-MoE-718B")["config"]
    reduced = set(d["reduced"])
    assert {k for k in cat if d.get(k) != cat[k]} == reduced


@pytest.mark.parametrize("key, value", [
    ("attention_bias", True), ("rope_scaling", {"type": "yarn"})])
def test_config_from_hf_refuses_what_it_does_not_implement(key, value):
    H.hf_refuses(_published(), {key: value}, key)


def test_loader_names_reach_every_leaf():
    """A checkpoint named as the family's are (per-expert matrices, the
    MTP module as layer ``num_hidden_layers``) converts to the tree the
    runner serves, leaf for leaf."""
    cfg = tiny(experts_held=None, experts_first=0, nextn_layers=1)
    params = jax.tree_util.tree_map(np.asarray, mt.init_params(cfg, 1))
    norms = {"input_norm": "input_layernorm",
             "attn_branch_norm": "post_attention_layernorm",
             "post_attn_norm": "pre_mlp_layernorm",
             "mlp_branch_norm": "post_mlp_layernorm"}
    state = H.hf_trunk(params)

    def block(p, pre):
        for ours, theirs in norms.items():
            state[f"{pre}.{theirs}.weight"] = p[ours]["scale"]
        a = p["attn"]
        for n in ("q_a", "q_b", "kv_b", "o"):
            state[f"{pre}.self_attn.{n}_proj.weight"] = \
                a[f"{n}_proj"]["kernel"].T
        state[f"{pre}.self_attn.kv_a_proj_with_mqa.weight"] = \
            a["kv_a_proj"]["kernel"].T
        for n in ("q_a", "kv_a"):
            state[f"{pre}.self_attn.{n}_layernorm.weight"] = \
                a[f"{n}_norm"]["scale"]
        if "mlp" in p:
            H.hf_projections(state, f"{pre}.mlp", p["mlp"],
                             ("gate", "up", "down"))
            return
        state[f"{pre}.mlp.gate.weight"] = p["moe"]["gate"].T
        for n in ("gate", "up", "down"):
            state[f"{pre}.mlp.shared_experts.{n}_proj.weight"] = \
                p[f"shared_{n}_proj"]["kernel"].T
        H.hf_experts(state, f"{pre}.mlp.experts", p["moe"], (
            ("wi_gate", "gate_proj"), ("wi_up", "up_proj"),
            ("wo", "down_proj")))

    for i in range(3):
        block(params[f"layer_{i}"], f"model.layers.{i}")
    m = params["mtp_0"]
    block(m["block"], "model.layers.3")
    for n in ("enorm", "hnorm"):
        state[f"model.layers.3.{n}.weight"] = m[n]["scale"]
    state["model.layers.3.eh_proj.weight"] = m["eh_proj"]["kernel"].T
    state["model.layers.3.shared_head.norm.weight"] = m["final_norm"]["scale"]
    H.loader_reaches_every_leaf("pangu_ultra_moe", state,
                                {"num_hidden_layers": 3}, params)
