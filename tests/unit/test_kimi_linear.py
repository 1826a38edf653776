"""Kimi-Linear (gated delta-rule layers AND NoPE latent-attention layers in
one model: a state pool beside a one-plane latent pool in one cache value, a
dense layer before the sparse ones, a share of the experts) through the
normal engine, at a small size on the CPU: hidden 64, 4 KDA heads of 16,
4 latent heads of 16 + 8 over a 128 + 8 lane row, 8 experts of which a share
holds 4, top-2, 4 layers (KDA + dense, KDA, KDA, MLA). Logits against the
plain reference (``benchmark/reference/kimi_linear.py``), the absorbed NoPE
attention against the expanded one, both decode kernels at the published 32
heads, the share rule of the model-configs guide, the cache's two parts,
the loader's names, and the union of the refusals a recurrent and a latent
model make."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmark.model_types import kimi_linear as mt
from benchmark.reference import kimi_linear as reference
from deepspeed_tpu.inference.v2 import RaggedInferenceConfig
from deepspeed_tpu.inference.v2.model_runner import RaggedBatch
from deepspeed_tpu.models.kimi_linear import (KimiLinear, KimiLinearConfig,
                                              mla_param_count, param_counts)
from deepspeed_tpu.models.registry import config_from_hf
from deepspeed_tpu.models.solar_open2 import mixer_param_count
from family_harness import prompt_of

CONFIG = "kimi-linear-48b-a3b.json"
REDUCED = ("num_hidden_layers", "linear_attn_config", "num_experts",
           "vocab_size")


def tiny(**kw):
    kw.setdefault("experts_held", 4)
    kw.setdefault("experts_first", 2)
    return KimiLinearConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                                 **kw)


#: float32 engine against a float32 reference at highest precision: what
#: is left is the order of the sums (the chunked delta rule against the
#: recurrence, the absorbed products against the expanded ones, the
#: grouped matmul against the dense mask), a few 1e-6 on logits of size 4
FAMILY = H.Family(mt, tiny, tol=2e-4)
engine = FAMILY.engine


@pytest.fixture(scope="module")
def model():
    return FAMILY.model()


# ------------------------- (a) engine vs reference ------------------------ #


@H.chunk_and_decode
def test_engine_logits_match_the_reference(model, chunk, decode):
    """A 37-token prompt (three 16-token latent blocks) prefilled in one
    chunk or in three (the chunked delta rule and the absorbed prefill side
    by side), 8 tokens decoded through the fused loop (the state in its
    carry, the latent rows in its ring, then the flush into the one plane)
    or step by step, then one more position's logits: each against the
    reference's forward pass over the whole sequence (token-by-token
    recurrence, expanded attention, no cache)."""
    prompt = prompt_of(37)
    # BOTH families of counters fill in the one run
    stats = FAMILY.serve_against_reference(model, chunk,
                                           decode).pipeline_stats
    assert stats["mla_prefill_tokens"] == len(prompt)
    assert stats["linear_attn_prefill_tokens"] == len(prompt)
    assert stats["linear_attn_prefill_kernel_tokens"] == 0
    live = (sum(range(38, 46)) if decode == "pipelined" else 8 * 37) + 46
    assert stats["latent_rows_live"] == live
    assert stats["latent_rows_fetched"] >= live
    # ONE latent layer of the four keeps rows: 128 + 8 lanes of float32
    assert stats["latent_bytes_live"] == live * 1 * (128 + 8) * 4
    # 8 decode steps and the one-token step: a state row live in each, of
    # 3 recurrent layers x (4 heads x 16 x 16 + 3 taps x 192 lanes) x 4 B
    assert stats["state_slots_live"] == 9
    assert stats["state_bytes_live"] == 9 * 3 * (4 * 16 * 16 + 3 * 192) * 4
    # no K/V rows: that kernel never ran
    assert stats["decode_kv_rows_live"] == 0
    assert stats["decode_kv_rows_fetched"] == 0
    if decode == "fused":
        # 8 steps x 3 sparse layers x top-2, split with the other shares
        assert stats["moe_rows_routed"] + stats["moe_rows_elsewhere"] == 48
        assert stats["moe_rows_elsewhere"] > 0


def test_flax_model_and_runner_read_one_tree(model):
    FAMILY.flax_model_reads_the_runners_tree(KimiLinear, model)


def test_two_sequences_decode_as_they_do_alone_and_a_slot_starts_fresh(model):
    """Two sequences of different lengths in one batch, and then a third in
    a slot the first one left: each decodes what it decodes alone (the
    state rows and the latent blocks of a flushed tenant reach nobody)."""
    FAMILY.two_sequences_decode_as_alone(model)


# ----------------------- (b) absorbed == expanded ------------------------ #


def test_absorbed_nope_attention_is_the_expanded_attention(model):
    """The runner's mixer with a full-rank query and no rotary call (W_UK
    in the query, W_UV on the output, scores and values over the cached
    rows) against the reference's per-head keys and values, on the same
    inputs: a 20-token chunk after nothing, then a second chunk over the
    rows the first left. And with rotary positions the result differs: the
    switch does something."""
    from deepspeed_tpu.inference.v2.kv_cache import BlockedKVCache
    from deepspeed_tpu.inference.v2.llama_runner import _mla_mixer
    cfg, params = model
    p = params["layer_3"]["attn"]
    assert set(p) == {"q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj",
                      "o_proj"}
    icfg = RaggedInferenceConfig(max_seqs=2, chunk_size=32, block_size=16,
                                 num_blocks=8, max_blocks_per_seq=4,
                                 dtype="float32")
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 64))
    tables = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)

    def absorbed(c):
        pool = BlockedKVCache(icfg, 1, 1, c.latent_row, dtype=jnp.float32,
                              planes=1).pool
        outs = []
        for lo, n in ((0, 20), (20, 20)):
            batch = RaggedBatch(jnp.zeros((2, 20), jnp.int32),
                                jnp.full((2,), lo, jnp.int32),
                                jnp.full((2,), n, jnp.int32), tables)
            pos = lo + jnp.broadcast_to(jnp.arange(20), (2, 20))
            pool, y = _mla_mixer(p, h[:, lo:lo + n], pool, 0, batch, c,
                                 icfg, pos, jnp.ones((2, 20), bool),
                                 jnp.float32)
            outs.append(y)
        return jnp.concatenate(outs, 1)

    dims = {k: v for k, v in mt.reference_dims(cfg).items()
            if k in ("num_heads", "nope", "rope", "v_dim", "rank",
                     "rms_eps")}
    with jax.default_matmul_precision("highest"):
        got = absorbed(cfg)
        want = reference._latent_attention(p, h, **dims)
        roped = absorbed(dataclasses.replace(cfg, use_rope=True))
        want_roped = reference._latent_attention(p, h, rope_theta=1e4,
                                                 **dims)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(roped - want_roped).max()) < 1e-5
    assert float(jnp.abs(roped - got).max()) > 1e-2


# -------------------- (c) both kernels at 32 heads ----------------------- #


def test_latent_decode_kernel_at_32_heads():
    """``mla_decode_attention`` (interpreted) at the published 32 heads
    over 640-lane rows, pool and ring, against plain absorbed attention."""
    from deepspeed_tpu.ops.kernels.mla_attention import (
        mla_attention_reference, mla_decode_attention)
    rng = np.random.default_rng(0)
    S, H, W, LAT, bs, maxb, L, R = 8, 32, 640, 512, 128, 2, 2, 4
    nb = S * maxb
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    pool, q, rk = f(L, 1, (nb + 1) * bs, W), f(S, H, W), f(L, 1, S, R, W)
    tables = jnp.asarray(rng.permutation(nb).reshape(S, maxb), jnp.int32)
    lens = jnp.asarray([0, 1, 127, 128, 129, 200, 256, 77], jnp.int32)
    got = mla_decode_attention(q, pool, rk, tables, lens, jnp.asarray(3),
                               jnp.asarray([1, 1]), block_size=bs,
                               latent=LAT, sm_scale=0.07, interpret=True)
    j = jnp.arange(maxb * bs)
    rows = jnp.concatenate(
        [pool[1, 0][tables[:, j // bs] * bs + j % bs], rk[1, 0]], 1)
    mask = jnp.concatenate([
        j[None, None, :] < lens[:, None, None],
        jnp.broadcast_to((jnp.arange(R) < 3)[None, None, :]
                         & (lens > 0)[:, None, None], (S, 1, R))], 2)
    want = mla_attention_reference(q[:, None], rows, mask, LAT, 0.07)[:, 0]
    assert got.shape == (S, H, LAT)
    assert float(jnp.abs(got - want).max()) < 5e-5


def test_state_update_kernel_at_32_heads():
    """``kda_decode_state_update`` (interpreted) at 32 heads of 128 x 128:
    four grid steps of 8 heads a row, the pool rows picked by slot and
    updated in place, against the one-token recurrence."""
    from deepspeed_tpu.ops.kernels.delta_rule import (kda_decode_update,
                                                      kda_step)
    rng = np.random.default_rng(1)
    S, H, d, rows = 2, 32, 128, 4
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    state = f(rows, H, d, d) * 0.1
    slots = jnp.asarray([2, 0], jnp.int32)
    q, k, v = f(S, H, d) * 0.1, f(S, H, d) * 0.1, f(S, H, d)
    g = -jnp.abs(f(S, H, d))
    beta = jax.nn.sigmoid(f(S, H))
    o, new = kda_decode_update(state, slots, q, k, v, g, beta,
                               impl="interpret")
    o_ref, S_ref = kda_step(q, k, v, g, beta,
                            jnp.swapaxes(state[slots], -1, -2))
    assert float(jnp.abs(o - o_ref).max()) < 1e-4
    assert float(jnp.abs(new[slots]
                         - jnp.swapaxes(S_ref, -1, -2)).max()) < 1e-4
    # the rows no slot named are as they were
    assert np.array_equal(np.asarray(new[jnp.asarray([1, 3])]),
                          np.asarray(state[jnp.asarray([1, 3])]))


def test_engine_through_the_kernels_matches_the_reference(model):
    """The same engine with the Pallas attention paths forced (interpreted
    here): the latent prefill chunks through the paged kernel with the ONE
    plane as its K and its V operand, the decode steps through the latent
    decode kernel, per step and in the fused loop over its ring, beside
    the recurrent layers' state."""
    FAMILY.serve_through_the_kernels(model)


def test_decode_through_the_conv_kernel_serves_the_jnp_paths_tokens(
        model, monkeypatch):
    """``Family.decode_through_the_conv_kernel``; the engine counts the
    layer-steps the kernel took."""
    plain, forced = FAMILY.decode_through_the_conv_kernel(model, monkeypatch)
    assert plain["conv_steps_in_place"] == 0     # the CPU path: gather and scatter
    assert forced["conv_steps_in_place"] == (4 + 5) * 3      # 3 KDA layers


# ------------------------------ (d) shares ------------------------------- #


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Guide section 4 at the deployment's own division: the routed parts
    of the 4 shares (2 of 8 experts each, as 64 of 256), plus the shared
    expert once, equal the uncut reference's layer: in the engine's sparse
    block and in the reference alike."""
    from deepspeed_tpu.inference.v2.llama_runner import _moe_mlp
    whole_cfg = tiny(experts_held=None, experts_first=0)
    whole = mt.init_params(whole_cfg, 11)["layer_1"]
    # a bias large enough to move the selection of some tokens
    whole["moe"]["sel_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(5), (8,))
    h = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 64))
    shared = ("shared_gate_proj", "shared_up_proj", "shared_down_proj")
    kw = dict(top_k=2, scaling=whole_cfg.routed_scaling)
    assert whole_cfg.routed_scaling == 2.446

    def share(first, held):
        return H.share_of(whole_cfg, whole["moe"], first, held)

    with jax.default_matmul_precision("highest"):
        once = reference._swiglu(whole, h, shared)
        uncut = reference._sparse_mlp(whole["moe"], h, first=0, **kw) + once
        parts, refs = [], []
        for first in (0, 2, 4, 6):
            cfg, p = share(first, 2)
            parts.append(_moe_mlp(p, h, cfg, jnp.float32)[0])
            refs.append(reference._sparse_mlp(p, h, first=first, **kw))
        unbiased = reference._sparse_mlp(
            dict(whole["moe"], sel_bias=jnp.zeros((8,))), h, first=0, **kw)
    H.shares_add_up(parts, refs, uncut, once)
    # and the bias took part: without it other experts are chosen
    assert float(jnp.abs(unbiased + once - uncut).max()) > 1e-2


# ------------------------- (e) the cache's two parts ---------------------- #


def test_one_cache_value_holds_a_latent_plane_and_a_state_pool(model):
    """``"mla"`` beside ``"kda"`` builds ONE plane over the latent layers
    alone and a state pool over the recurrent ones, in one donated value."""
    cfg, params = model
    eng = engine(cfg, params)
    r, cache = eng.runner, eng.kv_cache
    assert (r.kv_planes, r.kv_layers, r.kv_heads, r.head_dim) \
        == (1, 1, 1, 256)
    assert r.state_spec == {"kind": "kda", "layers": 3, "heads": 4,
                            "d_v": 16, "d_k": 16, "taps": 4,
                            "conv_width": 192}
    # 1 latent layer, ONE plane, 24 blocks + the trash block, 128 + 8
    # lanes stored in 256; 3 state arrays of 4 slots + the idle row
    assert cache.data.shape == (1, 1, 25 * 16, 256)
    assert [s.shape for s in cache.state] == [(5, 4, 16, 16)] * 3
    assert cache.conv.shape == (3, 5, 3, 192)
    assert cache.kv_bytes_per_token() == 256 * 4
    assert mt.kv_bytes_per_token(cfg, 4) == 136 * 4
    assert cache.state_bytes_per_slot() == 3 * (4 * 16 * 16 + 3 * 192) * 4
    assert cache.memory_bytes() == 25 * 16 * 256 * 4 \
        + 5 * cache.state_bytes_per_slot()
    pool = eng._kv_data
    assert type(pool).__name__ == "KVPool" and pool.scales is None
    eng.put([1], [prompt_of(20)])
    pool = eng._kv_data
    rows = np.asarray(pool.data)
    assert np.abs(rows[..., 136:]).max() == 0.0         # the zero tail
    assert (np.abs(rows[:, :, :24 * 16]).max(-1) > 0).sum() == 20
    slot = eng.state.sequences[1].state_slot
    for s in pool.state:
        assert float(jnp.abs(s[slot]).max()) > 0
        assert float(jnp.abs(s[-1]).max()) == 0.0       # the idle row


# ------------------------------ (f) refusals ----------------------------- #


@pytest.mark.parametrize("feature, kw, call", H.REFUSALS)
def test_the_union_of_the_two_families_refusals(model, feature, kw, call):
    """What a recurrent model refuses and what a latent one refuses, this
    model refuses, each by its existing wording: construction options by
    ``config.validate`` (both reasons: either cache alone stands in the
    way), calls by the engine. ``pause`` / ``resume`` work over a latent
    plane and need a state snapshot: the recurrent wording alone."""
    from deepspeed_tpu.inference.v2.config import (latent_refusal,
                                                   stateful_refusal)
    said = FAMILY.refusal(model, feature, kw, call)
    assert stateful_refusal(feature) in said
    assert (latent_refusal(feature) in said) \
        == (feature not in ("pause", "resume"))


# ------------------------- (g) registry and loader ----------------------- #


def _published():
    return H.published(CONFIG, REDUCED)


def test_config_from_hf_layer_lists_and_parameter_counts():
    """The numbers under Motivation of ISSUE 40 (and in the configuration
    file's ``deployment``)."""
    arch, cfg = config_from_hf(_published())
    assert arch == "kimi_linear" and isinstance(cfg, KimiLinearConfig)
    # the config's lists are 1-based
    assert [i + 1 for i, k in enumerate(cfg.layer_kinds) if k == "mla"] \
        == [4, 8, 12, 16, 20, 24, 27]
    assert cfg.layer_kinds.count("kda") == 20 and len(cfg.layer_kinds) == 27
    assert cfg.ffn_kinds == ("dense",) + ("moe",) * 26
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.latent_row) == (32, 1, 576, 640)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (None, 512, 128, 64, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv, cfg.kda_rank,
            cfg.kda_neg_eigval) == (32, 128, 4, 128, False)
    assert (cfg.hidden_size, cfg.dense_intermediate_size,
            cfg.intermediate_size, cfg.shared_expert_size) \
        == (2304, 9216, 1024, 1024)
    assert (cfg.num_experts, cfg.held, cfg.experts_top_k) == (256, 256, 8)
    assert cfg.routed_scaling == 2.446 and cfg.router_bias
    assert cfg.norm_topk_prob and not cfg.use_rope
    assert not cfg.sandwich_norm and not cfg.tie_embeddings
    assert (cfg.vocab_size, cfg.rms_eps) == (163840, 1e-5)
    M = 1e6
    assert round(mixer_param_count(cfg, "kda") / M, 1) == 39.5
    assert round(mla_param_count(cfg) / M, 1) == 29.1
    assert round(3 * 2304 * 1024 / M, 2) == 7.08
    total, active = param_counts(cfg)
    assert abs(total / 49.1e9 - 1) < 0.005         # the published "48B"
    assert abs(active / 3.48e9 - 1) < 0.005        # "-A3B", embedding in


def test_the_benchmarks_cut_is_a_share_of_the_published_model():
    d = H.benchmark_config(CONFIG)
    cfg = mt.model_config(d)
    assert cfg.layer_kinds == ("kda", "kda", "kda", "mla") * 2
    assert cfg.ffn_kinds == ("dense",) + ("moe",) * 7
    assert (cfg.num_experts, cfg.held, cfg.vocab_size) == (256, 64, 40960)
    total, _ = param_counts(cfg)
    assert abs(total / 3.772e9 - 1) < 0.001        # 7.54 GB in bfloat16
    assert mt.kv_bytes_per_token(cfg) == 2304      # 2 x 576 lanes x 2 B
    # every catalog key is carried; what differs is what ``reduced`` names,
    # and inside the one nested group only the two layer lists
    cat = H.catalog_row("Kimi-Linear-48B-A3B-Instruct")["config"]
    assert {k for k in cat if d.get(k) != cat[k]} == set(d["reduced"]) \
        == set(REDUCED)
    la, pub = d["linear_attn_config"], cat["linear_attn_config"]
    assert {k for k in pub if la[k] != pub[k]} \
        == {"kda_layers", "full_attn_layers"}
    assert d["linear_attn_config_published"] == pub
    assert (d["chips_sharing_a_layer"], d["chips_in_the_deployment"]) \
        == (4, 16)


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("rope_scaling", {"type": "yarn"}),
    ("mla_use_nope", False), ("num_expert_group", 8), ("topk_group", 4),
    ("moe_layer_freq", 2), ("num_nextn_predict_layers", 1),
    ("hidden_act", "gelu"), ("moe_router_activation_func", "softmax"),
    ("linear_attn_config", {"kda_layers": [1, 2], "full_attn_layers": [2]})])
def test_config_from_hf_refuses_what_it_does_not_implement(key, value):
    H.hf_refuses(_published(), {key: value}, key)


def test_loader_names_reach_every_leaf():
    """A checkpoint named as the family's are (both mixers under
    ``self_attn``, per-expert ``w1`` / ``w3`` / ``w2``, convolutions
    ``[C, 1, K]``, ``A_log`` ``[1, 1, H, 1]``) converts to the tree the
    runner serves, leaf for leaf."""
    cfg = tiny(experts_held=None, experts_first=0)
    params = jax.tree_util.tree_map(np.asarray, mt.init_params(cfg, 1))
    state = H.hf_trunk(params)
    for i, kind in enumerate(cfg.layer_kinds):
        p, pre = params[f"layer_{i}"], f"model.layers.{i}"
        state[f"{pre}.input_layernorm.weight"] = p["input_norm"]["scale"]
        state[f"{pre}.post_attention_layernorm.weight"] = \
            p["post_attn_norm"]["scale"]
        a = f"{pre}.self_attn"
        if kind == "kda":
            k = p["kda"]
            for n in "qkvob":
                state[f"{a}.{n}_proj.weight"] = k[f"{n}_proj"].T
            for n in ("f_a", "f_b", "g_a", "g_b"):
                state[f"{a}.{n}_proj.weight"] = k[n].T
            for n in "qkv":
                state[f"{a}.{n}_conv1d.weight"] = k[f"{n}_conv"].T[:, None]
            state[f"{a}.A_log"] = k["A_log"].reshape(1, 1, -1, 1)
            state[f"{a}.dt_bias"] = k["dt_bias"]
            state[f"{a}.o_norm.weight"] = k["o_norm"]
        else:
            k = p["attn"]
            for n in ("q", "kv_b", "o"):
                state[f"{a}.{n}_proj.weight"] = k[f"{n}_proj"]["kernel"].T
            state[f"{a}.kv_a_proj_with_mqa.weight"] = \
                k["kv_a_proj"]["kernel"].T
            state[f"{a}.kv_a_layernorm.weight"] = k["kv_a_norm"]["scale"]
        if "mlp" in p:
            H.hf_projections(state, f"{pre}.mlp", p["mlp"],
                             ("gate", "up", "down"))
            continue
        m = f"{pre}.block_sparse_moe"
        state[f"{m}.gate.weight"] = p["moe"]["gate"].T
        state[f"{m}.gate.e_score_correction_bias"] = p["moe"]["sel_bias"]
        for n in ("gate", "up", "down"):
            state[f"{m}.shared_experts.{n}_proj.weight"] = \
                p[f"shared_{n}_proj"]["kernel"].T
        H.hf_experts(state, f"{m}.experts", p["moe"],
                     (("wi_gate", "w1"), ("wi_up", "w3"), ("wo", "w2")))
    H.loader_reaches_every_leaf(
        "kimi_linear", state, {"linear_attn_config": {
            "kda_layers": [1, 2, 3], "full_attn_layers": [4]}}, params)
