"""Olmo-Hybrid (gated delta-rule layers with ONE decay a head, keys and
values of two widths, beside full multi-head attention layers with a
QK-norm over the whole projection and no position code; a dense SwiGLU in
every layer; the norms on the branches' OUTPUTS) through the normal engine,
at a small size on the CPU that keeps the awkward geometry: hidden 96, 6
heads (no multiple of 8) of keys 12 and values 24, 6 attention heads of 16,
4 layers (three recurrent, one full). Logits against the plain reference
(``benchmark/reference/olmo_hybrid.py``), the chunked against the
token-by-token delta rule, both Pallas kernels interpreted at the published
30 x 96 x 192 against their jnp twins, the state pool's layout and its
counters, the norm arrangement as one field, the refusals a recurrent model
makes, the registry and the benchmark's cut."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmark.model_types import olmo_hybrid as mt
from deepspeed_tpu.inference.v2 import RaggedInferenceConfig
from deepspeed_tpu.models.olmo_hybrid import (OlmoHybrid, OlmoHybridConfig,
                                              mixer_param_count,
                                              param_counts)
from deepspeed_tpu.models.registry import config_from_hf
from deepspeed_tpu.ops.kernels import delta_rule as dr
from family_harness import prompt_of

CONFIG = "olmo-hybrid-7b.json"
REDUCED = ("num_hidden_layers", "layer_types")


def tiny(**kw):
    return OlmoHybridConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                                 **kw)


#: float32 engine against a float32 reference at highest precision: what
#: is left is the order of the sums (the chunked delta rule against the
#: recurrence, paged against dense attention), a few 1e-6 on logits of
#: size 4
FAMILY = H.Family(mt, tiny, tol=2e-4)
engine = FAMILY.engine


@pytest.fixture(scope="module")
def model():
    return FAMILY.model()


# ------------------------- (a) engine vs reference ------------------------ #


@H.chunk_and_decode
def test_engine_logits_match_the_reference(model, chunk, decode):
    """A 37-token prompt (three 16-token blocks of the attention layer)
    prefilled in one chunk or in three, 8 tokens decoded through the fused
    loop (the state in its carry, K and V in its ring, then the flush into
    the paged pool) or step by step, then one more position's logits: each
    against the reference's forward pass over the whole sequence
    (token-by-token recurrence, dense attention, no cache)."""
    prompt = prompt_of(37)
    stats = FAMILY.serve_against_reference(model, chunk,
                                           decode).pipeline_stats
    assert stats["linear_attn_prefill_tokens"] == len(prompt)
    assert stats["linear_attn_prefill_kernel_tokens"] == 0      # a CPU
    # 8 decode steps and the one-token step: a state row live in each, of
    # 3 recurrent layers x (6 heads x 12 x 24 floats + 3 taps x 288 lanes)
    slot = 3 * (6 * 12 * 24 + 3 * 288) * 4
    assert stats["state_slots_live"] == 9
    assert stats["state_bytes_live"] == 9 * slot
    # as stored: [12 -> 16 sublanes, 144 -> 256 lanes] a state, a slot's
    # carried inputs [3 -> 8, 288 -> 384]; the difference is the padding
    stored = 3 * (16 * 256 + 8 * 384) * 4
    assert stats["state_bytes_resident"] == 9 * stored
    assert stats["state_bytes_padding"] == 9 * (stored - slot)
    # ONE full-attention layer of the four keeps rows
    live = (sum(range(38, 46)) if decode == "pipelined" else 8 * 37) + 46
    assert stats["decode_kv_rows_live"] == live


def test_flax_model_and_runner_read_one_tree(model):
    FAMILY.flax_model_reads_the_runners_tree(OlmoHybrid, model)


def test_two_sequences_decode_as_they_do_alone_and_a_slot_starts_fresh(model):
    """Two sequences of different lengths in one batch, and then a third
    refilled into the slot the first one left: each decodes what it
    decodes alone (the state, the carried inputs and the blocks of a
    flushed tenant reach nobody)."""
    def left_behind(eng, slot):
        # the flushed tenant's state is still in its row: the next one
        # must start from zero all the same
        assert float(jnp.abs(eng._kv_data.state[0][slot]).max()) > 0
    FAMILY.two_sequences_decode_as_alone(model, after_flush=left_behind)


def test_two_fused_loops_and_a_flush_between_them(model):
    """Two fused loops of 4 steps: the second reads the rows the first
    one's flush wrote and the state its carry left."""
    FAMILY.serve_against_reference(model, 16, "fused", loops=(4, 4))


def test_the_attention_kernels_serve_the_family(model):
    """The Pallas attention paths forced (interpreted here) at 6 K/V heads,
    group 1, no position code."""
    FAMILY.serve_through_the_kernels(model)


def test_decode_through_the_short_conv_kernel(model, monkeypatch):
    plain, forced = FAMILY.decode_through_the_conv_kernel(model, monkeypatch)
    assert plain["conv_steps"] == forced["conv_steps"] > 0
    assert plain["conv_steps_in_place"] == 0
    assert forced["conv_steps_in_place"] == forced["conv_steps"]


def test_a_pool_wider_than_the_channels_carries_zeros():
    """Channels that are whole lane rows and no whole tiles a tap (256
    lanes of float32: 2 rows of a tile's 8) ask the pool for the next
    width that is (``short_conv.whole_width``: 1,024), the mixer pads the
    step's inputs and the taps, and the engine still serves the
    reference's logits; the live bytes count the channels, the resident
    ones the pool."""
    from deepspeed_tpu.ops.kernels.short_conv import whole_width
    assert whole_width(11520, jnp.bfloat16) == 12288
    assert whole_width(12288, jnp.bfloat16) == 12288
    assert whole_width(288, jnp.float32) == 288           # a toy width
    family = H.Family(mt, lambda: tiny(num_layers=2, gdn_heads=4,
                                       gdn_key_dim=16, gdn_value_dim=32,
                                       layer_kinds=("gdn", "attn")))
    cfg, params = model = family.model()
    assert cfg.gdn_conv_width == 256
    eng = family.engine(cfg, params, 16)
    assert eng.runner.state_spec["conv_width"] == 1024
    assert eng.runner.state_spec["conv_channels"] == 256
    assert eng.kv_cache.conv.shape == (1, 5, 24, 128)
    family.walk(eng, model, 7, prompt_of(37))
    assert eng.kv_cache.state_bytes_per_slot() \
        == (4 * 16 * 32 + 3 * 256) * 4
    assert eng.kv_cache.state_bytes_per_slot(resident=True) \
        == (16 * 128 + 24 * 128) * 4
    # the lanes past the channels never hold anything
    conv = np.asarray(eng._kv_data.conv).reshape(5, 3, 1024)
    assert np.abs(conv[..., :256]).max() > 0
    assert np.abs(conv[..., 256:]).max() == 0


# ----------------------- (b) the delta rule's forms ----------------------- #


def _inputs(B, T, H, dk, dv, seed=0, decay=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -decay * jnp.exp(2.0 * jax.random.normal(ks[3], (B, T, H)))
    beta = 1.0 + jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (B, dk, H * dv))


@pytest.mark.parametrize("decay", [1.0, 4.0], ids=["spread", "near-zero"])
def test_chunked_is_the_token_by_token_delta_rule(decay):
    """The chunked form on a broadcast decay (the CPU path of a prefill
    chunk) against the definition, with beta in (1, 2) (negative
    eigenvalues allowed) and, at ``near-zero``, log decays of -4 in the
    median and past -104 (where float32's exp is 0) for a position in
    twenty: a decay that underflows
    must give the recurrence's 0, not a division by it. Keys 12 and values 24 wide, 6 heads, 150
    positions (chunks of 64 with a ragged tail), from a non-zero state."""
    q, k, v, g, beta, St0 = _inputs(2, 150, 6, 12, 24, decay=decay)
    o, St = dr.gdn_prefill(q, k, v, g, beta, St0, impl="xla")
    want_o, want_S = dr.kda_recurrent(q, k, v, dr._over_keys(g, q), beta,
                                      dr._from_pool(St0, 6))
    # float32 sums in another order, on outputs of size 0.5 from a state
    # of unit entries: a few 1e-5 (3.2e-5 read); the chunked form's
    # exponents are differences of running sums of up to 64 log decays,
    # exact to 1e-7 of a sum that reaches 5e3 at ``near-zero``
    assert float(jnp.abs(o - want_o).max()) < 2e-4
    assert float(jnp.abs(St - dr._to_pool(want_S)).max()) < 4e-4
    assert float(jnp.abs(want_o).max()) > 0.1
    assert float(jnp.exp(g).min()) == 0.0


def test_the_pools_layout_is_whole_tiles_at_the_published_widths():
    assert dr.gdn_state_shape(30, 96, 192) == (96, 5760)
    assert 96 % 8 == 0 and 5760 % 128 == 0
    S = jnp.arange(2 * 3 * 4 * 5, dtype=jnp.float32).reshape(2, 3, 4, 5)
    assert dr._to_pool(S).shape == (2, 4, 15)
    assert np.array_equal(dr._from_pool(dr._to_pool(S), 3), S)
    # two heads of 192 values fill three lane tiles; 10 of 30 heads a step
    assert dr._lane_group(192) == 2 and dr._decode_heads(30, 96, 192) == 10
    assert dr._prefill_heads(30) == 6
    assert dr.gdn_decode_uses_kernel(30, 96, 192, backend="tpu")
    assert dr.gdn_prefill_uses_kernel(512, 30, 96, 192, backend="tpu")
    assert not dr.gdn_prefill_uses_kernel(512, 30, 96, 192, backend="cpu")
    assert not dr.gdn_prefill_uses_kernel(100, 30, 96, 192, backend="tpu")
    # the toy geometry stays off both kernels, the published one off KDA's
    assert not dr.gdn_decode_uses_kernel(6, 12, 24, backend="tpu")
    assert not dr.kda_prefill_uses_kernel(512, 30, 96, 192, backend="tpu")


def test_the_decode_kernel_is_its_twin_at_the_published_shape():
    """``gdn_decode_state_update`` interpreted at 30 heads of 96 x 192
    against gather / ``kda_step`` / scatter: three rows of a five-row
    pool, one of them wiped (``g = -inf``), the untouched rows bit for
    bit."""
    q, k, v, g, beta, _ = _inputs(3, 1, 30, 96, 192, seed=1)
    q, k, v, g, beta = (x[:, 0] for x in (q, k, v, g, beta))
    g = g.at[1].set(-jnp.inf)
    state = jax.random.normal(jax.random.PRNGKey(9), (5, 96, 30 * 192))
    slots = jnp.asarray([3, 0, 2], jnp.int32)
    want_o, want = dr.gdn_decode_update(state, slots, q, k, v, g, beta,
                                        impl="xla")
    o, got = dr.gdn_decode_update(state, slots, q, k, v, g, beta,
                                  impl="interpret")
    assert float(jnp.abs(o - want_o).max()) < 1e-5
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert np.array_equal(got[jnp.asarray([1, 4])],
                          state[jnp.asarray([1, 4])])
    assert float(jnp.abs(o).max()) > 0.05


def test_the_chunk_kernel_is_its_twin_at_the_published_shape():
    """``gdn_chunk_prefill`` interpreted at 30 heads of 96 x 192 against
    the token-by-token recurrence: a row of 128 positions and one of 70
    (its second chunk ragged), beta in (1, 2), from a non-zero state."""
    q, k, v, g, beta, St0 = _inputs(2, 128, 30, 96, 192, seed=2)
    n = jnp.asarray([128, 70], jnp.int32)
    real = jnp.arange(128)[None, :] < n[:, None]
    g = jnp.where(real[..., None], g, 0.0)
    beta = jnp.where(real[..., None], beta, 0.0)
    o, St = dr.gdn_prefill(q, k, v, g, beta, St0, n, impl="interpret")
    want_o, want_S = dr.kda_recurrent(q, k, v, dr._over_keys(g, q), beta,
                                      dr._from_pool(St0, 30))
    mask = real[..., None, None]
    assert float(jnp.abs(jnp.where(mask, o - want_o, 0.0)).max()) < 1e-5
    assert float(jnp.abs(St - dr._to_pool(want_S)).max()) < 1e-4


# ------------------- (c) the cache and the norm arrangement --------------- #


def test_the_cache_holds_k_and_v_for_one_layer_and_state_for_three(model):
    cfg, params = model
    eng = engine(cfg, params)
    r, cache = eng.runner, eng.kv_cache
    assert (r.kv_planes, r.kv_layers, r.kv_heads, r.head_dim) \
        == (2, 1, 6, 16)
    assert r.state_spec == {
        "kind": "gdn", "layers": 3, "heads": 6, "d_v": 24, "d_k": 12,
        "taps": 4, "conv_width": 288, "conv_channels": 288,
        "state_shape": (12, 144)}
    assert [s.shape for s in cache.state] == [(5, 12, 144)] * 3
    assert cache.conv.shape == (3, 5, 3, 288)
    assert mt.kv_bytes_per_token(cfg, 4) == cache.kv_bytes_per_token()
    eng.put([1], [prompt_of(20)])
    pool = eng._kv_data
    slot = eng.state.sequences[1].state_slot
    assert all(float(jnp.abs(s[slot]).max()) > 0 for s in pool.state)
    assert all(float(jnp.abs(s[-1]).max()) == 0.0 for s in pool.state)


def test_two_recurrent_kinds_in_one_model_are_refused_by_name():
    cfg = tiny(layer_kinds=("gdn", "kda", "gdn", "attn"))
    with pytest.raises(ValueError, match=r"\['gdn', 'kda'\]"):
        from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
        LlamaRaggedRunner(cfg, RaggedInferenceConfig(
            max_seqs=2, chunk_size=16, block_size=16, num_blocks=8,
            max_blocks_per_seq=4, dtype="float32"))


def test_where_a_blocks_norms_stand_is_one_field():
    """``block_norms`` is what the step reads: the families with a norm in
    front of each branch have no such field or say "pre", the sandwich
    family derives it from its published boolean, this one says "post"
    and its tree has the output norms alone."""
    from deepspeed_tpu.models.kimi_linear import KimiLinearConfig
    from deepspeed_tpu.models.llama import LlamaConfig
    from deepspeed_tpu.models.pangu_ultra_moe import PanguUltraMoEConfig
    assert PanguUltraMoEConfig().block_norms == "sandwich"
    assert PanguUltraMoEConfig(sandwich_norm=False).block_norms == "pre"
    assert KimiLinearConfig.tiny().block_norms == "pre"
    assert not hasattr(LlamaConfig(), "block_norms")
    cfg = tiny()
    assert cfg.block_norms == "post"
    layer = FAMILY.model()[1]["layer_0"]
    assert set(layer) == {"gdn", "mlp", "attn_branch_norm",
                          "mlp_branch_norm"}


def test_the_norm_on_the_input_is_another_model(model):
    """The reference with each branch's norm moved in front of it (the
    pre-norm arrangement on the same weights) gives other logits: the
    arrangement is part of what the engine is held to."""
    cfg, params = model
    tokens = jnp.asarray([prompt_of(24, seed=2)])
    at = jnp.asarray([[23]])
    dims = mt.reference_dims(cfg)
    from benchmark.reference import olmo_hybrid as reference
    want = reference.logits(params, tokens, at, **dims)
    for wrong in (dict(norm_at="input"), dict(beta_scale=1.0),
                  dict(channel_decay=True), dict(rope_theta=10000.0)):
        got = reference.logits(params, tokens, at, **dims, **wrong)
        assert float(jnp.abs(got - want).max()) > 1e-2, wrong


# ------------------------------ (d) refusals ------------------------------ #


@pytest.mark.parametrize("feature, kw, call", H.REFUSALS)
def test_what_a_scalar_decay_model_refuses(model, feature, kw, call):
    """What would need a snapshot, a rewind or a shard of the recurrent
    state refuses by the feature's name and the layer kind ``'gdn'``, in
    the recurrent kinds' one wording: construction options by
    ``config.validate``, calls by the engine."""
    from deepspeed_tpu.inference.v2.config import stateful_refusal
    said = FAMILY.refusal(model, feature, kw, call)
    assert said == stateful_refusal(feature, "gdn")
    assert "('gdn')" in said


# ------------------------- (e) registry and the cut ----------------------- #


def test_config_from_hf_layer_list_and_parameter_counts():
    if not os.path.exists(H.CATALOG):
        pytest.skip("no catalog on this machine")
    row = H.catalog_row("Olmo-Hybrid-7B")
    name, cfg = config_from_hf(row["config"])
    assert name == "olmo_hybrid" and isinstance(cfg, OlmoHybridConfig)
    assert len(cfg.layer_kinds) == 32 and cfg.layer_kinds.count("attn") == 8
    assert cfg.layer_kinds[:4] == ("gdn", "gdn", "gdn", "attn")
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size) \
        == (3840, 11008, 30, 30, 128, 100352)
    assert (cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_conv,
            cfg.gdn_neg_eigval, cfg.gdn_conv_width) \
        == (30, 96, 192, 4, True, 11520)
    assert cfg.qk_norm is True and not cfg.use_rope and not cfg.qkv_bias
    assert cfg.rms_eps == 1e-6 and not cfg.tie_embeddings
    assert cfg.block_norms == "post"
    # ISSUE 65's counts: 88.7 M a recurrent mixer, 59.0 M an attention one
    assert abs(mixer_param_count(cfg, "gdn") / 88.7e6 - 1) < 2e-3
    assert abs(mixer_param_count(cfg, "attn") / 59.0e6 - 1) < 2e-3
    total, active = param_counts(cfg)
    assert total == active and 7.0e9 < total < 7.6e9         # "7B", dense


def test_the_benchmarks_cut_is_the_first_two_periods():
    if not os.path.exists(H.CATALOG):
        pytest.skip("no catalog on this machine")
    row = H.catalog_row("Olmo-Hybrid-7B")
    cut = H.benchmark_config(CONFIG)
    assert cut["_source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in REDUCED:
            assert cut[key] == value, key
    assert sorted(cut["reduced"]) == sorted(REDUCED)
    assert cut["layer_types"] == row["config"]["layer_types"][:8]
    assert cut["num_hidden_layers"] == 8
    assert cut["num_hidden_layers_published"] == 32
    cfg = mt.model_config(cut)
    assert cfg.layer_kinds == ("gdn", "gdn", "gdn", "attn") * 2
    assert cfg.vocab_size == row["config"]["vocab_size"]
    total, _ = param_counts(cfg)
    assert total == cut["parameters"]
    assert abs(total / 2.435e9 - 1) < 1e-3                # ISSUE 65's count
    for word in ("OUTPUT", "WHOLE", "rope_theta is null", "head_dim 128",
                 "no bias", "SiLU", "tie_word_embeddings"):
        assert any(word in line for line in cut["assumed"]), word


@pytest.mark.parametrize("key, value, match", [
    ("linear_num_key_heads", 2, "linear_num_key_heads"),
    ("attention_bias", True, "attention_bias"),
    ("hidden_act", "gelu", "hidden_act"),
    ("sliding_window", 4096, "sliding_window"),
    ("layer_types", ["linear_attention", "sliding_attention"],
     "layer_types"),
    ("layer_types", ["linear_attention"], "layer_types"),
    ("rope_parameters", {"rope_type": "yarn", "rope_theta": 5e5}, "rotary"),
    ("head_dim", 64, "head_dim")])
def test_config_from_hf_refuses_what_it_does_not_serve(key, value, match):
    hf = dict(model_type="olmo_hybrid", num_hidden_layers=2,
              hidden_size=96, num_attention_heads=6,
              linear_num_key_heads=6, linear_num_value_heads=6,
              layer_types=["linear_attention", "full_attention"],
              rope_parameters={"rope_theta": None})
    H.hf_refuses(hf, {key: value}, match)


def test_a_rotary_code_is_served_when_the_config_gives_one():
    hf = dict(model_type="olmo_hybrid", num_hidden_layers=2,
              layer_types=["linear_attention", "full_attention"])
    _, none = config_from_hf(dict(hf, rope_parameters={"rope_theta": None}))
    _, some = config_from_hf(dict(hf, rope_parameters={"rope_theta": 5e5}))
    assert not none.use_rope
    assert some.use_rope and some.rope_theta == 5e5


def test_the_mixers_device_time_falls_under_the_regions_that_are_there(model):
    """No new region: the recurrent mixer opens ``linear_attn``, the
    attention layer ``attn_proj`` / ``attn_core``, the rest the regions
    every dense model has."""
    import re

    from deepspeed_tpu.inference.v2.model_runner import RaggedBatch
    from deepspeed_tpu.telemetry.trace import REGION_MARK, REGIONS
    assert len(REGIONS) == 24
    cfg, params = model
    eng = engine(cfg, params)
    batch = RaggedBatch(jnp.zeros((4, 1), jnp.int32),
                        jnp.zeros((4,), jnp.int32),
                        jnp.ones((4,), jnp.int32),
                        jnp.zeros((4, 6), jnp.int32),
                        jnp.arange(4, dtype=jnp.int32))
    text = eng.runner._step.lower(params, eng._kv_data,
                                  batch).as_text(debug_info=True)
    opened = set(re.findall(re.escape(REGION_MARK) + r"(\w+)", text))
    assert opened <= set(REGIONS)
    assert {"linear_attn", "attn_proj", "attn_core", "ffn_dense", "norm",
            "residual", "head", "embed", "kv_write"} <= opened
