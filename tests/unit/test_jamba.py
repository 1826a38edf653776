"""Jamba (Mamba-1 mixers, a decay a channel AND state, beside multi-query
attention layers without a position code; a dense SwiGLU in every layer,
the head tied to the embedding) through the normal engine, at a small size
on the CPU: hidden 64, 128 channels of 4 states, step-size rank 8, 4 query
heads on ONE K/V head of 16, 4 layers (three Mamba, one attention). Logits
against the plain reference (``benchmark/reference/jamba.py``; which
``test_jamba_reference.py`` holds to the published modelling code), the
recurrence's definition against a loop, both Pallas kernels interpreted
against their jnp twins and under the engine, the state pool's layout and
its counters, the refusals a recurrent model makes, the registry, the
loader and the benchmark's configuration (no cut)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmark.model_types import jamba as mt
from deepspeed_tpu.inference.v2 import RaggedInferenceConfig
from deepspeed_tpu.models.jamba import (Jamba, JambaConfig,
                                        mixer_param_count, param_counts)
from deepspeed_tpu.models.registry import config_from_hf
from deepspeed_tpu.ops.kernels import selective_scan as ss
from family_harness import prompt_of

CONFIG = "jamba2-3b.json"


def tiny(**kw):
    return JambaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32, **kw)


#: float32 engine against a float32 reference at highest precision: what
#: is left is the order of the sums (a prefill chunk's carried state
#: against one scan, paged against dense attention), a few 1e-6 on logits
#: of size 3
FAMILY = H.Family(mt, tiny, tol=2e-4)
engine = FAMILY.engine


@pytest.fixture(scope="module")
def model():
    return FAMILY.model()


# ------------------------- (a) engine vs reference ------------------------ #


@H.chunk_and_decode
def test_engine_logits_match_the_reference(model, chunk, decode):
    """A 37-token prompt (three 16-token blocks of the attention layer)
    prefilled in one chunk or in three (the state carried from chunk to
    chunk through the pool), 8 tokens decoded through the fused loop (the
    state in its carry, K and V in its ring, then the flush into the
    paged pool) or step by step, then one more position's logits: each
    against the reference's forward pass over the whole sequence
    (token-by-token recurrence, dense attention, no cache)."""
    prompt = prompt_of(37)
    stats = FAMILY.serve_against_reference(model, chunk,
                                           decode).pipeline_stats
    assert stats["linear_attn_prefill_tokens"] == len(prompt)
    assert stats["linear_attn_prefill_kernel_tokens"] == 0      # a CPU
    # 8 decode steps and the one-token step: a state row live in each, of
    # 3 Mamba layers x (4 x 128 floats + 3 taps x 128 channels of floats)
    slot = 3 * (4 * 128 + 3 * 128) * 4
    assert stats["state_slots_live"] == 9
    assert stats["state_bytes_live"] == 9 * slot
    # as stored: [4 -> 8 sublanes, 128 lanes] a state; the convolution's
    # pool 1,024 wide (short_conv.whole_width of float32), 24 rows of 128
    stored = 3 * (8 * 128 + 24 * 128) * 4
    assert stats["state_bytes_resident"] == 9 * stored
    assert stats["state_bytes_padding"] == 9 * (stored - slot)
    # ONE attention layer of the four keeps rows
    live = (sum(range(38, 46)) if decode == "pipelined" else 8 * 37) + 46
    assert stats["decode_kv_rows_live"] == live


def test_flax_model_and_runner_read_one_tree(model):
    FAMILY.flax_model_reads_the_runners_tree(Jamba, model)


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


def test_the_attention_kernels_serve_multi_query_heads(model):
    """The Pallas attention paths forced (interpreted here) at ONE K/V
    head under 4 query heads, no position code."""
    FAMILY.serve_through_the_kernels(model)


def test_decode_through_the_short_conv_kernel(model, monkeypatch):
    plain, forced = FAMILY.decode_through_the_conv_kernel(model, monkeypatch)
    assert plain["conv_steps"] == forced["conv_steps"] > 0
    assert plain["conv_steps_in_place"] == 0
    assert forced["conv_steps_in_place"] == forced["conv_steps"]


def test_the_engine_through_both_scan_kernels(monkeypatch):
    """The two Pallas forms forced under the engine (interpreted here; on
    the chip platform and shape pick them) at a shape they take, 512
    channels of 8 states: a 150-token prompt in chunks of 64 (its third
    chunk ragged: padded positions take ``dt`` 0), 4 steps of the fused
    loop and 3 step by step (rows of one block, three of them idle),
    against the reference."""
    family = H.Family(mt, lambda: tiny(hidden_size=256, num_layers=2,
                                       mamba_state=8,
                                       layer_kinds=("mamba1", "attn")))
    cfg, params = model = family.model()
    assert ss.kernel_shape(cfg.mamba_inner, cfg.mamba_state)
    used = []
    monkeypatch.setattr(
        ss, "_impl", lambda impl, kernel: used.append(kernel) or "interpret")
    eng = family.engine(cfg, params, 64, max_blocks_per_seq=12, num_blocks=30)
    family.walk(eng, model, 7, prompt_of(150), "fused", loops=(4,))
    toks = H.decode_tokens(eng, 7, 5, 3, "pipelined")
    # (asked at trace time: the prefill step's, the loop's, the step's)
    assert len(toks) == 3 and len(used) >= 3


@pytest.mark.parametrize("wrong", [
    dict(inner_norms=False), dict(shared_decay=True),
    dict(state_reset=(20, 4)), dict(conv_bias=False),
    dict(rope_theta=10000.0)], ids=lambda w: next(iter(w)))
def test_each_wrong_model_of_the_cells_check_differs(model, wrong):
    """The reference with one thing wrong (the controls of the cell's
    ``correct``) gives other logits on the same weights: each is part of
    what the engine is held to."""
    from benchmark.reference import jamba as reference
    cfg, params = model
    tokens = jnp.asarray([prompt_of(24, seed=2)])
    at = jnp.asarray([[23]])
    dims = mt.reference_dims(cfg)
    want = reference.logits(params, tokens, at, **dims)
    got = reference.logits(params, tokens, at, **dims, **wrong)
    assert float(jnp.abs(got - want).max()) > 1e-2


# ----------------------- (b) the recurrence's forms ----------------------- #


def _inputs(S, T, E, N, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (S, T, E))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (S, T, E)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (N, E)))
    B = jax.random.normal(ks[3], (S, T, N))
    C = jax.random.normal(ks[4], (S, T, N))
    D = jax.random.normal(ks[5], (E,))
    return x, dt, A, B, C, D, ks[6]


def test_the_recurrence_is_the_loop_it_says():
    """``mamba1_recurrent`` against numpy loops over positions and states
    from a non-zero state: a decay for every (state, channel) pair."""
    x, dt, A, B, C, D, k = _inputs(2, 11, 24, 4)
    h0 = jax.random.normal(k, (2, 4, 24))
    y, h = ss.mamba1_recurrent(x, dt, A, B, C, D, h0)
    xn, dtn, An, Bn, Cn, Dn = (np.asarray(t, np.float64)
                               for t in (x, dt, A, B, C, D))
    S = np.asarray(h0, np.float64)
    want = np.zeros(x.shape)
    for t in range(11):
        for n in range(4):
            S[:, n] = np.exp(dtn[:, t] * An[n]) * S[:, n] \
                + dtn[:, t] * xn[:, t] * Bn[:, t, n:n + 1]
        want[:, t] = (S * Cn[:, t][:, :, None]).sum(1) + Dn * xn[:, t]
    assert float(np.abs(np.asarray(y) - want).max()) < 1e-4
    assert float(np.abs(np.asarray(h) - S).max()) < 1e-5
    assert float(np.abs(want).max()) > 1.0


@pytest.mark.parametrize("S, rows", [(3, 7), (8, 9), (12, 14), (16, 17)],
                         ids=["one-block", "eight", "padded", "two-blocks"])
def test_the_decode_kernel_is_its_twin(S, rows):
    """``mamba1_decode_state_update`` interpreted against gather /
    ``mamba1_step`` / scatter at 1,024 channels of 16 states: rows of one
    block, whole blocks of 8 and a batch the wrapper pads to them (12 ->
    16, the pad rows on the pool's idle row); one row wiped (a fresh
    slot), one with ``dt`` 0 (writes back what it read), the slots in
    reverse; the untouched rows bit for bit."""
    E, N = 1024, 16
    x, dt, A, B, C, D, k = _inputs(S, 1, E, N, seed=S)
    x, dt, B, C = (t[:, 0] for t in (x, dt, B, C))
    dt = dt.at[S - 1].set(0.0)
    wipe = jnp.zeros((S,), bool).at[1].set(True)
    state = jax.random.normal(k, (rows, N, E))
    slots = jnp.arange(S, dtype=jnp.int32)[::-1]
    want_y, want = ss.mamba1_decode_update(state, slots, x, dt, A, B, C, D,
                                           wipe=wipe, impl="xla")
    y, got = ss.mamba1_decode_update(state, slots, x, dt, A, B, C, D,
                                     wipe=wipe, impl="interpret")
    assert float(jnp.abs(y - want_y).max()) < 1e-5
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert np.array_equal(got[S:], state[S:])              # nobody's rows
    assert np.array_equal(got[0], state[0])                # dt = 0
    # the wiped row's new state is its input's alone
    assert np.allclose(got[S - 2], (dt[1] * x[1])[None] * B[1][:, None],
                       atol=1e-6)
    assert float(jnp.abs(y).max()) > 0.5


def test_the_chunk_kernel_is_its_twin():
    """``mamba1_chunk_scan`` interpreted against the token-by-token
    recurrence at 1,024 channels of 16 states over 128 positions (two
    blocks of 64: the state crosses a block boundary in VMEM): a full
    row, a row of 70 (its second block ragged, ``dt`` 0 past it), a fresh
    row (wiped) and an idle row (``live`` false: its slot keeps what it
    had), from non-zero states."""
    E, N, T = 1024, 16, 128
    x, dt, A, B, C, D, k = _inputs(4, T, E, N, seed=5)
    n = jnp.asarray([128, 70, 128, 0], jnp.int32)
    real = jnp.arange(T)[None, :] < n[:, None]
    dt = jnp.where(real[..., None], dt, 0.0)
    state = jax.random.normal(k, (7, N, E))
    slots = jnp.asarray([4, 1, 5, 6], jnp.int32)
    wipe = jnp.asarray([False, False, True, True])
    live = n > 0
    want_y, want = ss.mamba1_prefill(state, slots, x, dt, A, B, C, D,
                                     wipe=wipe, live=live, impl="xla")
    y, got = ss.mamba1_prefill(state, slots, x, dt, A, B, C, D, wipe=wipe,
                               live=live, impl="interpret")
    assert float(jnp.abs(jnp.where(real[..., None], y - want_y,
                                   0.0)).max()) < 1e-4
    assert float(jnp.abs(got - want).max()) < 1e-5
    others = jnp.asarray([0, 2, 3, 6])
    assert np.array_equal(got[others], state[others])
    assert float(jnp.abs(got[5] - state[5]).max()) > 0.1
    assert float(jnp.abs(want_y).max()) > 1.0


def test_a_chunk_boundary_mid_prompt_is_one_scan():
    """A prompt's positions in two calls, the state through the pool
    between them, give the one call's outputs and state."""
    E, N = 512, 8
    x, dt, A, B, C, D, k = _inputs(1, 128, E, N, seed=3)
    state = jnp.zeros((3, N, E))
    slots = jnp.asarray([1], jnp.int32)
    fresh = jnp.asarray([True])
    for impl in ("xla", "interpret"):
        y, one = ss.mamba1_prefill(state, slots, x, dt, A, B, C, D,
                                   wipe=fresh, impl=impl)
        ya, half = ss.mamba1_prefill(state, slots, x[:, :64], dt[:, :64], A,
                                     B[:, :64], C[:, :64], D, wipe=fresh,
                                     impl=impl)
        yb, two = ss.mamba1_prefill(half, slots, x[:, 64:], dt[:, 64:], A,
                                    B[:, 64:], C[:, 64:], D, impl=impl)
        assert float(jnp.abs(jnp.concatenate([ya, yb], 1) - y).max()) < 1e-4
        assert float(jnp.abs(two - one).max()) < 1e-5


def test_which_shapes_the_kernels_take():
    assert ss.mamba1_state_shape(5120, 16) == (16, 5120)
    assert 16 % 8 == 0 and 5120 % 128 == 0                 # whole tiles
    assert ss.mamba1_decode_uses_kernel(5120, 16, backend="tpu")
    assert not ss.mamba1_decode_uses_kernel(5120, 16, backend="cpu")
    assert ss.mamba1_prefill_uses_kernel(512, 1, 16, 5120, backend="tpu")
    assert not ss.mamba1_prefill_uses_kernel(512, 1, 16, 5120,
                                             backend="cpu")
    assert not ss.mamba1_prefill_uses_kernel(100, 1, 16, 5120,
                                             backend="tpu")
    # the toy geometry stays off both
    assert not ss.mamba1_decode_uses_kernel(128, 4, backend="tpu")
    assert not ss.mamba1_prefill_uses_kernel(64, 1, 4, 128, backend="tpu")


# ------------------------------ (c) the cache ----------------------------- #


def test_the_cache_holds_k_and_v_for_one_layer_and_state_for_three(model):
    cfg, params = model
    eng = engine(cfg, params)
    r, cache = eng.runner, eng.kv_cache
    assert (r.kv_planes, r.kv_layers, r.kv_heads, r.head_dim) \
        == (2, 1, 1, 16)
    assert r.state_spec == {
        "kind": "mamba1", "layers": 3, "heads": 1, "d_v": 128, "d_k": 4,
        "taps": 4, "conv_width": 1024, "conv_channels": 128,
        "state_shape": (4, 128)}
    assert [s.shape for s in cache.state] == [(5, 4, 128)] * 3
    assert cache.conv.shape == (3, 5, 24, 128)
    assert mt.kv_bytes_per_token(cfg, 4) == cache.kv_bytes_per_token()
    eng.put([1], [prompt_of(20)])
    pool = eng._kv_data
    slot = eng.state.sequences[1].state_slot
    assert all(float(jnp.abs(s[slot]).max()) > 0 for s in pool.state)
    assert all(float(jnp.abs(s[-1]).max()) == 0.0 for s in pool.state)
    # the lanes past the channels never hold anything
    conv = np.asarray(pool.conv).reshape(3, 5, 3, 1024)
    assert np.abs(conv[..., :128]).max() > 0
    assert np.abs(conv[..., 128:]).max() == 0


def test_the_published_pool_tiles_whole():
    """At the published widths a slot's state is [16, 5120] float32, 2 x 40
    whole tiles: the resident bytes are the live ones but for the
    convolution's pool (5,120 channels in 6,144)."""
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    cfg = mt.model_config(H.benchmark_config(CONFIG))
    spec = LlamaRaggedRunner(cfg, RaggedInferenceConfig(
        max_seqs=2, chunk_size=16, block_size=16, num_blocks=8,
        max_blocks_per_seq=4)).state_spec
    assert spec == {
        "kind": "mamba1", "layers": 26, "heads": 1, "d_v": 5120, "d_k": 16,
        "taps": 4, "conv_width": 6144, "conv_channels": 5120,
        "state_shape": (16, 5120)}


def test_two_recurrent_kinds_in_one_model_are_refused_by_name():
    cfg = tiny(layer_kinds=("mamba1", "mamba2", "mamba1", "attn"))
    with pytest.raises(ValueError, match=r"\['mamba1', 'mamba2'\]"):
        from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
        LlamaRaggedRunner(cfg, RaggedInferenceConfig(
            max_seqs=2, chunk_size=16, block_size=16, num_blocks=8,
            max_blocks_per_seq=4, dtype="float32"))


# ------------------------------ (d) refusals ------------------------------ #


@pytest.mark.parametrize("feature, kw, call", H.REFUSALS)
def test_what_a_mamba1_model_refuses(model, feature, kw, call):
    """What would need a snapshot, a rewind or a shard of the recurrent
    state refuses by the feature's name and the layer kind ``'mamba1'``,
    in the recurrent kinds' one wording: construction options by
    ``config.validate``, calls by the engine."""
    from deepspeed_tpu.inference.v2.config import stateful_refusal
    said = FAMILY.refusal(model, feature, kw, call)
    assert said == stateful_refusal(feature, "mamba1")
    assert "('mamba1')" in said


# ---------------------- (e) registry, loader, the config ------------------ #


def test_config_from_hf_layer_list_and_parameter_counts():
    if not os.path.exists(H.CATALOG):
        pytest.skip("no catalog on this machine")
    row = H.catalog_row("AI21-Jamba2-3B")
    name, cfg = config_from_hf(row["config"])
    assert name == "jamba" and isinstance(cfg, JambaConfig)
    assert len(cfg.layer_kinds) == 28
    assert [i for i, k in enumerate(cfg.layer_kinds) if k == "attn"] \
        == [7, 21]
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size) \
        == (2560, 8192, 20, 1, 128, 65536)
    assert (cfg.mamba_inner, cfg.mamba_state, cfg.mamba_conv,
            cfg.mamba_dt_rank) == (5120, 16, 4, 160)
    assert not cfg.use_rope and not cfg.qkv_bias and cfg.tie_embeddings
    assert cfg.rms_eps == 1e-6
    # ISSUE 68's table
    assert mixer_param_count(cfg, "mamba1") == 41241792
    assert mixer_param_count(cfg, "attn") == 13762560
    assert param_counts(cfg) == (3029337472, 3029337472)


def test_the_benchmarks_configuration_is_the_published_model_whole():
    if not os.path.exists(H.CATALOG):
        pytest.skip("no catalog on this machine")
    row = H.catalog_row("AI21-Jamba2-3B")
    whole = H.benchmark_config(CONFIG)
    assert whole["_source"] == row["source_url"]
    for key, value in row["config"].items():
        assert whole[key] == value, key
    assert whole["reduced"] == {}
    cfg = mt.model_config(whole)
    assert param_counts(cfg)[0] == whole["parameters"] == 3029337472
    assert mt.kv_bytes_per_token(cfg) == 1024
    for word in ("attn_layer_period", "head_dim 128", "NO position code",
                 "WITH a bias", "RMSNorms", "time_step_min", "float32",
                 "tie_word_embeddings", "pre_ff_layernorm"):
        assert any(word in line for line in whole["assumed"]), word
    assert "WHOLE" in whole["deployment"]


@pytest.mark.parametrize("key, value, match", [
    ("num_experts", 16, "num_experts"),
    ("num_experts_per_tok", 2, "num_experts_per_tok"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("mamba_conv_bias", False, "mamba_conv_bias"),
    ("hidden_act", "gelu", "hidden_act"),
    ("sliding_window", 4096, "sliding_window"),
    ("attn_layer_offset", 9, "attn_layer_offset")])
def test_config_from_hf_refuses_what_it_does_not_serve(key, value, match):
    hf = dict(model_type="jamba", num_hidden_layers=4, hidden_size=64,
              num_attention_heads=4, num_key_value_heads=1,
              attn_layer_period=4, attn_layer_offset=2)
    H.hf_refuses(hf, {key: value}, match)


def test_an_auto_rank_is_a_sixteenth_of_the_hidden_size():
    _, cfg = config_from_hf(dict(model_type="jamba", num_hidden_layers=2,
                                 hidden_size=2560, mamba_dt_rank="auto"))
    assert cfg.mamba_dt_rank == 160


def test_loader_names_reach_every_leaf():
    """A checkpoint named as the family's are (``transformers``' modelling
    file, which ``test_jamba_reference.py`` runs: ``feed_forward``,
    ``pre_ff_layernorm``, ``final_layernorm``, the convolution
    ``[C, 1, K]``, ``A_log`` ``[channels, state]``) converts to the tree
    the runner serves, leaf for leaf."""
    cfg = tiny()
    params = jax.tree_util.tree_map(np.asarray, mt.init_params(cfg, 1))
    state = {"model.embed_tokens.weight": params["embed"]["embedding"],
             "model.final_layernorm.weight": params["final_norm"]["scale"],
             "lm_head.weight": params["embed"]["embedding"]}
    for i, kind in enumerate(cfg.layer_kinds):
        p, pre = params[f"layer_{i}"], f"model.layers.{i}"
        state[f"{pre}.input_layernorm.weight"] = p["input_norm"]["scale"]
        state[f"{pre}.pre_ff_layernorm.weight"] = p["post_attn_norm"]["scale"]
        H.hf_projections(state, f"{pre}.feed_forward", p["mlp"],
                         ("gate", "up", "down"))
        if kind == "attn":
            H.hf_projections(state, f"{pre}.self_attn", p["attn"], "qkvo")
            continue
        k, m = p["mamba"], f"{pre}.mamba"
        for n in ("in", "x", "dt", "out"):
            state[f"{m}.{n}_proj.weight"] = k[f"{n}_proj"].T
        state[f"{m}.dt_proj.bias"] = k["dt_bias"]
        state[f"{m}.conv1d.weight"] = k["conv_w"].T[:, None]
        state[f"{m}.conv1d.bias"] = k["conv_b"]
        state[f"{m}.A_log"] = k["A_log"].T
        state[f"{m}.D"] = k["D"]
        for n in ("dt", "b", "c"):
            state[f"{m}.{n}_layernorm.weight"] = k[f"{n}_norm"]
    from deepspeed_tpu.checkpoint.hf_loader import (SPECIAL_HANDLERS,
                                                    convert_hf_state)
    got = convert_hf_state("jamba", SPECIAL_HANDLERS["jamba"](state, {}),
                           tied=True)
    want = jax.tree_util.tree_leaves_with_path(params)
    have = dict(jax.tree_util.tree_leaves_with_path(got))
    assert len(have) == len(want)
    for path, leaf in want:
        assert np.array_equal(np.asarray(have[path]), leaf), path


def test_the_mixers_device_time_falls_under_the_regions_that_are_there(model):
    """No new region: the Mamba mixer opens ``ssm`` (Mamba-2's), the
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
    assert {"ssm", "attn_proj", "attn_core", "ffn_dense", "norm",
            "residual", "head", "embed", "kv_write"} <= opened
