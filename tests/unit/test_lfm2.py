"""LFM2 (gated short-convolution layers whose whole per-sequence state is
the convolution's carried inputs, one softmax layer in four, a dense layer
before the sparse ones, sigmoid + selection-bias experts) through the
normal engine, at a small size on the CPU: hidden 64, 4 / 2 heads of 16,
3 taps, 8 experts top-2, 5 layers (conv + dense, attn, conv, conv, conv).
The engine's logits against the plain reference
(``benchmark/reference/lfm2.py``, which ``test_lfm2_reference.py`` holds to
the family's own published code), the decode step's convolution
kernel against its ``jax.numpy`` twin, the router by hand, the share rule
of the model-configs guide, the cache's one part, the refusals and the
registry."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmark.model_types import lfm2_moe as mt
from benchmark.reference import lfm2 as reference
from deepspeed_tpu.inference.v2 import RaggedInferenceConfig
from deepspeed_tpu.inference.v2.model_runner import RaggedBatch
from deepspeed_tpu.models.lfm2 import Lfm2Config, param_counts
from deepspeed_tpu.models.registry import config_from_hf
from family_harness import prompt_of

REDUCED = ("num_hidden_layers", "layer_types", "num_dense_layers")


def tiny(**kw):
    return Lfm2Config.tiny(dtype=jnp.float32, param_dtype=jnp.float32, **kw)


#: float32 engine against a float32 reference at highest precision: what
#: is left is the order of the sums (a chunk's convolution against the
#: whole sequence's, the grouped matmul against the dense mask), a few
#: 1e-6 on logits of size 1
FAMILY = H.Family(mt, tiny, tol=2e-4)
engine = FAMILY.engine


@pytest.fixture(scope="module")
def model():
    return FAMILY.model()


# ------------------------- (b) engine vs reference ------------------------ #


@H.chunk_and_decode
def test_engine_logits_match_the_reference(model, chunk, decode):
    """A 37-token prompt prefilled in one chunk or in three of uneven
    real lengths (16, 16, 5: a chunk's convolution reads the inputs the
    last chunk carried), 8 tokens decoded through the fused loop (two
    loops of 4: the second reads carried inputs and K/V rows a flush
    lies behind) or step by step, then one more position's logits: each
    against the reference's forward pass over the whole sequence."""
    stats = FAMILY.serve_against_reference(model, chunk, decode,
                                           loops=(4, 4)).pipeline_stats
    # 8 decode steps and the one-token step: a slot live in each, holding
    # 4 conv layers x 2 carried inputs x 64 lanes x 4 B and nothing else
    assert stats["state_slots_live"] == 9
    assert stats["state_bytes_live"] == 9 * 4 * 2 * 64 * 4
    assert stats["conv_steps"] == 9 * 4
    assert stats["conv_steps_in_place"] == 0            # the CPU's path
    # a convolution has no recurrence to chunk
    assert stats["linear_attn_prefill_tokens"] == 0
    # ONE attention layer keeps K/V rows: 2 kv heads of 16, float32
    live = (sum(range(38, 46)) if decode == "pipelined"
            else 4 * 37 + 4 * 41) + 46
    assert stats["decode_kv_rows_live"] == live
    assert stats["kv_bytes_live"] == live * 1 * 2 * 2 * 16 * 4
    if decode == "fused":
        assert stats["moe_rows_routed"] == 8 * 4 * 2    # every expert held
        assert stats["moe_rows_elsewhere"] == 0


def test_two_sequences_decode_as_they_do_alone_and_a_slot_starts_fresh(model):
    """Two sequences of different lengths in one batch (in a step where
    one of them has run out of prompt the other's row has ``n_tokens``
    0 and keeps its carried inputs), and then a third in a slot the
    first one left: each decodes what it decodes alone (a fresh row does
    not see the last tenant's carried inputs, which are still there)."""
    def garbage(eng, slot):
        assert float(jnp.abs(eng._kv_data.conv[:, slot]).max()) > 0

    eng, slot = FAMILY.two_sequences_decode_as_alone(
        model, (21, 43, 18), after_flush=garbage)
    assert eng.state.sequences[3].state_slot == slot


def test_an_idle_row_of_a_step_keeps_its_carried_inputs(model):
    """One step of the runner over two rows, the second with ``n_tokens``
    0: its slot of the pool is what it was, bit for bit, the first row's
    is not."""
    cfg, params = model
    eng = engine(cfg, params, 16)
    eng.put([1, 2], [prompt_of(9, seed=1), prompt_of(12, seed=2)])
    kv = eng._kv_data
    s1, s2 = (eng.state.sequences[u].state_slot for u in (1, 2))
    tables = np.zeros((2, 6), np.int32)
    for i, u in enumerate((1, 2)):
        blocks = eng.state.sequences[u].kv_blocks
        tables[i, :len(blocks)] = blocks
    batch = RaggedBatch(
        tokens=jnp.asarray([[5], [6]], jnp.int32),
        start_pos=jnp.asarray([9, 12], jnp.int32),
        n_tokens=jnp.asarray([1, 0], jnp.int32),
        block_tables=jnp.asarray(tables),
        state_slots=jnp.asarray([s1, s2], jnp.int32))
    before = np.asarray(kv.conv)
    _, after = eng.runner._step(eng.params, kv, batch)
    after = np.asarray(after.conv)
    assert np.array_equal(after[:, s2], before[:, s2])
    assert not np.array_equal(after[:, s1], before[:, s1])
    # the row's new last input is in; the one before it moved up
    assert np.array_equal(after[:, s1, 0], before[:, s1, 1])


# --------------------- (c) the decode step's convolution ------------------ #


@pytest.mark.parametrize("S, W, dtype, taps, act, bias", [
    (4, 64, jnp.float32, 3, None, False),
    (32, 2048, jnp.bfloat16, 3, None, False),
    (24, 2048, jnp.bfloat16, 3, None, False),
    (32, 2048, jnp.bfloat16, 4, "silu", True),
], ids=["lfm2-toy", "lfm2-tiles-16-rows", "lfm2-tiles-8-rows",
        "4-taps-silu-biased"])
def test_the_conv_kernel_is_its_jnp_twin_at_any_taps_and_activation(
        S, W, dtype, taps, act, bias):
    """``short_conv_decode_step`` interpreted against
    ``llama_runner._short_conv``'s gather, convolve and scatter, bit for
    bit, at LFM2's 3 taps with no activation and no bias (the cell's
    width, 16 and 8 rows a grid step) and at the other families' corner
    of the one body, four taps with SiLU and a bias
    (``tests/unit/test_short_conv.py`` holds KDA's and Mamba-2's own
    shapes, untouched). Ordinary, fresh and idle rows in one
    call."""
    from deepspeed_tpu.inference.v2.llama_runner import _short_conv
    from deepspeed_tpu.ops.kernels import short_conv as sc
    rng = np.random.default_rng(S + W + taps)
    layers, rows, si = 3, S + 5, 2
    pool = jnp.asarray(rng.normal(size=sc.pool_shape(layers, rows, taps, W)),
                       dtype)
    slots = rng.permutation(rows)[:S].astype(np.int32)
    x = jnp.asarray(rng.normal(size=(S, 1, W)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(taps, W)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(W,)), jnp.float32) if bias else None
    kind = rng.integers(0, 4, S)      # 0 ordinary, 1 fresh, 2 idle, 3 both
    kind[:4] = [0, 1, 2, 3]
    fresh, live = np.isin(kind, (1, 3)), ~np.isin(kind, (2, 3))
    batch = RaggedBatch(
        tokens=jnp.zeros((S, 1), jnp.int32),
        start_pos=jnp.asarray(np.where(fresh, 0, 7), jnp.int32),
        n_tokens=jnp.asarray(live, jnp.int32),
        block_tables=jnp.zeros((S, 1), jnp.int32),
        state_slots=jnp.asarray(slots))
    want_pool, want_y = jax.jit(
        _short_conv, static_argnums=1, static_argnames=("activation",))(
        pool, si, batch, jnp.asarray(fresh), jnp.asarray(live), x, w, b,
        activation=act)
    got_pool, got_y = sc.short_conv_decode_step(
        pool, si, jnp.asarray(slots), x[:, 0], w, b, jnp.asarray(fresh),
        jnp.asarray(live), activation=act, interpret=True)
    assert np.array_equal(np.asarray(got_y), np.asarray(want_y[:, 0]))
    f32 = lambda a: np.asarray(a.astype(jnp.float32))      # noqa: E731
    assert np.array_equal(f32(got_pool), f32(want_pool))
    # a live row carries its slot's last taps - 2 inputs and the new one
    r = int(np.flatnonzero(kind == 0)[0])
    old = f32(pool)[si, slots[r]].reshape(taps - 1, W)
    new = f32(got_pool)[si, slots[r]].reshape(taps - 1, W)
    assert np.array_equal(new[:-1], old[1:])
    assert np.array_equal(new[-1], f32(x[r, 0].astype(dtype)))
    if act is None and not bias:
        # by hand: the plain K-term sum, no activation
        prev = np.zeros((taps - 1, W)) if fresh[r] else old
        hand = (prev * np.asarray(w)[:-1]).sum(0) \
            + np.asarray(x[r, 0]) * np.asarray(w)[-1]
        assert np.allclose(np.asarray(got_y)[r], hand, rtol=1e-5, atol=1e-5)


def test_the_pool_and_the_dispatch_at_three_taps():
    from deepspeed_tpu.ops.kernels import short_conv as sc
    # the cell's pool: a slot's [2, 2048] as 32 rows of 128 lanes = 8 KB
    assert sc.pool_shape(7, 129, 3, 2048) == (7, 129, 32, 128)
    assert sc.pool_shape(4, 5, 3, 64) == (4, 5, 2, 64)
    assert sc.decode_uses_kernel(128, 2048, jnp.bfloat16, backend="tpu")
    assert not sc.decode_uses_kernel(128, 2048, jnp.bfloat16, backend="cpu")


def test_decode_through_the_conv_kernel_serves_the_jnp_paths_tokens(
        model, monkeypatch):
    """``Family.decode_through_the_conv_kernel``; the engine counts the
    layer-steps, in place when the kernel took them."""
    plain, forced = FAMILY.decode_through_the_conv_kernel(model, monkeypatch)
    assert (plain["conv_steps"], plain["conv_steps_in_place"]) == (9 * 4, 0)
    assert (forced["conv_steps"], forced["conv_steps_in_place"]) \
        == (9 * 4, 9 * 4)


# ------------------------------ (d) the router ---------------------------- #


def test_the_router_by_hand():
    """Selection by ``s + b``, weights from ``s`` alone, ``+ 1e-6`` in the
    renormalisation: a case worked by hand in which the bias changes the
    selection, through ``route_topk`` (the engine's) and through the
    reference's mask."""
    from deepspeed_tpu.moe.sharded_moe import route_topk
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0]])
    bias = jnp.asarray([0.0, -0.2, 0.0, 0.0])
    s = 1 / (1 + np.exp(-np.asarray(logits[0], np.float64)))
    # unbiased: experts 0 and 1; biased: 1 falls to 0.531 under 2's 0.622
    assert s[1] > s[2] and s[1] - 0.2 < s[2]
    idx, w, _ = route_topk(logits, 2, score="sigmoid", bias=bias,
                           norm_eps=1e-6)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 2]
    want = {0: s[0] / (s[0] + s[2] + 1e-6), 2: s[2] / (s[0] + s[2] + 1e-6)}
    for e, got in zip(np.asarray(idx[0]).tolist(), np.asarray(w[0])):
        assert got == pytest.approx(want[e], rel=1e-6)
    # the epsilon is the family's, not the default's: they differ in the
    # seventh digit and the sum is short of 1 by it
    w20 = route_topk(logits, 2, score="sigmoid", bias=bias)[1]
    assert float(w.sum()) == pytest.approx(1 - 1e-6 / (s[0] + s[2]),
                                           abs=1e-7)
    assert float(w20.sum()) == pytest.approx(1.0, abs=1e-7)
    idx0, _, _ = route_topk(logits, 2, score="sigmoid")
    assert sorted(np.asarray(idx0[0]).tolist()) == [0, 1]
    # the reference's mask says the same
    E, M = 4, 4
    p = {"gate": jnp.eye(M, E), "sel_bias": bias,
         "wi_gate": jnp.ones((E, M, 2)), "wi_up": jnp.ones((E, M, 2)),
         "wo": jnp.stack([jnp.full((2, M), float(e + 1))
                          for e in range(E)])}
    z = logits[None]                                    # [1, 1, 4]
    y = reference._sparse_mlp(p, z, top_k=2, first=0, scaling=1.0)
    act = float(jax.nn.silu(z.sum()) * z.sum())         # every expert's
    hand = 2 * act * (want[0] * 1 + want[2] * 3)
    assert float(y[0, 0, 0]) == pytest.approx(hand, rel=1e-5)
    off = reference._sparse_mlp(p, z, top_k=2, first=0, scaling=1.0,
                                biased=False)
    assert abs(float(off[0, 0, 0]) - hand) > 1e-2


def test_the_engines_sparse_block_is_the_references(model):
    from deepspeed_tpu.inference.v2.llama_runner import _moe_mlp
    cfg, params = model
    p = dict(params["layer_1"]["moe"])
    p["sel_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (8,))
    h = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 64))
    with jax.default_matmul_precision("highest"):
        got = _moe_mlp(p, h, cfg, jnp.float32)[0]
        want = reference._sparse_mlp(p, h, top_k=2, first=0, scaling=1.0)
        unbiased = reference._sparse_mlp(p, h, top_k=2, first=0,
                                         scaling=1.0, biased=False)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(unbiased - want).max()) > 1e-2


# ------------------------------ (e) shares -------------------------------- #


def test_the_two_halves_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts of two shares (4 of 8 experts
    each) equal the uncut reference's layer, in the engine's sparse block
    and in the reference alike (no shared expert to count once). The cell
    holds every expert; the held-share path stays as it is."""
    from deepspeed_tpu.inference.v2.llama_runner import _moe_mlp
    whole_cfg = tiny()
    whole = mt.init_params(whole_cfg, 11)["layer_1"]["moe"]
    whole["sel_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (8,))
    h = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 64))
    kw = dict(top_k=2, scaling=1.0)
    with jax.default_matmul_precision("highest"):
        uncut = reference._sparse_mlp(whole, h, first=0, **kw)
        parts, refs = [], []
        for first in (0, 4):
            cfg = dataclasses.replace(whole_cfg, experts_first=first,
                                      experts_held=4)
            p = dict(whole, **{n: whole[n][first:first + 4]
                               for n in ("wi_gate", "wi_up", "wo")})
            parts.append(_moe_mlp(p, h, cfg, jnp.float32)[0])
            refs.append(reference._sparse_mlp(p, h, first=first, **kw))
    for part, ref in zip(parts, refs):
        assert float(jnp.abs(part).max()) > 1e-3
        assert float(jnp.abs(part - ref).max()) < 1e-5
    assert float(jnp.abs(sum(parts) - uncut).max()) < 1e-5
    assert float(jnp.abs(sum(refs) - uncut).max()) < 1e-5


# ------------------------- (f) the cache's one part ----------------------- #


def test_one_cache_value_holds_kv_planes_and_carried_inputs_and_no_state(
        model):
    cfg, params = model
    eng = engine(cfg, params)
    r, cache = eng.runner, eng.kv_cache
    assert (r.kv_planes, r.kv_layers, r.kv_heads, r.head_dim) \
        == (2, 1, 2, 16)
    assert r.state_spec == {"kind": "conv", "layers": 4, "heads": 0,
                            "taps": 3, "conv_width": 64}
    assert cache.state is None and cache.stateful
    assert cache.conv.shape == (4, 5, 2, 64)
    assert cache.state_bytes_per_slot() == 4 * 2 * 64 * 4
    assert cache.memory_bytes() == 2 * 25 * 16 * 32 * 4 \
        + 5 * cache.state_bytes_per_slot()
    assert mt.kv_bytes_per_token(cfg, 4) == cache.kv_bytes_per_token()
    pool = eng._kv_data
    assert type(pool).__name__ == "KVPool" and pool.state is None
    eng.put([1], [prompt_of(20)])
    pool = eng._kv_data
    assert pool.state is None
    slot = eng.state.sequences[1].state_slot
    assert float(jnp.abs(pool.conv[:, slot]).max()) > 0
    assert float(jnp.abs(pool.conv[:, -1]).max()) == 0.0    # the idle row
    eng.flush(1)
    assert len(eng.state.state_slots_free) == 4


def test_two_recurrent_kinds_in_one_model_are_refused_by_name():
    cfg = tiny(layer_kinds=("conv", "kda", "attn", "conv", "conv"))
    with pytest.raises(ValueError, match=r"\['conv', 'kda'\]"):
        from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
        LlamaRaggedRunner(cfg, RaggedInferenceConfig(
            max_seqs=2, chunk_size=16, block_size=16, num_blocks=8,
            max_blocks_per_seq=4, dtype="float32"))


# ------------------------------ (g) refusals ------------------------------ #


@pytest.mark.parametrize("feature, kw, call", H.REFUSALS)
def test_what_a_conv_model_refuses(model, feature, kw, call):
    """What would need a snapshot, a rewind or a shard of the carried
    inputs refuses by the feature's name and the layer kind ``'conv'``,
    in the recurrent kinds' one wording: construction options by
    ``config.validate``, calls by the engine."""
    from deepspeed_tpu.inference.v2.config import stateful_refusal
    said = FAMILY.refusal(model, feature, kw, call)
    assert said == stateful_refusal(feature, "conv")
    assert "('conv')" in said


# ------------------------- (h) registry and the cut ----------------------- #


def test_config_from_hf_layer_lists_and_parameter_counts():
    if not os.path.exists(H.CATALOG):
        pytest.skip("no catalog on this machine")
    row = H.catalog_row("LFM2-24B-A2B")
    name, cfg = config_from_hf(row["config"])
    assert name == "lfm2_moe" and isinstance(cfg, Lfm2Config)
    assert len(cfg.layer_kinds) == 40
    assert cfg.layer_kinds.count("attn") == 10
    assert [i for i, k in enumerate(cfg.layer_kinds) if k == "attn"] \
        == list(range(2, 40, 4))
    assert cfg.ffn_kinds == ("dense",) * 2 + ("moe",) * 38
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.conv_taps, cfg.vocab_size) == (2048, 32, 8, 64, 3, 65536)
    assert (cfg.num_experts, cfg.experts_top_k, cfg.intermediate_size,
            cfg.dense_intermediate_size) == (64, 4, 1536, 11776)
    assert (cfg.router_score, cfg.router_bias, cfg.norm_topk_prob,
            cfg.routed_scaling, cfg.router_norm_eps) \
        == ("sigmoid", True, True, 1.0, 1e-6)
    assert cfg.rope_theta == 1e6 and cfg.rms_eps == 1e-5
    assert cfg.tie_embeddings and cfg.qk_norm == "head"
    total, active = param_counts(cfg)
    # "24B-A2B": the name's own figures
    assert 23.0e9 < total < 24.5e9 and 2.0e9 < active < 2.5e9


def test_the_benchmarks_cut_is_layers_one_to_nine_of_the_published_model():
    if not os.path.exists(H.CATALOG):
        pytest.skip("no catalog on this machine")
    row = H.catalog_row("LFM2-24B-A2B")
    cut = H.benchmark_config("lfm2-24b-a2b.json")
    assert cut["_source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in REDUCED:
            assert cut[key] == value, key
    assert sorted(cut["reduced"]) == sorted(REDUCED)
    assert cut["layer_types"] == row["config"]["layer_types"][1:10]
    assert (cut["num_hidden_layers"], cut["num_dense_layers"]) == (9, 1)
    cfg = mt.model_config(cut)
    # one leading dense layer, then two whole periods at the published 3:1
    assert cfg.layer_kinds == ("conv", "attn", "conv", "conv", "conv",
                               "attn", "conv", "conv", "conv")
    assert cfg.ffn_kinds == ("dense",) + ("moe",) * 8
    assert cfg.held == cfg.num_experts == 64            # EVERY expert held
    assert cfg.vocab_size == row["config"]["vocab_size"]
    total, _ = param_counts(cfg)
    assert total == cut["parameters"]
    assert abs(total / 5.178e9 - 1) < 5e-3              # ISSUE 59's count
    for word in ("tie_word_embeddings", "head_dim", "intermediate_size",
                 "sigmoid", "1e-6", "SELECTION"):
        assert any(word in line for line in cut["assumed"]), word


@pytest.mark.parametrize("key, value", [
    ("conv_bias", True), ("block_auto_adjust_ff_dim", True),
    ("layer_types", ["conv", "sliding_attention"]),
    ("rope_parameters", {"rope_type": "yarn", "rope_theta": 1e6})])
def test_config_from_hf_refuses_what_it_does_not_implement(key, value):
    hf = dict(model_type="lfm2_moe", num_hidden_layers=2,
              layer_types=["conv", "full_attention"])
    H.hf_refuses(hf, {key: value},
                 key if key != "rope_parameters" else "rotary")


def test_the_dense_sibling_leaves_its_width_key_out_and_is_refused():
    with pytest.raises(ValueError, match="block_auto_adjust_ff_dim"):
        config_from_hf(dict(model_type="lfm2", num_hidden_layers=2))


def test_the_region_is_in_the_vocabulary_and_in_the_step():
    from deepspeed_tpu.telemetry.trace import REGIONS
    assert "conv_mixer" in REGIONS and len(REGIONS) == 24
