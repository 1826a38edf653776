"""OLMoE (``model_type: olmoe``): 64-expert-style sparse layers with the
top-k kept unrenormalised, and RMSNorm over the whole q and k projections,
against the plain float32 reference ``benchmark/reference/olmoe.py``.

Tiny, on the CPU, float32 on both sides at ``highest`` precision: the
flax model, the ragged engine (prefill in chunks, then the pipelined and
the fused decode paths through the paged cache), the registry entry, the
HF name map and the routed-row counter. The chip run at the published
widths is ``tools/chip_parity.py --config olmoe-1b-7b``.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmark.reference import olmoe as reference
from deepspeed_tpu.inference.v2 import RaggedInferenceConfig
from deepspeed_tpu.models.mixtral import Mixtral, MixtralConfig

#: same precision on both sides (float32 inputs, float32 accumulation,
#: the same operations in another order), so the two agree to rounding: a
#: few 1e-6 of the logits' spread. A bfloat16 step is off by ~1e-2 and a
#: per-head norm in place of the projection-wide one by ~1e-1 (both
#: checked below), so 1e-4 tells them apart with two orders to spare on
#: either side.
RTOL = 1e-4
LAYERS = 2

#: the catalog's ``config`` of OLMoE-1B-7B-0125-Instruct
#: (https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json)
CATALOG = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


def tiny_cfg(top_k=2, **kw):
    kw.setdefault("dtype", jnp.float32)
    return MixtralConfig.tiny(
        num_layers=LAYERS, hidden_size=64, num_heads=4, num_kv_heads=4,
        intermediate_size=32, num_experts=8, experts_top_k=top_k,
        norm_topk_prob=False, qk_norm=True, rms_eps=1e-5,
        param_dtype=jnp.float32, attention_impl="xla", **kw)


def tiny_params(cfg, seed=0):
    """Seeded weights with every norm scale away from one (a scale left
    out would otherwise go unnoticed) and each expert at its own fan-in."""
    key = jax.random.PRNGKey(seed)
    params = Mixtral(cfg).init({"params": key, "gating": key},
                               jnp.zeros((1, 8), jnp.int32))["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name, k = jax.tree_util.keystr(path), jax.random.fold_in(key, i)
        if "scale" in name:
            leaf = 1.0 + 0.3 * jax.random.normal(k, leaf.shape)
        elif "'wi_" in name or "'wo'" in name:
            leaf = jax.random.normal(k, leaf.shape) * leaf.shape[-2] ** -0.5
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def ref_logits(cfg, params, tokens, at=None):
    tokens = jnp.asarray(tokens, jnp.int32)
    if at is None:
        at = jnp.tile(jnp.arange(tokens.shape[1])[None], (tokens.shape[0], 1))
    return np.asarray(reference.logits(
        params, tokens, jnp.asarray(at, jnp.int32), num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, top_k=cfg.experts_top_k,
        rope_theta=cfg.rope_theta, rms_eps=cfg.rms_eps))


def rel_err(ours, theirs):
    """Largest difference over the reference's spread."""
    return float(np.abs(np.asarray(ours) - theirs).max() / theirs.std())


def tokens_of(seed, shape):
    return np.random.RandomState(seed).randint(1, 500, shape).astype(np.int32)


#: chunks and blocks of 8 rows, so that two short prompts take several of
#: each; prefill chunks capped at the default
make_engine = functools.partial(
    H.toy_engine, chunk_size=8, block_size=8, num_blocks=40,
    max_blocks_per_seq=8, prefill_chunk_cap=256)


# ------------------------- (i) the flax model ------------------------- #

@pytest.mark.parametrize("top_k", [2, 4])
def test_flax_model_matches_the_reference(top_k):
    cfg = tiny_cfg(top_k)
    params = tiny_params(cfg)
    toks = tokens_of(1, (2, 24))
    with jax.default_matmul_precision("highest"):
        ours = Mixtral(cfg).apply({"params": params}, jnp.asarray(toks),
                                  train=False)
    assert rel_err(ours, ref_logits(cfg, params, toks)) < RTOL


# ---------- (ii) the engine: prefill, then both decode paths ---------- #

@pytest.fixture(scope="module", params=[2, 4], ids=["top2", "top4"])
def served(request):
    """Two prompts prefilled in chunks of 8, then 3 tokens through
    ``decode_pipelined`` and 4 through the fused ``decode_batch``, greedy;
    the logits ``put`` returns on the way, and one more teacher-forced
    step whose logits read the cache both decode paths wrote."""
    cfg = tiny_cfg(request.param)
    params = tiny_params(cfg)
    toks = tokens_of(2, (2, 21))
    prompts = [list(map(int, toks[0, :21])), list(map(int, toks[1, :13]))]
    eng = make_engine(cfg, params)
    with jax.default_matmul_precision("highest"):
        first = eng.put([0, 1], prompts)                  # logits
        streams = {u: list(p) for u, p in enumerate(prompts)}
        last = {u: int(np.argmax(first[u])) for u in (0, 1)}
        piped = eng.decode_pipelined([0, 1], [last[0], last[1]], 3)
        for u in (0, 1):
            streams[u] += [last[u]] + [int(t) for t in piped[u][:-1]]
            last[u] = int(piped[u][-1])
        fused = eng.decode_batch([0, 1], [last[0], last[1]], 4)
        for u in (0, 1):
            streams[u] += [last[u]] + [int(t) for t in fused[u][:-1]]
            last[u] = int(fused[u][-1])
        after = eng.put([0, 1], [[last[0]], [last[1]]])   # logits
        for u in (0, 1):
            streams[u].append(last[u])
    return cfg, params, eng, prompts, streams, first, piped, fused, after


def _teacher_forced(cfg, params, streams, u):
    return ref_logits(cfg, params, [streams[u]])[0]


@pytest.mark.parametrize("u", [0, 1])
def test_engine_prefill_logits_match_the_reference(served, u):
    cfg, params, _, prompts, streams, first, _, _, _ = served
    ref = _teacher_forced(cfg, params, streams, u)
    assert rel_err(first[u], ref[len(prompts[u]) - 1]) < RTOL


@pytest.mark.parametrize("u", [0, 1])
def test_every_decoded_token_is_the_references_best(served, u):
    """Both decode paths, at every served position: the token is the
    argmax of the reference's full forward over what was served so far."""
    cfg, params, _, prompts, streams, _, piped, fused, _ = served
    ref = _teacher_forced(cfg, params, streams, u)
    n0 = len(prompts[u])
    got = [int(t) for t in piped[u]] + [int(t) for t in fused[u]]
    # position n0 + i holds the token fed at step i and predicts got[i]
    want = [int(ref[n0 + i].argmax()) for i in range(len(got))]
    assert got == want


@pytest.mark.parametrize("u", [0, 1])
def test_logits_after_both_decode_paths_read_the_cache_they_wrote(served, u):
    cfg, params, _, _, streams, _, _, _, after = served
    ref = _teacher_forced(cfg, params, streams, u)
    assert rel_err(after[u], ref[len(streams[u]) - 1]) < RTOL


def test_a_bfloat16_step_is_outside_the_tolerance():
    cfg = tiny_cfg(2)
    params = tiny_params(cfg)
    prompt = list(map(int, tokens_of(3, (21,))))
    eng = make_engine(dataclasses.replace(cfg, dtype=jnp.bfloat16), params,
                      dtype="bfloat16")
    got = eng.put([0], [prompt])[0]
    ref = ref_logits(cfg, params, [prompt])[0, -1]
    assert rel_err(got, ref) > 10 * RTOL


def test_a_per_head_norm_is_outside_the_tolerance():
    """The reference with q and k normalised per head of 16 values in
    place of the whole projection of 64: what the tolerance must catch."""
    cfg = tiny_cfg(2)
    params = tiny_params(cfg)
    toks = tokens_of(4, (1, 16))
    whole = ref_logits(cfg, params, toks)
    orig = reference._rms

    def per_head(x, scale, eps):
        if x.shape[-1] != cfg.hidden_size or scale.shape[0] != x.shape[-1]:
            return orig(x, scale, eps)
        heads = x.reshape(x.shape[:-1] + (cfg.num_heads, cfg.head_dim))
        return orig(heads, jnp.ones((cfg.head_dim,)), eps).reshape(x.shape) \
            * scale

    # _rms also serves the layer norms (same width here): patch it only
    # inside the attention block
    attention = reference._attention

    def patched_attention(p, h, **kw):
        reference._rms = per_head
        try:
            return attention(p, h, **kw)
        finally:
            reference._rms = orig

    reference._attention = patched_attention
    try:
        heads = ref_logits(cfg, params, toks)
    finally:
        reference._attention = attention
    assert rel_err(heads, whole) > 100 * RTOL


# ------------- (iii) the norm is traced only where it is on ------------ #

def _rsqrt_count(cfg):
    params = tiny_params(tiny_cfg(2))
    eng = make_engine(cfg, params if cfg.qk_norm
                      else _without_qk_norm(params))
    from deepspeed_tpu.analysis import serve_program_calls
    fn, args, static = serve_program_calls(eng, ("step",))["step"]
    return str(jax.make_jaxpr(fn)(*args, **static)).count("rsqrt")


def _without_qk_norm(params):
    params = jax.tree_util.tree_map(lambda x: x, params)
    for i in range(LAYERS):
        attn = dict(params[f"layer_{i}"]["attn"])
        attn.pop("q_norm"), attn.pop("k_norm")
        params[f"layer_{i}"] = dict(params[f"layer_{i}"], attn=attn)
    return params


def test_qk_norm_adds_two_norms_a_layer_and_nothing_when_off():
    on = _rsqrt_count(tiny_cfg(2))
    off = _rsqrt_count(dataclasses.replace(tiny_cfg(2), qk_norm=False))
    fields = {f.name: getattr(tiny_cfg(2), f.name)
              for f in dataclasses.fields(MixtralConfig)
              if f.name != "qk_norm"}
    default = _rsqrt_count(MixtralConfig(**fields))   # the field left out
    assert on - off == 2 * LAYERS
    assert off == default == 2 * LAYERS + 1   # two layer norms + the final


# ----------------------- (iv) the registry entry ----------------------- #

def test_config_from_hf_on_the_catalogs_config():
    from deepspeed_tpu.models.registry import config_from_hf
    arch, cfg = config_from_hf(CATALOG)
    assert arch == "olmoe" and isinstance(cfg, MixtralConfig)
    assert (cfg.num_experts, cfg.experts_top_k) == (64, 8)
    assert cfg.norm_topk_prob is False and cfg.qk_norm is True
    assert cfg.tie_embeddings is False and cfg.shared_expert_size == 0
    assert (cfg.num_layers, cfg.hidden_size, cfg.intermediate_size,
            cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) \
        == (16, 2048, 1024, 16, 16, 128)
    assert cfg.vocab_size == 50304 and cfg.rope_theta == 10000
    with pytest.raises(ValueError, match="clip_qkv"):
        config_from_hf(dict(CATALOG, clip_qkv=8.0))


def test_qk_norm_under_tensor_parallel_is_refused_at_construction():
    cfg = tiny_cfg(2)
    icfg = RaggedInferenceConfig(max_seqs=2, chunk_size=8, block_size=8,
                                 num_blocks=8, max_blocks_per_seq=4,
                                 tp_size=2, ep_size=2)
    with pytest.raises(ValueError, match="qk_norm"):
        icfg.validate(cfg)


# ------------------------ (v) the HF name map -------------------------- #

def _hf_state(cfg, params):
    """The tiny tree under the names an ``olmoe`` checkpoint uses."""
    params = jax.tree_util.tree_map(np.asarray, params)
    state = H.hf_trunk(params)
    for i in range(cfg.num_layers):
        p, pre = params[f"layer_{i}"], f"model.layers.{i}."
        state[pre + "input_layernorm.weight"] = p["input_norm"]["scale"]
        state[pre + "post_attention_layernorm.weight"] = \
            p["post_attn_norm"]["scale"]
        H.hf_projections(state, pre + "self_attn", p["attn"], "qkvo")
        for n in "qk":
            state[pre + f"self_attn.{n}_norm.weight"] = \
                p["attn"][f"{n}_norm"]["scale"]
        state[pre + "mlp.gate.weight"] = p["moe"]["gate"].T
        H.hf_experts(state, pre + "mlp.experts", p["moe"], (
            ("wi_gate", "gate_proj"), ("wi_up", "up_proj"),
            ("wo", "down_proj")))
    return state


def test_hf_name_map_loads_an_olmoe_named_state_dict():
    from deepspeed_tpu.checkpoint.hf_loader import (SPECIAL_HANDLERS,
                                                    convert_hf_state)
    cfg = tiny_cfg(2)
    params = tiny_params(cfg)
    state = SPECIAL_HANDLERS["olmoe"](_hf_state(cfg, params), {})
    loaded = convert_hf_state("olmoe", state, strict=True)
    flat = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    got = dict(jax.tree_util.tree_flatten_with_path(loaded)[0])
    assert set(got) == set(flat)
    for path, leaf in flat.items():
        np.testing.assert_array_equal(got[path], np.asarray(leaf),
                                      err_msg=jax.tree_util.keystr(path))


def test_served_checkpoint_matches_the_published_modelling_code(tmp_path):
    """A tiny ``OlmoeForCausalLM`` of ``transformers`` saved to disk,
    served by ``build_hf_engine``: logits against the published code's
    own, and the reference against both."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    if not hasattr(transformers, "OlmoeForCausalLM"):
        pytest.skip("this transformers has no olmoe")
    from deepspeed_tpu.checkpoint.hf_loader import load_hf_model
    from deepspeed_tpu.inference.v2.engine_factory import build_hf_engine
    torch.manual_seed(0)
    hf_cfg = transformers.OlmoeConfig(
        vocab_size=96, hidden_size=32, intermediate_size=24,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=False,
        max_position_embeddings=64, tie_word_embeddings=False,
        rms_norm_eps=1e-5, rope_theta=10000.0)
    hf_model = transformers.OlmoeForCausalLM(hf_cfg).eval()
    with torch.no_grad():                 # norm scales away from one
        for name, p in hf_model.named_parameters():
            if "norm" in name:
                p.add_(0.3 * torch.randn_like(p))
    hf_model.save_pretrained(tmp_path)
    prompt = list(map(int, np.random.RandomState(5).randint(1, 90, 11)))
    with torch.no_grad():
        theirs = hf_model(torch.tensor([prompt])).logits[0].numpy()
    eng = build_hf_engine(str(tmp_path), dtype="float32",
                          engine_config=RaggedInferenceConfig(
                              max_seqs=2, chunk_size=8, block_size=4,
                              num_blocks=32, max_blocks_per_seq=8,
                              dtype="float32"))
    with jax.default_matmul_precision("highest"):
        served = eng.put([0], [prompt])[0]
    assert rel_err(served, theirs[-1]) < RTOL
    _, cfg, params = load_hf_model(str(tmp_path))
    assert cfg.qk_norm and not cfg.norm_topk_prob
    assert rel_err(ref_logits(cfg, params, [prompt])[0], theirs) < RTOL


# --------------------- (vi) the routed-row counter --------------------- #

@pytest.mark.parametrize("top_k", [2, 4])
def test_routed_row_counter_counts_real_rows_only(top_k):
    """Three live sequences in four slots: the fused loop's count is real
    rows x k x layers x steps; the idle slot's row is computed and not
    counted. ``moe_rows_hottest`` is the busiest expert's share scaled to
    all experts, so it is at least the routed count."""
    cfg = tiny_cfg(top_k)
    eng = make_engine(cfg, tiny_params(cfg))
    prompts = [list(map(int, tokens_of(6 + i, (5 + 3 * i,))))
               for i in range(3)]
    first = eng.put([0, 1, 2], prompts, _greedy=True)
    assert eng.pipeline_stats["moe_rows_routed"] == 0   # fused loop only
    eng.decode_batch([0, 1, 2], [first[u] for u in (0, 1, 2)], 4)
    stats = eng.pipeline_stats
    assert stats["moe_rows_routed"] == 3 * top_k * LAYERS * 4
    assert stats["moe_rows_routed"] <= stats["moe_rows_hottest"] \
        <= stats["moe_rows_routed"] * cfg.num_experts
    assert stats["moe_rows_hottest"] % cfg.num_experts == 0


def test_moe_mlp_counts_valid_positions_per_expert():
    from deepspeed_tpu.inference.v2.llama_runner import _moe_mlp
    cfg = tiny_cfg(2)
    p_moe = tiny_params(cfg)["layer_0"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(3), (3, 4, cfg.hidden_size))
    valid = jnp.asarray([[1, 1, 1, 0], [1, 0, 0, 0], [0, 0, 0, 0]], bool)
    y, rows = _moe_mlp(p_moe, h, cfg, jnp.float32, valid=valid)
    y_all, none = _moe_mlp(p_moe, h, cfg, jnp.float32)
    # behind the experts' counts, the grouped kernel's two: 0 on this path
    assert none is None and rows.shape == (cfg.num_experts + 2,)
    rows, kernel = rows[:-2], rows[-2:]
    assert int(rows.sum()) == 4 * cfg.experts_top_k and not kernel.any()
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_all))
    logits = h.reshape(12, -1) @ p_moe["gate"]
    want = np.zeros(cfg.num_experts, np.int64)
    for r in np.flatnonzero(np.asarray(valid).reshape(-1)):
        want[np.argsort(-np.asarray(logits[r]))[:cfg.experts_top_k]] += 1
    np.testing.assert_array_equal(np.asarray(rows), want)


# --------------------- the benchmark's model type ---------------------- #

def test_benchmark_weights_are_the_models_tree_in_the_served_dtype():
    from benchmark.common import load_json
    from benchmark.model_types import olmoe as mt
    dims = load_json("configs", "olmoe-1b-7b.json")
    assert {k: dims[k] for k in CATALOG if k != "num_hidden_layers"} \
        == {k: CATALOG[k] for k in CATALOG if k != "num_hidden_layers"}
    assert dims["num_hidden_layers"] == 8
    dims.update(dims["rehearse"])
    cfg = mt.model_config(dims)
    params = mt.init_params(cfg, 3000000077)
    want = jax.eval_shape(
        lambda k: Mixtral(cfg).init({"params": k, "gating": k},
                                    jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(params) \
        == jax.tree_util.tree_structure(want)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        assert a.shape == b.shape, name
        assert a.dtype == (jnp.float32 if "scale" in name else jnp.bfloat16)
    wi = np.asarray(params["layer_0"]["moe"]["wi_gate"], np.float32)
    assert wi.std() == pytest.approx(cfg.hidden_size ** -0.5, rel=0.05)
    assert mt.kv_bytes_per_token(mt.model_config(load_json(
        "configs", "olmoe-1b-7b.json"))) == 65536
