"""MiniCPM-SALA (``model_type: minicpm_sala``) through the serving engine
against its plain reference: block-selected attention layers (a compressed
key plane beside the paged pool, a selection a query and kv head, a decode
kernel that reads the listed blocks alone) and Lightning linear-attention
layers (the state pool without a convolution part), on a preset whose
SELECTION sizes are scaled with it: stride 2, kernel 4, blocks of 8, top-4,
a local window of 16, ``dense_len`` 64, at contexts of ~200, so that free
choices, forced blocks and the ``dense_len`` crossing all occur.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmark.model_types import minicpm_sala as mt
from benchmark.reference import minicpm_sala as reference
from deepspeed_tpu.inference.v2 import RaggedInferenceConfig
from deepspeed_tpu.models.minicpm_sala import (MiniCPMSALA,
                                               MiniCPMSALAConfig,
                                               SparseConfig,
                                               lightning_log_decay,
                                               param_count, select_blocks)

BLOCK, CHUNK, LOOP = 16, 32, 8


def tiny(**kw):
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("param_dtype", jnp.float32)
    return MiniCPMSALAConfig.tiny(**kw)


#: what the sibling families reach in float32 (the issue's figure); 80
#: blocks for contexts of ~200, a fused loop of 8, prefill chunks capped at
#: the default
FAMILY = H.Family(mt, tiny, tol=2e-4, chunk_size=CHUNK, num_blocks=80,
                  max_blocks_per_seq=20, decode_loop_steps=LOOP,
                  prefill_chunk_cap=256, attention_impl="auto")
TOL = FAMILY.tol
engine = FAMILY.engine


@pytest.fixture(scope="module")
def model():
    return FAMILY.model(seed=7)


def ref_logits(cfg, params, tokens, at, **variant):
    dims = dict(mt.reference_dims(cfg), **variant)
    return np.asarray(reference.logits(
        params, jnp.asarray(tokens)[None], jnp.asarray(at)[None], **dims))[0]


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 512, size=n).astype(np.int32)


def walk(eng, toks, prompt, singles, loops=1):
    """``put`` the prompt, ``singles`` one-token steps through the cache
    (teacher-forced), then ``loops`` fused loops; returns the engine's
    logits at the prompt's last position and after every single step, and
    the fused loops' tokens with the position of the first."""
    got = [eng.put([0], [toks[:prompt].tolist()])[0]]
    for i in range(singles):
        got.append(eng.put([0], [[int(toks[prompt + i])]])[0])
    at = prompt + singles
    out = []
    last = int(toks[at])
    for _ in range(loops):
        part = eng.decode_batch([0], [last], LOOP)[0]
        out += [int(t) for t in part]
        last = out[-1]
    return np.stack(got), out, at


@pytest.mark.parametrize("prompt, singles, chunk, impl", [
    (150, 6, CHUNK, "auto"),         # past dense_len: selection in prefill
    (150, 6, 64, "auto"),            # another chunking of the same prompt
    (40, 30, CHUNK, "auto"),         # crosses dense_len (64) while decoding
    (61, 2, CHUNK, "auto"),          # ... inside the fused loop
    (150, 3, CHUNK, "paged_flash"),  # the three kernels, interpreted
], ids=["prefill-sparse", "rechunked", "crossing-single", "crossing-fused",
        "kernels"])
def test_engine_logits_match_the_reference(model, prompt, singles, chunk,
                                           impl):
    """Prefill chunks, single steps through the cache, a fused loop and
    the rows its flush wrote, in float32 against the reference's whole
    forward: logits within 2e-4, and the fused loops' tokens the
    reference's own best under teacher forcing."""
    cfg, params = model
    toks = tokens_of(prompt + singles + 1, seed=prompt)
    eng = engine(cfg, params, chunk, attention_impl=impl)
    got, out, at = walk(eng, toks, prompt, singles, loops=2)
    seq = np.concatenate([toks[:at + 1], np.asarray(out, np.int32)])
    want = ref_logits(cfg, params, seq,
                      np.arange(prompt - 1, at + 2 * LOOP))
    assert np.abs(got - want[:singles + 1]).max() < TOL
    assert np.abs(want).max() > 0.5
    served = want[singles + 1:]
    gap = served.max(-1) - served[np.arange(2 * LOOP), out]
    assert gap.max() < TOL
    stats = eng.pipeline_stats
    if prompt > cfg.sparse.dense_len:
        assert stats["sparse_prefill_blocks_selected"] > 0
        assert stats["sparse_prefill_blocks_visited"] \
            >= stats["sparse_prefill_blocks_selected"]
    else:
        assert stats["sparse_dense_tokens"] > 0
    assert stats["sparse_rows_selected"] > 0


def test_two_chunkings_of_one_prompt_give_the_same_logits(model):
    cfg, params = model
    toks = tokens_of(200, seed=3)
    a = engine(cfg, params, 32).put([0], [toks.tolist()])[0]
    b = engine(cfg, params, 64).put([0], [toks.tolist()])[0]
    eng = engine(cfg, params, 32)
    eng.put([0], [toks[:77].tolist()])
    c = eng.put([0], [toks[77:].tolist()])[0]
    assert np.abs(a - b).max() < TOL and np.abs(a - c).max() < TOL


@pytest.mark.parametrize("t", [63, 64, 95, 130, 199])
def test_the_selected_block_sets_equal_the_references(t):
    """``select_blocks`` over scores made the ENGINE's way (a compressed
    key = the mean of two cached group means) against the reference's
    selection written out query by query (loops, means of whole windows):
    the same sets, free choices and forced blocks."""
    sp = SparseConfig(4, 2, 8, 4, 1, 16, 64)
    H, KV, d, T = 4, 2, 16, 200
    rng = np.random.default_rng(t)
    q = jnp.asarray(rng.normal(size=(H, d)) * 3, jnp.float32)
    k = jnp.asarray(rng.normal(size=(T, KV, d)) * 2, jnp.float32)
    NB = T // sp.block_size
    groups = k.reshape(T // 2, 2, KV, d).mean(1)             # [J, KV, d]
    gs = jnp.einsum("kgd,jkd->kgj", q.reshape(KV, H // KV, d), groups)
    cs = (gs + jnp.roll(gs, -1, axis=-1)) * (0.5 * d ** -0.5)
    blocks = np.asarray(select_blocks(
        cs, jnp.full((1,), t, jnp.int32), sp, NB))           # [KV, K]
    want = np.asarray(reference.selected_blocks(
        q, k, t, dataclasses.asdict(sp)))                    # [KV, NB']
    for kv in range(KV):
        got = set(int(b) for b in blocks[kv] if b >= 0)
        assert got == set(np.flatnonzero(want[kv])), (kv, got)
        # block 0 and the two blocks of the local window, always
        assert {0, t // 8, t // 8 - 1} <= got
    assert len(got) == min(sp.topk, t // 8 + 1)


def test_the_compressed_key_plane_is_the_means_of_the_stored_keys(model):
    """After a chunked prefill, single steps, a fused loop's ring and its
    flush: every FULL group's row of the plane is the mean of the K rows
    the pool holds for its positions, through the same block table."""
    cfg, params = model
    toks = tokens_of(120, seed=5)
    eng = engine(cfg, params, 32)
    _, _, at = walk(eng, toks, 101, 5, loops=2)
    seen = at + 2 * LOOP
    seq = eng.state.sequences[0]
    assert seq.seen_tokens == seen
    kv = eng._kv_data
    stride = cfg.sparse.kernel_stride
    data, index = np.asarray(kv.data), np.asarray(kv.index)
    assert index.shape == (1, data.shape[2] // stride, data.shape[3])
    table = np.asarray(seq.kv_blocks)
    pos = np.arange(seen // stride * stride)
    rows = table[pos // BLOCK] * BLOCK + pos % BLOCK
    want = data[0, 0][rows].reshape(-1, stride, data.shape[3]).mean(1)
    got = index[0][rows[::stride] // stride]
    assert np.abs(got - want).max() < 1e-6


def test_lightning_recurrent_chunked_and_in_place_agree():
    """The three forms of ``ops/kernels/ssd`` at a Lightning layer's
    operands (dt 1, a = log lambda, D 0), padding masked by dt 0."""
    from deepspeed_tpu.ops.kernels.ssd import (mamba2_decode_update,
                                               mamba2_prefill,
                                               mamba2_recurrent)
    B, T, H, d = 2, 37, 4, 16
    rng = np.random.default_rng(0)
    v, k, q = (jnp.asarray(rng.normal(size=(B, T, H, d)), jnp.float32)
               for _ in range(3))
    dt = jnp.ones((B, T, H), jnp.float32).at[1, 30:].set(0.0)
    a = lightning_log_decay(H)
    S0 = jnp.zeros((B, H, d, d), jnp.float32)
    y0, S_rec = mamba2_recurrent(v, dt, a, k, q, S0)
    y1, S_chk = mamba2_prefill(v, dt, a, k, q, S0, chunk=16)
    assert np.abs(np.asarray(y0 - y1)).max() < 1e-4
    assert np.abs(np.asarray(S_rec - S_chk)).max() < 1e-4
    pool = jnp.zeros((B + 1, H, d, d), jnp.float32)
    slots = jnp.arange(B, dtype=jnp.int32)
    ys = []
    for t in range(T):
        y, pool = mamba2_decode_update(
            pool, slots, v[:, t], dt[:, t], a, k[:, t], q[:, t],
            jnp.zeros((H,), jnp.float32), impl="interpret")
        ys.append(y)
    assert np.abs(np.asarray(jnp.stack(ys, 1) - y0)).max() < 1e-4
    assert np.abs(np.asarray(pool[:B] - S_rec)).max() < 1e-4
    # the decay is the family's: lambda_h = exp(-2^(-8 h / H))
    assert np.allclose(np.exp(np.asarray(a)),
                       np.exp(-2.0 ** (-8.0 * np.arange(1, H + 1) / H)))


def _pool_case(seed, S=3, KV=2, D=128, bs=32, nb=12, maxb=4, sb=16):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(2, 2, (nb + 1) * bs, KV * D)),
                       jnp.float32)
    tables = jnp.asarray(np.stack([rng.permutation(nb)[:maxb]
                                   for _ in range(S)]), jnp.int32)
    return rng, pool, tables


@pytest.mark.parametrize("ring", [False, True], ids=["step", "ring"])
def test_the_sparse_decode_kernel_is_its_twin(ring):
    """Interpret mode against gather, mask, softmax: listed blocks through
    a permuted table, an unused entry, an idle sequence, the loop's ring."""
    from deepspeed_tpu.ops.kernels import sparse_attention as sa
    S, H, KV, D, bs, sb, K = 3, 4, 2, 128, 32, 16, 4
    rng, pool, tables = _pool_case(1, S, KV, D, bs)
    q = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
    blocks = jnp.asarray([[[0, 2, 5, 6], [0, 1, 5, 6]],
                          [[0, 3, 4, -1], [0, 2, 4, -1]],
                          [[0, 1, 2, 3], [0, 1, 2, 3]]], jnp.int32)
    start = jnp.asarray([110, 75, 60], jnp.int32)
    lens = jnp.asarray([104, 70, 0], jnp.int32)
    rows, col = sa.selection_rows(blocks, tables, bs, sb, pool.shape[2] - bs)
    kw = dict(sel_block=sb, sm_scale=D ** -0.5)
    if ring:
        kw.update(ring=jnp.asarray(rng.normal(size=(8, 2, 2, S, KV * D)),
                                   jnp.float32),
                  ring_count=jnp.int32(6), ring_layer=1)
    else:
        lens = jnp.where(lens > 0, start + 1, 0)
    want = sa.sparse_decode_reference(q, pool, 1, rows, col, start, lens,
                                      **kw)
    got = sa.sparse_decode_attention(q, pool, 1, rows, col, start, lens,
                                     interpret=True, **kw)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    assert np.abs(np.asarray(want[:2])).max() > 0.1
    assert np.abs(np.asarray(got[2])).max() == 0.0            # idle


def test_the_sparse_prefill_kernel_is_its_twin():
    """Interpret mode against the gathered context under a mask a query:
    the union of a tile's blocks is visited, the per-query mask decides,
    and the kernel counts what it walked."""
    from deepspeed_tpu.ops.kernels import sparse_attention as sa
    S, C, H, KV, D, bs, sb, maxb = 2, 16, 4, 2, 128, 32, 16, 4
    rng, pool, tables = _pool_case(2, S, KV, D, bs, maxb=maxb)
    q = jnp.asarray(rng.normal(size=(S, C, H, D)), jnp.float32)
    start = jnp.asarray([96, 64], jnp.int32)
    lens = jnp.asarray([96 + 16, 64 + 9], jnp.int32)
    NB = maxb * bs // sb
    chosen = jnp.asarray(rng.random(size=(S, C, KV, NB)) < 0.4)
    pos = start[:, None] + jnp.arange(C)[None, :]
    chosen = chosen | (jnp.arange(NB)[None, None, :]
                       == (pos // sb)[..., None])[:, :, None, :]
    kw = dict(block_size=bs, sel_block=sb, sm_scale=D ** -0.5)
    want, n0 = sa.sparse_prefill_reference(q, pool, 0, tables, start, lens,
                                           chosen, **kw)
    got, n1 = sa.sparse_prefill_attention(q, pool, 0, tables, start, lens,
                                          chosen, interpret=True, **kw)
    real = np.arange(C)[None, :] < np.asarray(lens - start)[:, None]
    assert np.abs(np.asarray(got - want))[real].max() < 1e-5
    assert int(n0[0]) == int(n1[0]) and int(n1[1]) >= int(n1[0]) > 0


def test_flax_model_and_runner_read_one_tree(model):
    cfg, params = model
    toks = tokens_of(90, seed=9)
    want = np.asarray(MiniCPMSALA(cfg).apply(
        {"params": params}, jnp.asarray(toks)[None]))[0, -1]
    got = engine(cfg, params).put([0], [toks.tolist()])[0]
    assert np.abs(got - want).max() < TOL
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == param_count(cfg)


def test_one_cache_value_holds_pool_plane_and_state(model):
    """The cache value: paged planes for the sparse layers alone, the
    compressed-key plane addressed by the same blocks, a state row a
    Lightning layer and slot, and NO convolution part."""
    cfg, params = model
    eng = engine(cfg, params)
    r, kv, cache = eng.runner, eng._kv_data, eng.kv_cache
    assert r.state_spec == {"kind": "lightning", "layers": 3, "heads": 4,
                            "d_v": 16, "d_k": 16, "taps": 0,
                            "conv_width": 0}
    assert r.index_spec == {"layers": 1, "stride": 2, "pool_layers": (0,)}
    assert (r.kv_layers, r.ring_layers) == (1, 1)
    assert kv.conv is None and cache.conv is None
    assert len(kv.state) == 3 and kv.state[0].shape == (5, 4, 16, 16)
    assert kv.index.shape == (1, 81 * BLOCK // 2, 32)
    assert cache.state_bytes_per_slot() == 3 * 4 * 16 * 16 * 4
    assert cache.memory_bytes() == kv.data.nbytes + kv.index.nbytes \
        + 5 * cache.state_bytes_per_slot()


def test_the_regions_and_the_counters_are_in_the_vocabulary(model):
    from deepspeed_tpu.telemetry.trace import REGIONS
    cfg, params = model
    assert {"attn_select", "attn_sparse", "linear_attn"} <= set(REGIONS)
    eng = engine(cfg, params)
    toks = tokens_of(130, seed=2)
    walk(eng, toks, 120, 2, loops=1)
    st = eng.pipeline_stats
    kvh, sp = cfg.num_kv_heads, cfg.sparse
    # a fused loop of 8 steps from 122 settled rows and two single steps:
    # every step past dense_len reads top-4 blocks of 8 a kv head
    steps = LOOP + 2
    assert st["sparse_rows_selected"] == steps * sp.rows_selected * kvh
    live = sum(121 + i for i in range(2)) + sum(122 + t + 1
                                                for t in range(LOOP))
    assert st["sparse_rows_live"] == live * kvh
    assert st["sparse_dense_tokens"] == sp.dense_len - 1
    # every position from dense_len - 1 on selected: 120 prompt positions
    # less the 63 below it, and every decode step; no kernel off the TPU
    assert st["sparse_select_queries"] == 120 - (sp.dense_len - 1) + steps
    assert st["sparse_select_kernel_queries"] == 0
    assert st["state_slots_live"] == steps
    assert st["state_bytes_live"] == steps * 3 * 4 * 16 * 16 * 4
    assert st["conv_steps_in_place"] == 0


@pytest.mark.parametrize("variant", [
    {"selection": "dense"}, {"topk": 2}, {"window_size": 0},
    {"init_blocks": 0}, {"sparse_rope": True}, {"lightning_rope": False},
    {"decay_one": True}, {"mup": False}], ids=lambda v: next(iter(v)))
def test_each_wrong_model_of_the_cells_check_differs(model, variant):
    """What ``tools/chip_parity.py`` plants, at the toy size: each moves
    the reference's own logits far past the engine's tolerance."""
    cfg, params = model
    toks = tokens_of(160, seed=4)
    at = np.arange(100, 160)
    right = ref_logits(cfg, params, toks, at)
    wrong = ref_logits(cfg, params, toks, at, **variant)
    assert np.abs(right - wrong).max() > 50 * TOL


@pytest.mark.parametrize("feature, kw", [
    ("prefix_cache", {"prefix_cache": True}),
    ("spec_decode", {"spec_decode": "ngram"}),
    ("kv_cache_dtype='int8'", {"kv_cache_dtype": "int8"}),
    ("tp_size > 1", {"tp_size": 2}),
    ("seq_size > 1", {"seq_size": 2}),
    ("ep_size > 1", {"ep_size": 2})])
def test_what_needs_the_plane_or_the_state_elsewhere_refuses_at_construction(
        model, feature, kw):
    from deepspeed_tpu.inference.v2.config import (selecting_refusal,
                                                   stateful_refusal)
    said = FAMILY.refusal(model, feature, kw, None)
    assert stateful_refusal(feature, "lightning") in said
    assert selecting_refusal(feature) in said


def test_a_loop_longer_than_the_forced_window_refuses_at_construction(model):
    """The fused loop's sparse call reads every ring row unasked, so the
    ring must lie inside the blocks every query is forced to read: 16 - 8
    + 1 = 9 positions back here, which LOOP = 8 stays inside."""
    cfg, params = model
    sp = cfg.sparse
    reach = sp.window_size - sp.block_size + 1
    assert LOOP <= reach == 9
    engine(cfg, params, decode_loop_steps=reach)
    with pytest.raises(ValueError, match="decode_loop_steps=10 is past "
                                         "the 9 positions"):
        engine(cfg, params, decode_loop_steps=reach + 1)


@pytest.mark.parametrize("feature, call", [
    ("pause", lambda e: e.pause(0)), ("resume", lambda e: e.resume(0)),
    ("drain", lambda e: e.drain("/tmp/x")),
    ("replay", lambda e: e.replay(None)),
    ("handoff_out", lambda e: e.handoff_out([0])),
    ("handoff_in", lambda e: e.handoff_in(None)),
    ("attach_draft", lambda e: e.attach_draft(None, None)),
    ("decode_spec", lambda e: e.decode_spec([0], [1], 4))])
def test_what_needs_a_snapshot_refuses_by_name(model, feature, call):
    from deepspeed_tpu.inference.v2.config import (selecting_refusal,
                                                   stateful_refusal)
    cfg, params = model
    eng = engine(cfg, params)
    with pytest.raises(NotImplementedError) as err:
        call(eng)
    assert selecting_refusal(feature) in str(err.value)
    assert stateful_refusal(feature, "lightning") in str(err.value)


@pytest.mark.parametrize("new", ["sparse", "lightning"])
@pytest.mark.parametrize("other", ["swa", "mla", "kda", "mamba2"])
def test_beside_another_familys_kind_refuses_at_construction(new, other):
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    cfg = tiny(num_layers=2, layer_kinds=(new, other), depth_published=2)
    with pytest.raises(ValueError, match="do not mix|two kinds"):
        LlamaRaggedRunner(cfg, RaggedInferenceConfig(
            max_seqs=2, chunk_size=16, block_size=16, num_blocks=8,
            max_blocks_per_seq=4, dtype="float32"))


def _published():
    return H.catalog_row("MiniCPM-SALA")["config"]


def test_config_from_hf_reads_the_published_keys():
    """``mixer_types`` letter for letter (no period: sparse at 0, 9, 16,
    17, 22, 29, 30, 31), the muP factors, NoPE sparse / rotary linear, and
    the model's own "9B" back from the widths."""
    from deepspeed_tpu.models.registry import config_from_hf
    if not os.path.exists("/opt/skills/guides/model-configs"):
        pytest.skip("no catalog here")
    arch, cfg = config_from_hf(_published())
    assert arch == "minicpm_sala"
    sparse_at = [i for i, k in enumerate(cfg.layer_kinds) if k == "sparse"]
    assert sparse_at == [0, 9, 16, 17, 22, 29, 30, 31]
    assert set(cfg.layer_kinds) == {"sparse", "lightning"}
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.lightning_heads) == (32, 2, 128, 32)
    assert not cfg.use_rope and cfg.lightning_rope and cfg.attn_gate
    assert cfg.embed_scale == 12.0 and cfg.logit_divisor == 16.0
    assert abs(cfg.residual_scale - 1.4 / 32 ** 0.5) < 1e-12
    assert cfg.sparse == SparseConfig(32, 16, 64, 64, 1, 2048, 8192)
    assert cfg.rms_eps == 1e-6 and not cfg.tie_embeddings
    assert round(param_count(cfg) / 1e6) == 9477
    # the benchmark's cut: layers 9-16, the published depth kept
    cut = mt.model_config(H.benchmark_config("minicpm-sala-9b.json"))
    assert cut.layer_kinds == cfg.layer_kinds[9:17] \
        == ("sparse",) + ("lightning",) * 6 + ("sparse",)
    assert cut.residual_scale == cfg.residual_scale
    assert param_count(cut) == 2820569088          # 2,820.6 M: 5.64 GB


@pytest.mark.parametrize("change, match", [
    ({"mixer_types": ["minicpm4", "mamba"]}, "mixer_types"),
    ({"qk_norm": False}, "qk_norm"),
    ({"use_output_gate": False}, "use_output_gate"),
    ({"lightning_scale": "1"}, "lightning_scale"),
    ({"sparse_config": {"kernel_size": 48}}, "kernel_size")])
def test_config_from_hf_refuses_what_it_does_not_implement(change, match):
    base = {"model_type": "minicpm_sala", "num_hidden_layers": 2,
            "mixer_types": ["minicpm4", "lightning-attn"]}
    H.hf_refuses(base, change, match)


def test_loader_names_reach_every_leaf(model):
    cfg, params = model
    params = jax.tree_util.tree_map(np.asarray, params)
    mixers = ["minicpm4" if k == "sparse" else "lightning-attn"
              for k in cfg.layer_kinds]
    state = H.hf_trunk(params)
    for i, kind in enumerate(cfg.layer_kinds):
        p, pre = params[f"layer_{i}"], f"model.layers.{i}."
        state[pre + "input_layernorm.weight"] = p["input_norm"]["scale"]
        state[pre + "post_attention_layernorm.weight"] = \
            p["post_attn_norm"]["scale"]
        H.hf_projections(state, pre + "mlp", p["mlp"], ("gate", "up", "down"))
        mix = p["attn" if kind == "sparse" else "lin"]
        H.hf_projections(state, pre + "self_attn", mix, "qkvo")
        for n in "qk":
            state[pre + f"self_attn.{n}_norm.weight"] = \
                mix[f"{n}_norm"]["scale"]
        gate = "o_gate" if kind == "sparse" else "z_proj"
        state[pre + f"self_attn.{gate}.weight"] = mix["g_proj"]["kernel"].T
        if kind == "lightning":
            state[pre + "self_attn.o_norm.weight"] = mix["o_norm"]["scale"]
    H.loader_reaches_every_leaf("minicpm_sala", state,
                                {"mixer_types": mixers}, params)


def _select_case(case):
    """One call of the selection at a toy size whose blocks still cross:
    stride 2, windows of 4, selection blocks of 8 (4 groups), pool blocks
    of 16 (8 plane rows), top-4, a local window of 16, ``dense_len`` 64.
    Returns (q, plane, tables, pos, n_tokens, ring sums, settled)."""
    from deepspeed_tpu.inference.v2 import index_plane
    S, KV, G, D, bs, maxb, nb = 4, 2, 2, 16, 16, 16, 80
    rng = np.random.default_rng(11)
    plane = jnp.asarray(rng.normal(size=(2, (nb + 1) * bs // 2, KV * D)),
                        jnp.float32)
    tables = jnp.asarray(np.stack([rng.permutation(nb)[:maxb]
                                   for _ in range(S)]), jnp.int32)
    idx = settled = None
    if case == "pool":
        start, ntok, C = [200, 97, 64, 255], [1, 1, 1, 1], 1
    elif case == "ring":
        # the loop's groups (from settled // 2 on, 5 of them) cross the
        # edge of a plane block (8 groups) in rows 0 and 3
        start, ntok, C = [116, 97, 70, 241], [1, 1, 1, 1], 1
        settled = jnp.asarray([110, 96, 64, 236], jnp.int32)
        idx = jnp.asarray(rng.normal(size=(2, S, index_plane.ring_group_count(
            8, 2), KV * D)), jnp.float32)
    elif case == "straddle":
        start, ntok, C = [48, 56, 40, 100], [32, 24, 32, 32], 32
    elif case == "empty_row":
        start, ntok, C = [96, 0, 130, 0], [32, 0, 32, 0], 32
    else:
        assert case == "part_block"
        # the last pool block holds 3 and 9 of its 16 rows
        start, ntok, C = [170, 121], [1, 1], 1
        tables = tables[:2]
    S = len(start)
    q = jnp.asarray(rng.normal(size=(S, C, KV, G, D)) * 2.0, jnp.float32)
    start = jnp.asarray(start, jnp.int32)
    pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    return (q, plane, tables, pos, jnp.asarray(ntok, jnp.int32), idx,
            settled, bs)


@pytest.mark.parametrize("case", ["pool", "ring", "straddle", "empty_row",
                                  "part_block"])
def test_the_selection_kernel_is_its_twin(case):
    """Interpret mode against ``block_scores(group_scores(..))``: a real
    query's block scores are the twin's to float32 rounding with the
    infinities in the same places, and the selected SETS are the same; a
    query that is not real (a row with no tokens, a chunk's tail) scores
    nothing."""
    from deepspeed_tpu.inference.v2 import index_plane
    from deepspeed_tpu.inference.v2.kv_quant import KVPool
    from deepspeed_tpu.models.minicpm_sala import (block_scores,
                                                   blocks_of_scores)
    from deepspeed_tpu.ops.kernels import sparse_attention as sa
    sp = SparseConfig(4, 2, 8, 4, 1, 16, 64)
    q, plane, tables, pos, ntok, idx, settled, bs = _select_case(case)
    S, C, KV, G, D = q.shape
    NB = tables.shape[1] * bs // sp.block_size
    scale = D ** -0.5
    assert sa.select_fits(G, KV, C, bs, sp.kernel_stride)
    kv = KVPool(None, None, None, None, None, plane, None)
    gs = index_plane.group_scores(kv, 1, q, tables, bs, sp.kernel_stride,
                                  idx, settled)
    cs = (gs + jnp.concatenate([gs[..., 1:], gs[..., :1]], -1)) \
        * (0.5 * scale)
    want = np.asarray(block_scores(cs, pos[:, :, None], sp, NB))
    got = np.asarray(sa.block_select_scores(
        q, plane, 1, tables, pos, ntok, sp, pool_block=bs, sm_scale=scale,
        num_blocks=NB, ring_sums=idx, settled=settled, interpret=True))
    real = np.arange(C)[None, :] < np.asarray(ntok)[:, None]
    assert real.any() and got.shape == want.shape == (S, C, KV, NB)
    assert (got[~real] == -np.inf).all()
    got, want = got[real], want[real]
    finite = np.isfinite(want)
    assert (np.isfinite(got) == finite).all()
    assert (got[~finite] == want[~finite]).all()
    assert finite.sum() > 8 \
        and np.abs(got[finite] - want[finite]).max() < 1e-5
    assert (want[finite] > 1e-3).sum() > 4            # the plane was read
    a, b = (np.asarray(blocks_of_scores(jnp.asarray(x), sp))
            for x in (got, want))
    assert (a == b).all() and (b >= 0).sum(-1).min() >= 1
