"""Solar-Open2 (three gated delta-rule layers to one NoPE GQA layer, every
layer sparse) through the normal engine, at a small size on the CPU:
hidden 64, 4 heads of 16, 8 experts of which each of 2 shares holds 4,
top-2, 4 layers in the 1 : 3 pattern. Logits against the plain reference
(``benchmark/reference/solar_open2.py``), the chunked delta rule against
the token-by-token recurrence, the share rule of the model-configs guide,
the state pool's hygiene, and every refusal a stateful model makes."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmark.model_types import solar_open2 as mt
from benchmark.reference import solar_open2 as reference
from deepspeed_tpu.models.registry import config_from_hf
from deepspeed_tpu.models.solar_open2 import SolarOpen2Config, param_counts
from deepspeed_tpu.ops.kernels import delta_rule as dr
from family_harness import prompt_of

CONFIG = "solar-open2-250b.json"


def tiny(**kw):
    return SolarOpen2Config.tiny(experts_held=4, dtype=jnp.float32,
                                 param_dtype=jnp.float32, **kw)


#: float32 engine against a float32 reference at highest precision: what
#: is left is the order of the sums (chunked against token by token, the
#: grouped matmul against the dense mask), a few 1e-6 on logits of size 3.
#: Eight slots over 40 blocks, prefill chunks capped at the default
FAMILY = H.Family(mt, tiny, tol=2e-4, max_seqs=8, num_blocks=40,
                  max_blocks_per_seq=8, prefill_chunk_cap=256)
engine, ref_logits = FAMILY.engine, FAMILY.ref_logits


@pytest.fixture(scope="module")
def model():
    return FAMILY.model()


# ------------------------- (a) engine vs reference ------------------------ #


@H.chunk_and_decode
def test_engine_logits_match_the_reference(model, chunk, decode):
    """A 37-token prompt prefilled in one chunk or in three (the state
    rides from chunk to chunk), 8 tokens decoded through the fused loop
    or step by step, then one more position's logits: each against the
    reference's forward pass over the whole sequence."""
    eng = FAMILY.serve_against_reference(model, chunk, decode)
    prompt, stats = prompt_of(37), eng.pipeline_stats
    assert stats["linear_attn_prefill_tokens"] == len(prompt)
    # the chunk kernel is the chip's: none of them went through it here
    assert stats["linear_attn_prefill_kernel_tokens"] == 0
    assert stats["state_slots_live"] >= 8 and stats["state_bytes_live"] == \
        stats["state_slots_live"] * eng.kv_cache.state_bytes_per_slot()
    if decode == "fused":
        # 8 steps x 4 layers x top-2, split between this share and the other
        assert stats["moe_rows_routed"] + stats["moe_rows_elsewhere"] == 64
        assert stats["moe_rows_elsewhere"] > 0


def test_generate_serves_the_model(model):
    cfg, params = model
    eng = engine(cfg, params)
    prompt = prompt_of(20, seed=5)
    out = eng.generate([prompt], max_new_tokens=6)[0]
    seq = prompt + [int(t) for t in out]
    want = ref_logits(cfg, params, seq,
                      list(range(len(prompt) - 1, len(seq) - 1)))
    assert [int(t) for t in out] == np.argmax(want, -1).tolist()


def test_decode_through_the_conv_kernel_serves_the_jnp_paths_tokens(
        model, monkeypatch):
    """``Family.decode_through_the_conv_kernel``; the engine counts the
    layer-steps the kernel took."""
    plain, forced = FAMILY.decode_through_the_conv_kernel(model, monkeypatch)
    assert plain["conv_steps_in_place"] == 0     # the CPU path: gather and scatter
    assert forced["conv_steps_in_place"] == (4 + 5) * 3      # 3 KDA layers


# ------------------- (b) chunked against token by token ------------------- #


def _kda_case(key, T, gscale, beta_shift, B=2, H=3, dk=16, dv=8):
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (B, T, H, dk))
    k = jax.random.normal(ks[1], (B, T, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jnp.exp(2.0 * jax.random.normal(ks[3], (B, T, H, dk))) * gscale
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H))
                              + beta_shift)
    return q, k, v, g, beta, jax.random.normal(ks[5], (B, H, dk, dv))


_REGIMES = pytest.mark.parametrize("gscale, beta_shift", [
    (1.0, 0.0),        # decays spread over (0, 1)
    (20.0, 0.0),       # decays near 0: exp(G) underflows inside a chunk
    (1e-3, 0.0),       # decays near 1: the state never forgets
    (1e-3, 6.0),       # beta near 2: transitions with eigenvalues near -1
], ids=["spread", "decay-near-0", "decay-near-1", "beta-near-2"])


@_REGIMES
@pytest.mark.parametrize("chunk, sub", [(64, 16), (32, 32)])
def test_chunked_delta_rule_is_the_recurrence(gscale, beta_shift, chunk, sub):
    args = _kda_case(jax.random.PRNGKey(1), 100, gscale, beta_shift)
    with jax.default_matmul_precision("highest"):
        o1, S1 = dr.kda_recurrent(*args)
        o2, S2 = dr.kda_chunked(*args, chunk=chunk, sub=sub)
    assert np.isfinite(np.asarray(o2)).all()
    scale = max(1.0, float(jnp.abs(S1).max()))
    assert float(jnp.abs(o1 - o2).max()) < 1e-4 * scale
    assert float(jnp.abs(S1 - S2).max()) < 1e-4 * scale


@_REGIMES
@pytest.mark.parametrize("H, T", [
    (8, 128), (2 * dr._PREFILL_HEADS, 512)], ids=["8-heads", "2-blocks"])
def test_chunk_kernel_is_the_recurrence(gscale, beta_shift, H, T):
    """The Pallas chunk kernel (interpreted here) at lane-true shapes,
    from a nonzero state, against the token-by-token definition."""
    q, k, v, g, beta, S0 = _kda_case(jax.random.PRNGKey(5), T, gscale,
                                     beta_shift, B=1, H=H, dk=128, dv=128)
    with jax.default_matmul_precision("highest"):
        o1, S1 = dr.kda_recurrent(q, k, v, g, beta, S0)
    o2, St2 = dr.kda_prefill(q, k, v, g, beta, jnp.swapaxes(S0, -1, -2),
                             impl="interpret")
    assert np.isfinite(np.asarray(o2)).all()
    scale = max(1.0, float(jnp.abs(S1).max()))
    assert float(jnp.abs(o1 - o2).max()) < 1e-4 * scale
    assert float(jnp.abs(S1 - jnp.swapaxes(St2, -1, -2)).max()) \
        < 1e-4 * scale


@pytest.mark.parametrize("keep", [100, 64, 0],
                         ids=["masked-tail", "masked-chunk", "masked-row"])
def test_chunk_kernel_leaves_masked_positions_alone(keep):
    """Row 1 keeps ``keep`` of its 128 positions (beta 0, g 0 after
    them): its state is, to the bit, what its whole chunks alone give
    whatever the masked positions hold, and a wholly masked row's state
    is what it was."""
    q, k, v, g, beta, S0 = _kda_case(jax.random.PRNGKey(6), 128, 1.0, 0.0,
                                     B=2, H=dr._PREFILL_HEADS, dk=128,
                                     dv=128)
    on = (jnp.arange(128) < keep)[None] | (jnp.arange(2) == 0)[:, None]
    g = jnp.where(on[..., None, None], g, 0.0)
    beta = jnp.where(on[..., None], beta, 0.0)
    St0 = jnp.swapaxes(S0, -1, -2)
    o, St = dr.kda_prefill(q, k, v, g, beta, St0, impl="interpret")
    # told the rows' lengths, the kernel skips the chunks past them: the
    # same states, and the same outputs at the real positions
    o_n, St_n = dr.kda_prefill(q, k, v, g, beta, St0,
                               jnp.array([128, keep]), impl="interpret")
    assert np.array_equal(np.asarray(St_n), np.asarray(St))
    assert np.array_equal(np.asarray(o_n[1, :keep]), np.asarray(o[1, :keep]))
    assert np.array_equal(np.asarray(o_n[0]), np.asarray(o[0]))
    if keep:
        n = -(-keep // 64) * 64        # whole chunks: the kernel's grain
        # other values under the mask, and no chunk past the last real one
        cut = lambda x: jnp.where(                       # noqa: E731
            on[1:, :n].reshape((1, n) + (1,) * (x.ndim - 2)),
            x[1:, :n], 3.0 - x[1:, :n])
        _, want = dr.kda_prefill(cut(q), cut(k), cut(v), g[1:, :n],
                                 beta[1:, :n], St0[1:], impl="interpret")
        assert np.array_equal(np.asarray(St[1]), np.asarray(want[0]))
        _, real = dr.kda_recurrent(q[1:, :keep], k[1:, :keep], v[1:, :keep],
                                   g[1:, :keep], beta[1:, :keep], S0[1:])
        assert float(jnp.abs(jnp.swapaxes(St[1:], -1, -2) - real).max()) \
            < 1e-4
    else:
        assert np.array_equal(np.asarray(St[1]), np.asarray(St0[1]))
    assert not np.array_equal(np.asarray(St[0]), np.asarray(St0[0]))


@pytest.mark.parametrize("backend, T, H, dk, want", [
    ("tpu", 512, 64, 128, True), ("tpu", 512, 32, 128, True),
    ("tpu", 64, dr._PREFILL_HEADS, 128, True),
    ("cpu", 512, 64, 128, False), ("tpu", 100, 64, 128, False),
    ("tpu", 512, 64, 16, False), ("tpu", 512, 3, 128, False),
], ids=["solar", "kimi", "one-chunk", "cpu", "T-100", "dk-16", "3-heads"])
def test_chunk_kernel_is_taken_on_the_chip_at_its_grain(backend, T, H, dk,
                                                        want):
    assert dr.kda_prefill_uses_kernel(T, H, dk, dk, backend=backend) is want
    # and here, on the CPU, whatever the shape: the jnp form
    assert not dr.kda_prefill_uses_kernel(T, H, dk, dk)


def test_masked_positions_leave_the_state_as_it_was():
    q, k, v, g, beta, S0 = _kda_case(jax.random.PRNGKey(2), 24, 1.0, 0.0)
    keep = jnp.arange(24) < 17
    g = jnp.where(keep[None, :, None, None], g, 0.0)
    beta = jnp.where(keep[None, :, None], beta, 0.0)
    _, S_all = dr.kda_chunked(q, k, v, g, beta, S0, chunk=8, sub=4)
    _, S_17 = dr.kda_chunked(q[:, :17], k[:, :17], v[:, :17], g[:, :17],
                             beta[:, :17], S0, chunk=8, sub=4)
    assert float(jnp.abs(S_all - S_17).max()) < 1e-5


def test_decode_kernel_updates_the_pool_in_place_by_slot():
    """The Pallas decode update (interpreted here) against gather,
    ``kda_step``, scatter: two rows on one slot apart, an idle-style row
    (beta 0, g 0) writes back what it read."""
    q, k, v, g, beta, _ = _kda_case(jax.random.PRNGKey(3), 5, 1.0, 0.0,
                                    B=1, H=8, dk=128, dv=128)
    state = jax.random.normal(jax.random.PRNGKey(4), (7, 8, 128, 128))
    slots = jnp.array([3, 0, 6, 5, 2], jnp.int32)
    beta = beta.at[0, 3].set(0.0)
    g = g.at[0, 3].set(0.0)
    args = (state, slots, q[0], k[0], v[0], g[0], beta[0])
    o_x, st_x = dr.kda_decode_update(*args, impl="xla")
    o_i, st_i = dr.kda_decode_update(*args, impl="interpret")
    assert float(jnp.abs(o_x - o_i).max()) < 1e-5
    assert float(jnp.abs(st_x - st_i).max()) < 1e-5
    assert np.array_equal(np.asarray(st_i[5]), np.asarray(state[5]))
    assert np.array_equal(np.asarray(st_i[1]), np.asarray(state[1]))
    assert not np.array_equal(np.asarray(st_i[3]), np.asarray(state[3]))


# ------------------------------ (c) shares ------------------------------- #


def test_shares_routed_parts_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts of all shares plus the shared
    expert once equal the uncut layer, in the engine's sparse block and
    in the reference alike."""
    from deepspeed_tpu.inference.v2.llama_runner import _moe_mlp
    whole_cfg = SolarOpen2Config.tiny(dtype=jnp.float32,
                                      param_dtype=jnp.float32)
    whole = mt.init_params(whole_cfg, 11)["layer_1"]
    h = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 64))

    def share(first, held):
        return H.share_of(whole_cfg, whole["moe"], first, held)

    with jax.default_matmul_precision("highest"):
        uncut, _ = _moe_mlp(whole["moe"], h, whole_cfg, jnp.float32)
        parts, refs = [], []
        for first in (0, 4):
            cfg, p = share(first, 4)
            parts.append(_moe_mlp(p, h, cfg, jnp.float32)[0])
            refs.append(reference._sparse_mlp(p, h, top_k=2, first=first,
                                              scaling=1.0))
        ref_uncut = reference._sparse_mlp(whole["moe"], h, top_k=2, first=0,
                                          scaling=1.0)
    assert float(jnp.abs(parts[0]).max()) > 1e-3      # each share does work
    assert float(jnp.abs(parts[1]).max()) > 1e-3
    assert float(jnp.abs(parts[0] + parts[1] - uncut).max()) < 1e-5
    assert float(jnp.abs(refs[0] + refs[1] - ref_uncut).max()) < 1e-5
    assert float(jnp.abs(uncut - ref_uncut).max()) < 1e-5
    for a, b in zip(parts, refs):
        assert float(jnp.abs(a - b).max()) < 1e-5


def test_softmax_router_without_a_share_is_the_program_it_was():
    """``grouped_moe_ffn`` at softmax scores, no bias and every expert
    held lowers to the same program with and without the new arguments."""
    from deepspeed_tpu.moe.sharded_moe import grouped_moe_ffn
    x = jnp.ones((6, 16))
    logits = jnp.arange(48.0).reshape(6, 8)
    w = tuple(jnp.ones(s) for s in ((8, 16, 4), (8, 16, 4), (8, 4, 16)))

    def program(**new):
        def fn(x, logits):
            return grouped_moe_ffn(x, logits, 2, w, jax.nn.silu, jnp.float32,
                                   normalize_weights=False, **new)
        return jax.jit(fn).lower(x, logits).as_text()

    assert program() == program(score="softmax", select_bias=None,
                                held=(0, 8))


# ----------------------------- (d) state pool ---------------------------- #


def test_a_slot_reused_after_flush_starts_from_zero(model):
    cfg, params = model
    a, b = prompt_of(30, seed=1), prompt_of(21, seed=2)
    eng = engine(cfg, params, max_seqs=1)
    eng.put([1], [a])
    eng.decode_batch([1], [5], 4)
    slot = eng.state.get(1).state_slot
    eng.flush(1)
    got = np.asarray(eng.put([2], [b])[2])
    assert eng.state.get(2).state_slot == slot
    fresh = np.asarray(engine(cfg, params, max_seqs=1).put([2], [b])[2])
    assert np.array_equal(got, fresh)


def test_padding_and_idle_rows_leave_every_other_state_bit_identical(model):
    cfg, params = model
    eng = engine(cfg, params)
    eng.put([1, 2], [prompt_of(19, seed=3), prompt_of(33, seed=4)])
    s1, s2 = (eng.state.get(u).state_slot for u in (1, 2))
    before = jax.device_get((eng._kv_data.state, eng._kv_data.conv))
    # sequence 2 alone: a padded prefill chunk, a fused loop whose other
    # rows are idle, a per-step decode in a 16-row bucket
    eng.put([2], [prompt_of(5, seed=5)])
    eng.decode_batch([2], [9], 4)
    eng.decode_pipelined([2], [11], 3)
    after = jax.device_get((eng._kv_data.state, eng._kv_data.conv))
    # rows lead a layer's states and follow the layer in the conv inputs
    pairs = [(w, n) for w, n in zip(before[0], after[0])] \
        + [(np.moveaxis(before[1], 1, 0), np.moveaxis(after[1], 1, 0))]
    assert len(pairs) == 4
    for was, now in pairs:
        others = [r for r in range(was.shape[0] - 1) if r != s2]
        assert s1 in others
        assert np.array_equal(was[others], now[others])
        assert not np.array_equal(was[s2], now[s2])


def test_two_sequences_in_eight_slots_decode_as_they_do_alone(model):
    cfg, params = model
    prompts = {1: prompt_of(23, seed=6), 2: prompt_of(40, seed=7)}
    alone = {}
    for uid, p in prompts.items():
        eng = engine(cfg, params)
        first = eng.put([uid], [p], _greedy=True)[uid]
        alone[uid] = [first] + list(eng.decode_batch([uid], [first], 8)[uid])
    eng = engine(cfg, params)
    first = eng.put([1, 2], [prompts[1], prompts[2]], _greedy=True)
    outs = eng.decode_batch([1, 2], [first[1], first[2]], 8)
    for uid in (1, 2):
        assert [first[uid]] + list(outs[uid]) == alone[uid]


# ------------------------------ (e) refusals ----------------------------- #


@pytest.mark.parametrize("feature, kw", H.CONSTRUCTION_REFUSALS)
def test_construction_refuses_what_needs_a_state_snapshot(model, feature, kw):
    said = FAMILY.refusal(model, feature, kw, None)
    assert feature in said and "'kda'" in said


@pytest.mark.parametrize("call", [
    "pause", "resume", "handoff_out", "handoff_in", "drain", "replay",
    "attach_draft", "decode_spec"])
def test_calls_refuse_what_needs_a_state_snapshot(model, call):
    said = FAMILY.refusal(model, call, {}, H.CALL_ARGS[call])
    assert call in said and "'kda'" in said


# ------------------------------ (f) registry ----------------------------- #


def _published():
    return H.published(CONFIG, ("num_hidden_layers", "n_routed_experts",
                                "vocab_size"))


def test_config_from_hf_layer_list_and_parameter_counts():
    arch, cfg = config_from_hf(_published())
    assert arch == "solar_open2" and isinstance(cfg, SolarOpen2Config)
    assert len(cfg.layer_kinds) == 48
    assert [i for i, k in enumerate(cfg.layer_kinds) if k == "attn"] \
        == list(range(0, 48, 4))
    assert set(cfg.layer_kinds) == {"attn", "kda"}
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (64, 8, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv) == (64, 128, 4)
    assert (cfg.num_experts, cfg.held, cfg.experts_top_k) == (320, 320, 8)
    assert not cfg.use_rope and cfg.attn_gate and cfg.kda_neg_eigval
    total, active = param_counts(cfg)
    assert abs(total / 250e9 - 1) < 0.01           # the published 250B
    assert abs(active / 15e9 - 1) < 0.03           # ... -A15B


def test_the_benchmarks_cut_is_a_share_of_the_published_model():
    d = H.benchmark_config(CONFIG)
    cfg = mt.model_config(d)
    assert cfg.layer_kinds == ("attn", "kda", "kda", "kda")
    assert (cfg.num_experts, cfg.held, cfg.vocab_size) == (320, 40, 24576)
    total, _ = param_counts(cfg)
    assert abs(total / 3.308e9 - 1) < 0.005        # 6.62 GB in bfloat16
    assert mt.kv_bytes_per_token(cfg) == 4096      # one softmax layer


@pytest.mark.parametrize("key, value", [
    ("first_k_dense_replace", 1), ("kda_use_full_proj", True)])
def test_config_from_hf_refuses_what_it_does_not_implement(key, value):
    H.hf_refuses(_published(), {key: value}, key)


def test_flax_model_and_runner_read_one_tree(model):
    """The flax module's forward (token-by-token recurrence, every held
    expert densely) gives the reference's logits on the served tree."""
    from deepspeed_tpu.models.solar_open2 import SolarOpen2
    FAMILY.flax_model_reads_the_runners_tree(SolarOpen2, model)
