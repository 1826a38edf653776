"""Nemotron-H (layers that are a Mamba-2 mixer ALONE, attention ALONE or a
sparse feed-forward of ungated relu2 experts ALONE; a state pool of
non-square states beside paged K/V in one cache value; a share of the
experts, stored at a width rounded up to whole lane groups) through the
normal engine, at a small size on the CPU: hidden 64, 4 state-space heads
of 8 over a state of 16 in 2 groups, 4 query heads over 2 kv heads of 16,
8 experts of width 40 (stored 128) of which a share holds 4, top-2, the
pattern ``MEM*EME``. Logits against the plain reference
(``benchmark/reference/nemotron_h.py``), the chunked SSD form and the
decode update kernel against the token-by-token recurrence, the share rule
of the model-configs guide, the cache's two parts, the loader's names, the
refusals of the new kind, and that the five other families' layer lists
resolve as they did."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmark.model_types import nemotron_h as mt
from benchmark.reference import nemotron_h as reference
from deepspeed_tpu.inference.v2 import RaggedInferenceConfig
from deepspeed_tpu.inference.v2.model_runner import RaggedBatch
from deepspeed_tpu.models.nemotron_h import (NemotronH, NemotronHConfig,
                                             kinds_from_pattern, pad_experts,
                                             param_counts, relu2)
from deepspeed_tpu.models.registry import config_from_hf
from deepspeed_tpu.ops.kernels.ssd import (mamba2_decode_update,
                                           mamba2_prefill, mamba2_recurrent)
from family_harness import prompt_of

CONFIG = "nemotron-3-nano-30b-a3b.json"
REDUCED = ("num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size")


def tiny(**kw):
    kw.setdefault("experts_held", 4)
    kw.setdefault("experts_first", 2)
    return NemotronHConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                                **kw)


#: float32 engine against a float32 reference at highest precision: what
#: is left is the order of the sums (the chunked SSD form against the
#: recurrence, the paged attention against the dense one, the grouped
#: matmul against the dense mask), a few 1e-6 on logits of size 4
FAMILY = H.Family(mt, tiny, tol=2e-4)
TOL = FAMILY.tol
engine, ref_logits = FAMILY.engine, FAMILY.ref_logits


@pytest.fixture(scope="module")
def model():
    return FAMILY.model()


# ------------------------- (a) engine vs reference ------------------------ #


@H.chunk_and_decode
def test_engine_logits_match_the_reference(model, chunk, decode):
    """A 37-token prompt prefilled in one chunk or in three (the chunked
    SSD form at chunks of 8, a ragged tail, the state and the convolution's
    inputs carried between the steps), 8 tokens decoded through the fused
    loop (the state in its carry, the K/V rows in its ring, then the flush)
    or step by step, then one more position's logits: each against the
    reference's forward pass over the whole sequence (token-by-token
    recurrence, dense attention, no cache)."""
    prompt = prompt_of(37)
    # the state pool's counters and the paged planes' fill in the one run
    stats = FAMILY.serve_against_reference(model, chunk,
                                           decode).pipeline_stats
    assert stats["linear_attn_prefill_tokens"] == len(prompt)
    assert stats["linear_attn_prefill_kernel_tokens"] == 0
    live = (sum(range(38, 46)) if decode == "pipelined" else 8 * 37) + 46
    assert stats["decode_kv_rows_live"] == live
    # ONE softmax layer of the seven keeps rows: K and V, 2 heads x 16
    assert stats["kv_bytes_live"] == live * 1 * 2 * 2 * 16 * 4
    # 8 decode steps and the one-token step: a state row live in each, of
    # 3 state-space layers x (4 heads x 8 x 16 + 3 taps x 96 lanes) x 4 B
    assert stats["state_slots_live"] == 9
    assert stats["state_bytes_live"] == 9 * 3 * (4 * 8 * 16 + 3 * 96) * 4
    assert stats["latent_rows_live"] == stats["latent_bytes_live"] == 0
    if decode == "fused":
        # 8 steps x 3 sparse layers x top-2, split with the other shares
        assert stats["moe_rows_routed"] + stats["moe_rows_elsewhere"] == 48
        assert stats["moe_rows_elsewhere"] > 0


def test_flax_model_and_runner_read_one_tree(model):
    FAMILY.flax_model_reads_the_runners_tree(NemotronH, model)


def test_two_sequences_decode_as_they_do_alone_and_a_slot_starts_fresh(model):
    """Two sequences of different lengths in one batch, and then a third in
    a slot the first one left: each decodes what it decodes alone (the
    state rows and the blocks of a flushed tenant reach nobody)."""
    FAMILY.two_sequences_decode_as_alone(model)


def test_engine_through_the_kernels_matches_the_reference(model):
    """The same engine with the Pallas attention path forced (interpreted
    here): the prefill chunks and the decode steps of the softmax layer
    through the paged kernels at 2 query heads a kv head, beside the
    state-space layers' state."""
    FAMILY.serve_through_the_kernels(model)


def test_decode_through_the_conv_kernel_serves_the_jnp_paths_tokens(
        model, monkeypatch):
    """``Family.decode_through_the_conv_kernel``; the engine counts the
    layer-steps the kernel took."""
    plain, forced = FAMILY.decode_through_the_conv_kernel(model, monkeypatch)
    assert plain["conv_steps_in_place"] == 0     # the CPU path: gather and scatter
    assert forced["conv_steps_in_place"] == (4 + 5) * 3      # 3 Mamba-2 layers


@pytest.mark.parametrize("variant", [
    dict(skip_term=False), dict(gate_first=False), dict(conv_bias=False),
    dict(rope_theta=10000.0)],
    ids=["no-D-x", "gate-after-norm", "no-conv-bias", "rotary"])
def test_each_wrong_model_of_the_cells_check_differs(model, variant):
    """The four wrong references the cell's ``why`` measures on the chip
    are other models: each moves the logits by more than the engine's
    distance from the right one."""
    import functools
    cfg, params = model
    toks = jnp.asarray([prompt_of(24, seed=6)])
    at = jnp.asarray([list(range(24))])
    dims = mt.reference_dims(cfg)
    right = reference.logits(params, toks, at, **dims)
    wrong = functools.partial(reference.logits, **dims, **variant)(
        params, toks, at)
    assert float(jnp.abs(wrong - right).max()) > 100 * TOL


# ------------------- (b) the recurrence's three forms -------------------- #


def _ssm_inputs(rng, B, T, H=4, P=8, N=16):
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, Bm, Cm = f(B, T, H, P), f(B, T, H, N), f(B, T, H, N)
    dt = jax.nn.softplus(f(B, T, H) - 2.0)
    a = -jnp.exp(jnp.asarray(rng.uniform(0.0, 2.5, H), jnp.float32))
    return x, dt, a, Bm, Cm, 0.3 * f(B, H, P, N)


@pytest.mark.parametrize("T, chunk, carried", [
    (32, 8, False), (32, 8, True), (27, 8, True), (5, 8, True),
    (128, 128, True), (200, 128, False)],
    ids=["whole-chunks-fresh", "whole-chunks-carried", "ragged-tail",
         "shorter-than-a-chunk", "one-published-chunk", "published-ragged"])
def test_chunked_ssd_form_is_the_recurrence(T, chunk, carried):
    """``mamba2_prefill`` against the token-by-token definition: outputs
    and the state it leaves, from a zero state (a fresh row) and from a
    carried one, at whole chunks and at a ragged tail."""
    x, dt, a, Bm, Cm, S0 = _ssm_inputs(np.random.default_rng(T), 2, T)
    if not carried:
        S0 = jnp.zeros_like(S0)
    y, S = mamba2_prefill(x, dt, a, Bm, Cm, S0, chunk=chunk)
    y_ref, S_ref = mamba2_recurrent(x, dt, a, Bm, Cm, S0)
    assert float(jnp.abs(y - y_ref).max()) < 1e-4
    assert float(jnp.abs(S - S_ref).max()) < 1e-4


def test_positions_past_a_rows_tokens_contribute_nothing():
    """``dt`` 0 from ``n_tokens`` on (the mixer's mask): the state a row
    leaves is the state after its real positions, whatever lies behind."""
    x, dt, a, Bm, Cm, S0 = _ssm_inputs(np.random.default_rng(9), 2, 24)
    n = jnp.asarray([24, 13])
    dt = jnp.where(jnp.arange(24)[None, :, None] < n[:, None, None], dt, 0.0)
    _, S = mamba2_prefill(x, dt, a, Bm, Cm, S0, chunk=8)
    _, S13 = mamba2_recurrent(x[1:, :13], dt[1:, :13], a, Bm[1:, :13],
                              Cm[1:, :13], S0[1:])
    assert float(jnp.abs(S[1] - S13[0]).max()) < 1e-5


@pytest.mark.parametrize("H, P, N", [(4, 8, 16), (64, 64, 128)],
                         ids=["toy", "published"])
def test_decode_update_kernel_is_one_step_of_the_recurrence(H, P, N):
    """``mamba2_decode_state_update`` (interpreted): the pool rows picked
    by slot and updated in place, a wiped row starting from zero, a row
    with a zero step writing back what it read, the rows no slot named as
    they were; at the published 64 heads of 64 x 128 (eight grid steps of 8
    heads a row) too."""
    rng = np.random.default_rng(H)
    S, rows = 3, 5
    x, dt, a, Bm, Cm, _ = _ssm_inputs(rng, S, 1, H, P, N)
    x, dt, Bm, Cm = x[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0]
    dt = dt.at[2].set(0.0)                               # an idle row
    D = jnp.asarray(rng.normal(size=H), jnp.float32)
    state = jnp.asarray(rng.normal(size=(rows, H, P, N)), jnp.float32)
    slots = jnp.asarray([3, 0, 4], jnp.int32)
    wipe = jnp.asarray([False, True, False])
    got = {impl: mamba2_decode_update(state, slots, x, dt, a, Bm, Cm, D,
                                      wipe=wipe, impl=impl)
           for impl in ("interpret", "xla")}
    S0 = jnp.where(wipe[:, None, None, None], 0.0, state[slots])
    y_ref, S_ref = mamba2_recurrent(x[:, None], dt[:, None], a, Bm[:, None],
                                    Cm[:, None], S0)
    y_ref = y_ref[:, 0] + D[:, None] * x
    for y, new in got.values():
        assert float(jnp.abs(y - y_ref).max()) < 1e-4
        assert float(jnp.abs(new[slots] - S_ref).max()) < 1e-5
        assert np.array_equal(np.asarray(new[4]), np.asarray(state[4]))
        assert np.array_equal(np.asarray(new[jnp.asarray([1, 2])]),
                              np.asarray(state[jnp.asarray([1, 2])]))


def test_mixer_leaves_an_idle_row_untouched_and_starts_a_fresh_one_at_zero(
        model):
    """The runner's mixer over a pool whose rows hold a last tenant's
    state: a row at position 0 starts from zero state and zero convolution
    inputs; a row with no token leaves its pool row as it was; a row that
    continues reads what it left. One token (the kernel's path) and a
    chunk (the SSD form) alike."""
    from deepspeed_tpu.inference.v2.kv_cache import BlockedKVCache
    from deepspeed_tpu.inference.v2.llama_runner import _mamba2_mixer
    cfg, params = model
    p = params["layer_0"]["mamba"]
    icfg = RaggedInferenceConfig(max_seqs=3, chunk_size=16, block_size=16,
                                 num_blocks=8, max_blocks_per_seq=2,
                                 dtype="float32")
    spec = {"kind": "mamba2", "layers": 1, "heads": 4, "d_v": 8, "d_k": 16,
            "taps": 4, "conv_width": 96}
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(3, 6, 64)), jnp.float32)
    for C in (1, 6):
        pool = BlockedKVCache(icfg, 1, 2, 16, dtype=jnp.float32,
                              state_spec=spec).pool
        dirty = jnp.asarray(rng.normal(size=(4, 4, 8, 16)), jnp.float32)
        dirty_conv = jnp.asarray(rng.normal(size=(1, 4, 3, 96)), jnp.float32)
        pool = pool._replace(state=(dirty,), conv=dirty_conv)
        # row 0 fresh (position 0), row 1 idle, row 2 continues at 9
        batch = RaggedBatch(jnp.zeros((3, C), jnp.int32),
                            jnp.asarray([0, 0, 9], jnp.int32),
                            jnp.asarray([C, 0, C], jnp.int32),
                            jnp.zeros((3, 2), jnp.int32),
                            jnp.asarray([2, 3, 0], jnp.int32))
        valid = jnp.arange(C)[None, :] < batch.n_tokens[:, None]
        out, y = _mamba2_mixer(p, h[:, :C], pool, 0, batch, cfg, valid,
                               jnp.float32)
        clean = pool._replace(
            state=(dirty.at[2].set(0.0),),
            conv=dirty_conv.at[0, 2].set(0.0))
        _, y_clean = _mamba2_mixer(p, h[:, :C], clean, 0, batch, cfg, valid,
                                   jnp.float32)
        assert float(jnp.abs(y[0] - y_clean[0]).max()) == 0.0
        new, = out.state
        assert np.array_equal(np.asarray(new[3]), np.asarray(dirty[3]))
        assert np.array_equal(np.asarray(out.conv[0, 3]),
                              np.asarray(dirty_conv[0, 3]))
        assert np.array_equal(np.asarray(new[1]), np.asarray(dirty[1]))
        assert float(jnp.abs(new[0] - dirty[0]).max()) > 0    # continued


# ---------------- (c) layers of one branch, in the two lists -------------- #


@pytest.mark.parametrize("pattern", ["E*M", "M*", "MEE*", "*EM*"])
def test_a_layer_list_of_single_branches_serves(pattern):
    """Mixer-only and feed-forward-only layers in any order: the tree
    holds the ONE norm of the branch that is there, the cache a plane a
    softmax layer and a state row a state-space one, and the engine's
    logits are the reference's."""
    cfg = tiny(pattern=pattern)
    assert cfg.num_layers == len(pattern)
    assert (cfg.layer_kinds, cfg.ffn_kinds) == kinds_from_pattern(pattern)
    params = mt.init_params(cfg, 5)
    for i, letter in enumerate(pattern):
        assert set(params[f"layer_{i}"]) == {
            "M": {"input_norm", "mamba"}, "*": {"input_norm", "attn"},
            "E": {"post_attn_norm", "moe", "shared_up_proj",
                  "shared_down_proj"}}[letter]
    eng = engine(cfg, params, 16)
    assert eng.runner.kv_layers == pattern.count("*")
    if "M" in pattern:
        assert eng.runner.state_spec["layers"] == pattern.count("M")
    else:
        assert eng.runner.state_spec is None
    prompt = prompt_of(19, seed=len(pattern))
    lg = np.asarray(eng.put([1], [prompt])[1])
    want = ref_logits(cfg, params, prompt, [len(prompt) - 1])[0]
    assert np.abs(lg - want).max() < TOL
    tok = int(np.argmax(lg))
    toks = [int(t) for t in eng.decode_batch([1], [tok], 4)[1]]
    seq = prompt + [tok] + toks
    want = ref_logits(cfg, params, seq, list(range(len(prompt), len(seq))))
    assert toks == np.argmax(want[:-1], -1).tolist()


def test_a_pattern_letter_the_runner_has_no_branch_for_is_refused():
    with pytest.raises(ValueError, match="'-'"):
        kinds_from_pattern("M-M*")


@pytest.mark.parametrize("family, kinds, ffn", [
    ("llama", None, None), ("mixtral", None, None),
    ("solar_open2", ("attn", "kda", "kda", "kda"), None),
    ("pangu_ultra_moe", ("mla",) * 4, ("dense", "moe", "moe", "moe")),
    ("kimi_linear", ("kda", "kda", "kda", "mla"),
     ("dense", "moe", "moe", "moe"))])
def test_the_five_other_families_lists_resolve_as_before(family, kinds, ffn):
    """What the step function reads of the families the benchmark runs:
    the same lists (or none: every layer softmax attention and one kind of
    feed-forward), no None in them, SiLU and gated experts."""
    from deepspeed_tpu.inference.v2.llama_runner import _mlp_act
    from deepspeed_tpu.models.kimi_linear import KimiLinearConfig
    from deepspeed_tpu.models.llama import LlamaConfig
    from deepspeed_tpu.models.mixtral import MixtralConfig
    from deepspeed_tpu.models.pangu_ultra_moe import PanguUltraMoEConfig
    from deepspeed_tpu.models.solar_open2 import SolarOpen2Config
    cfg = {"llama": LlamaConfig, "mixtral": MixtralConfig,
           "solar_open2": SolarOpen2Config,
           "pangu_ultra_moe": PanguUltraMoEConfig,
           "kimi_linear": KimiLinearConfig}[family].tiny(num_layers=4)
    assert (getattr(cfg, "layer_kinds", None) or None) == kinds
    assert (getattr(cfg, "ffn_kinds", None) or None) == ffn
    assert _mlp_act(cfg) is jax.nn.silu
    assert getattr(cfg, "gated_experts", True)
    assert _mlp_act(tiny()) is relu2


# ------------------- (d) the expert width and the shares ------------------ #


@pytest.mark.parametrize("impl", [None, "interpret"],
                         ids=["ragged_dot", "grouped-kernel"])
def test_an_expert_width_of_half_a_lane_group_more_equals_the_plain_product(
        impl):
    """An expert width that is no multiple of 128 lanes (232 = 1.8125
    groups, as 1856 = 14.5), stored padded to 256 with zero columns of
    ``W_up`` and zero rows of ``W_down``: the sparse block over the stored
    stacks, through ``ragged_dot`` and through the grouped kernel
    (interpreted; ``fits`` takes the stored width and refuses the
    published one), equals the plain product at the published width."""
    from deepspeed_tpu.moe.sharded_moe import grouped_moe_ffn
    from deepspeed_tpu.ops.kernels import grouped_ffn
    rng = np.random.default_rng(0)
    E, M, F, k, S = 4, 128, 232, 2, 24
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    wi, wo = f(E, M, F) * M ** -0.5, f(E, F, M) * F ** -0.5
    x, logits = f(S, M), f(S, E)
    wi_s, wo_s = pad_experts(wi, wo)
    assert wi_s.shape == (E, M, 256) and wo_s.shape == (E, 256, M)
    assert not grouped_ffn.fits((wi, wo), jnp.float32)
    assert grouped_ffn.fits((wi_s, wo_s), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, _ = grouped_moe_ffn(x, logits, k, (wi_s, wo_s), relu2,
                                 jnp.float32, score="sigmoid",
                                 weight_scale=2.5, impl=impl)
        s = jax.nn.sigmoid(logits)
        top, idx = jax.lax.top_k(s, k)
        w = top / top.sum(-1, keepdims=True) * 2.5
        every = jnp.einsum("enf,efm->enm",
                           relu2(jnp.einsum("nm,emf->enf", x, wi)), wo)
        want = sum(w[:, j, None] * every[idx[:, j], jnp.arange(S)]
                   for j in range(k))
    assert float(jnp.abs(got - want).max()) < 1e-4


def test_the_stored_tail_is_zero_and_counts_for_nothing(model):
    cfg, params = model
    assert (cfg.intermediate_size, cfg.expert_width_stored) == (40, 128)
    moe = params["layer_1"]["moe"]
    assert moe["wi"].shape == (4, 64, 128) and moe["wo"].shape == (4, 128, 64)
    assert float(jnp.abs(moe["wi"][..., 40:]).max()) == 0.0
    assert float(jnp.abs(moe["wo"][:, 40:]).max()) == 0.0
    assert float(jnp.abs(moe["wi"][..., :40]).min()) > 0.0
    # the flax init keeps the tail zero too, and the counts are the
    # published width's
    fresh = NemotronH(cfg).init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 4), jnp.int32))["params"]
    assert float(jnp.abs(fresh["layer_1"]["moe"]["wi"][..., 40:]).max()) == 0
    assert float(jnp.abs(fresh["layer_1"]["moe"]["wo"][:, 40:]).max()) == 0
    real = sum(int(np.count_nonzero(np.asarray(x)))
               for x in jax.tree_util.tree_leaves(params))
    assert abs(real / param_counts(cfg)[0] - 1) < 0.01


def test_the_two_shares_add_up_to_the_uncut_layer():
    """Guide section 4 at the deployment's own division: the routed parts
    of the 2 shares (4 of 8 experts each, as 64 of 128), plus the shared
    expert once, equal the uncut reference's layer: in the engine's sparse
    block and in the reference alike."""
    from deepspeed_tpu.inference.v2.llama_runner import _moe_mlp
    whole_cfg = tiny(experts_held=None, experts_first=0)
    whole = mt.init_params(whole_cfg, 11)["layer_1"]
    # a bias large enough to move the selection of some tokens
    whole["moe"]["sel_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(5), (8,))
    h = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 64))
    kw = dict(top_k=2, scaling=whole_cfg.routed_scaling, width=40)
    assert whole_cfg.routed_scaling == 2.5

    def share(first, held):
        return H.share_of(whole_cfg, whole["moe"], first, held,
                          ("wi", "wo"))

    with jax.default_matmul_precision("highest"):
        once = reference._relu2(h @ whole["shared_up_proj"]["kernel"]) \
            @ whole["shared_down_proj"]["kernel"]
        uncut = reference._sparse_mlp(whole["moe"], h, first=0, **kw) + once
        parts, refs = [], []
        for first in (0, 4):
            cfg, p = share(first, 4)
            parts.append(_moe_mlp(p, h, cfg, jnp.float32)[0])
            refs.append(reference._sparse_mlp(p, h, first=first, **kw))
        unbiased = reference._sparse_mlp(
            dict(whole["moe"], sel_bias=jnp.zeros((8,))), h, first=0, **kw)
    H.shares_add_up(parts, refs, uncut, once)
    # and the bias took part: without it other experts are chosen
    assert float(jnp.abs(unbiased + once - uncut).max()) > 1e-2


# ------------------------- (e) the cache's two parts ---------------------- #


def test_one_cache_value_holds_paged_planes_and_a_pool_of_oblong_states(
        model):
    """``"mamba2"`` beside ``"attn"`` builds K and V planes over the
    softmax layer alone and a state pool over the state-space ones, a
    state ``[heads, head_dim, state]`` with its two sizes apart, in one
    donated value."""
    cfg, params = model
    eng = engine(cfg, params)
    r, cache = eng.runner, eng.kv_cache
    assert (r.kv_planes, r.kv_layers, r.kv_heads, r.head_dim) \
        == (2, 1, 2, 16)
    assert r.state_spec == {"kind": "mamba2", "layers": 3, "heads": 4,
                            "d_v": 8, "d_k": 16, "taps": 4,
                            "conv_width": 96}
    assert cache.data.shape == (1, 2, 25 * 16, 32)
    assert [s.shape for s in cache.state] == [(5, 4, 8, 16)] * 3
    assert cache.conv.shape == (3, 5, 3, 96)
    assert cache.kv_bytes_per_token() == 2 * 32 * 4 \
        == mt.kv_bytes_per_token(cfg, 4)
    assert cache.state_bytes_per_slot() == 3 * (4 * 8 * 16 + 3 * 96) * 4
    assert cache.memory_bytes() == 2 * 25 * 16 * 32 * 4 \
        + 5 * cache.state_bytes_per_slot()
    eng.put([1], [prompt_of(20)])
    pool = eng._kv_data
    slot = eng.state.sequences[1].state_slot
    for s in pool.state:
        assert float(jnp.abs(s[slot]).max()) > 0
        assert float(jnp.abs(s[-1]).max()) == 0.0       # the idle row


def test_the_region_and_the_counter_are_in_the_vocabulary(model):
    from deepspeed_tpu.telemetry.trace import REGIONS
    assert "ssm" in REGIONS and len(REGIONS) == 24
    cfg, params = model
    assert "kv_bytes_live" in engine(cfg, params).pipeline_stats
    # the state-space layers trace under the region, the kernel inside it
    eng = engine(cfg, params)
    text = eng.runner._step.trace(
        params, eng._kv_data, RaggedBatch(
            jnp.zeros((4, 1), jnp.int32), jnp.zeros((4,), jnp.int32),
            jnp.ones((4,), jnp.int32), jnp.zeros((4, 6), jnp.int32),
            jnp.arange(4, dtype=jnp.int32))).lower().as_text(
                debug_info=True)
    assert "rg.ssm" in text and "rg.linear_attn" not in text


# ------------------------------ (f) refusals ----------------------------- #


@pytest.mark.parametrize("feature, kw, call", H.REFUSALS)
def test_what_needs_a_state_snapshot_refuses_by_the_new_kinds_name(
        model, feature, kw, call):
    """What a recurrent model refuses this model refuses, by the one
    wording, under ITS layer kind: construction options by
    ``config.validate``, calls by the engine. Its K/V planes are the paged
    ones: no latent refusal beside it."""
    from deepspeed_tpu.inference.v2.config import stateful_refusal
    said = FAMILY.refusal(model, feature, kw, call)
    assert said == stateful_refusal(feature, "mamba2")
    assert "'mamba2'" in said


# ------------------------- (g) registry and loader ----------------------- #


def _published():
    return H.published(CONFIG, REDUCED)


def test_config_from_hf_layer_lists_and_parameter_counts():
    """The numbers under Tentpole of ISSUE 44 (and in the configuration
    file's ``deployment``)."""
    arch, cfg = config_from_hf(_published())
    assert arch == "nemotron_h" and isinstance(cfg, NemotronHConfig)
    assert len(cfg.layer_kinds) == len(cfg.ffn_kinds) == cfg.num_layers == 52
    assert (cfg.layer_kinds.count("mamba2"), cfg.ffn_kinds.count("moe"),
            cfg.layer_kinds.count("attn")) == (23, 23, 6)
    # every layer is ONE branch
    assert all((k is None) != (f is None)
               for k, f in zip(cfg.layer_kinds, cfg.ffn_kinds))
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups,
            cfg.mamba_state, cfg.mamba_conv, cfg.mamba_chunk) \
        == (64, 64, 8, 128, 4, 128)
    assert (cfg.mamba_inner, cfg.mamba_conv_width) == (4096, 6144)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.expert_width_stored,
            cfg.shared_expert_size) == (2688, 1856, 1920, 3712)
    assert (cfg.num_experts, cfg.held, cfg.experts_top_k) == (128, 128, 6)
    assert cfg.routed_scaling == 2.5 and cfg.router_bias
    assert cfg.router_score == "sigmoid" and cfg.norm_topk_prob
    assert not cfg.use_rope and not cfg.gated_experts
    assert cfg.mlp_act == "relu2" and not cfg.tie_embeddings
    assert (cfg.vocab_size, cfg.rms_eps) == (131072, 1e-5)
    total, active = param_counts(cfg)
    assert abs(total / 31.58e9 - 1) < 0.001        # the published "30B"
    assert abs(active / 3.58e9 - 1) < 0.001        # "-A3B", embedding in


def test_the_benchmarks_cut_is_a_share_of_the_published_model():
    d = H.benchmark_config(CONFIG)
    cfg = mt.model_config(d)
    assert (cfg.layer_kinds, cfg.ffn_kinds) \
        == kinds_from_pattern("MEMEM*EMEMEM*")
    assert (cfg.num_experts, cfg.held, cfg.vocab_size) == (128, 64, 65536)
    total, _ = param_counts(cfg)
    assert abs(total / 3.926e9 - 1) < 0.001        # 7.85 GB in bfloat16
    assert mt.kv_bytes_per_token(cfg) == 2048      # 2 x K, V x 2 x 128 x 2 B
    # every catalog key is carried; what differs is what ``reduced`` names
    cat = H.catalog_row("NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert d["_source"] == cat["source_url"]
    cat = cat["config"]
    assert {k for k in cat if d.get(k) != cat[k]} == set(d["reduced"]) \
        == set(REDUCED)
    assert all(d[k + "_published"] == cat[k] for k in REDUCED)
    assert d["hybrid_override_pattern"] \
        == cat["hybrid_override_pattern"][:13]
    assert (d["chips_sharing_a_layer"], d["chips_in_the_deployment"]) \
        == (2, 8)


@pytest.mark.parametrize("key, value", [
    ("n_group", 8), ("topk_group", 4), ("mlp_hidden_act", "silu"),
    ("mamba_hidden_act", "gelu"), ("attention_bias", True),
    ("mlp_bias", True), ("mamba_proj_bias", True), ("use_conv_bias", False),
    ("n_shared_experts", 2), ("sliding_window", 4096),
    ("num_hidden_layers", 51)])
def test_config_from_hf_refuses_what_it_does_not_implement(key, value):
    H.hf_refuses(_published(), {key: value}, key)


def test_loader_names_reach_every_leaf():
    """A checkpoint named as the family's are (every block ``norm`` +
    ``mixer`` whatever its kind, per-expert ``up_proj`` / ``down_proj`` at
    the PUBLISHED width, the convolution ``[C, 1, K]``) converts to the
    tree the runner serves, leaf for leaf, the experts at the stored
    width."""
    cfg = tiny(experts_held=None, experts_first=0)
    params = jax.tree_util.tree_map(np.asarray, mt.init_params(cfg, 1))
    F = cfg.intermediate_size
    state = H.hf_trunk(params, "backbone", "embeddings", "norm_f")
    pattern = "MEM*EME"
    for i, letter in enumerate(pattern):
        p, pre = params[f"layer_{i}"], f"backbone.layers.{i}"
        norm = "post_attn_norm" if letter == "E" else "input_norm"
        state[f"{pre}.norm.weight"] = p[norm]["scale"]
        m = f"{pre}.mixer"
        if letter == "M":
            k = p["mamba"]
            state[f"{m}.in_proj.weight"] = k["in_proj"].T
            state[f"{m}.out_proj.weight"] = k["out_proj"].T
            state[f"{m}.conv1d.weight"] = k["conv_w"].T[:, None]
            state[f"{m}.conv1d.bias"] = k["conv_b"]
            for n in ("A_log", "D", "dt_bias"):
                state[f"{m}.{n}"] = k[n]
            state[f"{m}.norm.weight"] = k["norm"]
        elif letter == "*":
            for n in "qkvo":
                state[f"{m}.{n}_proj.weight"] = \
                    p["attn"][f"{n}_proj"]["kernel"].T
        else:
            state[f"{m}.gate.weight"] = p["moe"]["gate"].T
            state[f"{m}.gate.e_score_correction_bias"] = p["moe"]["sel_bias"]
            for n in ("up", "down"):
                state[f"{m}.shared_experts.{n}_proj.weight"] = \
                    p[f"shared_{n}_proj"]["kernel"].T
            for e in range(cfg.num_experts):
                state[f"{m}.experts.{e}.up_proj.weight"] = \
                    p["moe"]["wi"][e, :, :F].T
                state[f"{m}.experts.{e}.down_proj.weight"] = \
                    p["moe"]["wo"][e, :F].T
    H.loader_reaches_every_leaf(
        "nemotron_h", state, {"hybrid_override_pattern": pattern}, params)
