"""Mellum (a window PER LAYER: sliding-window layers whose rows live in a
bounded window pool beside the paged pool in the one cache value, YaRN on
the full layers alone, per-head QK-norm, a renormalised softmax router over
a share of the experts) through the normal engine, at a small size on the
CPU: hidden 64, 4 query heads over 2 kv heads of 16, 8 experts of width 32
of which a share holds 4, top-2, layers ``swa swa swa attn``, a window of 8
rows over blocks of 4, so that the pool (R = 4 blocks a slot) wraps several
times inside every test. Logits against the plain reference
(``benchmark/reference/mellum.py``) through prefill chunks, the fused loop
and its flush at every residue of a start position, a slot's second
tenant, the YaRN table by hand, the share rule of the model-configs guide,
the cache's two pools, the refusals of the new kind, and that the seven
other families build what they built."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmark.model_types import mellum as mt
from benchmark.reference import mellum as reference
from deepspeed_tpu.inference.v2 import RaggedInferenceConfig
from deepspeed_tpu.inference.v2.kv_cache import window_blocks
from deepspeed_tpu.inference.v2.model_runner import (RaggedBatch,
                                                     window_tables)
from deepspeed_tpu.models.llama import yarn_frequencies
from deepspeed_tpu.models.mellum import (Mellum, MellumConfig, YarnRope,
                                         param_counts)
from deepspeed_tpu.models.registry import config_from_hf
from family_harness import prompt_of

CONFIG = "mellum2-12b-a2.5b.json"
REDUCED = ("num_hidden_layers", "layer_types", "mlp_layer_types",
           "num_experts", "vocab_size")
WINDOW, BLOCK, CHUNK, LOOP = 8, 4, 8, 4


def tiny(**kw):
    kw.setdefault("experts_held", 4)
    kw.setdefault("experts_first", 2)
    return MellumConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                             **kw)


#: float32 engine against a float32 reference at highest precision: what
#: is left is the order of the sums (the paged attention's online softmax
#: over tiles against the dense one, the grouped matmul against the dense
#: mask, the rotary table built by numpy against jax.numpy's), a few 1e-6
#: on logits of size 4. Blocks of 4 rows under a window of 8, so that a
#: slot of the window pool wraps inside every test
FAMILY = H.Family(mt, tiny, tol=2e-4, chunk_size=CHUNK, block_size=BLOCK,
                  num_blocks=64, max_blocks_per_seq=24,
                  decode_loop_steps=LOOP)
TOL = FAMILY.tol
engine = FAMILY.engine


@pytest.fixture(scope="module")
def model():
    return FAMILY.model()


@pytest.fixture(scope="module")
def shared_engine(model):
    """One engine for the residue walk: its programs compile once and its
    slots pass from tenant to tenant."""
    return engine(*model)


def ref_logits(cfg, params, tokens, at, **variant):
    """The reference's logits, or those of the reference with ``variant``
    wrong."""
    if not variant:
        return FAMILY.ref_logits(cfg, params, tokens, at)
    fn = jax.jit(lambda p, t, a: reference.logits(
        p, t, a, **mt.reference_dims(cfg), **variant))
    return np.asarray(fn(params, jnp.asarray([tokens]),
                         jnp.asarray([at])))[0]


def serve(eng, model, uid, prompt, loops=2, decode="fused"):
    """``Family.walk`` at ``loops`` fused loops of ``LOOP`` steps (or as
    many single steps)."""
    FAMILY.walk(eng, model, uid, prompt, decode, (LOOP,) * loops)


# ------------------------- (a) engine vs reference ------------------------ #


@pytest.mark.parametrize("chunk", [64, 8], ids=["one-chunk", "five-chunks"])
@pytest.mark.parametrize("decode", ["fused", "pipelined"])
def test_engine_logits_match_the_reference(model, chunk, decode):
    """A 37-token prompt prefilled in one chunk or in five, 8 tokens
    decoded through two fused loops (the rows in the ring, then the flush
    into BOTH pools) or step by step, then one more position's logits: each
    against the reference's forward pass over the whole sequence (dense
    attention under each layer's own mask, no cache). A window layer's
    slot holds 16 rows (72 at the single chunk), so 46 positions wrap it."""
    eng = engine(*model, chunk)
    assert eng.kv_cache.window_blocks == -(-(WINDOW - 1 + chunk) // BLOCK)
    serve(eng, model, 7, prompt_of(37), decode=decode)
    stats = eng.pipeline_stats
    # the full layer's rows: one layer's worth, K and V, 2 heads x 16
    live = (sum(range(38, 46)) if decode == "pipelined"
            else LOOP * (37 + 41)) + 46
    assert stats["decode_kv_rows_live"] == live
    assert stats["kv_bytes_live"] == live * 1 * 2 * 2 * 16 * 4
    # the window layers': at most the window, less the loop's own rows
    wlive = 8 * WINDOW + WINDOW if decode == "pipelined" \
        else 2 * sum(WINDOW - 1 - t for t in range(LOOP)) + WINDOW
    assert stats["window_rows_live"] == wlive
    assert stats["window_bytes_live"] == wlive * 3 * 2 * 2 * 16 * 4
    # ... and the columns the decode kernel's plan holds for them: every
    # chunk of every call, from the helper the kernel plans with
    from deepspeed_tpu.ops.kernels import decode_rows_scored
    calls = 9 if decode == "pipelined" else 2 * LOOP + 1
    assert stats["window_rows_scored"] == calls * decode_rows_scored(
        4, 24 * BLOCK, eng._window_tile, 2 * 16 * 4, WINDOW)
    assert stats["window_rows_scored"] >= stats["window_rows_fetched"] \
        >= wlive


@pytest.mark.parametrize("length", range(21, 21 + 16))
def test_every_residue_of_a_start_position(model, shared_engine, length):
    """The derivation of R, walked: a prompt of every length modulo the
    slot's 16 rows, so that the last chunk, the two flushes and the single
    step after them start at every residue of the slot (and of a block),
    aligned or not; each tenant takes the slot its predecessor left full."""
    eng = shared_engine
    assert eng.kv_cache.window_blocks == 4
    try:
        serve(eng, model, length, prompt_of(length, length))
    finally:
        eng.flush(length)


def test_engine_through_the_kernels_matches_the_reference():
    """The same walk through the Pallas kernels, interpreted: the BlockSpec
    prefill kernel and the decode kernel (rows of 128 lanes: 2 kv heads of
    64) read a window layer through the slot's table, from the first tile
    its window reaches, and mask by position what a wrapped tile holds."""
    cfg = tiny(attn_head_dim=64)
    params = mt.init_params(cfg, 5)
    eng = engine(cfg, params, block_size=8, num_blocks=32,
                 max_blocks_per_seq=12, attention_impl="paged_flash")
    assert eng.kv_cache.window_blocks == 2
    serve(eng, (cfg, params), 1, prompt_of(29))


def test_flax_model_and_runner_read_one_tree(model):
    cfg, params = model
    prompt = prompt_of(23)
    want = np.asarray(Mellum(cfg).apply({"params": params},
                                        jnp.asarray([prompt])))[0, -1]
    got = np.asarray(engine(cfg, params).put([1], [prompt])[1])
    assert np.abs(got - want).max() < TOL


# --------------------- (b) slots, tenants and idle rows ------------------- #


def test_a_slots_second_tenant_reads_none_of_the_firsts_rows(model):
    """A sequence fills its slot's 16 rows several times over and leaves;
    the next tenant of the SAME slot, 5 tokens long, attends over its own
    rows alone (the rest of the slot still holds the first tenant's)."""
    cfg, params = model
    eng = engine(cfg, params, max_seqs=1)
    eng.put([1], [prompt_of(40)])
    slot = eng.state.sequences[1].state_slot
    eng.flush(1)
    serve(eng, model, 2, prompt_of(5, 9), loops=1)
    assert eng.state.sequences[2].state_slot == slot
    rows = np.asarray(eng._kv_data.window)[:, :, slot * 16:(slot + 1) * 16]
    assert np.abs(rows).min(axis=-1).min() > 0     # every row was written


def test_a_sequence_that_sits_a_step_out_keeps_its_rows(model):
    """Two sequences hold slots; a step and a fused loop that carry only
    one of them leave the other's rows of the window pool bit for bit, and
    the idle slot's blocks but the trash block untouched."""
    cfg, params = model
    eng = engine(cfg, params)
    eng.put([1, 2], [prompt_of(19, 1), prompt_of(26, 2)])
    s1 = eng.state.sequences[1].state_slot
    before = np.asarray(eng._kv_data.window)
    tok = int(np.argmax(eng.put([2], [[5]])[2]))
    eng.decode_batch([2], [tok], LOOP)
    after = np.asarray(eng._kv_data.window)
    R = eng.kv_cache.window_blocks * BLOCK
    assert (after[:, :, s1 * R:(s1 + 1) * R]
            == before[:, :, s1 * R:(s1 + 1) * R]).all()
    idle = after[:, :, 4 * R:5 * R - BLOCK]       # max_seqs = 4: slot 4
    assert not idle.any()
    assert (after != before).any()


def test_the_table_is_a_function_of_the_slot(model):
    cfg, params = model
    eng = engine(cfg, params)
    table = np.asarray(window_tables(jnp.asarray([2, 0, 4]), eng._kv_data,
                                     eng.config))
    assert table.shape == (3, 24)
    assert table[0].tolist() == [8 + b % 4 for b in range(24)]
    assert table[1, :6].tolist() == [0, 1, 2, 3, 0, 1]
    assert table[2].max() == 4 * 4 + 3            # the idle slot's last


@pytest.mark.parametrize("window, chunk, loop, block, want", [
    (1024, 512, 128, 256, 6), (1024, 256, 128, 256, 5),
    (1024, 512, 128, 128, 12), (4096, 512, 16, 256, 18),
    (8, 8, 4, 4, 4), (8, 64, 4, 4, 18), (8, 2, 16, 4, 6)])
def test_window_blocks_is_derived_in_one_place(window, chunk, loop, block,
                                               want):
    """R = ceil((window - 1 + the most rows a step stores) / block): the
    cell's 6 blocks of 256, and what other shapes would take."""
    cfg = RaggedInferenceConfig(
        max_seqs=4, chunk_size=chunk, block_size=block, num_blocks=64,
        max_blocks_per_seq=32, decode_loop_steps=loop, prefill_chunk_cap=0)
    assert window_blocks(window, cfg) == want
    assert want * block >= window - 1 + max(chunk, loop)
    assert (want - 1) * block < window - 1 + max(chunk, loop)


def test_a_loop_longer_than_the_pool_was_sized_for_refuses(model):
    cfg, params = model
    eng = engine(cfg, params)
    eng.put([1], [prompt_of(9)])
    with pytest.raises(ValueError, match="window pool sized"):
        eng.decode_batch([1], [3], CHUNK + 1)
    assert len(eng.decode_batch([1], [3], CHUNK)[1]) == CHUNK


def test_one_block_too_few_serves_wrong_logits(model, monkeypatch):
    """R is the FEWEST blocks that is exact: with one fewer, a chunk's rows
    overwrite rows its own first queries still need."""
    from deepspeed_tpu.inference.v2 import kv_cache
    cfg, params = model
    real = kv_cache.window_blocks
    monkeypatch.setattr(kv_cache, "window_blocks",
                        lambda w, c: real(w, c) - 1)
    eng = engine(cfg, params)
    assert eng.kv_cache.window_blocks == 3
    prompt = prompt_of(37)
    lg = np.asarray(eng.put([1], [prompt])[1])
    want = ref_logits(cfg, params, prompt, [36])[0]
    assert np.abs(lg - want).max() > 100 * TOL


# ------------------- (c) one cache value, two pools ---------------------- #


def test_one_cache_value_holds_the_paged_pool_and_the_window_pool(model):
    """A "swa" layer's rows live ONLY in the window pool: the paged pool
    has the one full layer's planes, the allocator its blocks alone."""
    cfg, params = model
    eng = engine(cfg, params)
    cache, pool = eng.kv_cache, eng._kv_data
    assert eng.runner.kv_layers == 1
    assert eng.runner.window_spec == {
        "layers": 3, "window": 8,
        "ring_of": {False: (3,), True: (0, 1, 2)}}
    assert pool.data.shape == (1, 2, 65 * 4, 32)
    assert pool.window.shape == (3, 2, 5 * 4 * 4, 32)
    assert pool.scales is None and pool.state is None
    assert cache.kv_bytes_per_token() == 1 * 2 * 32 * 4
    assert cache.window_bytes_per_slot() == 3 * 2 * 16 * 32 * 4
    assert cache.memory_bytes() == 2 * 260 * 32 * 4 \
        + 5 * cache.window_bytes_per_slot()
    assert cache.memory_bytes_per_chip() == cache.memory_bytes()
    free = cache.free_blocks
    eng.put([1], [prompt_of(20)])
    assert free - cache.free_blocks == 5          # the full layer's chain
    eng.flush(1)
    assert cache.free_blocks == free
    assert len(eng.state.state_slots_free) == 4


def test_the_region_and_the_counters_are_in_the_vocabulary(model):
    from deepspeed_tpu.telemetry.trace import REGIONS
    assert "attn_window" in REGIONS and len(REGIONS) == 24
    cfg, params = model
    eng = engine(cfg, params)
    assert {"window_rows_live", "window_rows_fetched", "window_rows_scored",
            "window_bytes_live"} <= set(eng.pipeline_stats)
    text = eng.runner._step.trace(
        params, eng._kv_data, RaggedBatch(
            jnp.zeros((4, 1), jnp.int32), jnp.zeros((4,), jnp.int32),
            jnp.ones((4,), jnp.int32), jnp.zeros((4, 24), jnp.int32),
            jnp.arange(4, dtype=jnp.int32))).lower().as_text(
                debug_info=True)
    assert text.count("rg.attn_window") > 0 and "rg.attn_core" in text


def test_the_window_counters_are_the_kernels_own_arithmetic(model):
    """``window_rows_live`` / ``_fetched`` in closed form against a walk
    over the steps with the kernel's start tile (``decode_rows_fetched``
    at a window shortened by the rows the ring holds), ``_scored`` against
    the kernel's plan: ``NCH x CR`` a live sequence and step."""
    from deepspeed_tpu.ops.kernels import decode_rows_fetched
    from deepspeed_tpu.ops.kernels.paged_attention import _decode_plan
    cfg, params = model
    eng = engine(cfg, params)
    ts = eng._window_tile
    runs = [(4, 3), (4, 9), (2, 40), (0, 7)]
    got = eng._decode_row_counts(runs, 4, in_ring=True)
    # the window's three tiles of 4 rows in one chunk, where the context
    # of 24 blocks would be 96 columns a sequence
    _, cr, nch = _decode_plan(4, 24 * BLOCK, ts, 2 * 16 * 4, WINDOW)
    assert (cr, nch) == (12, 1)
    assert got["window_rows_scored"] == 10 * nch * cr \
        >= got["window_rows_fetched"] >= got["window_rows_live"]
    live = sum(min(rows, max(WINDOW - 1 - t, 0))
               for ran, rows in runs for t in range(ran))
    fetched = sum(decode_rows_fetched(rows, ts, window=WINDOW - 1 - t)
                  for ran, rows in runs for t in range(ran))
    assert (got["window_rows_live"], got["window_rows_fetched"]) \
        == (live, fetched)
    step = eng._decode_row_counts([(1, 3), (1, 40)], 4)
    assert step["window_rows_scored"] == 2 * 12
    assert step["window_rows_live"] == 3 + WINDOW
    assert step["window_rows_fetched"] == sum(
        decode_rows_fetched(n, ts, window=WINDOW) for n in (3, 40))
    assert step["decode_kv_rows_live"] == 43


# ------------------------ (d) the position codes ------------------------- #


def test_the_yarn_table_by_hand_at_the_published_parameters():
    """ISSUE 48's formulas in float64 by hand: the ramp runs from
    frequency 18 to frequency 35 of 64, below it the plain rotary's
    frequency, above it a sixteenth of it."""
    D, theta, factor, orig = 128, 500000.0, 16.0, 8192

    def dim(n):
        return D * math.log(orig / (2 * math.pi * n)) / (2 * math.log(theta))

    low, high = max(math.floor(dim(32)), 0), min(math.ceil(dim(1)), D - 1)
    assert (low, high) == (18, 35)
    i = np.arange(D // 2, dtype=np.float64)
    plain = theta ** (-2 * i / D)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = (1 - ramp) * plain + ramp * plain / factor
    got = yarn_frequencies(D, theta, factor, orig, 32, 1)
    assert got.dtype == np.float32 and got.shape == (64,)
    assert np.abs(got / want - 1).max() < 1e-6
    assert np.abs(got[:19] / plain[:19] - 1).max() < 1e-6
    assert np.abs(got[35:] * 16 / plain[35:] - 1).max() < 1e-6
    # the reference builds its own table from the same formulas
    ref, scale = reference.rope_table(D, theta, (factor, orig, 32, 1, 1.25))
    assert np.abs(np.asarray(ref) / want - 1).max() < 1e-6 and scale == 1.25
    assert abs(0.1 * math.log(16) + 1 - 1.2772588722239782) < 1e-15


def test_each_layer_kind_has_its_own_code(model):
    cfg, _ = model
    assert cfg.rope_of("swa") == (None, None)
    inv, scale = cfg.rope_of("attn")
    assert scale == cfg.full_rope.attention_factor == 1.1386
    plain = 1.0 / cfg.rope_theta ** (np.arange(0, 16, 2) / 16)
    assert inv.shape == (8,) and not np.allclose(inv, plain)
    assert dataclasses.replace(cfg, full_rope=None).rope_of("attn") \
        == (None, None)


@pytest.mark.parametrize("variant", [
    dict(window_on="none"), dict(window_on="all"), dict(yarn_on=False),
    dict(attention_factor_on=False), dict(head_norm=False)],
    ids=lambda v: "-".join(f"{k}={x}" for k, x in v.items()))
def test_each_wrong_model_of_the_cells_check_differs(model, variant):
    """The reference with one thing wrong (the window left out of the
    sliding layers, applied to the full layer too, plain rotary on the full
    layer, the attention factor left out, the per-head norm left out) is
    not what the engine serves; the right one is."""
    cfg, params = model
    prompt = prompt_of(37)
    lg = np.asarray(engine(cfg, params).put([1], [prompt])[1])
    right = ref_logits(cfg, params, prompt, [36])[0]
    wrong = ref_logits(cfg, params, prompt, [36], **variant)[0]
    assert np.abs(lg - right).max() < TOL
    assert np.abs(lg - wrong).max() > 100 * TOL


def test_the_norm_is_over_each_heads_own_lanes(model):
    """``qk_norm == "head"``: one scale of head_dim a norm, and scaling ONE
    head's slice of W_q leaves the layer's output as it was (the norm
    undoes it a head; over the whole projection it would not)."""
    from deepspeed_tpu.inference.v2.llama_runner import _attn_mixer
    cfg, params = model
    assert cfg.qk_norm == "head"
    pa = params["layer_0"]["attn"]
    assert pa["q_norm"]["scale"].shape == pa["k_norm"]["scale"].shape == (16,)
    eng = engine(cfg, params)
    h = jax.random.normal(jax.random.PRNGKey(1), (4, 1, 64))
    batch = RaggedBatch(jnp.zeros((4, 1), jnp.int32),
                        jnp.zeros((4,), jnp.int32),
                        jnp.ones((4,), jnp.int32),
                        jnp.zeros((4, 24), jnp.int32),
                        jnp.arange(4, dtype=jnp.int32))
    pos, valid = jnp.zeros((4, 1), jnp.int32), jnp.ones((4, 1), bool)
    scaled = dict(pa, q_proj={"kernel": pa["q_proj"]["kernel"].at[
        :, 16:32].multiply(3.0)})
    outs = [_attn_mixer(p, h, eng.kv_cache.pool, 0, batch, cfg, eng.config,
                        pos, valid, jnp.float32, "swa")[1]
            for p in (pa, scaled)]
    assert float(jnp.abs(outs[0] - outs[1]).max()) < 1e-5


# ----------------------------- (e) the shares ----------------------------- #


def test_the_two_shares_add_up_to_the_uncut_layer():
    """Guide section 4 at the deployment's own division: the routed parts
    of the 2 shares (experts 0-3 and 4-7 of 8, as 0-31 and 32-63 of 64)
    equal the uncut reference's layer, in the engine's sparse block and in
    the reference alike; no shared expert to count once."""
    from deepspeed_tpu.inference.v2.llama_runner import _moe_mlp
    whole_cfg = tiny(experts_held=None, experts_first=0)
    whole = mt.init_params(whole_cfg, 11)["layer_1"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 64))
    assert whole_cfg.norm_topk_prob and whole_cfg.router_score == "softmax"

    def share(first, held):
        return H.share_of(whole_cfg, whole, first, held)

    with jax.default_matmul_precision("highest"):
        uncut = reference._sparse_mlp(whole, h, top_k=2, first=0)
        parts, refs = [], []
        for first in (0, 4):
            cfg, p = share(first, 4)
            parts.append(_moe_mlp(p, h, cfg, jnp.float32)[0])
            refs.append(reference._sparse_mlp(p, h, top_k=2, first=first))
    H.shares_add_up(parts, refs, uncut)
    # the top-2 weights are renormalised: they sum to 1 a token over the
    # two shares together
    probs = jax.nn.softmax(h @ whole["gate"], axis=-1)
    kept = jnp.sort(probs, axis=-1)[..., -2:]
    assert float(jnp.abs(kept.sum(-1)).max()) < 1.0


# ------------------------------ (f) refusals ------------------------------ #


@pytest.mark.parametrize("feature, kw, call", H.REFUSALS)
def test_what_needs_a_windows_rows_elsewhere_refuses_by_name(
        model, feature, kw, call):
    """One wording (``config.windowed_refusal``): construction options by
    ``config.validate``, calls by the engine."""
    from deepspeed_tpu.inference.v2.config import windowed_refusal
    said = FAMILY.refusal(model, feature, kw, call)
    assert said == windowed_refusal(feature)
    assert "'swa'" in said


@pytest.mark.parametrize("other", ["mla", "kda", "mamba2"])
def test_swa_beside_a_latent_or_recurrent_kind_refuses_at_construction(
        other):
    from deepspeed_tpu.inference.v2.llama_runner import LlamaRaggedRunner
    cfg = tiny(layer_kinds=("swa", other, "swa", "attn"))
    with pytest.raises(ValueError, match=f"'swa'.*{other}"):
        LlamaRaggedRunner(cfg, RaggedInferenceConfig(
            max_seqs=4, chunk_size=8, block_size=4, num_blocks=8,
            max_blocks_per_seq=4))


# ------------------ (g) the seven other families, as before --------------- #


def _family(name):
    from deepspeed_tpu.models.gpt2 import GPT2Config
    from deepspeed_tpu.models.kimi_linear import KimiLinearConfig
    from deepspeed_tpu.models.llama import LlamaConfig
    from deepspeed_tpu.models.mixtral import MixtralConfig
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig
    from deepspeed_tpu.models.pangu_ultra_moe import PanguUltraMoEConfig
    from deepspeed_tpu.models.solar_open2 import SolarOpen2Config
    if name == "gpt2":
        return GPT2Config(vocab_size=512, max_seq_len=64, num_layers=4,
                          num_heads=4, hidden_size=64)
    return {"llama": LlamaConfig, "mixtral": MixtralConfig,
            "solar_open2": SolarOpen2Config,
            "pangu_ultra_moe": PanguUltraMoEConfig,
            "kimi_linear": KimiLinearConfig,
            "nemotron_h": NemotronHConfig}[name].tiny(num_layers=4)


@pytest.mark.parametrize("family, kinds, ffn, paged, state", [
    ("gpt2", None, None, 4, None), ("llama", None, None, 4, None),
    ("mixtral", None, None, 4, None),
    ("solar_open2", ("attn", "kda", "kda", "kda"), None, 1, "kda"),
    ("pangu_ultra_moe", ("mla",) * 4, ("dense", "moe", "moe", "moe"), 4,
     None),
    ("kimi_linear", ("kda", "kda", "kda", "mla"),
     ("dense", "moe", "moe", "moe"), 1, "kda"),
    ("nemotron_h", ("mamba2", None, "mamba2", "attn", None, "mamba2", None),
     (None, "moe", None, None, "moe", None, "moe"), 1, "mamba2")])
def test_the_seven_other_families_build_what_they_built(family, kinds, ffn,
                                                        paged, state):
    """What the step function reads of the families the benchmark runs
    (the same lists, or none), and what their cache is: paged planes as
    before, a state pool where they had one, NO window pool and no slot
    for it, the cache value's own type as before."""
    from deepspeed_tpu.inference.v2.engine_v2 import _runner_for
    from deepspeed_tpu.inference.v2.kv_cache import BlockedKVCache
    from deepspeed_tpu.inference.v2.state_manager import StateManager
    cfg = _family(family)
    assert (getattr(cfg, "layer_kinds", None) or None) == kinds
    assert (getattr(cfg, "ffn_kinds", None) or None) == ffn
    assert not hasattr(cfg, "rope_of")
    icfg = RaggedInferenceConfig(max_seqs=4, chunk_size=8, block_size=4,
                                 num_blocks=8, max_blocks_per_seq=4)
    runner = _runner_for(cfg, icfg)
    assert runner.window_spec is None and runner.kv_layers == paged
    assert (runner.state_spec or {}).get("kind") == state
    cache = BlockedKVCache(icfg, runner.kv_layers, runner.kv_heads,
                           runner.head_dim, state_spec=runner.state_spec,
                           planes=runner.kv_planes,
                           window_spec=runner.window_spec)
    assert cache.window is None and cache.window_bytes_per_slot() == 0
    if state is None:
        assert isinstance(cache.pool, jax.Array)
        assert StateManager(icfg, cache).state_slots_free is None
    else:
        assert cache.pool.window is None and cache.pool.state is not None


def test_one_window_for_every_layer_stays_on_the_paged_pool():
    """``LlamaConfig.sliding_window`` (Mistral's: no layer list) keeps its
    behaviour: every layer's whole chain in the paged pool, the mask from
    the one number, no window pool."""
    from deepspeed_tpu.models.llama import Llama, LlamaConfig
    cfg = LlamaConfig.tiny(sliding_window=8, dtype=jnp.float32,
                           param_dtype=jnp.float32, attention_impl="xla")
    params = Llama(cfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    eng = engine(cfg, params)
    assert eng.kv_cache.window is None and not eng._windowed
    assert isinstance(eng._kv_data, jax.Array)
    prompt = prompt_of(37)
    want = np.asarray(Llama(cfg).apply({"params": params},
                                       jnp.asarray([prompt])))[0, -1]
    assert np.abs(np.asarray(eng.put([1], [prompt])[1]) - want).max() < TOL
    eng.decode_batch([1], [3], LOOP)
    assert eng.pipeline_stats["window_rows_live"] == 0
    full = dataclasses.replace(cfg, sliding_window=None)
    other = np.asarray(Llama(full).apply({"params": params},
                                         jnp.asarray([prompt])))[0, -1]
    assert np.abs(other - want).max() > 100 * TOL


# ------------------------- (h) registry and loader ------------------------ #


def _published():
    d = H.published(CONFIG, ("num_hidden_layers", "num_experts",
                             "vocab_size"))
    d["layer_types"] = (d["layer_types"][:4] * 7)
    d["mlp_layer_types"] = ["sparse"] * 28
    return d


def test_config_from_hf_layer_lists_and_parameter_counts():
    """The numbers under Tentpole of ISSUE 48: 12.15 B in all, 2.44 B a
    token: the model's own name back."""
    arch, cfg = config_from_hf(_published())
    assert arch == "mellum" and isinstance(cfg, MellumConfig)
    assert cfg.layer_kinds == ("swa", "swa", "swa", "attn") * 7
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 4, 128)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size) \
        == (2304, 896, 98304)
    assert (cfg.num_experts, cfg.held, cfg.experts_top_k) == (64, 64, 8)
    assert cfg.norm_topk_prob and cfg.router_score == "softmax"
    assert not cfg.router_bias and not cfg.shared_expert_size
    assert cfg.sliding_window == 1024 and cfg.qk_norm == "head"
    assert (cfg.rope_theta, cfg.rms_eps) == (500000.0, 1e-6)
    assert cfg.full_rope == YarnRope(16.0, 8192, 32.0, 1.0,
                                     1.2772588722239782)
    assert not cfg.tie_embeddings and not cfg.qkv_bias
    assert not hasattr(cfg, "residual_dtype")      # a bfloat16 stream
    total, active = param_counts(cfg)
    assert abs(total / 12.15e9 - 1) < 0.002
    assert abs(active / 2.44e9 - 1) < 0.002


def test_the_benchmarks_cut_is_a_share_of_the_published_model():
    d = H.benchmark_config(CONFIG)
    cfg = mt.model_config(d)
    assert cfg.layer_kinds == ("swa", "swa", "swa", "attn") * 2
    assert (cfg.num_experts, cfg.held, cfg.vocab_size) == (64, 32, 49152)
    total, _ = param_counts(cfg)
    assert abs(total / 1.983e9 - 1) < 0.001        # 3.97 GB in bfloat16
    assert mt.kv_bytes_per_token(cfg) == 4096      # 2 x K, V x 4 x 128 x 2 B
    cat = H.catalog_row("Mellum2-12B-A2.5B-Instruct")
    assert d["_source"] == cat["source_url"]
    cat = cat["config"]
    assert {k for k in cat if d.get(k) != cat[k]} == set(d["reduced"]) \
        == set(REDUCED)
    assert d["layer_types"] == cat["layer_types"][:8]
    assert d["mlp_layer_types"] == cat["mlp_layer_types"][:8]
    assert all(d[k + "_published"] == cat[k]
               for k in ("num_hidden_layers", "num_experts", "vocab_size"))
    assert d["rope_parameters"] == cat["rope_parameters"]
    assert (d["chips_sharing_a_layer"], d["chips_in_the_deployment"]) \
        == (2, 8)


@pytest.mark.parametrize("change, match", [
    ({"layer_types": ["chunked_attention"] * 28}, "layer_types"),
    ({"num_hidden_layers": 27}, "layer_types"),
    ({"mlp_layer_types": ["dense"] + ["sparse"] * 27}, "mlp_layer_types"),
    ({"attention_bias": True}, "attention_bias"),
    ({"rope_parameters": {"chunked_attention": {}}}, "rope_parameters"),
    ({"rope_parameters": {"full_attention": {"rope_type": "llama3"}}},
     "rope_type"),
    ({"rope_parameters": {"sliding_attention": {"rope_type": "yarn"}}},
     "rope_parameters")])
def test_config_from_hf_refuses_what_it_does_not_implement(change, match):
    H.hf_refuses(_published(), change, match)


def test_loader_names_reach_every_leaf():
    """A checkpoint named as Qwen3MoE's are (assumed: the family's config
    keys are that one's) converts to the tree the runner serves, leaf for
    leaf."""
    cfg = tiny(experts_held=None, experts_first=0, num_layers=2,
               layer_kinds=("swa", "attn"))
    params = jax.tree_util.tree_map(np.asarray, mt.init_params(cfg, 1))
    state = H.hf_trunk(params)
    for i in range(2):
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
    H.loader_reaches_every_leaf("mellum", state, {"num_experts": 8}, params)
