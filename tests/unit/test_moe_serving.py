"""Expert-parallel MoE serving tests (ISSUE 20): sharded expert stacks,
ragged all-to-all dispatch/combine, overlapped exchange
(inference/v2/expert_parallel.py + moe/sharded_moe.grouped_moe_ffn_ep_serve).

The contract under test: ``ep_size=2`` on the 8-device CPU mesh yields
TOKEN-IDENTICAL streams to the ``ep_size=1`` oracle across greedy,
sampled, speculative (dense draft + MoE target) and prefix-cache
serving; per-chip expert-stack bytes halve (the sparse-model HBM
lever); the expert axis's comm is exactly budgeted (TWO all_to_all hops
per MoE layer per step, 2*chunks under the chunked overlap, zero
anything-else); ``overlap='chunked'`` is numerics-preserving; ep
composes with tp on the 2-D (expert, model) mesh; drain/handoff
manifests cross ep geometries; the warm path stays compile-free; and
``ep_size=1`` is the exact single-chip engine (zero
collectives under the auditor).

Tier-1 wall discipline: every Mixtral engine build compiles real XLA
MoE programs on the 1-core harness, so the default-geometry oracle
(ep=1) and ep=2 engines are MODULE-scoped and shared across the parity
/ budget / memory / warm tests; only tests that mutate engine lifecycle
(drain) or need a different geometry (overlap, ep x tp, spec, prefix,
killswitch) build their own, and the widest ones ride the full tier.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.analysis import (CollectiveBudget, RecompileTripwire,
                                    assert_budget, audit_serve_programs,
                                    budget_args)
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceConfig,
                                        SamplingParams)
from deepspeed_tpu.inference.v2.expert_parallel import (
    EP_AXIS, expert_memory_report)
from deepspeed_tpu.models import llama, mixtral

L = 2          # layers of MixtralConfig.tiny (every layer is MoE)
V = 512        # its vocab


def _setup(**mcfg_kw):
    mcfg = mixtral.MixtralConfig.tiny(dtype=jnp.float32, **mcfg_kw)
    _, init_fn, _ = mixtral.make_model(mcfg)
    params = init_fn(jax.random.PRNGKey(0), seq_len=16)
    base = dict(max_seqs=2, chunk_size=8, block_size=4, num_blocks=64,
                max_blocks_per_seq=16, dtype="float32",
                decode_loop_steps=4)
    return mcfg, params, base


def _prompts(seed=29, n=2, lens=(11, 7)):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, V, lens[i % len(lens)]).tolist()
            for i in range(n)]


@pytest.fixture(scope="module")
def base_pair():
    """(mcfg, params, base-config) shared module-wide — PRNGKey(0) makes
    params deterministic, so inline engines built from this triple stay
    stream-identical to the shared oracle below."""
    return _setup()


@pytest.fixture(scope="module")
def oracle(base_pair):
    """The ep=1 oracle engine (single-chip grouped-GEMM MoE)."""
    mcfg, params, base = base_pair
    return InferenceEngineV2(mcfg, params, RaggedInferenceConfig(**base))


@pytest.fixture(scope="module")
def ep2(base_pair):
    """The ep=2 engine (2 experts/chip), built once."""
    mcfg, params, base = base_pair
    return InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
        **base, ep_size=2), devices=jax.devices()[:2])


@pytest.fixture(scope="module")
def ep2_reports(ep2):
    return audit_serve_programs(ep2)


# ------------------------------------------------------------------ #
# construction-time geometry validation
# ------------------------------------------------------------------ #


class TestEPGeometry:

    def test_tp_without_ep_rejected_at_construction(self, base_pair):
        # the former trace-time refusal (tp.py) moved to config.validate:
        # a MoE model with tp_size>1 must open the expert axis
        mcfg, params, base = base_pair
        with pytest.raises(ValueError, match="requires the expert axis"):
            InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
                **base, tp_size=2))

    def test_ep_on_dense_model_rejected(self):
        mcfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
        _, init_fn, _ = llama.make_model(mcfg)
        params = init_fn(jax.random.PRNGKey(0), seq_len=16)
        with pytest.raises(ValueError, match="MoE-only"):
            InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
                max_seqs=2, chunk_size=8, block_size=4, num_blocks=64,
                max_blocks_per_seq=16, dtype="float32", ep_size=2))

    def test_ep_seq_composition_excluded(self):
        with pytest.raises(ValueError):
            RaggedInferenceConfig(ep_size=2, seq_size=2,
                                  max_blocks_per_seq=16)

    def test_non_dividing_expert_count_rejected(self, base_pair):
        mcfg, params, base = base_pair
        with pytest.raises(ValueError, match="divide"):
            InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
                **base, ep_size=3))

    def test_expert_bytes_halve_at_ep2(self, ep2, oracle):
        # the HBM lever, gauge-read from the LIVE device shardings
        rep = expert_memory_report(ep2)
        assert rep["ep_size"] == 2
        assert rep["expert_bytes_per_chip"] * 2 == \
            rep["expert_bytes_total"]
        rep1 = expert_memory_report(oracle)
        assert rep1["expert_bytes_per_chip"] == rep1["expert_bytes_total"]


# ------------------------------------------------------------------ #
# token parity ep in {1, 2} x serving modes
# ------------------------------------------------------------------ #


class TestEPParity:
    """Streams must be identical across ep sizes — the expert axis is a
    placement change, not a model change (the dispatch is dropless at
    the default capacity factor, see ep_serve_capacity)."""

    def test_one_expert_moe_matches_dense_runner(self):
        # degenerate oracle: E=1, k=1 routes every token to the single
        # expert with weight softmax([v]) == 1.0, so the MoE runner must
        # emit the SAME stream as the dense Llama runner fed the same
        # weights (moe.wi_gate[0] == mlp.gate_proj etc.)
        mcfg, params, base = _setup(num_experts=1, experts_top_k=1)
        dense_cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
        dense_params = {}
        for k, v in params.items():
            if not k.startswith("layer_"):
                dense_params[k] = v
                continue
            lyr = dict(v)
            moe = lyr.pop("moe")
            lyr["mlp"] = {"gate_proj": {"kernel": moe["wi_gate"][0]},
                          "up_proj": {"kernel": moe["wi_up"][0]},
                          "down_proj": {"kernel": moe["wo"][0]}}
            dense_params[k] = lyr
        prompts = _prompts(seed=3)
        ref = InferenceEngineV2(dense_cfg, dense_params,
                                RaggedInferenceConfig(**base)).generate(
            prompts, max_new_tokens=5)
        got = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base)).generate(prompts, max_new_tokens=5)
        assert got == ref

    def test_ep2_greedy_token_identical(self, oracle, ep2):
        prompts = _prompts()
        ref = oracle.generate(prompts, max_new_tokens=6)
        assert ep2.generate(prompts, max_new_tokens=6) == ref

    def test_ep2_sampled_token_identical(self, oracle, ep2):
        prompts = _prompts(seed=5)
        sp = SamplingParams(temperature=0.8, top_k=20, seed=13)
        ref = oracle.generate(prompts, max_new_tokens=6, sampling=sp)
        got = ep2.generate(prompts, max_new_tokens=6, sampling=sp)
        assert got == ref

    def test_ep2_overlap_chunked_token_identical(self, base_pair, oracle):
        # the chunked dispatch/combine schedule (expert GEMMs for chunk
        # k under chunk k+1's exchange) must be numerics-preserving —
        # the overlap=off engine IS the parity oracle
        mcfg, params, base = base_pair
        prompts = _prompts(seed=7)
        ref = oracle.generate(prompts, max_new_tokens=6)
        eng = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base, ep_size=2, ep_comm_overlap="chunked",
            ep_comm_chunks=2), devices=jax.devices()[:2])
        assert eng.generate(prompts, max_new_tokens=6) == ref
        rep = audit_serve_programs(
            eng, programs=("step_greedy_fb",))["step_greedy_fb"]
        assert_budget(rep, CollectiveBudget(**budget_args(
            "ep-step-overlap", num_layers=L, chunks=2,
            label="ep2-step-chunked")))

    def test_ep2_spec_dense_draft_token_identical(self, base_pair,
                                                  oracle):
        # a dense Llama draft proposes, the sharded MoE target verifies:
        # speculation is lossless, so the composed pair matches the
        # plain ep=1 stream (attach_draft resets ep_size for the draft)
        mcfg, params, base = base_pair
        dcfg = llama.LlamaConfig.tiny(dtype=jnp.float32, num_layers=1)
        _, dinit, _ = llama.make_model(dcfg)
        dparams = dinit(jax.random.PRNGKey(7), seq_len=16)
        pat = np.random.default_rng(3).integers(1, V, 6).tolist()
        prompts = [(pat * 3)[:13]]
        ref = oracle.generate(prompts, max_new_tokens=6)
        eng = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base, ep_size=2, spec_decode="draft", spec_k=3),
            devices=jax.devices()[:2])
        draft = eng.attach_draft(dcfg, dparams)
        assert draft.config.ep_size == 1
        assert eng.generate(prompts, max_new_tokens=6) == ref

    @pytest.mark.full
    def test_ep2_prefix_cache_token_identical(self, base_pair):
        # shared preambles hit the cache on the SECOND wave and the
        # replicated pool's CoW copies stay geometry-free
        mcfg, params, base = base_pair
        rng = np.random.default_rng(11)
        pre = rng.integers(1, V, 8).tolist()
        prompts = [pre + rng.integers(1, V, 7).tolist() for _ in range(2)]

        def run(ep):
            eng = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
                **base, prefix_cache=True, ep_size=ep),
                devices=jax.devices()[:max(ep, 1)])
            first = eng.generate(prompts[:1], max_new_tokens=5)
            second = eng.generate(prompts, max_new_tokens=5)
            return first, second, eng.prefix_stats["matched_tokens"]

        ref_a, ref_b, ref_hits = run(1)
        got_a, got_b, got_hits = run(2)
        assert (got_a, got_b) == (ref_a, ref_b)
        assert got_hits == ref_hits and got_hits > 0

    @pytest.mark.full
    def test_ep4_greedy_token_identical(self, base_pair, oracle):
        # 1 expert/chip: the narrowest legal shard of the tiny model
        mcfg, params, base = base_pair
        prompts = _prompts(seed=9)
        ref = oracle.generate(prompts, max_new_tokens=6)
        got = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base, ep_size=4), devices=jax.devices()[:4]).generate(
            prompts, max_new_tokens=6)
        assert got == ref

    def test_ep2_tp2_composed_token_identical(self, base_pair, oracle):
        # composition is the point: 2-D (expert, model) mesh, attention
        # head-sharded over tp while experts shard over ep — still the
        # exact ep=1 stream
        mcfg, params, base = base_pair
        prompts = _prompts(seed=15)
        ref = oracle.generate(prompts, max_new_tokens=6)
        eng = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base, ep_size=2, tp_size=2), devices=jax.devices()[:4])
        assert eng.runner.epctx.mesh.shape == {EP_AXIS: 2, "model": 2}
        assert eng.generate(prompts, max_new_tokens=6) == ref

    def test_ep1_is_the_single_chip_engine(self, base_pair, oracle):
        # ep_size=1 is the exact pre-EP engine: programs carry ZERO
        # collectives, tokens match
        mcfg, params, base = base_pair
        eng = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base, ep_size=1))
        assert eng.runner.epctx is None
        for name, rep in audit_serve_programs(eng).items():
            assert rep.total_collectives == 0, (name, rep.summary())
        prompts = _prompts(seed=17)
        ref = oracle.generate(prompts, max_new_tokens=5)
        assert eng.generate(prompts, max_new_tokens=5) == ref


# ------------------------------------------------------------------ #
# drain / handoff across ep geometries
# ------------------------------------------------------------------ #


class TestEPDrainHandoff:

    def test_drain_replay_parity_ep2_to_ep1(self, base_pair, oracle):
        # drain an ep=2 engine mid-stream, replay the manifest on an
        # ep=1 engine: continuations token-identical to the
        # uninterrupted oracle — manifests record token chains, never
        # expert placement, so they cross ep geometries freely
        mcfg, params, base = base_pair
        prompts = {100: _prompts(seed=19)[0], 101: _prompts(seed=19)[1]}
        want = oracle.generate(list(prompts.values()), max_new_tokens=8)
        src = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base, ep_size=2), devices=jax.devices()[:2])
        uids = list(prompts)
        first = src.put(uids, list(prompts.values()), _greedy=True)
        got = {u: [first[u]] for u in uids}
        step1 = src.decode_pipelined(uids, [first[u] for u in uids], 3)
        for u in uids:
            got[u].extend(step1[u])
        m = src.drain()
        assert m["config"]["ep_size"] == 2
        dst = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base))
        out = dst.replay(m)        # replay itself emits a token
        for u in uids:
            got[u].append(int(out[u]))
        more = dst.decode_pipelined(uids, [got[u][-1] for u in uids], 3)
        for u in uids:
            got[u].extend(more[u])
        for i, u in enumerate(uids):
            assert got[u] == want[i], u

    @pytest.mark.full
    def test_drain_replay_parity_ep1_to_ep2(self, base_pair, oracle,
                                            ep2):
        # the reverse hop: a single-chip manifest resumes on the sharded
        # engine (module-scoped ep2 — replay flushes what it admits)
        mcfg, params, base = base_pair
        prompts = {200: _prompts(seed=23)[0]}
        want = oracle.generate(list(prompts.values()), max_new_tokens=8)
        src = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base))
        first = src.put([200], list(prompts.values()), _greedy=True)
        got = [first[200]]
        got.extend(src.decode_pipelined([200], [first[200]], 3)[200])
        m = src.drain()
        out = ep2.replay(m)
        got.append(int(out[200]))
        got.extend(ep2.decode_pipelined([200], [got[-1]], 3)[200])
        assert got == want[0]
        ep2.flush(200)


# ------------------------------------------------------------------ #
# audited hop budgets + warm-path compile hygiene
# ------------------------------------------------------------------ #


class TestEPHopBudget:
    """ISSUE 20 acceptance: the expert axis's comm is exactly TWO
    all_to_all hops per MoE layer — nothing extra rides along."""

    def test_step_dispatch_combine_budget(self, ep2_reports):
        # per MoE layer: dispatch + combine, nothing per-program (the
        # batch replicates, logits need no gather) — the spec lives in
        # the shared registry (analysis/budgets.py "ep-step"), the same
        # one dslint DSL008 cross-checks
        budget = CollectiveBudget(**budget_args(
            "ep-step", num_layers=L, label="ep2-step"))
        for name in ("step", "step_greedy", "step_greedy_fb",
                     "step_sample_fb"):
            assert_budget(ep2_reports[name], budget)

    def test_decode_loop_budget_scan_weighted(self, ep2_reports):
        # the fused loop's scan body carries the same 2 hops per MoE
        # layer, trip-weighted over the 4 loop steps; zero host
        # callbacks (the dispatch is entirely on-device)
        assert_budget(ep2_reports["decode_loop"], CollectiveBudget(
            **budget_args("ep-decode-loop", num_layers=L, steps=4,
                          label="ep2-decode-loop")))

    def test_a2a_hops_ride_the_expert_axis_only(self, ep2_reports):
        rep = ep2_reports["step_greedy_fb"]
        assert rep.by_kind() == {"all_to_all": 2 * L}
        assert rep.count(kind="all_to_all", axis=EP_AXIS) == 2 * L


class TestEPWarmPath:

    def test_warm_pipeline_zero_fresh_compiles(self, ep2):
        # the shared ep=2 engine has served the parity generates by now;
        # a put+pipelined-decode primes any remaining shape, then the
        # measured window must be compile-free (a miss here is a
        # shape/dtype leak in the dispatch/combine wrapper)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, V, 6).tolist() for _ in range(2)]
        uids = [70, 71]
        first = ep2.put(uids, prompts, _greedy=True)
        ep2.decode_pipelined(uids, [first[u] for u in uids], 4)
        with RecompileTripwire() as warm:
            ep2.decode_pipelined(
                uids, [int(rng.integers(1, V)) for _ in uids], 4)
        assert warm.fresh_compiles == 0, (
            f"{warm.fresh_compiles} jit cache misses on a warm ep=2 "
            f"pipeline run")
        for u in uids:
            ep2.flush(u)


# ------------------------------------------------------------------ #
# the fused loop's counters of a sparse layer (single chip)
# ------------------------------------------------------------------ #


def _scattered_counts(top, valid, E, held, kernel):
    """The counters as the scatter-add made them (NumPy ``add.at``)."""
    from deepspeed_tpu.ops.kernels import grouped_ffn as gf
    rows = np.zeros(E, np.int64)
    np.add.at(rows, top, np.broadcast_to(valid[:, None], top.shape))
    hit = reads = 0
    if kernel:
        first, count = held or (0, E)
        mine = np.zeros(E, np.int64)
        np.add.at(mine, top, 1)
        mine = mine[first:first + count]
        hit = int((mine > 0).sum())
        reads = int(gf.streams(jnp.asarray(mine, jnp.int32),
                               gf.row_tile(top.size, E),
                               gf.span_cap(top.size, E)).sum())
    return np.concatenate([rows, [hit, reads]])


class TestLoopCounters:
    """``rows`` (what ``expert_imbalance.rollout`` reads), ``hit`` and
    ``reads`` (``moe_reads_per_hit.rollout``) hold the integers the
    scatter-adds held: counted by a compare, under a ``valid`` mask with
    dead rows and under a chip's ``held`` share of the experts."""

    @pytest.mark.parametrize("kernel", [True, False],
                             ids=["kernel", "ragged_dot"])
    @pytest.mark.parametrize("held", [None, (0, 8), (8, 8), (4, 3)],
                             ids=lambda h: "all" if h is None
                             else f"held{h[0]}-{h[1]}")
    @pytest.mark.parametrize("dead", [0.0, 0.4, 1.0],
                             ids=["all-valid", "dead-rows", "none-valid"])
    def test_counts_are_the_scatter_adds(self, dead, held, kernel):
        from deepspeed_tpu.inference.v2.llama_runner import _moe_counts
        E, k, rows = 16, 4, 96
        rng = np.random.default_rng(int(dead * 10) + (held or (9, 9))[0])
        # every row's k choices differ, as a top-k's do; a hot expert so
        # that one held group passes a row tile
        top = np.stack([rng.permutation(E)[:k] for _ in range(rows)])
        top[: rows // 2, 0] = 5
        valid = rng.random(rows) >= dead
        got = jax.jit(_moe_counts, static_argnums=(2, 3, 4))(
            jnp.asarray(top, jnp.int32), jnp.asarray(valid), E, held, kernel)
        assert got.dtype == jnp.int32 and got.shape == (E + 2,)
        np.testing.assert_array_equal(
            np.asarray(got), _scattered_counts(top, valid, E, held, kernel))

    def test_a_hot_expert_past_the_span_cap_is_read_twice(self):
        from deepspeed_tpu.inference.v2.llama_runner import _moe_counts
        top = np.zeros((300, 1), np.int64)            # 300 rows on expert 0
        top[:3, 0] = [1, 2, 2]
        got = np.asarray(_moe_counts(jnp.asarray(top, jnp.int32),
                                     jnp.ones((300,), bool), 4, None, True))
        want = _scattered_counts(top, np.ones(300, bool), 4, None, True)
        np.testing.assert_array_equal(got, want)
        assert list(got) == [297, 1, 2, 0, 3, 5]      # 297 rows: 3 streams

    def test_moe_mlp_counts_valid_positions_alone(self, base_pair):
        """Through ``_moe_mlp`` itself: the routed rows of valid positions
        per expert, the choice ``route_topk`` makes of the layer's own
        logits; padding is computed and left out of the count."""
        from deepspeed_tpu.inference.v2.llama_runner import _moe_mlp
        from deepspeed_tpu.moe.sharded_moe import route_topk
        mcfg, params, _ = base_pair
        p_moe = params["layer_0"]["moe"]
        M = p_moe["gate"].shape[0]
        h = jax.random.normal(jax.random.PRNGKey(3), (3, 8, M), jnp.float32)
        valid = jnp.arange(8)[None, :] < jnp.asarray([8, 3, 0])[:, None]
        y, rows = _moe_mlp(p_moe, h, mcfg, jnp.float32, valid=valid)
        assert y.shape == h.shape
        top = np.asarray(route_topk(
            h.reshape(24, M) @ p_moe["gate"], mcfg.experts_top_k)[0])
        want = _scattered_counts(top, np.asarray(valid).reshape(24),
                                 mcfg.num_experts, None, False)
        np.testing.assert_array_equal(np.asarray(rows), want)
        assert int(rows[:-2].sum()) == 11 * mcfg.experts_top_k
