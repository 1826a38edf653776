"""Sequence-parallel serving tests (ISSUE 18): context-parallel prefill +
sequence-sharded paged attention (inference/v2/seq_parallel.py).

The contract under test: ``seq_size=2`` on the 8-device CPU mesh yields
TOKEN-IDENTICAL streams to the ``seq_size=1`` oracle across greedy,
sampled, speculative, prefix-cache and int8-pool serving; per-chip KV
pool bytes halve (the long-context capacity lever); the seq axis's comm
is exactly budgeted (ring hops = seq-1 ppermutes + 1 fresh-KV all-gather
per layer in prefill, 1 stat-combine all-gather per layer per fused
decode step, 1 owner psum per step program); drain/handoff manifests
cross seq geometries; the warm path stays compile-free; and
``seq_size=1`` is the exact pre-seq engine (zero
collectives under the auditor).

Tier-1 wall discipline: params init and every engine build compile real
XLA programs on the 1-core harness, so the default-geometry oracle
(seq=1) and seq=2 engines are MODULE-scoped and shared across the
parity / budget / warm tests (``generate`` flushes its sequences, and
the program auditor only traces, so sharing is state-safe); only tests
that mutate engine lifecycle (drain/handoff) or need a different config
(spec, prefix, int8, chunk=7) build their own.
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
from deepspeed_tpu.inference.v2.blocked_allocator import (BlockedAllocator,
                                                          OutOfBlocksError)
from deepspeed_tpu.inference.v2.seq_parallel import slot_rows
from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config

L = 2          # layers of the tiny model below
SEQ_AXIS = "seq"


def _setup(num_heads=4, hidden=64, vocab=96, **cfg_kw):
    mcfg = GPT2Config(vocab_size=vocab, max_seq_len=128, num_layers=L,
                      num_heads=num_heads, hidden_size=hidden,
                      dtype=jnp.float32)
    params = GPT2(mcfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    base = dict(max_seqs=4, chunk_size=8, block_size=4, num_blocks=64,
                max_blocks_per_seq=16, dtype="float32",
                attention_impl="dense", decode_loop_steps=4)
    base.update(cfg_kw)
    return mcfg, params, base


def _prompts(seed=21, n=3, lens=(9, 17, 26)):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 96, lens[i % len(lens)]).tolist()
            for i in range(n)]


@pytest.fixture(scope="module")
def base_pair():
    """(mcfg, params, base-config) shared module-wide — PRNGKey(0) makes
    params deterministic, so inline engines built from this triple stay
    stream-identical to the shared oracle below."""
    return _setup()


@pytest.fixture(scope="module")
def oracle(base_pair):
    """The seq=1 oracle engine (default geometry), built once."""
    mcfg, params, base = base_pair
    return InferenceEngineV2(mcfg, params, RaggedInferenceConfig(**base))


@pytest.fixture(scope="module")
def seq2(base_pair):
    """The seq=2 engine (default geometry), built once."""
    mcfg, params, base = base_pair
    return InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
        **base, seq_size=2))


@pytest.fixture(scope="module")
def seq2_reports(seq2):
    return audit_serve_programs(seq2)


# ------------------------------------------------------------------ #
# host-side layout: allocator homes + pool row math
# ------------------------------------------------------------------ #


class TestSeqLayout:

    def test_allocator_single_home_is_historical(self):
        a = BlockedAllocator(8)
        assert a.allocate(3) == [0, 1, 2]
        a.free([1])
        assert a.allocate(1) == [1]

    def test_allocator_homes_round_robin(self):
        a = BlockedAllocator(8, num_homes=2)
        # a chain's ordinals land on homes 0,1,0,1 and stay balanced
        got = a.allocate(4, homes=[0, 1, 0, 1])
        assert [b % 2 for b in got] == [0, 1, 0, 1]
        assert a.free_in_home(0) == a.free_in_home(1) == 2
        # a dry home fails even while the TOTAL could cover the ask
        with pytest.raises(OutOfBlocksError):
            a.allocate(3, homes=[0, 0, 0])
        assert a.shortfall([0, 0, 0]) == [1, 0]
        a.free(got)
        assert a.free_blocks == 8

    def test_slot_rows_seq1_is_classic_layout(self):
        rows = slot_rows([0, 3, 5], block_size=4, num_blocks=64, seq=1)
        want = np.concatenate([np.arange(b * 4, b * 4 + 4)
                               for b in (0, 3, 5)])
        assert (rows == want).all()

    def test_slot_rows_seq2_round_robin_shards(self):
        # block b lives in shard b % 2 at local index b // 2; each
        # shard carries (num_blocks//2 + 1) * bs rows (own trash last)
        shard_rows = (64 // 2 + 1) * 4
        rows = slot_rows([0, 1, 2], block_size=4, num_blocks=64, seq=2)
        assert (rows[:4] == np.arange(4)).all()                 # b0 -> s0
        assert (rows[4:8] == shard_rows + np.arange(4)).all()   # b1 -> s1
        assert (rows[8:12] == 4 + np.arange(4)).all()           # b2 -> s0

    def test_config_rejects_bad_seq_geometry(self):
        with pytest.raises(ValueError):
            RaggedInferenceConfig(seq_size=2, num_blocks=63)
        with pytest.raises(ValueError):
            RaggedInferenceConfig(seq_size=2, tp_size=2)
        with pytest.raises(ValueError):
            RaggedInferenceConfig(seq_size=2, max_blocks_per_seq=15)

    def test_effective_chunk_rounds_up_to_seq(self):
        # ISSUE 18 satellite bugfix: effective_chunk must divide evenly
        # across the seq axis — the last sub-chunk pads, it never emits
        # a zero-token shard
        cfg = RaggedInferenceConfig(chunk_size=7, seq_size=2,
                                    max_blocks_per_seq=16)
        assert cfg.effective_chunk == 8
        assert cfg.effective_chunk % 2 == 0
        assert cfg.effective_chunk // 2 >= 1
        # seq=1 keeps the historical chunk exactly
        assert RaggedInferenceConfig(chunk_size=7).effective_chunk == 7


# ------------------------------------------------------------------ #
# token parity seq in {1, 2} x serving modes
# ------------------------------------------------------------------ #


class TestSeqParity:
    """Greedy/sampled/spec/prefix/int8 streams must be identical across
    seq sizes — the seq axis is a layout change, not a model change."""

    def test_seq2_greedy_token_identical_and_kv_flat(self, oracle, seq2):
        prompts = _prompts()
        ref = oracle.generate(prompts, max_new_tokens=6)
        assert seq2.generate(prompts, max_new_tokens=6) == ref
        rep = seq2.state.kv_memory_report()
        assert rep["seq_size"] == 2
        # per-chip pool bytes halve: the long-context capacity lever
        assert rep["kv_pool_bytes_per_chip"] * 2 == \
            rep["kv_pool_bytes_total"]

    def test_seq2_sampled_token_identical(self, oracle, seq2):
        prompts = _prompts(seed=5)
        sp = SamplingParams(temperature=0.8, top_k=20, seed=13)
        ref = oracle.generate(prompts, max_new_tokens=6, sampling=sp)
        got = seq2.generate(prompts, max_new_tokens=6, sampling=sp)
        assert got == ref

    def test_seq2_spec_ngram_token_identical(self, base_pair):
        # speculation is lossless, so it composes: seq=2 spec streams
        # == seq=1 spec streams (periodic prompts feed the n-gram
        # proposer actual acceptances)
        mcfg, params, base = base_pair
        pat = np.random.default_rng(3).integers(1, 96, 6).tolist()
        prompts = [(pat * 4)[:14], (pat * 4)[:19]]
        kw = dict(spec_decode="ngram", spec_k=4)
        ref = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base, **kw)).generate(prompts, max_new_tokens=8)
        got = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base, seq_size=2, **kw)).generate(prompts, max_new_tokens=8)
        assert got == ref

    def test_seq2_prefix_cache_token_identical(self, base_pair):
        # shared preambles: the second wave hits the cache (CoW +
        # home-aligned prefix chains) and still matches the oracle
        mcfg, params, base = base_pair
        rng = np.random.default_rng(11)
        pre = rng.integers(1, 96, 8).tolist()
        prompts = [pre + rng.integers(1, 96, 7).tolist()
                   for _ in range(3)]

        def run(seq):
            eng = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
                **base, prefix_cache=True, seq_size=seq))
            first = eng.generate(prompts[:2], max_new_tokens=5)
            second = eng.generate(prompts, max_new_tokens=5)
            return first, second, eng.prefix_stats["matched_tokens"]

        ref_a, ref_b, ref_hits = run(1)
        got_a, got_b, got_hits = run(2)
        assert (got_a, got_b) == (ref_a, ref_b)
        assert got_hits == ref_hits and got_hits > 0

    def test_seq2_int8_pool_token_identical(self, base_pair, int8_seq2):
        # every chip quantizes the gathered fresh chunk identically, so
        # int8 pool bytes — and the streams — match the seq=1 engine
        mcfg, params, base = base_pair
        prompts = _prompts(seed=7)
        ref = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base, kv_cache_dtype="int8")).generate(
                prompts, max_new_tokens=6)
        got = int8_seq2.generate(prompts, max_new_tokens=6)
        assert got == ref

    @pytest.mark.full
    def test_seq4_greedy_token_identical(self, base_pair, oracle):
        mcfg, params, base = base_pair
        prompts = _prompts(seed=9)
        ref = oracle.generate(prompts, max_new_tokens=6)
        got = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base, seq_size=4)).generate(prompts, max_new_tokens=6)
        assert got == ref

    def test_chunk_not_divisible_by_seq_regression(self, base_pair):
        # ISSUE 18 satellite bugfix regression: chunk_size=7 with seq=2
        # (effective_chunk rounds to 8) — prefill chunks, replay tails
        # and C=1 decode steps all pad instead of emitting a zero-token
        # shard, and streams stay identical to the seq=1 oracle AT THE
        # SAME effective chunk
        mcfg, params, base = base_pair
        cfg7 = dict(base, chunk_size=7)
        prompts = _prompts(seed=13)
        ref = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **cfg7)).generate(prompts, max_new_tokens=6)
        eng = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **cfg7, seq_size=2))
        assert eng.config.effective_chunk == 8
        assert eng.generate(prompts, max_new_tokens=6) == ref

    def test_seq1_is_the_single_chip_engine(self, base_pair, oracle):
        # seq_size=1 is the exact pre-seq engine: programs carry ZERO
        # collectives (the auditor sees no diff vs the single-chip
        # baseline), tokens match
        mcfg, params, base = base_pair
        eng = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base, seq_size=1))
        assert eng.runner.seqctx is None
        prompts = _prompts(seed=17)
        ref = oracle.generate(prompts, max_new_tokens=5)
        assert eng.generate(prompts, max_new_tokens=5) == ref
        for name, rep in audit_serve_programs(eng).items():
            assert rep.total_collectives == 0, (name, rep.summary())


# ------------------------------------------------------------------ #
# drain / handoff across seq geometries
# ------------------------------------------------------------------ #


class TestSeqDrainHandoff:

    def test_drain_replay_parity_across_geometries(self, base_pair,
                                                   oracle):
        # drain a seq=2 engine mid-stream, replay the manifest on a
        # seq=1 engine (and vice versa): continuations token-identical
        # to the uninterrupted oracle — the manifest records the shard
        # map but replay is geometry-free
        mcfg, params, base = base_pair
        prompts = {100: _prompts(seed=19)[0], 101: _prompts(seed=19)[1]}
        want = oracle.generate(list(prompts.values()), max_new_tokens=8)
        for src_seq, dst_seq in ((2, 1), (1, 2)):
            src = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
                **base, seq_size=src_seq))
            uids = list(prompts)
            first = src.put(uids, list(prompts.values()), _greedy=True)
            got = {u: [first[u]] for u in uids}
            step1 = src.decode_pipelined(uids, [first[u] for u in uids], 3)
            for u in uids:
                got[u].extend(step1[u])
            m = src.drain()
            assert m["config"]["seq_size"] == src_seq
            dst = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
                **base, seq_size=dst_seq))
            out = dst.replay(m)        # replay itself emits a token
            for u in uids:
                got[u].append(int(out[u]))
            more = dst.decode_pipelined(uids, [got[u][-1] for u in uids],
                                        3)
            for u in uids:
                got[u].extend(more[u])
            for i, u in enumerate(uids):
                assert got[u] == want[i], (src_seq, dst_seq, u)

    def test_handoff_manifest_carries_shard_map(self, base_pair, oracle):
        # disagg handoff out of a seq=2 replica into a seq=1 one: the
        # manifest carries seq_size, the destination continues the
        # stream token-identically (block-ordered payloads are
        # geometry-free)
        mcfg, params, base = base_pair
        prompts = {7: _prompts(seed=23)[0]}
        want = oracle.generate(list(prompts.values()),
                               max_new_tokens=7)[0]
        src = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base, seq_size=2))
        first = src.put([7], list(prompts.values()), _greedy=True)
        got = [first[7]]
        got.extend(src.decode_pipelined([7], [first[7]], 2)[7])
        m = src.handoff_out([7])
        assert m["seq_size"] == 2
        assert len(m["sequences"]) == 1
        dst = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
            **base))
        res = dst.handoff_in(m)
        assert res["accepted"] == [7] and not res["spilled"]
        got.extend(dst.decode_pipelined([7], [got[-1]], 4)[7])
        assert got == want


# ------------------------------------------------------------------ #
# audited hop budgets + warm-path compile hygiene
# ------------------------------------------------------------------ #


@pytest.fixture(scope="module")
def int8_seq2(base_pair):
    """int8-pool seq=2 engine, shared by the int8 parity and scale-ride
    budget tests."""
    mcfg, params, base = base_pair
    return InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
        **base, kv_cache_dtype="int8", seq_size=2))


class TestSeqHopBudget:
    """ISSUE 18 acceptance: the seq axis's comm is exactly what the
    design says — nothing extra rides along."""

    def test_step_ring_budget(self, seq2_reports):
        # per layer: 1 fresh-KV all-gather + (seq-1)=1 ring ppermute;
        # per program: 1 owner-logits psum (GPT-2's tied unembed adds
        # no logits gather) — the spec lives in the shared registry
        # (analysis/budgets.py "seq-step"), the same one dslint DSL008
        # cross-checks
        budget = CollectiveBudget(**budget_args(
            "seq-step", num_layers=L, seq=2, label="seq2-step"))
        for name in ("step", "step_greedy", "step_greedy_fb"):
            assert_budget(seq2_reports[name], budget)

    def test_decode_loop_stat_combine_budget(self, seq2_reports):
        # the fused loop: ONE packed stat-combine all-gather per layer
        # per step, zero per-program collectives (every chip computes
        # identical merged logits), scan trip-weighted over 4 steps
        assert_budget(seq2_reports["decode_loop"], CollectiveBudget(
            **budget_args("seq-decode-loop", num_layers=L, seq=2,
                          steps=4, label="seq2-decode-loop")))

    def test_flush_ring_chip_local(self, seq2_reports):
        # the ownership-masked flush scatter is chip-local: zero comm
        assert_budget(seq2_reports["flush_ring"], CollectiveBudget(
            **budget_args("seq-flush", num_layers=L, seq=2,
                          label="seq2-flush")))

    def test_int8_scale_planes_ride_the_ring(self, int8_seq2):
        # over an int8 pool the ring doubles: per hop one int8 data
        # ppermute + one f32 scale-plane ppermute (the PR 6 quantized-
        # collective shape), while the fresh-KV exchange stays ONE
        # compute-dtype all-gather — expectations derive from the
        # registry's dtype-pinned "seq-step-int8" entry
        rep = audit_serve_programs(int8_seq2, programs=("step",))["step"]
        exp = CollectiveBudget(**budget_args(
            "seq-step-int8", num_layers=L, seq=2)).expected()
        assert rep.count(kind="ppermute", dtype="int8") \
            == exp["ppermute@int8"]
        assert rep.count(kind="ppermute", dtype="float32") \
            == exp["ppermute@float32"]
        assert rep.count(kind="all_gather", dtype="float32") \
            == exp["all_gather@float32"]

    def test_seq4_ring_hops_scale(self, base_pair):
        # seq=4: (seq-1)=3 ring hops per layer, still 1 all-gather —
        # the SAME registry entry as seq=2, resolved at a wider shard
        mcfg, params, base = base_pair
        rep = audit_serve_programs(
            InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
                **base, seq_size=4)), programs=("step",))["step"]
        assert_budget(rep, CollectiveBudget(**budget_args(
            "seq-step", num_layers=L, seq=4, label="seq4-step")))


class TestSeqWarmPath:

    def test_warm_pipeline_zero_fresh_compiles(self, seq2):
        # the shared seq=2 engine has served the parity generates by
        # now, so its programs are compiled — one put+pipelined-decode
        # primes any remaining shape, then the measured window must be
        # compile-free (a miss here is a shape/dtype/static-arg leak in
        # the seq slice wrapper)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 96, 6).tolist() for _ in range(2)]
        uids = [70, 71]
        first = seq2.put(uids, prompts, _greedy=True)
        seq2.decode_pipelined(uids, [first[u] for u in uids], 4)
        with RecompileTripwire() as warm:
            seq2.decode_pipelined(
                uids, [int(rng.integers(1, 96)) for _ in uids], 4)
        assert warm.fresh_compiles == 0, (
            f"{warm.fresh_compiles} jit cache misses on a warm seq=2 "
            f"pipeline run")
        for u in uids:
            seq2.flush(u)
