"""On-device sampling + speculative decoding tests (ISSUE 12).

The two oracles this layer stands on:

  * **temperature→0 parity** — a sampled sequence at temperature 0 (and
    a greedy sequence riding a mixed batch through the sampler program)
    must be token-identical to the pure-greedy path, at every pipeline
    depth, through the fused loop, and under tp=2 (slow tier).
  * **speculative parity** — decode with speculation armed (ngram or a
    draft model) must be token-identical to non-speculative greedy:
    a draft token is only ever accepted where it equals greedy's own
    choice, and rejected tokens roll back through ``trim_blocks`` with
    prefix-cache refcounts exact (``PrefixCache.assert_exact_refs``).

Plus the determinism contract: sampled streams are a pure function of
(seed, position) — identical across pipeline depths, fused-vs-per-step
paths, and drain/replay restarts (the manifest carries SamplingParams).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (
    InferenceEngineV2,
    RaggedInferenceConfig,
    SamplingParams,
)
from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config

_CACHE = {}


def _gpt2(layers=2, hidden=32, key=0):
    name = f"gpt2-{layers}-{hidden}"
    if name not in _CACHE:
        mcfg = GPT2Config(vocab_size=96, max_seq_len=256, num_layers=layers,
                          num_heads=2, hidden_size=hidden,
                          dtype=jnp.float32)
        params = GPT2(mcfg).init(jax.random.PRNGKey(key),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
        _CACHE[name] = (mcfg, params)
    return _CACHE[name]


def _cfg(depth=2, prefix=True, **kw):
    base = dict(max_seqs=4, chunk_size=8, block_size=4, num_blocks=96,
                max_blocks_per_seq=24, dtype="float32",
                attention_impl="dense", decode_loop_steps=0,
                serve_pipeline_depth=depth, prefix_cache=prefix)
    base.update(kw)
    return RaggedInferenceConfig(**base)


_rng = np.random.default_rng(3)
#: 3 prompts sharing a 10-token preamble (two full shared blocks at
#: block_size 4 + a CoW tail) — the shared-prefix-chain workload the
#: rollback-exactness tests need
_SHARED = _rng.integers(1, 96, 10).tolist()
PROMPTS = [_SHARED + _rng.integers(1, 96, 5).tolist() for _ in range(3)]
#: periodic prompts whose greedy continuations settle into short cycles
#: — the self-drafting (ngram) acceptance food
_PAT = _rng.integers(1, 96, 6).tolist()
REP_PROMPTS = [(_PAT * 4)[: 15 + i] for i in range(3)]
UIDS = [0, 1, 2]


def _stream(eng, prompts, n, sampling=None, uids=UIDS):
    """put + pipelined decode; returns the full per-uid streams
    (first emitted token + n continuation tokens)."""
    first = eng.put(uids, [list(p) for p in prompts], _greedy=True,
                    sampling=sampling)
    out = eng.decode_pipelined(uids, [first[u] for u in uids], n)
    return {u: [first[u]] + out[u] for u in uids}


class TestSamplingStack:
    def test_temp0_and_mixed_batch_parity_across_depths(self):
        mcfg, params = _gpt2()
        ref = _stream(InferenceEngineV2(mcfg, params, _cfg(depth=2)),
                      PROMPTS, 10)
        # uid0 explicit temperature-0 params, uid1 no params (greedy
        # rides the sampler program in the mixed batch), uid2 sampled —
        # the greedy members must be UNCHANGED by the mixed batch
        for depth in (0, 2):
            sp = {0: SamplingParams(temperature=0.0, logprobs=True),
                  2: SamplingParams(temperature=0.9, top_k=8, seed=4)}
            eng = InferenceEngineV2(mcfg, params, _cfg(depth=depth))
            got = _stream(eng, PROMPTS, 10, sampling=sp)
            assert got[0] == ref[0], f"temp0 parity broke at depth {depth}"
            assert got[1] == ref[1], f"greedy-in-mixed broke at depth {depth}"
            # a temp-0 'sampled' sequence still records logprobs
            lps = eng.logprobs_of(0)
            assert len(lps) == len(got[0]) and all(v <= 0.0 for v in lps)

    def test_seeded_streams_identical_across_paths_and_seeds(self):
        mcfg, params = _gpt2()
        sp = {u: SamplingParams(temperature=0.8, top_k=12, top_p=0.95,
                                seed=100 + u) for u in UIDS}
        runs = {}
        for label, depth, loop in (("sync", 0, 0), ("pipe2", 2, 0),
                                   ("pipe3", 3, 0), ("fused", 2, 10)):
            eng = InferenceEngineV2(mcfg, params,
                                    _cfg(depth=depth,
                                         decode_loop_steps=loop))
            first = eng.put(UIDS, [list(p) for p in PROMPTS],
                            _greedy=True, sampling=sp)
            if loop:
                out = eng.decode_batch(UIDS, [first[u] for u in UIDS], 10)
            else:
                out = eng.decode_pipelined(UIDS,
                                           [first[u] for u in UIDS], 10)
            runs[label] = {u: [first[u]] + list(out[u]) for u in UIDS}
        assert runs["sync"] == runs["pipe2"] == runs["pipe3"] \
            == runs["fused"]
        # a different seed diverges (the sampler is actually sampling)
        sp9 = {u: SamplingParams(temperature=0.8, top_k=12, top_p=0.95,
                                 seed=900 + u) for u in UIDS}
        eng = InferenceEngineV2(mcfg, params, _cfg())
        other = _stream(eng, PROMPTS, 10, sampling=sp9)
        assert other != runs["sync"]

    def test_sampled_drain_replay_restart_determinism(self):
        mcfg, params = _gpt2()
        sp = {u: SamplingParams(temperature=0.7, top_k=16, seed=7 + u)
              for u in UIDS}
        cfg = _cfg()
        want = _stream(InferenceEngineV2(mcfg, params, cfg), PROMPTS, 9,
                       sampling=sp)
        eng = InferenceEngineV2(mcfg, params, cfg)
        first = eng.put(UIDS, [list(p) for p in PROMPTS], _greedy=True,
                        sampling=sp)
        part = eng.decode_pipelined(UIDS, [first[u] for u in UIDS], 4)
        manifest = eng.drain()
        assert all(r.get("sampling") for r in manifest["sequences"])
        surv = InferenceEngineV2(mcfg, params, cfg)
        rep = surv.replay(manifest)
        cont = surv.decode_pipelined(UIDS, [rep[u] for u in UIDS], 4)
        got = {u: [first[u]] + part[u] + [rep[u]] + cont[u] for u in UIDS}
        assert got == want

    @pytest.mark.slow
    def test_journal_carries_sampling_identity(self, tmp_path):
        from deepspeed_tpu.inference.v2 import manifest_from_journal
        mcfg, params = _gpt2()
        jpath = str(tmp_path / "journal.jsonl")
        cfg = _cfg(serve_journal=jpath)
        eng = InferenceEngineV2(mcfg, params, cfg)
        sp = {0: SamplingParams(temperature=0.6, seed=42)}
        first = eng.put([0], [list(PROMPTS[0])], _greedy=True, sampling=sp)
        eng.decode_pipelined([0], [first[0]], 3)
        m = manifest_from_journal(jpath)
        rec = m["sequences"][0]
        assert rec["sampling"]["temperature"] == 0.6
        assert rec["sampling"]["seed"] == 42
        # a journal-reconstructed replay continues the SAME stream (the
        # journal's `generated` already includes the first emitted
        # token — the prefill's last-chunk commit journals it)
        want = _stream(InferenceEngineV2(mcfg, params, _cfg()),
                       [PROMPTS[0]], 7, sampling=sp, uids=[0])
        surv = InferenceEngineV2(mcfg, params, _cfg())
        rep = surv.replay(m)
        gen = list(rec["generated"])
        cont = surv.decode_pipelined(
            [0], [rep[0]], len(want[0]) - len(gen) - 1)
        got = gen + [rep[0]] + cont[0]
        assert got == want[0]

    @pytest.mark.slow
    def test_pool_passthrough_sampling(self):
        from deepspeed_tpu.serving import ReplicaPool
        mcfg, params = _gpt2()
        sp = {u: SamplingParams(temperature=0.8, top_k=8, seed=50 + u)
              for u in UIDS}
        want = _stream(InferenceEngineV2(mcfg, params, _cfg()), PROMPTS,
                       8, sampling=sp)
        pool = ReplicaPool([InferenceEngineV2(mcfg, params, _cfg())
                            for _ in range(2)], policy="round_robin")
        first = pool.put(UIDS, [list(p) for p in PROMPTS], _greedy=True,
                         sampling=sp)
        out = pool.decode_pipelined(UIDS, [first[u] for u in UIDS], 8)
        got = {u: [first[u]] + out[u] for u in UIDS}
        assert got == want


class TestSpeculativeDecode:
    def test_ngram_parity_counters_and_exact_release(self):
        mcfg, params = _gpt2()
        ref_eng = InferenceEngineV2(mcfg, params, _cfg())
        want = _stream(ref_eng, REP_PROMPTS, 12)
        eng = InferenceEngineV2(mcfg, params,
                                _cfg(spec_decode="ngram", spec_k=4))
        got = _stream(eng, REP_PROMPTS, 12)
        assert got == want
        rep = eng.slo_report()
        assert rep["spec"]["rounds"] > 0
        assert rep["spec"]["proposed"] > 0
        assert rep["spec_accept_rate"] is not None
        assert eng.state.sequences[UIDS[0]].spec_proposed > 0
        # rejected-run rollbacks on the shared-prefix chain kept the
        # cache refcounts EXACT and the pool recovers fully
        eng._prefix.assert_exact_refs(eng.state.sequences.values())
        for u in UIDS:
            eng.flush(u)
        assert eng.kv_cache.free_blocks == eng.config.num_blocks
        eng._prefix.check_invariants()

    def test_budget_exact_and_eos_truncation(self):
        mcfg, params = _gpt2()
        ref = InferenceEngineV2(mcfg, params, _cfg())
        f0 = ref.put(UIDS, [list(p) for p in REP_PROMPTS], _greedy=True)
        budgets = [5, 9, 12]
        r0 = ref.decode_pipelined(UIDS, [f0[u] for u in UIDS], budgets)
        eng = InferenceEngineV2(mcfg, params,
                                _cfg(spec_decode="ngram", spec_k=4))
        f1 = eng.put(UIDS, [list(p) for p in REP_PROMPTS], _greedy=True)
        r1 = eng.decode_pipelined(UIDS, [f1[u] for u in UIDS], budgets)
        assert r1 == r0
        assert [len(r1[u]) for u in UIDS] == budgets
        # eos mid-stream truncates identically
        eos = r0[UIDS[1]][2]
        ref2 = InferenceEngineV2(mcfg, params, _cfg())
        f2 = ref2.put(UIDS, [list(p) for p in REP_PROMPTS], _greedy=True)
        r2 = ref2.decode_pipelined(UIDS, [f2[u] for u in UIDS], 12,
                                   eos_token_id=eos)
        eng2 = InferenceEngineV2(mcfg, params,
                                 _cfg(spec_decode="ngram", spec_k=4))
        f3 = eng2.put(UIDS, [list(p) for p in REP_PROMPTS], _greedy=True)
        r3 = eng2.decode_pipelined(UIDS, [f3[u] for u in UIDS], 12,
                                   eos_token_id=eos)
        assert r3 == r2

    def test_noisy_proposer_rollback_refcounts_exact(self):
        # heavy rejection pressure ON a shared-prefix chain: every
        # round retracts most of its speculated span; each shared
        # block must be decref'd exactly once per release, never freed
        mcfg, params = _gpt2()
        os.environ["DSTPU_SPEC_NOISE"] = "0.6"
        try:
            eng = InferenceEngineV2(mcfg, params,
                                    _cfg(spec_decode="ngram", spec_k=4))
            want = _stream(InferenceEngineV2(mcfg, params, _cfg()),
                           PROMPTS, 10)
            got = _stream(eng, PROMPTS, 10)
        finally:
            os.environ.pop("DSTPU_SPEC_NOISE", None)
        assert got == want
        st = eng.state.prefix_stats
        assert st["trims"] > 0, "noisy speculation never rolled back"
        eng._prefix.assert_exact_refs(eng.state.sequences.values())
        for u in UIDS:
            eng.flush(u)
        assert eng.kv_cache.free_blocks == eng.config.num_blocks

    def test_draft_model_same_params_full_acceptance(self):
        mcfg, params = _gpt2()
        want = _stream(InferenceEngineV2(mcfg, params, _cfg()),
                       PROMPTS, 10)
        eng = InferenceEngineV2(mcfg, params,
                                _cfg(spec_decode="draft", spec_k=3))
        eng.attach_draft(mcfg, params)
        got = _stream(eng, PROMPTS, 10)
        assert got == want
        rep = eng.slo_report()
        assert rep["spec_accept_rate"] == 1.0
        for u in UIDS:
            eng.flush(u)
        assert eng.kv_cache.free_blocks == eng.config.num_blocks
        assert eng._draft_engine.kv_cache.free_blocks \
            == eng._draft_engine.config.num_blocks

    def test_spec_warm_path_zero_fresh_compiles(self):
        from deepspeed_tpu.analysis import RecompileTripwire
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params,
                                _cfg(spec_decode="ngram", spec_k=4))
        first = eng.put(UIDS, [list(p) for p in REP_PROMPTS],
                        _greedy=True)
        warm = eng.decode_pipelined(UIDS, [first[u] for u in UIDS], 6)
        tw = RecompileTripwire()
        with tw:
            eng.decode_pipelined(UIDS, [warm[u][-1] for u in UIDS], 12)
        assert tw.fresh_compiles == 0

    def test_draft_vocab_mismatch_rejected(self):
        mcfg, params = _gpt2()
        bad = GPT2Config(vocab_size=64, max_seq_len=256, num_layers=1,
                         num_heads=2, hidden_size=16, dtype=jnp.float32)
        eng = InferenceEngineV2(mcfg, params, _cfg(spec_decode="draft"))
        with pytest.raises(ValueError, match="vocab"):
            eng.attach_draft(bad, None)

    @pytest.mark.slow
    def test_draft_small_model_parity(self):
        mcfg, params = _gpt2()
        dcfg, dparams = _gpt2(layers=1, hidden=16, key=5)
        want = _stream(InferenceEngineV2(mcfg, params, _cfg()),
                       REP_PROMPTS, 14)
        eng = InferenceEngineV2(mcfg, params,
                                _cfg(spec_decode="draft", spec_k=4))
        eng.attach_draft(dcfg, dparams)
        got = _stream(eng, REP_PROMPTS, 14)
        assert got == want
        rate = eng.slo_report()["spec_accept_rate"]
        assert rate is not None
        for u in UIDS:
            eng.flush(u)
        assert eng.kv_cache.free_blocks == eng.config.num_blocks

    @pytest.mark.slow
    def test_spec_drain_replay_parity(self):
        # a drain mid-speculation breaks the round loop; the manifest
        # chain (committed tokens only — rejected drafts never entered
        # gen_log) must replay token-identically on a survivor
        mcfg, params = _gpt2()
        cfg_s = _cfg(spec_decode="ngram", spec_k=4)
        want = _stream(InferenceEngineV2(mcfg, params, _cfg()),
                       REP_PROMPTS, 12)
        eng = InferenceEngineV2(mcfg, params, cfg_s)
        first = eng.put(UIDS, [list(p) for p in REP_PROMPTS],
                        _greedy=True)
        part = eng.decode_pipelined(UIDS, [first[u] for u in UIDS], 5)
        m = eng.drain()
        assert m["pool"]["fully_recovered"]
        surv = InferenceEngineV2(mcfg, params, cfg_s)
        rep = surv.replay(m)
        cont = surv.decode_pipelined(UIDS, [rep[u] for u in UIDS], 6)
        got = {u: [first[u]] + part[u] + [rep[u]] + cont[u]
               for u in UIDS}
        assert got == want

    @pytest.mark.slow
    def test_tp2_spec_and_temp0_parity(self):
        # the acceptance-criteria grid: tp∈{1,2} (tp1 is the tier-1
        # suite above), pipeline depth 2, prefix cache on
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        mcfg, params = _gpt2()
        base = dict(depth=2, prefix=True, tp_size=2, max_seqs=2)
        uids = [0, 1]
        prompts = REP_PROMPTS[:2]
        ref = _stream(InferenceEngineV2(mcfg, params, _cfg(**base)),
                      prompts, 10, uids=uids)
        eng_s = InferenceEngineV2(
            mcfg, params, _cfg(**base, spec_decode="ngram", spec_k=4))
        got_s = _stream(eng_s, prompts, 10, uids=uids)
        assert got_s == ref
        sp0 = {u: SamplingParams(temperature=0.0) for u in uids}
        eng_0 = InferenceEngineV2(mcfg, params, _cfg(**base))
        got_0 = _stream(eng_0, prompts, 10, sampling=sp0, uids=uids)
        assert got_0 == ref
        # seeded sampled streams are tp-stable too (the sampler runs on
        # replicated logits after the one pre-sampling gather)
        sp = {u: SamplingParams(temperature=0.8, top_k=8, seed=60 + u)
              for u in uids}
        tp1 = _stream(InferenceEngineV2(
            mcfg, params, _cfg(depth=2, prefix=True, max_seqs=2)),
            prompts, 10, sampling=sp, uids=uids)
        tp2 = _stream(InferenceEngineV2(mcfg, params, _cfg(**base)),
                      prompts, 10, sampling=sp, uids=uids)
        assert tp1 == tp2

    @pytest.mark.slow
    def test_spec_programs_audited_clean(self):
        # sampling/verification add ZERO collectives and zero host
        # callbacks over their greedy siblings
        from deepspeed_tpu.analysis import (CollectiveBudget,
                                            assert_budget,
                                            audit_serve_programs)
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params,
                                _cfg(spec_decode="ngram", spec_k=4))
        reps = audit_serve_programs(
            eng, programs=("step_sample_fb", "decode_verify"))
        for name in ("step_sample_fb", "decode_verify"):
            assert_budget(reps[name],
                          CollectiveBudget(f"tp1-{name}", num_layers=2))
