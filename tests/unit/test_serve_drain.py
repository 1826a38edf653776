"""Serve-side resilience tests (ISSUE 7): preemption-safe drain/replay
for the v2 ragged engine.

The parity oracle for the whole layer: a kill (injected fault or
cooperative drain) at ANY pipeline stage, followed by manifest/journal
replay on a fresh or survivor engine, must yield token streams identical
to the uninterrupted greedy run — with zero leaked KV blocks and exact
prefix-cache refcounts. Heavier combos (full kill grid, llama, tp2) ride
the full/slow tier; ``bin/dstpu_faultdrill --mode serve`` drills the
hard-crash (``os._exit``) variants in subprocesses."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (
    EngineDrainingError,
    InferenceEngineV2,
    RaggedInferenceConfig,
    ServeStepError,
    load_replay_state,
    manifest_from_journal,
)
from deepspeed_tpu.inference.v2.drain import load_manifest, write_manifest
from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
from deepspeed_tpu.resilience.fault_injection import (
    SERVE_FAULT_SITES,
    FaultInjector,
    InjectedFault,
    set_fault_injector,
)

# the standard workload: 3 requests sharing a 10-token system preamble
# (block_size 4 -> two full shared blocks + a partial-tail CoW copy on
# every later request) with unique 5-token tails; serve N_TOK tokens each
UIDS = (0, 1, 2)
N_TOK = 8
_rng = np.random.default_rng(55)
_SHARED = _rng.integers(1, 96, 10).tolist()
PROMPTS = tuple(_SHARED + _rng.integers(1, 96, 5).tolist() for _ in UIDS)

_CACHE = {}


def _gpt2():
    if "gpt2" not in _CACHE:
        mcfg = GPT2Config(vocab_size=96, max_seq_len=128, num_layers=2,
                          num_heads=2, hidden_size=32, dtype=jnp.float32)
        params = GPT2(mcfg).init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
        _CACHE["gpt2"] = (mcfg, params)
    return _CACHE["gpt2"]


def _cfg(prefix=True, depth=2, **kw):
    base = dict(max_seqs=4, chunk_size=8, block_size=4, num_blocks=64,
                max_blocks_per_seq=16, dtype="float32",
                attention_impl="dense", decode_loop_steps=0,
                serve_pipeline_depth=depth, prefix_cache=prefix)
    base.update(kw)
    return RaggedInferenceConfig(**base)


def _serve(eng, n=N_TOK, uids=UIDS, prompts=PROMPTS, rounds_of=2):
    """Drive the serve loop the way a serving layer does: admit each
    request (prefix matching + CoW fire on the later ones), then decode
    all live sequences in small pipelined rounds. Sequences stay LIVE on
    return — the drain tests snapshot them mid-service."""
    toks = {}
    for u, p in zip(uids, prompts):
        r = eng.put([u], [list(p)], _greedy=True)
        if u in r:
            toks[u] = [int(r[u])]
    while True:
        live = [u for u in toks
                if len(toks[u]) < n and u not in eng.rejections
                and u in eng.state.sequences]
        if not live:
            return toks
        k = min(rounds_of, n - min(len(toks[u]) for u in live))
        outs = eng.decode_pipelined(live, [toks[u][-1] for u in live], k)
        got = False
        for u in live:
            if outs[u]:
                got = True
            toks[u].extend(outs[u][:n - len(toks[u])])
        if not got:          # draining / everything shed: no progress
            return toks


@pytest.fixture(scope="module")
def oracle():
    """The uninterrupted greedy stream — computed once on the sync
    (depth-0, cache-off) engine; every interrupted-then-replayed run
    must reproduce it token for token."""
    mcfg, params = _gpt2()
    eng = InferenceEngineV2(mcfg, params, _cfg(prefix=False, depth=0))
    return _serve(eng)


def _assert_released(eng, manifest):
    """No leaked state after a drain: every block back to the allocator
    (or the cache's refcount-0 evictable set, which counts as free
    capacity), refcounts exactly zero, sequence table empty."""
    assert manifest["pool"]["fully_recovered"], manifest["pool"]
    assert eng.free_blocks == eng.config.num_blocks
    assert not eng.state.sequences
    if eng._prefix is not None:
        eng._prefix.check_invariants()
        assert eng._prefix.evictable_blocks == eng._prefix.cached_blocks


def _replay_and_finish(manifest, cfg, n=N_TOK, model=None):
    """Fresh-engine recovery: re-put() every manifest sequence and decode
    each to ``n`` total tokens. Returns (engine, {uid: tokens})."""
    mcfg, params = model if model is not None else _gpt2()
    eng = InferenceEngineV2(mcfg, params, cfg)
    out = eng.replay(manifest)
    toks = {int(s["uid"]): list(s["generated"])
            for s in manifest["sequences"]}
    for u in list(toks):
        # a kill after a request finished its budget leaves a full
        # generated list; replay's next token is then beyond the
        # comparison window
        if u in out and len(toks[u]) < n:
            toks[u].append(int(out[u]))
    while True:
        short = [u for u in toks if len(toks[u]) < n]
        if not short:
            return eng, toks
        outs = eng.decode_pipelined(short, [toks[u][-1] for u in short],
                                    [n - len(toks[u]) for u in short])
        for u in short:
            toks[u].extend(outs[u][:n - len(toks[u])])


class TestDrainReplay:
    """Cooperative drain (the SIGTERM path, minus the signal): stop
    admitting, unwind the pipeline, manifest, replay elsewhere."""

    def test_drain_replay_parity_and_release(self, oracle, tmp_path):
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params, _cfg())
        partial = _serve(eng, n=4)
        eng.request_drain()
        # draining: FRESH admissions are refused with a structured
        # rejection; a continuation of a live sequence is NOT rejected
        # (it rides the manifest — a record would double-route it), and
        # replay() on this replica is an error
        assert eng.put([9], [[1, 2, 3]]) == {}
        assert eng.rejections[9]["reason"] == "draining"
        assert eng.put([0], [[partial[0][-1]]]) == {}
        assert 0 not in eng.rejections
        with pytest.raises(EngineDrainingError):
            eng.replay({"sequences": []})
        path = str(tmp_path / "m.json")
        m = eng.drain(path)
        _assert_released(eng, m)
        # atomic publish round-trips, and the manifest carries exactly
        # the committed partial streams plus the scheduler snapshot
        m2 = load_manifest(path)
        assert [s["uid"] for s in m2["sequences"]] == list(UIDS)
        for s in m2["sequences"]:
            assert s["prompt"] == list(PROMPTS[s["uid"]])
            assert s["generated"] == partial[s["uid"]]
            assert s["scheduler"]["seen_tokens"] > 0
        # replay on a fresh engine: token-identical continuation
        eng2, toks = _replay_and_finish(m2, _cfg())
        assert toks == oracle
        # the replayed sequences stay live with prompt/generated split
        # restored: a LATER drain is cumulative
        m3 = eng2.drain()
        for s in m3["sequences"]:
            assert s["prompt"] == list(PROMPTS[s["uid"]])
            assert s["generated"] == oracle[s["uid"]]
        _assert_released(eng2, m3)

    @pytest.mark.slow
    def test_drain_replay_parity_prefix_off(self, oracle):
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params, _cfg(prefix=False))
        _serve(eng, n=3)
        m = eng.drain()
        _assert_released(eng, m)
        _, toks = _replay_and_finish(m, _cfg(prefix=False))
        assert toks == oracle

    @pytest.mark.slow
    def test_survivor_replay_is_mostly_prefix_hits(self, oracle):
        # a SURVIVOR engine that already served the shared-prefix
        # workload replays the manifest with most re-prefill served from
        # its cache (the ROADMAP's cheap-recovery claim)
        mcfg, params = _gpt2()
        dead = InferenceEngineV2(mcfg, params, _cfg())
        _serve(dead, n=4)
        m = dead.drain()
        surv = InferenceEngineV2(mcfg, params, _cfg())
        warm = _serve(surv, uids=(7, 8), prompts=(
            _SHARED + [3, 1, 4, 1, 5], _SHARED + [9, 2, 6, 5, 3]), n=2)
        assert set(warm) == {7, 8}
        st0 = surv.prefix_stats
        out = surv.replay(m)
        assert set(out) == set(UIDS)
        st = surv.prefix_stats
        hit = st["matched_tokens"] - st0["matched_tokens"]
        ran = st["prefill_tokens"] - st0["prefill_tokens"]
        # the 10-token preamble (minus CoW tails) never re-prefills
        assert hit / (hit + ran) > 0.4
        toks = {u: list(s["generated"]) + [int(out[u])]
                for u, s in ((int(s["uid"]), s) for s in m["sequences"])}
        short = sorted(toks)
        outs = surv.decode_pipelined(
            short, [toks[u][-1] for u in short],
            [N_TOK - len(toks[u]) for u in short])
        for u in short:
            toks[u].extend(outs[u])
        assert toks == oracle

    def test_fused_decode_loop_replay_parity(self, oracle):
        # the fused n-token decode loop (decode_batch) commits its whole
        # burst in one readback; its replay bookkeeping (fed first token
        # + consumed outputs into gen_log, journal batched) must drain
        # and replay exactly like the per-step paths
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params,
                                _cfg(prefix=False, decode_loop_steps=4))
        r = eng.put(list(UIDS), [list(p) for p in PROMPTS], _greedy=True)
        outs = eng.decode_batch(list(UIDS), [int(r[u]) for u in UIDS], 4)
        m = eng.drain()
        _assert_released(eng, m)
        for s in m["sequences"]:
            u = s["uid"]
            assert s["generated"] == [int(r[u])] + outs[u]
        _, toks = _replay_and_finish(m, _cfg(prefix=False))
        assert toks == oracle

    @pytest.mark.slow
    def test_drain_manifest_records_ledger(self, tmp_path):
        from deepspeed_tpu.resilience.ledger import RestartLedger
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params, _cfg())
        _serve(eng, n=2)
        led = RestartLedger(str(tmp_path / "ledger.json"))
        m = eng.drain(str(tmp_path / "m.json"), ledger=led)
        ev = [e for e in led.events if e["event"] == "serve_drain"]
        assert len(ev) == 1
        assert ev[0]["sequences"] == len(m["sequences"]) == 3
        assert ev[0]["fully_recovered"] is True


class TestKillPointModel:
    """Randomized kill-point model: an injected fault (in-process
    ``raise`` mode — the drill covers hard ``os._exit``) at every serve
    pipeline stage, then drain + fresh-engine replay. Parity, no leaked
    blocks or refcounts, allocator full-capacity recovery."""

    def _kill_and_replay(self, oracle, site, skip, depth, prefix):
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params, _cfg(prefix, depth))
        set_fault_injector(FaultInjector(site=site, mode="raise",
                                         skip=skip))
        fired = False
        try:
            try:
                _serve(eng)
            except InjectedFault:
                fired = True
        finally:
            set_fault_injector(None)
        m = eng.drain()
        _assert_released(eng, m)
        if not m["sequences"]:      # killed before the first admission
            assert fired
            return
        _, toks = _replay_and_finish(m, _cfg(prefix, depth))
        for u in toks:
            assert toks[u] == oracle[u], \
                f"site={site} skip={skip} depth={depth} prefix={prefix}"

    @pytest.mark.parametrize(
        "seed", [0, 1, pytest.param(2, marks=pytest.mark.slow)])
    def test_random_kill_replay_parity(self, oracle, seed):
        rng = np.random.default_rng(seed)
        site = SERVE_FAULT_SITES[rng.integers(0, len(SERVE_FAULT_SITES))]
        skip = int(rng.integers(0, 6))
        self._kill_and_replay(oracle, site, skip, depth=2, prefix=True)

    @pytest.mark.slow
    @pytest.mark.parametrize("depth", [0, 2, 3])
    @pytest.mark.parametrize("prefix", [True, False])
    def test_kill_grid(self, oracle, depth, prefix):
        # every serve site x this (depth, prefix) cell, x3 seeds for the
        # fire-point; during_cow_copy needs the cache on to ever fire
        # (a no-fire run degenerates to the plain drain test — fine)
        for seed in range(3):
            rng = np.random.default_rng(100 * depth + seed + int(prefix))
            for site in SERVE_FAULT_SITES:
                self._kill_and_replay(oracle, site, int(rng.integers(0, 6)),
                                      depth, prefix)


class TestAbort:
    """engine.abort(uid): safe any-time cancellation — frees deferred
    past in-flight steps, prefix refcounts released exactly."""

    def test_abort_unknown_uid(self):
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params, _cfg())
        assert eng.abort(123) is False

    @pytest.mark.slow
    def test_abort_idle_releases_immediately(self, oracle):
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params, _cfg(prefix=False))
        r = eng.put(list(UIDS), [list(p) for p in PROMPTS], _greedy=True)
        assert eng.abort(1) is True
        assert 1 not in eng.state.sequences
        live_blocks = sum(len(s.kv_blocks)
                          for s in eng.state.sequences.values())
        assert eng.free_blocks == eng.config.num_blocks - live_blocks
        # the survivors decode on, token-identical
        outs = eng.decode_pipelined([0, 2], [int(r[0]), int(r[2])],
                                    N_TOK - 1)
        for u in (0, 2):
            assert [int(r[u])] + outs[u] == oracle[u]

    def test_abort_mid_pipeline_defers_frees(self, oracle):
        # abort fired from inside a commit (the deadline/shed call site)
        # while later steps are still in flight: the victim's slots die,
        # its flush waits for the last in-flight step's commit, and the
        # allocator's exact double-free detection proves the deferral
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params, _cfg(prefix=False, depth=2))
        r = eng.put(list(UIDS), [list(p) for p in PROMPTS], _greedy=True)
        orig, state = eng._pre_commit, {"n": 0}

        def hook(fl):
            orig(fl)
            state["n"] += 1
            if state["n"] == 3:            # mid-decode, ring non-empty
                assert eng.abort(1) is True
        eng._pre_commit = hook
        outs = eng.decode_pipelined(list(UIDS),
                                    [int(r[u]) for u in UIDS], N_TOK - 1)
        eng._pre_commit = orig
        assert 1 not in eng.state.sequences
        live_blocks = sum(len(s.kv_blocks)
                          for s in eng.state.sequences.values())
        assert eng.free_blocks == eng.config.num_blocks - live_blocks
        for u in (0, 2):                   # survivors unaffected
            assert [int(r[u])] + outs[u] == oracle[u]
        # the aborted stream is a prefix of its oracle (nothing invented)
        got = [int(r[1])] + outs[1]
        assert got == oracle[1][:len(got)]

    def test_abort_racing_eos_rollback_no_double_free(self):
        # a late EOS marks a sequence's later in-flight slots dead and
        # queues a deferred rollback; an abort() arriving before that
        # rollback's carrier step commits must not flush the blocks the
        # rollback will then trim again (allocator double-free) — the
        # review-found race behind deadline-abort + EOS interleavings
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params, _cfg(
            prefix=False, depth=3, block_size=1, num_blocks=64,
            max_blocks_per_seq=32, attention_impl="dense"))
        # the tiny random model's greedy chains mostly repeat one token,
        # and WHICH prompts do follows the jax.random stream the weights
        # were drawn from (it changed under this test once already): take
        # the first prompt whose third token is new, so that using it as
        # EOS fires mid-ring at depth 3 and not at the first token
        for seed in range(9, 40):
            prompt = list(np.random.default_rng(seed).integers(1, 96, 10))
            f = eng.put([0], [prompt], _greedy=True)
            chain = eng.decode_pipelined([0], [int(f[0])], 8)[0]
            eng.flush(0)
            if chain[2] not in chain[:2]:
                break
        else:
            pytest.fail("no prompt yields a chain with a fresh third token")
        eos = chain[2]
        f = eng.put([1], [prompt], _greedy=True)
        orig, state = eng._pre_commit, {"done": False}

        def hook(fl):
            orig(fl)
            if fl.rollbacks and not state["done"]:
                state["done"] = True       # rollback carrier committing:
                eng.abort(1)               # the abort races the trim
        eng._pre_commit = hook
        out = eng.decode_pipelined([1], [int(f[1])], 8, eos_token_id=eos)
        eng._pre_commit = orig
        assert state["done"], "EOS rollback never queued — dead scenario"
        assert out[1] == chain[:3]         # stream ends at eos, as sync
        assert 1 not in eng.state.sequences
        assert eng.free_blocks == eng.config.num_blocks

    def test_abort_shared_prefix_refcounts_exact(self):
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params, _cfg())
        _serve(eng, n=3)
        assert eng.abort(1) is True
        eng._prefix.check_invariants()
        for u in (0, 2):
            eng.flush(u)
        eng._prefix.check_invariants()
        assert eng._prefix.evictable_blocks == eng._prefix.cached_blocks
        assert eng.free_blocks == eng.config.num_blocks


class TestJournalReplay:
    """The write-ahead journal: a hard crash (no drain ran) still
    recovers every COMMITTED token from the JSONL log."""

    def test_journal_crash_replay_parity(self, oracle, tmp_path):
        jpath = str(tmp_path / "serve.jsonl")
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params,
                                _cfg(serve_journal=jpath))
        partial = _serve(eng, n=4)
        # hard crash: NO drain — the journal alone carries the state
        del eng
        m = manifest_from_journal(jpath)
        assert m["source"] == "journal"
        got = {int(s["uid"]): s["generated"] for s in m["sequences"]}
        assert got == partial
        _, toks = _replay_and_finish(m, _cfg())
        assert toks == oracle

    def test_journal_finish_drops_sequence(self, tmp_path):
        jpath = str(tmp_path / "serve.jsonl")
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params,
                                _cfg(serve_journal=jpath))
        _serve(eng, n=2)
        eng.flush(1)                       # journals the finish
        m = manifest_from_journal(jpath)
        assert sorted(int(s["uid"]) for s in m["sequences"]) == [0, 2]

    def test_journal_torn_tail_tolerated(self, tmp_path):
        jpath = str(tmp_path / "serve.jsonl")
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params,
                                _cfg(serve_journal=jpath))
        partial = _serve(eng, n=3)
        with open(jpath, "a") as f:
            f.write('{"e": "tokens", "t": {"0": [7')   # killed mid-write
        m = manifest_from_journal(jpath)
        got = {int(s["uid"]): s["generated"] for s in m["sequences"]}
        assert got == partial              # committed prefix intact

    @pytest.mark.slow
    def test_drain_leaves_journal_intact_as_fallback(self, oracle,
                                                     tmp_path):
        # the drain flush must NOT append 'finish' records for the
        # sequences the manifest still owes to a survivor: if the drain
        # itself dies before write_manifest lands, the journal is the
        # only recovery channel left (review-found torn-drain hole)
        jpath = str(tmp_path / "serve.jsonl")
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params, _cfg(serve_journal=jpath))
        partial = _serve(eng, n=4)
        m = eng.drain()
        assert len(m["sequences"]) == 3
        m2 = manifest_from_journal(jpath)
        got = {int(s["uid"]): s["generated"] for s in m2["sequences"]}
        assert got == partial              # all three still recoverable
        _, toks = _replay_and_finish(m2, _cfg())
        assert toks == oracle

    def test_load_replay_state_prefers_manifest(self, tmp_path):
        mpath, jpath = str(tmp_path / "m.json"), str(tmp_path / "j.jsonl")
        write_manifest({"version": 1, "source": "drain",
                        "sequences": []}, mpath)
        with open(jpath, "w") as f:
            f.write(json.dumps({"e": "admit", "uid": 3,
                                "prompt": [1, 2]}) + "\n")
        assert load_replay_state(mpath, jpath)["source"] == "drain"
        assert load_replay_state(None, jpath)["source"] == "journal"
        assert load_replay_state(str(tmp_path / "nope.json"), None) is None


class TestDeadlinesShedRetry:
    """Request deadlines, graceful load shedding, bounded retry — the
    crash-free failure paths of the serve loop."""

    def test_deadline_expiry_aborts_with_rejection(self, oracle):
        mcfg, params = _gpt2()
        # a roomy deadline so admission stamping never fires on its own
        eng = InferenceEngineV2(mcfg, params,
                                _cfg(prefix=False, request_deadline_s=60))
        r = eng.put(list(UIDS), [list(p) for p in PROMPTS], _greedy=True)
        for u in UIDS:
            assert eng.state.sequences[u].deadline_at is not None
        eng.state.sequences[1].deadline_at = time.monotonic() - 1
        outs = eng.decode_pipelined(list(UIDS),
                                    [int(r[u]) for u in UIDS], N_TOK - 1)
        rej = eng.rejections[1]
        assert rej["reason"] == "deadline_exceeded"
        assert rej["deadline_s"] == 60
        assert 1 not in eng.state.sequences
        for u in (0, 2):                   # on-time requests unaffected
            assert [int(r[u])] + outs[u] == oracle[u]
        # a request that COMPLETED its budget on time owes nothing: an
        # expired deadline on its idle descriptor must not reap it
        # while other traffic decodes (review finding — late-503 for an
        # already-answered request)
        eng.state.sequences[0].deadline_at = time.monotonic() - 1
        more = eng.decode_pipelined([2], [outs[2][-1]], 2)
        assert 0 not in eng.rejections
        assert 0 in eng.state.sequences
        assert len(more[2]) == 2

    def test_decode_outgrows_pool_sheds_gracefully(self):
        mcfg, params = _gpt2()
        # prompt (13) + first token fills the 4-block pool exactly; the
        # next decode token needs a 5th block -> starvation mid-flight
        eng = InferenceEngineV2(mcfg, params, _cfg(
            prefix=False, num_blocks=4, max_seqs=2))
        prompt = list(np.random.default_rng(3).integers(1, 96, 13))
        r = eng.put([0], [prompt], _greedy=True)
        outs = eng.decode_pipelined([0], [int(r[0])], 8)
        assert len(outs[0]) < 8            # shed before the budget
        assert eng.rejections[0]["reason"] == "kv_pool_exhausted"
        assert 0 not in eng.state.sequences
        assert eng.free_blocks == 4        # full-capacity recovery
        # and the engine keeps serving new traffic
        ok = eng.put([1], [[5, 6, 7]], _greedy=True)
        assert 1 in ok

    @pytest.mark.slow
    def test_decode_outgrows_pool_hard_mode_raises(self):
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params, _cfg(
            prefix=False, num_blocks=4, max_seqs=2, serve_shed=False))
        prompt = list(np.random.default_rng(3).integers(1, 96, 13))
        r = eng.put([0], [prompt], _greedy=True)
        with pytest.raises(RuntimeError, match="starved"):
            eng.decode_pipelined([0], [int(r[0])], 8)

    def test_transient_dispatch_failure_retries(self, oracle):
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params, _cfg(
            prefix=False, serve_retry_backoff_s=0.0))
        set_fault_injector(FaultInjector(site="pre_dispatch",
                                         mode="ioerror", times=2))
        try:
            toks = _serve(eng)
        finally:
            set_fault_injector(None)
        assert eng.pipeline_stats["retries"] == 2
        assert toks == oracle              # retries are invisible

    @pytest.mark.slow
    def test_persistent_dispatch_failure_surfaces_then_drains(self, oracle):
        mcfg, params = _gpt2()
        eng = InferenceEngineV2(mcfg, params, _cfg(
            prefix=False, serve_retry_backoff_s=0.0, serve_step_retries=2))
        set_fault_injector(FaultInjector(site="pre_dispatch",
                                         mode="ioerror", times=1000))
        try:
            with pytest.raises(ServeStepError):
                _serve(eng)
        finally:
            set_fault_injector(None)
        # the drained state is still consistent and replayable
        m = eng.drain()
        _assert_released(eng, m)
        if m["sequences"]:
            _, toks = _replay_and_finish(m, _cfg(prefix=False))
            for u in toks:
                assert toks[u] == oracle[u]


class TestServeDrainPrograms:
    """The drain/replay layer must add NOTHING to the device story:
    replay on a warm engine compiles no fresh programs, and the serve
    programs stay collective/callback-clean at tp1."""

    @pytest.mark.slow
    def test_replay_warm_zero_fresh_compiles_and_clean_programs(self):
        from deepspeed_tpu.analysis import RecompileTripwire
        from deepspeed_tpu.analysis.program_audit import (
            CollectiveBudget, assert_budget, audit_serve_programs)
        mcfg, params = _gpt2()
        dead = InferenceEngineV2(mcfg, params, _cfg())
        _serve(dead, n=4)
        m = dead.drain()
        surv = InferenceEngineV2(mcfg, params, _cfg())
        _serve(surv, uids=(7,), prompts=(_SHARED + [3, 1, 4, 1, 5],),
               n=N_TOK)                    # warm every program
        surv.flush(7)
        tw = RecompileTripwire()
        with tw:
            out = surv.replay(m)
            short = sorted(int(s["uid"]) for s in m["sequences"])
            surv.decode_pipelined(short, [int(out[u]) for u in short], 3)
        assert tw.fresh_compiles == 0
        # drain-path device programs: zero collectives, zero callbacks
        reports = audit_serve_programs(surv)
        clean = CollectiveBudget(name="tp1 serve after drain/replay")
        for name, rep in reports.items():
            assert_budget(rep, clean)
            assert rep.host_callbacks == 0, name

    @pytest.mark.slow
    def test_llama_drain_replay_parity(self):
        from deepspeed_tpu.models.llama import Llama, LlamaConfig
        mcfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="xla")
        params = Llama(mcfg).init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32))["params"]
        prompts = tuple(_SHARED + t for t in ([7, 1, 3], [2, 9, 4]))
        base = _cfg()
        eng0 = InferenceEngineV2(mcfg, params, base)
        want = _serve(eng0, uids=(0, 1), prompts=prompts, n=6)
        eng = InferenceEngineV2(mcfg, params, base)
        _serve(eng, uids=(0, 1), prompts=prompts, n=3)
        m = eng.drain()
        _assert_released(eng, m)
        eng2, toks = _replay_and_finish(m, base, n=6,
                                        model=(mcfg, params))
        assert toks == want

    @pytest.mark.slow
    def test_tp2_drain_replay_parity(self):
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        mcfg, params = _gpt2()
        base = _cfg(tp_size=2, max_seqs=2)
        prompts = (PROMPTS[0], PROMPTS[1])
        eng0 = InferenceEngineV2(mcfg, params, base)
        want = _serve(eng0, uids=(0, 1), prompts=prompts, n=6)
        eng = InferenceEngineV2(mcfg, params, base)
        _serve(eng, uids=(0, 1), prompts=prompts, n=3)
        m = eng.drain()
        _assert_released(eng, m)
        _, toks = _replay_and_finish(m, base, n=6)
        assert toks == want
