"""Disaggregated prefill/decode serving tests (ISSUE 17).

The contract under test: a pool whose replicas declare phase specialisms
(``roles=["prefill", "decode"]``) serves every request token-identically
to a colocated pool — the prefill→decode migration (one batched
non-blocking KV gather, one batched restore scatter, drain-shaped
manifest records) is invisible to callers. Covered here: greedy /
seeded-sampled / speculative parity, int8 payload + scale exactness
across the handoff, refcount exactness on both replicas after the move,
the aborted-handoff fault site losing nothing, the draining-destination
fallback replay, and the ``DSTPU_DISAGG=0`` kill switch restoring the
exact pre-disagg path. The SIGTERM-mid-handoff variant rides
``bin/dstpu_faultdrill --mode disagg`` (subprocess, slow tier)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceConfig,
                                        SamplingParams)
from deepspeed_tpu.inference.v2.drain import EngineDrainingError
from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
from deepspeed_tpu.resilience.fault_injection import (DISAGG_FAULT_SITE,
                                                      FaultInjector,
                                                      set_fault_injector)
from deepspeed_tpu.serving import REPLICA_ROLES, ReplicaPool

_CACHE = {}


def _gpt2():
    if "m" not in _CACHE:
        mcfg = GPT2Config(vocab_size=96, max_seq_len=256, num_layers=2,
                          num_heads=2, hidden_size=32, dtype=jnp.float32)
        params = GPT2(mcfg).init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
        _CACHE["m"] = (mcfg, params)
    return _CACHE["m"]


def _engine(**kw):
    mcfg, params = _gpt2()
    base = dict(max_seqs=4, chunk_size=8, block_size=4, num_blocks=96,
                max_blocks_per_seq=24, dtype="float32",
                attention_impl="dense", decode_loop_steps=0,
                serve_pipeline_depth=2, prefix_cache=True)
    base.update(kw)
    return InferenceEngineV2(mcfg, params, RaggedInferenceConfig(**base))


def _disagg_pool(**ekw):
    return ReplicaPool([_engine(**ekw), _engine(**ekw)],
                       policy="prefix_aware", seed=0,
                       replica_ids=["pre", "dec"],
                       roles=["prefill", "decode"])


def _colocated_pool(n=1, **ekw):
    return ReplicaPool([_engine(**ekw) for _ in range(n)],
                       policy="prefix_aware", seed=0)


GEN = 6
_rng = np.random.default_rng(5)
_SHARED = [_rng.integers(1, 96, 10).tolist() for _ in range(2)]
#: 4 prompts over 2 shared preambles — the affinity-scored workload
PROMPTS = {u: _SHARED[u % 2] + _rng.integers(1, 96, 4 + u).tolist()
           for u in range(4)}


def _drive(pool, prompts, gen=GEN, sampling=None):
    """put + decode rounds to ``gen`` tokens per uid; returns
    ({uid: full stream}, {uid: final owner replica id})."""
    toks = {}
    out = pool.put(list(prompts), [prompts[u] for u in prompts],
                   _greedy=True, sampling=sampling)
    for u in prompts:
        toks[u] = [int(out[u])]
    while True:
        live = [u for u in toks if len(toks[u]) < gen
                and u in pool.state.sequences]
        if not live:
            break
        outs = pool.decode_pipelined(live, [toks[u][-1] for u in live], 2)
        for u in live:
            toks[u].extend(outs[u][:gen - len(toks[u])])
    owners = {u: pool.owner_of(u).replica_id for u in toks
              if pool.owner_of(u) is not None}
    for u in toks:
        pool.flush(u)
    return toks, owners


@pytest.fixture(scope="module")
def greedy_oracle():
    """The colocated greedy streams for PROMPTS — computed once, shared
    by every parity check in the module."""
    toks, _ = _drive(_colocated_pool(1), PROMPTS)
    return toks


# ------------------------------------------------------------------ #
# token parity — the tentpole invariant
# ------------------------------------------------------------------ #


class TestDisaggParity:
    def test_greedy_parity_and_invisible_migration(self, greedy_oracle):
        pool = _disagg_pool()
        out = pool.put(list(PROMPTS), [PROMPTS[u] for u in PROMPTS],
                       _greedy=True)
        toks = {u: [int(out[u])] for u in PROMPTS}
        # ownership flipped to the decode specialist INSIDE put — the
        # caller saw first tokens computed on the prefill side, but the
        # very next decode call lands on the destination
        assert all(pool.owner_of(u).replica_id == "dec" for u in PROMPTS)
        pre_m = pool.replica("pre").engine.metrics
        dec_m = pool.replica("dec").engine.metrics
        assert pre_m.counter("serve_handoff_seqs").value == len(PROMPTS)
        assert dec_m.counter("serve_handoff_seqs_in").value == len(PROMPTS)
        assert pre_m.counter("serve_handoff_blocks").value > 0
        assert pre_m.counter("serve_handoff_bytes").value > 0
        # ONE batched materialize per migration → one exposed-wall sample
        assert dec_m.histogram("serve_handoff_exposed_s").count == 1
        # blocks arrive private; refcounts exact on BOTH replicas
        for rid in ("pre", "dec"):
            eng = pool.replica(rid).engine
            eng._prefix.assert_exact_refs(eng.state.sequences.values())
        while True:
            live = [u for u in toks if len(toks[u]) < GEN
                    and u in pool.state.sequences]
            if not live:
                break
            outs = pool.decode_pipelined(live,
                                         [toks[u][-1] for u in live], 2)
            for u in live:
                toks[u].extend(outs[u][:GEN - len(toks[u])])
        assert toks == greedy_oracle
        for u in toks:
            pool.flush(u)

    def test_two_mixed_vs_disagg_parity(self, greedy_oracle):
        # same N, different specialisation — streams identical
        toks, _ = _drive(_colocated_pool(2), PROMPTS)
        assert toks == greedy_oracle

    def test_warm_handoff_pass_zero_fresh_compiles(self, greedy_oracle):
        # the batched KV gather and restore scatter are shape-bucketed:
        # a second wave of the same shapes migrates and decodes without
        # compiling anything
        from deepspeed_tpu.analysis import RecompileTripwire
        pool = _disagg_pool()
        _drive(pool, PROMPTS)
        again = {10 + u: p for u, p in PROMPTS.items()}
        tw = RecompileTripwire()
        with tw:
            toks, owners = _drive(pool, again)
        assert {u - 10: t for u, t in toks.items()} == greedy_oracle
        assert set(owners.values()) == {"dec"}
        assert tw.fresh_compiles == 0

    def test_sampled_seeded_parity(self):
        sp = {u: SamplingParams(temperature=0.8, top_k=12, seed=70 + u)
              for u in PROMPTS}
        want, _ = _drive(_colocated_pool(1), PROMPTS, sampling=sp)
        got, owners = _drive(_disagg_pool(), PROMPTS, sampling=sp)
        # the handoff record carries the sampling identity — the
        # destination continues the SAME seeded stream
        assert got == want
        assert set(owners.values()) == {"dec"}

    def test_spec_decode_parity(self):
        # periodic prompts (self-drafting acceptance food); speculation
        # is lossless, so disagg spec streams == colocated spec streams
        pat = _rng.integers(1, 96, 6).tolist()
        prompts = {u: (pat * 4)[: 14 + u] for u in range(3)}
        kw = dict(spec_decode="ngram", spec_k=4)
        want, _ = _drive(_colocated_pool(1, **kw), prompts, gen=8)
        got, owners = _drive(_disagg_pool(**kw), prompts, gen=8)
        assert got == want
        assert set(owners.values()) == {"dec"}


# ------------------------------------------------------------------ #
# int8 pools — payload + scale exactness across the wire
# ------------------------------------------------------------------ #


class TestInt8Handoff:
    def test_payload_and_scales_exact(self):
        src = _engine(kv_cache_dtype="int8")
        dst = _engine(kv_cache_dtype="int8")
        uids = list(PROMPTS)
        first = src.put(uids, [PROMPTS[u] for u in uids], _greedy=True)
        manifest = src.handoff_out(uids)
        recs = manifest["sequences"]
        assert len(recs) == len(uids)
        host = jax.device_get([r["kv"] for r in recs])
        for rec, h in zip(recs, host):
            rows, scales = h
            # int8 payload + f32 scale planes ride AS-IS: content-exact
            # at half the bytes — never a dequant/requant round trip
            assert rows.dtype == np.int8
            assert scales.dtype == np.float32
            rec["kv"] = h
        res = dst.handoff_in(manifest)
        assert sorted(res["accepted"]) == sorted(uids)
        assert res["spilled"] == []
        for rec in recs:
            seq = dst.state.get(rec["uid"])
            got_rows, got_scales = jax.device_get(
                dst.kv_cache.gather_blocks(dst._kv_data, seq.kv_blocks))
            assert np.array_equal(got_rows, rec["kv"][0])
            assert np.array_equal(got_scales, rec["kv"][1])
        # the destination continues the stream token-identically
        oracle = _engine(kv_cache_dtype="int8")
        of = oracle.put(uids, [PROMPTS[u] for u in uids], _greedy=True)
        ocont = oracle.decode_pipelined(uids, [of[u] for u in uids], 5)
        cont = dst.decode_pipelined(uids, [first[u] for u in uids], 5)
        assert {u: [first[u]] + cont[u] for u in uids} \
            == {u: [of[u]] + ocont[u] for u in uids}


# ------------------------------------------------------------------ #
# failure paths — nothing lost, ever
# ------------------------------------------------------------------ #


class TestDisaggFaults:
    def test_aborted_handoff_loses_nothing(self, greedy_oracle):
        # an injected fault mid-gather (the during_handoff_gather site)
        # aborts the WHOLE handoff before any source state is released:
        # every sequence stays live on the prefill specialist and
        # decodes colocated, token-identically
        pool = _disagg_pool()
        inj = FaultInjector(site=DISAGG_FAULT_SITE, mode="raise",
                            times=1)
        set_fault_injector(inj)
        try:
            out = pool.put(list(PROMPTS), [PROMPTS[u] for u in PROMPTS],
                           _greedy=True)
        finally:
            set_fault_injector(None)
        assert inj._fired == 1
        toks = {u: [int(out[u])] for u in PROMPTS}
        assert all(pool.owner_of(u).replica_id == "pre" for u in PROMPTS)
        pre = pool.replica("pre").engine
        assert all(pre.state.get(u) is not None for u in PROMPTS)
        pre._prefix.assert_exact_refs(pre.state.sequences.values())
        assert pool.replica("dec").engine.metrics.counter(
            "serve_handoff_seqs_in").value == 0
        while True:
            live = [u for u in toks if len(toks[u]) < GEN
                    and u in pool.state.sequences]
            if not live:
                break
            outs = pool.decode_pipelined(live,
                                         [toks[u][-1] for u in live], 2)
            for u in live:
                toks[u].extend(outs[u][:GEN - len(toks[u])])
        assert toks == greedy_oracle
        for u in toks:
            pool.flush(u)
        # the injector is spent — the next wave migrates normally
        toks2, owners2 = _drive(pool, PROMPTS)
        assert toks2 == greedy_oracle
        assert set(owners2.values()) == {"dec"}

    def test_draining_destination_falls_back_to_replay(
            self, greedy_oracle, monkeypatch):
        # the decode specialist flips draining between the routing
        # decision and the adopt: the pool replays the SAME records
        # drain-style on a survivor — token-identical, counted in
        # serve_handoff_fallback_replays
        pool = _disagg_pool()
        dec = pool.replica("dec").engine

        def refuse(manifest, exposed_s=0.0):
            raise EngineDrainingError("flipped draining under the adopt")

        monkeypatch.setattr(dec, "handoff_in", refuse)
        toks, owners = _drive(pool, PROMPTS)
        assert toks == greedy_oracle
        assert set(owners.values()) <= {"pre", "dec"}
        replays = sum(
            int(r.engine.metrics.counter(
                "serve_handoff_fallback_replays").value)
            for r in pool.replicas())
        assert replays == len(PROMPTS)

    @pytest.mark.slow
    def test_disagg_faultdrill_subprocess(self, tmp_path):
        # the CI drill end-to-end in a fresh process: aborted handoff
        # (nothing lost) + real SIGTERM on the prefill specialist
        # (drain replay onto the decode specialist) + post-kill traffic
        from deepspeed_tpu.resilience.faultdrill import drill_disagg
        result = drill_disagg(str(tmp_path))
        assert result["recovered"] is True
        assert result["abort_safe"] is True
        assert result["token_parity"] is True
        assert result["post_kill_on_survivor"] is True


# ------------------------------------------------------------------ #
# kill switch + role surface
# ------------------------------------------------------------------ #


class TestKillSwitchAndRoles:
    def test_disagg_off_restores_colocated_path(self, greedy_oracle,
                                                monkeypatch):
        monkeypatch.setenv("DSTPU_DISAGG", "0")
        pool = ReplicaPool([_engine(), _engine()],
                           policy="prefix_aware", seed=0,
                           replica_ids=["pre", "dec"],
                           roles=["prefill", "decode"])
        plain = ReplicaPool([_engine(), _engine()],
                            policy="prefix_aware", seed=0,
                            replica_ids=["pre", "dec"])
        assert all(r.role == "mixed" for r in pool.replicas())
        toks, owners = _drive(pool, PROMPTS)
        want, want_owners = _drive(plain, PROMPTS)
        # exact pre-disagg behaviour: same placements, same streams,
        # zero migrations
        assert toks == want == greedy_oracle
        assert owners == want_owners
        assert all(
            r.engine.metrics.counter("serve_handoff_seqs").value == 0
            for r in pool.replicas())

    def test_role_surface_validated(self):
        assert REPLICA_ROLES == ("prefill", "decode", "mixed")
        with pytest.raises(ValueError):
            ReplicaPool([_engine()], roles=["turbo"])
        with pytest.raises(ValueError):
            ReplicaPool([_engine(), _engine()], roles=["prefill"])
        pool = _disagg_pool()
        desc = {r.replica_id: r.describe() for r in pool.replicas()}
        assert desc["pre"]["role"] == "prefill"
        assert desc["dec"]["role"] == "decode"
