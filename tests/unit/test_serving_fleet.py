"""Replica-pool serving fleet tests (ISSUE 11).

Router unit tests run against fake replicas (pure host scoring — no
engines), so placement determinism, the queue-depth fallback, slot
admission control and draining exclusion pin the POLICY, not engine
timing. The pool tests drive real 2-replica fleets of tiny CPU engines:
a tier-1 smoke through the open-loop loadgen (books balanced, fleet
rollup exact, stable source labels), routing affinity, and elastic
membership (drain mid-stream -> survivor absorb -> token parity + late
joiner). Heavier N and the subprocess SIGTERM drill ride the slow tier
(``bin/dstpu_faultdrill --mode fleet`` is the CI gate).
"""

import jax
import pytest

from deepspeed_tpu.serving import (NoServingReplicaError, ReplicaPool,
                                   Router, build_replica_engines,
                                   fleet_prefix_stats, single_stream_oracle)
from deepspeed_tpu.telemetry.loadgen import (UniformArrivals, WorkloadMix,
                                             _tiny_engine, build_requests,
                                             run_open_loop)
from deepspeed_tpu.telemetry.registry import (Histogram, MetricsRegistry,
                                              merge_snapshots)

# ------------------------------------------------------------------ #
# router policy — fake replicas, pure host
# ------------------------------------------------------------------ #


class FakeReplica:
    """Just the scoring surface the router reads."""

    def __init__(self, rid, overlap=0, queue=0.0, headroom=1.0,
                 available=True):
        self.replica_id = rid
        self._overlap = overlap
        self._queue = queue
        self._headroom = headroom
        self.available = available

    def prefix_overlap(self, tokens):
        return self._overlap

    def queue_frac(self):
        return self._queue

    def slo_headroom(self, slo):
        return self._headroom


class TieredFakeReplica(FakeReplica):
    """A replica whose overlap splits device/host — the hierarchical-KV
    scoring surface."""

    def __init__(self, rid, dev=0, host=0, **kw):
        super().__init__(rid, overlap=dev + host, **kw)
        self._dev, self._host = dev, host

    def prefix_overlap_tiered(self, tokens):
        return self._dev, self._host


class TestRouterPolicy:
    def test_demoted_overlap_scored_at_discount(self):
        """Hierarchical KV routing: equal total overlap, but one
        replica holds the chain on DEVICE and the other would have to
        PROMOTE it — the device holder must win; yet a host-resident
        chain still beats no chain at all."""
        from deepspeed_tpu.serving.router import Router
        r = Router(policy="prefix_aware", seed=3)
        prompt = list(range(64))
        dev_holder = TieredFakeReplica("a", dev=48, host=0)
        host_holder = TieredFakeReplica("b", dev=0, host=48)
        cold = TieredFakeReplica("c")
        assert r.score(dev_holder, prompt) > r.score(host_holder, prompt)
        assert r.score(host_holder, prompt) > r.score(cold, prompt)
        # with the discount at 1.0 the tiers are indistinguishable
        flat = Router(policy="prefix_aware", seed=3, w_demoted=1.0)
        assert flat.score(dev_holder, prompt) == \
            flat.score(host_holder, prompt)
        # plain (un-tiered) replicas keep working through the fallback
        legacy = FakeReplica("d", overlap=48)
        assert r.score(legacy, prompt) == r.score(dev_holder, prompt)
        assert "w_demoted" in r.describe()

    def test_prefix_overlap_wins_over_mild_load(self):
        cold = FakeReplica("cold", overlap=0, queue=0.0)
        warm = FakeReplica("warm", overlap=32, queue=0.5)
        r = Router(policy="prefix_aware", seed=0)
        prompt = list(range(48))
        # overlap 32/48 = 0.667 beats the 0.5 queue handicap
        assert r.select([cold, warm], prompt) is warm

    def test_queue_depth_fallback_when_no_prefix_matches(self):
        # no cached overlap anywhere -> pure least-loaded
        busy = FakeReplica("busy", overlap=0, queue=0.75)
        idle = FakeReplica("idle", overlap=0, queue=0.25)
        r = Router(policy="prefix_aware", seed=3)
        for _ in range(5):
            assert r.select([busy, idle], list(range(16))) is idle

    def test_slot_admission_control_overrides_affinity(self):
        # a FULL replica loses even a perfect cache hit to an open one;
        # when every replica is full, the best full one is used
        full = FakeReplica("full", overlap=48, queue=1.0)
        open_ = FakeReplica("open", overlap=0, queue=0.25)
        r = Router(policy="prefix_aware", seed=0)
        prompt = list(range(48))
        assert r.select([full, open_], prompt) is open_
        open_._queue = 1.5
        assert r.select([full, open_], prompt) is full

    def test_draining_replica_excluded(self):
        live = FakeReplica("live", overlap=0, queue=0.9)
        gone = FakeReplica("gone", overlap=48, queue=0.0,
                           available=False)
        for policy in ("prefix_aware", "round_robin", "random"):
            r = Router(policy=policy, seed=1)
            for _ in range(4):
                assert r.select([gone, live], list(range(48))) is live
        with pytest.raises(NoServingReplicaError):
            Router(seed=0).select(
                [FakeReplica("a", available=False)], [1, 2])

    def test_seed_stable_tie_breaks_and_determinism(self):
        # identical request/replica history => identical placements,
        # including the rng-broken ties of a cold (all-equal) fleet
        def placements(seed):
            reps = [FakeReplica(f"r{i}") for i in range(3)]
            r = Router(policy="prefix_aware", seed=seed)
            return [r.select(reps, [1] * 8).replica_id
                    for _ in range(12)]

        assert placements(7) == placements(7)
        a = placements(7)
        assert len(set(a)) > 1          # ties spread, not replica-0 bias

    def test_round_robin_cycles_available(self):
        reps = [FakeReplica(f"r{i}") for i in range(3)]
        r = Router(policy="round_robin", seed=0)
        got = [r.select(reps, [1]).replica_id for _ in range(6)]
        assert got == ["r0", "r1", "r2", "r0", "r1", "r2"]

    def test_bad_policy_rejected(self):
        with pytest.raises(ValueError):
            Router(policy="sticky")


# ------------------------------------------------------------------ #
# merge source labels — the satellite regression (no engines)
# ------------------------------------------------------------------ #


class TestMergeSourceScheme:
    def _reg(self, free):
        r = MetricsRegistry("serve")      # every replica's default name
        r.counter("serve_requests_admitted").inc(3)
        r.gauge("kv_pool_blocks_free").set(free)
        r.histogram("serve_ttft_s").observe(0.1 * (1 + free))
        return r

    def test_sources_stable_regardless_of_order(self):
        a, b = self._reg(10), self._reg(20)
        m1 = MetricsRegistry.merge([a, b], sources=["r0", "r1"])
        m2 = MetricsRegistry.merge([b, a], sources=["r1", "r0"])
        g1, g2 = m1.snapshot()["gauges"], m2.snapshot()["gauges"]
        assert set(g1) == set(g2)
        assert g1['kv_pool_blocks_free{source="r0"}'] == 10
        assert g1['kv_pool_blocks_free{source="r1"}'] == 20
        # without sources, same-named registries disambiguate by index:
        # order-dependent — exactly what the id scheme exists to avoid
        mi = MetricsRegistry.merge([b, a])
        gi = mi.snapshot()["gauges"]
        assert gi['kv_pool_blocks_free{source="serve"}'] == 20

    def test_merge_of_merge_idempotent(self):
        a, b = self._reg(10), self._reg(20)
        m1 = MetricsRegistry.merge([a, b], sources=["r0", "r1"])
        # re-rolling the rollup (e.g. a pool-of-pools) keeps the
        # original per-replica gauge identities and exact histograms
        mm = MetricsRegistry.merge([m1], sources=["poolA"])
        g = mm.snapshot()["gauges"]
        assert 'kv_pool_blocks_free{source="r0"}' in g
        assert 'kv_pool_blocks_free{source="r1"}' in g
        h = mm.snapshot()["histograms"]["serve_ttft_s"]
        ref = MetricsRegistry.merge(
            [a, b], sources=["r0", "r1"]
        ).snapshot()["histograms"]["serve_ttft_s"]
        assert h == ref
        assert mm.counter("serve_requests_admitted").value == 6

    def test_short_sources_refused(self):
        with pytest.raises(ValueError):
            MetricsRegistry.merge([self._reg(1), self._reg(2)],
                                  sources=["only-one"])

    def test_snapshot_merge_matches_registry_merge(self):
        a, b = self._reg(10), self._reg(20)
        via_reg = MetricsRegistry.merge(
            [a, b], sources=["r0", "r1"]).snapshot()
        via_snap = merge_snapshots([a.snapshot(), b.snapshot()],
                                   sources=["r0", "r1"])
        assert via_reg["counters"] == via_snap["counters"]
        assert via_reg["gauges"] == via_snap["gauges"]
        assert via_reg["histograms"] == via_snap["histograms"]


# ------------------------------------------------------------------ #
# real 2-replica pool — tier-1 smoke
# ------------------------------------------------------------------ #


def _mk_pool(n=2, policy="prefix_aware", seed=0):
    # one device per replica; the factory commits nothing itself (its
    # arrays are only CREATED under build_replica_engines' device scope)
    built = []

    def factory(i, dev):
        built.append(_tiny_engine())
        return built[-1][0]

    pool = ReplicaPool(build_replica_engines(factory, n), policy=policy,
                       seed=seed)
    return pool, built[0][1]


def _grouped_mix(vocab, groups=3, gen=6):
    return WorkloadMix(
        prompt_lens=(24,), prompt_probs=(1.0,),
        gen_lens=(gen,), gen_probs=(1.0,),
        shared_prefix_frac=1.0, shared_prefix_len=16,
        prefix_group_count=groups, vocab_size=vocab)


@pytest.fixture(scope="module")
def smoke_pool():
    return _mk_pool(2)


class TestPoolSmoke:
    def test_open_loop_books_and_rollup(self, smoke_pool):
        pool, mcfg = smoke_pool
        reqs = build_requests(UniformArrivals(50.0),
                              _grouped_mix(mcfg.vocab_size), 16, seed=4)
        res = run_open_loop(pool, reqs, decode_burst=4, max_live=16)
        rep = res.report
        assert rep["requests"]["completed"] == 16
        assert rep["goodput_frac"] == 1.0
        assert sorted(len(s) for s in res.streams.values()) == [6] * 16
        # engines empty, owners cleared; refcount-0 cached blocks count
        # as free capacity, so a drained fleet reports a full pool
        assert not pool.state.sequences
        assert all(r.engine.free_blocks == r.engine.config.num_blocks
                   for r in pool.replicas())
        # AFTER serving from the pool's worker threads each replica's
        # weights and KV pool still sit on the device it was built on
        # (uncommitted engine state used to drift to device 0)
        for i, r in enumerate(pool.replicas()):
            for tree in (r.engine.params, r.engine._kv_data):
                assert {d.id for leaf in jax.tree_util.tree_leaves(tree)
                        for d in leaf.devices()} == {jax.devices()[i].id}
        # fleet rollup: merged admitted counter covers every request,
        # gauges carry stable per-replica source labels
        snap = pool.fleet_snapshot()
        assert snap["counters"]["serve_requests_admitted"] >= 16
        assert 'kv_pool_blocks_free{source="r0"}' in snap["gauges"]
        assert 'kv_pool_blocks_free{source="r1"}' in snap["gauges"]
        assert set(snap["replicas"]) == {"r0", "r1"}
        slo = pool.slo_report()
        assert slo["goodput_frac"] == 1.0
        assert slo["ttft_s"]["count"] >= 16

    def test_prefix_affinity_groups_stick(self, smoke_pool):
        # steady state: requests of one preamble group land on the
        # replica already holding its blocks (scored overlap > 0)
        pool, mcfg = smoke_pool
        mix = _grouped_mix(mcfg.vocab_size, groups=2)
        reqs = build_requests(UniformArrivals(1000.0), mix, 12, seed=9,
                              uid_base=500)
        by_group = {}
        out = {}
        for r in reqs:                      # admit one by one: owner
            out.update(pool.put([r.uid], [r.prompt], _greedy=True))
            if r.group is not None:
                rep = pool.owner_of(r.uid)
                by_group.setdefault(r.group, set()).add(rep.replica_id)
        # after the cold first-touch, every group maps to ONE replica
        tail = {g: owners for g, owners in by_group.items()}
        assert all(len(owners) <= 2 for owners in tail.values())
        # drive to completion and check the fleet actually hit
        live = [u for u in out]
        pool.decode_pipelined(live, [out[u] for u in live], 6)
        st = fleet_prefix_stats(pool)
        assert st["matched_tokens"] > 0
        for r in reqs:
            pool.flush(r.uid)


class TestRoutingEarnsItsKeep:
    def test_prefix_aware_beats_random_on_fleet_hit_fraction(self):
        """More preamble groups than ONE replica's prefix-cache cap
        holds, the same requests one after another: affinity keeps each
        replica's group subset resident where random placement thrashes
        both caps. Tokens do not depend on placement."""
        import jax.numpy as jnp
        import numpy as np

        from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                                RaggedInferenceConfig)
        from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
        mcfg = GPT2Config(vocab_size=96, max_seq_len=256, num_layers=2,
                          num_heads=2, hidden_size=32, dtype=jnp.float32)
        params = GPT2(mcfg).init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]

        def factory(i, dev):
            # the cap holds two of the four 2-block preambles
            return InferenceEngineV2(
                mcfg, jax.device_put(params, dev), RaggedInferenceConfig(
                    max_seqs=4, chunk_size=16, block_size=16,
                    num_blocks=48, max_blocks_per_seq=8, dtype="float32",
                    attention_impl="dense", decode_loop_steps=0,
                    prefix_cache=True, prefix_cache_max_blocks=4))

        rng = np.random.default_rng(3)
        pres = [rng.integers(1, 96, 32).tolist() for _ in range(4)]
        reqs = [(lap * 4 + g, pres[g] + rng.integers(1, 96, 6).tolist())
                for lap in range(5) for g in range(4)]
        hit, streams = {}, {}
        for policy in ("prefix_aware", "random"):
            pool = ReplicaPool(build_replica_engines(factory, 2),
                               policy=policy, seed=0)
            out = {}
            for uid, prompt in reqs:
                first = pool.put([uid], [prompt], _greedy=True)
                toks = pool.decode_pipelined([uid], [first[uid]], 2)
                out[uid] = [int(first[uid])] + toks[uid]
                pool.flush(uid)
            st = fleet_prefix_stats(pool)
            hit[policy] = st["matched_tokens"] / (
                st["matched_tokens"] + st["prefill_tokens"])
            streams[policy] = out
        assert hit["prefix_aware"] > 2 * hit["random"] > 0
        assert streams["prefix_aware"] == streams["random"]


class TestElasticMembership:
    def _drive(self, pool, prompts, gen, drain_at=None, joiner=None):
        toks = {}
        out = pool.put(list(prompts), [prompts[u] for u in prompts],
                       _greedy=True)
        for u in prompts:
            toks[u] = [int(out[u])]
        rounds = 0
        while True:
            live = [u for u in toks if len(toks[u]) < gen
                    and u in pool.state.sequences]
            if not live:
                break
            if rounds == drain_at:
                # preemption notice lands between engine calls; the
                # pool absorbs on its next entry (the SIGTERM-delivery
                # variant rides the faultdrill fleet mode)
                pool.replica("r0").engine.request_drain()
            if rounds == joiner:
                pool.add_replica(_tiny_engine()[0], replica_id="late")
            outs = pool.decode_pipelined(
                live, [toks[u][-1] for u in live], 2)
            for u in live:
                toks[u].extend(outs[u][:gen - len(toks[u])])
            rounds += 1
        owners = {u: pool.owner_of(u).replica_id for u in toks
                  if pool.owner_of(u) is not None}
        for u in toks:
            pool.flush(u)
        return toks, owners

    def test_drain_absorb_parity_and_joiner(self):
        import numpy as np
        gen = 6
        rng = np.random.default_rng(21)
        shared = [rng.integers(1, 96, 16).tolist() for _ in range(2)]
        prompts = {u: shared[u % 2] + rng.integers(1, 96, 6).tolist()
                   for u in range(6)}

        oracle_pool, _ = _mk_pool(1)
        oracle, _ = self._drive(oracle_pool, prompts, gen)

        pool, _ = _mk_pool(2)
        toks, owners = self._drive(pool, prompts, gen, drain_at=1,
                                   joiner=1)
        # token-identical through the membership change, exact recovery
        assert toks == oracle
        victim = pool.replica("r0")
        assert victim.state == "dead"
        assert victim.manifest["pool"]["fully_recovered"] is True
        assert victim.manifest["sequences"]
        # every sequence ended on a survivor; the dead replica is no
        # longer a routing candidate
        assert set(owners.values()) <= {"r1", "late"}
        fresh = pool.put([900], [list(range(1, 20))], _greedy=True)
        assert pool.owner_of(900).replica_id in ("r1", "late")
        pool.flush(900)
        # rollup excludes the dead replica but keeps exact counters
        snap = pool.fleet_snapshot()
        assert 'kv_pool_blocks_free{source="r0"}' not in snap["gauges"]
        assert 'kv_pool_blocks_free{source="r1"}' in snap["gauges"]

    def test_no_serving_replica_rejects(self):
        pool, _ = _mk_pool(1)
        pool.replica("r0").engine.request_drain()
        out = pool.put([7], [[1, 2, 3]], _greedy=True)
        assert out == {}
        assert pool.rejections[7]["reason"] == "no_serving_replica"

    def test_orphan_manifest_replays_onto_joiner(self):
        # the LAST replica dies with live sequences: the manifest waits
        # as an orphan (no crash), fresh work is refused, and the first
        # joiner absorbs the orphan token-identically; a retried uid
        # sheds its stale pool-level rejection
        gen = 6
        prompts = {u: list(range(1, 20 + u)) for u in range(2)}
        oracle_pool, _ = _mk_pool(1)
        oracle, _ = self._drive(oracle_pool, prompts, gen)

        pool, _ = _mk_pool(1)
        out = pool.put(list(prompts), [prompts[u] for u in prompts],
                       _greedy=True)
        toks = {u: [int(out[u])] for u in prompts}
        pool.replica("r0").engine.request_drain()
        assert pool.put([50], [[1, 2, 3]], _greedy=True) == {}
        assert pool.rejections[50]["reason"] == "no_serving_replica"
        assert pool.replica("r0").state == "dead"
        pool.add_replica(_tiny_engine()[0], replica_id="j")
        while any(len(toks[u]) < gen for u in toks):
            live = [u for u in toks if len(toks[u]) < gen]
            outs = pool.decode_pipelined(
                live, [toks[u][-1] for u in live], 2)
            for u in live:
                toks[u].extend(outs[u][:gen - len(toks[u])])
        assert toks == oracle
        out2 = pool.put([50], [[1, 2, 3]], _greedy=True)
        assert 50 in out2
        assert 50 not in pool.rejections
        for u in (*toks, 50):
            pool.flush(u)


class TestRollupExactness:
    def test_merged_quantiles_equal_single_stream(self, smoke_pool):
        # the drill's oracle, in-process: merged serve_ttft_s over the
        # replicas == one histogram fed the same values in one stream.
        # Drives its own small pass so the check stands alone (the
        # shared fixture may or may not have served traffic yet).
        pool, mcfg = smoke_pool
        reqs = build_requests(UniformArrivals(100.0),
                              _grouped_mix(mcfg.vocab_size), 8, seed=17,
                              uid_base=17_000)
        run_open_loop(pool, reqs, decode_burst=4, max_live=16)
        regs = [r.engine.metrics for r in pool.replicas()]
        snaps = [m.snapshot() for m in regs]
        merged = merge_snapshots(
            snaps, sources=[r.replica_id for r in pool.replicas()])
        state = merged["histograms"].get("serve_ttft_s")
        assert state and state["count"] > 0
        mhist = Histogram.from_state(state)
        single = Histogram()
        for s in snaps:
            single.merge(Histogram.from_state(
                s["histograms"]["serve_ttft_s"]))
        assert mhist.count == single.count
        for q in (0.5, 0.9, 0.99):
            assert mhist.quantile(q) == single.quantile(q)

    def test_single_stream_oracle_helper(self):
        vals = [0.01, 0.02, 0.5, 0.5, 1.7]
        h = single_stream_oracle(vals)
        ref = Histogram()
        for v in vals:
            ref.observe(v)
        assert h.summary() == ref.summary()


# ------------------------------------------------------------------ #
# routing-map lock discipline (dslint DSL007 fix, ISSUE 19)
# ------------------------------------------------------------------ #


class TestRouteLockDiscipline:
    """The pool's routing maps (_owner/_trace_ids/_trace_n/_replayed)
    are written from the admit, absorb and decode-driver threads; the
    _route_lock critical sections added for the DSL007 findings must
    hold under a real interleaving hammer, and the serving layer must
    stay statically race-free."""

    def _pool(self):
        return ReplicaPool()

    def test_concurrent_trace_mint_never_drops_a_count(self):
        import sys
        import threading
        pool = self._pool()
        nthreads, per = 8, 200
        start = threading.Barrier(nthreads)

        def hammer(base):
            start.wait()
            for i in range(per):
                pool._mint_trace(base + i)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)   # force interleaving
        try:
            threads = [threading.Thread(target=hammer, args=(t * per,))
                       for t in range(nthreads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            sys.setswitchinterval(old)
        # an unlocked `self._trace_n += 1` loses increments under this
        # hammer; the lock makes the counter exact and every id unique
        assert pool._trace_n == nthreads * per
        assert len(pool._trace_ids) == nthreads * per
        assert len(set(pool._trace_ids.values())) == nthreads * per

    def test_stash_vs_take_never_loses_a_token(self):
        import sys
        import threading
        pool = self._pool()
        uid, total = 7, 2000
        out = {uid: []}
        taken = []
        done = threading.Event()

        def stasher():
            for tok in range(total):
                pool._stash_replay(uid, tok)
            done.set()

        def taker():
            # splice in small budgets while the stasher is appending —
            # the pre-fix setdefault().append() raced the pop/reinsert
            # window and lost tokens
            while not done.is_set() or pool._replayed.get(uid):
                taken.append(pool._take_stash(uid, 3, out))

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            ts = [threading.Thread(target=stasher),
                  threading.Thread(target=taker)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        finally:
            sys.setswitchinterval(old)
        leftover = pool._replayed.get(uid, [])
        assert sorted(out[uid] + leftover) == list(range(total))
        assert sum(taken) == len(out[uid])

    def test_serving_layer_lints_race_free(self):
        import os
        import sys
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        sys.path.insert(0, os.path.join(repo, "tools"))
        try:
            import dslint
        finally:
            sys.path.pop(0)
        findings = [f for f in dslint.lint([], repo_root=repo,
                                           knob_rules=False)
                    if f.rule == "DSL007"]
        assert findings == [], "\n".join(str(f) for f in findings)


# ------------------------------------------------------------------ #
# heavier fleets — slow tier
# ------------------------------------------------------------------ #


@pytest.mark.slow
class TestFleetSlow:
    def test_four_replicas_two_sequential_drains(self):
        import numpy as np
        gen = 6
        rng = np.random.default_rng(33)
        shared = [rng.integers(1, 96, 16).tolist() for _ in range(3)]
        prompts = {u: shared[u % 3] + rng.integers(1, 96, 6).tolist()
                   for u in range(10)}

        def drive(pool, kills=()):
            toks = {}
            out = pool.put(list(prompts),
                           [prompts[u] for u in prompts], _greedy=True)
            for u in prompts:
                toks[u] = [int(out[u])]
            rounds = 0
            while True:
                live = [u for u in toks if len(toks[u]) < gen
                        and u in pool.state.sequences]
                if not live:
                    break
                for at, rid in kills:
                    if rounds == at:
                        pool.replica(rid).engine.request_drain()
                outs = pool.decode_pipelined(
                    live, [toks[u][-1] for u in live], 2)
                for u in live:
                    toks[u].extend(outs[u][:gen - len(toks[u])])
                rounds += 1
            for u in toks:
                pool.flush(u)
            return toks

        oracle = drive(_mk_pool(1)[0])
        pool, _ = _mk_pool(4)
        got = drive(pool, kills=((1, "r0"), (2, "r2")))
        assert got == oracle
        dead = [r for r in pool.replicas() if r.state == "dead"]
        assert {r.replica_id for r in dead} == {"r0", "r2"}
        assert all(r.manifest["pool"]["fully_recovered"] for r in dead)
        assert pool.serving_count == 2

    def test_fleet_faultdrill_subprocess(self, tmp_path):
        # the CI drill end-to-end: real SIGTERM, busiest-replica victim,
        # rollup exactness, late joiner — in a fresh process
        from deepspeed_tpu.resilience.faultdrill import drill_fleet
        result = drill_fleet(str(tmp_path))
        assert result["recovered"] is True
        assert result["rollup_quantiles_exact"] is True
        assert result["joiner_requests"] >= 1
