"""Prefix cache (ISSUE 5): the content-addressed refcounted block index
(`inference/v2/prefix_cache.py`) and its allocator/state-manager seams.

The centerpiece is the randomized stress test: interleaved
alloc/match/share/decref/evict/trim against a reference-counting model
checker — no double free (the allocator now detects it exactly), no freed
block aliasing into a live block table, and full capacity recovery at
drain. This covers the PR 3 interplay where the pipelined EOS rollback's
deferred ``trim_blocks`` must decref shared blocks instead of freeing
them."""

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (
    BlockedAllocator,
    BlockedKVCache,
    PrefixCache,
    RaggedInferenceConfig,
    StateManager,
)
from deepspeed_tpu.inference.v2.blocked_allocator import OutOfBlocksError


class TestAllocatorGuards:
    def test_double_free_detected_exactly(self):
        a = BlockedAllocator(8)
        blocks = a.allocate(3)
        a.free(blocks[:1])
        with pytest.raises(RuntimeError, match="double free of block"):
            a.free(blocks[:1])
        # the failed free must not have corrupted the free list
        assert a.free_blocks == 6

    def test_partial_double_free_rolls_nothing_in(self):
        a = BlockedAllocator(4)
        b = a.allocate(2)
        a.free([b[0]])
        with pytest.raises(RuntimeError):
            a.free([b[0], b[1]])       # first id already free
        assert a.free_blocks == 3      # b[1] NOT silently freed

    def test_same_call_duplicate_detected(self):
        a = BlockedAllocator(8)
        b = a.allocate(1)[0]
        # the duplicate is WITHIN one call: neither copy is in the free
        # set when checked, so only a same-call guard catches it (a miss
        # would hand block b to two later allocate() calls)
        with pytest.raises(RuntimeError, match="double free"):
            a.free([b, b])
        assert a.free_blocks == 7      # nothing rolled in


class TestPrefixCacheIndex:
    def _pc(self, bs=4, **kw):
        return PrefixCache(bs, **kw)

    def test_identity_includes_parent_chain(self):
        pc = self._pc()
        a = pc.insert(None, (1, 2, 3, 4), 0)
        b = pc.insert(a, (9, 9, 9, 9), 1)
        # the SAME tokens under a different prefix are a different block
        c = pc.insert(None, (9, 9, 9, 9), 2)
        assert b is not None and c is not None and b is not c
        ents, cow, n = pc.match([1, 2, 3, 4, 9, 9, 9, 9, 5])
        assert [e.block for e in ents] == [0, 1]
        ents2, _, _ = pc.match([9, 9, 9, 9, 5])
        assert [e.block for e in ents2] == [2]

    def test_match_leaves_last_token(self):
        pc = self._pc()
        a = pc.insert(None, (1, 2, 3, 4), 0)
        pc.insert(a, (5, 6, 7, 8), 1)
        # the whole query is cached — the match must still leave >= 1
        # token for the engine's final chunk (last-token logits)
        ents, cow, n = pc.match([1, 2, 3, 4, 5, 6, 7, 8])
        assert [e.block for e in ents] == [0]
        assert cow is not None and cow.block == 1 and n == 3

    def test_cow_longest_agreeing_child(self):
        pc = self._pc()
        root = pc.insert(None, (1, 2, 3, 4), 0)
        pc.insert(root, (5, 6, 0, 0), 1)
        pc.insert(root, (5, 6, 7, 0), 2)
        ents, cow, n = pc.match([1, 2, 3, 4, 5, 6, 7, 9, 9])
        assert [e.block for e in ents] == [0]
        assert cow.block == 2 and n == 3

    def test_eviction_leaf_first_lru(self):
        pc = self._pc()
        a = pc.insert(None, (1,) * 4, 0)
        b = pc.insert(a, (2,) * 4, 1)
        c = pc.insert(None, (3,) * 4, 2)
        for e in (a, b, c):
            pc.release_block(e.block)      # refs 1 -> 0, in insert order
        # a has a cached child: only b and c are leaf-evictable; b was
        # released before c -> LRU takes b; that makes a a leaf, and a
        # (released before c) goes next, then c
        assert pc.evict(1) == [1]
        assert pc.evict(2) == [0, 2]
        assert pc.cached_blocks == 0

    def test_refcounted_blocks_not_evictable(self):
        pc = self._pc()
        a = pc.insert(None, (1,) * 4, 0)
        pc.acquire(a)                      # a matcher holds it
        pc.release_block(0)                # registering seq lets go
        assert pc.evictable_blocks == 0 and pc.evict(4) == []
        pc.release_block(0)
        assert pc.evictable_blocks == 1

    def test_refcount_underflow_raises(self):
        pc = self._pc()
        pc.insert(None, (1,) * 4, 0)
        pc.release_block(0)
        with pytest.raises(RuntimeError, match="underflow"):
            pc.release_block(0)

    def test_insert_duplicate_not_adopted(self):
        pc = self._pc()
        assert pc.insert(None, (1,) * 4, 0) is not None
        assert pc.insert(None, (1,) * 4, 5) is None
        assert pc.cached_blocks == 1

    def test_max_blocks_cap_evicts_or_skips(self):
        pc = self._pc(max_blocks=2)
        a = pc.insert(None, (1,) * 4, 0)
        b = pc.insert(None, (2,) * 4, 1)
        # everything referenced: cap reached, insert skipped
        assert pc.insert(None, (3,) * 4, 2) is None
        pc.release_block(0)
        # a is cold now: the capped insert evicts it and adopts
        e = pc.insert(None, (4,) * 4, 3)
        assert e is not None
        assert pc.collect_pending_free() == [0]
        assert pc.cached_blocks == 2

    def test_fifo_policy_orders_by_insertion(self):
        pc = self._pc(policy="fifo")
        pc.insert(None, (1,) * 4, 0)
        pc.insert(None, (2,) * 4, 1)
        pc.release_block(1)                # released FIRST
        pc.release_block(0)
        assert pc.evict(1) == [0]          # but 0 was inserted first


class TestBatchedPutRegistration:
    def test_no_graft_under_foreign_chain(self):
        """Batched put() race: two fresh prompts sharing a prefix both
        match (empty cache) BEFORE either registers. The first writer
        owns the chain; the second's copies stay private — it must NOT
        graft its extra full block under the foreign chain, which would
        let the chain's ancestors hit refcount 0 while a referenced
        child stays cached (breaking refs(parent) >= refs(child) and
        overcounting evictable capacity)."""
        import jax.numpy as jnp
        bs = 4
        cfg = RaggedInferenceConfig(
            max_seqs=4, chunk_size=8, block_size=bs, num_blocks=16,
            max_blocks_per_seq=8, dtype="float32", prefix_cache=True)
        kv = BlockedKVCache(cfg, 1, 1, 4, jnp.float32)
        pc = PrefixCache(bs)
        kv.attach_prefix_cache(pc)
        sm = StateManager(cfg, kv)
        sm.prefix = pc
        shared = [1, 2, 3, 4, 5, 6, 7, 8]
        s0 = sm.put_tokens(0, shared + [9])                    # 2 full blocks
        s1 = sm.put_tokens(1, shared + [10, 11, 12, 13, 14])   # 3 full blocks
        sm.match_prefix(s0)
        sm.match_prefix(s1)            # nothing cached yet: both miss
        for s in (s0, s1):
            n = s.in_flight
            sm.ensure_blocks(s, n)
            del s.pending_tokens[:n]
            s.seen_tokens += n
        sm.register_prefix(s0)         # first writer wins the shared chain
        sm.register_prefix(s1)
        pc.check_invariants()
        sm.flush(0)                    # chain goes cold; must ALL be
        pc.check_invariants()          # evictable — no stranded child
        assert pc.evictable_blocks == pc.cached_blocks == 2
        sm.flush(1)
        kv.allocator.free(pc.evict(16))
        assert pc.cached_blocks == 0
        assert kv.allocator.free_blocks == 16

    def test_rejected_spec_run_on_shared_chain_decrefs_once(self):
        """The ISSUE-12 rollback exactness case: two sequences share a
        cached prefix chain; one runs a speculative verify window that
        is mostly REJECTED. The multi-token trim must release only the
        over-allocated private blocks and decref nothing it does not
        own — the shared chain's refcounts stay exact (one per
        referencing sequence) and no double free is possible."""
        import jax.numpy as jnp
        bs = 4
        cfg = RaggedInferenceConfig(
            max_seqs=4, chunk_size=8, block_size=bs, num_blocks=16,
            max_blocks_per_seq=8, dtype="float32", prefix_cache=True)
        kv = BlockedKVCache(cfg, 1, 1, 4, jnp.float32)
        pc = PrefixCache(bs)
        kv.attach_prefix_cache(pc)
        sm = StateManager(cfg, kv)
        sm.prefix = pc
        shared = [1, 2, 3, 4, 5, 6, 7, 8]
        s0 = sm.put_tokens(0, shared + [9])
        sm.match_prefix(s0)
        n = s0.in_flight
        sm.ensure_blocks(s0, n)
        del s0.pending_tokens[:n]
        s0.seen_tokens += n
        sm.register_prefix(s0)
        s1 = sm.put_tokens(1, shared + [10])
        sm.match_prefix(s1)               # hits the registered chain
        assert len(s1.shared) == 2
        for e in pc._by_block.values():
            assert e.refs == 2            # both sequences on the chain
        n = s1.in_flight
        sm.ensure_blocks(s1, n)
        del s1.pending_tokens[:n]
        s1.seen_tokens += n
        # speculative verify window: K+1 = 6 positions appended, only 1
        # accepted -> trim retracts 5, freeing the over-allocation
        free0 = kv.allocator.free_blocks
        sm.ensure_blocks(s1, 6)
        seen0 = s1.seen_tokens
        s1.seen_tokens = seen0 + 6
        s1.seen_tokens = seen0 + 1        # host accepted 1 token
        freed = sm.trim_blocks(s1)
        assert freed >= 1
        assert kv.allocator.free_blocks == free0
        pc.check_invariants()
        pc.assert_exact_refs([s0, s1])    # chain refs STILL exactly 2
        for e in pc._by_block.values():
            assert e.refs == 2
        # a second trim at the same seen is a no-op (nothing left over)
        assert sm.trim_blocks(s1) == 0
        sm.flush(0)
        sm.flush(1)
        pc.assert_exact_refs([])
        kv.allocator.free(pc.evict(16))
        assert kv.allocator.free_blocks == 16


def _hier_fixture(bs=4, num_blocks=8, host_blocks=16, policy="lru",
                  dtype=None):
    """A BlockedKVCache + two-tier PrefixCache + StateManager wired the
    way the engine wires them (pool source attached so reserve pressure
    demotes instead of destroying)."""
    import jax.numpy as jnp
    cfg = RaggedInferenceConfig(
        max_seqs=4, chunk_size=8, block_size=bs, num_blocks=num_blocks,
        max_blocks_per_seq=8, dtype="float32", prefix_cache=True,
        kv_cache_dtype="int8" if dtype == "int8" else "auto",
        attention_impl="dense",
        prefix_cache_host_blocks=host_blocks)
    kv = BlockedKVCache(cfg, 1, 1, 4,
                        None if dtype == "int8" else jnp.float32)
    pc = PrefixCache(bs, host_blocks=host_blocks, policy=policy)
    kv.attach_prefix_cache(pc)
    box = {"pool": kv.pool}
    kv.attach_pool_source(lambda: box["pool"])
    sm = StateManager(cfg, kv)
    sm.prefix = pc
    return cfg, kv, pc, sm, box


def _prefill(sm, seq):
    """Run a sequence's remaining prefill as pure bookkeeping (the
    stress/unit tests never dispatch compute)."""
    n = seq.in_flight
    sm.ensure_blocks(seq, n)
    del seq.pending_tokens[:n]
    seq.seen_tokens += n


class TestHostTierIndex:
    """Hierarchical KV at the cache/kv-cache seam: demotion under
    reserve pressure, promotion on a match, host-cap eviction, and the
    evicted_cap/evicted_pressure churn split."""

    def test_pressure_demotes_instead_of_destroying(self):
        cfg, kv, pc, sm, box = _hier_fixture()
        s0 = sm.put_tokens(0, [1, 2, 3, 4, 5, 6, 7, 8, 9])
        sm.match_prefix(s0)
        _prefill(sm, s0)
        sm.register_prefix(s0)
        sm.flush(0)                      # chain cold: 2 refcount-0 blocks
        assert pc.cached_blocks == 2 and pc.evictable_blocks == 2
        # demand the whole pool: the cold chain must move to the host
        # tier, not die
        blocks = kv.reserve(cfg.num_blocks)
        assert len(blocks) == cfg.num_blocks
        assert pc.cached_blocks == 0 and pc.host_cached_blocks == 2
        assert pc.stats["demoted"] == 2
        assert pc.stats["evicted"] == 0 == pc.stats["evicted_pressure"]
        kv.free(blocks)
        # the chain is STILL matchable — a later identical prompt
        # promotes it back through fresh device blocks
        s1 = sm.put_tokens(1, [1, 2, 3, 4, 5, 6, 7, 8, 9])
        plan = sm.match_prefix(s1)
        assert len(plan.promotes) == 2 and not plan.copies
        assert s1.seen_tokens == 8 and len(s1.shared) == 2
        assert pc.stats["promoted"] == 2
        assert pc.stats["host_hit_blocks"] == 2
        assert pc.host_cached_blocks == 0 and pc.cached_blocks == 2
        pc.check_invariants()
        pc.assert_exact_refs([s1])

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_promotion_restores_exact_content(self, kv_dtype):
        """The data-path half: rows written before demotion come back
        bit-identical after the demote gather -> host -> promote scatter
        round trip (bf16/float rows AND int8 payloads + scale planes)."""
        import numpy as np

        import jax.numpy as jnp
        from deepspeed_tpu.inference.v2.kv_quant import pool_parts
        cfg, kv, pc, sm, box = _hier_fixture(dtype=kv_dtype)
        bs = cfg.block_size
        s0 = sm.put_tokens(0, [1, 2, 3, 4, 5])
        sm.match_prefix(s0)
        _prefill(sm, s0)
        blk = s0.kv_blocks[0]
        # stamp recognizable KV content into the block's rows
        data, scales = pool_parts(box["pool"])
        rows = np.arange(bs * 4, dtype=np.float32).reshape(bs, 4)
        sl = slice(blk * bs, (blk + 1) * bs)
        if scales is not None:
            data = data.at[:, :, sl].set(
                jnp.asarray(rows % 127, jnp.int8))
            scales = scales.at[:, :, :, sl].set(0.5)
            from deepspeed_tpu.inference.v2.kv_quant import KVPool
            box["pool"] = KVPool(data, scales)
        else:
            data = data.at[:, :, sl].set(jnp.asarray(rows))
            box["pool"] = data
        want_rows = np.asarray(pool_parts(box["pool"])[0][:, :, sl])
        sm.register_prefix(s0)
        sm.flush(0)
        held = kv.reserve(cfg.num_blocks)       # force the demotion
        assert pc.host_cached_blocks >= 1
        kv.finalize_demotions()                 # D2H materialize path
        kv.free(held)
        s1 = sm.put_tokens(1, [1, 2, 3, 4, 5])
        plan = sm.match_prefix(s1)
        assert len(plan.promotes) == 1
        buf, dst = plan.promotes[0]
        box["pool"] = kv.promote_block(box["pool"], buf, dst)
        got_data, got_scales = pool_parts(box["pool"])
        got = np.asarray(got_data[:, :, dst * bs:(dst + 1) * bs])
        assert np.array_equal(got, want_rows)
        if got_scales is not None:
            assert np.all(np.asarray(
                got_scales[:, :, :, dst * bs:(dst + 1) * bs]) == 0.5)

    def test_pending_device_promotion_no_materialize(self):
        """A chain matched BEFORE the demotion gather materializes is
        promoted straight off the in-flight device slice — the zero-
        host-round-trip fast path."""
        cfg, kv, pc, sm, box = _hier_fixture()
        s0 = sm.put_tokens(0, [1, 2, 3, 4, 5])
        sm.match_prefix(s0)
        _prefill(sm, s0)
        sm.register_prefix(s0)
        sm.flush(0)
        held = kv.reserve(cfg.num_blocks)
        kv.free(held)
        assert kv._pending_host                # gather NOT materialized
        s1 = sm.put_tokens(1, [1, 2, 3, 4, 5])
        plan = sm.match_prefix(s1)
        assert len(plan.promotes) == 1
        buf, dst = plan.promotes[0]
        box["pool"] = kv.promote_block(box["pool"], buf, dst)
        pc.check_invariants()

    def test_host_cap_evicts_lru_leaf_first(self):
        cfg, kv, pc, sm, box = _hier_fixture(num_blocks=16, host_blocks=2)
        # three independent cold chains of 2 blocks, released in order
        for uid, base in ((0, 10), (1, 20), (2, 30)):
            s = sm.put_tokens(uid, [base + i for i in range(9)])
            sm.match_prefix(s)
            _prefill(sm, s)
            sm.register_prefix(s)
        for uid in (0, 1, 2):
            sm.flush(uid)
        held = kv.reserve(cfg.num_blocks)       # demote all 6
        kv.free(held)
        # cap 2: only the two COLDEST-demoted survive... demotion is
        # leaf-first LRU over release stamps, so the survivors are the
        # newest demotions and 4 were destroyed for real
        assert pc.host_cached_blocks == 2
        assert pc.stats["demoted"] == 6
        assert pc.stats["host_evicted"] == 4
        pc.check_invariants()

    def test_fifo_host_parent_repush_after_child_leaves(self):
        """FIFO host ranks order parents BEFORE their children (born
        first); the cap sweep must skip-and-repush so a parent is
        destroyed only after its last host child."""
        cfg, kv, pc, sm, box = _hier_fixture(num_blocks=16,
                                             host_blocks=3,
                                             policy="fifo")
        s = sm.put_tokens(0, [i + 1 for i in range(13)])   # 3-block chain
        sm.match_prefix(s)
        _prefill(sm, s)
        sm.register_prefix(s)
        sm.flush(0)
        held = kv.reserve(cfg.num_blocks)
        kv.free(held)
        assert pc.host_cached_blocks == 3
        # shrink the cap by demoting more: a fresh 2-block chain
        s2 = sm.put_tokens(1, [100 + i for i in range(9)])
        sm.match_prefix(s2)
        _prefill(sm, s2)
        sm.register_prefix(s2)
        sm.flush(1)
        held = kv.reserve(cfg.num_blocks)
        kv.free(held)
        # 5 host-resident, cap 3 -> 2 destroyed; the structural
        # invariants (host children only under host parents, heap
        # coverage) are the real assertion here
        assert pc.host_cached_blocks == 3
        pc.check_invariants()

    def test_cow_killed_mid_promotion_is_skipped(self):
        """Review regression: the promotion loop's own reserves can
        host-cap-evict the (host-tier) CoW candidate the match walk
        returned — the cow branch must re-read the tier and SKIP a dead
        entry instead of acquiring it (which crashed the serve path)."""
        cfg, kv, pc, sm, box = _hier_fixture(num_blocks=8)
        s0 = sm.put_tokens(0, [1, 2, 3, 4, 5, 6, 7, 8, 9])
        sm.match_prefix(s0)
        _prefill(sm, s0)
        sm.register_prefix(s0)
        sm.flush(0)
        held = kv.reserve(cfg.num_blocks)       # demote the whole chain
        kv.free(held)
        assert pc.host_cached_blocks == 2
        # s1 fully matches the root block; the second chain link is the
        # longest-agreeing COW candidate for tokens [5, 6, 7]
        real_reserve = kv.reserve
        cow_entry = next(e for r in pc._roots.values()
                         for e in r.children.values())

        def reserve_killing_cow(n):
            out = real_reserve(n)
            if cow_entry.tier == "host":
                # simulate the host-cap sweep claiming the cow while
                # this reserve's demotions overflowed the tier
                pc._unlink(cow_entry)
                cow_entry.tier = "dead"
                pc._drop_host_ref(cow_entry)
                pc._host_count -= 1
                pc.stats["host_evicted"] += 1
            return out

        kv.reserve = reserve_killing_cow
        s1 = sm.put_tokens(1, [1, 2, 3, 4, 5, 6, 7, 10, 11])
        plan = sm.match_prefix(s1)              # must not raise
        kv.reserve = real_reserve
        assert plan.promoted_blocks == 1        # the root block promoted
        assert s1.seen_tokens == 4              # cow span NOT matched
        pc.check_invariants()
        pc.assert_exact_refs([s1])

    def test_acquire_on_host_entry_raises(self):
        cfg, kv, pc, sm, box = _hier_fixture()
        s0 = sm.put_tokens(0, [1, 2, 3, 4, 5])
        sm.match_prefix(s0)
        _prefill(sm, s0)
        sm.register_prefix(s0)
        sm.flush(0)
        held = kv.reserve(cfg.num_blocks)
        kv.free(held)
        entry = next(iter(pc._roots.values()))
        assert entry.tier == "host"
        with pytest.raises(RuntimeError, match="promote it first"):
            pc.acquire(entry)

    def test_churn_split_tier_off(self):
        """The ISSUE-13 bugfix: cap-pressure inserts and reserve-
        pressure evictions are separately attributable (they used to
        conflate into one 'evicted' count)."""
        pc = PrefixCache(4, max_blocks=2)
        pc.insert(None, (1,) * 4, 0)
        pc.insert(None, (2,) * 4, 1)
        pc.release_block(0)
        pc.release_block(1)
        # cap-pressure: the third insert evicts one cold block
        assert pc.insert(None, (3,) * 4, 2) is not None
        assert pc.stats["evicted_cap"] == 1
        assert pc.stats["evicted_pressure"] == 0
        # reserve-pressure: an explicit evict() call (what
        # BlockedKVCache.reserve does tier-off)
        pc.release_block(2)
        assert len(pc.evict(1)) == 1
        assert pc.stats["evicted_pressure"] == 1
        assert pc.stats["evicted_cap"] == 1
        assert pc.stats["evicted"] == 2         # back-compat total


class TestRandomizedRefcountModel:
    """The satellite model checker: random interleavings of the full
    block lifecycle against a shadow ownership model."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_stress_no_double_free_no_aliasing_full_drain(self, seed):
        import jax.numpy as jnp
        rng = np.random.default_rng(seed)
        bs, num_blocks = 4, 48
        cfg = RaggedInferenceConfig(
            max_seqs=4, chunk_size=8, block_size=bs, num_blocks=num_blocks,
            max_blocks_per_seq=8, dtype="float32", prefix_cache=True)
        kv = BlockedKVCache(cfg, 1, 1, 4, jnp.float32)
        pc = PrefixCache(bs, policy=rng.choice(["lru", "fifo"]))
        kv.attach_prefix_cache(pc)
        sm = StateManager(cfg, kv)
        sm.prefix = pc

        # a small prompt alphabet so random prompts actually collide
        vocab, next_uid = 3, [0]
        live = {}

        def new_seq():
            uid = next_uid[0]
            next_uid[0] += 1
            n = int(rng.integers(2, 29))
            toks = rng.integers(0, vocab, n).tolist()
            try:
                seq = sm.put_tokens(uid, toks)
            except ValueError:
                return
            sm.match_prefix(seq)       # copies would be device work: the
            #                            stress checks bookkeeping only
            # prefill the rest in random chunk sizes
            while seq.in_flight:
                c = int(rng.integers(1, 9))
                c = min(c, seq.in_flight)
                try:
                    sm.ensure_blocks(seq, c)
                except OutOfBlocksError:
                    if not live:        # nothing to victimize: drop it
                        sm.flush(uid)
                        return
                    # evict pressure path exercised; give up on this seq
                    sm.flush(uid)
                    return
                del seq.pending_tokens[:c]
                seq.seen_tokens += c
            sm.register_prefix(seq)
            live[uid] = seq

        def decode_some(uid):
            seq = live[uid]
            n = int(rng.integers(1, 9))
            try:
                sm.ensure_blocks(seq, n)
            except OutOfBlocksError:
                return
            seq.seen_tokens += n

        def trim(uid):
            seq = live[uid]
            # retract a random speculative overrun (never into the prompt)
            prompt = seq.prompt_len
            if seq.seen_tokens > prompt:
                seq.seen_tokens -= int(
                    rng.integers(0, seq.seen_tokens - prompt + 1))
            sm.trim_blocks(seq)

        def spec_round(uid):
            # the decode_spec lifecycle as one op: allocate KV for a
            # pinned K+1-token verify window, then commit only the
            # accepted prefix and trim the rest — a rejected run on a
            # shared-prefix chain must decref each released shared
            # block exactly once (the conservation + refcount-drift
            # asserts in check() are the oracle)
            seq = live[uid]
            L = int(rng.integers(2, 8))
            try:
                sm.ensure_blocks(seq, L)
            except OutOfBlocksError:
                return
            seen0 = seq.seen_tokens
            seq.seen_tokens = seen0 + L          # verify wrote L slots
            accepted = int(rng.integers(1, L + 1))
            seq.seen_tokens = seen0 + accepted   # host accepts a prefix
            sm.trim_blocks(seq)

        def check():
            alloc = kv.allocator
            free = set(alloc.free_list())
            assert len(free) == alloc.free_blocks          # list == set
            pc.check_invariants()
            pc.assert_exact_refs(live.values())
            cached = set(pc._by_block)
            assert not free & cached, "freed block still cached"
            refs = {}
            for seq in live.values():
                tabs = set(seq.kv_blocks)
                assert len(tabs) == len(seq.kv_blocks), \
                    "block repeated in one table"
                assert not any(alloc.is_free(b) for b in tabs), \
                    "freed block aliased into a live block table"
                for b in seq.kv_blocks:
                    if b in seq.shared:
                        assert b in cached, "shared block not cached"
                        refs[b] = refs.get(b, 0) + 1
                    else:
                        # a private block is owned by exactly one table
                        assert refs.setdefault(b, "private") == "private"
            for b, n in refs.items():
                if n != "private":
                    assert pc.entry_of(b).refs == n, \
                        f"refcount drift on block {b}"
            # conservation: every block is free, cached, or exactly one
            # sequence's private block
            private = {b for s in live.values() for b in s.kv_blocks
                       if b not in s.shared}
            assert len(free) + len(cached) + len(private) == num_blocks

        for _ in range(300):
            op = rng.integers(0, 5)
            if op == 0 or not live:
                new_seq()
            elif op == 1:
                decode_some(int(rng.choice(list(live))))
            elif op == 2:
                trim(int(rng.choice(list(live))))
            elif op == 3:
                spec_round(int(rng.choice(list(live))))
            else:
                uid = int(rng.choice(list(live)))
                sm.flush(uid)
                del live[uid]
            check()

        # drain: flush everything, then evict the whole cache — the
        # allocator must recover FULL capacity
        for uid in list(live):
            sm.flush(uid)
        live.clear()
        check()
        kv.allocator.free(pc.evict(num_blocks))
        assert pc.cached_blocks == 0
        assert kv.allocator.free_blocks == num_blocks

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_stress_hierarchical_two_tier(self, seed):
        """The ISSUE-13 extension: the same shadow-model stress with the
        HOST TIER armed — random interleavings now include reserve-
        pressure demotion (through the real ``BlockedKVCache.reserve``
        path), promotion on re-match, host-cap eviction and the
        pending-gather materialize, on top of the existing alloc/match/
        decref/trim/spec lifecycle. Oracles: ``check_invariants`` (tier
        ordering, dev_kids, host cap, heap coverage),
        ``assert_exact_refs`` across BOTH tiers, block conservation, no
        freed-block aliasing, and full allocator recovery at drain."""
        import jax.numpy as jnp
        rng = np.random.default_rng(seed)
        bs, num_blocks, host_cap = 4, 24, 10
        cfg = RaggedInferenceConfig(
            max_seqs=4, chunk_size=8, block_size=bs,
            num_blocks=num_blocks, max_blocks_per_seq=8,
            dtype="float32", prefix_cache=True,
            prefix_cache_host_blocks=host_cap)
        kv = BlockedKVCache(cfg, 1, 1, 4, jnp.float32)
        pc = PrefixCache(bs, policy=rng.choice(["lru", "fifo"]),
                         host_blocks=host_cap)
        kv.attach_prefix_cache(pc)
        box = {"pool": kv.pool}
        kv.attach_pool_source(lambda: box["pool"])
        sm = StateManager(cfg, kv)
        sm.prefix = pc

        vocab, next_uid = 3, [0]
        live = {}

        def dispatch_plan(plan):
            # the engine's half of a match: promote scatters + CoW
            # copies ride the functional pool thread
            for buf, dst in plan.promotes:
                box["pool"] = kv.promote_block(box["pool"], buf, dst)
            for src, dst in plan.copies:
                box["pool"] = kv.copy_block(box["pool"], src, dst)

        def new_seq():
            uid = next_uid[0]
            next_uid[0] += 1
            n = int(rng.integers(2, 21))
            toks = rng.integers(0, vocab, n).tolist()
            try:
                seq = sm.put_tokens(uid, toks)
            except ValueError:
                return
            dispatch_plan(sm.match_prefix(seq))
            while seq.in_flight:
                c = min(int(rng.integers(1, 9)), seq.in_flight)
                try:
                    sm.ensure_blocks(seq, c)
                except OutOfBlocksError:
                    sm.flush(uid)
                    return
                del seq.pending_tokens[:c]
                seq.seen_tokens += c
            sm.register_prefix(seq)
            live[uid] = seq

        def pressure(uid=None):
            # reserve-then-free a random slab: drives the REAL demote
            # path (batched gather dispatch, host-cap sweep) without
            # retaining blocks
            want = int(rng.integers(1, num_blocks))
            try:
                held = kv.reserve(want)
            except OutOfBlocksError:
                return
            kv.free(held)

        def spec_round(uid):
            seq = live[uid]
            L = int(rng.integers(2, 8))
            try:
                sm.ensure_blocks(seq, L)
            except OutOfBlocksError:
                return
            seen0 = seq.seen_tokens
            seq.seen_tokens = seen0 + int(rng.integers(1, L + 1))
            sm.trim_blocks(seq)

        def materialize():
            kv.finalize_demotions()

        def check():
            alloc = kv.allocator
            free = set(alloc.free_list())
            assert len(free) == alloc.free_blocks
            pc.check_invariants()
            pc.assert_exact_refs(live.values())
            cached = set(pc._by_block)
            assert not free & cached, "freed block still cached"
            for seq in live.values():
                tabs = set(seq.kv_blocks)
                assert len(tabs) == len(seq.kv_blocks)
                assert not any(alloc.is_free(b) for b in tabs), \
                    "freed block aliased into a live block table"
            private = {b for s in live.values() for b in s.kv_blocks
                       if b not in s.shared}
            # conservation over DEVICE blocks: host-tier entries own no
            # pool block, so the partition is free/cached/private alone
            assert len(free) + len(cached) + len(private) == num_blocks
            assert pc.host_cached_blocks <= host_cap

        for _ in range(300):
            op = rng.integers(0, 6)
            if op == 0 or not live:
                new_seq()
            elif op == 1:
                pressure()
            elif op == 2:
                spec_round(int(rng.choice(list(live))))
            elif op == 3:
                materialize()
            elif op == 4:
                uid = int(rng.choice(list(live)))
                sm.flush(uid)
                del live[uid]
            else:
                # decode growth
                seq = live[int(rng.choice(list(live)))]
                try:
                    sm.ensure_blocks(seq, int(rng.integers(1, 9)))
                except OutOfBlocksError:
                    pass
                else:
                    seq.seen_tokens += 0   # blocks reserved ahead only
                    sm.trim_blocks(seq)
            check()

        for uid in list(live):
            sm.flush(uid)
        live.clear()
        check()
        # drain: destroy-evict the device tier (host descendants die
        # with their chains) — FULL allocator recovery, empty tiers
        kv.allocator.free(pc.evict(num_blocks))
        assert pc.cached_blocks == 0
        assert kv.allocator.free_blocks == num_blocks


class TestHierKVServing:
    """Hierarchical KV end-to-end through the v2 engine: the tier must
    be token-INVISIBLE (streams identical tier on / tier off / cache
    off) while actually demoting and promoting, survive drain->replay
    with tier-resident chains, and compose with the pipelined +
    speculative serve paths."""

    def _engine(self, mcfg, params, **kw):
        from deepspeed_tpu.inference.v2 import InferenceEngineV2
        base = dict(max_seqs=4, chunk_size=16, block_size=8,
                    num_blocks=10, max_blocks_per_seq=8,
                    dtype="float32", attention_impl="dense",
                    decode_loop_steps=0, serve_pipeline_depth=2)
        base.update(kw)
        return InferenceEngineV2(mcfg, params,
                                 RaggedInferenceConfig(**base))

    def _model(self):
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
        mcfg = GPT2Config(vocab_size=96, max_seq_len=256, num_layers=2,
                          num_heads=2, hidden_size=32,
                          dtype=jnp.float32)
        params = GPT2(mcfg).init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
        return mcfg, params

    def _workload(self, groups=6, rounds=3, tail=5, pre=24, seed=0):
        # a shared-prefix working set larger than the 10-block pool:
        # `groups` preambles of 3 blocks each, revisited cyclically —
        # tier-off destroys exactly the chain the next revisit needs
        rng = np.random.RandomState(seed)
        pres = [rng.randint(1, 96, size=pre).tolist()
                for _ in range(groups)]
        return [(i, pres[i % groups]
                 + rng.randint(1, 96, size=tail).tolist())
                for i in range(rounds * groups)]

    def _run(self, eng, reqs, gen=6):
        out = {}
        for uid, p in reqs:
            first = eng.put([uid], [p], _greedy=True)
            toks = eng.decode_pipelined([uid], [first[uid]], gen)
            out[uid] = [first[uid]] + toks[uid]
            eng.flush(uid)
            if eng._prefix is not None:
                eng._prefix.check_invariants()
                eng._prefix.assert_exact_refs(
                    eng.state.sequences.values())
        return out

    def test_tier_token_parity_and_hits(self):
        mcfg, params = self._model()
        reqs = self._workload()
        off = self._run(self._engine(mcfg, params, prefix_cache=False),
                        reqs)
        dev_eng = self._engine(mcfg, params, prefix_cache=True)
        dev = self._run(dev_eng, reqs)
        hier_eng = self._engine(mcfg, params, prefix_cache=True,
                                prefix_cache_host_blocks=64)
        hier = self._run(hier_eng, reqs)
        assert dev == off
        assert hier == off
        st = hier_eng.prefix_stats
        # the tier genuinely worked: demotions happened, revisits were
        # served by promotion, and the skipped-prefill fraction beat
        # the destroy-on-pressure cache on the SAME workload
        assert st["demoted"] > 0 and st["promoted"] > 0
        assert st["host_hit_blocks"] > 0
        assert st["host_matched_tokens"] > 0
        assert st["prefill_chunks_skipped_frac"] > 0.3
        assert st["prefill_chunks_skipped_frac"] >= 1.3 * \
            dev_eng.prefix_stats["prefill_chunks_skipped_frac"]
        assert st["evicted_pressure"] == 0      # nothing destroyed

    def test_tier_warm_path_zero_fresh_compiles(self):
        # demotion gathers and promotion scatters are shape-bucketed: a
        # second lap over the working set, demoting and promoting all
        # the way, compiles nothing
        from deepspeed_tpu.analysis import RecompileTripwire
        mcfg, params = self._model()
        eng = self._engine(mcfg, params, prefix_cache=True,
                           prefix_cache_host_blocks=64)
        self._run(eng, self._workload(rounds=2))
        st0 = dict(eng.prefix_stats)
        tw = RecompileTripwire()
        with tw:
            # fresh preambles push the old ones down, a revisit pulls
            # them back up
            self._run(eng, [(100 + u, p) for u, p in
                            self._workload(rounds=1, seed=1)])
            self._run(eng, [(200 + u, p) for u, p in
                            self._workload(rounds=1)])
        st = eng.prefix_stats
        assert st["demoted"] > st0["demoted"]
        assert st["promoted"] > st0["promoted"]
        assert tw.fresh_compiles == 0

    def test_tier_parity_with_spec_decode(self):
        mcfg, params = self._model()
        reqs = self._workload(groups=4, rounds=2)
        off = self._run(self._engine(mcfg, params, prefix_cache=False,
                                     spec_decode="ngram", spec_k=3),
                        reqs, gen=8)
        hier_eng = self._engine(mcfg, params, prefix_cache=True,
                                prefix_cache_host_blocks=48,
                                spec_decode="ngram", spec_k=3)
        hier = self._run(hier_eng, reqs, gen=8)
        assert hier == off
        st = hier_eng.prefix_stats
        assert st["demoted"] > 0 and st["promoted"] > 0
        hier_eng._prefix.assert_exact_refs(
            hier_eng.state.sequences.values())

    def test_drain_replay_with_tier_resident_chain(self):
        """Kill an engine whose cache is mostly HOST-resident mid-
        workload: the drain manifest must replay token-identically on a
        fresh engine AND on the same engine (whose host tier then
        serves the replayed prefills as promotions)."""
        mcfg, params = self._model()
        reqs = self._workload(groups=5, rounds=2)
        # oracle: uninterrupted run
        want = self._run(self._engine(mcfg, params, prefix_cache=False),
                         reqs, gen=6)
        eng = self._engine(mcfg, params, prefix_cache=True,
                           prefix_cache_host_blocks=64)
        got = {}
        cut = len(reqs) // 2
        for uid, p in reqs[:cut]:
            first = eng.put([uid], [p], _greedy=True)
            toks = eng.decode_pipelined([uid], [first[uid]], 3)
            got[uid] = [first[uid]] + toks[uid]
            # no flush: keep them live so the drain has work to carry
        assert eng._prefix.host_cached_blocks > 0 \
            or eng.prefix_stats["demoted"] > 0
        manifest = eng.drain()
        assert manifest["pool"]["fully_recovered"]
        # the survivor: same engine object post-drain is not allowed to
        # replay (draining) — build the restarted twin, replay, finish
        surv = self._engine(mcfg, params, prefix_cache=True,
                            prefix_cache_host_blocks=64)
        next_tok = surv.replay(manifest)
        for uid, p in reqs[:cut]:
            done = len(got[uid])
            toks = surv.decode_pipelined([uid], [next_tok[uid]],
                                         6 - done)
            got[uid].extend([next_tok[uid]] + toks[uid])
            surv.flush(uid)
        for uid, p in reqs[cut:]:
            first = surv.put([uid], [p], _greedy=True)
            toks = surv.decode_pipelined([uid], [first[uid]], 6)
            got[uid] = [first[uid]] + toks[uid]
            surv.flush(uid)
        assert got == want
        surv._prefix.check_invariants()

    @pytest.mark.slow
    def test_tier_parity_tp2_pipelined(self):
        """tp=2 + depth-2 pipeline + hierarchical KV: the promotion
        scatter is head-local under the sharded pool (lane dim
        untouched) — streams must still be identical tier on/off."""
        import jax
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        mcfg, params = self._model()
        reqs = self._workload(groups=4, rounds=2)
        off = self._run(self._engine(mcfg, params, prefix_cache=False,
                                     tp_size=2), reqs)
        hier_eng = self._engine(mcfg, params, prefix_cache=True,
                                prefix_cache_host_blocks=48, tp_size=2)
        hier = self._run(hier_eng, reqs)
        assert hier == off
        assert hier_eng.prefix_stats["promoted"] > 0
