"""Sequence state manager.

Analogue of the reference's ``DSStateManager``
(``inference/v2/ragged/ragged_manager.py:19``): tracks live sequences,
grows their KV block allocations as tokens arrive, and frees state on flush.

With prefix caching enabled (``prefix_cache.py``) the manager is also the
refcount boundary: a sequence's leading blocks may be CACHE-SHARED
(``seq.shared``), and every release path here — flush, the pipelined EOS
rollback's ``trim_blocks``, the engine's pause offload — *decrefs* shared
blocks through the cache instead of freeing them to the allocator. Matching
(``match_prefix``) and registration (``register_prefix``) are the two
host-side halves of automatic prefix reuse; the engine dispatches the
device-side CoW copies that matching requests.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Tuple

from .blocked_allocator import OutOfBlocksError
from .config import RaggedInferenceConfig
from .kv_cache import BlockedKVCache
from .prefix_cache import PrefixCache
from .sequence import SequenceDescriptor, SequenceStatus


@dataclass
class MatchPlan:
    """Device work one prefix match requests of the engine: ``copies``
    are device-to-device CoW row copies (src_block, dst_block) behind a
    device-tier partial-tail hit; ``promotes`` are host→device restore
    scatters ((rows, scales), dst_block) behind hierarchical-KV hits —
    full-block promotions AND host-tier CoW tails. All host bookkeeping
    (refcounts, tier flips, block-table updates) already happened; the
    engine only dispatches the data movement, non-blocking, before any
    step that could read the blocks."""

    copies: List[Tuple[int, int]] = field(default_factory=list)
    promotes: List[Tuple[Any, int]] = field(default_factory=list)
    #: promotes entries that FLIPPED a host entry to the device tier
    #: (a host-tier CoW tail scatters without flipping its source) —
    #: the live prefix_promoted_blocks counter must match
    #: PrefixCache.stats["promoted"] exactly
    promoted_blocks: int = 0

    def __bool__(self) -> bool:
        return bool(self.copies or self.promotes)


class StateManager:
    def __init__(self, cfg: RaggedInferenceConfig, kv_cache: BlockedKVCache):
        self.cfg = cfg
        self.kv_cache = kv_cache
        self._seqs: Dict[int, SequenceDescriptor] = {}
        #: free rows of the recurrent state pool (None: the model has no
        #: recurrent layer). A sequence takes one with its first blocks
        #: and returns it at flush; the row's last tenant's state is
        #: wiped by the program that runs the new tenant's position 0.
        #: A model with sliding-window layers hands out the same slots:
        #: a slot's row of the window pool is its R blocks, whose last
        #: tenant's rows lie past the new tenant's length until it
        #: overwrites them, and no mask lets a row past the length in
        self.state_slots_free: Optional[List[int]] = \
            list(range(cfg.max_seqs - 1, -1, -1)) \
            if kv_cache.stateful or kv_cache.window is not None \
            else None
        # scheduler clock: ONE tick per scheduler invocation (bumped by
        # the engine's plan phase — deliberately NOT the engine step
        # counter, which decode_batch advances by n per fused call and
        # would instantly "age" every waiting prefill). New sequences
        # stamp their arrival here so aging measures real waiting time.
        self.step: int = 0
        #: the content-addressed block index (None = prefix caching off);
        #: set by the engine, which also attaches it to the kv cache
        self.prefix: Optional[PrefixCache] = None
        #: scheduler ticks of head start a host->device prefix
        #: promotion gets before its sequence's next prefill chunk
        #: (scheduler.py promote-ahead). 1 = the steady-state overlap;
        #: the admission controller's brownout L1 (defer_promote)
        #: stretches it so promotions yield ticks to decode chunks —
        #: token-stream-invariant, it changes only WHEN a chunk runs
        self.promote_defer_ticks: int = 1
        #: skipped-vs-run prefill accounting for the prefix tests and
        #: smoke rows: matched_tokens never ran a prefill chunk,
        #: prefill_tokens did (scheduler-counted, prompt positions only)
        self.prefix_stats = {"matched_tokens": 0, "matched_blocks": 0,
                             "cow_tokens": 0, "cow_copies": 0,
                             "prefill_tokens": 0, "match_queries": 0,
                             # multi-token trims (speculative rollback /
                             # pipelined EOS retraction) and the blocks
                             # they returned — the rollback-pressure
                             # signal
                             "trims": 0, "trimmed_blocks": 0,
                             # hierarchical KV: tokens matched out of the
                             # HOST tier (full promoted blocks + host CoW
                             # spans) — the "demoted hit is still a hit"
                             # numerator
                             "host_matched_tokens": 0}

    # ------------------------------------------------------------------ #

    def get_or_create(self, uid: int) -> SequenceDescriptor:
        if uid not in self._seqs:
            self._seqs[uid] = SequenceDescriptor(uid=uid,
                                                 last_sched=self.step)
        return self._seqs[uid]

    def get(self, uid: int) -> Optional[SequenceDescriptor]:
        return self._seqs.get(uid)

    @property
    def sequences(self) -> Dict[int, SequenceDescriptor]:
        return self._seqs

    def put_tokens(self, uid: int, tokens: Iterable[int]) -> SequenceDescriptor:
        seq = self.get_or_create(uid)
        toks = [int(t) for t in tokens]
        if seq.seen_tokens == 0 and not seq.kv_blocks:
            # still a fresh prompt: the fed tokens are prompt — they join
            # the replay chain's prompt half (drain.py)
            seq.prompt_log.extend(toks)
        else:
            # continuation feed: a token is new replay history UNLESS it
            # is one of our own committed outputs being fed back (the
            # greedy loops append outputs to gen_log at commit — feeding
            # them again must not double-count). The number of chain
            # tokens not yet consumed-or-queued as inputs is exactly how
            # many of the fed tokens are already accounted for.
            unfed = len(seq.prompt_log) + len(seq.gen_log) \
                - seq.seen_tokens - len(seq.pending_tokens)
            seq.gen_log.extend(toks[max(0, unfed):])
        seq.pending_tokens.extend(toks)
        if seq.seen_tokens == 0 and not seq.kv_blocks:
            # still a fresh prompt (nothing prefilled yet): everything
            # pending is prompt — the span the prefix tracker hashes and
            # the scheduler counts as prefill work
            seq.prompt_len = seq.in_flight
        # PAUSED sequences keep their status: the scheduler skips them and
        # the engine auto-resumes as blocks free up (engine_v2._try_resume).
        if seq.status not in (SequenceStatus.RUNNING, SequenceStatus.PAUSED):
            seq.status = SequenceStatus.WAITING
        total = seq.seen_tokens + seq.in_flight
        if total > self.cfg.max_context:
            raise ValueError(
                f"sequence {uid}: {total} tokens exceeds max_context "
                f"{self.cfg.max_context} (raise max_blocks_per_seq)")
        return seq

    # ------------------------------------------------------------------ #
    # prefix caching: match (longest cached prefix) + register (insert
    # this sequence's full prompt blocks)
    # ------------------------------------------------------------------ #

    def _reserve_next(self, seq: SequenceDescriptor) -> int:
        """Reserve ONE block at ``seq``'s next chain ordinal — under
        sequence parallelism ordinal ``o`` must land on home chip
        ``o % seq`` so every chip holds the same share of the chain (the
        flat-per-chip-bytes invariant). seq=1 takes the legacy path."""
        kv = self.kv_cache
        if kv.seq > 1:
            return kv.reserve(1, homes=[len(seq.kv_blocks) % kv.seq])[0]
        return kv.reserve(1)[0]

    def match_prefix(self, seq: SequenceDescriptor) -> MatchPlan:
        """Point a FRESH sequence's block table at the longest cached
        chain of its prompt and skip those tokens' prefill entirely
        (pending -> seen with no scheduled chunk). Returns the
        :class:`MatchPlan` of device work the engine must dispatch:
        copy-on-write row copies (partial-tail match into a private
        copy) and hierarchical-KV promotion scatters (host-resident
        chain links restored into fresh device blocks — a demoted hit
        is still a hit). At least one trailing token is always left to
        prefill so the last chunk still produces this sequence's
        logits. Pure host work plus non-blocking device dispatch — a
        DSL001 hot path."""
        plan = MatchPlan()
        pc = self.prefix
        if pc is None or seq.seen_tokens or seq.kv_blocks \
                or seq.in_flight < 2:
            return plan
        toks = seq.pending_tokens
        seq.prefix_tokens = list(toks)
        self.prefix_stats["match_queries"] += 1
        entries, cow, cow_len = pc.match(toks)
        bs = self.cfg.block_size
        maxb = self.cfg.max_blocks_per_seq
        # no table-width truncation needed here: put_tokens caps the
        # prompt at max_context = maxb * bs, and match leaves >= 1 token,
        # so at most maxb - 1 full blocks can match; the cow append below
        # carries its own < maxb guard
        matched = 0
        hit_blocks = 0
        # demotion is leaf-first, so the matched chain is a DEVICE
        # prefix followed by a HOST suffix. Acquire the device prefix
        # FIRST: every entry on it is then pinned (refs > 0) before any
        # promotion reserve below can go hunting for demotion victims —
        # a reserve must never demote the very chain being matched
        n_dev = 0
        kvseq = self.kv_cache.seq
        for e in entries:
            if e.tier != "device":
                break
            if kvseq > 1 and e.block % kvseq \
                    != len(seq.kv_blocks) % kvseq:
                # chains are registered ordinal-aligned, so a cached
                # block's home always matches its adopter's ordinal;
                # this guards a (never-expected) misaligned entry from
                # breaking the per-chip share invariant
                break
            n_dev += 1
            pc.acquire(e)
            seq.kv_blocks.append(e.block)
            seq.shared.add(e.block)
            matched += bs
            hit_blocks += 1
        for e in entries[n_dev:]:
            # hierarchical-KV hit: restore the demoted link through a
            # fresh device block. The reserve may demote OTHER cold
            # chains (ours is pinned: the device prefix holds refs, the
            # host suffix is not a demotion candidate) and may overflow
            # the host tier's cap — re-check the entry survived before
            # touching its buffer. Stop the match at the first link the
            # pool cannot cover: the rest stays host-resident for the
            # next request.
            try:
                dst = self._reserve_next(seq)
            except OutOfBlocksError:
                break
            if e.host_ref is None or e.tier != "host":
                # host-cap eviction raced us inside that reserve: the
                # link is gone, nothing left to promote
                self.kv_cache.free([dst])
                break
            buf = self.kv_cache.buffer_of(e)
            pc.promote(e, dst)
            pc.acquire(e)
            plan.promotes.append((buf, dst))
            plan.promoted_blocks += 1
            seq.kv_blocks.append(dst)
            seq.shared.add(dst)
            matched += bs
            hit_blocks += 1
            self.prefix_stats["host_matched_tokens"] += bs
        pc.stats["hit_blocks"] += hit_blocks
        self.prefix_stats["matched_blocks"] += hit_blocks
        if cow is not None and hit_blocks == len(entries) \
                and len(seq.kv_blocks) < maxb and cow.tier != "dead":
            # partial-tail hit (only when the full chain matched — a
            # truncated promotion means the cow child is deeper than the
            # table reaches). The tier is RE-READ here, not taken from
            # the match walk: the promotion loop's reserves above may
            # have demoted a device cow (serve it off the host path) or
            # host-cap-evicted a host cow outright (tier "dead" — the
            # guard above skips it; acquiring a dead entry would crash
            # the serve path). A device-tier source is pinned across
            # the reserve — with refcount 0 it would itself be a
            # reclaim candidate for the block we are about to allocate
            # as the copy destination; a host-tier source is no
            # candidate but can be host-cap-evicted by the reserve, so
            # it is re-checked after.
            host_cow = cow.tier == "host"
            if not host_cow:
                pc.acquire(cow)
            try:
                dst = self._reserve_next(seq)
            except OutOfBlocksError:
                dst = None
            finally:
                if not host_cow:
                    pc.release_block(cow.block)
            if dst is not None and host_cow \
                    and (cow.host_ref is None or cow.tier != "host"):
                self.kv_cache.free([dst])
                dst = None
            if dst is not None:
                if host_cow:
                    # the agreeing span is scattered host->device into
                    # the PRIVATE copy; the source entry stays demoted
                    plan.promotes.append((self.kv_cache.buffer_of(cow),
                                          dst))
                    pc.stats["host_hit_blocks"] += 1
                    self.prefix_stats["host_matched_tokens"] += cow_len
                else:
                    plan.copies.append((cow.block, dst))
                seq.kv_blocks.append(dst)        # private: CoW, not shared
                matched += cow_len
                pc.stats["cow_hits"] += 1
                self.prefix_stats["cow_copies"] += 1
                self.prefix_stats["cow_tokens"] += cow_len
        if matched:
            seq.seen_tokens += matched
            del seq.pending_tokens[:matched]
            self.prefix_stats["matched_tokens"] += matched
        if plan.promotes:
            # promote-ahead (scheduler.py): give the H2D scatters a
            # head start under other sequences' chunks (brownout L1
            # stretches promote_defer_ticks beyond the default 1)
            seq.promote_defer = self.promote_defer_ticks
        return plan

    def register_prefix(self, seq: SequenceDescriptor) -> None:
        """Insert this sequence's fully-prefilled full prompt blocks into
        the cache (first writer wins; duplicates stay private). Called by
        the engine once a put() call has drained — every registered
        block's KV writes are already dispatched, and any later matcher
        dispatches after, so the device orders reads after writes through
        the pool data dependence."""
        pc = self.prefix
        toks = seq.prefix_tokens
        if pc is None or toks is None:
            return
        if seq.status is SequenceStatus.PAUSED or not seq.kv_blocks:
            # defensive only — unreachable via put(), which drains before
            # registering; guards a future out-of-drain caller against
            # caching a paused sequence's released block ids
            return
        bs = self.cfg.block_size
        usable = min(seq.seen_tokens, len(toks), len(seq.kv_blocks) * bs)
        node = None
        for i in range(usable // bs):
            grp = tuple(toks[i * bs:(i + 1) * bs])
            child = pc.lookup_child(node, grp)
            if child is not None:
                if child.block != seq.kv_blocks[i]:
                    # another sequence won the race with a DIFFERENT device
                    # block: our copy stays private, and grafting our NEXT
                    # blocks under the foreign chain would break
                    # refs(parent) >= refs(child) — we hold no refs along
                    # it, so its ancestors could hit 0 while our child is
                    # still referenced, stranding "evictable" capacity
                    break
                node = child       # ours (matched or registered earlier)
                continue
            entry = pc.insert(node, grp, seq.kv_blocks[i])
            if entry is None:
                break              # cap reached and nothing evictable
            seq.shared.add(seq.kv_blocks[i])
            node = entry
        if seq.seen_tokens >= len(toks):
            seq.prefix_tokens = None        # prompt fully processed
        self.kv_cache.collect_prefix_evictions()

    def release_blocks(self, seq: SequenceDescriptor, blocks) -> None:
        """The one release path: cache-shared blocks are DECREF'd (they
        stay cached, evictable once cold), private blocks go back to the
        allocator."""
        private: List[int] = []
        for b in blocks:
            if b in seq.shared:
                seq.shared.discard(b)
                self.prefix.release_block(b)
            else:
                private.append(b)
        if private:
            self.kv_cache.free(private)

    # ------------------------------------------------------------------ #

    def can_schedule(self, uid: int, n_tokens: int) -> bool:
        """Scheduling hint (reference ``engine_v2.py:158-184``): would
        `n_tokens` more tokens fit in blocks we can still allocate?
        Paused sequences (KV on host) are never schedulable — resume first."""
        seq = self.get_or_create(uid)
        if seq.status is SequenceStatus.PAUSED:
            return False
        if self.state_slots_free is not None and seq.state_slot is None \
                and not self.state_slots_free:
            return False                   # every state row has a tenant
        need = seq.blocks_needed(n_tokens, self.cfg.block_size)
        if not (need <= self.kv_cache.free_blocks
                and len(seq.kv_blocks) + need
                <= self.cfg.max_blocks_per_seq):
            return False
        kv = self.kv_cache
        if need and kv.seq > 1:
            # per-home form: the total can cover `need` while one home
            # is dry. Free-list deficits must be coverable by evictable
            # cached blocks (reserve's per-home pressure loop reclaims
            # victims onto their own homes, so the total evictable count
            # is the honest upper bound on what it can recover).
            start = len(seq.kv_blocks)
            homes = [(start + i) % kv.seq for i in range(need)]
            deficit = sum(kv.allocator.shortfall(homes))
            evictable = kv.prefix.evictable_blocks if kv.prefix else 0
            if deficit > evictable:
                return False
        return True

    def ensure_blocks(self, seq: SequenceDescriptor, n_tokens: int) -> None:
        if self.state_slots_free is not None and seq.state_slot is None:
            if not self.state_slots_free:
                raise OutOfBlocksError(
                    f"sequence {seq.uid}: all {self.cfg.max_seqs} sequence "
                    f"slots (recurrent state rows, window-pool rows) are "
                    f"taken")
            seq.state_slot = self.state_slots_free.pop()
        need = seq.blocks_needed(n_tokens, self.cfg.block_size)
        if need:
            if len(seq.kv_blocks) + need > self.cfg.max_blocks_per_seq:
                raise OutOfBlocksError(
                    f"sequence {seq.uid} exceeds max_blocks_per_seq "
                    f"({self.cfg.max_blocks_per_seq})")
            kv = self.kv_cache
            homes = None
            if kv.seq > 1:
                start = len(seq.kv_blocks)
                homes = [(start + i) % kv.seq for i in range(need)]
            seq.kv_blocks.extend(kv.reserve(need, homes=homes))

    def trim_blocks(self, seq: SequenceDescriptor) -> int:
        """Free KV blocks beyond what ``seq.seen_tokens`` needs — the
        MULTI-TOKEN rollback primitive shared by pipelined EOS
        retraction (PR 3) and speculative-decode rejection
        (``engine.decode_spec``): the caller retracts ``seen_tokens``
        to the accepted length and this returns every over-allocated
        block to the pool. Cache-shared blocks are decref'd EXACTLY
        ONCE, never freed (another sequence — or the cache — may still
        own them; ``release_blocks`` is the single release path, and
        the allocator's set-membership double-free detection backstops
        it). Garbage KV within the retained tail block (positions past
        ``seen_tokens``) is harmless: appends are position-addressed,
        so the next accepted tokens overwrite it. Returns the number
        of blocks released."""
        needed = -(-seq.seen_tokens // self.cfg.block_size)
        extra = seq.kv_blocks[needed:]
        if extra:
            del seq.kv_blocks[needed:]
            self.release_blocks(seq, extra)
            self.prefix_stats["trims"] += 1
            self.prefix_stats["trimmed_blocks"] += len(extra)
        return len(extra)

    def kv_memory_report(self) -> Dict[str, int]:
        """Serving-memory self-description: total KV-pool bytes, the bytes
        ONE chip holds (read from the live device sharding — ∝ 1/tp under
        head-sharded tensor parallelism), and the TP degree."""
        return {
            "kv_pool_bytes_total": self.kv_cache.memory_bytes(),
            "kv_pool_bytes_per_chip": self.kv_cache.memory_bytes_per_chip(),
            "kv_bytes_per_token": self.kv_cache.kv_bytes_per_token(),
            "tp_size": max(1, int(getattr(self.cfg, "tp_size", 1))),
            "seq_size": max(1, int(getattr(self.cfg, "seq_size", 1))),
        }

    def flush(self, uid: int) -> None:
        """Release a sequence and its KV blocks (reference ``flush``)."""
        seq = self._seqs.pop(uid, None)
        if seq is not None and seq.kv_blocks:
            self.release_blocks(seq, seq.kv_blocks)
        if seq is not None and seq.state_slot is not None:
            self.state_slots_free.append(seq.state_slot)
            seq.state_slot = None

    def flush_all(self) -> None:
        for uid in list(self._seqs):
            self.flush(uid)
