"""Sequence descriptors for the ragged engine.

Analogue of the reference's ``DSSequenceDescriptor``
(``inference/v2/ragged/sequence_descriptor.py``): per-sequence host state —
tokens seen by the model, KV blocks owned, tokens still waiting to be
prefilled, and scheduling status.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import List, Optional, Set


class SequenceStatus(enum.Enum):
    WAITING = "waiting"        # has pending tokens, not yet scheduled
    RUNNING = "running"        # scheduled in the current/last batch
    PAUSED = "paused"          # KV evicted to host (engine.pause)
    FINISHED = "finished"      # flushed / EOS'd by the caller


@dataclass
class SequenceDescriptor:
    uid: int
    pending_tokens: List[int] = field(default_factory=list)
    seen_tokens: int = 0                  # tokens whose KV is in cache
    kv_blocks: List[int] = field(default_factory=list)
    status: SequenceStatus = SequenceStatus.WAITING
    generated: List[int] = field(default_factory=list)
    # row of the recurrent state pool this sequence owns (models with
    # recurrent layers; taken with its first blocks, given back at flush)
    state_slot: Optional[int] = None
    host_kv: object = None                # offloaded KV (engine.pause)
    paused_blocks: int = 0                # block count captured at pause()
    last_step: int = 0                    # engine step last scheduled (LRU)
    # scheduler-clock stamp (one tick per scheduler invocation — unlike
    # last_step, whose engine-step clock jumps by n per fused decode_batch
    # call): what prefill AGING measures waiting time against
    last_sched: int = 0
    # set by the scheduler while a multi-token feed is only partly
    # scheduled: the single token its chunking may leave last is still a
    # prefill row (ordered and capped with the chunks), not a decode row
    mid_prefill: bool = False
    # prefix caching (engine prefix_cache=True): block ids in kv_blocks
    # that are CACHE-SHARED — co-owned by the prefix cache (and possibly
    # other sequences). Release paths (flush / trim_blocks rollback /
    # pause) must DECREF these through the cache, never free them to the
    # allocator; only cache eviction frees a shared block.
    shared: Set[int] = field(default_factory=set)
    # the sequence's initial prompt (set at first put) while its full
    # blocks still await registration into the prefix cache; None once
    # registered (or when caching is off)
    prefix_tokens: Optional[List[int]] = None
    # prompt length incl. any cache-matched span — scheduler positions
    # below this count as PREFILL work for the skipped-chunk accounting
    prompt_len: int = 0
    # hierarchical KV promote-ahead (scheduler.py): set when this
    # sequence's prefix match promoted host-tier blocks — the scheduler
    # then yields its first prefill chunk for up to this many ticks
    # WHEN other work can fill the step, so the H2D promotion scatters
    # get a head start under another sequence's compute instead of
    # racing this sequence's own paged-attention reads. Pure timing
    # (token streams are schedule-order-invariant); never starves — it
    # only defers when something else schedules, and decrements every
    # deferral.
    promote_defer: int = 0
    # per-request sampling identity (sampling.SamplingParams; None =
    # greedy). Attached at admission via put(..., sampling=...), carried
    # for the sequence's whole life INCLUDING across drain/replay (the
    # manifest serializes it) — the seed + position-folded keys are what
    # make sampled streams restart-deterministic.
    sampling: object = None
    # chosen-token log-probabilities (UNMODIFIED model distribution),
    # recorded per committed token when sampling.logprobs is set
    logprob_log: List[float] = field(default_factory=list)
    # speculative-decoding accounting (engine.decode_spec): draft tokens
    # proposed for / accepted by this sequence — the per-request half of
    # the fleet-level spec_proposed/spec_accepted counters
    spec_proposed: int = 0
    spec_accepted: int = 0
    # pipelined serving (engine serve_pipeline_depth > 0): number of
    # SPECULATIVE placeholder tokens in pending_tokens whose value is
    # still on the device (a prior step's in-flight last-token buffer).
    # The scheduler may only pop one while its producing step is the
    # latest dispatched step (device feedback); otherwise the commit of
    # the producing step patches the placeholder with the real value.
    spec_pending: int = 0
    # drain/replay (drain.py): the durable identity of the request. The
    # replay chain is prompt_log + gen_log — re-put()ting it on a fresh
    # or survivor engine reproduces this sequence's KV (and therefore its
    # greedy continuation) exactly. prompt_log is every token fed while
    # the sequence was still a fresh prompt; gen_log is every COMMITTED
    # output of the greedy serve paths plus any caller-fed continuation
    # token not already accounted (see StateManager.put_tokens) — dead
    # (rolled-back) pipeline slots never reach it by construction.
    prompt_log: List[int] = field(default_factory=list)
    gen_log: List[int] = field(default_factory=list)
    # absolute time.monotonic() deadline for this request (0/None = no
    # deadline); the engine aborts expired sequences with a structured
    # rejection instead of serving them late. deadline_s keeps the
    # DURATION it was derived from (engine default or the per-request
    # put(..., deadlines=...) value) so rejection records report the
    # request's actual budget, not the engine knob
    deadline_at: Optional[float] = None
    deadline_s: Optional[float] = None
    # lifecycle stamps (time.monotonic; None until reached): admission
    # (the DUE instant when the caller passes ``arrivals``), first
    # scheduled chunk, first and latest COMMITTED output token.
    # ``put_at`` (when put() received the request), ``first_sched_at``
    # and ``first_token_at`` are set by the engine itself, observer or
    # not; ``admitted_at`` and ``last_token_at`` by the serve observer
    # (None when DSTPU_TELEMETRY=0). The first-token time splits into
    # door wait (put_at - admitted_at), scheduler wait (first_sched_at
    # - put_at) and prefill (first_token_at - first_sched_at). The
    # registry histograms aggregate them (telemetry/serve.py,
    # docs/observability.md).
    admitted_at: Optional[float] = None
    put_at: Optional[float] = None
    first_sched_at: Optional[float] = None
    first_token_at: Optional[float] = None
    last_token_at: Optional[float] = None
    # fleet-wide trace context (docs/observability.md "Distributed
    # tracing"): minted at ReplicaPool.put (or passed by any caller via
    # put(..., traces=...)), carried for the request's whole life
    # INCLUDING across drain/replay — the manifest serializes it, so a
    # merged multi-replica flight dump reconstructs one gapless track
    # per request even through a membership change. None = untraced
    # (single-engine callers; spans then key on the uid alone).
    trace_id: Optional[str] = None

    @property
    def in_flight(self) -> int:
        return len(self.pending_tokens)

    def stamp_first_token(self, now: float) -> bool:
        """A commit at ``now`` made output of this sequence host-visible:
        stamp ``first_token_at`` if it is the first; True when it was."""
        if self.first_token_at is not None:
            return False
        self.first_token_at = now
        return True

    def blocks_needed(self, new_tokens: int, block_size: int) -> int:
        """KV blocks to allocate so `seen_tokens + new_tokens` fit."""
        total = self.seen_tokens + new_tokens
        needed = -(-total // block_size)          # ceil
        return max(0, needed - len(self.kv_blocks))
