"""Dynamic-SplitFuse token scheduler.

Analogue of the reference's FastGen scheduling (``put``/``query``/
``can_schedule``, ``inference/v2/engine_v2.py:107-184`` + the Dynamic
SplitFuse policy from the FastGen blog): long prompts are split into fixed
chunks and fused with decode tokens so no forward exceeds a token budget.
Shapes are static (SURVEY.md §7 hard part 3), so a step runs one of a few
compiled ``[slots, tokens]`` programs and pads to it; the scheduler keeps
that padding small by making a prefill step as wide as the prompts it
holds: at most ``prefill_rows`` rows (2 at chunks of 256, 4 at 512) carry
more than one token, and the engine runs them in the one
``[prefill_rows, effective_chunk]`` program (``engine_v2._plan_step``).
``token_budget`` stays an upper limit on a step's prefill tokens. The
single token that chunking may leave last (``mid_prefill``) is a prefill
row too, so a ``put`` never mixes it with chunks beyond the cap.

Decode sequences (1 pending token) are scheduled first and are exempt from
both limits — they bound per-token latency; prefill chunks follow.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional

from .config import RaggedInferenceConfig
from .sequence import SequenceDescriptor, SequenceStatus
from .state_manager import StateManager

#: steps a prefill may wait before it jumps the longest-first queue.
#: Longest-prefill-first alone starves short prompts under sustained load
#: (a stream of fresh long prompts always outranks a waiting short one);
#: once a prefill has waited this many steps it is ordered oldest-first
#: ahead of the fresh pool, so no waiting prefill is deferred unboundedly.
PREFILL_AGING_STEPS = 8


@dataclass
class ScheduledSeq:
    seq: SequenceDescriptor
    tokens: List[int]          # tokens this step (<= chunk_size)
    start_pos: int             # absolute position of tokens[0]
    is_last_chunk: bool        # True -> logits of final token are meaningful


class SplitFuseScheduler:
    def __init__(self, cfg: RaggedInferenceConfig, state: StateManager):
        self.cfg = cfg
        self.state = state

    def describe(self, seq: SequenceDescriptor) -> dict:
        """Scheduler-state snapshot for one sequence — the diagnostics
        half of a drain manifest (drain.py): where the request stood in
        the SplitFuse queue when the replica died, plus its sampling
        mode and speculative accepted-length accounting. Pure host
        reads."""
        waited = self.state.step - seq.last_sched
        return {
            "status": seq.status.value,
            "seen_tokens": seq.seen_tokens,
            "pending_tokens": seq.in_flight,
            "prompt_len": seq.prompt_len,
            "kv_blocks": len(seq.kv_blocks),
            "shared_blocks": len(seq.shared),
            "last_sched": seq.last_sched,
            "waited_steps": waited,
            "aged": seq.in_flight > 1 and waited >= PREFILL_AGING_STEPS,
            "sampled": seq.sampling is not None
            and not seq.sampling.greedy,
            "spec_proposed": seq.spec_proposed,
            "spec_accepted": seq.spec_accepted,
            # hierarchical KV: whether the sequence was mid promote-ahead
            # when the replica died (diagnostics only — replay re-matches
            # and re-promotes from whatever tier the survivor holds)
            "promote_defer": seq.promote_defer,
        }

    def schedule(self, eligible: Optional[
            Callable[[SequenceDescriptor], bool]] = None
            ) -> List[ScheduledSeq]:
        """Pick up to ``max_seqs`` sequences with pending tokens.
        ``eligible`` lets the engine veto sequences for this step (the
        pipelined decode path defers a sequence whose next token is a
        device-side speculative placeholder that cannot be fed yet)."""
        cfg = self.cfg
        pending = [s for s in self.state.sequences.values()
                   if s.in_flight > 0 and s.status is not SequenceStatus.FINISHED]
        if eligible is not None:
            pending = [s for s in pending if eligible(s)]
        # decode (1 token) first: latency-bound; then prefills — starved
        # ones (waited >= PREFILL_AGING_STEPS) oldest-first ahead of the
        # fresh pool, which stays longest-first (they need the most
        # chunks, start them early)
        now = self.state.step
        decode = [s for s in pending
                  if s.in_flight == 1 and not s.mid_prefill]

        def prefill_key(s):
            if now - s.last_sched >= PREFILL_AGING_STEPS:
                return (0, s.last_sched, -s.in_flight)
            return (1, -s.in_flight, s.last_sched)

        prefill = sorted((s for s in pending
                          if s.in_flight > 1 or s.mid_prefill),
                         key=prefill_key)
        out: List[ScheduledSeq] = []
        # Dynamic-SplitFuse forward budget: decode rows always fit (1 token
        # each, latency-bound); prefill chunks fill — and SPLIT mid-chunk —
        # up to the remaining budget and at most ``prefill_rows`` rows,
        # bounding every forward's token count (and its activation memory)
        # regardless of how many slots hold fresh prompts
        budget = cfg.token_budget
        used = 0
        rows_left = cfg.prefill_rows
        for seq in decode + prefill:
            if len(out) == cfg.max_seqs or rows_left == 0:
                break                      # prefills come last: none fits
            if seq.promote_defer and seq.in_flight > 1 and out:
                # hierarchical-KV promote-ahead: this sequence's prefix
                # match just dispatched host->device promotion scatters;
                # yield its first chunk for one tick while OTHER work
                # fills the step, so the H2D copies overlap a neighbor's
                # compute instead of sitting in front of this sequence's
                # own paged-attention reads. Only defers when the step
                # already has work (an empty schedule here would read as
                # starvation), and the counter decrements every skip —
                # bounded, never starving, token-stream-invariant.
                seq.promote_defer -= 1
                continue
            is_prefill = seq.in_flight > 1 or seq.mid_prefill
            if not is_prefill:
                n = 1                          # decode rows are budget-EXEMPT
            else:
                # effective_chunk = min(chunk_size, prefill_chunk_cap):
                # uncapped 512-token chunks OOM prefill activations at
                # max_seqs >= 384 (PROFILE.md serving levers)
                n = min(seq.in_flight, cfg.effective_chunk,
                        max(budget - used, 0))
                if n <= 0:
                    break                      # prefill budget exhausted
            if not self.state.can_schedule(seq.uid, n):
                continue                       # KV pressure: leave waiting
            self.state.ensure_blocks(seq, n)
            if seq.seen_tokens < seq.prompt_len:
                # prefill work that actually RAN — the denominator of the
                # prefix cache's skipped-chunk fraction (matched tokens
                # never reach this point: they moved pending->seen at
                # match time and no chunk is ever scheduled for them)
                self.state.prefix_stats["prefill_tokens"] += \
                    min(n, seq.prompt_len - seq.seen_tokens)
            tokens = seq.pending_tokens[:n]
            del seq.pending_tokens[:n]
            out.append(ScheduledSeq(
                seq=seq, tokens=tokens, start_pos=seq.seen_tokens,
                is_last_chunk=seq.in_flight == 0))
            seq.seen_tokens += n
            seq.status = SequenceStatus.RUNNING
            seq.promote_defer = 0     # first chunk ran: head start over
            if is_prefill:
                used += n
                rows_left -= 1
                seq.mid_prefill = seq.in_flight > 0
        return out
