"""Job kind ``open_loop``: independent users on one replica.

A generator thread offers requests on a schedule fixed before the run (it
only sleeps, stamps and enqueues); the serving loop admits what is due with
``engine.put`` and advances the live set with short
``engine.decode_pipelined`` bursts, as ``telemetry/loadgen.py``'s driver
does. Every time is taken from the instant a request was DUE, so a stall
charges the requests that waited behind it.

Phases on one clock: ``ramp_s`` of load at the cell's rate (set-up: the
batch reaches its steady state), the window (``--seconds``), a traced
stretch when asked for, then up to ``drain_s`` more of the same load until
every request of the sample has finished. The sample, for every tail, is
the requests due inside the window and no other; one still unfinished at
the end counts in ``failed`` and enters the tails with the time waited so
far.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional

from ..common import Ctx, counters_delta, percentile, say
from ..traffic import Request, arrivals_schedule
from . import serve_common


class OpenLoop:
    """The generator thread and the serving loop of one pass."""

    def __init__(self, ctx: Ctx, engine, schedule: List[Request],
                 decode_burst: int, admit_max: int):
        self.ctx, self.engine = ctx, engine
        self.schedule = schedule
        self.burst, self.admit_max = int(decode_burst), int(admit_max)
        self.max_seqs = engine.config.max_seqs
        self.queue: collections.deque = collections.deque()
        self.stop = threading.Event()
        self.t0 = 0.0
        self.offered: Dict[int, float] = {}
        self.t_first: Dict[int, float] = {}
        self.t_last: Dict[int, float] = {}
        self.queue_wait: Dict[int, float] = {}
        # the engine's own stamps of a request (sequence.py), read when
        # its ``put`` returns: (put_at, first_sched_at, first_token_at)
        self.stamps: Dict[int, tuple] = {}
        self.refused: set = set()
        self.streams: Dict[int, List[int]] = {}
        self.live: Dict[int, Dict[str, int]] = {}
        self.by_uid = {r.uid: r for r in schedule}
        # per decode burst, while ``recording``: steps, live sequences,
        # and context tokens read (sum over steps and sequences)
        self.recording = False
        self.bursts: List[tuple] = []
        # calls into the engine that took over a second: a stall's cause
        self.slow_calls: List[tuple] = []

    # ------------------------- generator thread ------------------------- #

    def _generate(self) -> None:
        for r in self.schedule:
            while True:
                wait = self.t0 + r.due_s - time.monotonic()
                if wait <= 0 or self.stop.wait(wait):
                    break
            if self.stop.is_set():
                return
            self.offered[r.uid] = time.monotonic()
            self.queue.append(r)

    # --------------------------- serving loop --------------------------- #

    def _admit(self) -> None:
        room = min(self.admit_max, self.max_seqs - len(self.live))
        due: List[Request] = []
        while room > len(due) and self.queue:
            due.append(self.queue.popleft())
        if not due:
            return
        t_call = time.monotonic()
        with self.ctx.span("put"):
            res = self.engine.put(
                [r.uid for r in due], [r.prompt for r in due], _greedy=True,
                arrivals={r.uid: self.t0 + r.due_s for r in due})
        now = time.monotonic()
        self._note_slow("put", t_call, now, len(due))
        for r in due:
            if r.uid not in res:
                self.refused.add(r.uid)
                continue
            tok = int(res[r.uid])
            self.t_first[r.uid] = now
            self.streams[r.uid] = [tok]
            seq = self.engine.state.get(r.uid)
            if seq is not None and seq.first_sched_at is not None:
                self.queue_wait[r.uid] = seq.first_sched_at \
                    - (self.t0 + r.due_s)
            if seq is not None:
                self.stamps[r.uid] = (seq.put_at, seq.first_sched_at,
                                      seq.first_token_at)
            if r.gen_len <= 1:
                self._finish(r.uid, now)
            else:
                self.live[r.uid] = {"last": tok, "remaining": r.gen_len - 1}

    def _finish(self, uid: int, now: float) -> None:
        self.t_last[uid] = now
        self.live.pop(uid, None)
        self.engine.flush(uid)

    def _decode(self) -> None:
        uids = list(self.live)
        budgets = [min(self.burst, self.live[u]["remaining"]) for u in uids]
        if self.recording:
            seqs = self.engine.state.sequences
            ctx_tokens = sum(b * seqs[u].seen_tokens + b * (b + 1) // 2
                             for u, b in zip(uids, budgets))
            self.bursts.append((max(budgets), len(uids), sum(budgets),
                                ctx_tokens))
        t_call = time.monotonic()
        with self.ctx.span("decode_pipelined"):
            outs = self.engine.decode_pipelined(
                uids, [self.live[u]["last"] for u in uids], budgets)
        now = time.monotonic()
        self._note_slow("decode_pipelined", t_call, now, len(uids))
        for u in uids:
            got = outs.get(u) or []
            st = self.live[u]
            self.streams[u].extend(int(t) for t in got)
            st["remaining"] -= len(got)
            if got:
                st["last"] = int(got[-1])
            if st["remaining"] <= 0 or not got:
                self._finish(u, now)

    def _note_slow(self, what: str, t_call: float, now: float,
                   n: int) -> None:
        if now - t_call > 1.0:
            self.slow_calls.append((what, round(t_call - self.t0, 3),
                                    round(now - t_call, 3), n))

    def serve_until(self, t_rel: float, done=None) -> None:
        while time.monotonic() - self.t0 < t_rel and not (done and done()):
            self._admit()
            if self.live:
                self._decode()
            else:
                time.sleep(0.0005)

    def start(self) -> threading.Thread:
        self.t0 = time.monotonic()
        th = threading.Thread(target=self._generate, name="bench-generator",
                              daemon=True)
        th.start()
        return th

    def close(self, th: threading.Thread) -> None:
        self.stop.set()
        th.join()
        for u in list(self.live):
            self.live.pop(u)
            self.engine.flush(u)

    # ----------------------------- the sample --------------------------- #

    def sample(self, segment: str = "window") -> Dict[str, Any]:
        """Times of every request due in ``segment``, finished or not."""
        now = time.monotonic()
        ttft, tpot, late, qwait, met = [], [], [], [], []
        # a request's wait by stage, each where both of its stamps exist:
        # at the door (due -> put), in the scheduler (put -> first
        # scheduled), in prefill (-> first token committed) and for the
        # rest of its put group (-> first token host-visible)
        stages: Dict[str, List[float]] = {
            "door_wait_s": [], "sched_wait_s": [], "prefill_s": [],
            "group_wait_s": []}
        failed = 0
        limits = self.ctx.param("limits")
        for r in self.schedule:
            if r.segment != segment:
                continue
            due = self.t0 + r.due_s
            done = r.uid in self.t_last \
                and len(self.streams[r.uid]) >= r.gen_len
            failed += not done
            first = self.t_first.get(r.uid)
            n_out = len(self.streams.get(r.uid, ()))
            t_ttft = (first if first is not None else now) - due
            end = self.t_last.get(r.uid, now)
            t_tpot = (end - first) / max(1, n_out - 1) \
                if first is not None else now - due
            ttft.append(t_ttft)
            tpot.append(t_tpot)
            if r.uid in self.offered:
                late.append(self.offered[r.uid] - due)
            if r.uid in self.queue_wait:
                qwait.append(self.queue_wait[r.uid])
            put_at, sched_at, token_at = self.stamps.get(
                r.uid, (None, None, None))
            for key, end, start in (("door_wait_s", put_at, due),
                                    ("sched_wait_s", sched_at, put_at),
                                    ("prefill_s", token_at, sched_at),
                                    ("group_wait_s", first, token_at)):
                if end is not None and start is not None:
                    stages[key].append(end - start)
            met.append(done and t_ttft <= limits["ttft_s"]
                       + limits["ttft_s_per_prompt_token"] * len(r.prompt)
                       and t_tpot <= limits["tpot_s"])
        return dict(stages, n=len(ttft), failed=failed, ttft_s=ttft,
                    tpot_s=tpot, gen_late_s=late, queue_wait_s=qwait,
                    met_limits=met)


def warm_up(ctx: Ctx, engine, vocab: int) -> None:
    """Compile every program this cell's traffic can touch, and no other:
    one prefill group, then at each live-set size the door allows (halving
    from ``max_seqs``: the slot dimension's buckets are powers of two) the
    unfed and the fed decode step, and the fed step that follows a larger
    live set (a fed step's program depends on the slot bucket of the step
    that feeds it too, so a live set that shrinks across a bucket inside a
    burst meets a program of its own)."""
    import numpy as np
    rng = np.random.default_rng(0)
    n, group = engine.config.max_seqs, int(ctx.param("admit_max"))
    uids = list(range(-1, -1 - n, -1))
    last: Dict[int, int] = {}
    for i in range(0, n, group):
        part = uids[i:i + group]
        res = engine.put(part, [rng.integers(0, vocab, 24).tolist()
                                for _ in part], _greedy=True)
        last.update({u: int(res[u]) for u in part})
    live = list(uids)
    while live:
        keep = len(live) // 2
        # every sequence for three steps, then the second half for one
        # step only: the second burst's step 2 is fed by a larger bucket
        for budgets in ([3] * len(live),
                        [3] * keep + [1] * (len(live) - keep)):
            outs = engine.decode_pipelined(live, [last[u] for u in live],
                                           budgets)
            last.update({u: int(outs[u][-1]) for u in live})
        for u in live[keep:]:
            engine.flush(u)
        live = live[:keep]


def summarize(loop: OpenLoop, stats0, stats1) -> Dict[str, Any]:
    """Observations of the window, for the end-to-end metrics and the
    per-layer readers."""
    s = loop.sample()
    steps = sum(b[0] for b in loop.bursts)
    obs = dict(s)
    obs["pipeline"] = counters_delta(stats1, stats0)
    obs["decode_steps"] = steps
    obs["slot_steps_live"] = sum(b[0] * b[1] for b in loop.bursts)
    obs["slot_steps"] = steps * loop.max_seqs
    obs["decode_context_tokens"] = sum(b[3] for b in loop.bursts)
    return obs


def run(ctx: Ctx) -> Dict[str, Any]:
    from deepspeed_tpu.analysis.program_audit import RecompileTripwire
    engine, mt, model_cfg, params = serve_common.build(ctx)
    warm_up(ctx, engine, model_cfg.vocab_size)
    ctx.mark("warm_up")
    say("setup_compiles", ctx.compiles.snapshot())

    ramp_s, drain_s = float(ctx.param("ramp_s")), float(ctx.param("drain_s"))
    trace_s = float(ctx.param("trace_s")) if ctx.trace else 0.0
    segments = [("ramp", ramp_s), ("window", ctx.seconds),
                ("trace", trace_s), ("tail", drain_s)]
    schedule = arrivals_schedule(ctx.traffic, float(ctx.param("rate_rps")),
                                 segments, ctx.seed, model_cfg.vocab_size)
    loop = OpenLoop(ctx, engine, schedule, ctx.param("decode_burst"),
                    ctx.param("admit_max"))
    with RecompileTripwire() as serving:
        th = loop.start()
        loop.serve_until(ramp_s)
        ctx.window_opens()
        loop.recording = True
        stats0 = dict(engine.pipeline_stats)
        with RecompileTripwire() as trip:
            loop.serve_until(ramp_s + ctx.seconds)
        stats1 = dict(engine.pipeline_stats)
        loop.recording = False
        ctx.read_memory_peak()
        win_bursts = loop.bursts
        t_end = ramp_s + ctx.seconds
        traced: Optional[Dict[str, Any]] = None
        if ctx.trace:
            loop.bursts = []
            traced_stats0 = dict(engine.pipeline_stats)
            with ctx.traced_window():
                loop.recording = True
                loop.serve_until(time.monotonic() - loop.t0 + trace_s)
                loop.recording = False
            traced = {"decode_context_tokens": sum(b[3] for b in loop.bursts),
                      "decode_steps": sum(b[0] for b in loop.bursts),
                      "pipeline": counters_delta(
                          dict(engine.pipeline_stats), traced_stats0)}
            loop.bursts = win_bursts
            t_end = time.monotonic() - loop.t0
        window_uids = [r.uid for r in schedule if r.segment == "window"]
        loop.serve_until(t_end + drain_s,
                         done=lambda: all(u in loop.t_last or u in loop.refused
                                          for u in window_uids))
        obs = summarize(loop, stats0, stats1)
        loop.close(th)
    if traced is not None:
        obs["traced"] = traced
        obs["attention"] = {"q_heads": model_cfg.num_heads,
                            "kv_heads": model_cfg.num_kv_heads,
                            "head_dim": model_cfg.head_dim,
                            "kv_row": model_cfg.num_kv_heads
                            * model_cfg.head_dim,
                            "layers": model_cfg.num_layers}
    say("sample", {"due_in_window": obs["n"], "failed": obs["failed"],
                   "gen_late_p99_ms": 1e3 * percentile(obs["gen_late_s"], 99),
                   "ttft_p50_ms": 1e3 * percentile(obs["ttft_s"], 50),
                   "ttft_p75_ms": 1e3 * percentile(obs["ttft_s"], 75),
                   "ttft_p90_ms": 1e3 * percentile(obs["ttft_s"], 90),
                   "ttft_mean_ms": 1e3 * sum(obs["ttft_s"]) / obs["n"],
                   "tpot_p50_ms": 1e3 * percentile(obs["tpot_s"], 50),
                   "tpot_p90_ms": 1e3 * percentile(obs["tpot_s"], 90),
                   "compiles_in_window": trip.fresh_compiles,
                   "compiles_while_serving": serving.fresh_compiles,
                   "slow_calls": loop.slow_calls})
    finished = [(loop.by_uid[u].prompt, loop.streams[u])
                for u in window_uids if u in loop.t_last]
    del engine
    check = serve_common.check_streams(ctx, mt, model_cfg, params, finished)
    checks = {"no_compile_in_window": trip.fresh_compiles == 0,
              "no_compile_from_ramp_to_drain": serving.fresh_compiles == 0,
              "served_tokens_match_reference": check["ok"],
              "sample_holds_requests": obs["n"] > 0}
    return {"attempted": obs["n"], "failed": obs["failed"], "checks": checks,
            "compared": serve_common.compared(check), "obs": obs,
            "end_to_end": {
                "ttft_p90_ms": 1e3 * percentile(obs["ttft_s"], 90),
                "tpot_p90_ms": 1e3 * percentile(obs["tpot_s"], 90)}}
