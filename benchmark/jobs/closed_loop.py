"""Job kind ``closed_loop``: offline generation at a full batch.

``clients`` callers each send their next prompt when the last one has
finished, so every slot is always live and arrivals and queueing are
bypassed. A round is one fused ``engine.decode_batch`` of
``decode_loop_steps`` tokens for every live sequence, then the refill of
the slots that finished (``engine.put``, in groups). A prompt is prefilled
up to its last token, which the fused loop then feeds, so a request of
output length L is exactly L / decode_loop_steps rounds; every output
length is a multiple of it. Set-up fills the slots with a first wave
staggered as if caught mid-flight (``traffic.first_wave``) and runs one
round. The window runs whole rounds until ``--seconds`` have passed:
output tokens of those rounds over the seconds they took.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Iterator, List

from ..common import Ctx, counters_delta, say
from ..traffic import Request, closed_loop_requests, first_wave
from . import serve_common


class ClosedLoop:
    def __init__(self, ctx: Ctx, engine, wave: List[Request],
                 requests: Iterator[Request]):
        self.ctx, self.engine = ctx, engine
        self.quantum = int(engine.config.decode_loop_steps)
        self.group = int(ctx.param("admit_max"))
        self.requests = requests
        self.live: Dict[int, Dict[str, Any]] = {}
        self.finished: List[Request] = []
        self.streams: Dict[int, List[int]] = {}
        self.rounds: List[Dict[str, float]] = []
        self._admit(wave)

    def _admit(self, reqs: List[Request]) -> float:
        t0 = time.perf_counter()
        for i in range(0, len(reqs), self.group):
            part = reqs[i:i + self.group]
            assert all(r.gen_len % self.quantum == 0 for r in part), \
                "output lengths must be multiples of decode_loop_steps"
            with self.ctx.span("put"):
                res = self.engine.put([r.uid for r in part],
                                      [r.prompt[:-1] for r in part],
                                      _greedy=True)
            for r in part:
                if r.uid not in res:
                    raise RuntimeError(f"request {r.uid} was refused: "
                                       f"{self.engine.rejections.get(r.uid)}")
                self.live[r.uid] = {"req": r, "last": r.prompt[-1],
                                    "remaining": r.gen_len}
                self.streams[r.uid] = []
        return time.perf_counter() - t0

    def round(self) -> None:
        uids = list(self.live)
        seqs = self.engine.state.sequences
        n = self.quantum
        ctx_tokens = sum(n * seqs[u].seen_tokens + n * (n + 1) // 2
                         for u in uids)
        t0 = time.perf_counter()
        with self.ctx.span("decode_batch"):
            outs = self.engine.decode_batch(
                uids, [self.live[u]["last"] for u in uids], n)
        decode_s = time.perf_counter() - t0
        # the job's own code between the loop's return and the first
        # refill ``put``: a device gap here is the harness's, by name
        with self.ctx.span("harvest"):
            done: List[int] = []
            for u in uids:
                st, got = self.live[u], outs[u]
                self.streams[u].extend(int(t) for t in got)
                st["last"] = int(got[-1])
                st["remaining"] -= len(got)
                if st["remaining"] <= 0:
                    done.append(u)
            for u in done:
                self.finished.append(self.live.pop(u)["req"])
                self.engine.flush(u)
            refills = [next(self.requests) for _ in done]
        refill_s = self._admit(refills)
        self.rounds.append({"live": len(uids), "tokens": len(uids) * n,
                            "decode_s": decode_s, "refill_s": refill_s,
                            "refills": len(done),
                            "context_tokens": ctx_tokens})

    def run_rounds(self, until) -> Dict[str, Any]:
        """Whole rounds until ``until(rounds, elapsed)``; beside them the
        delta of every number the engine counts (``pipeline_stats``),
        copied outside the seconds they took."""
        first = len(self.rounds)
        stats0 = dict(self.engine.pipeline_stats)
        t0 = time.perf_counter()
        while True:
            self.round()
            if until(len(self.rounds) - first, time.perf_counter() - t0):
                break
        elapsed = time.perf_counter() - t0
        return {"elapsed_s": elapsed, "rounds": self.rounds[first:],
                "pipeline": counters_delta(
                    dict(self.engine.pipeline_stats), stats0)}


def _cycle(reqs: List[Request]) -> Iterator[Request]:
    uid = 0
    while True:
        for r in reqs:
            yield Request(uid, r.prompt, r.gen_len)
            uid += 1


def run(ctx: Ctx) -> Dict[str, Any]:
    from deepspeed_tpu.analysis.program_audit import RecompileTripwire
    engine, mt, model_cfg, params = serve_common.build(ctx)
    clients = int(ctx.param("clients"))
    quantum = int(engine.config.decode_loop_steps)
    vocab = model_cfg.vocab_size
    wave = first_wave(ctx.traffic, clients, quantum, ctx.seed, vocab)
    reqs = closed_loop_requests(ctx.traffic, int(ctx.param("planned_requests")),
                                ctx.seed, vocab)
    loop = ClosedLoop(ctx, engine, wave, _cycle(reqs))
    ctx.mark("fill")
    loop.run_rounds(lambda n, _t: n >= 1)
    ctx.mark("warm_round")
    say("setup_compiles", ctx.compiles.snapshot())

    ctx.window_opens()
    n_done0 = len(loop.finished)
    with RecompileTripwire() as trip:
        win = loop.run_rounds(lambda _n, t: t >= ctx.seconds)
    ctx.read_memory_peak()
    rounds = win["rounds"]
    tokens = sum(r["tokens"] for r in rounds)
    tok_s = tokens / win["elapsed_s"]
    steps = len(rounds) * quantum
    obs: Dict[str, Any] = {
        "window_s": win["elapsed_s"], "tokens": tokens, "rounds": len(rounds),
        "decode_steps": steps,
        "decode_s": sum(r["decode_s"] for r in rounds),
        "refill_s": sum(r["refill_s"] for r in rounds),
        "refills": sum(r["refills"] for r in rounds),
        "slot_steps_live": sum(r["live"] for r in rounds) * quantum,
        "slot_steps": steps * engine.config.max_seqs,
        "decode_context_tokens": sum(r["context_tokens"] for r in rounds),
        "pipeline": win["pipeline"],
    }
    say("window", dict(obs, serve_tok_s=tok_s,
                       compiles_in_window=trip.fresh_compiles))
    if ctx.trace:
        with ctx.traced_window():
            tr = loop.run_rounds(
                lambda n, _t: n >= int(ctx.param("trace_rounds")))
        obs["traced"] = {
            "decode_context_tokens": sum(r["context_tokens"]
                                         for r in tr["rounds"]),
            "decode_steps": len(tr["rounds"]) * quantum,
            "rounds": len(tr["rounds"]), "pipeline": tr["pipeline"]}
        obs["attention"] = {"q_heads": model_cfg.num_heads,
                            "kv_heads": model_cfg.num_kv_heads,
                            "head_dim": model_cfg.head_dim,
                            "kv_row": model_cfg.num_kv_heads
                            * model_cfg.head_dim,
                            "layers": model_cfg.num_layers}
    served = loop.finished[n_done0:]
    # shortest prompts first: the reference's dense attention is quadratic
    samples = [(r.prompt, loop.streams[r.uid])
               for r in sorted(served, key=lambda r: len(r.prompt))]
    for u in list(loop.live):
        engine.flush(u)
    del engine, loop.engine
    check = serve_common.check_streams(ctx, mt, model_cfg, params, samples)
    checks = {"no_compile_in_window": trip.fresh_compiles == 0,
              "served_tokens_match_reference": check["ok"],
              "every_slot_live": obs["slot_steps_live"] == steps * clients}
    return {"attempted": len(served), "failed": 0, "checks": checks,
            "compared": serve_common.compared(check), "obs": obs,
            "end_to_end": {"serve_tok_s": tok_s}}
