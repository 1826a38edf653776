"""Find an open-loop cell's knee once, on the chip: the same engine, the
same generator and serving loop as the cell, at a ladder of fixed rates.

    python3 benchmark/sweep.py --workload serve-chat-steady \
        --rates 6,8,10,12 --seconds 20 --out chiprun_out/sweep.json

The result is kept under ``benchmark/sweeps/``; the cell's ``rate_rps`` is
four fifths of the highest rate whose backlog does not grow. No cell runs
this: a cell offers load at its fixed rate and does not search.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    from benchmark.common import (CompileClock, Ctx, fail, load_cell,
                                  load_manifest, percentile)
    from benchmark.jobs import open_loop, serve_common
    from benchmark.traffic import arrivals_schedule
    entry, cell, config, traffic = load_cell(load_manifest(), args.workload)
    if jax.devices()[0].platform != "tpu":
        fail("the sweep needs a TPU")
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ctx = Ctx(cell_name=entry["name"], cell=cell, config=config,
              traffic=traffic, seed=args.seed, seconds=args.seconds,
              trace=False, rehearse=False, t_process=T_PROCESS)
    ctx.compiles = CompileClock()
    engine, _, model_cfg, _ = serve_common.build(ctx)
    open_loop.warm_up(ctx, engine, model_cfg.vocab_size)
    ramp_s, drain_s = float(cell["ramp_s"]), float(cell["drain_s"])
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        schedule = arrivals_schedule(
            ctx.traffic, rate, [("ramp", ramp_s), ("window", args.seconds),
                                ("tail", drain_s)],
            args.seed, model_cfg.vocab_size)
        loop = open_loop.OpenLoop(ctx, engine, schedule, cell["decode_burst"],
                                  cell["admit_max"])
        th = loop.start()
        loop.serve_until(ramp_s)
        backlog0 = len(loop.queue) + len(loop.live)
        loop.recording = True
        loop.serve_until(ramp_s + args.seconds)
        loop.recording = False
        backlog1 = len(loop.queue) + len(loop.live)
        waiting1 = len(loop.queue)
        uids = [r.uid for r in schedule if r.segment == "window"]
        loop.serve_until(ramp_s + args.seconds + drain_s, done=lambda: all(
            u in loop.t_last or u in loop.refused for u in uids))
        s = loop.sample()
        loop.close(th)
        steps = sum(b[0] for b in loop.bursts)
        row = {"rate_rps": rate, "due_in_window": s["n"],
               "failed": s["failed"],
               "ttft_p50_ms": 1e3 * percentile(s["ttft_s"], 50),
               "ttft_p90_ms": 1e3 * percentile(s["ttft_s"], 90),
               "tpot_p50_ms": 1e3 * percentile(s["tpot_s"], 50),
               "tpot_p90_ms": 1e3 * percentile(s["tpot_s"], 90),
               "goodput_share": 100.0 * sum(s["met_limits"]) / s["n"],
               "live_and_waiting_at_window_start": backlog0,
               "live_and_waiting_at_window_end": backlog1,
               "waiting_at_window_end": waiting1,
               "mean_live": sum(b[0] * b[1] for b in loop.bursts)
               / max(1, steps),
               "decode_tokens_per_s": sum(b[2] for b in loop.bursts)
               / args.seconds,
               "slow_calls": loop.slow_calls,
               "programs_so_far": ctx.compiles.programs}
        print(json.dumps(row), flush=True)
        rows.append(row)
    dev = jax.devices()[0]
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "seed": args.seed,
                   "seconds": args.seconds,
                   "device": {"platform": dev.platform,
                              "kind": dev.device_kind},
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
