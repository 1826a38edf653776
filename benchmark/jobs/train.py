"""Job kind ``train``: ``dstpu.initialize`` then ``engine.train_batch`` in a
loop, on seeded synthetic token batches made on the device.

The traffic file is the job: micro-batch, mesh, the precision recipe and
the ``ds_config`` handed to ``dstpu.initialize``. Before the window the
engine's first-step loss is compared with the plain reference's loss on the
same parameters and batch, and the loss must be finite and fall over a few
repeats of that batch. The window then runs whole steps on distinct batches
until ``seconds`` have passed, one step kept in flight, and ends in
``block_until_ready``: tokens of those steps over the seconds they took.
"""

from __future__ import annotations

import importlib
import time
from typing import Any, Dict, List

from ..common import Ctx, counters_delta, say


def _run_steps(ctx: Ctx, engine, batches: List[Any], until) -> Dict[str, Any]:
    """Whole steps until ``until(steps, elapsed)``; returns steps, seconds,
    the losses (read after the clock stops) and the delta of every number
    the engine counts in ``step_stats`` (copied outside the seconds)."""
    import jax
    losses, prev, steps = [], None, 0
    stats0 = dict(engine.step_stats)
    t0 = time.perf_counter()
    while True:
        with ctx.span("train_batch"):
            loss = engine.train_batch(batches[steps % len(batches)])
        if prev is not None:
            jax.block_until_ready(prev)
        losses.append(loss)
        prev, steps = loss, steps + 1
        if until(steps, time.perf_counter() - t0):
            break
    jax.block_until_ready(prev)
    elapsed = time.perf_counter() - t0
    return {"steps": steps, "elapsed_s": elapsed,
            "step_stats": counters_delta(dict(engine.step_stats), stats0),
            "losses": [float(v) for v in losses]}


def run(ctx: Ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as dstpu
    from deepspeed_tpu.analysis.program_audit import RecompileTripwire
    from deepspeed_tpu.config.config import MeshConfig

    job = ctx.traffic
    mt = importlib.import_module(
        f"benchmark.model_types.{ctx.config['model_type']}")
    model_cfg = mt.model_config(ctx.model_dims(), job["param_dtype"])
    params, loss_fn = mt.make(model_cfg, ctx.seed)
    n_params = sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
    topology = dstpu.build_mesh(MeshConfig(**job["mesh"]),
                                devices=ctx.devices)
    engine, _, _, _ = dstpu.initialize(
        loss_fn=loss_fn, params=params, topology=topology,
        config=dict(job["ds_config"], mesh=job["mesh"]))
    del params
    ctx.mark("init")
    B = engine.config.train_batch_size
    seq = model_cfg.max_seq_len - 1
    tokens = jax.jit(lambda k: jax.random.randint(
        k, (job["distinct_batches"], B, seq + 1), 0, model_cfg.vocab_size,
        jnp.int32))(jax.random.PRNGKey((ctx.seed + 1) % (2 ** 31)))
    batches = [{"tokens": tokens[i]} for i in range(tokens.shape[0])]
    say("job", {"n_params": n_params, "global_batch": B, "seq": seq,
                "mesh": dict(engine.topology.axis_sizes),
                "zero_stage": engine.zero_plan.stage,
                "param_dtype": str(jax.tree_util.tree_leaves(
                    engine.state.params)[0].dtype)})

    # the plain reference on the very parameters the first step will read,
    # one sequence at a time (so any number fits beside the training state)
    ref = mt.reference_loss(model_cfg)
    ref_losses = [float(ref(engine.state.params, tokens[0, i:i + 1]))
                  for i in range(B)]
    del ref
    ctx.mark("reference")
    warm = _run_steps(ctx, engine, batches[:1],
                      lambda s, _t: s >= int(job["warm_repeats"]))
    ctx.mark("warm_steps")
    first_loss = warm["losses"][0]
    ref_loss = float(np.mean(ref_losses))
    tol = float(job["loss_tolerance"])
    checks = {
        "loss_finite": bool(np.all(np.isfinite(warm["losses"]))),
        "loss_falls": warm["losses"][-1] < warm["losses"][0],
        "first_loss_matches_reference": abs(first_loss - ref_loss) <= tol,
    }
    say("correct", {"first_loss": first_loss, "reference_loss": ref_loss,
                    "tolerance": tol, "warm_losses": warm["losses"],
                    "checks": checks})

    ctx.window_opens()
    with RecompileTripwire() as trip:
        win = _run_steps(ctx, engine, batches,
                         lambda _s, t: t >= ctx.seconds)
    ctx.read_memory_peak()
    checks["no_compile_in_window"] = trip.fresh_compiles == 0
    checks["window_loss_finite"] = bool(np.all(np.isfinite(win["losses"])))
    tokens_done = win["steps"] * B * seq
    chips = len(ctx.devices)
    tok_s_chip = tokens_done / win["elapsed_s"] / chips
    say("window", {"steps": win["steps"], "elapsed_s": win["elapsed_s"],
                   "tokens": tokens_done, "compiles": trip.fresh_compiles,
                   "last_loss": win["losses"][-1]})
    obs: Dict[str, Any] = {
        "steps": win["steps"], "window_s": win["elapsed_s"],
        "tokens": tokens_done, "n_params": n_params, "chips": chips,
        "train_tok_s_chip": tok_s_chip,
        "step_ms": 1e3 * win["elapsed_s"] / win["steps"],
        "step_stats": win["step_stats"],
        "model_flops_per_s_chip": 6.0 * n_params * tok_s_chip,
        "attention": {"batch": B // chips, "heads": model_cfg.num_heads,
                      "seq": seq,
                      "head_dim": model_cfg.hidden_size
                      // model_cfg.num_heads},
    }
    if ctx.trace:
        with ctx.traced_window():
            tr = _run_steps(ctx, engine, batches,
                            lambda s, _t: s >= int(job["trace_steps"]))
        obs["traced_steps"] = tr["steps"]
    return {"attempted": win["steps"], "failed": 0, "checks": checks,
            "compared": {"first_loss_gap": {
                "value": abs(first_loss - ref_loss), "limit": tol}},
            "obs": obs, "end_to_end": {"train_tok_s": tok_s_chip}}
