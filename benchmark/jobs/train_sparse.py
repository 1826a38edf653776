"""Job kind ``train_sparse``: job kind ``train``'s window (``dstpu.initialize``
then ``engine.train_batch`` in a loop on seeded synthetic token batches) for
a SPARSE model that holds one chip's share of its experts, whose FLOPs a
token and whose attention geometry ``train`` cannot state (it counts ``6 x
every parameter`` and one causal square a call).

Before the window, outside the seconds, the numbers that decide ``correct``
are read from the ENGINE'S OWN FIRST STEP on the first timed batch:

(a) BEFORE ``dstpu.initialize`` (the training state leaves no room beside
    it): the plain float32 reference's per-token NLL, per-expert rows and
    GRADIENT of that batch, a sequence at a time, the gradient kept on the
    host.
(b) after ``engine.train_batch`` step 1, a leaf at a time on the device:
    the parameters' CHANGE against a plain AdamW + clip step applied to the
    reference's gradient (``step_update_gap``: a state left unchanged reads
    1), the step's own clipped gradient (its first moment over ``1 - b1``)
    against the clipped reference gradient a leaf group (cosine and norm
    ratio), and the change against plain AdamW applied to the step's OWN
    moments (``optimizer_gap``: the learning rate, the decay, the
    epsilon's place, the bias correction).
(c) the step's loss against the reference's; every selection bias's master
    against ``reference.bias_update`` of the rows the reference counts.
(d) a witness of the forward alone: the model's per-token NLL of the
    batch's first sequence, in the job's compute dtype, against the
    reference's (RMS and worst gap in units of the reference NLL's
    deviation over the sequence).
(e) ``obs``: ``active_params`` from the COUNTED rows (``moe_rows_routed /
    tokens``, not the expected 1.0 a layer), ``active_model_flops_per_s_chip
    = 6 x active_params x tokens/s`` (embedding lookup and attention scores
    not counted, as ``mfu.train`` counts: it can only read low), the
    attention geometry with its window, the counters' window deltas.

``python3 -m benchmark.jobs.train_sparse --workload <cell> --seed <n>`` runs
the same first step against each CONTROL that must fail and prints a line
each: the reference with one equation wrong, the engine over float8 e4m3
weights, on half the batch, with its state left unchanged; and, without
the engine, the model's gradient of one sequence computed in float32 and
in the job's dtype (which of the gap to the reference is the precision's).
"""

from __future__ import annotations

import functools
import importlib
from typing import Any, Dict

from ..common import Ctx, say
from .train import _run_steps

ENGINE_FAULTS = ("float8_weights", "half_batch", "unchanged_state")
WITNESSES = ("float32_compute", "job_compute")


def _nll_gaps(nll, ref_nll) -> Dict[str, float]:
    import numpy as np
    nll, ref_nll = np.asarray(nll, np.float64), np.asarray(ref_nll, np.float64)
    sigma = float(ref_nll.std())
    gap = np.abs(nll - ref_nll)
    return {"nll_rms_gap_sigma": float(np.sqrt((gap ** 2).mean())) / sigma,
            "nll_worst_gap_sigma": float(gap.max()) / sigma,
            "loss_gap": abs(float(nll.mean() - ref_nll.mean()))}


def _group_stats(mt, grads, ref_grads) -> Dict[str, Dict[str, float]]:
    """{group: cosine and norm ratio of a gradient against the
    reference's}, reduced on the device a group."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def stats(g, r):
        f = lambda xs: [x.astype(jnp.float32) for x in xs]   # noqa: E731
        dot = sum(jnp.vdot(a, b) for a, b in zip(f(g), f(r)))
        ng = jnp.sqrt(sum(jnp.vdot(a, a) for a in f(g)))
        nr = jnp.sqrt(sum(jnp.vdot(b, b) for b in f(r)))
        return dot / (ng * nr), ng / nr

    mine, theirs = mt.param_groups(grads), mt.param_groups(ref_grads)
    out = {}
    for group in sorted(theirs):
        cos, ratio = stats(mine[group], theirs[group])
        out[group] = {"cosine": float(cos), "norm_ratio": float(ratio)}
    return out


def _hyper(ds_config: Dict[str, Any]) -> Dict[str, float]:
    """The optimizer's numbers as the job states them (AdamW's defaults
    where it states none)."""
    opt = ds_config["optimizer"]["params"]
    b1, b2 = opt.get("betas", (0.9, 0.999))
    return {"lr": float(opt["lr"]), "b1": float(b1), "b2": float(b2),
            "eps": float(opt.get("eps", 1e-8)),
            "wd": float(opt.get("weight_decay", 0.0)),
            "clip": float(ds_config.get("gradient_clipping", 0.0))}


def _reference(mt, model_cfg, params, batch, wrong=(),
               keep_first: bool = False) -> Dict[str, Any]:
    """The plain reference (or one of its wrong models) on ``batch`` [B,
    T + 1], a sequence at a time: the gradient of the batch's mean loss
    (numpy, on the host), every position's NLL [B, T], the rows each
    expert was chosen for [sparse layers, E]; with ``keep_first`` the
    first sequence's own gradient too."""
    import jax
    import numpy as np
    fn = jax.jit(functools.partial(
        mt.reference.grads_nll_counts,
        **dict(mt.reference_dims(model_cfg), wrong=tuple(wrong))))
    grads, first, nlls, counts = None, None, [], 0
    for i in range(batch.shape[0]):
        g, nll, c = fn(params, batch[i:i + 1])
        g = jax.device_get(g)
        if grads is None:
            grads, first = g, g if keep_first else None
        else:
            grads = jax.tree_util.tree_map(lambda a, b: a + b, grads, g)
        nlls.append(np.asarray(nll[0]))
        counts = counts + np.stack([np.asarray(x) for x in c])
    grads = jax.tree_util.tree_map(lambda a: a / batch.shape[0], grads)
    return {"grads": grads, "grads_first": first, "nll": np.stack(nlls),
            "counts": counts}


def _adam_moments(opt_state):
    """(first, second) moment trees of an optimizer state that has them."""
    import jax
    has = lambda n: hasattr(n, "mu") and hasattr(n, "nu")    # noqa: E731
    node = next(n for n in jax.tree_util.tree_leaves(opt_state, is_leaf=has)
                if has(n))
    return node.mu, node.nu


def _step_readings(mt, params1, opt_state, start, ref_grads,
                   hyper: Dict[str, float], step_grad_norm=None
                   ) -> Dict[str, Any]:
    """What step 1 made of ``start`` (the parameters it read, on the host)
    against the plain step from ``ref_grads``, a leaf at a time on the
    device; ``params1`` / ``opt_state`` are the engine's after the step.

    A leaf group: ``update_gap`` = the worst leaf's ``|change - plain
    change| / |plain change|`` (plain AdamW step 1 on the clipped
    reference gradient), ``optimizer_gap`` = the same against plain AdamW
    on the step's own moments, ``cosine`` / ``norm_ratio`` = the step's
    clipped gradient (first moment over ``1 - b1``) against the clipped
    reference gradient. ``grad_norm_gap``: the step's own gradient norm
    before the clip (``step_grad_norm``, as the engine reports it) against
    the reference gradient's, ``|ratio - 1|``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    lr, b1, b2, eps, wd = (hyper[k] for k in ("lr", "b1", "b2", "eps", "wd"))

    @jax.jit
    def sums(p0, g_ref, p1, mu, nu, factor):
        p0, g_ref, p1, mu, nu = (x.astype(jnp.float32)
                                 for x in (p0, g_ref, p1, mu, nu))
        plain = lambda g, v: -lr * (g / (jnp.sqrt(v) + eps)  # noqa: E731
                                    + wd * p0)
        gr, ge, d = g_ref * factor, mu / (1.0 - b1), p1 - p0
        u_ref, u_own = plain(gr, gr * gr), plain(ge, nu / (1.0 - b2))
        sq = lambda x: jnp.vdot(x, x)                        # noqa: E731
        return jnp.stack([sq(d - u_ref), sq(u_ref), sq(d - u_own),
                          sq(u_own), jnp.vdot(ge, gr), sq(ge), sq(gr)])

    leaves = lambda t: jax.tree_util.tree_leaves(t)          # noqa: E731
    norm = float(np.sqrt(sum(float(np.vdot(g, g).real)
                             for g in leaves(ref_grads))))
    factor = min(1.0, hyper["clip"] / norm) if hyper["clip"] > 0 else 1.0
    mu, nu = _adam_moments(opt_state)
    rows: Dict[str, list] = {}
    for (path, p1), p0, g, m, v in zip(
            jax.tree_util.tree_leaves_with_path(params1), leaves(start),
            leaves(ref_grads), leaves(mu), leaves(nu)):
        group = mt.group_of(jax.tree_util.keystr(path))
        if group is not None:
            rows.setdefault(group, []).append(
                np.asarray(sums(p0, g, p1, m, v, factor), np.float64))
    ratio = lambda a, b: float(np.sqrt(a / b)) if b > 0 \
        else float("nan")                                    # noqa: E731
    out = {}
    for group, rs in sorted(rows.items()):
        t = np.sum(rs, axis=0)
        out[group] = {
            "update_gap": max(ratio(r[0], r[1]) for r in rs),
            "optimizer_gap": max(ratio(r[2], r[3]) for r in rs),
            "cosine": float(t[4] / np.sqrt(t[5] * t[6]))
            if t[5] > 0 and t[6] > 0 else float("nan"),
            "norm_ratio": ratio(t[5], t[6])}
    return {"reference_grad_norm": norm, "step_grad_norm": step_grad_norm,
            "grad_norm_gap": abs(step_grad_norm / norm - 1.0)
            if step_grad_norm is not None else 1e30, "groups": out}


def _summary(found: Dict[str, Any]) -> Dict[str, float]:
    """The numbers the limits are on, of one comparison a leaf group
    (``found``: ``{"groups": .., "grad_norm_gap": ..}``). A group with no
    gradient on one side (a cosine that is no number) reads as opposed and
    unbounded."""
    import math
    g = found["groups"].values()
    cos = [v["cosine"] if math.isfinite(v["cosine"]) else -1.0 for v in g]
    ratio = [max(v["norm_ratio"], 1.0 / v["norm_ratio"])
             if math.isfinite(v["norm_ratio"]) and v["norm_ratio"] > 0
             else 1e30 for v in g]
    out = {"step_grad_min_cosine": min(cos),
           "step_grad_worst_norm_ratio": max(ratio)}
    if "grad_norm_gap" in found:
        out["step_grad_norm_gap"] = found["grad_norm_gap"]
    for key, name in (("update_gap", "step_update_gap"),
                      ("optimizer_gap", "optimizer_gap")):
        if all(key in v for v in g):      # a witness has cosines alone
            out[name] = max(v[key] if math.isfinite(v[key]) else 1e30
                            for v in g)
    return out


def _setup(ctx: Ctx):
    import jax
    import jax.numpy as jnp
    job = ctx.traffic
    mt = importlib.import_module(
        f"benchmark.model_types.{ctx.config['model_type']}")
    model_cfg = mt.model_config(ctx.model_dims(), job["param_dtype"])
    ds_config = dict(job["ds_config"], mesh=job["mesh"])
    compute = jnp.bfloat16 if ds_config.get("bf16", {}).get(
        "enabled") else jnp.float32
    if ctx.rehearse:           # the CPU walks the control flow in float32
        import dataclasses
        model_cfg = dataclasses.replace(model_cfg, dtype=jnp.float32)
        ds_config["bf16"] = {"enabled": False}
        compute = jnp.float32
    seq = model_cfg.max_seq_len - 1
    B = ds_config["train_micro_batch_size_per_gpu"] \
        * ds_config["gradient_accumulation_steps"] \
        * int(job["mesh"].get("data", 1))
    tokens = jax.jit(lambda k: jax.random.randint(
        k, (job["distinct_batches"], B, seq + 1), 0, model_cfg.vocab_size,
        jnp.int32))(jax.random.PRNGKey((ctx.seed + 1) % (2 ** 31)))
    return job, mt, model_cfg, ds_config, compute, tokens


def _first_step(ctx: Ctx, job, mt, model_cfg, ds_config, params_box: list,
                batch, fault=None):
    """``dstpu.initialize`` on the parameters and ONE ``engine.train_batch``
    of ``batch`` [B, T + 1], with ``fault`` planted (one of
    :data:`ENGINE_FAULTS`, a control). The parameters are TAKEN out of
    ``params_box`` (a one-element list whose owner keeps no other name for
    them): the engine makes its own copy, and a second float32 tree left
    alive beside the training state leaves the step no room on the chip.
    Returns the engine, the parameters the step read (on the host) and the
    step's ``_run_steps`` record."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as dstpu
    from deepspeed_tpu.config.config import MeshConfig
    params = params_box.pop()
    if fault == "float8_weights":
        # every matrix through float8 e4m3, the nearest precision below
        # the bfloat16 the job computes in (the compiler may keep the
        # precision a cast and its inverse drop: the option forbids it)
        params = jax.jit(lambda tree: jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float8_e4m3fn).astype(p.dtype)
            if p.ndim >= 2 else p, tree), donate_argnums=0,
            compiler_options={"xla_allow_excess_precision": False})(params)
    if fault == "half_batch":
        half = batch[:max(batch.shape[0] // 2, 1)]
        batch = jnp.concatenate([half, half])[:batch.shape[0]]
    engine, _, _, _ = dstpu.initialize(
        loss_fn=mt.loss_fn(model_cfg), params=params,
        topology=dstpu.build_mesh(MeshConfig(**job["mesh"]),
                                  devices=ctx.devices),
        config=ds_config)
    del params
    ctx.mark("init")
    start = jax.device_get(engine.state.params)
    if fault == "unchanged_state":
        return engine, start, {"losses": [float("nan")]}
    step = _run_steps(ctx, engine, [{"tokens": batch}], lambda s, _t: s >= 1)
    return engine, start, step


def run(ctx: Ctx) -> Dict[str, Any]:
    import jax
    import numpy as np

    from deepspeed_tpu.analysis.program_audit import RecompileTripwire

    job, mt, model_cfg, ds_config, compute, tokens = _setup(ctx)
    tol, hyper = job["tolerances"], _hyper(ds_config)
    params, _ = mt.make(model_cfg, ctx.seed)
    n_params = sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
    seq = tokens.shape[-1] - 1

    # (a) the reference on the first timed batch, (d) the forward's witness
    ref = _reference(mt, model_cfg, params, tokens[0])
    witness = _nll_gaps(mt.nll(model_cfg, compute)(params, tokens[0, :1])[0],
                        ref["nll"][0])
    say("forward_witness", witness)
    ctx.mark("reference")

    # (b) the engine's own first step on that batch
    box = [params]
    del params
    engine, start, first = _first_step(ctx, job, mt, model_cfg, ds_config,
                                       box, tokens[0])
    B = engine.config.train_batch_size
    assert B == tokens.shape[1], (B, tokens.shape)
    batches = [{"tokens": tokens[i]} for i in range(tokens.shape[0])]
    say("job", {"n_params": n_params, "global_batch": B, "seq": seq,
                "mesh": dict(engine.topology.axis_sizes),
                "zero_stage": engine.zero_plan.stage,
                "held": list(model_cfg.held),
                "param_dtype": str(jax.tree_util.tree_leaves(
                    engine.state.params)[0].dtype)})
    found = _step_readings(mt, engine.state.params, engine.state.opt_state,
                           start, ref["grads"], hyper,
                           engine.get_global_grad_norm())
    step = _summary(found)
    say("first_step_check", found)

    # (c) the loss, and the selection biases' masters
    sparse = [i for i in range(model_cfg.num_layers) if model_cfg.sparse(i)]
    bias = lambda tree: np.stack([np.asarray(                # noqa: E731
        tree[f"layer_{i}"]["moe"]["select_bias"]) for i in sparse])
    bias0, bias1 = bias(start), bias(engine.state.params)
    del start, ref["grads"]
    ctx.mark("first_step")
    rest = _run_steps(ctx, engine, batches[:1],
                      lambda s, _t: s >= int(job["warm_repeats"]) - 1)
    warm_losses = first["losses"] + rest["losses"]
    ctx.mark("warm_steps")
    coeff = model_cfg.load_balance_coeff
    off_rule = float(np.abs(np.abs(bias1 - bias0)
                            - coeff * (bias1 != bias0)).max())
    want = np.sign(np.stack([np.asarray(mt.reference.bias_update(b, c, coeff))
                             for b, c in zip(bias0, ref["counts"])]) - bias0)
    bias_share = float(np.mean(np.sign(bias1 - bias0) == want))
    first_loss, ref_loss = warm_losses[0], float(ref["nll"].mean())
    checks = {
        "loss_finite": bool(np.all(np.isfinite(warm_losses))),
        "loss_falls": warm_losses[-1] < warm_losses[0],
        "first_loss_matches_reference":
            abs(first_loss - ref_loss) <= tol["loss"],
        "first_step_matches_plain_adamw":
            step["step_update_gap"] <= tol["step_update_gap"]
            and step["optimizer_gap"] <= tol["optimizer_gap"],
        "step_gradient_matches_reference":
            step["step_grad_min_cosine"] >= tol["step_grad_min_cosine"]
            and step["step_grad_worst_norm_ratio"]
            <= tol["step_grad_worst_norm_ratio"]
            and step["step_grad_norm_gap"] <= tol["step_grad_norm_gap"],
        "forward_matches_reference":
            witness["nll_rms_gap_sigma"] <= tol["nll_rms_gap_sigma"]
            and witness["nll_worst_gap_sigma"] <= tol["nll_worst_gap_sigma"],
        "bias_moves_by_the_rule":
            off_rule <= 1e-6 and bias_share >= tol["bias_agree_share"],
    }
    compared = {
        "first_loss_gap": {"value": abs(first_loss - ref_loss),
                           "limit": tol["loss"]},
        "bias_off_rule": {"value": off_rule, "limit": 1e-6},
        "bias_agree_share": {"value": bias_share,
                             "limit": tol["bias_agree_share"]}}
    compared.update({k: {"value": v, "limit": tol[k]}
                     for k, v in {**step, **witness}.items() if k in tol})
    say("correct", {"first_loss": first_loss, "reference_loss": ref_loss,
                    "warm_losses": warm_losses, "bias_off_rule": off_rule,
                    "bias_agree_share": bias_share, "checks": checks})

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
    stats = win["step_stats"]
    routed = stats.get("moe_rows_routed", 0)
    active = mt.active_params(model_cfg, routed / max(tokens_done, 1))
    say("window", {"steps": win["steps"], "elapsed_s": win["elapsed_s"],
                   "tokens": tokens_done, "compiles": trip.fresh_compiles,
                   "last_loss": win["losses"][-1], "active_params": active,
                   "rows_routed_per_token": routed / max(tokens_done, 1)})
    kinds = model_cfg.layer_kinds
    obs: Dict[str, Any] = {
        "steps": win["steps"], "window_s": win["elapsed_s"],
        "tokens": tokens_done, "n_params": n_params, "chips": chips,
        "train_tok_s_chip": tok_s_chip,
        "step_ms": 1e3 * win["elapsed_s"] / win["steps"],
        "step_stats": stats, "active_params": active,
        "active_model_flops_per_s_chip": 6.0 * active * tok_s_chip,
        "attention": {"batch": B // chips, "heads": model_cfg.num_heads,
                      "kv_heads": model_cfg.num_kv_heads, "seq": seq,
                      "head_dim": model_cfg.head_dim,
                      "window": model_cfg.sliding_window,
                      "window_layers": sum(k == "swa" for k in kinds),
                      "full_layers": sum(k == "attn" for k in kinds)},
        "first_step_check": found, "forward_witness": witness,
    }
    if ctx.trace:
        with ctx.traced_window():
            tr = _run_steps(ctx, engine, batches,
                            lambda s, _t: s >= int(job["trace_steps"]))
        obs["traced_steps"] = tr["steps"]
    return {"attempted": win["steps"], "failed": 0, "checks": checks,
            "compared": compared, "obs": obs,
            "end_to_end": {"train_tok_s": tok_s_chip}}


def controls(argv=None) -> int:
    """Each control that must FAIL beside the sound step (``right``), one
    printed line each with the numbers the limits are on: a wrong model of
    the reference against the sound engine's first step; the engine with a
    fault planted against the right reference; and (no engine) the model's
    gradient of one sequence in float32 and in the job's dtype."""
    import argparse
    import json
    import time

    import jax
    import jax.numpy as jnp

    from ..common import load_cell, load_manifest
    ap = argparse.ArgumentParser(description=controls.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    else:
        from deepspeed_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
    from deepspeed_tpu.utils.dtypes import cast_floating
    entry, cell, config, traffic = load_cell(load_manifest(), args.workload)
    ctx = Ctx(cell_name=entry["name"], cell=cell, config=config,
              traffic=traffic, seed=args.seed, seconds=0.0, trace=False,
              rehearse=args.rehearse, t_process=time.perf_counter())
    job, mt, model_cfg, ds_config, compute, tokens = _setup(ctx)
    hyper, batch = _hyper(ds_config), tokens[0]
    wrong = mt.reference.WRONG
    names = [n for n in ("right",) + WITNESSES + wrong + ENGINE_FAULTS
             if not args.only or n in args.only.split(",")]

    def line(name, **found):
        print(json.dumps({"control": name, "seed": args.seed, **found}),
              flush=True)

    params, _ = mt.make(model_cfg, args.seed)
    witnessed = [n for n in WITNESSES if n in names]
    refs = {n: _reference(mt, model_cfg, params, batch,
                          (n,) if n in wrong else (),
                          keep_first=n == "right" and bool(witnessed))
            for n in ["right"] + [n for n in names if n in wrong]}
    # the forward's witness: the model's NLL of the first sequence, in
    # the job's dtype, against each reference's
    model_nll = jax.device_get(
        mt.nll(model_cfg, compute)(params, batch[:1])[0])
    for name in witnessed:
        # the model's gradient of the first sequence, no engine: computed
        # in float32 throughout (products at highest precision; the flash
        # kernels at half the blocks, which is what fits VMEM at four
        # bytes) and as the job computes it
        import dataclasses
        cfg, dtype = model_cfg, compute
        if name == "float32_compute":
            dtype = jnp.float32
            cfg = dataclasses.replace(
                model_cfg, dtype=dtype,
                flash_block_q=max(model_cfg.flash_block_q // 2, 8),
                flash_block_k=max(model_cfg.flash_block_k // 2, 8))
        loss = mt.loss_fn(cfg)
        with jax.default_matmul_precision(
                "highest" if dtype == jnp.float32 else "default"):
            grads = jax.jit(jax.grad(lambda p, t: loss(
                cast_floating(p, dtype), {"tokens": t}, None)[0]))(
                    params, batch[:1])
        groups = _group_stats(mt, grads, refs["right"]["grads_first"])
        del grads
        line(name, **_summary({"groups": groups}), groups=groups)
    refs["right"]["grads_first"] = None

    box = [params]
    del params

    def first_step(fault=None):
        if not box:
            box.append(mt.make(model_cfg, args.seed)[0])
        engine, start, _ = _first_step(ctx, job, mt, model_cfg, ds_config,
                                       box, batch, fault)
        return engine, start

    def free(engine):
        for leaf in jax.tree_util.tree_leaves(engine.state):
            if isinstance(leaf, jax.Array):
                leaf.delete()

    if set(names) & set(("right",) + wrong):
        engine, start = first_step()
        for name in names:
            if name == "right" or name in wrong:
                found = _step_readings(
                    mt, engine.state.params, engine.state.opt_state, start,
                    refs[name]["grads"], hyper,
                    engine.get_global_grad_norm())
                line(name, **_summary(found),
                     **_nll_gaps(model_nll, refs[name]["nll"][0]), **found)
        free(engine)
    for name in names:
        if name in ENGINE_FAULTS:
            engine, start = first_step(name)
            found = _step_readings(
                mt, engine.state.params, engine.state.opt_state, start,
                refs["right"]["grads"], hyper,
                engine.get_global_grad_norm())
            line(name, **_summary(found), **found)
            free(engine)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(controls())
