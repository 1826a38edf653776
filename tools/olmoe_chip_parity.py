"""OLMoE at the published widths on the chip, against the plain reference.

    python tools/olmoe_chip_parity.py [--seed N]      # on a TPU host
    python tools/olmoe_chip_parity.py --rehearse      # control flow, CPU, toy

Outside any timed window: the configuration ``serve-olmoe-rollout`` runs
(``benchmark/configs/olmoe-1b-7b.json``: hidden 2048, 16 heads of 128, 64
experts of width 1024 top-8, 8 layers, bfloat16 weights from the seed) is
served through ``InferenceEngineV2`` on the cell's cache geometry (two
640-token blocks a sequence, prefill chunks of 512) and compared with
``benchmark/reference/olmoe.py``'s full float32 forward on 4 seeded
sequences x 32 positions each: the last prompt position (two prefill
chunks), 22 single-token steps through the cache (contexts crossing the
block boundary at 640), then 8 steps of the fused ``decode_batch`` loop
and single-token steps that read the ring rows it flushed.

Two comparisons, because with random weights a wrong expert moves the
logits of an 8-layer model little:

* LOGITS of the 8-layer model, in deviations of the reference's row;
* the HIDDEN STATE after layer 1's sparse block: a one-layer engine on the
  same first layer whose final norm has scale one and whose head is the
  identity serves the RMS-normalised residual stream as its "logits".

Tolerances (the reasons; the readings are in PERF.md section 6, PR 27).
The engine computes in bfloat16 (8 bits of mantissa: a rounding is 2^-9 =
0.2 % of a value) against float32 at ``highest``. After one layer a
position's hidden state may differ from the reference's by HIDDEN_TOL =
3 % of its length, in the median position and in every position but
those where the reference's own router holds its 8th and 9th expert
within TIE_LOGIT = 0.02 of each other in log probability: there a
bfloat16 rounding of the router's input picks the other one, and the
step is two experts' outputs at the smallest kept weight (the first chip
run read 25 % at one such position of 128 and 0.8 % in the median; the
limit of 12 % for every position set before that run was wrong). The
same reference with the smallest of the 8 experts LEFT OUT (``top_k``
7), the least a wrong expert can cost, must lie outside HIDDEN_TOL in
the median, or the comparison could not see an expert at all. The
logits may differ by LOGIT_TOL = 0.06 deviations of the row in the
median position and 0.3, the figure the cell's ``correct`` allows the
served token, in the worst (eight layers of such ties add up: the first
run read 0.031 and 0.144); the reference with its weights rounded to
float8 (e4m3, the nearest precision below the bfloat16 the configuration
states) reads about four times that and must come out as NOT correct by
the cell's own rule.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

HIDDEN_TOL = 0.03
TIE_LOGIT = 0.02
LOGIT_TOL = 0.06
LOGIT_TOL_WORST = 0.3
POSITIONS = 32
SINGLE_BEFORE, FUSED = 22, 8


def serve_rows(engine, prompts, vocab_rows=None):
    """Per sequence: the [POSITIONS, width] rows ``put`` returned (the
    last prompt position, then single-token steps before and after one
    fused loop) and the tokens fed, teacher-forced on the served argmax."""
    import numpy as np
    uids = list(range(len(prompts)))
    rows = {u: [] for u in uids}
    streams = {u: list(p) for u, p in enumerate(prompts)}

    def pick(u, row):
        rows[u].append(np.asarray(row, np.float32))
        return int(np.argmax(row[:vocab_rows]))

    out = engine.put(uids, prompts)
    nxt = {u: pick(u, out[u]) for u in uids}
    for _ in range(SINGLE_BEFORE):
        out = engine.put(uids, [[nxt[u]] for u in uids])
        for u in uids:
            streams[u].append(nxt[u])
            nxt[u] = pick(u, out[u])
    fused = engine.decode_batch(uids, [nxt[u] for u in uids], FUSED)
    for u in uids:
        streams[u] += [nxt[u]] + [int(t) for t in fused[u][:-1]]
        nxt[u] = int(fused[u][-1])
    while len(rows[uids[0]]) < POSITIONS:
        out = engine.put(uids, [[nxt[u]] for u in uids])
        for u in uids:
            streams[u].append(nxt[u])
            nxt[u] = pick(u, out[u])
    return rows, streams


def row_positions(prompt_len):
    """Positions of ``serve_rows``'s rows in the served stream."""
    first = [prompt_len - 1 + i for i in range(1 + SINGLE_BEFORE)]
    rest = POSITIONS - len(first)
    start = first[-1] + FUSED + 1
    return first + [start + i for i in range(rest)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3000000731)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    elif jax.devices()[0].platform != "tpu":
        print(f"needs a TPU; JAX found {jax.devices()}", file=sys.stderr)
        return 3
    from benchmark.common import load_json
    from benchmark.model_types import olmoe as mt
    from benchmark.reference import olmoe as reference
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    if not args.rehearse:
        enable_compile_cache()
    dims = load_json("configs", "olmoe-1b-7b.json")
    if args.rehearse:
        dims.update(dims["rehearse"])
    cfg = mt.model_config(dims)
    params = mt.init_params(cfg, args.seed)
    cell = load_json("cells", "serve-olmoe-rollout.json")["engine"]
    block = 640 if not args.rehearse else 64
    icfg = RaggedInferenceConfig(**dict(
        cell, max_seqs=4, num_blocks=10, block_size=block,
        chunk_size=512 if not args.rehearse else 48,
        decode_loop_steps=FUSED,
        dtype="bfloat16" if not args.rehearse else "float32"))
    if args.rehearse:
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    rs = np.random.RandomState(args.seed % (2 ** 31))
    # contexts that cross the block boundary while decoding
    lens = [block - 30, block - 20, block - 10, block + 5]
    prompts = [list(map(int, rs.randint(1, cfg.vocab_size, n)))
               for n in lens]
    kw = dict(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
              rope_theta=cfg.rope_theta, rms_eps=cfg.rms_eps)
    result = {"device": jax.devices()[0].device_kind, "seed": args.seed,
              "widths": {k: dims[k] for k in (
                  "hidden_size", "intermediate_size", "num_experts",
                  "num_experts_per_tok", "num_hidden_layers")}}

    def padded(streams):
        T = max(len(s) for s in streams.values())
        toks = np.zeros((len(streams), T), np.int32)
        for u, s in streams.items():
            toks[u, :len(s)] = s        # right padding: causal, unseen
        at = np.stack([row_positions(n) for n in lens]).astype(np.int32)
        return jnp.asarray(toks), jnp.asarray(at)

    # ---- the hidden state after layer 1's sparse block ---- #
    C = cfg.hidden_size
    one = dataclasses.replace(cfg, num_layers=1)
    p_one = {"embed": params["embed"], "layer_0": params["layer_0"],
             "final_norm": {"scale": jnp.ones((C,), jnp.float32)},
             "lm_head": {"kernel": jnp.eye(C, dtype=cfg.param_dtype)}}
    eng = InferenceEngineV2(one, p_one, icfg)
    rows, streams = serve_rows(eng, prompts, vocab_rows=C)
    del eng
    toks, at = padded(streams)

    def normed_hidden(top_k):
        @jax.jit
        def f(p, toks, at):
            x = reference.hidden_states(p, toks, top_k=top_k, layers=1, **kw)
            x = jnp.take_along_axis(x, at[..., None], axis=1)
            return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                                     + cfg.rms_eps)
        return np.asarray(f(params, toks, at), np.float32)

    ref = normed_hidden(cfg.experts_top_k)
    one_less = normed_hidden(cfg.experts_top_k - 1)
    served = np.stack([np.stack(rows[u]) for u in sorted(rows)])

    @jax.jit
    def router_margin(p, toks, at):
        """log p of the reference's 8th expert minus its 9th's, layer 1."""
        with jax.default_matmul_precision("highest"):
            lp = p["layer_0"]
            x = p["embed"]["embedding"].astype(jnp.float32)[toks]
            x = x + reference._attention(
                lp["attn"], reference._rms(x, lp["input_norm"]["scale"],
                                           cfg.rms_eps), **kw)
            h = reference._rms(x, lp["post_attn_norm"]["scale"], cfg.rms_eps)
            top = jax.lax.top_k(jax.nn.log_softmax(
                h @ lp["moe"]["gate"].astype(jnp.float32)),
                cfg.experts_top_k + 1)[0]
            return jnp.take_along_axis(top[..., -2] - top[..., -1], at,
                                       axis=1)

    margin = np.asarray(router_margin(params, toks, at))

    def rel(a, b):
        return np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)

    err, miss = rel(served, ref), rel(one_less, ref)
    off = err > HIDDEN_TOL
    result["hidden_after_layer_1"] = {
        "positions": int(err.size),
        "served_rel_err_median": float(np.median(err)),
        "served_rel_err_p90": float(np.percentile(err, 90)),
        "served_rel_err_max": float(err.max()),
        "positions_over_tolerance": int(off.sum()),
        "their_router_margins": [float(m) for m in margin[off]],
        "their_rel_errs": [float(e) for e in err[off]],
        "positions_with_a_router_tie": int((margin < TIE_LOGIT).sum()),
        "one_expert_left_out_rel_err_median": float(np.median(miss)),
        "one_expert_left_out_rel_err_min": float(miss.min()),
        "tolerance": HIDDEN_TOL, "tie_logit": TIE_LOGIT}
    ok_hidden = bool(np.median(err) <= HIDDEN_TOL
                     and (margin[off] < TIE_LOGIT).all()
                     and np.median(miss) > HIDDEN_TOL)
    print(json.dumps(result["hidden_after_layer_1"]), flush=True)

    # ---- the logits of the whole configuration ---- #
    eng = InferenceEngineV2(cfg, params, icfg)
    rows, streams = serve_rows(eng, prompts)
    del eng
    toks, at = padded(streams)
    logits = mt.reference_logits(cfg)
    ref = np.asarray(logits(params, toks, at), np.float32)
    served = np.stack([np.stack(rows[u]) for u in sorted(rows)])
    sigma = ref.std(-1, keepdims=True)
    gap = np.abs(served - ref).max(-1) / sigma[..., 0]
    same = served.argmax(-1) == ref.argmax(-1)

    def cell_rule(tokens_served):
        """The cell's ``correct`` rule on served tokens [B, n]."""
        best = ref.max(-1)
        got = np.take_along_axis(ref, tokens_served[..., None], -1)[..., 0]
        g = (best - got) / sigma[..., 0]
        return {"worst_gap_sigma": float(g.max()),
                "same_top1_share": float((g == 0).mean()),
                "correct": bool(g.max() <= 0.3 and (g == 0).mean() >= 0.9)}

    f8 = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        if x.ndim >= 2 else x, params)
    low = np.asarray(logits(f8, toks, at), np.float32)
    result["logits"] = {
        "positions": int(gap.size),
        "served_max_abs_err_sigma_worst": float(gap.max()),
        "served_max_abs_err_sigma_median": float(np.median(gap)),
        "served_same_top1_share": float(same.mean()),
        "served_by_the_cells_rule": cell_rule(served.argmax(-1)),
        "float8_reference_by_the_cells_rule": cell_rule(low.argmax(-1)),
        "float8_reference_max_abs_err_sigma_worst": float(
            (np.abs(low - ref).max(-1) / sigma[..., 0]).max()),
        "float8_reference_max_abs_err_sigma_median": float(np.median(
            np.abs(low - ref).max(-1) / sigma[..., 0])),
        "tolerance_sigma_median": LOGIT_TOL,
        "tolerance_sigma_worst": LOGIT_TOL_WORST}
    ok_logits = np.median(gap) <= LOGIT_TOL \
        and gap.max() <= LOGIT_TOL_WORST \
        and result["logits"]["served_by_the_cells_rule"]["correct"] \
        and not result["logits"]["float8_reference_by_the_cells_rule"][
            "correct"]
    result["ok"] = bool(ok_hidden and ok_logits)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "olmoe_chip_parity.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
