"""What each tolerance of ``serve-solar2-rollout``'s ``correct`` catches.

    chiprun -- python tools/solar2_chip_parity.py          # published widths
    python tools/solar2_chip_parity.py --rehearse          # CPU, toy widths

The plain reference (``benchmark/reference/solar_open2.py``) against
itself with one thing wrong at a time: a bfloat16 recurrent state, the
L2 normalisation of q and k left out, ``beta`` without its 2, a softmax
router, rotary positions left on, and every weight matrix rounded to
float8 (e4m3) first. Each wrong model "serves" its own best
token at every compared position (4 sequences x 32 positions after a
128-token prefix, as the cell compares); reported is the cell's own pair of
numbers: how far that token sits under the true reference's best logit, in
deviations of the row (worst position), and the share of positions where
it IS the reference's best. A wrong model must fail one of the cell's
limits (0.3 sigma, 90 %).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

VARIANTS = {
    "reference": {},
    "bf16_state": {"state_dtype": "bfloat16"},
    "no_l2_norm": {"l2_norm": False},
    "beta_without_its_2": {"beta_scale": 1.0},
    "softmax_router": {"router": "softmax"},
    "rotary_left_on": {"rope_theta": 10000.0},
    # the nearest precision below the bfloat16 the configuration states
    "float8_weights": {"weights": "float8_e4m3fn"},
}


def engine_against_reference(cfg, params, rng, fixed, args) -> int:
    """4 prompts (a multi-chunk one among them) prefilled by ``put``, then
    32 single-token steps each through the cache and the state pool; the
    engine's logits at every step against the reference's forward pass
    over the whole sequence, in deviations of the reference's row."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import solar_open2 as reference
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)
    with open(os.path.join(ROOT, "benchmark", "cells",
                           "serve-solar2-rollout.json")) as f:
        cell = json.load(f)
    ecfg = cell["rehearse" if args.rehearse else "engine"]
    ecfg = ecfg["engine"] if args.rehearse else ecfg
    eng = InferenceEngineV2(cfg, params, RaggedInferenceConfig(
        **dict(ecfg, max_seqs=16, num_blocks=40)))
    lens, n = [97, 256, 383, 600], 32
    seqs = [rng.integers(0, cfg.vocab_size, L + n).tolist() for L in lens]
    got = np.zeros((len(seqs), n, cfg.vocab_size), np.float32)
    out = eng.put(list(range(4)), [s[:L] for s, L in zip(seqs, lens)])
    for i in range(4):
        got[i, 0] = np.asarray(out[i])
    for t in range(1, n):
        out = eng.put(list(range(4)), [[s[L + t - 1]]
                                       for s, L in zip(seqs, lens)])
        for i in range(4):
            got[i, t] = np.asarray(out[i])
    T = max(lens) + n
    toks = np.zeros((4, T), np.int32)
    at = np.zeros((4, n), np.int32)
    for i, (s, L) in enumerate(zip(seqs, lens)):
        toks[i, :L + n] = s
        at[i] = L - 1 + np.arange(n)
    want = np.asarray(jax.jit(functools.partial(reference.logits, **fixed))(
        params, jnp.asarray(toks), jnp.asarray(at)), np.float32)
    sigma = want.std(-1)
    err = np.abs(got - want).max(-1) / sigma
    served = got.argmax(-1)
    gap = (want.max(-1) - np.take_along_axis(
        want, served[..., None], -1)[..., 0]) / sigma
    print(json.dumps({
        "engine_vs_reference": True,
        "logit_err_sigma_median": float(np.median(err)),
        "logit_err_sigma_worst": float(err.max()),
        "logit_err_sigma_by_step": [float(np.median(err[:, t]))
                                    for t in (0, 1, 8, 31)],
        "served_gap_sigma_worst": float(gap.max()),
        "same_top1_share": float((served == want.argmax(-1)).mean()),
        "platform": jax.devices()[0].platform}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seed", type=int, default=3200000101)
    ap.add_argument("--engine", action="store_true",
                    help="serve through InferenceEngineV2 on the cell's "
                    "cache geometry and compare its logits instead")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.model_types import solar_open2 as mt
    from benchmark.reference import solar_open2 as reference
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "solar-open2-250b.json")) as f:
        dims = json.load(f)
    if args.rehearse:
        dims.update(dims["rehearse"])
    cfg = mt.model_config(dims)
    params = mt.init_params(cfg, args.seed)
    rng = np.random.default_rng(args.seed)
    B, T, n = 4, 160, 32
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    at = jnp.asarray(np.tile(np.arange(T - n, T), (B, 1)), jnp.int32)
    fixed = dict(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                 kda_heads=cfg.kda_heads, top_k=cfg.experts_top_k,
                 rms_eps=cfg.rms_eps, experts_first=cfg.experts_first,
                 routed_scaling=cfg.routed_scaling)
    if args.engine:
        return engine_against_reference(cfg, params, rng, fixed, args)
    base = None
    for name, wrong in VARIANTS.items():
        wrong, tree = dict(wrong), params
        if "state_dtype" in wrong:
            wrong["state_dtype"] = jnp.bfloat16
        if wrong.pop("weights", None):
            tree = jax.tree_util.tree_map(
                lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
                if w.ndim >= 2 else w, params)
        fn = jax.jit(functools.partial(reference.logits, **fixed, **wrong))
        lg = np.asarray(fn(tree, toks, at), np.float32).reshape(B * n, -1)
        if base is None:
            base = lg
        served = lg.argmax(-1)
        rows = np.arange(len(base))
        gap = (base.max(-1) - base[rows, served]) / base.std(-1)
        print(json.dumps({
            "variant": name, "worst_gap_sigma": float(gap.max()),
            "median_gap_sigma": float(np.median(gap)),
            "same_top1_share": float((served == base.argmax(-1)).mean()),
            "logit_err_sigma_median": float(np.median(
                np.abs(lg - base).max(-1) / base.std(-1))),
            "platform": jax.devices()[0].platform}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
