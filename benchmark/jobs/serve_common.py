"""What the two serving job kinds share: building the engine from the cell
file, and the comparison with the plain reference that decides ``correct``.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, List, Sequence, Tuple

from ..common import Ctx, say


def build(ctx: Ctx):
    """(engine, model-type module, model config, weights)."""
    import jax
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)
    mt = importlib.import_module(
        f"benchmark.model_types.{ctx.config['model_type']}")
    model_cfg = mt.model_config(ctx.model_dims())
    params = mt.init_params(model_cfg, ctx.seed)
    ctx.mark("weights")
    engine_cfg = RaggedInferenceConfig(**ctx.param("engine"))
    engine = InferenceEngineV2(model_cfg, params, engine_cfg)
    ctx.mark("engine")
    pool_tokens = engine_cfg.num_blocks * engine_cfg.block_size
    say("engine", {
        "max_seqs": engine_cfg.max_seqs, "pool_tokens": pool_tokens,
        "pool_bytes": pool_tokens * mt.kv_bytes_per_token(model_cfg),
        "weights_bytes": sum(int(p.nbytes) for p in
                             jax.tree_util.tree_leaves(params)),
    })
    return engine, mt, model_cfg, params


def check_streams(ctx: Ctx, mt, model_cfg, params,
                  samples: Sequence[Tuple[List[int], List[int]]]
                  ) -> Dict[str, Any]:
    """Teacher-force the plain reference with what was served.

    For each sampled (prompt, served tokens) the reference reads the prompt
    followed by the served tokens and gives its logits at every position
    that predicted a served token. The served token may sit below the
    reference's best logit by at most ``tolerance_sigma`` standard
    deviations of that logit row, and must BE the reference's best on at
    least ``min_same_top1_share`` of the positions (the reasons for the
    numbers are in the cell file). A wrong mask, scale, position or block
    table moves it by whole sigmas."""
    import jax.numpy as jnp
    import numpy as np
    spec = ctx.param("correct")
    n_tok = int(spec["tokens"])
    samples = [(p, s) for p, s in samples if len(s) >= n_tok][
        :int(spec["sequences"])]
    if not samples:
        return {"ok": False, "why": "no finished sequence to compare"}
    T = max(len(p) for p, _ in samples) + n_tok
    toks = np.zeros((len(samples), T), np.int32)
    at = np.zeros((len(samples), n_tok), np.int32)
    for i, (p, s) in enumerate(samples):
        row = list(p) + list(s[:n_tok])
        toks[i, :len(row)] = row            # right padding: causal, unseen
        at[i] = len(p) - 1 + np.arange(n_tok)
    logits = np.asarray(mt.reference_logits(model_cfg)(
        params, jnp.asarray(toks), jnp.asarray(at)), np.float32)
    worst, same = 0.0, 0
    for i, (_, s) in enumerate(samples):
        for t in range(n_tok):
            row = logits[i, t]
            gap = float(row.max() - row[s[t]]) / float(row.std())
            worst = max(worst, gap)
            same += gap == 0.0
    total = n_tok * len(samples)
    out = {"worst_gap_sigma": worst, "same_top1": same, "of": total,
           "tolerance_sigma": float(spec["tolerance_sigma"]),
           "min_same_top1_share": float(spec["min_same_top1_share"])}
    out["ok"] = worst <= out["tolerance_sigma"] \
        and same >= out["min_same_top1_share"] * total
    say("correct", out)
    return out


def compared(check: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """The numbers :func:`check_streams` compared, each beside its limit
    (an upper one for the gap, a lower one for the share)."""
    if "of" not in check:
        return {}
    return {"worst_gap_sigma": {"value": check["worst_gap_sigma"],
                                "limit": check["tolerance_sigma"]},
            "same_top1_share": {"value": check["same_top1"] / check["of"],
                                "limit": check["min_same_top1_share"]}}

