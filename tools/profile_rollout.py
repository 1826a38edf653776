"""Hybrid-engine rollout throughput: KV-cached (default) vs uncached.

VERDICT r4 #7's bar: the cached rollout must be >=10x the uncached
full-context-recompute scan on a 256-token generate at a real model
size. Runs a GPT-2-124M hybrid engine on the current backend, times
both paths (one warmup + timed repeats), prints one JSON line and
appends it to profiles/r05_rollout.json.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, make_model
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    gen = int(os.environ.get("DSTPU_ROLLOUT_GEN", "256"))
    cfg = GPT2Config(
        vocab_size=50304, max_seq_len=1024, num_layers=12, num_heads=12,
        hidden_size=768,
        attention_impl=os.environ.get("DSTPU_ROLLOUT_IMPL", "auto"))
    model, init_fn, loss_fn = make_model(cfg)
    params = init_fn(jax.random.PRNGKey(0), batch_size=2, seq_len=64)

    def apply_fn(p, tokens):
        return model.apply({"params": p}, tokens)

    engine, _, _, _ = dstpu.initialize(
        loss_fn=loss_fn, model=apply_fn, params=params, model_cfg=cfg,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "hybrid_engine": {"enabled": True, "max_out_tokens": gen},
        })
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(1, 50304, size=(1, 64)), jnp.int32)

    def timed(n=2):
        engine.generate(prompt, max_new_tokens=gen)      # warmup/compile
        t0 = time.perf_counter()
        for _ in range(n):
            engine.generate(prompt, max_new_tokens=gen)
        return (time.perf_counter() - t0) / n

    cached_s = timed()
    engine.model_cfg = None                              # uncached scan
    uncached_s = timed(n=1)

    rec = {
        "model": "gpt2-124M", "prompt": 64, "gen": gen,
        "cached_s": round(cached_s, 3),
        "uncached_s": round(uncached_s, 3),
        "speedup": round(uncached_s / cached_s, 1),
        "cached_tok_s": round(gen / cached_s, 1),
        "backend": jax.default_backend(),
    }
    print(json.dumps(rec))
    os.makedirs(os.path.join(REPO, "profiles"), exist_ok=True)
    with open(os.path.join(REPO, "profiles", "r05_rollout.json"), "a") as f:
        f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    sys.exit(main())
