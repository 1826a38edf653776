"""BERT MLM pretraining with ZeRO stage 1.

The second BASELINE.json config row ("BERT-base pretraining, ZeRO
stage-1 (FusedAdam path)") — the reference's bert-pretraining tutorial
(docs/_tutorials/bert-pretraining.md), TPU form: BERT through the
engine with FusedAdam (ds_config name; optax-fused on TPU), optimizer
state sharded over the data axis (ZeRO-1), synthetic MLM data with
learnable structure (arithmetic token sequences) so the loss drops.

Run:  python examples/bert_zero1.py [--steps 40] [--size base]
``--size base`` is the real BERT-base (single chip / bigger host);
the default tiny config finishes in ~2 min on the 8-device CPU mesh.
"""

import argparse
import os
import sys

import jax

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # the 8-device virtual CPU mesh, when asked for by name; with nothing
    # set, JAX picks the accelerator the host has
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from deepspeed_tpu.utils.jax_compat import request_cpu_devices
    request_cpu_devices(8)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax.numpy as jnp
import numpy as np

import deepspeed_tpu as dstpu
from deepspeed_tpu.models.bert import BertConfig, make_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--size", default="tiny", choices=["tiny", "base"])
    args = ap.parse_args()

    if args.size == "base":
        cfg = BertConfig(dtype=jnp.bfloat16, remat=True)
    else:
        cfg = BertConfig.tiny(dtype=jnp.float32)
    model, init_fn, loss_fn = make_model(cfg, mask_token_id=3)
    params = init_fn(jax.random.PRNGKey(0), batch_size=2, seq_len=64)

    engine, _, _, _ = dstpu.initialize(
        loss_fn=loss_fn, params=params,
        config={
            "train_micro_batch_size_per_gpu": 4,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "FusedAdam",
                          "params": {"lr": 1e-3, "weight_decay": 0.01}},
            "zero_optimization": {"stage": 1},
            "gradient_clipping": 1.0,
            "steps_per_print": 10,
        })

    V, T = cfg.vocab_size, 64
    rng = np.random.default_rng(0)
    B = engine.config.train_batch_size

    def batch():
        # +1-increment sequences: a masked token is its left neighbor + 1
        # (mod 64) — fully inferable from unmasked context, so MLM loss
        # drops fast even at tiny scale
        starts = rng.integers(8, 72, size=(B, 1))
        seq = (starts + np.arange(T)[None, :] - 8) % 64 + 8
        return {"tokens": jnp.asarray(seq, jnp.int32)}

    first = last = None
    for _ in range(args.steps):
        last = float(engine.train_batch(batch()))
        first = first if first is not None else last
    shards = engine.topology.axis_size("data")
    print(f"BERT-{args.size} MLM + ZeRO-1 over {shards} shards: "
          f"loss {first:.3f} -> {last:.3f} over {args.steps} steps")
    assert last < 0.7 * first, "loss did not drop"
    print("OK")


if __name__ == "__main__":
    main()
