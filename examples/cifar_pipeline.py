"""CIFAR-10-class pipeline-parallel training toy.

The first BASELINE.json config row ("CIFAR-10 PipelineModule toy") — the
reference's canonical pipeline tutorial
(docs/_tutorials/cifar-10.md + DeepSpeedExamples/training/cifar), TPU
form: a small conv-free patch classifier described as a LayerSpec list,
partitioned over a pipe=2 mesh, trained through the ordinary
``Engine.train_batch`` (GAS, clipping, AdamW — the pipeline composes
with everything). Data is synthetic CIFAR-shaped (32x32x3; zero-egress
environment), with a LEARNABLE rule (label = dominant color channel of
a colored square) so the loss visibly drops and accuracy is checkable.

Run (any box; 8 virtual CPU devices by default):
    python examples/cifar_pipeline.py [--steps 40]
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

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu as dstpu
from deepspeed_tpu.config.config import MeshConfig
from deepspeed_tpu.parallel.pipeline import LayerSpec, PipelineModule
from deepspeed_tpu.parallel.topology import build_mesh

DIM = 64


class PatchEmbed(nn.Module):
    """32x32x3 image -> 64 patch tokens of DIM features."""

    @nn.compact
    def __call__(self, images):
        B = images.shape[0]
        patches = images.reshape(B, 8, 4, 8, 4, 3).transpose(
            0, 1, 3, 2, 4, 5).reshape(B, 64, 4 * 4 * 3)
        return nn.Dense(DIM, name="proj")(patches)


class MixerBlock(nn.Module):
    """Token-mix + channel-mix residual block (conv-free, MXU-shaped)."""

    @nn.compact
    def __call__(self, x):
        t = jnp.swapaxes(nn.Dense(64, name="token_mix")(
            jnp.swapaxes(nn.LayerNorm()(x), 1, 2)), 1, 2)
        x = x + t
        return x + nn.Dense(DIM, name="channel_mix")(
            jnp.tanh(nn.Dense(2 * DIM, name="expand")(nn.LayerNorm()(x))))


class Head(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(10, name="classifier")(x.mean(axis=1))


def synthetic_cifar(batch, rng):
    """Colored-square images whose label is recoverable from pixels."""
    labels = rng.integers(0, 10, size=batch)
    imgs = rng.normal(0.0, 0.1, size=(batch, 32, 32, 3)).astype(np.float32)
    for i, y in enumerate(labels):
        r, c = (y % 4) * 8, (y // 4) * 8
        imgs[i, r:r + 8, c:c + 8, y % 3] += 1.0
    return {"images": jnp.asarray(imgs),
            "labels": jnp.asarray(labels, jnp.int32)}


def cls_loss(logits, batch):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(
        logp, batch["labels"][:, None], axis=1).mean()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--pipe", type=int, default=2)
    args = ap.parse_args()

    n = jax.device_count()
    topo = build_mesh(MeshConfig(pipe=args.pipe, data=n // args.pipe))
    specs = ([LayerSpec(PatchEmbed)]
             + [LayerSpec(MixerBlock) for _ in range(6)]
             + [LayerSpec(Head)])
    pm = PipelineModule(specs, topo.mesh, num_microbatches=4,
                        input_fn=lambda b: b["images"],
                        loss_fn=cls_loss)
    sample = synthetic_cifar(8, np.random.default_rng(0))
    params = pm.init(jax.random.PRNGKey(0), sample)

    engine, _, _, _ = dstpu.initialize(
        loss_fn=pm.loss_fn, params=params, topology=topo,
        config={
            "train_micro_batch_size_per_gpu": 16,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
            "gradient_clipping": 1.0,
            "steps_per_print": 10,
        })

    rng = np.random.default_rng(1)
    B = engine.config.train_batch_size
    first = last = None
    for step in range(args.steps):
        loss = float(engine.train_batch(synthetic_cifar(B, rng)))
        first = first if first is not None else loss
        last = loss
    print(f"pipeline(pipe={args.pipe}) CIFAR toy: "
          f"loss {first:.3f} -> {last:.3f} over {args.steps} steps")
    assert last < 0.6 * first, "loss did not drop"
    print("OK")


if __name__ == "__main__":
    main()
