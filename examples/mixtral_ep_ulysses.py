"""Mixtral MoE training with expert parallelism + Ulysses sequence
parallelism composed on one mesh.

The fifth BASELINE.json config row ("Mixtral-8x7B MoE expert-parallel +
Ulysses sequence-parallel (all_to_all)"): a Mixtral-architecture model
trained through the engine on a mesh with BOTH an ``expert`` axis (MoE
dispatch all-to-alls ride it — moe/sharded_moe.py) and a ``seq`` axis
(activations sequence-sharded end to end; the engine's SP loss handles
the seq-sharded cross-entropy). Default shape is tiny (CPU mesh);
``--size 8x7b`` builds the real architecture for a pod slice.

Run:  python examples/mixtral_ep_ulysses.py [--steps 20]
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
from deepspeed_tpu.config.config import MeshConfig
from deepspeed_tpu.models.mixtral import MixtralConfig, make_model
from deepspeed_tpu.parallel.topology import build_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--size", default="tiny", choices=["tiny", "8x7b"])
    args = ap.parse_args()

    n = jax.device_count()
    ep = 2 if n % 2 == 0 else 1
    sp = 2 if n % (ep * 2) == 0 else 1
    topo = build_mesh(MeshConfig(expert=ep, seq=sp,
                                 data=n // (ep * sp)))

    if args.size == "8x7b":
        cfg = MixtralConfig.mixtral_8x7b(max_seq_len=4097, remat=True)
    else:
        cfg = MixtralConfig.tiny(dtype=jnp.float32, max_seq_len=65)
    model, init_fn, loss_fn = make_model(cfg, ep_mesh=topo.mesh)
    T = min(cfg.max_seq_len - 1, 64)
    params = init_fn(jax.random.PRNGKey(0), batch_size=2, seq_len=T)

    engine, _, _, _ = dstpu.initialize(
        loss_fn=loss_fn, params=params, topology=topo,
        config={
            "train_micro_batch_size_per_gpu": 4,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 2e-3}},
            "zero_optimization": {"stage": 1},
            "gradient_clipping": 1.0,
            "steps_per_print": 10,
        })

    rng = np.random.default_rng(0)
    B = engine.config.train_batch_size
    V = cfg.vocab_size

    def batch():
        starts = rng.integers(0, V - T - 1, size=(B, 1))
        return {"tokens": jnp.asarray(
            starts + np.arange(T + 1)[None, :], jnp.int32)}

    first = last = None
    for _ in range(args.steps):
        last = float(engine.train_batch(batch()))
        first = first if first is not None else last
    print(f"mixtral {args.size} on mesh(expert={ep}, seq={sp}, "
          f"data={n // (ep * sp)}): loss {first:.3f} -> {last:.3f} "
          f"over {args.steps} steps")
    assert last < 0.8 * first, "loss did not drop"
    print("OK")


if __name__ == "__main__":
    main()
