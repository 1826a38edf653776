"""Measured long-context training runs (VERDICT r4 #4).

The reference's loudest long-context claim is DeepSpeed-Ulysses at 1M
tokens over 64 GPUs (``blogs/deepspeed-ulysses/README.md:78-83``) — per
GPU that is ~16k tokens of attention work. This tool measures what ONE
v5e chip sustains with the TPU-native stack (Pallas flash attention +
full remat + chunked fused LM xent) at seq 32k-131k on a Llama-150M
class model, recording step time, achieved TFLOPS, and the max sequence
that fits 16 GiB. The multi-chip sequence-parallel path (Ulysses sp=8 +
ring attention) is validated by ``__graft_entry__.dryrun_multichip``;
single-chip long-seq throughput is the number that stands next to the
blog's per-GPU figure.

Each experiment runs in its own subprocess, one after the other (the parent
never imports jax, so each child has the chip to itself). Results append to
``chiprun_out/profile_longctx.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "chiprun_out", "profile_longctx.jsonl")

# name -> seq_len (llama-150M: 12 x hidden 768, RoPE so no position table)
EXPERIMENTS = {
    "seq8k":   dict(seq=8192),
    "seq16k":  dict(seq=16384),
    "seq32k":  dict(seq=32768),
    "seq64k":  dict(seq=65536),
    "seq128k": dict(seq=131072),
    # ring attention API path on a 1-device mesh at 32k: same kernel,
    # exercises the ppermute ring machinery end to end on chip
    "ring32k": dict(seq=32768, ring=1),
}

DEFAULTS = dict(seq=32768, steps=4, micro=1, ring=0)


def run_one(exp: str):
    cfg = {**DEFAULTS, **EXPERIMENTS[exp]}
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.llama import LlamaConfig, make_model
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    seq, micro = cfg["seq"], cfg["micro"]
    if os.environ.get("DSTPU_LC_SEQ"):        # CPU smoke-test override
        seq = int(os.environ["DSTPU_LC_SEQ"])
    mcfg = LlamaConfig(
        vocab_size=32000, max_seq_len=seq + 1, num_layers=12,
        num_heads=12, num_kv_heads=12, hidden_size=768,
        intermediate_size=2048, remat=True,
        xent_chunks=max(8, seq // 2048),
        attention_impl=os.environ.get("DSTPU_LC_IMPL", "auto"))
    model, init_fn, loss_fn = make_model(mcfg)
    params = init_fn(jax.random.PRNGKey(0), batch_size=1, seq_len=256)
    n_params = sum(int(np.prod(np.shape(p)))
                   for p in jax.tree_util.tree_leaves(params))

    if cfg["ring"]:
        # time the ring-attention collective itself at long seq on a
        # 1-device mesh: validates the ppermute KV-rotation machinery on
        # real hardware (multi-device ring is CPU-mesh tested; the ring
        # adds its ppermute schedule even at world 1)
        from deepspeed_tpu.config.config import MeshConfig
        from deepspeed_tpu.parallel.ring_attention import ring_attention
        from deepspeed_tpu.parallel.topology import build_mesh
        topo = build_mesh(MeshConfig(seq=1), devices=jax.devices()[:1])
        H, D = 12, 64
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, seq, H, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, seq, H, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, seq, H, D), jnp.bfloat16)

        def attn_loss(q_, k_, v_):
            return ring_attention(q_, k_, v_, topo.mesh,
                                  causal=True).astype(jnp.float32).mean()

        fn = jax.jit(jax.grad(attn_loss, (0, 1, 2)))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(q, k, v))
        compile_s = time.perf_counter() - t0
        steps = int(cfg["steps"])
        t0 = time.perf_counter()
        for _ in range(steps):
            g = fn(q, k, v)
        jax.block_until_ready(g)
        dt = time.perf_counter() - t0
        macs = seq * seq * (H * D) / 2 * 2            # QK^T + PV, causal
        print(json.dumps({
            "exp": exp, "seq": seq, "mode": "ring_attention fwd+bwd",
            "step_ms": round(1e3 * dt / steps, 1),
            "tflops": round(6.0 * macs * steps / dt / 1e12, 1),
            "compile_s": round(compile_s, 1),
        }))
        return

    engine, _, _, _ = dstpu.initialize(
        loss_fn=loss_fn, params=params,
        config={
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-4, "weight_decay": 0.01}},
            "bf16": {"enabled": True},
            "data_types": {"grad_accum_dtype": "bfloat16"},
            "zero_optimization": {"stage": 0},
            "gradient_clipping": 1.0,
            "steps_per_print": 10_000,
        })

    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(
        rng.randint(0, 32000, size=(micro, seq + 1)), jnp.int32)}

    t0 = time.perf_counter()
    loss = engine.train_batch(batch)
    first = float(loss)                      # forces the compile + step
    compile_s = time.perf_counter() - t0

    steps = int(cfg["steps"])
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch)
    last = float(loss)
    dt = time.perf_counter() - t0

    L, C = mcfg.num_layers, mcfg.hidden_size
    dense = 6.0 * n_params * micro * seq
    # causal attention matmuls: QK^T + PV = seq^2 * C MACs/layer (half of
    # the full 2*seq^2*C), x2 FLOPs, x3 for fwd+bwd
    attn = 6.0 * L * micro * seq * seq * C / 2 * 2
    stats = jax.local_devices()[0].memory_stats() or {}
    print(json.dumps({
        "exp": exp, "seq": seq, "micro": micro, "steps": steps,
        "n_params": n_params,
        "step_ms": round(1e3 * dt / steps, 1),
        "tokens_per_sec": round(micro * seq * steps / dt, 1),
        "tflops_6nd": round(dense * steps / dt / 1e12, 1),
        "tflops_with_attn": round((dense + attn) * steps / dt / 1e12, 1),
        "attn_flop_share": round(attn / (dense + attn), 3),
        "compile_s": round(compile_s, 1),
        "loss0": first, "loss_last": last,
        "device_peak_bytes": stats.get("peak_bytes_in_use"),
    }))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp")
    ap.add_argument("--grid", default="seq8k,seq16k,seq32k,seq64k,seq128k")
    args = ap.parse_args()
    if args.exp:
        return run_one(args.exp)

    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    for exp in args.grid.split(","):
        if not exp:
            continue
        t0 = time.time()
        r = subprocess.run([sys.executable, __file__, "--exp", exp],
                           capture_output=True, text=True)
        lines = [ln for ln in r.stdout.strip().splitlines()
                 if ln.startswith("{")]
        if r.returncode == 0 and lines:
            rec = json.loads(lines[-1])
        else:
            rec = {"exp": exp, "error": f"rc={r.returncode}",
                   "stderr": r.stderr[-1500:]}
        rec["wall_s"] = round(time.time() - t0, 1)
        with open(OUT, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    sys.exit(main())
