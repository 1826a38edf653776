"""Training-step profiler: where does the step time go?

Instead of blind knob-turning, run a grid of ablations of the compiled
train step ON the chip and record the deltas. Each experiment runs in its
OWN subprocess, one after the other; the parent never imports jax, so each
child has the chip to itself.

Usage:
    python tools/profile_train.py            # run the default grid
    python tools/profile_train.py --exp NAME # run one experiment (subprocess)

Results append to chiprun_out/profile_train.jsonl; a profiler trace (when
the `trace` experiment runs) lands in chiprun_out/profile_train_trace/.

Ablation axes:
  mode   step (full engine train_batch) | grad (value_and_grad only) |
         fwd (loss only)
  loss   xent8/xent16/xent32 (chunked fused LM xent, N chunks) |
         none (hidden-mean loss — isolates the unembed+xent cost)
  model  gpt124 (bench flagship) | large710 (hidden 2048, D=128 heads,
         seq-2k class — the honest-arithmetic-intensity config)
  policy remat policy string (gpt2.py remat_policy)
  impl   flash | xla attention
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "chiprun_out", "profile_train.jsonl")

# name -> overrides
EXPERIMENTS = {
    # baseline repro + decomposition
    "base":        dict(),
    "grad_only":   dict(mode="grad"),
    "fwd_only":    dict(mode="fwd"),
    "no_xent":     dict(loss="none"),
    "xent32":      dict(loss="xent32"),
    "xla_attn":    dict(impl="xla"),
    # finer remat: save mlp_pre_act too -> backward recomputes only
    # LN/gelu/flash, no repeated matmuls
    "save_mlp128": dict(policy="save:qkv,attn_out,mlp_pre_act"),
    "save_mlp96":  dict(policy="save:qkv,attn_out,mlp_pre_act", micro=96),
    "save_mlp64":  dict(policy="save:qkv,attn_out,mlp_pre_act", micro=64),
    # honest-arithmetic-intensity model: hidden 2048, head_dim 128, seq 2048
    "big_qkv8":    dict(model="large710", seq=2048, micro=8),
    "big_full8":   dict(model="large710", seq=2048, micro=8, policy="full"),
    "big_save4":   dict(model="large710", seq=2048, micro=4,
                        policy="save:qkv,attn_out,mlp_pre_act"),
    "big_save8":   dict(model="large710", seq=2048, micro=8,
                        policy="save:qkv,attn_out,mlp_pre_act"),
    # device trace of the baseline
    "trace":       dict(trace=1, steps=3),
    # round 2 of the grid: bf16 grad accumulation frees ~2.8 GB at the big
    # shape, which is what the lighter remat policies need to fit
    "big_fwd":     dict(model="large710", seq=2048, micro=8, mode="fwd"),
    "big_full8_gb": dict(model="large710", seq=2048, micro=8, policy="full",
                         gdtype="bfloat16"),
    "big_qkv4_gb": dict(model="large710", seq=2048, micro=4,
                        gdtype="bfloat16"),
    "big_qkv8_gb": dict(model="large710", seq=2048, micro=8,
                        gdtype="bfloat16"),
    "big_save4_gb": dict(model="large710", seq=2048, micro=4,
                         policy="save:qkv,attn_out,mlp_pre_act",
                         gdtype="bfloat16"),
    "big_qkv8_x32": dict(model="large710", seq=2048, micro=8,
                         gdtype="bfloat16", loss="xent32"),
    # round 3 of the grid: skip the xent chunk recompute (keep fp32 logit
    # chunks for backward) — the bwd drops a whole unembed matmul
    "big_qkv4_nr": dict(model="large710", seq=2048, micro=4,
                        gdtype="bfloat16", loss="xentnr8"),
    "big_save4_nr": dict(model="large710", seq=2048, micro=4,
                         policy="save:qkv,attn_out,mlp_pre_act",
                         gdtype="bfloat16", loss="xentnr8"),
    "big_qkv4_nr32": dict(model="large710", seq=2048, micro=4,
                          gdtype="bfloat16", loss="xentnr32"),
    "big_xla4_nr": dict(model="large710", seq=2048, micro=4, impl="xla",
                        gdtype="bfloat16", loss="xentnr8"),
    # round 4: probe the OOM boundary between micro 4 and 8, and isolate
    # the optimizer-update cost at the big shape
    "big_qkv6_gb": dict(model="large710", seq=2048, micro=6,
                        gdtype="bfloat16"),
    "big_grad4":   dict(model="large710", seq=2048, micro=4, mode="grad"),
    "big_xla6_gb": dict(model="large710", seq=2048, micro=6, impl="xla",
                        gdtype="bfloat16"),
    # round 5: streaming fused LM-head xent (ops/kernels/fused_xent.py) —
    # no fp32 logit chunks in HBM at all; the freed memory may also admit
    # a bigger micro batch or lighter remat
    "big_qkv6_fx": dict(model="large710", seq=2048, micro=6,
                        gdtype="bfloat16", loss="fused"),
    "big_qkv8_fx": dict(model="large710", seq=2048, micro=8,
                        gdtype="bfloat16", loss="fused"),
    "big_save4_fx": dict(model="large710", seq=2048, micro=4,
                         policy="save:qkv,attn_out,mlp_pre_act",
                         gdtype="bfloat16", loss="fused"),
    "big_save6_fx": dict(model="large710", seq=2048, micro=6,
                         policy="save:qkv,attn_out,mlp_pre_act",
                         gdtype="bfloat16", loss="fused"),
    "fx124":       dict(loss="fused"),
    # flash tile geometry at seq 2048 (512/512 was tuned at seq 512)
    "big_bq1024":  dict(model="large710", seq=2048, micro=6,
                        gdtype="bfloat16", bq=1024, bk=512),
    "big_bk1024":  dict(model="large710", seq=2048, micro=6,
                        gdtype="bfloat16", bq=512, bk=1024),
    "big_bq256":   dict(model="large710", seq=2048, micro=6,
                        gdtype="bfloat16", bq=256, bk=512),
    "big_bqk1024": dict(model="large710", seq=2048, micro=6,
                        gdtype="bfloat16", bq=1024, bk=1024),
    # round 6: combine the flash 1024-tile win with the fused xent, and
    # probe whether the xent memory savings admit micro 8
    "big_b6_fx":   dict(model="large710", seq=2048, micro=6,
                        gdtype="bfloat16", bq=1024, bk=1024, loss="fused"),
    "big_b8_fx":   dict(model="large710", seq=2048, micro=8,
                        gdtype="bfloat16", bq=1024, bk=1024, loss="fused"),
    "big_b8_gb":   dict(model="large710", seq=2048, micro=8,
                        gdtype="bfloat16", bq=1024, bk=1024),
    "big_b6s_fx":  dict(model="large710", seq=2048, micro=6,
                        policy="save:qkv,attn_out,mlp_pre_act",
                        gdtype="bfloat16", bq=1024, bk=1024, loss="fused"),
}

DEFAULTS = dict(mode="step", loss="xent8", model="gpt124", policy="qkv_out",
                impl="flash", micro=128, seq=512, steps=8, trace=0,
                gdtype="float32", bq=512, bk=512)


def run_one(exp: str):
    cfg = {**DEFAULTS, **EXPERIMENTS[exp]}
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    seq, micro = cfg["seq"], cfg["micro"]
    if cfg["model"] == "gpt124":
        mcfg = GPT2Config(vocab_size=50304, max_seq_len=seq + 1,
                          num_layers=12, num_heads=12, hidden_size=768,
                          remat=cfg["policy"] != "none",
                          remat_policy=cfg["policy"],
                          attention_impl=cfg["impl"],
                          flash_block_q=cfg["bq"], flash_block_k=cfg["bk"])
    elif cfg["model"] == "large710":
        mcfg = GPT2Config(vocab_size=50304, max_seq_len=seq + 1,
                          num_layers=12, num_heads=16, hidden_size=2048,
                          remat=cfg["policy"] != "none",
                          remat_policy=cfg["policy"],
                          attention_impl=cfg["impl"],
                          flash_block_q=cfg["bq"], flash_block_k=cfg["bk"])
    else:
        raise ValueError(cfg["model"])

    model = GPT2(mcfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((2, 16), jnp.int32))["params"]
    n_params = sum(int(np.prod(np.shape(p)))
                   for p in jax.tree_util.tree_leaves(params))

    from deepspeed_tpu.models._lm_utils import chunked_lm_xent

    loss_kind = cfg["loss"]

    def loss_fn(p, batch, rng):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        hidden = model.apply({"params": p}, inputs, True, True)
        if loss_kind == "none":
            return hidden.astype(jnp.float32).mean()
        if loss_kind == "fused":
            from deepspeed_tpu.ops.kernels import fused_lm_xent
            return fused_lm_xent(hidden, p["wte"]["embedding"], targets)
        if loss_kind.startswith("xentnr"):
            return chunked_lm_xent(hidden, p["wte"]["embedding"], targets,
                                   num_chunks=int(loss_kind[6:]),
                                   remat=False)
        nc = int(loss_kind[4:])
        return chunked_lm_xent(hidden, p["wte"]["embedding"], targets,
                               num_chunks=nc)

    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(
        rng.randint(0, 50304, size=(micro, seq + 1)), jnp.int32)}

    mode = cfg["mode"]
    if mode == "step":
        import deepspeed_tpu as dstpu
        engine, _, _, _ = dstpu.initialize(
            loss_fn=loss_fn, params=params,
            config={
                "train_micro_batch_size_per_gpu": micro,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "AdamW",
                              "params": {"lr": 1e-4, "weight_decay": 0.01}},
                "bf16": {"enabled": True},
                "data_types": {"grad_accum_dtype": cfg["gdtype"]},
                "zero_optimization": {"stage": 0},
                "gradient_clipping": 1.0,
                "steps_per_print": 10_000,
            })
        step = lambda: engine.train_batch(batch)  # noqa: E731
    else:
        from deepspeed_tpu.utils.dtypes import cast_floating

        def fwd(p, b):
            return loss_fn(cast_floating(p, jnp.bfloat16), b,
                           jax.random.PRNGKey(0))

        if mode == "fwd":
            fn = jax.jit(fwd)
            step = lambda: fn(params, batch)  # noqa: E731
        else:  # grad
            gfn = jax.jit(jax.value_and_grad(fwd))

            def step():
                loss, _g = gfn(params, batch)
                return loss

    # warmup/compile
    t0 = time.perf_counter()
    first = float(jax.block_until_ready(step()))
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(step())

    tracing = bool(cfg["trace"])
    if tracing:
        import jax.profiler
        tdir = os.path.join(REPO, "chiprun_out", "profile_train_trace")
        os.makedirs(tdir, exist_ok=True)
        jax.profiler.start_trace(tdir)

    steps = int(cfg["steps"])
    t0 = time.perf_counter()
    for _ in range(steps):
        out = step()
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    if tracing:
        jax.profiler.stop_trace()

    flops = 6.0 * n_params * micro * seq   # counted (6ND) per step
    print(json.dumps({
        "exp": exp, **{k: cfg[k] for k in
                       ("mode", "loss", "model", "policy", "impl",
                        "micro", "seq", "gdtype")},
        "n_params": n_params,
        "steps": steps,
        "step_ms": round(1e3 * dt / steps, 2),
        "tflops_6nd": round(flops * steps / dt / 1e12, 1),
        "compile_s": round(compile_s, 1),
        "loss0": first,
    }))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp")
    ap.add_argument("--grid", default=",".join(EXPERIMENTS))
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
