"""ZeRO-3 scaling-efficiency model for the flagship GPT-2-1.3B config.

The BASELINE.json headline is "samples/sec/chip + ZeRO-3 scaling
efficiency 8->256 chips (GPT-2-1.3B, seq 2k)". Multi-chip hardware is
not available in this environment, so this tool does the honest next
thing: it compiles the REAL training step (full engine: GAS + clip +
update + ZeRO-3 sharding) on virtual N-device meshes, counts the
collective traffic the SPMD partitioner actually inserted (all-gather /
reduce-scatter / all-reduce bytes from the compiled HLO), and combines
it with v5e roofline constants into a per-chip efficiency model:

    T_compute = step FLOPs/chip / (MXU peak * achieved-MFU)
    T_comm    = ring-cost collective bytes/chip / ICI bandwidth
    eff_overlapped = T_compute / max(T_compute, T_comm)
    eff_serial     = T_compute / (T_compute + T_comm)

The collective BYTES are exact (read from the compiled module — the
same partitioner decides TPU lowering); the TIME model is labeled
assumptions. Results: profiles/r05_scaling.json. Each mesh size runs in
its own subprocess (jax_num_cpu_devices is fixed per process).

Reference yardstick: deepspeed's GPT-2 ZeRO scaling claims
(docs/_pages/training.md; blogs zero figures) report near-linear
per-GPU throughput 8->256 GPUs for this model class.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "profiles", "r05_scaling.json")

# --- labeled model constants (v5e) -----------------------------------
MXU_PEAK = 197e12          # bf16 FLOPs/s per chip
ACHIEVED_MFU = 0.50        # measured round-4 train MFU at this shape class
ICI_BW = 9e10              # bytes/s per chip, effective all-gather ring BW
                           # (v5e 2D torus; scaling-book class estimate)

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8}

_COLL = re.compile(
    r"= (.*?) (all-gather|reduce-scatter|all-reduce|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_SHAPE = re.compile(r"([a-z]\w*)\[([\d,]*)\]")


def parse_collectives(hlo: str):
    """-> {op: {"count": n, "bytes": total buffer bytes}} from compiled
    HLO text. The type string before the op name may be a single
    ``dtype[dims]`` or a tuple ``(dtype[dims], ...)`` (combined/variadic
    collectives); async ``-start`` forms fold into the base op (their
    ``-done`` twin carries no new traffic)."""
    out = {}
    for m in _COLL.finditer(hlo):
        typestr, op = m.group(1), m.group(2)
        b = 0
        for sm in _SHAPE.finditer(typestr):
            dt, dims = sm.group(1), sm.group(2)
            size = 1
            for d in dims.split(","):
                if d:
                    size *= int(d)
            b += size * _DTYPE_BYTES.get(dt, 4)
        rec = out.setdefault(op, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += b
    return out


def run_one(n_dev: int, micro: int):
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, REPO)
    from deepspeed_tpu.utils.jax_compat import request_cpu_devices
    request_cpu_devices(n_dev)
    import jax.numpy as jnp
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, make_model

    seq = 2048
    cfg_model = GPT2Config(
        vocab_size=50304, max_seq_len=seq + 1, num_layers=24, num_heads=16,
        hidden_size=2048, param_dtype=jnp.bfloat16, remat=True,
        remat_policy="qkv_out", attention_impl="xla")
    model, init_fn, loss_fn = make_model(cfg_model)
    params = init_fn(jax.random.PRNGKey(0), batch_size=2, seq_len=64)
    import numpy as np
    n_params = sum(int(np.prod(np.shape(p)))
                   for p in jax.tree_util.tree_leaves(params))

    engine, _, _, _ = dstpu.initialize(
        loss_fn=loss_fn, params=params,
        config={
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-4, "moment_dtype": "bfloat16"}},
            "bf16": {"enabled": True},
            "data_types": {"grad_accum_dtype": "bfloat16"},
            "zero_optimization": {"stage": 3,
                                  "stage3_param_persistence_threshold": 0},
            "gradient_clipping": 1.0,
            "steps_per_print": 10_000,
        })
    B = engine.config.train_batch_size
    batch = {"tokens": jnp.zeros((B, seq + 1), jnp.int32)}
    t0 = time.time()
    comp = engine._train_step.lower(engine.state, batch).compile()
    compile_s = time.time() - t0
    colls = parse_collectives(comp.as_text())

    # ring cost per chip: AG/RS move (N-1)/N of the full buffer; AR = 2x
    ring = (n_dev - 1) / n_dev
    comm_bytes = 0.0
    for op, rec in colls.items():
        f = 2 * ring if op == "all-reduce" else ring
        comm_bytes += f * rec["bytes"]

    L, C = cfg_model.num_layers, cfg_model.hidden_size
    flops = 6.0 * n_params * micro * seq + 6.0 * L * micro * seq * seq * C
    t_compute = flops / (MXU_PEAK * ACHIEVED_MFU)
    t_comm = comm_bytes / ICI_BW
    print(json.dumps({
        "n_devices": n_dev, "micro_per_chip": micro,
        "n_params": n_params,
        "compile_s": round(compile_s, 1),
        "collectives": colls,
        "comm_bytes_per_chip": int(comm_bytes),
        "t_compute_s": round(t_compute, 4),
        "t_comm_s": round(t_comm, 4),
        "eff_overlapped": round(t_compute / max(t_compute, t_comm), 3),
        "eff_serial": round(t_compute / (t_compute + t_comm), 3),
    }))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--one", type=int)
    ap.add_argument("--micro", type=int, default=4)
    ap.add_argument("--sizes", default="8,16,32")
    args = ap.parse_args()
    if args.one:
        return run_one(args.one, args.micro)

    results = {"model": "gpt2-1.3B seq2048 zero3 bf16 (compact moments)",
               "assumptions": {"mxu_peak": MXU_PEAK,
                               "achieved_mfu": ACHIEVED_MFU,
                               "ici_bytes_per_s": ICI_BW},
               "meshes": []}
    for n in args.sizes.split(","):
        r = subprocess.run(
            [sys.executable, __file__, "--one", n, "--micro",
             str(args.micro)],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        lines = [ln for ln in r.stdout.strip().splitlines()
                 if ln.startswith("{")]
        if r.returncode == 0 and lines:
            results["meshes"].append(json.loads(lines[-1]))
        else:
            results["meshes"].append({"n_devices": int(n),
                                      "error": f"rc={r.returncode}",
                                      "stderr": r.stderr[-800:]})
        print(json.dumps(results["meshes"][-1])[:400], flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {OUT}")


if __name__ == "__main__":
    sys.exit(main())
