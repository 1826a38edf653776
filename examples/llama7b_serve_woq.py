"""Llama-2-7B-class serving with weight-only quantization.

The fourth BASELINE.json config row ("Llama-2-7B DeepSpeed-Inference
kernel-inject"): the 7B architecture served through the v2 ragged engine
(paged-flash attention kernel, SplitFuse prefill, fused multi-token
decode) with int8 WOQ — 7B bf16 is 13.5 GiB of weights; int8 (6.7 GiB)
is what makes it + KV fit a single 16 GiB v5e chip. fp6 drops it to
5.1 GiB (``--woq fp6``).

Default is a tiny shape so the example runs anywhere; ``--size 7b``
builds the real architecture (TPU host with HBM required; zero-weights
init — serving SPEED does not depend on weight values, and checkpoint
loading is `build_hf_engine`'s job).

Run:  python examples/llama7b_serve_woq.py [--size 7b] [--woq int8|fp6]
"""

import argparse
import os
import sys
import time

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

from deepspeed_tpu.inference.quantization import (quantize_model_params,
                                                  woq_memory_bytes)
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceConfig)
from deepspeed_tpu.models.llama import Llama, LlamaConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="tiny", choices=["tiny", "7b"])
    ap.add_argument("--woq", default="int8",
                    choices=["none", "int8", "int4", "fp6", "fp6_fused"])
    ap.add_argument("--seqs", type=int, default=0)
    args = ap.parse_args()

    if args.size == "7b":
        mcfg = LlamaConfig.llama2_7b(max_seq_len=2048, dtype=jnp.bfloat16)
        S = args.seqs or 64
        dtype = jnp.bfloat16
    else:
        mcfg = LlamaConfig.tiny(dtype=jnp.float32, max_seq_len=512)
        S = args.seqs or 4
        dtype = jnp.float32

    model = Llama(mcfg)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32)))["params"]
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, dtype), shapes)
    dense_bytes = woq_memory_bytes(params)

    if args.woq != "none":
        qcfg = ({"num_bits": 8} if args.woq == "int8" else
                {"num_bits": 4} if args.woq == "int4" else
                {"dtype": "fp6"} if args.woq == "fp6" else
                # fused: eligible matmul weights stream through the
                # Pallas 6-bit GEMM (llama_runner woq_mm dispatch)
                {"dtype": "fp6", "fused_gemm": True})
        params = quantize_model_params(
            params, {"quantized_weights": {
                **qcfg, "group_size": 64 if args.size == "tiny" else 128,
                "excluded_modules": ["embed", "norm", "lm_head"]}})
    woq_bytes = woq_memory_bytes(params)

    PROMPT, GEN = (512, 128) if args.size == "7b" else (16, 8)
    cfg = RaggedInferenceConfig(
        max_seqs=S, chunk_size=PROMPT, block_size=PROMPT + GEN,
        num_blocks=S + 2, max_blocks_per_seq=1,
        decode_loop_steps=min(GEN, 32),
        dtype="bfloat16" if args.size == "7b" else "float32",
        attention_impl="auto",
        kv_cache_dtype="int8" if args.size == "7b" else "auto")
    eng = InferenceEngineV2(mcfg, params, cfg)

    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, mcfg.vocab_size, size=PROMPT).tolist()
               for _ in range(S)]
    uids = list(range(S))
    w = eng.put([9991], [prompts[0][:8]], _greedy=True)
    eng.decode_greedy([9991], [w[9991]], cfg.decode_loop_steps)
    eng.flush(9991)

    t0 = time.perf_counter()
    toks = eng.put(uids, prompts, _greedy=True)
    t1 = time.perf_counter()
    last = [toks[u] for u in uids]
    for _ in range(GEN // cfg.decode_loop_steps):
        outs = eng.decode_greedy(uids, last, cfg.decode_loop_steps)
        last = [outs[u][-1] for u in uids]
    t2 = time.perf_counter()

    print(f"llama-{args.size} woq={args.woq}: weights "
          f"{dense_bytes / 1e9:.2f} GB -> {woq_bytes / 1e9:.2f} GB; "
          f"prefill {S * PROMPT / (t1 - t0):.0f} tok/s, "
          f"decode {S * GEN / (t2 - t1):.0f} tok/s "
          f"({S} seqs x {PROMPT}+{GEN})")
    if args.woq != "none":
        assert woq_bytes < 0.62 * dense_bytes
    print("OK")


if __name__ == "__main__":
    main()
