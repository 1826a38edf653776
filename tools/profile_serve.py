"""Capture + summarize a device trace of the fused decode loop.

Usage:
    python tools/profile_serve.py capture   # on the TPU, one process per chip
    python tools/profile_serve.py report    # parses the newest trace

The trace lands in chiprun_out/profile_serve_trace/.
"""

import collections
import glob
import gzip
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TDIR = os.path.join(REPO, "chiprun_out", "profile_serve_trace")


def capture():
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)
    from deepspeed_tpu.models.llama import Llama, LlamaConfig
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    mcfg = LlamaConfig(vocab_size=32000, max_seq_len=2048, num_layers=22,
                       num_heads=32, num_kv_heads=4, hidden_size=2048,
                       intermediate_size=5632, dtype=jnp.bfloat16)
    model = Llama(mcfg)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32)))["params"]
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.bfloat16), shapes)
    S, PROMPT, NL = 256, 512, 32
    bs = PROMPT + 128
    cfg = RaggedInferenceConfig(max_seqs=S, chunk_size=PROMPT, block_size=bs,
                                num_blocks=S + 4, max_blocks_per_seq=1,
                                decode_loop_steps=NL, dtype="bfloat16",
                                attention_impl="paged_flash",
                                # uncapped: single-forward prefill
                                prefill_chunk_cap=int(os.environ.get(
                                    "DSTPU_PROF_CHUNK_CAP", "0")),
                                kv_cache_dtype=os.environ.get(
                                    "DSTPU_PROF_KV", "auto"))
    eng = InferenceEngineV2(mcfg, params, cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 32000, size=PROMPT).tolist() for _ in range(S)]
    uids = list(range(S))
    toks = eng.put(uids, prompts, _greedy=True)
    last = [toks[u] for u in uids]
    outs = eng.decode_greedy(uids, last, NL)      # compile + warm
    last = [outs[u][-1] for u in uids]

    os.makedirs(TDIR, exist_ok=True)
    import jax.profiler
    jax.profiler.start_trace(TDIR)
    eng.decode_greedy(uids, last, NL)     # returns after the readback
    jax.profiler.stop_trace()
    print("trace captured")


def report(topn=30):
    paths = sorted(glob.glob(os.path.join(
        TDIR, "plugins", "profile", "*", "*.trace.json.gz")))
    if not paths:
        raise SystemExit("no trace found; run capture first")
    with gzip.open(paths[-1]) as f:
        t = json.load(f)
    ev = t.get("traceEvents", [])
    pids = {e["pid"]: e["args"].get("name", "")
            for e in ev if e.get("ph") == "M"
            and e.get("name") == "process_name"}
    dur = collections.defaultdict(float)
    cnt = collections.Counter()
    total_dev = 0.0
    for e in ev:
        if e.get("ph") == "X" and "dur" in e:
            pid = pids.get(e["pid"], "")
            if "TPU" not in pid:
                continue
            key = e.get("name", "")[:70]
            dur[key] += e["dur"]
            cnt[key] += 1
            total_dev += e["dur"]
    print(f"total device event time: {total_dev / 1e3:.1f} ms "
          f"(nested events double-count)")
    for name, d in sorted(dur.items(), key=lambda kv: -kv[1])[:topn]:
        print(f"{d / 1e3:9.2f} ms  x{cnt[name]:6d}  {name}")


if __name__ == "__main__":
    if sys.argv[1:] == ["capture"]:
        capture()
    elif sys.argv[1:] in ([], ["report"]):
        report()
    else:
        raise SystemExit(f"usage: {sys.argv[0]} capture|report "
                         f"(got {sys.argv[1:]})")
