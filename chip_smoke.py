"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of the models the repo measures, with depth kept and weights
random from a seed:

  train — GPT-2 1.3B (24 x 2048, 16 heads of 128, seq 2048, vocab 50304,
      bf16 params, bf16 Adam moments, remat ``qkv_out``, flash 1024 tiles)
      through ``dstpu.initialize`` and a few ``engine.train_batch`` steps on
      one repeated batch: loss finite and falling, peak device memory.
  serve — the TinyLlama-1.1B shape (22 x 2048, 32 q / 4 kv heads of 64,
      FFN 5632, vocab 32000), bf16, int8 KV pool in the one-block-per-
      sequence 128-aligned layout, a few dozen mixed greedy/sampled
      requests through ``loadgen.build_requests`` + ``run_open_loop``
      (``put`` + depth-2 ``decode_pipelined``) and one fused ``decode_batch``
      loop; the served greedy tokens are the top-1 of an
      ``attention_impl="dense"`` engine on the same weights, to a bf16
      tolerance; a second, warm pass compiles nothing and repeats every
      stream bit for bit.

Both stages keep the defaults users get (``attention_impl="auto"``,
``xent_impl="chunked"``) and fail unless the step programs they ran hold a
Mosaic custom call — a step that resolved to ``dense``/``xla`` on a TPU is a
failure, not a fallback. With four or more devices the same two models also
run sharded (ZeRO-3 ``data=4`` training, ``tp_size=4`` serving, four
one-chip replicas behind a ``ReplicaPool``) and placement is asserted from
the live arrays.

The stage bodies are functions of a model and an engine config so that
``tests/unit/test_chip_smoke.py`` drives the same code at toy width on the
CPU mesh; only :func:`main` carries the platform refusal and the full
widths. Everything runs in ONE process (a chip belongs to one process at a
time): the train engine is freed, and ``bytes_in_use`` reported, before the
serve stage builds its own. Nothing is caught and summarised: any failed
check raises and the exit code is non-zero.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import json
import os
import re
import sys
import tempfile
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

MOSAIC_CALL = "tpu_custom_call"


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _say(tag: str, obj: Any) -> None:
    print(f"[chip_smoke] {tag}: {json.dumps(obj, default=str)}", flush=True)


class _Phases:
    """Wall seconds between marks: where a stage's time went (set-up,
    compiles, the steps themselves), for the cold and the warm run."""

    def __init__(self):
        self.s: Dict[str, float] = {}
        self._t = time.perf_counter()

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.s[name] = round(now - self._t, 2)
        self._t = now


@contextlib.contextmanager
def lowered_programs(*prefixes: str) -> Iterator[Dict[str, List[str]]]:
    """{jit name: [StableHLO text per specialization]} of every program
    lowered inside the block whose name starts with one of ``prefixes``,
    filled when the block ends. Read from JAX's own IR dump
    (``jax_dump_ir_to``): the text IS the program that then ran, and it
    costs no second trace — on the chip's host, tracing and lowering one
    22-layer serve program is ~50 s of Python, several times its XLA
    compile."""
    out: Dict[str, List[str]] = {}
    with tempfile.TemporaryDirectory() as tmp:
        jax.config.update("jax_dump_ir_to", tmp)
        try:
            yield out
        finally:
            jax.config.update("jax_dump_ir_to", None)
            for fname in sorted(os.listdir(tmp)):
                m = re.fullmatch(r"jax_ir\d+_jit_(.+)_compile\.mlir", fname)
                if m and m.group(1).startswith(prefixes):
                    with open(os.path.join(tmp, fname)) as f:
                        out.setdefault(m.group(1), []).append(f.read())


def bytes_per_device(tree: Any) -> Dict[int, int]:
    """Bytes each device holds of ``tree``, read from the live shards."""
    out: Dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) \
                + shard.data.nbytes
    return out


def memory_stats() -> Dict[str, int]:
    """Allocator counters of device 0 ({} where the backend has none)."""
    stats = jax.devices()[0].memory_stats() or {}
    return {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use",
                                       "bytes_limit") if k in stats}


def _even_shares(per_dev: Dict[int, int], n: int, what: str,
                 tol: float = 0.15) -> None:
    """``what`` must sit on ``n`` devices in near-equal shares."""
    total = sum(per_dev.values())
    _require(len(per_dev) == n,
             f"{what}: on devices {sorted(per_dev)} — expected {n} devices")
    for dev, b in per_dev.items():
        _require(abs(b - total / n) <= tol * total / n,
                 f"{what}: device {dev} holds {b} of {total} bytes, "
                 f"expected ~1/{n}")


# ---------------------------------------------------------------------- #
# train stage
# ---------------------------------------------------------------------- #


def train_stage(model_cfg, ds_config: Dict[str, Any], steps: int = 4,
                seed: int = 0,
                devices: Optional[Sequence[Any]] = None) -> Dict[str, Any]:
    """``dstpu.initialize`` -> ``steps`` x ``engine.train_batch`` on one
    repeated batch, over ``devices`` (default: all of them). Returns the
    report; raises :class:`SmokeFailure` when the loss is not finite and
    falling."""
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.models.gpt2 import make_model

    clock = _Phases()
    _, init_fn, loss_fn = make_model(model_cfg)
    seq = model_cfg.max_seq_len - 1
    params = jax.jit(functools.partial(init_fn, batch_size=1, seq_len=seq))(
        jax.random.PRNGKey(seed))
    n_params = sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
    topology = None if devices is None else dstpu.build_mesh(
        MeshConfig(**ds_config.get("mesh", {})), devices=devices)
    engine, _, _, _ = dstpu.initialize(loss_fn=loss_fn, params=params,
                                       config=ds_config, topology=topology)
    del params                     # the engine owns its own copy
    clock.mark("init")
    B = engine.config.train_batch_size
    tokens = np.random.RandomState(seed).randint(
        0, model_cfg.vocab_size, size=(B, seq + 1))
    batch = {"tokens": jnp.asarray(tokens, jnp.int32)}

    # the program train_batch is about to run, read before it runs: the
    # Mosaic calls in it and the collectives the partitioner placed
    hlo = engine._train_step.lower(engine.state, batch).compile().as_text()
    report: Dict[str, Any] = {
        "n_params": n_params, "batch": B, "seq": seq,
        "mesh": dict(engine.topology.axis_sizes),
        "zero_stage": engine.zero_plan.stage,
        "mosaic_calls": hlo.count(MOSAIC_CALL),
        "collectives": {k: hlo.count(f" {k}(") + hlo.count(f" {k}-start(")
                        for k in ("all-gather", "reduce-scatter",
                                  "all-reduce")},
    }
    del hlo
    clock.mark("lower_compile")
    losses: List[float] = []
    step_s: List[float] = []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = jax.block_until_ready(engine.train_batch(batch))
        step_s.append(round(time.perf_counter() - t0, 3))
        losses.append(float(loss))
    clock.mark("steps")
    report.update(
        losses=[round(v, 4) for v in losses], step_s=step_s,
        phase_s=clock.s,
        params_bytes_per_device=bytes_per_device(engine.state.params),
        opt_bytes_per_device=bytes_per_device(engine.state.opt_state),
        memory=memory_stats())
    _require(bool(np.all(np.isfinite(losses))), f"loss not finite: {losses}")
    _require(losses[-1] < losses[0], f"loss not falling: {losses}")
    return report


# ---------------------------------------------------------------------- #
# serve stage
# ---------------------------------------------------------------------- #

#: how far below the reference engine's best logit a served greedy token
#: may sit, in standard deviations of that logit row. The engines compute
#: in bf16 and differ in summation order (flash tiles vs one dense
#: softmax, tp partial sums), so their logits differ by ~1e-2 sigma while
#: the top two of 32000 random-weight logits are ~0.2 sigma apart:
#: near-ties flip an argmax a few times per hundred tokens, and exact
#: token equality would fail on noise. A wrong mask, scale or block table
#: moves the served token several sigma down the reference's ranking.
TOP1_TOL_SIGMA = 0.1


def llama_params(model_cfg, seed: int = 0):
    """Random weights from a seed, made on device in ``param_dtype``."""
    from deepspeed_tpu.models.llama import Llama
    model = Llama(model_cfg)
    return jax.jit(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.PRNGKey(seed))


def _smoke_requests(mix, n: int, sampled_every: int, seed: int):
    """``n`` burst requests plus the per-uid sampling map: every
    ``sampled_every``-th request samples (explicit seeds, so a second pass
    must reproduce the stream; 0 = none), the rest are greedy.

    All arrivals are at t=0 with the door held at ``max_seqs``: which
    requests share a step then depends only on their lengths, never on
    how long a compile took, so the cold and the warm pass run the same
    programs and the warm pass can be held to zero compiles."""
    from deepspeed_tpu.inference.v2 import SamplingParams
    from deepspeed_tpu.telemetry.loadgen import TraceArrivals, build_requests
    reqs = build_requests(TraceArrivals([0.0] * n), mix, n, seed=seed)
    sampling = {r.uid: SamplingParams(temperature=0.8, top_k=40, top_p=0.95,
                                      seed=1000 + r.uid)
                for r in reqs if sampled_every
                and r.uid % sampled_every == sampled_every - 1}
    return reqs, sampling


def _open_loop(engine, reqs, sampling, max_live) -> Dict[int, List[int]]:
    from deepspeed_tpu.telemetry.loadgen import run_open_loop
    res = run_open_loop(engine, reqs, decode_burst=8, max_live=max_live,
                        sampling=sampling)
    rq = res.report["requests"]
    _require(rq["completed"] == len(reqs) and rq["balance_ok"],
             f"open loop did not complete every request: {rq}")
    for r in reqs:
        _require(len(res.streams[r.uid]) == r.gen_len,
                 f"request {r.uid}: {len(res.streams[r.uid])} tokens, "
                 f"asked {r.gen_len}")
    return res.streams


def _fused_tokens(engine, reqs, n: int) -> Dict[int, List[int]]:
    """The first ``n`` greedy tokens of each request through ``put`` and
    the fused on-device decode loop (``decode_batch``)."""
    uids = [r.uid for r in reqs]
    first = engine.put(uids, [r.prompt for r in reqs], _greedy=True)
    rest = engine.decode_batch(uids, [first[u] for u in uids], n - 1)
    for u in uids:
        engine.flush(u)
    return {u: [first[u]] + list(rest[u]) for u in uids}


def _top1_gap(ref_engine, reqs, streams, n: int) -> Dict[str, Any]:
    """Teacher-force ``ref_engine`` with each request's served tokens and
    measure, per position, how far the served token's logit sits below
    the reference's best (0 = the reference picks the same token)."""
    uids = [r.uid for r in reqs]
    feed = [list(r.prompt) for r in reqs]
    worst, exact = 0.0, 0
    for t in range(n):
        logits = ref_engine.put(uids, feed)
        for u in uids:
            row = np.asarray(logits[u], np.float32)
            gap = float(row.max() - row[streams[u][t]]) / float(row.std())
            worst = max(worst, gap)
            exact += gap == 0.0
        feed = [[streams[u][t]] for u in uids]
    for u in uids:
        ref_engine.flush(u)
    return {"worst_gap_sigma": round(worst, 4), "same_top1": exact,
            "of": n * len(uids)}


def serve_stage(model_cfg, engine_cfg, mix, n_requests: int,
                reference: Dict[str, Any], sampled_every: int = 3,
                parity_requests: int = 4, parity_tokens: int = 16,
                seed: int = 0) -> Dict[str, Any]:
    """Open-loop serving through ``InferenceEngineV2``, checked against a
    reference engine on the same weights that differs from ``engine_cfg``
    by the ``reference`` overrides (``attention_impl="dense"`` on one
    chip, ``tp_size=1`` against a tensor-parallel engine)."""
    from deepspeed_tpu.analysis.program_audit import RecompileTripwire
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    clock = _Phases()
    params = llama_params(model_cfg, seed)
    engine = InferenceEngineV2(model_cfg, params, engine_cfg)
    reqs, sampling = _smoke_requests(mix, n_requests, sampled_every, seed)
    clock.mark("init")
    greedy = [r for r in reqs if r.uid not in sampling
              and r.gen_len >= parity_tokens][:parity_requests]
    _require(len(greedy) == parity_requests,
             "too few greedy requests for the parity check")
    # the engine's step programs (prefill chunk, fed decode step, fused
    # decode loop; greedy and sampled), as lowered for this very run
    with lowered_programs("_step", "_decode_loop") as programs:
        cold = _open_loop(engine, reqs, sampling, engine_cfg.max_seqs)
        clock.mark("cold_pass")
        fused = _fused_tokens(engine, greedy, parity_tokens)
        clock.mark("fused_loop")
    # which of _step_greedy / _step_greedy_fb / _step_sample_fb a pass
    # touches follows from which requests share a step; every one it did
    # touch is held to the same checks
    steps = [t for name, texts in programs.items() for t in texts
             if name.startswith("_step")]
    _require(steps and "_decode_loop_ring" in programs,
             f"the serve stage lowered no step program: {sorted(programs)}")
    report: Dict[str, Any] = {
        "requests": n_requests, "sampled": len(sampling),
        "mosaic_calls": {name: min(t.count(MOSAIC_CALL) for t in texts)
                         for name, texts in programs.items()},
        "specializations": {name: len(texts)
                            for name, texts in programs.items()},
        "collectives": {k: min(t.count(f"stablehlo.{k}") for t in steps)
                        for k in ("all_reduce", "all_gather")},
    }
    del programs, steps

    # warm pass: same requests, same programs — nothing may compile, and
    # every stream (sampled ones too: their seeds are explicit) repeats
    with RecompileTripwire() as trip:
        warm = _open_loop(engine, reqs, sampling, engine_cfg.max_seqs)
    clock.mark("warm_pass")
    report["warm_fresh_compiles"] = trip.fresh_compiles
    _require(trip.fresh_compiles == 0,
             f"warm pass compiled {trip.fresh_compiles} program(s)")
    _require(warm == cold, "warm pass streams differ from the cold pass")
    report["output_tokens"] = sum(len(s) for s in warm.values())
    report["kv_pool_bytes_per_device"] = bytes_per_device(engine._kv_data)
    report["params_bytes_per_device"] = bytes_per_device(engine.params)

    ref_engine = InferenceEngineV2(
        model_cfg, params, dataclasses.replace(engine_cfg, **reference))
    report["reference"] = reference
    for path, streams in (("pipelined", cold), ("fused_loop", fused)):
        gap = _top1_gap(ref_engine, greedy, streams, parity_tokens)
        report[f"parity_{path}"] = gap
        _require(gap["worst_gap_sigma"] <= TOP1_TOL_SIGMA,
                 f"{path} greedy tokens are not the {reference} engine's "
                 f"top-1 within {TOP1_TOL_SIGMA} sigma: {gap}")
    clock.mark("reference")
    report.update(phase_s=clock.s, memory=memory_stats())
    return report


# ---------------------------------------------------------------------- #
# replica stage (one-chip engines behind the router)
# ---------------------------------------------------------------------- #


def replica_stage(model_cfg, engine_cfg, mix, n_requests: int,
                  devices: Sequence[Any], seed: int = 0) -> Dict[str, Any]:
    """One engine per device behind a ``ReplicaPool``; AFTER serving,
    every replica's weights and KV pool must sit on its own device and
    every replica must have run steps."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.serving import ReplicaPool, build_replica_engines

    params = llama_params(model_cfg, seed)
    engines = build_replica_engines(
        lambda i, dev: InferenceEngineV2(
            model_cfg, jax.device_put(params, dev), engine_cfg),
        len(devices), devices=devices)
    pool = ReplicaPool(engines, policy="round_robin")
    # greedy only: every replica compiles its own copy of each program,
    # and the sampled variants are the one-chip serve stage's business
    reqs, sampling = _smoke_requests(mix, n_requests, 0, seed)
    _open_loop(pool, reqs, sampling, None)
    report: Dict[str, Any] = {"requests": n_requests, "replicas": {}}
    for rep, dev in zip(pool.replicas(), devices):
        eng = rep.engine
        where = {"params": sorted(bytes_per_device(eng.params)),
                 "kv_pool": sorted(bytes_per_device(eng._kv_data)),
                 "steps": eng._step_counter}
        report["replicas"][rep.replica_id] = where
        _require(where["steps"] > 0, f"{rep.replica_id} served nothing")
        for what in ("params", "kv_pool"):
            _require(where[what] == [dev.id],
                     f"{rep.replica_id} {what} on devices {where[what]}, "
                     f"expected [{dev.id}]")
    return report


# ---------------------------------------------------------------------- #
# the full-width configurations
# ---------------------------------------------------------------------- #


def gpt2_1p3b():
    from deepspeed_tpu.models.gpt2 import GPT2Config
    return GPT2Config(
        vocab_size=50304, max_seq_len=2048 + 1, num_layers=24, num_heads=16,
        hidden_size=2048, param_dtype=jnp.bfloat16, remat=True,
        remat_policy="qkv_out", flash_block_q=1024, flash_block_k=1024)


def train_config(micro: int, zero_stage: int, data: int) -> Dict[str, Any]:
    return {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        # bf16-stored moments: fp32 Adam state for 1.31B params alone is
        # 15.7 GiB; lr large enough that a 4-step trajectory shows through
        # bf16 update rounding
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 3e-4, "weight_decay": 0.01,
                                 "moment_dtype": "bfloat16"}},
        "bf16": {"enabled": True},
        "data_types": {"grad_accum_dtype": "bfloat16"},
        "zero_optimization": {"stage": zero_stage},
        "gradient_clipping": 1.0,
        "steps_per_print": 10_000,
        "mesh": {"data": data},
    }


def tinyllama_1p1b(num_layers: int = 22):
    from deepspeed_tpu.models.llama import LlamaConfig
    return LlamaConfig(
        vocab_size=32000, max_seq_len=2048, num_layers=num_layers,
        num_heads=32, num_kv_heads=4, hidden_size=2048,
        intermediate_size=5632, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)


PROMPT_MAX, GEN_MAX, SLOTS = 512, 128, 16


def serve_config(**overrides):
    """The linear pool layout: ONE 128-aligned block of PROMPT_MAX +
    GEN_MAX tokens per sequence, so a kernel grid step streams a whole
    context as one DMA and the int8 rows tile. One slot bucket (16), so
    the slot dimension adds no program specializations."""
    from deepspeed_tpu.inference.v2 import RaggedInferenceConfig
    kw = dict(max_seqs=SLOTS, chunk_size=PROMPT_MAX,
              block_size=PROMPT_MAX + GEN_MAX, num_blocks=SLOTS + 4,
              max_blocks_per_seq=1, decode_loop_steps=64, dtype="bfloat16",
              kv_cache_dtype="int8", prefill_chunk_cap=0, max_batch_tokens=0)
    kw.update(overrides)
    return RaggedInferenceConfig(**kw)


def serve_mix():
    from deepspeed_tpu.telemetry.loadgen import WorkloadMix
    return WorkloadMix(prompt_lens=(128, 256, 384, PROMPT_MAX),
                       prompt_probs=(0.25, 0.25, 0.25, 0.25),
                       gen_lens=(32, 64, 96, GEN_MAX),
                       gen_probs=(0.25, 0.25, 0.25, 0.25), vocab_size=32000)


def live_array_bytes() -> int:
    """Bytes of every array the process still references, any backend."""
    return sum(a.nbytes for a in jax.live_arrays())


def free_stage(tag: str) -> Dict[str, int]:
    """After a stage returned: drop what it registered, collect, and say
    what the process still holds on the devices — an engine that cannot
    be freed shows up here, before the next stage needs the memory."""
    from deepspeed_tpu.parallel import topology
    topology._TOPOLOGY = None
    gc.collect()
    left = dict(memory_stats(), live_array_bytes=live_array_bytes())
    _say(f"{tag}.freed", left)
    return left


def _need_mosaic(stage: str, calls) -> None:
    counts = calls.values() if isinstance(calls, dict) else [calls]
    _require(all(c > 0 for c in counts),
             f"{stage}: a step program holds no Mosaic custom call "
             f"({calls}) — it resolved to dense/xla on the chip")


def one_chip_stages() -> None:
    rep = train_stage(gpt2_1p3b(), train_config(micro=2, zero_stage=0,
                                                data=1),
                      devices=jax.devices()[:1])
    _say("train", rep)
    _need_mosaic("train", rep["mosaic_calls"])
    free_stage("train")

    rep = serve_stage(tinyllama_1p1b(), serve_config(), serve_mix(),
                      n_requests=36, reference={"attention_impl": "dense"})
    _say("serve", rep)
    _need_mosaic("serve", rep["mosaic_calls"])
    free_stage("serve")


def train4_stage(four) -> None:
    """The 1.3B model, ZeRO-3 over ``data=4``: "auto" must pick the flash
    kernel under shard_map, and every chip must hold a quarter."""
    rep = train_stage(gpt2_1p3b(), train_config(micro=2, zero_stage=3,
                                                data=4), devices=four)
    _say("train4", rep)
    _need_mosaic("train4", rep["mosaic_calls"])
    _even_shares(rep["params_bytes_per_device"], 4, "ZeRO-3 params")
    _even_shares(rep["opt_bytes_per_device"], 4, "ZeRO-3 optimizer state")
    _require(rep["collectives"]["all-gather"] > 0
             and rep["collectives"]["reduce-scatter"]
             + rep["collectives"]["all-reduce"] > 0,
             f"ZeRO-3 step holds no gather/reduce: {rep['collectives']}")
    free_stage("train4")


#: depth of the llama shape in the two four-chip SERVE stages. Width is
#: what decides tiling, sharding and placement; depth only multiplies the
#: host-side cost, ~2 s of Python tracing and lowering per layer per
#: program specialization on the chip's host (PERF.md), times six
#: specializations, times five engines: at 22 layers these two stages
#: alone would hold four chips for over a quarter of an hour.
FOUR_CHIP_SERVE_LAYERS = 4


def serve_tp4_stage() -> None:
    """The llama shape at ``tp_size=4`` against the one-chip engine. 4 kv
    heads over 4 chips leave 64-wide rows, which the int8 kernel's DMA
    tiling refuses: tp=4 serves from the bf16 pool."""
    rep = serve_stage(tinyllama_1p1b(FOUR_CHIP_SERVE_LAYERS),
                      serve_config(tp_size=4, kv_cache_dtype="auto"),
                      serve_mix(), n_requests=24, reference={"tp_size": 1})
    _say("serve_tp4", rep)
    _need_mosaic("serve_tp4", rep["mosaic_calls"])
    _even_shares(rep["kv_pool_bytes_per_device"], 4, "tp=4 KV pool")
    _require(rep["collectives"]["all_reduce"] > 0,
             "tp=4 decode step holds no all-reduce")
    free_stage("serve_tp4")


def replicas4_stage(four) -> None:
    rep = replica_stage(tinyllama_1p1b(FOUR_CHIP_SERVE_LAYERS),
                        serve_config(), serve_mix(),
                        n_requests=16, devices=four)
    _say("replicas", rep)
    free_stage("replicas")


def four_chip_stages() -> None:
    four = jax.devices()[:4]
    train4_stage(four)
    serve_tp4_stage()
    replicas4_stage(four)


def main() -> int:
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
              f"({dev.device_kind}, {device['count']} device(s))",
              file=sys.stderr)
        return 2
    from importlib.metadata import version

    from deepspeed_tpu.ops.kernels import default_interpret
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    _say("device", device)
    _say("versions", {p: version(p) for p in ("jax", "jaxlib", "libtpu")})
    _say("compile_cache", enable_compile_cache())
    _require(not default_interpret(),
             "Pallas kernels would run interpreted on this backend")
    t_start = time.perf_counter()
    one_chip_stages()
    if device["count"] >= 4:
        four_chip_stages()
    _say("wall_s", round(time.perf_counter() - t_start, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
