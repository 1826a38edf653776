"""InferenceEngineV2 — continuous-batching ragged engine.

Analogue of the reference's ``InferenceEngineV2`` (``inference/v2/
engine_v2.py:30``): ``put(batch_uids, batch_tokens)`` feeds tokens for any
mix of new prompts and decode continuations, runs one fixed-shape forward
over whatever the SplitFuse scheduler picked, and returns last-token logits
for every sequence that completed its pending work this step. ``query`` /
``can_schedule`` expose KV-pressure hints; ``flush`` releases sequence state.
A built-in ``generate`` drives the put-loop with sampling for convenience.

The serving hot path is an overlapped pipeline (``serve_pipeline_depth``,
docs/serving.md): every step splits into **plan** (host: scheduler +
staged-buffer fill, runs ahead), **dispatch** (enqueue the compiled step —
JAX async dispatch keeps the result as an in-flight future in a small
ring) and **commit** (apply step k's readback while step k+1 executes).
Greedy decode keeps the feedback token on device: each step returns a
device-resident ``[S]`` last-token buffer that feeds the next step's token
slots directly, so the steady pure-decode state never round-trips tokens
through the host; EOS is reconciled on the delayed readback with explicit
rollback (dead in-flight slots, retracted positions, freed KV blocks).
Depth 0 is the fully synchronous path — the parity oracle.
"""

from __future__ import annotations

import functools
import os
import time
from collections import deque
from collections.abc import Mapping
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ...ops.kernels.delta_rule import (gdn_prefill_uses_kernel,
                                       kda_prefill_uses_kernel)
from ...ops.kernels import grouped_ffn, short_conv, sparse_attention
from ...ops.kernels.selective_scan import mamba1_prefill_uses_kernel
from ...resilience.fault_injection import get_fault_injector
from ...telemetry.serve import serve_observer
from ...telemetry.trace import SpanSet
from ...utils.dtypes import resolve_dtype
from ...utils.logging import log_dist, logger
from .blocked_allocator import OutOfBlocksError
from ..config import InferenceConfig
from .config import RaggedInferenceConfig
from .drain import (EngineDrainingError, ReplayJournal, ServeDrainError,
                    ServeStepError, build_manifest, write_manifest)
from .kv_cache import BlockedKVCache, window_step_rows
from .kv_write import runs_issued
from .model_runner import (GPT2RaggedRunner, RaggedBatch,
                           _attention_impl)
from .sampling import SamplingParams, stage_slot
from .scheduler import SplitFuseScheduler
from .sequence import SequenceStatus
from .state_manager import StateManager

#: placeholder value a speculatively scheduled decode token carries in
#: ``pending_tokens`` while its real value is still an in-flight device
#: future (the step program substitutes the device value; the host value
#: is patched in at commit if the placeholder is still queued)
_SPEC_TOKEN = -1

#: slot dimensions a step program is compiled for (capped at ``max_seqs``);
#: a step that carries prefill chunks and nothing more takes ``prefill_rows``
_SLOT_BUCKETS = (16, 32, 64, 128, 256, 512)


class _PlannedStep:
    """Host half of one step (the plan phase): the schedule plus its
    staged numpy arrays, ready to dispatch. ``sample`` is the staged
    (seeds, spos, temps, topks, topps) per-slot sampling arrays when
    any scheduled sequence samples (None = the pure-greedy program)."""

    __slots__ = ("sched", "tokens", "start", "ntok", "tables",
                 "feed_mask", "feed_idx", "use_greedy", "sample", "sslots")

    def __init__(self, sched, tokens, start, ntok, tables, feed_mask,
                 feed_idx, use_greedy, sample=None, sslots=None):
        self.sslots = sslots                # [S] state-pool rows, or None
        self.sched = sched
        self.tokens = tokens
        self.start = start
        self.ntok = ntok
        self.tables = tables
        self.feed_mask = feed_mask          # None when no slot is device-fed
        self.feed_idx = feed_idx
        self.use_greedy = use_greedy
        self.sample = sample


class _InFlightStep:
    """A dispatched, uncommitted step: the device-side result future plus
    the host bookkeeping needed to commit — or partially kill — it.
    ``dead`` slots were invalidated by a late EOS (their readback is
    discarded) or an abort; ``rollbacks`` are (seq, n_tokens) retractions
    that must wait until THIS step has executed (its KV writes still
    reference the blocks being freed); ``aborts`` are sequences whose
    flush is deferred to this step's commit for the same reason — it is
    the last in-flight step whose KV writes target their blocks."""

    __slots__ = ("sched", "result", "use_greedy", "dead", "rollbacks",
                 "aborts", "logprobs")

    def __init__(self, sched, result, use_greedy, logprobs=None):
        self.sched = sched
        self.result = result
        self.use_greedy = use_greedy
        self.dead: set = set()
        self.rollbacks: List[Tuple[Any, int]] = []
        self.aborts: List[Any] = []
        #: in-flight [S] chosen-token logprob buffer (the sampled
        #: programs emit it alongside the token buffer; None on greedy)
        self.logprobs = logprobs


def _runner_for(model_cfg: Any, cfg: RaggedInferenceConfig):
    """Arch dispatch (the reference's policy map, ``engine_factory.py:92``)."""
    from ...models.llama import LlamaConfig
    from ...models.opt import OPTConfig
    if isinstance(model_cfg, LlamaConfig):   # includes MixtralConfig
        from .llama_runner import LlamaRaggedRunner
        return LlamaRaggedRunner(model_cfg, cfg)
    if isinstance(model_cfg, OPTConfig):
        from .opt_runner import OPTRaggedRunner
        return OPTRaggedRunner(model_cfg, cfg)
    from ...models.falcon import FalconConfig
    from ...models.phi import PhiConfig
    if isinstance(model_cfg, FalconConfig):
        from .falcon_phi_runner import FalconRaggedRunner
        return FalconRaggedRunner(model_cfg, cfg)
    if isinstance(model_cfg, PhiConfig):
        from .falcon_phi_runner import PhiRaggedRunner
        return PhiRaggedRunner(model_cfg, cfg)
    from ...models.bloom import BloomConfig
    from ...models.gpt_neox import GPTJConfig, GPTNeoXConfig
    if isinstance(model_cfg, BloomConfig):
        from .bloom_gptj_neox_runner import BloomRaggedRunner
        return BloomRaggedRunner(model_cfg, cfg)
    if isinstance(model_cfg, GPTNeoXConfig):
        from .bloom_gptj_neox_runner import GPTNeoXRaggedRunner
        return GPTNeoXRaggedRunner(model_cfg, cfg)
    if isinstance(model_cfg, GPTJConfig):
        from .bloom_gptj_neox_runner import GPTJRaggedRunner
        return GPTJRaggedRunner(model_cfg, cfg)
    return GPT2RaggedRunner(model_cfg, cfg)


def _expert_stacks(params, local) -> Optional[tuple]:
    """The stacked expert matrices of the model's first sparse layer, as
    shapes and types behind ``local`` (the runner's in-jit view of the
    parameters: its dequant pass), in ``llama_runner._moe_mlp``'s order;
    None for a model with no routed experts."""
    layers = params.values() if isinstance(params, Mapping) else ()
    moe = next((p["moe"] for p in layers
                if isinstance(p, Mapping) and "moe" in p), None)
    if moe is None:
        return None
    names = [k for k in ("wi_gate", "wi_up", "wi", "wo") if k in moe]
    seen = jax.eval_shape(local, {k: moe[k] for k in names})
    return tuple(seen[k] for k in names)


class InferenceEngineV2:
    def __init__(self, model_cfg: Any, params: Any,
                 config: Optional[RaggedInferenceConfig] = None,
                 runner: Any = None, devices: Any = None):
        """``model_cfg``: a model config understood by a ragged runner
        (GPT2Config here; llama-family runners register the same interface).
        ``params``: the matching param pytree. ``devices``: optional
        explicit device list for the sharding mesh (seq/tp) — a replica
        pool hands each engine its DISJOINT slice
        (serving/pool.py ``build_replica_engines``)."""
        self.config = config or RaggedInferenceConfig()
        # decomposed-collective env override (the operational kill-switch /
        # force-on): DSTPU_TP_OVERLAP = off|rs_ag|rs_ag_chunked[:k],
        # DSTPU_TP_OVERLAP_CHUNKS = k. Applied BEFORE the runner builds so
        # the traced step functions close over the final schedule.
        if os.environ.get("DSTPU_TP_OVERLAP") \
                or os.environ.get("DSTPU_TP_OVERLAP_CHUNKS"):
            import dataclasses as _dc

            from ... import comm
            mode, chunks = comm.resolve_tp_overlap(
                self.config.tp_comm_overlap, self.config.tp_comm_chunks)
            # replace, never mutate: the caller's config object must not
            # silently inherit the env schedule (an oracle engine built
            # later from the same object would stop being the oracle)
            self.config = _dc.replace(
                self.config, tp_comm_overlap=mode,
                **({"tp_comm_chunks": chunks}
                   if mode == "rs_ag_chunked" else {}))
        # config × model validation at CONSTRUCTION (satellite of ISSUE
        # 20): unsupported combos (MoE×tp without ep, ep on a dense
        # model, ep not dividing num_experts) fail here with the knob
        # names instead of deep inside a trace
        self.config.validate(model_cfg)
        self.runner = runner or _runner_for(model_cfg, self.config)
        tp = self.config.tp_size
        if self.config.ep_size > 1:
            # expert-parallel MoE serving (expert_parallel.py): the
            # stacked expert weights shard over 'expert' (composing with
            # tp over 'model' on a 2-D mesh when tp_size > 1) and every
            # runner program rebuilds under the shard_map — host-side
            # scheduler/allocator stay single-program like TP/seq
            if not hasattr(self.runner, "init_ep"):
                raise ValueError(
                    f"runner {type(self.runner).__name__} does not support "
                    f"expert-parallel serving (no init_ep)")
            from .expert_parallel import build_ep_context
            ep_ctx, params = build_ep_context(self.config, self.runner,
                                              params, devices=devices)
            self.runner.init_ep(ep_ctx)
        elif tp > 1:
            # tensor-parallel serving (tp.py): params are re-laid/sharded
            # over the 'model' mesh and every runner program rebuilds under
            # shard_map — the host-side scheduler/allocator stay as-is
            if not hasattr(self.runner, "init_tp"):
                raise ValueError(
                    f"runner {type(self.runner).__name__} does not support "
                    f"tensor-parallel serving (no init_tp)")
            from .tp import build_tp_context
            tp_ctx, params = build_tp_context(self.config, self.runner,
                                              params, devices=devices)
            self.runner.init_tp(tp_ctx)
        elif self.config.seq_size > 1:
            # sequence-parallel serving (seq_parallel.py): the KV pool
            # shards round-robin by block home over the 'seq' mesh and
            # params REPLICATE — the axis shards context, not the model.
            # Host-side scheduler/allocator stay single-program (the
            # allocator grows per-home free lists, nothing else moves).
            if not hasattr(self.runner, "init_seq"):
                raise ValueError(
                    f"runner {type(self.runner).__name__} does not support "
                    f"sequence-parallel serving (no init_seq)")
            from .seq_parallel import build_seq_context
            seq_ctx, params = build_seq_context(self.config, self.runner,
                                                params, devices=devices)
            self.runner.init_seq(seq_ctx)
        self.params = params
        if self.config.kv_cache_dtype == "int8" \
                and self.config.attention_impl in ("auto", "paged_flash") \
                and jax.default_backend() == "tpu":
            # surface the Mosaic DMA-tiling constraint of the int8 decode
            # kernel at engine construction, not deep inside a compile
            # (the dense fallback dequantizes per row and has no such
            # constraint — it is exempt). Under TP the kernel sees the
            # PER-CHIP row width.
            kvd = self.runner.kv_heads * self.runner.head_dim // tp
            if kvd % 128:
                raise ValueError(
                    f"kv_cache_dtype='int8' with the paged-flash kernel "
                    f"needs per-chip kv_heads*head_dim ({kvd}) to be a "
                    f"multiple of 128 (int8 DMA tiling); use "
                    f"attention_impl='dense' or the bf16 pool for this "
                    f"head geometry")
            if self.config.block_size % 128:
                raise ValueError(
                    f"kv_cache_dtype='int8' with the paged-flash kernel "
                    f"needs block_size ({self.config.block_size}) to be a "
                    f"multiple of 128 (int8 DMA tiling); round block_size "
                    f"up, or use attention_impl='dense' or the bf16 pool")
        self.kv_cache = BlockedKVCache(
            self.config, self.runner.kv_layers, self.runner.kv_heads,
            self.runner.head_dim, dtype=resolve_dtype(self.config.dtype),
            state_spec=self.runner.state_spec,
            planes=self.runner.kv_planes,
            window_spec=self.runner.window_spec,
            index_spec=self.runner.index_spec)
        #: layer kind of the model's recurrent layers (None: it has none);
        #: what needs a state snapshot refuses by this name
        self._stateful = (self.runner.state_spec or {}).get("kind")
        #: a latent-attention model's cache has one plane a layer; what
        #: has not been carried over it refuses by name
        self._latent = self.runner.kv_planes == 1
        #: a model with sliding-window layers keeps their rows in a slot
        #: of the window pool; what would need those rows in a block, a
        #: manifest or a shard refuses by name
        self._windowed = self.kv_cache.window is not None
        #: a model with block-selected layers keeps their compressed keys
        #: in a plane beside the pool, which no manifest, scale or shard
        #: covers: what would need one refuses by name
        self._selecting = self.kv_cache.index is not None
        #: sequences name a slot (a state row, a window-pool row)
        self._slotted = bool(self._stateful) or self._windowed
        #: a sparse layer's expert stacks as the step programs see them
        #: (None: no routed experts, or they travel the expert-parallel
        #: path, which has no grouped kernel)
        self._moe_stacks = None if self.config.ep_size > 1 \
            else _expert_stacks(params, self.runner._local_params)
        if self.config.ep_size > 1:
            if self.runner.tp is not None:
                # composed ep×tp: the pool head-shards over 'model' on
                # the 2-D mesh (implicitly replicated over 'expert')
                self.kv_cache.shard(self.runner.epctx.mesh)
            else:
                # ep alone: the pool replicates — the batch (and every
                # KV write) is identical on all expert ranks
                self.kv_cache.shard_replicated(self.runner.epctx.mesh)
        elif tp > 1:
            # head-shard the pool at rest: per-chip KV bytes ∝ 1/tp — the
            # lever that lets a model's KV footprint span chips
            self.kv_cache.shard(self.runner.tp.mesh)
        elif self.config.seq_size > 1:
            # block-shard the pool at rest: per-chip KV bytes ∝ 1/seq as
            # CONTEXT grows — the capacity lever for long prompts
            self.kv_cache.shard_seq(self.runner.seqctx.mesh)
        if self.runner.tp is None and self.runner.seqctx is None \
                and self.runner.epctx is None:
            # a one-device engine lives where its weights sit: commit
            # them and the pool there, so a step dispatched from any
            # thread runs on that device (weights spread over a mesh the
            # engine does not own — the hybrid engine's — stay as given)
            on = {d for leaf in jax.tree_util.tree_leaves(params)
                  if isinstance(leaf, jax.Array) for d in leaf.devices()}
            if len(on) == 1:
                (dev,) = on
                self.params = jax.device_put(params, dev)
                self.kv_cache.pin(dev)
        self.state = StateManager(self.config, self.kv_cache)
        self._prefix = None
        if self.config.prefix_cache:
            # automatic prefix caching (prefix_cache.py): the index layers
            # on the allocator via the kv cache (evictable-block capacity,
            # pressure-driven eviction inside reserve) and on the state
            # manager (match/register/decref); put() drives it below
            from .prefix_cache import PrefixCache
            self._prefix = PrefixCache(
                self.config.block_size,
                max_blocks=self.config.prefix_cache_max_blocks,
                policy=self.config.prefix_cache_policy,
                host_blocks=self.config.prefix_cache_host_blocks)
            self.kv_cache.attach_prefix_cache(self._prefix)
            self.state.prefix = self._prefix
        self.scheduler = SplitFuseScheduler(self.config, self.state)
        self._kv_data = self.kv_cache.pool
        # hierarchical KV: demotion gathers must read the engine's
        # CURRENT functional pool value (every step rethreads it) —
        # hand the kv cache a live view, not a snapshot
        self.kv_cache.attach_pool_source(lambda: self._kv_data)
        self._step_counter = 0
        # overlapped serving pipeline: max in-flight steps (0 = the
        # synchronous parity oracle)
        self.pipeline_depth = self.config.serve_pipeline_depth
        # reused per-(S, C) staging buffers (host alloc churn is on the
        # overlap-critical path) — see _staging_bufs
        self._staging: Dict[Tuple[int, int], Dict[str, Any]] = {}
        # device feedback source: the latest dispatched greedy step's
        # [S] last-token buffer and each uid's slot in it
        self._feed_src = None
        self._feed_slot: Dict[int, int] = {}
        #: the engine's own totals, filled where the work happens: the
        #: ``*_s`` seconds by the brackets (telemetry/trace.py), the
        #: counts by _plan_step / _dispatch_step / decode_batch. Per step
        #: that carries a multi-token chunk: the scheduled chunk lengths
        #: (real) against S x T of the program it runs (planned), and the
        #: rows that carried more than one token (prefill_rows); per
        #: pure-decode step: live sequences against the slot bucket; per
        #: fused loop of a model with routed experts: the rows they took.
        #: Per pure-decode step and per fused loop: the settled K/V rows
        #: of the live sequences (decode_kv_rows_live) against the rows
        #: the paged kernel streams for them in whole copy tiles
        #: (decode_kv_rows_fetched; the kernel module's own arithmetic)
        self.pipeline_stats = {
            "steps": 0, "fed_steps": 0, "retries": 0, "plan_s": 0.0,
            "dispatch_s": 0.0, "commit_block_s": 0.0, "commit_apply_s": 0.0,
            "fused_dispatch_s": 0.0, "fused_apply_s": 0.0,
            # a call's own seconds (put, decode_pipelined, decode_batch)
            # and the rest of what lies under it: put's admission loop,
            # decode_batch's staging, the fused readback's wait, and the
            # counters' own arithmetic (plan_count_s nested in plan_s)
            "put_s": 0.0, "decode_pipelined_s": 0.0, "decode_batch_s": 0.0,
            "admit_s": 0.0, "plan_count_s": 0.0, "fused_stage_s": 0.0,
            "fused_readback_s": 0.0, "fused_count_s": 0.0,
            "prefill_tokens_real": 0,
            "prefill_tokens_planned": 0, "prefill_steps": 0,
            "prefill_rows": 0, "decode_slots_live": 0,
            "decode_slots_planned": 0,
            "decode_kv_rows_live": 0, "decode_kv_rows_fetched": 0,
            # bytes of settled K/V rows the live sequences hold over all
            # the layers that keep K/V, counted where decode_kv_rows_live
            # is (the paged twin of latent_bytes_live)
            "kv_bytes_live": 0,
            # rows stored into the paged pool, a layer's worth (real
            # positions of every step that writes it, ring rows of every
            # flush), and the contiguous windows the writer issued for
            # them (kv_write.runs_issued; trash windows not counted):
            # rows / runs is what a run carries, 1.0 for one-token steps
            "kv_write_rows": 0, "kv_write_runs": 0,
            "moe_rows_routed": 0, "moe_rows_hottest": 0,
            # routed rows whose expert another chip holds (a model told
            # it holds a share of each layer's experts), from the fused
            # loop's per-expert carry like the two above
            "moe_rows_elsewhere": 0,
            # the grouped expert kernel's work in the fused loop: held
            # experts with at least one row, and visits to them (times an
            # expert's matrices were streamed), over layers and steps
            "moe_experts_hit": 0, "moe_expert_reads": 0,
            # real positions of prefill steps through the sparse layers'
            # routed experts, and those of them in steps whose shape took
            # the grouped kernel (grouped_ffn.kernel_impl, as
            # llama_runner._moe_mlp asks it: all of them on a TPU over
            # plain floating stacks, none on a CPU)
            "moe_prefill_tokens": 0, "moe_prefill_kernel_tokens": 0,
            # recurrent models: state rows with a live tenant and their
            # bytes, per decode step (sampled where decode_slots_live
            # is, and per step of a fused loop), and the real positions
            # that went through the chunked delta rule; of those, the
            # ones of steps whose shape took the Pallas chunk kernel
            # (delta_rule.kda_prefill_uses_kernel / gdn_..,
            # selective_scan.mamba1_.., as the mixer asks it; none of a
            # Mamba-2 or Lightning layer's, whose chunked form has no
            # kernel yet). ``state_bytes_resident`` counts the
            # same slots by what the device STORES for one, its arrays'
            # last two dimensions in whole tiles
            # (kv_cache.state_bytes_per_slot(resident=True)): over
            # ``state_bytes_live`` it says what a layout pads
            # (``state_bytes_padding``, their difference, is counted too:
            # the benchmark's readers sum and divide)
            "state_slots_live": 0, "state_bytes_live": 0,
            "state_bytes_resident": 0, "state_bytes_padding": 0,
            "linear_attn_prefill_tokens": 0,
            "linear_attn_prefill_kernel_tokens": 0,
            # layer-steps of decode through a short convolution (recurrent
            # layers that have one x decode steps, a fused loop's and a
            # pipelined step's alike), and of those the ones that ran the
            # in-place kernel on the pool of carried inputs
            # (short_conv.decode_uses_kernel, as the mixers ask it: all
            # of a step's or none)
            "conv_steps": 0, "conv_steps_in_place": 0,
            # latent-attention models, a layer's worth each: settled
            # latent rows of the live sequences per pure-decode step (and
            # per step of a fused loop), the rows the decode kernel
            # streams for them in whole copy tiles (each ONCE: the row is
            # key and value), the bytes the live rows are over all layers;
            # per prefill step the real positions through the absorbed
            # prefill. These stand IN PLACE of decode_kv_rows_*: such a
            # model has no K/V rows and never runs that kernel (beside
            # recurrent layers the state_* counters fill in the same run)
            "latent_rows_live": 0, "latent_rows_fetched": 0,
            "latent_bytes_live": 0, "mla_prefill_tokens": 0,
            # models with sliding-window layers, a layer's worth each:
            # the settled rows of its window ONE window layer must read
            # for the live sequences per pure-decode step (and per step
            # of a fused loop, whose own rows ride the ring), at most the
            # window; the rows the decode kernel streams for them in
            # whole copy tiles; the score columns its plan holds for them
            # (every chunk of the call at its rows); the bytes the live
            # rows are over all window layers. decode_kv_rows_* and
            # kv_bytes_live keep their meaning over such a model's FULL
            # layers
            "window_rows_live": 0, "window_rows_fetched": 0,
            "window_rows_scored": 0, "window_bytes_live": 0,
            # models with block-selected layers, a layer's worth each, per
            # pure-decode step (and per step of a fused loop) over the live
            # sequences past ``dense_len``: the rows ONE sparse layer's
            # attention must read (topk blocks a kv head, kv heads
            # counted: 2 x 4,096 at the published sizes; the sparse
            # decode kernel copies the listed blocks and no other) and
            # the settled rows a dense call would have read (a kv head
            # each, so that selected / live is the share of the context
            # the selection's sizes imply a step reads: host arithmetic,
            # the kernel's traced time is the evidence). Per prefill chunk
            # (read back from the program: ``KVPool.sel_counts``) the
            # selection blocks its real queries selected at or before
            # their own and those the block-union kernel's tiles visited
            # for them. ``sparse_dense_tokens``: positions (prefill and
            # decode) whose context was below ``dense_len``, which attend
            # over every key through the paged pool's own kernels
            # ``sparse_select_queries``: the real queries (prefill and
            # decode) past ``dense_len``, each of which selected; of
            # those, ``sparse_select_kernel_queries`` had their blocks'
            # scores made by the selection kernel
            # (sparse_attention.select_uses_kernel, as the layer asks it:
            # all of a step's or none; 0 off the TPU)
            "sparse_rows_selected": 0, "sparse_rows_live": 0,
            "sparse_select_queries": 0, "sparse_select_kernel_queries": 0,
            "sparse_prefill_blocks_selected": 0,
            "sparse_prefill_blocks_visited": 0,
            "sparse_dense_tokens": 0}
        #: ``KVPool.sel_counts`` as last read back
        self._sel_counts_seen = np.zeros((2,), np.int64)
        #: rows the decode kernel this model runs streams for a sequence
        #: of so many settled tokens (each kernel module's own arithmetic)
        if self._latent:
            from ...ops.kernels.mla_attention import decode_rows_fetched
            self._kv_rows_fetched = functools.partial(
                decode_rows_fetched, block_size=self.config.block_size)
        else:
            from ...ops.kernels import (decode_rows_fetched,
                                        decode_rows_scored, decode_tile_rows)
            kv_row = self.runner.local_kv_heads * self.runner.head_dim
            itemsize = 1 if self.config.kv_cache_dtype == "int8" \
                else np.dtype(resolve_dtype(self.config.dtype)).itemsize
            #: the tile the decode kernel copies by, over either pool
            self._window_tile = decode_tile_rows(
                self.config.block_size, kv_row, itemsize)
            if self._windowed:
                #: score columns a window layer's decode call of so many
                #: slots holds a sequence (the kernel's own plan)
                self._window_scored = functools.partial(
                    decode_rows_scored,
                    ctx_rows=self.config.max_blocks_per_seq
                    * self.config.block_size,
                    tile_rows=self._window_tile,
                    row_bytes=kv_row * itemsize,
                    window=self.runner.window_spec["window"])
            self._kv_rows_fetched = functools.partial(
                decode_rows_fetched, tile_rows=self._window_tile,
                # one window for every layer, kept whole in the paged pool;
                # a model that LISTS its window layers keeps those in the
                # window pool and these counters over its full layers
                window=None if self._windowed
                else getattr(model_cfg, "sliding_window", None))
        #: bytes of one token's latent rows over all layers, as the model
        #: states them (the stored row's zero tail left out)
        self._latent_token_bytes = self.runner.kv_layers \
            * getattr(model_cfg, "head_dim", 0) \
            * np.dtype(resolve_dtype(self.config.dtype)).itemsize \
            if self._latent else 0
        self._spans = SpanSet(self.pipeline_stats, lambda: self._obs)
        # ---- serve-side resilience (drain.py, docs/resilience.md) ---- #
        cfg = self.config
        self.request_deadline_s = cfg.request_deadline_s
        #: True once ANY sequence carries a deadline (engine-level knob
        #: or a per-request ``put(..., deadlines=...)`` entry) — the
        #: deadline sweep's cheap skip must not assume the engine knob
        #: is the only deadline source
        self._has_deadlines = self.request_deadline_s > 0
        self.serve_step_retries = cfg.serve_step_retries
        self.serve_retry_backoff_s = cfg.serve_retry_backoff_s
        self.serve_shed = cfg.serve_shed
        # paths are deployment settings: the environment may place them,
        # read with LITERAL names so the dslint knob scan (DSL004/5) and
        # gen_config_doc keep seeing them
        jpath = os.environ.get("DSTPU_SERVE_JOURNAL") or cfg.serve_journal
        self.journal = ReplayJournal(
            jpath,
            fsync=os.environ.get("DSTPU_SERVE_JOURNAL_FSYNC") == "1") \
            if jpath else None
        self._manifest_path = \
            os.environ.get("DSTPU_SERVE_DRAIN_MANIFEST") or None
        # ---- speculative decoding (speculative.py, docs/serving.md) -- #
        # attributes, not config reads: the admission controller's
        # brownout ladder lowers spec_mode / spec_k on a live engine
        self.spec_mode = cfg.spec_decode
        self.spec_k = cfg.spec_k
        self.spec_ngram = cfg.spec_ngram
        #: paired draft engine (attach_draft) for spec_mode='draft'
        self._draft_engine = None
        #: lazy proposer instance (speculative.build_proposer)
        self._proposer = None
        #: PreemptionHandler polled inside the pipeline (attach_preemption)
        self.preemption = None
        self._watchdog = None
        if os.environ.get("DSTPU_SERVE_WATCHDOG") == "1":
            from ...resilience.watchdog import StepWatchdog
            self.attach_watchdog(StepWatchdog(action="log"))
        self._drain_requested = False
        self._drained = False
        self._live_ring: Optional[deque] = None
        #: structured rejections (load shedding, deadlines, drain-time
        #: admission refusals): uid -> record. The serving layer above
        #: turns these into 503-style responses; tests assert on them.
        self.rejections: Dict[int, Dict[str, Any]] = {}
        #: telemetry observer (telemetry/serve.py; None when
        #: DSTPU_TELEMETRY=0 — the zero-overhead path): per-request SLO
        #: metrics + the phase flight recorder, recorded only at the
        #: host-side plan/commit boundaries this loop already owns
        self._obs = serve_observer(self)
        log_dist(
            f"InferenceEngineV2 ready: {self.config.max_seqs} slots x "
            f"{self.config.chunk_size} tokens "
            f"(prefill chunk cap {self.config.effective_chunk}), "
            f"{self.config.num_blocks} KV blocks x {self.config.block_size}"
            + (f", tp={tp}" if tp > 1 else "")
            + (", prefix_cache=on" if self._prefix is not None else ""))

    # ------------------------------------------------------------------ #
    # reference-parity surface
    # ------------------------------------------------------------------ #

    def put(self, batch_uids: Sequence[int],
            batch_tokens: Sequence[Sequence[int]],
            _greedy: bool = False,
            arrivals: Optional[Dict[int, float]] = None,
            deadlines: Optional[Dict[int, float]] = None,
            sampling: Optional[Dict[int, SamplingParams]] = None,
            traces: Optional[Dict[int, str]] = None
            ) -> Dict[int, Any]:
        """Feed tokens, run scheduled steps until all fed work is consumed,
        return {uid: last-token logits} for sequences with no pending work
        (or {uid: argmax token id} on the internal ``_greedy`` fast path,
        which keeps sampling on-device — used by :meth:`generate`).

        The KV pool may be oversubscribed: when the scheduler starves, the
        engine pauses (host-offloads) least-recently-scheduled idle sequences
        to free blocks, and resumes paused sequences as room appears — the
        reference's state manager exists precisely to oversubscribe
        (``inference/v2/ragged/kv_cache.py:166,176``).

        Runs through the overlapped pipeline: up to ``pipeline_depth``
        steps are planned and dispatched ahead of the oldest step's
        commit (chunks of one sequence may span in-flight steps — the
        device orders them through the KV-pool data dependence). Depth 0
        plans, dispatches and commits each step synchronously.

        Admission control (docs/resilience.md "Serving"): while the
        engine is DRAINING, and for fresh prompts that could never fit
        the KV pool even after eviction, the request is refused with a
        structured record in :attr:`rejections` (never a crash) and its
        uid is simply absent from the returned dict.

        Admission hooks for open-loop drivers (telemetry/loadgen.py):
        ``arrivals`` maps uid -> the request's ``time.monotonic()``
        ARRIVAL stamp (typically in the past when admission lagged the
        arrival clock) — used as the telemetry admission stamp and the
        deadline anchor, so queue-wait/TTFT measure from when the
        request was offered, not from when the engine got around to it;
        ``deadlines`` maps uid -> a per-request deadline in seconds
        (overriding the engine-level ``request_deadline_s``). Both
        apply to FRESH sequences only.

        Per-request sampling (docs/serving.md "Sampling"): ``sampling``
        maps uid -> :class:`~.sampling.SamplingParams`, attached at
        admission and carried for the sequence's life (manifested
        across drain/replay). On the ``_greedy`` fast path a sampled
        sequence's last-chunk token is selected ON DEVICE by the
        per-slot sampler — temperature 0 reproduces greedy
        token-for-token.

        ``traces`` maps uid -> a fleet-wide trace id (minted by
        ``ReplicaPool.put``, or any caller's correlation id): attached
        at admission, tagged onto every request-lifecycle flight span,
        and carried through the drain manifest so a replayed request's
        survivor spans join the same logical track
        (docs/observability.md "Distributed tracing")."""
        with self._spans.span("serve/put", requests=len(batch_uids)):
            # pure host work, nothing of this call queued on the device yet
            with self._spans.span("serve/admit"):
                admitted: List[int] = []
                bs = self.config.block_size
                for uid, toks in zip(batch_uids, batch_tokens):
                    seq0 = self.state.get(uid)
                    fresh = seq0 is None or (seq0.seen_tokens == 0
                                             and not seq0.kv_blocks)
                    if self._draining():
                        # a FRESH request is refused outright — the client
                        # must retry on another replica. A continuation of
                        # a LIVE sequence is simply not fed: that sequence
                        # rides the drain manifest (a rejection record here
                        # would double-route the same request — replayed by
                        # the survivor AND retried by the client)
                        if fresh:
                            self._reject(uid, "draining",
                                         detail="engine is draining for "
                                         "preemption")
                        continue
                    if fresh and self.serve_shed:
                        # load shedding at the door: a prompt whose KV (plus
                        # one generated token) exceeds the WHOLE pool can
                        # never be served, eviction or not — shed it before
                        # it poisons the scheduler (serve_shed=False keeps
                        # the legacy hard starvation RuntimeError instead)
                        need = -(-(len(toks) + 1) // bs)
                        if need > self.config.num_blocks:
                            self._reject(
                                uid, "kv_pool_exhausted",
                                needed_blocks=need,
                                num_blocks=self.config.num_blocks,
                                detail="prompt exceeds the whole KV pool")
                            continue
                    seq = self.state.put_tokens(uid, toks)
                    admitted.append(uid)
                    # a reused uid sheds its STALE rejection record —
                    # generate() and the serving layer treat a present record
                    # as "this request failed", which must only ever mean
                    # THIS admission
                    self.rejections.pop(uid, None)
                    if fresh:
                        sp = sampling.get(uid) if sampling else None
                        if sp is not None:
                            seq.sampling = sp
                        tid = traces.get(uid) if traces else None
                        if tid is not None:
                            # set BEFORE on_admit: the admit span must already
                            # carry the trace context
                            seq.trace_id = tid
                        arrived = arrivals.get(uid) if arrivals else None
                        seq.put_at = time.monotonic()
                        if arrived is None:
                            arrived = seq.put_at
                        if self._obs is not None:
                            self._obs.on_admit(seq, arrived)
                        dl = deadlines.get(uid) if deadlines else None
                        if dl is None and self.request_deadline_s > 0:
                            dl = self.request_deadline_s
                        if dl is not None and dl > 0 \
                                and seq.deadline_at is None:
                            seq.deadline_at = dl + arrived
                            seq.deadline_s = dl
                            self._has_deadlines = True
                        if self.journal is not None \
                                and seq.seen_tokens == 0 and not seq.kv_blocks:
                            # prompt still building: (re-)journal the full
                            # chain (+ sampling identity, so a hard-crash
                            # replay keeps the stream deterministic)
                            self.journal.admit(uid, seq.prompt_log,
                                               sampling=seq.sampling.to_dict()
                                               if seq.sampling is not None
                                               else None,
                                               trace=seq.trace_id)
                    if self._prefix is not None:
                        self._match_prefix(seq)
            done: Dict[int, np.ndarray] = {}

            def work_left():
                return any(s.in_flight for s in self.state.sequences.values())

            def commit_one(ring):
                _, step_done = self._commit_step(ring.popleft())
                done.update(step_done)

            self._drive_pipeline(
                work_left, lambda: self._plan_step(greedy=_greedy), commit_one)
            if self._prefix is not None:
                self._register_prefix(admitted)
            # the last step's result has been read: the programs that
            # counted are done
            self._read_sel_counts()
            return done

    def _match_prefix(self, seq) -> None:
        """Prefix-cache hit path: point a fresh prompt's table at the
        longest cached block chain and dispatch the device work the
        match requested — CoW row copies for partial-tail hits and
        host→device promotion scatters for hierarchical-KV hits. All
        non-blocking enqueues on the functional pool thread, so later
        steps (and later matchers' reads) order after them on device;
        the scatters additionally get a promote-ahead scheduler tick
        (scheduler.py) to overlap under other sequences' chunks. A
        DSL001-registered hot path: matching must never block on the
        device. ``promote_wait_s`` records the host-side dispatch cost
        of the promotion — the only part of a demoted hit the plan path
        pays; the transfer itself overlaps."""
        plan = self.state.match_prefix(seq)
        if plan:
            # serve fault site: a replica dying between the match (table
            # already points at shared blocks) and the CoW dispatch
            get_fault_injector().maybe_fire("during_cow_copy")
        for src, dst in plan.copies:
            self._kv_data = self.kv_cache.copy_block(self._kv_data, src,
                                                     dst)
        if plan.promotes:
            # ONE batched scatter for the whole promoted chain — k
            # per-block dispatches would put k eager-op launches on the
            # plan path (the promote_exposed_frac lever)
            t0 = time.perf_counter()
            self._kv_data = self.kv_cache.promote_blocks(
                self._kv_data, plan.promotes)
            if self._obs is not None:
                # promoted_blocks, not len(promotes): a host-tier CoW
                # tail scatters without flipping its source entry, and
                # the live counter must match stats["promoted"] exactly
                self._obs.on_promote(plan.promoted_blocks,
                                     time.perf_counter() - t0)

    def _register_prefix(self, batch_uids) -> None:
        """Insert this put() call's fully-prefilled prompt blocks into
        the cache (their KV writes are dispatched; device ordering makes
        them safe to share). DSL001-registered with ``_match_prefix``."""
        for uid in batch_uids:
            seq = self.state.get(uid)
            if seq is not None:
                self.state.register_prefix(seq)

    @property
    def prefix_stats(self) -> Dict[str, Any]:
        """Merged host-side prefix-cache counters plus the skipped-chunk
        fraction: matched tokens never ran a prefill chunk; the fraction
        is matched / (matched + prefilled prompt tokens)."""
        st = dict(self.state.prefix_stats)
        if self._prefix is not None:
            st.update(self._prefix.stats)
            st["cached_blocks"] = self._prefix.cached_blocks
            st["evictable_blocks"] = self._prefix.evictable_blocks
            st["host_cached_blocks"] = self._prefix.host_cached_blocks
            st["host_tier_blocks"] = self._prefix.host_blocks
        ran = st["prefill_tokens"]
        hit = st["matched_tokens"]
        st["prefill_chunks_skipped_frac"] = (
            hit / (hit + ran) if hit + ran else 0.0)
        # hierarchical KV: the fraction of matched tokens the HOST tier
        # served
        st["host_hit_frac"] = (
            st["host_matched_tokens"] / hit if hit else 0.0)
        return st

    def _drive_pipeline(self, work_left, make_plan, commit_one,
                        on_dispatch=None) -> None:
        """The shared ring-drive loop behind put() and decode_pipelined:
        fill the in-flight ring up to ``pipeline_depth`` (plan+dispatch),
        then commit the oldest step; when nothing is schedulable and
        nothing is in flight, relieve KV pressure, shed the starved
        request, or declare starvation. ``commit_one(ring)`` pops and
        applies the oldest step; ``on_dispatch(plan, fl)`` hooks
        post-dispatch bookkeeping.

        Drain discipline (docs/resilience.md "Serving"): a preemption
        signal (attached :class:`PreemptionHandler`) or an explicit
        :meth:`request_drain` is polled at every fill/commit boundary —
        once draining, no new step is planned, every already-dispatched
        step is COMMITTED (its rollbacks and deferred aborts applied),
        and the loop exits with host state token-consistent, ready for
        :meth:`drain` to snapshot. The watchdog (attach_watchdog) brackets
        each iteration, and the plan/dispatch/commit brackets
        (telemetry/trace.py) tell it their phase, so a stalled dispatch
        or commit is *named*."""
        depth = max(1, self.pipeline_depth)
        ring: deque = deque()
        wd = self._watchdog
        self._live_ring = ring
        if self._obs is not None:
            # step-time attribution window: everything between here and
            # the loop exit is accounted — bracketed phases by their own
            # histograms, the residual as host gap
            self._obs.on_loop_enter()
        try:
            while ring or (work_left() and not self._draining()):
                if wd is not None:
                    wd.step_start(self._step_counter)
                try:
                    while len(ring) < depth and not self._draining() \
                            and work_left():
                        self._expire_deadlines()
                        self._try_resume()
                        plan = make_plan()
                        if plan is None:
                            break
                        fl = self._dispatch_with_retry(plan)
                        ring.append(fl)
                        if on_dispatch is not None:
                            on_dispatch(plan, fl)
                    if ring:
                        commit_one(ring)
                        continue
                    if self._draining():
                        break
                    if not work_left():
                        # the fill loop consumed the last pending work
                        # without dispatching (a deadline expiry or
                        # abort cleared it) — that is completion, not
                        # starvation; the outer condition exits
                        continue
                    if not self._relieve_kv_pressure() \
                            and not self._shed_starved():
                        # nothing schedulable, evictable, resumable or
                        # sheddable -> a single sequence genuinely does
                        # not fit the pool and shedding is off
                        raise RuntimeError(
                            "scheduler starved: KV pool too small even "
                            "after pausing all idle sequences "
                            f"(free blocks={self.kv_cache.free_blocks})")
                except BaseException:
                    if wd is not None:
                        wd.step_abort()
                    raise
                finally:
                    if wd is not None:
                        wd.step_end(self._step_counter)
        finally:
            self._live_ring = None
            if self._obs is not None:
                self._obs.on_loop_exit()

    # ------------------------------------------------------------------ #
    # serve-side resilience: drain / replay / abort / shed / deadlines
    # (docs/resilience.md "Serving"; drain.py has the manifest format)
    # ------------------------------------------------------------------ #

    def attach_preemption(self, handler) -> None:
        """Wire a :class:`~...resilience.preemption.PreemptionHandler`
        into the serve loop: once its flag is set (SIGTERM or a manual
        request), the pipeline stops planning, commits everything in
        flight and exits — the caller then runs :meth:`drain`."""
        self.preemption = handler

    def attach_watchdog(self, wd) -> None:
        """Cover the serve loop with a
        :class:`~...resilience.watchdog.StepWatchdog`: each pipeline
        iteration is bracketed and the plan/dispatch/commit phases are
        named, so a stalled step's diagnosis says WHERE it hung."""
        self._watchdog = wd
        self._spans.watchdog = wd

    def request_drain(self) -> None:
        """Put the engine into draining mode (idempotent): no new
        admissions, no new planned steps; in-flight steps still commit."""
        self._drain_requested = True

    @property
    def draining(self) -> bool:
        return self._draining()

    def _draining(self) -> bool:
        return self._drain_requested or (
            self.preemption is not None and self.preemption.preempted)

    def _reject(self, uid: int, reason: str, **fields) -> None:
        """Record a structured rejection (load shed / deadline / drain
        refusal) — the crash-free failure path the serving layer turns
        into a retriable response. Pure host bookkeeping."""
        # retry_after_s is first-class in the record shape (usually
        # None; the admission controller's door rejections set it) so
        # clients can honor a backoff hint without a reason-specific
        # schema and report readers stay uniform
        rec = {"uid": uid, "reason": reason, "time": time.time(),
               "retry_after_s": fields.pop("retry_after_s", None),
               **fields}
        self.rejections[uid] = rec
        if self._obs is not None:
            seq = self.state.get(uid)
            self._obs.on_reject(reason, uid,
                                seq.trace_id if seq is not None else None)
        logger.warning(f"serve rejection uid={uid}: {reason} "
                       + (str(fields) if fields else ""))

    def _expire_deadlines(self) -> None:
        """Abort requests whose arrival-anchored deadline has passed —
        serving them late wastes pool and steps the on-time requests
        need. Runs at every pipeline fill boundary; pure host checks.
        Covers the engine-level ``request_deadline_s`` AND per-request
        ``put(..., deadlines=...)`` stamps (``_has_deadlines`` keeps
        the deadline-free common case a single attribute check)."""
        if not self._has_deadlines:
            return
        now = time.monotonic()
        for seq in list(self.state.sequences.values()):
            if not seq.in_flight:
                # owes nothing right now: a request that completed its
                # decode budget on time (awaiting caller flush) or one
                # idle between decode rounds must NOT be reaped — expiry
                # applies only to work actually being scheduled late
                continue
            if seq.deadline_at is not None and now > seq.deadline_at \
                    and seq.status is not SequenceStatus.FINISHED:
                self._reject(seq.uid, "deadline_exceeded",
                             deadline_s=seq.deadline_s
                             if seq.deadline_s is not None
                             else self.request_deadline_s,
                             deadline_at=seq.deadline_at,
                             seen_tokens=seq.seen_tokens,
                             generated=len(seq.gen_log))
                self.abort(seq.uid)

    def _shed_starved(self) -> bool:
        """Graceful load shedding: the scheduler starved with the pool
        exhausted even after prefix-cache eviction and pausing — abort
        the cheapest-to-redo victim (not-yet-started requests first,
        then the largest demand, i.e. the request that can never fit)
        with a structured rejection instead of crashing the loop."""
        if not self.serve_shed:
            return False
        cands = [s for s in self.state.sequences.values()
                 if s.in_flight and s.status is not SequenceStatus.FINISHED]
        if not cands:
            return False
        victim = min(cands, key=lambda s: (s.seen_tokens != 0,
                                           -(s.seen_tokens + s.in_flight)))
        self._reject(
            victim.uid, "kv_pool_exhausted",
            needed_blocks=victim.blocks_needed(victim.in_flight,
                                               self.config.block_size),
            free_blocks=self.kv_cache.free_blocks,
            seen_tokens=victim.seen_tokens)
        self.abort(victim.uid)
        return True

    def abort(self, uid: int) -> bool:
        """Cancel a sequence mid-pipeline, exactly releasing its state:
        pending work is dropped, its slots in every in-flight step are
        killed (their readback discarded), and the flush — KV blocks to
        the allocator, prefix-cache refcounts decref'd — is DEFERRED to
        the commit of the last in-flight step that still writes its
        blocks (the same discipline as the EOS rollback's
        ``trim_blocks``). Safe from inside or outside the pipeline;
        returns False for an unknown uid. ``flush`` only reconciles at
        commit — this is the any-time cancellation path."""
        seq = self.state.get(uid)
        if seq is None:
            return False
        if seq.status is SequenceStatus.FINISHED:
            # already cancelled, deferred flush pending: idempotent
            # (a re-scan would also re-queue the flush, and the abort
            # outcome must be counted once per request)
            return True
        if self._obs is not None:
            self._obs.on_abort(uid in self.rejections)
        seq.pending_tokens.clear()
        seq.spec_pending = 0
        seq.status = SequenceStatus.FINISHED   # scheduler skips it
        last_fl = None
        if self._live_ring:
            for fl in self._live_ring:
                touched = False
                for j, item in enumerate(fl.sched):
                    if item.seq.uid == uid:
                        # ALREADY-dead slots (a late EOS killed them)
                        # count too: the step's KV writes — and any
                        # rollback it carries for this sequence — still
                        # reference the blocks, so the flush must wait
                        # for it regardless
                        fl.dead.add(j)
                        touched = True
                if touched or any(s is seq for s, _ in fl.rollbacks):
                    last_fl = fl
        if last_fl is not None:
            last_fl.aborts.append(seq)
        else:
            self._flush_uid(uid)
        return True

    def _flush_uid(self, uid: int) -> None:
        """The one engine-level release path (flush / deferred abort /
        drain): journal the finish so a replayed journal drops the
        sequence, then free through the state manager (shared blocks
        decref'd, private blocks to the allocator)."""
        if self._obs is not None:
            self._obs.on_flush(self.state.get(uid),
                               uid in self.rejections, self._draining())
        if self.journal is not None \
                and self.state.get(uid) is not None:
            self.journal.finish(uid)
        if self._proposer is not None:
            # the draft-model proposer mirrors live sequences on its
            # own engine — release its copy with ours
            self._proposer.drop(uid)
        self.state.flush(uid)

    def drain(self, path: Optional[str] = None,
              ledger: Any = None) -> Dict[str, Any]:
        """Cooperative preemption drain: stop admitting, snapshot every
        live sequence into a replay manifest (uid, prompt, tokens
        generated so far, scheduler state), release ALL engine state —
        prefix-cache refcounts decref'd exactly, every block back to the
        allocator or the cache's evictable set — and atomically publish
        the manifest (``path``, or DSTPU_SERVE_DRAIN_MANIFEST). Appends a
        ``serve_drain`` entry to ``ledger`` (or a RestartLedger at
        DSTPU_RESTART_LEDGER). Call with no steps in flight — i.e. after
        the interrupted engine call returned; the pipeline itself unwinds
        on the drain flag. Returns the manifest dict (``pool`` carries
        the full-recovery verdict the drills assert on)."""
        self._refuse_stateful("drain", latent_too=True)
        if self._live_ring is not None:
            raise ServeDrainError(
                "drain() called with steps in flight — request_drain() "
                "and let the interrupted engine call return first")
        self.request_drain()
        t_drain0 = time.perf_counter()
        # land any in-flight demotion gathers before snapshotting: the
        # host tier (and whatever it still owes the next match) must
        # survive the drain on host memory, not as device futures
        self.kv_cache.finalize_demotions()
        manifest = build_manifest(self)
        if self.journal is not None:
            # retire the journal BEFORE flushing: the flush loop must not
            # append 'finish' records for sequences this manifest still
            # owes to a survivor — if the drain itself is killed before
            # write_manifest lands, the intact journal is the recovery
            # channel (finished-by-drain entries would erase it)
            self.journal.close()
            self.journal = None
        for uid in list(self.state.sequences):
            self._flush_uid(uid)
        free = self.kv_cache.free_blocks
        manifest["pool"] = {
            "num_blocks": self.config.num_blocks,
            "free_blocks_after_drain": free,
            # evictable refcount-0 cached blocks count as free capacity
            "fully_recovered": free == self.config.num_blocks,
        }
        manifest["rejections"] = list(self.rejections.values())
        if self._obs is not None:
            # the drain span + Chrome-trace auto-dump pair with the
            # manifest (docs/observability.md); the registry SLO report
            # rides the manifest — attached BEFORE the publish so the
            # on-disk copy carries it too
            self._obs.flight.record("drain", t_drain0,
                                    time.perf_counter(),
                                    step=self._step_counter)
            self._obs.on_drain(manifest)
        path = path or self._manifest_path
        if path:
            write_manifest(manifest, path)
            manifest["path"] = path
        if ledger is None and os.environ.get("DSTPU_RESTART_LEDGER"):
            from ...resilience.ledger import RestartLedger
            ledger = RestartLedger(os.environ["DSTPU_RESTART_LEDGER"])
        if ledger is not None:
            ledger.record("serve_drain",
                          sequences=len(manifest["sequences"]),
                          manifest=path,
                          fully_recovered=manifest["pool"]["fully_recovered"])
        self._drained = True
        log_dist(f"serve drain: {len(manifest['sequences'])} sequences "
                 f"manifested, pool fully_recovered="
                 f"{manifest['pool']['fully_recovered']}")
        return manifest

    def replay(self, manifest: Dict[str, Any]) -> Dict[int, Any]:
        """Re-admit a drained replica's sequences on THIS engine (a
        restarted process or a live survivor): each sequence re-enters
        the queue as ``prompt + generated`` and is prefilled — on a
        survivor sharing the workload's prefix, mostly as prefix-cache
        block hits — and the returned ``{uid: next greedy token}`` is
        token-identical to what the uninterrupted run would have emitted
        next. The sequences stay live for continued decoding, with
        prompt/generated split restored so a LATER drain of this engine
        emits cumulative manifests."""
        self._refuse_stateful("replay", latent_too=True)
        if self._draining():
            raise EngineDrainingError(
                "replay() on a draining engine — replay belongs on the "
                "restarted or survivor replica")
        recs = manifest.get("sequences", [])
        uids = [int(r["uid"]) for r in recs]
        chains = [list(r["prompt"]) + list(r["generated"]) for r in recs]
        # sampled sequences replay with their SamplingParams restored
        # BEFORE the prefill runs: the replay prefill's last-chunk token
        # is selected by the same (seed, position)-folded key the
        # uninterrupted run would have used — sampled replay is
        # token-identical, exactly like greedy replay
        sp_map = {int(r["uid"]): SamplingParams.from_dict(r["sampling"])
                  for r in recs if r.get("sampling")}
        # the trace context survives the membership change: the replayed
        # request's survivor spans join the SAME logical track the dead
        # replica's spans started (set via put so even the replay
        # admission span is trace-tagged)
        tr_map = {int(r["uid"]): r["trace"]
                  for r in recs if r.get("trace")}
        if self._obs is not None:
            with self._obs.flight.span("replay", step=self._step_counter,
                                       sequences=len(recs)):
                out = self.put(uids, chains, _greedy=True,
                               sampling=sp_map or None,
                               traces=tr_map or None)
        else:
            out = self.put(uids, chains, _greedy=True,
                           sampling=sp_map or None,
                           traces=tr_map or None)
        for r in recs:
            seq = self.state.get(int(r["uid"]))
            if seq is not None:
                # put() saw the whole chain as prompt; restore the true
                # request identity (original prompt, generated history +
                # whatever the replay prefill just emitted)
                seq.prompt_log = list(r["prompt"])
                seq.gen_log = list(r["generated"]) + seq.gen_log
        return out

    def _dispatch_with_retry(self, plan: _PlannedStep) -> _InFlightStep:
        """Bounded retry-with-backoff around one step dispatch: a
        TRANSIENT (I/O-class) failure re-dispatches the SAME planned step
        — a failed dispatch mutated no host or pool state, so this is
        always safe; persistent failure surfaces as ServeStepError (the
        serve loop's cue to drain). Registered DSL001 hot path: the
        backoff sleep only runs on the already-failed path."""
        delay = self.serve_retry_backoff_s
        attempt = 0
        while True:
            try:
                return self._dispatch_step(plan)
            except (OSError, ConnectionError) as e:
                attempt += 1
                self.pipeline_stats["retries"] += 1
                if self._obs is not None:
                    self._obs.on_retry()
                if attempt > self.serve_step_retries:
                    raise ServeStepError(
                        f"serve step dispatch failed {attempt} times; "
                        f"last error: {e}") from e
                logger.warning(
                    f"serve step dispatch transient failure ({e}); "
                    f"retry {attempt}/{self.serve_step_retries} in "
                    f"{delay:.3f}s")
                if delay > 0:
                    time.sleep(delay)
                delay *= 2

    def _pre_commit(self, fl: _InFlightStep) -> None:
        """Shared entry of both commit paths, ahead of the blocking
        readback (whose ``serve/commit_block`` bracket names the
        watchdog phase): carries the ``mid_commit`` fault site.
        Registered DSL001 hot path — pure host work."""
        get_fault_injector().maybe_fire("mid_commit")

    def _finish_commit(self, fl: _InFlightStep) -> None:
        """Shared exit of both commit paths: apply the EOS rollbacks that
        had to wait for this step's execution, then the deferred abort
        flushes (same reason — their blocks took this step's writes).
        A rollback whose sequence was flushed in the meantime (an abort
        raced the queued retraction, or the step itself was popped from
        the ring before the abort scan could see it) is a no-op — its
        blocks went back wholesale with the flush, and trimming the
        stale descriptor again would double-free them."""
        for seq, retract in fl.rollbacks:
            if self.state.get(seq.uid) is not seq:
                continue                       # flushed: blocks already back
            seq.seen_tokens -= retract
            self.state.trim_blocks(seq)
        for seq in fl.aborts:
            self._flush_uid(seq.uid)
        # hierarchical KV: pending demotion gathers are provably complete
        # (this commit's readback just blocked on a LATER dispatch) —
        # materialize them to host numpy here, off the plan/dispatch path
        self.kv_cache.finalize_demotions()

    def _resume_headroom(self, seq) -> int:
        """Blocks needed to restore ``seq`` AND schedule its next chunk —
        resuming with less would just thrash (restore, fail to schedule,
        get evicted again)."""
        bs = self.config.block_size
        n = min(seq.in_flight, self.config.effective_chunk)
        total = -(-(seq.seen_tokens + n) // bs)
        return max(total, seq.paused_blocks)

    def _try_resume(self) -> None:
        """Restore paused sequences that have pending work, oldest first,
        while free blocks cover their saved KV plus their next chunk."""
        paused = sorted(
            (s for s in self.state.sequences.values()
             if s.status is SequenceStatus.PAUSED and s.in_flight > 0),
            key=lambda s: s.last_step)
        for seq in paused:
            if self._resume_headroom(seq) > self.kv_cache.free_blocks:
                break
            self.resume(seq.uid)

    def _relieve_kv_pressure(self) -> bool:
        """Pause the least-recently-scheduled block-holder to free blocks.
        Idle holders (no pending tokens) are evicted first; if every holder
        is mid-work, the least-recently-scheduled pending holder is paused
        (its KV up to ``seen_tokens`` is complete, so this is always safe —
        its queued tokens simply wait for a later resume). Returns False
        when no sequence holds any blocks: the caller just failed to
        schedule into an empty-as-possible pool, a true deadlock."""
        holders = [s for s in self.state.sequences.values()
                   if s.status is not SequenceStatus.PAUSED and s.kv_blocks]
        idle = sorted((s for s in holders if not s.in_flight),
                      key=lambda s: s.last_step)
        if idle:
            self.pause(idle[0].uid)
            return True
        pending = sorted((s for s in holders if s.in_flight),
                         key=lambda s: s.last_step)
        if pending:
            self.pause(pending[0].uid)
            return True
        return False

    def query(self, uid: int) -> Tuple[int, int]:
        """(tokens seen, max additional tokens before block exhaustion).
        A paused sequence reports 0 headroom — resume() it first."""
        seq = self.state.get_or_create(uid)
        if seq.status is SequenceStatus.PAUSED:
            return seq.seen_tokens, 0
        free_local = self.config.max_blocks_per_seq - len(seq.kv_blocks)
        free = min(free_local, self.kv_cache.free_blocks)
        slack = len(seq.kv_blocks) * self.config.block_size - seq.seen_tokens
        return seq.seen_tokens, slack + free * self.config.block_size

    def can_schedule(self, uid: int, n_tokens: int) -> bool:
        return self.state.can_schedule(uid, n_tokens)

    def flush(self, uid: int) -> None:
        self._flush_uid(uid)

    def _conv_steps(self, S: int, n: int = 1) -> Dict[str, int]:
        """The short convolution's two counters for ``n`` decode steps of
        ``S`` rows: the recurrent layers that have one, a step, and of
        those the ones that take the in-place kernel (all or none)."""
        spec = self.runner.state_spec
        if self.kv_cache.conv is None:          # no short convolution
            return {"conv_steps": 0, "conv_steps_in_place": 0}
        steps = n * spec["layers"]
        return {"conv_steps": steps,
                "conv_steps_in_place": steps * short_conv.decode_uses_kernel(
                    S, spec["conv_width"], self.kv_cache.conv.dtype)}

    def _state_bytes_stored(self, slot_steps: int) -> Dict[str, int]:
        """``slot_steps`` live slots of the state pool by what the device
        stores for one, and what of that is padding."""
        stored = self.kv_cache.state_bytes_per_slot(resident=True)
        return {"state_bytes_resident": slot_steps * stored,
                "state_bytes_padding": slot_steps * (
                    stored - self.kv_cache.state_bytes_per_slot())}

    def _refuse_stateful(self, feature: str,
                         latent_too: bool = False) -> None:
        """What would need a snapshot of the recurrent state refuses, by
        the feature's name and the layer kind (config.stateful_refusal;
        carrying state through these is later work); with ``latent_too``
        also what has not been carried over a latent-attention model's
        one-plane cache (config.latent_refusal); a model with both kinds
        of layer gives both reasons."""
        from .config import (latent_refusal, selecting_refusal,
                             stateful_refusal, windowed_refusal)
        why = []
        if self._selecting:
            why.append(selecting_refusal(feature))
        if self._stateful:
            why.append(stateful_refusal(feature, self._stateful))
        if latent_too and self._latent:
            why.append(latent_refusal(feature))
        if self._windowed:
            why.append(windowed_refusal(feature))
        if why:
            raise NotImplementedError("; ".join(why))

    def _decode_row_counts(self, runs, S: int, in_ring: bool = False
                           ) -> Dict[str, int]:
        """The decode kernel's row counters for ``runs``, (steps a
        sequence ran, its settled rows) pairs of a program of ``S``
        slots: ``decode_kv_rows_*`` and
        their bytes over K/V planes, ``latent_rows_*`` and theirs over a latent
        plane (a layer's worth each; one pair a model, never both), and
        ``window_rows_*`` beside the first pair for a model with
        sliding-window layers. ``in_ring``: the runs are a fused loop's,
        whose own tokens ride its ring (a step's query then stands ``t``
        rows past the settled ones; a single step's own row is settled
        before the kernel runs)."""
        live = sum(ran * rows for ran, rows in runs)
        fetched = sum(ran * self._kv_rows_fetched(rows)
                      for ran, rows in runs)
        if self._latent:
            return {"latent_rows_live": live, "latent_rows_fetched": fetched,
                    "latent_bytes_live": live * self._latent_token_bytes}
        out = {"decode_kv_rows_live": live,
               "decode_kv_rows_fetched": fetched,
               "kv_bytes_live": live * self.kv_cache.kv_bytes_per_token()}
        if self._selecting and runs:
            out.update(self._sparse_decode_counts(runs, in_ring))
        if self._windowed and runs:
            # step t of a run reads the settled rows its window still
            # reaches, rows - max(rows + t - window + 1, 0), in whole
            # tiles from the tile that holds the first of them (the
            # kernel's own start tile, paged_attention.decode_rows_fetched)
            spec, ts = self.runner.window_spec, self._window_tile
            ran = np.asarray([r for r, _ in runs], np.int64)[:, None]
            rows = np.asarray([n for _, n in runs], np.int64)[:, None]
            t = np.arange(int(ran.max()), dtype=np.int64)[None, :]
            alive = t < ran
            first = np.maximum(rows + t - (0 if in_ring else 1)
                               - spec["window"] + 1, 0)
            wlive = int((np.maximum(rows - first, 0) * alive).sum())
            out.update(
                window_rows_live=wlive,
                window_rows_fetched=int((np.maximum(
                    -(-rows // ts) - first // ts, 0) * ts * alive).sum()),
                # the call's plan, live chunk or not: the kernel's own
                window_rows_scored=int(ran.sum()) * self._window_scored(S),
                window_bytes_live=wlive
                * self.kv_cache.window_bytes_per_row())
        return out

    def _sparse_decode_counts(self, runs, in_ring: bool) -> Dict[str, int]:
        """The block-selected layers' decode counters for ``runs`` (see
        ``_decode_row_counts``): step t of a run stands at position
        ``rows + t`` (a fused loop's) or ``rows - 1`` (a single step's own
        row is settled), and past ``dense_len`` reads ``min(blocks at or
        before it, topk)`` blocks a kv head. The decode counters
        ``decode_kv_rows_*`` keep counting what a dense call would
        read."""
        sp = self.runner.model_cfg.sparse
        kvh = self.runner.kv_heads
        ran = np.asarray([r for r, _ in runs], np.int64)[:, None]
        rows = np.asarray([n for _, n in runs], np.int64)[:, None]
        t = np.arange(int(ran.max()), dtype=np.int64)[None, :]
        pos = rows + t - (0 if in_ring else 1)
        alive = t < ran
        sparse = alive & (pos + 1 >= sp.dense_len)
        blocks = np.minimum(pos // sp.block_size + 1, sp.topk)
        sel = int((blocks * sp.block_size * sparse).sum()) * kvh
        return {
            **self._select_counts(int(sparse.sum()), 1),
            "sparse_rows_selected": sel,
            "sparse_rows_live": int(((pos + 1) * sparse).sum()) * kvh,
            "sparse_dense_tokens": int((alive & ~sparse).sum())}

    def _select_counts(self, queries: int, C: int) -> Dict[str, int]:
        """The selection's two counters for ``queries`` real queries past
        ``dense_len`` of steps of ``C`` queries a row."""
        r, sp = self.runner, self.runner.model_cfg.sparse
        kernel = _attention_impl(self.config) == "paged_flash" \
            and sparse_attention.select_uses_kernel(
                r.head_dim, r.model_cfg.num_heads // r.kv_heads, r.kv_heads,
                C, self.config.block_size, sp.kernel_stride, sp.block_size)
        return {"sparse_select_queries": queries,
                "sparse_select_kernel_queries": queries * kernel}

    def _read_sel_counts(self) -> None:
        """The prefill selection's two counts, read back from the cache
        value (a sync: called where a prefill's result has been read)."""
        if not self._selecting:
            return
        now = np.asarray(jax.device_get(self._kv_data.sel_counts), np.int64)
        # int32 on the device: a wrap shows as a negative step
        step = (now - self._sel_counts_seen) % (1 << 32)
        self._sel_counts_seen = now
        self.pipeline_stats["sparse_prefill_blocks_selected"] += int(step[0])
        self.pipeline_stats["sparse_prefill_blocks_visited"] += int(step[1])

    def _kv_write_counts(self, stores, n: int) -> Dict[str, int]:
        """The pool writer's counters for ``stores``, (first position, real
        rows) pairs of a program that holds ``n`` positions a sequence."""
        return {"kv_write_rows": sum(count for _, count in stores),
                "kv_write_runs": sum(
                    runs_issued(start, count, n, self.config.block_size,
                                self.kv_cache.data.shape[3])
                    for start, count in stores)}

    def pause(self, uid: int) -> None:
        """Evict a sequence's KV blocks to host memory and free them — the
        pool can then be oversubscribed by other sequences. Reference:
        ``BlockedKVCache.offload`` (inference/v2/ragged/kv_cache.py:166).
        Queued (pending) tokens are allowed: KV is complete up to
        ``seen_tokens`` after every step, so the pending tokens simply wait
        in the queue until the sequence is resumed."""
        self._refuse_stateful("pause")
        seq = self.state.get(uid)
        if seq is None:
            raise KeyError(f"unknown sequence {uid}")
        if seq.status is SequenceStatus.PAUSED:
            return
        seq.host_kv = self.kv_cache.offload(self._kv_data, seq.kv_blocks)
        # capture the exact block count now: resume() must reserve exactly
        # what was saved, not re-derive it from seen_tokens (the two could
        # diverge under future allocate-ahead policies)
        seq.paused_blocks = len(seq.kv_blocks)
        # cache-shared leading blocks are DECREF'd, not freed (the cache —
        # or another sequence — still owns them); resume() restores the
        # offloaded copy into all-private blocks, so the resumed sequence
        # simply stops sharing
        self.state.release_blocks(seq, seq.kv_blocks)
        seq.kv_blocks = []
        seq.status = SequenceStatus.PAUSED

    def resume(self, uid: int) -> None:
        """Re-allocate blocks for a paused sequence and restore its KV from
        host memory, exactly as it was (reference ``restore``,
        kv_cache.py:176). Block ids may differ — tables are per-sequence."""
        self._refuse_stateful("resume")
        seq = self.state.get(uid)
        if seq is None:
            raise KeyError(f"unknown sequence {uid}")
        if seq.status is not SequenceStatus.PAUSED:
            return
        blocks = self.kv_cache.reserve(
            seq.paused_blocks,
            homes=[i % self.kv_cache.seq for i in range(seq.paused_blocks)]
            if self.kv_cache.seq > 1 else None)
        self._kv_data = self.kv_cache.restore(self._kv_data, seq.host_kv,
                                              blocks)
        seq.kv_blocks = list(blocks)
        seq.host_kv = None
        seq.paused_blocks = 0
        seq.status = SequenceStatus.WAITING

    # ------------------ disaggregated serving handoff ----------------- #
    # docs/serving.md "Disaggregated serving": a prefill specialist hands
    # a freshly prefilled sequence — KV block chain + replay identity —
    # to a decode specialist. handoff_out is the source half (one batched
    # non-blocking gather per sequence, drain-shaped manifest record,
    # exact state release); handoff_in is the destination half (reserve,
    # ONE batched restore scatter per sequence, descriptor rebuilt
    # without re-prefill). The manifest records are a superset of the
    # drain manifest's per-sequence shape, so a failed handoff falls
    # back to token-identical replay from the same records.

    def handoff_out(self, batch_uids: Sequence[int]) -> Dict[str, Any]:
        """Snapshot + release sequences for migration to another replica.

        For each uid with fully-consumed pending work, dispatches a
        non-blocking exact-length gather of its KV block chain (int8
        payload + scale planes ride as-is for quantized pools — content-
        exact, half the bytes) and builds a handoff record carrying the
        full replay identity: prompt/generated split, sampling params,
        trace context, SLO stamps and deadline. Source state is then
        released through the one release path (journal finish, proposer
        drop, shared-block decref via ``state.flush``) WITHOUT counting
        a terminal outcome — the request is still in flight, on the
        destination. The returned manifest's ``kv`` entries are lazy
        device slices; the caller materializes them (one batched
        device_get) where the wait can hide under other replicas'
        compute. Registered DSL001 hot path — dispatch only."""
        self._refuse_stateful("handoff_out", latent_too=True)
        recs: List[Dict[str, Any]] = []
        blocks_moved = 0
        bytes_moved = 0
        for uid in batch_uids:
            seq = self.state.get(uid)
            if seq is None or not seq.kv_blocks or seq.in_flight \
                    or seq.status in (SequenceStatus.PAUSED,
                                      SequenceStatus.FINISHED):
                continue
            get_fault_injector().maybe_fire("during_handoff_gather")
            kv = self.kv_cache.gather_blocks(self._kv_data, seq.kv_blocks)
            rows = kv[0] if isinstance(kv, tuple) else kv
            recs.append({
                "uid": seq.uid,
                "prompt": list(seq.prompt_log),
                "generated": list(seq.gen_log),
                "sampling": seq.sampling.to_dict()
                if seq.sampling is not None else None,
                "trace": seq.trace_id,
                "seen_tokens": seq.seen_tokens,
                "blocks": len(seq.kv_blocks),
                "kv": kv,
                "logprobs": list(seq.logprob_log),
                "deadline_at": seq.deadline_at,
                "deadline_s": seq.deadline_s,
                "stamps": (seq.admitted_at, seq.first_sched_at,
                           seq.first_token_at, seq.last_token_at),
            })
            blocks_moved += len(seq.kv_blocks)
            bytes_moved += rows.size * rows.dtype.itemsize
            if isinstance(kv, tuple):
                bytes_moved += kv[1].size * kv[1].dtype.itemsize
        # TRANSACTIONAL release, after every record is built: the
        # gather loop above mutates nothing (pure reads + dispatch), so
        # a fault mid-gather — the during_handoff_gather drill site, or
        # a SIGTERM landing in the loop — leaves EVERY sequence live on
        # this replica: nothing migrated, nothing lost (the caller
        # retries, decodes colocated, or the drain manifest carries
        # them). Released WITHOUT an outcome: the request migrates, it
        # does not finish here (goodput counts it once, at the
        # destination); journal finish so a journal replay of THIS
        # replica no longer claims it
        for rec in recs:
            uid = rec["uid"]
            if self.journal is not None:
                self.journal.finish(uid)
            if self._proposer is not None:
                self._proposer.drop(uid)
            self.state.flush(uid)
        if recs and self._obs is not None:
            self._obs.on_handoff_out(len(recs), blocks_moved, bytes_moved)
        # seq_size IS the shard map: chain ordinal o lives on chip
        # o % seq_size. The kv payloads themselves are geometry-free
        # (gather_blocks returns block-chain-ordered rows), so a
        # destination with ANY seq_size restores them exactly.
        seq_size = self.config.seq_size     # host int (config field)
        return {"version": 1, "source": "handoff", "time": time.time(),
                "seq_size": max(1, int(seq_size)),
                "sequences": recs}

    def handoff_in(self, manifest: Dict[str, Any],
                   exposed_s: float = 0.0) -> Dict[str, List[int]]:
        """Adopt migrated sequences from :meth:`handoff_out`'s manifest:
        reserve each record's block count, scatter its KV payload with
        ONE batched restore per sequence, and rebuild the descriptor —
        prompt/generated split, ``seen_tokens``, sampling identity,
        trace context and SLO stamps — so the very next decode step
        continues the stream token-identically, with no re-prefill.
        Blocks arrive private (never cache-shared): ``assert_exact_refs``
        holds on both replicas immediately after migration. Records the
        pool cannot cover (OutOfBlocksError on reserve) are returned in
        ``spilled`` — the caller replays those from the same records'
        prompt+generated chains instead. ``exposed_s`` is the caller-
        measured non-overlapped transfer wall, observed into
        ``serve_handoff_exposed_s``. Registered DSL001 hot path —
        dispatch only."""
        self._refuse_stateful("handoff_in", latent_too=True)
        if self._draining():
            raise EngineDrainingError(
                "handoff_in() on a draining engine — migrate to a "
                "serving replica")
        accepted: List[int] = []
        spilled: List[int] = []
        blocks_in = 0
        for rec in manifest.get("sequences", []):
            # manifest fields are host ints (json-shaped record), not
            # device scalars — no sync behind these coercions
            uid = int(rec["uid"])     # dslint: allow(DSL001): host int
            if self.state.get(uid) is not None:
                raise ValueError(
                    f"handoff_in: sequence {uid} already live on this "
                    f"engine")
            nblocks = int(rec["blocks"])  # dslint: allow(DSL001): host int
            try:
                # a migrated chain restarts at ordinal 0 — at seq > 1 its
                # blocks must land on homes 0, 1, ... % seq so the
                # destination's seq-sharded gathers see the same layout
                blocks = self.kv_cache.reserve(
                    nblocks,
                    homes=[i % self.kv_cache.seq for i in range(nblocks)]
                    if self.kv_cache.seq > 1 else None)
            except OutOfBlocksError:
                spilled.append(uid)
                continue
            self._kv_data = self.kv_cache.restore(self._kv_data,
                                                  rec["kv"], blocks)
            seq = self.state.get_or_create(uid)
            seq.kv_blocks = list(blocks)
            seq.prompt_log = list(rec["prompt"])
            seq.gen_log = list(rec["generated"])
            seq.prompt_len = len(seq.prompt_log)
            seq.seen_tokens = int(  # dslint: allow(DSL001): host int
                rec["seen_tokens"])
            seq.prefix_tokens = None     # never registered here: private
            seq.status = SequenceStatus.WAITING
            if rec.get("sampling"):
                seq.sampling = SamplingParams.from_dict(rec["sampling"])
            seq.trace_id = rec.get("trace")
            seq.logprob_log = list(rec.get("logprobs") or [])
            (seq.admitted_at, seq.first_sched_at, seq.first_token_at,
             seq.last_token_at) = rec.get("stamps") or (None,) * 4
            if rec.get("deadline_at") is not None:
                seq.deadline_at = rec["deadline_at"]
                seq.deadline_s = rec.get("deadline_s")
                self._has_deadlines = True
            if self.journal is not None:
                # journal the FULL chain as the admitted prompt (exactly
                # what a drain-replay admission would journal): a
                # journal replay of this replica re-prefills the chain
                # and continues token-identically
                self.journal.admit(
                    uid, seq.prompt_log + seq.gen_log,
                    sampling=rec.get("sampling"), trace=seq.trace_id)
            accepted.append(uid)
            blocks_in += nblocks
        if accepted and self._obs is not None:
            self._obs.on_handoff_in(len(accepted), blocks_in, exposed_s)
        return {"accepted": accepted, "spilled": spilled}

    @property
    def free_blocks(self) -> int:
        return self.kv_cache.free_blocks

    # --------------------- telemetry accessors ------------------------ #
    # (telemetry/serve.py, docs/observability.md; all None/empty when
    # DSTPU_TELEMETRY=0)

    @property
    def metrics(self):
        """This engine's MetricsRegistry (per-engine, so a drill's dead
        replica and survivor never mix stats), or None."""
        return self._obs.registry if self._obs is not None else None

    @property
    def flight(self):
        """This engine's phase FlightRecorder, or None."""
        return self._obs.flight if self._obs is not None else None

    def slo_report(self) -> Dict[str, Any]:
        """TTFT/TPOT/queue-wait percentiles, outcome counts and goodput
        fraction for everything this engine served ({} when telemetry
        is off) — the numbers the serving layer above keys SLO-aware
        routing on."""
        return self._obs.slo_report() if self._obs is not None else {}

    def decode_greedy(self, batch_uids: Sequence[int],
                      first_tokens: Sequence[int],
                      n: int) -> Dict[int, List[int]]:
        """Back-compat wrapper: :meth:`decode_batch` with greedy
        selection."""
        return self.decode_batch(batch_uids, first_tokens, n)

    def logprobs_of(self, uid: int) -> List[float]:
        """Chosen-token log-probabilities recorded so far for ``uid``
        (empty unless its SamplingParams set ``logprobs=True``)."""
        seq = self.state.get(uid)
        return list(seq.logprob_log) if seq is not None else []

    def _stage_loop_sampling(self, seqs, S: int,
                             fallback: Optional[InferenceConfig]):
        """Per-slot sampling arrays for the fused decode loop: {} when
        every slot is greedy (the loop then runs its exact greedy
        program), else the seeds/temps/top_ks/top_ps kwargs — greedy
        slots at temperature 0 (in-program argmax). ``fallback`` maps a
        legacy per-call InferenceConfig onto sequences without their
        own params (per-uid seeds derived from its seed)."""
        from .sampling import (SAMPLE_CANDIDATES, derive_seed, seed_of)
        fb = fallback if fallback is not None and not fallback.greedy \
            else None
        if fb is None and not any(
                s.sampling is not None
                and (not s.sampling.greedy or s.sampling.logprobs)
                for s in seqs):
            return {}
        jnp = jax.numpy
        seeds = np.zeros((S,), np.int32)
        temps = np.zeros((S,), np.float32)
        topks = np.zeros((S,), np.int32)
        topps = np.ones((S,), np.float32)
        for i, seq in enumerate(seqs):
            p = seq.sampling
            if p is None and fb is not None:
                p = SamplingParams(
                    temperature=fb.temperature, top_k=fb.top_k,
                    top_p=fb.top_p,
                    seed=derive_seed(getattr(fb, "seed", 0), seq.uid))
            if p is None or p.greedy:
                continue
            seeds[i] = seed_of(p, seq.uid)
            temps[i] = p.temperature
            topks[i] = min(p.top_k, SAMPLE_CANDIDATES)
            topps[i] = p.top_p
        return {"seeds": jnp.asarray(seeds), "temps": jnp.asarray(temps),
                "top_ks": jnp.asarray(topks),
                "top_ps": jnp.asarray(topps)}

    def decode_batch(self, batch_uids: Sequence[int],
                     first_tokens: Sequence[int], n: int,
                     sampling: Optional[InferenceConfig] = None,
                     eos_token_id: Optional[int] = None,
                     ) -> Dict[int, List[int]]:
        """Decode ``n`` tokens for each uid in ONE fused device program
        (``RaggedRunnerBase.decode_loop``): forward + token selection + KV
        append scan entirely on-device, so the host pays one round-trip per
        ``n`` tokens instead of per token. Selection is greedy for
        sequences without sampling params, else the per-slot on-device
        temperature/top-k/top-p categorical with (seed, position)-folded
        threefry keys — one program serves mixed greedy/sampled batches
        and temperature→0 reproduces greedy exactly. ``sampling`` is a
        legacy per-CALL fallback applied to sequences without their own
        ``seq.sampling`` (per-uid seeds derived from its ``seed``). With
        ``eos_token_id`` a slot freezes once it emits eos (it stops
        consuming KV mid-loop). KV blocks for all n positions are reserved
        up front; raises OutOfBlocksError if the pool cannot cover them
        (callers wanting oversubscription semantics evict-then-retry, as
        :meth:`generate` does).

        first_tokens: each sequence's next INPUT token (its KV is appended
        at position seen_tokens, exactly like feeding it through put)."""
        if not hasattr(self.runner, "decode_loop"):
            raise NotImplementedError(
                f"{type(self.runner).__name__} has no decode_loop")
        with self._spans.span("serve/decode_batch", steps=n,
                              seqs=len(batch_uids)):
            spans, obs = self._spans, self._obs
            with spans.span("serve/fused_stage", steps=n):
                cfg = self.config
                if len(batch_uids) > cfg.max_seqs:
                    raise ValueError(f"{len(batch_uids)} uids > max_seqs "
                                     f"{cfg.max_seqs}")
                if len(batch_uids) != len(first_tokens):
                    raise ValueError(
                        f"{len(batch_uids)} uids but {len(first_tokens)} "
                        f"first_tokens")
                if self._windowed and n > window_step_rows(cfg):
                    # a longer flush would overwrite rows the next step's
                    # window still reaches
                    raise ValueError(
                        f"decode_batch of {n} steps over a window pool sized "
                        f"for steps of at most {window_step_rows(cfg)} rows "
                        f"(chunk_size / decode_loop_steps)")
                seqs = []
                for uid in batch_uids:
                    seq = self.state.get(uid)
                    if seq is None or seq.status is SequenceStatus.PAUSED:
                        raise ValueError(f"sequence {uid} missing or paused")
                    if seq.in_flight:
                        raise ValueError(f"sequence {uid} has pending tokens; "
                                         f"drain with put() first")
                    seqs.append(seq)
                # reserve atomically: check the WHOLE batch's demand first
                # so a mid-batch failure doesn't leave earlier sequences
                # holding allocate-ahead blocks that deepen the pool
                # pressure the caller is about to fall back from
                bsz = self.config.block_size
                need = 0
                for s_ in seqs:
                    nb = s_.blocks_needed(n, bsz)
                    if len(s_.kv_blocks) + nb > cfg.max_blocks_per_seq:
                        raise OutOfBlocksError(
                            f"sequence {s_.uid} would exceed "
                            f"max_blocks_per_seq")
                    need += nb
                if need > self.kv_cache.free_blocks:
                    raise OutOfBlocksError(
                        f"decode_greedy needs {need} blocks, "
                        f"{self.kv_cache.free_blocks} free")
                for seq in seqs:
                    # covers positions seen .. seen + n - 1
                    self.state.ensure_blocks(seq, n)

                S, MAXB = cfg.max_seqs, cfg.max_blocks_per_seq
                tok0 = np.zeros((S,), np.int32)
                start = np.zeros((S,), np.int32)
                active = np.zeros((S,), np.int32)
                tables = np.zeros((S, MAXB), np.int32)
                # idle rows point at the state pool's idle row
                sslots = np.full((S,), S, np.int32) if self._slotted else None
                for i, (seq, t0) in enumerate(zip(seqs, first_tokens)):
                    tok0[i] = t0
                    start[i] = seq.seen_tokens
                    active[i] = 1
                    tables[i, :len(seq.kv_blocks)] = seq.kv_blocks
                    if sslots is not None:
                        sslots[i] = seq.state_slot
                samp = self._stage_loop_sampling(seqs, S, sampling)
                if obs is not None:
                    # attribution window for the fused path: one dispatch
                    # + one blocking readback cover n steps; the bookkeeping
                    # after is the commit apply, anything else in the window
                    # is host gap
                    obs.on_loop_enter()
            live = len(seqs)
            with spans.span("serve/fused_dispatch", steps=n, seqs=live):
                toks, lps, self._kv_data, consumed, moe_rows = \
                    self.runner.decode_loop(
                        self.params, self._kv_data, jax.numpy.asarray(tok0),
                        jax.numpy.asarray(start), jax.numpy.asarray(active),
                        jax.numpy.asarray(tables), n,
                        eos_id=-1 if eos_token_id is None
                        else int(eos_token_id),
                        state_slots=None if sslots is None
                        else jax.numpy.asarray(sslots), **samp)
            # the flush stores all n ring rows of every live slot: counted
            # while the loop runs, not between its readback and the next call
            with spans.span("serve/fused_count", steps=n) as span:
                span.count(**self._kv_write_counts(
                    [(seq.seen_tokens, n) for seq in seqs], n))
            with spans.span("serve/fused_readback", steps=n, seqs=live):
                # one wait for all of it. lps is None for a greedy loop,
                # consumed when EOS is disabled (every slot fed all n),
                # moe_rows for a model without experts
                toks, lps, consumed, moe_rows = jax.device_get(
                    (toks, lps, consumed, moe_rows))
            # the counters' own arithmetic, on the host with the device idle
            with spans.span("serve/fused_count", steps=n):
                # the loop's own tokens ride its ring: every step a slot was
                # alive the kernel read the rows settled at entry
                stats = self.pipeline_stats
                for key, val in self._decode_row_counts([
                        (int(consumed[i]) if consumed is not None else n,
                         seq.seen_tokens) for i, seq in enumerate(seqs)],
                        S, in_ring=True).items():
                    stats[key] += val
                if self._stateful:
                    ran = n * len(seqs) if consumed is None \
                        else int(consumed[:len(seqs)].sum())
                    stats["state_slots_live"] += ran
                    stats["state_bytes_live"] += \
                        ran * self.kv_cache.state_bytes_per_slot()
                    for key, val in self._state_bytes_stored(ran).items():
                        stats[key] += val
                    for key, val in self._conv_steps(S, n).items():
                        stats[key] += val
                if moe_rows is not None:
                    # per call: rows the experts took, and what they would
                    # have taken had every expert been as busy as the busiest
                    # (hottest / routed = the imbalance a straggling
                    # expert-parallel chip would feel; 1.0 = even). A model
                    # that holds a share of the experts counts its own here
                    # and the rows it sent to the others apart
                    mc = self.runner.model_cfg
                    # the grouped kernel's own two counts ride behind the
                    # experts': held experts with a row, and visits to them
                    # (how often an expert's matrices were streamed); both 0
                    # from a program on the ragged_dot path
                    moe_rows, (hit, reads) = moe_rows[:-2], moe_rows[-2:]
                    stats["moe_experts_hit"] += int(hit)
                    stats["moe_expert_reads"] += int(reads)
                    first = getattr(mc, "experts_first", 0)
                    mine = moe_rows[first:first + getattr(mc, "held",
                                                          len(moe_rows))]
                    stats["moe_rows_routed"] += int(mine.sum())
                    stats["moe_rows_hottest"] += int(mine.max()) * len(mine)
                    stats["moe_rows_elsewhere"] += \
                        int(moe_rows.sum()) - int(mine.sum())
            with spans.span("serve/fused_apply", steps=n, seqs=live):
                out = self._apply_fused(batch_uids, seqs, first_tokens, n,
                                        toks, lps, consumed)
            if obs is not None:
                obs.after_commit(self._step_counter)
                obs.on_loop_exit()
            return out

    def _apply_fused(self, batch_uids, seqs, first_tokens, n, toks, lps,
                     consumed) -> Dict[int, List[int]]:
        """The fused loop's commit apply: token bookkeeping, replay
        history and journal for the ``n`` steps one readback covered."""
        obs = self._obs
        self.kv_cache.finalize_demotions()   # the readback proved them
        self._step_counter += n
        out: Dict[int, List[int]] = {}
        journal_toks: Dict[int, List[int]] = {}
        now = time.monotonic()
        for i, (uid, seq) in enumerate(zip(batch_uids, seqs)):
            used = int(consumed[i]) if consumed is not None else n
            # replay history (drain.py): the fed first token joins
            # gen_log unless it is one of our own committed outputs
            # being fed back, then the outputs the loop actually
            # consumed/emitted (post-EOS repeats never committed).
            # Sampled streams are (seed, position)-deterministic, so
            # they journal and replay exactly like greedy ones.
            hist = []
            if len(seq.prompt_log) + len(seq.gen_log) \
                    <= seq.seen_tokens:
                hist.append(int(first_tokens[i]))
            hist.extend(int(t) for t in toks[i][:used])
            seq.gen_log.extend(hist)
            if lps is not None and seq.sampling is not None \
                    and seq.sampling.logprobs:
                seq.logprob_log.extend(
                    float(v) for v in lps[i][:used])
            if self.journal is not None:
                journal_toks[uid] = hist
            # fed first_tokens + generated until eos (or all n)
            seq.seen_tokens += used
            seq.last_step = self._step_counter
            seq.status = SequenceStatus.WAITING
            out[uid] = toks[i].tolist()
            if used > 0:
                first = seq.stamp_first_token(now)
                if obs is not None:
                    # one fused chunk commits `used` tokens at one host
                    # timestamp: TPOT is the inter-chunk interval split
                    # evenly (telemetry/serve.py)
                    obs.on_token_commit(seq, now, first, n=used)
        if self.journal is not None:
            self.journal.tokens(journal_toks)
        return out

    # ------------------------------------------------------------------ #
    # the serving hot path: plan -> dispatch -> commit
    # ------------------------------------------------------------------ #

    def _staging_bufs(self, S: int, C: int):
        """Reused per-(S, C) numpy staging buffers — host-side allocation
        churn sits on the overlap-critical path, so the step arrays
        (tokens/start/ntok/tables + the feed mask/idx) are allocated once
        per shape bucket. A rotation of ``pipeline_depth + 1`` sets keeps
        an in-flight step's source buffers from being rewritten before
        its host->device copy is done."""
        pool = self._staging.get((S, C))
        if pool is None:
            MAXB = self.config.max_blocks_per_seq
            pool = {"sets": [
                # step arrays (tokens/start/ntok/tables), the feedback
                # mask/idx, then the per-slot sampling quintet
                # (seeds/spos/temps/topks/topps — staged only when a
                # scheduled sequence samples, but rotated with the rest
                # so an in-flight sampled step's source buffers are
                # never rewritten under its host->device copy)
                (np.zeros((S, C), np.int32), np.zeros((S,), np.int32),
                 np.zeros((S,), np.int32), np.zeros((S, MAXB), np.int32),
                 np.zeros((S,), np.int32), np.zeros((S,), np.int32),
                 np.zeros((S,), np.int32), np.zeros((S,), np.int32),
                 np.zeros((S,), np.float32), np.zeros((S,), np.int32),
                 np.ones((S,), np.float32))
                for _ in range(max(1, self.pipeline_depth) + 1)],
                "next": 0}
            self._staging[(S, C)] = pool
        bufs = pool["sets"][pool["next"]]
        pool["next"] = (pool["next"] + 1) % len(pool["sets"])
        for b in bufs[:-1]:
            b.fill(0)
        bufs[-1].fill(1)             # top_p neutral for untouched slots
        return bufs

    def _plan_step(self, greedy: bool = False,
                   eligible=None) -> Optional[_PlannedStep]:
        """PLAN: run the scheduler and stage the step's host arrays.
        Pure host work — runs ahead of the device in the pipelined loop."""
        with self._spans.span("serve/plan") as span:
            sched = self.scheduler.schedule(eligible)
            if not sched:
                span.void()
                return None
            self._step_counter += 1
            self.state.step += 1
            # the first-schedule stamp is the engine's own (set with or
            # without an observer, which files its histograms from it)
            now = time.monotonic()
            first = []
            for item in sched:
                seq = item.seq
                seq.last_step = self._step_counter
                seq.last_sched = self.state.step
                if seq.first_sched_at is None:
                    seq.first_sched_at = now
                    first.append(seq)
            if self._obs is not None:
                self._obs.on_sched(sched, first, now)
            cfg = self.config
            # shape bucketing (jit caches by shape, so a handful of compiled
            # programs total; the reference flattens tokens into one ragged
            # array instead, ragged_wrapper.py, which XLA's static shapes
            # forbid). A pure-decode step (every scheduled slot carries one
            # token) runs an [S, 1] program, S the smallest power of two from
            # 16 up to max_seqs that holds the rows. A step that carries a
            # prefill chunk runs at T = effective_chunk, and the scheduler
            # caps its chunk rows at prefill_rows, so a pure-prefill step
            # (every put of a fresh prompt) is always the one
            # [prefill_rows, T] program: as wide as the prompts it holds,
            # not padded to 16 slots. Only a step that mixes more decode
            # rows than that with a chunk falls back to the decode buckets.
            C = 1 if all(len(item.tokens) == 1 for item in sched) \
                else cfg.effective_chunk
            if C > 1 and len(sched) <= cfg.prefill_rows:
                S = cfg.prefill_rows
            else:
                S = next((b for b in _SLOT_BUCKETS
                          if len(sched) <= b <= cfg.max_seqs), cfg.max_seqs)
            (tokens, start, ntok, tables, feed_mask, feed_idx,
             seeds, spos, temps, topks, topps) = self._staging_bufs(S, C)
            use_greedy = greedy and hasattr(self.runner, "step_greedy")
            # sampled batch? then the per-slot sampler program selects the
            # last-chunk token for EVERY slot (greedy slots stage temperature
            # 0 -> in-program argmax, token-identical to step_greedy). The
            # pure-greedy common case keeps its exact original program. A
            # logprobs=True request forces the sampler program too — its
            # output must not depend on what else happens to share the batch
            use_sample = use_greedy \
                and hasattr(self.runner, "step_sample_fb") \
                and any(item.seq.sampling is not None
                        and (not item.seq.sampling.greedy
                             or item.seq.sampling.logprobs)
                        for item in sched)
            has_feed = False
            sslots = np.full((S,), cfg.max_seqs, np.int32) \
                if self._slotted else None
            for i, item in enumerate(sched):
                seq = item.seq
                if sslots is not None:
                    sslots[i] = seq.state_slot
                if seq.spec_pending and item.tokens == [_SPEC_TOKEN]:
                    # speculative placeholder: its value is the in-flight
                    # latest step's device-side output for this sequence —
                    # the step program substitutes it (no host round-trip)
                    seq.spec_pending -= 1
                    feed_mask[i] = 1
                    feed_idx[i] = self._feed_slot[seq.uid]
                    has_feed = True
                else:
                    tokens[i, :len(item.tokens)] = item.tokens
                start[i] = item.start_pos
                ntok[i] = len(item.tokens)
                tables[i, :len(seq.kv_blocks)] = seq.kv_blocks
                if use_sample:
                    # the fold_in operand: the absolute position the
                    # selected token will occupy (= seen after this step) —
                    # invariant to chunking/pipeline depth/restart, which
                    # is the whole determinism contract (sampling.py)
                    stage_slot((seeds, spos, temps, topks, topps), i, seq,
                               item.start_pos + len(item.tokens))
            real = sum(len(item.tokens) for item in sched)
            span.set(step=self._step_counter, seqs=len(sched), S=S, T=C,
                     real=real)
            # the counters' own arithmetic, under its own bracket (a total
            # alone, no annotation: a gap here keeps serve/plan's name):
            # closed forms over the scheduled items, computed in every run
            with self._spans.span("serve/plan_count"):
                span.count(**self._kv_write_counts(
                    [(item.start_pos, len(item.tokens)) for item in sched], C))
                if C > 1:
                    span.count(prefill_tokens_real=real,
                               prefill_tokens_planned=S * C, prefill_steps=1,
                               prefill_rows=sum(len(item.tokens) > 1
                                                for item in sched))
                    if self.kv_cache.state is not None:
                        # (layers that keep no state, a gated short
                        # convolution's, have no recurrence to chunk)
                        spec = self.runner.state_spec
                        uses_kernel = {
                            "kda": kda_prefill_uses_kernel,
                            "gdn": gdn_prefill_uses_kernel,
                            "mamba1": mamba1_prefill_uses_kernel}.get(
                                spec["kind"])
                        span.count(
                            linear_attn_prefill_tokens=real,
                            linear_attn_prefill_kernel_tokens=real * bool(
                                uses_kernel and uses_kernel(
                                    C, spec["heads"], spec["d_k"],
                                    spec["d_v"])))
                    if self._latent:
                        span.count(mla_prefill_tokens=real)
                    if self._selecting:
                        dl = self.runner.model_cfg.sparse.dense_len
                        below = sum(
                            max(0, min(item.start_pos + len(item.tokens),
                                       dl - 1) - item.start_pos)
                            for item in sched)
                        span.count(sparse_dense_tokens=below,
                                   **self._select_counts(real - below, C))
                    if self._moe_stacks is not None:
                        # the choice llama_runner._moe_mlp makes, of the same
                        # operand types and widths
                        span.count(
                            moe_prefill_tokens=real,
                            moe_prefill_kernel_tokens=real
                            * (grouped_ffn.kernel_impl(
                                self._moe_stacks,
                                self.runner.compute_dtype) is not None))
                else:
                    # this step's token is in the pool before the kernel runs
                    lens = [item.start_pos + 1 for item in sched]
                    span.count(decode_slots_live=real, decode_slots_planned=S,
                               **self._decode_row_counts(
                                   [(1, n) for n in lens], S))
                    if self._stateful:
                        span.count(state_slots_live=real,
                                   state_bytes_live=real
                                   * self.kv_cache.state_bytes_per_slot(),
                                   **self._state_bytes_stored(real),
                                   **self._conv_steps(S))
            if C > 1:
                # serve fault site: a replica dying with a freshly planned
                # multi-token prefill chunk (tokens consumed host-side, step
                # never dispatched)
                get_fault_injector().maybe_fire("during_prefill_chunk")
            return _PlannedStep(sched, tokens, start, ntok, tables,
                                feed_mask if has_feed else None, feed_idx,
                                use_greedy,
                                sample=(seeds, spos, temps, topks, topps)
                                if use_sample else None, sslots=sslots)

    def _dispatch_step(self, plan: _PlannedStep) -> _InFlightStep:
        """DISPATCH: enqueue the compiled step without blocking — the
        result stays an in-flight device future (JAX async dispatch).
        A greedy step's [S] token output becomes the device feedback
        source for the next plan's speculative slots."""
        # serve fault site: planned but not yet enqueued — with mode
        # 'ioerror' this is the transient _dispatch_with_retry absorbs
        get_fault_injector().maybe_fire("pre_dispatch")
        fed = plan.feed_mask is not None
        program = "step_sample_fb" if plan.sample is not None \
            else "step_greedy_fb" if fed \
            else "step_greedy" if plan.use_greedy else "step"
        with self._spans.span("serve/dispatch", step=self._step_counter,
                              fed=int(fed), program=program) as span:
            jnp = jax.numpy
            batch = RaggedBatch(
                tokens=jnp.asarray(plan.tokens),
                start_pos=jnp.asarray(plan.start),
                n_tokens=jnp.asarray(plan.ntok),
                block_tables=jnp.asarray(plan.tables),
                state_slots=None if plan.sslots is None
                else jnp.asarray(plan.sslots))
            logprobs = None
            if plan.sample is not None:
                # per-slot on-device sampler (greedy slots ride along at
                # temperature 0). One program covers fed and unfed steps:
                # an unfed step passes an all-zero mask and a cached [1]
                # dummy feed source (clipped gather, never read).
                seeds, spos, temps, topks, topps = plan.sample
                if plan.feed_mask is not None:
                    prev, mask = self._feed_src, plan.feed_mask
                else:
                    if not hasattr(self, "_dummy_feed"):
                        self._dummy_feed = (jnp.zeros((1,), jnp.int32),
                                            np.zeros((1,), np.int32))
                    prev, _ = self._dummy_feed
                    mask = np.zeros_like(plan.feed_idx)
                (result, logprobs), self._kv_data = self.runner.step_sample_fb(
                    self.params, self._kv_data, batch, prev,
                    jnp.asarray(mask), jnp.asarray(plan.feed_idx),
                    jnp.asarray(seeds), jnp.asarray(spos),
                    jnp.asarray(temps), jnp.asarray(topks),
                    jnp.asarray(topps))
            elif plan.feed_mask is not None:
                result, self._kv_data = self.runner.step_greedy_fb(
                    self.params, self._kv_data, batch, self._feed_src,
                    jnp.asarray(plan.feed_mask), jnp.asarray(plan.feed_idx))
            elif plan.use_greedy:
                result, self._kv_data = self.runner.step_greedy(
                    self.params, self._kv_data, batch)
            else:
                result, self._kv_data = self.runner.step(self.params,
                                                         self._kv_data, batch)
            if plan.use_greedy:
                self._feed_src = result
                self._feed_slot = {item.seq.uid: i
                                   for i, item in enumerate(plan.sched)}
            span.count(steps=1, fed_steps=int(fed))
            return _InFlightStep(plan.sched, result, plan.use_greedy,
                                 logprobs=logprobs)

    def _commit_step(self, fl: _InFlightStep) -> Tuple[int, Dict[int, Any]]:
        """COMMIT: apply a step's host readback — in the pipelined loop
        this runs one (or more) steps behind dispatch, while the next
        step executes on the device. Used by the put() path: its steps
        carry no speculation, so EOS rollbacks cannot occur here, but
        abort() may have killed slots (``fl.dead``) and deferred flushes
        (``fl.aborts``) to this commit. Greedy last-chunk tokens are the
        committed stream: they extend each sequence's replay ``gen_log``
        and land in the write-ahead journal."""
        self._pre_commit(fl)
        step = self._step_counter
        with self._spans.span("serve/commit_block", step=step):
            result = np.asarray(fl.result)
            lps = np.asarray(fl.logprobs) if fl.logprobs is not None \
                else None
        obs = self._obs
        now = time.monotonic()
        out: Dict[int, Any] = {}
        journal_toks: Dict[int, List[int]] = {}
        with self._spans.span("serve/commit_apply", step=step):
            for i, item in enumerate(fl.sched):
                if i in fl.dead:
                    continue
                if item.is_last_chunk:
                    if fl.use_greedy:
                        tok = int(result[i])
                        out[item.seq.uid] = tok
                        item.seq.gen_log.append(tok)
                        if lps is not None \
                                and item.seq.sampling is not None \
                                and item.seq.sampling.logprobs:
                            item.seq.logprob_log.append(float(lps[i]))
                        if self.journal is not None:
                            journal_toks[item.seq.uid] = [tok]
                    else:
                        out[item.seq.uid] = result[i]
                    # the last chunk's output (token or logits) is this
                    # request's first host-visible result -> TTFT/TPOT
                    first = item.seq.stamp_first_token(now)
                    if obs is not None:
                        obs.on_token_commit(item.seq, now, first)
                    item.seq.status = SequenceStatus.WAITING
            if self.journal is not None:
                self.journal.tokens(journal_toks)
            self._finish_commit(fl)
        if obs is not None:
            obs.after_commit(self._step_counter)
        return len(fl.sched), out

    def decode_pipelined(self, batch_uids: Sequence[int],
                         first_tokens: Sequence[int], n,
                         eos_token_id: Optional[int] = None,
                         ) -> Dict[int, List[int]]:
        """Decode up to ``n`` tokens per uid (int, or a per-uid sequence
        of budgets) through the overlapped pipeline — or, when
        speculative decoding is armed (``spec_decode``
        and every sequence in the batch is greedy), through
        :meth:`decode_spec`, token-identically. Single-engine drivers
        (the open-loop loadgen, the replica pool) call this one surface
        and get speculation transparently.

        The pipelined path: host-side planning and token bookkeeping run
        ``pipeline_depth`` steps ahead of the delayed commit, and each
        step's input tokens come straight from the previous step's
        device-resident last-token buffer — the steady decode state pays
        ZERO host round-trips on its critical path (vs one blocking
        readback per token in the synchronous loop). Sequences carrying
        SamplingParams decode through the same pipeline with the
        per-slot on-device sampler (the sampled token buffer is the
        feedback source, so sampling adds no host round-trips either).

        Scheduling past the newest committed token is SPECULATIVE: when
        the delayed readback reveals a sequence emitted ``eos_token_id``
        at step k, its already-dispatched steps k+1.. are killed (their
        readback discarded, no post-EOS tokens emitted) and the
        speculation rolled back — token positions retracted and
        over-allocated KV blocks freed via ``StateManager.trim_blocks``
        once the last dead step has executed.

        Sequences must have no pending tokens (drain with put() first);
        returns {uid: emitted tokens}, ending with eos when it fired.
        The token stream is identical to the synchronous per-step path."""
        # speculative fast path (greedy batches only — sampled
        # sequences need lossless rejection sampling, and a logprobs
        # request needs the sampler program's per-token logprob output,
        # which the verify pass does not produce); token-identical to
        # the pipelined path by the verify construction
        impl = self._decode_pipelined_impl
        if self.spec_mode != "off" and batch_uids \
                and hasattr(self.runner, "decode_loop") \
                and all((s := self.state.get(u)) is not None
                        and (s.sampling is None
                             or (s.sampling.greedy
                                 and not s.sampling.logprobs))
                        and not s.in_flight for u in batch_uids):
            impl = self.decode_spec
        with self._spans.span("serve/decode_pipelined",
                              seqs=len(batch_uids)):
            return impl(batch_uids, first_tokens, n,
                        eos_token_id=eos_token_id)

    def _decode_pipelined_impl(self, batch_uids: Sequence[int],
                               first_tokens: Sequence[int], n,
                               eos_token_id: Optional[int] = None,
                               ) -> Dict[int, List[int]]:
        cfg = self.config
        # the batch's checks and its first tokens queued, before the first
        # plan: the stretch put() brackets under the same name
        with self._spans.span("serve/admit"):
            if len(batch_uids) != len(first_tokens):
                raise ValueError(
                    f"{len(batch_uids)} uids but {len(first_tokens)} "
                    f"first_tokens")
            if isinstance(n, (list, tuple)):
                budgets = {u: int(b) for u, b in zip(batch_uids, n)}
            else:
                budgets = {u: int(n) for u in batch_uids}
            seqs: Dict[int, Any] = {}
            for uid in batch_uids:
                seq = self.state.get(uid)
                if seq is None:
                    raise ValueError(f"unknown sequence {uid}")
                if seq.in_flight:
                    raise ValueError(f"sequence {uid} has pending tokens; "
                                     f"drain with put() first")
                seqs[uid] = seq
            for uid, seq in self.state.sequences.items():
                if uid not in budgets and seq.in_flight:
                    raise ValueError(
                        f"sequence {uid} has pending tokens but is not in "
                        f"this decode batch")
            out: Dict[int, List[int]] = {u: [] for u in batch_uids}
            finished = {u for u in batch_uids if budgets[u] <= 0}
            inflight_n = {u: 0 for u in batch_uids}
            spec_src: Dict[int, _InFlightStep] = {}   # uid -> producer step
            for uid, t in zip(batch_uids, first_tokens):
                if uid not in finished:
                    self.state.put_tokens(uid, [int(t)])
        self._feed_src, self._feed_slot = None, {}

        def eligible(seq):
            # a speculative placeholder may only be scheduled while its
            # producing step is the latest dispatched one (that step's
            # output buffer is the feed source); otherwise wait for the
            # producer's commit to patch in the host value
            if seq.spec_pending and seq.pending_tokens \
                    and seq.pending_tokens[0] == _SPEC_TOKEN:
                return seq.uid in self._feed_slot
            return True

        def work_left():
            return any(seqs[u].in_flight for u in budgets
                       if u not in finished)

        def commit_one(ring):
            fl = ring.popleft()
            self._pre_commit(fl)
            step = self._step_counter
            with self._spans.span("serve/commit_block", step=step):
                toks = np.asarray(fl.result)
                lps = np.asarray(fl.logprobs) \
                    if fl.logprobs is not None else None
            obs = self._obs
            now = time.monotonic()
            with self._spans.span("serve/commit_apply", step=step):
                apply_commit(ring, fl, toks, lps, obs, now)
            if obs is not None:
                obs.after_commit(self._step_counter)

        def apply_commit(ring, fl, toks, lps, obs, now):
            journal_toks: Dict[int, List[int]] = {}
            for i, item in enumerate(fl.sched):
                seq = item.seq
                u = seq.uid
                inflight_n[u] -= 1
                if spec_src.get(u) is fl:
                    del spec_src[u]
                    patch = True
                else:
                    patch = False
                if i in fl.dead:
                    continue
                tok = int(toks[i])
                seq.status = SequenceStatus.WAITING
                out[u].append(tok)
                seq.gen_log.append(tok)       # committed replay history
                if lps is not None and seq.sampling is not None \
                        and seq.sampling.logprobs:
                    seq.logprob_log.append(float(lps[i]))
                first = seq.stamp_first_token(now)
                if obs is not None:
                    obs.on_token_commit(seq, now, first)
                if self.journal is not None:
                    journal_toks.setdefault(u, []).append(tok)
                if patch and seq.spec_pending and seq.pending_tokens \
                        and seq.pending_tokens[0] == _SPEC_TOKEN:
                    # this step produced the queued placeholder and its
                    # value is now host-known: feed it by value instead
                    seq.pending_tokens[0] = tok
                    seq.spec_pending -= 1
                if len(out[u]) < budgets[u] and \
                        (eos_token_id is None or tok != eos_token_id):
                    continue
                # stop condition reached on the DELAYED readback: kill
                # everything that ran (or is queued) speculatively past
                # it. The queued next-input token — whether still a
                # placeholder or just patched by value above — exists
                # only because of speculation: drop it, or the sequence
                # ends with a stale pending token the sync path never
                # leaves behind
                finished.add(u)
                if seq.pending_tokens:
                    seq.pending_tokens.pop()
                    if seq.spec_pending:
                        seq.spec_pending -= 1
                    spec_src.pop(u, None)
                retract, last_fl = 0, None
                for fl2 in ring:
                    for j, item2 in enumerate(fl2.sched):
                        if item2.seq.uid == u and j not in fl2.dead:
                            fl2.dead.add(j)
                            retract += 1
                            last_fl = fl2
                if retract:
                    # the dead steps' KV appends still target the blocks
                    # being retracted — free them only once the last such
                    # step has executed (its commit)
                    last_fl.rollbacks.append((seq, retract))
            if self.journal is not None:
                self.journal.tokens(journal_toks)
            self._finish_commit(fl)

        def speculate(plan, fl):
            # speculate the next step: every live sequence scheduled in
            # this step gets a placeholder token whose value is this
            # step's (still in-flight) device output. Never past the
            # sequence's block capacity: the call then returns what fits
            # and the NEXT call's put_tokens raises the same
            # 'exceeds max_context' the synchronous path raises
            for item in plan.sched:
                seq = item.seq
                u = seq.uid
                if u not in budgets or u in finished:
                    continue
                inflight_n[u] += 1
                if len(out[u]) + inflight_n[u] < budgets[u] and \
                        seq.seen_tokens + seq.in_flight < cfg.max_context:
                    seq.pending_tokens.append(_SPEC_TOKEN)
                    seq.spec_pending += 1
                    spec_src[u] = fl

        self._drive_pipeline(
            work_left, lambda: self._plan_step(greedy=True,
                                               eligible=eligible),
            commit_one, on_dispatch=speculate)
        self._feed_src, self._feed_slot = None, {}
        return out

    # ------------------------------------------------------------------ #
    # speculative decoding (speculative.py, docs/serving.md)
    # ------------------------------------------------------------------ #

    def attach_draft(self, draft_model_cfg: Any, draft_params: Any,
                     draft_config: Optional[RaggedInferenceConfig] = None):
        """Pair a small DRAFT model with this engine for
        ``spec_decode='draft'`` (the engine serves 9 families —
        gpt2-drafting-for-llama is one config pair). The draft runs as
        its own engine over the same slot/block geometry with its own
        KV pool; it must share the target's vocabulary (same
        tokenizer). Its journal and telemetry are disabled — draft
        tokens are proposals, never served output. Returns the draft
        engine (callers may size ``draft_config`` themselves)."""
        self._refuse_stateful("attach_draft", latent_too=True)
        tv = getattr(self.runner.model_cfg, "vocab_size", None)
        dv = getattr(draft_model_cfg, "vocab_size", None)
        if tv != dv:
            raise ValueError(
                f"draft model vocab_size {dv} != target {tv}: a drafting "
                f"pair must share the tokenizer")
        if draft_config is None:
            import dataclasses as _dc
            # ep_size resets: the usual pairing is a DENSE draft for a
            # MoE target, and the draft replicates across the expert
            # mesh rather than inheriting an axis it cannot shard over
            draft_config = _dc.replace(
                self.config, prefix_cache=False, serve_pipeline_depth=0,
                spec_decode="off", serve_journal="",
                request_deadline_s=0.0, ep_size=1)
        draft = InferenceEngineV2(draft_model_cfg, draft_params,
                                  draft_config)
        # proposals are internal: never journaled, never counted as
        # served traffic, never speculated themselves (even when env
        # knobs armed them at construction)
        draft.journal = None
        draft._obs = None
        draft.spec_mode = "off"
        self._draft_engine = draft
        self._proposer = None
        return draft

    def _spec_proposer(self):
        if self._proposer is None:
            from .speculative import build_proposer
            self._proposer = build_proposer(self)
        return self._proposer

    @property
    def spec_enabled(self) -> bool:
        """True when decode routes through speculative decoding."""
        return self.spec_mode != "off"

    def decode_spec(self, batch_uids: Sequence[int],
                    first_tokens: Sequence[int], n,
                    eos_token_id: Optional[int] = None,
                    ) -> Dict[int, List[int]]:
        """Speculative greedy decode: per round, a proposer drafts up
        to ``spec_k`` tokens per sequence, ONE fused verify program
        (``decode_loop`` with draft-fed inputs) scores all K+1
        positions, and the host commits the longest agreeing prefix
        plus the model's own token at the first disagreement (or the
        free bonus token on full acceptance) — so each dispatch
        advances every sequence by 1..K+1 tokens instead of exactly 1.

        Rollback rule (PR 3's ``trim_blocks`` discipline): the verify
        pass appended KV for ALL K+1 positions; the host retracts
        ``seen_tokens`` to the accepted length and frees the
        over-allocated blocks — cache-shared blocks are decref'd
        exactly once, never freed (``StateManager.release_blocks``),
        and retained-block positions past the accepted length are
        plain garbage that the next round's appends overwrite (decode
        positions never land in shared blocks, so no cached content is
        ever clobbered).

        Token-identical to non-speculative greedy by construction: a
        draft survives only where it equals greedy's own choice.
        Returns {uid: emitted tokens} exactly like
        :meth:`decode_pipelined` (budgets list, eos truncation);
        sequences must have no pending tokens. Under KV pressure it
        evicts-then-retries and finally falls back to the incremental
        pipelined path, which can shed."""
        self._refuse_stateful("decode_spec", latent_too=True)
        from .speculative import accept_length
        cfg = self.config
        if len(batch_uids) != len(first_tokens):
            raise ValueError(
                f"{len(batch_uids)} uids but {len(first_tokens)} "
                f"first_tokens")
        if isinstance(n, (list, tuple)):
            budgets = {u: int(b) for u, b in zip(batch_uids, n)}
        else:
            budgets = {u: int(n) for u in batch_uids}
        seqs: Dict[int, Any] = {}
        for uid in batch_uids:
            seq = self.state.get(uid)
            if seq is None:
                raise ValueError(f"unknown sequence {uid}")
            if seq.in_flight:
                raise ValueError(f"sequence {uid} has pending tokens; "
                                 f"drain with put() first")
            seqs[uid] = seq
        out: Dict[int, List[int]] = {u: [] for u in batch_uids}
        last = {u: int(t) for u, t in zip(batch_uids, first_tokens)}
        live = {u for u in batch_uids if budgets[u] > 0}
        proposer = self._spec_proposer()
        K = self.spec_k
        S, MAXB = cfg.max_seqs, cfg.max_blocks_per_seq
        bs = cfg.block_size
        obs, spans = self._obs, self._spans
        jnp = jax.numpy
        # per-CALL staging (decode_spec is synchronous — the verify
        # readback completes before the next round reuses these, so
        # one set suffices; per-round allocation would put host alloc
        # churn on the very path speculation is shortening)
        tok0 = np.zeros((S,), np.int32)
        start = np.zeros((S,), np.int32)
        active = np.zeros((S,), np.int32)
        tables = np.zeros((S, MAXB), np.int32)
        draft_arr = np.zeros((S, K + 1), np.int32)
        if obs is not None:
            # attribution window for the spec path: each round is one
            # fused verify dispatch + one blocking readback; the
            # accept/rollback bookkeeping is the commit apply
            obs.on_loop_enter()
        while live:
            if self._draining():
                # preemption mid-spec-decode: stop proposing, let the
                # fallback path below unwind immediately — the
                # outstanding budgets ride the drain manifest
                break
            self._try_resume()
            for u in list(live):
                # shed/abort landed out-of-band (a deadline sweep in a
                # concurrent put, a caller abort): drop it from decode
                if seqs[u].status is SequenceStatus.FINISHED \
                        or u in self.rejections:
                    live.discard(u)
            ready = sorted(
                (u for u in live
                 if seqs[u].status is not SequenceStatus.PAUSED
                 # never speculate past a sequence's context capacity —
                 # a near-cap straggler takes the fallback path below
                 # instead of a garbage write (or of shrinking L, which
                 # would compile a fresh program per tail length)
                 and seqs[u].seen_tokens + K + 1 <= cfg.max_context),
                key=lambda u: len(out[u]))[:S]
            if not ready:
                if live and self._relieve_kv_pressure():
                    continue
                break
            rem = {u: budgets[u] - len(out[u]) for u in ready}
            # L is PINNED to spec_k + 1: one compiled verify program
            # serves every round (0 fresh compiles on the warm path).
            # Budget tails over-verify a few positions and the commit
            # truncates to the remaining budget — trading a sliver of
            # tail compute for a stable program cache.
            n_draft = K
            L = n_draft + 1
            need = sum(seqs[u].blocks_needed(L, bs) for u in ready)
            if need > self.kv_cache.free_blocks or any(
                    len(seqs[u].kv_blocks)
                    + seqs[u].blocks_needed(L, bs) > MAXB
                    for u in ready):
                if self._relieve_kv_pressure():
                    continue
                break                       # irreducible pressure
            histories = [seqs[u].prompt_log + seqs[u].gen_log
                         for u in ready]
            if n_draft > 0:
                drafts_list = proposer.propose_batch(
                    [seqs[u] for u in ready], histories, n_draft)
            else:
                drafts_list = [[] for _ in ready]
            for u in ready:
                self.state.ensure_blocks(seqs[u], L)
            for b in (tok0, start, active, tables, draft_arr):
                b.fill(0)
            for i, u in enumerate(ready):
                seq = seqs[u]
                tok0[i] = last[u]
                start[i] = seq.seen_tokens
                active[i] = 1
                tables[i, :len(seq.kv_blocks)] = seq.kv_blocks
                row = list(drafts_list[i])[:n_draft]
                while len(row) < n_draft:
                    # a short/absent proposal pads by repeating — a pad
                    # is just a cheap draft that verification may still
                    # accept (it costs nothing extra: the L positions
                    # run regardless)
                    row.append(row[-1] if row else last[u])
                draft_arr[i, 0] = last[u]
                if n_draft:
                    draft_arr[i, 1:] = row
            with spans.span("serve/fused_dispatch", steps=L, seqs=len(ready)):
                # the ring's flush runs on after the readback below has
                # returned and reads start / active / tables when it
                # does: on the CPU backend jnp.asarray may alias numpy
                # memory (zero-copy, by the buffer's alignment), and the
                # next round refills these buffers, so the flush would
                # store to blocks of that round. It gets copies.
                toks, _, self._kv_data, _, _ = self.runner.decode_loop(
                    self.params, self._kv_data, jnp.asarray(tok0),
                    jnp.asarray(start.copy()), jnp.asarray(active.copy()),
                    jnp.asarray(tables.copy()), L,
                    draft_toks=jnp.asarray(draft_arr), eos_id=-1)
            with spans.span("serve/fused_readback", steps=L, seqs=len(ready)):
                toks = np.asarray(toks)
            with spans.span("serve/fused_apply", steps=L, seqs=len(ready)) \
                    as span:
                span.count(**self._kv_write_counts(
                    [(seqs[u].seen_tokens, L) for u in ready], L))
                self.kv_cache.finalize_demotions()
                self._step_counter += L
                now = time.monotonic()
                journal_toks: Dict[int, List[int]] = {}
                round_prop = 0
                round_acc = 0
                for i, u in enumerate(ready):
                    seq = seqs[u]
                    emitted = [int(t) for t in toks[i]]
                    d_row = [int(t) for t in draft_arr[i, 1:]]
                    j = accept_length(d_row, emitted)
                    acc = emitted[:j + 1]
                    if len(acc) > rem[u]:
                        acc = acc[:rem[u]]
                    if eos_token_id is not None and eos_token_id in acc:
                        acc = acc[:acc.index(eos_token_id) + 1]
                    a = len(acc)
                    seen0 = seq.seen_tokens
                    # acceptance accounting + the multi-token rollback:
                    # consumed inputs == committed tokens == a; the
                    # remaining L - a appended positions are retracted and
                    # their over-allocated blocks freed (deferred-free
                    # semantics are unnecessary here — the verify readback
                    # is already committed, nothing is in flight)
                    seq.seen_tokens = seen0 + a
                    self.state.trim_blocks(seq)
                    seq.last_step = self._step_counter
                    seq.status = SequenceStatus.WAITING
                    # replay history (drain.py): the fed first token joins
                    # gen_log unless it is one of our own committed outputs
                    # being fed back — the decode_batch discipline
                    hist = []
                    if len(seq.prompt_log) + len(seq.gen_log) <= seen0:
                        hist.append(int(draft_arr[i, 0]))
                    hist.extend(acc)
                    seq.gen_log.extend(hist)
                    out[u].extend(acc)
                    last[u] = acc[-1]
                    # acceptance accounting over the COMMITTABLE window:
                    # the numerator is drafts actually kept (consumed
                    # inputs are lt + d_1..d_{a-1} -> a-1 drafts; a
                    # rolled-back verified draft must not inflate the
                    # rate), and the denominator excludes
                    # the budget-capped tail (only rem-1 drafts could
                    # ever commit this round — the rest is the pinned-L
                    # over-verification padding, not a proposer miss), so
                    # a perfect proposer reads 1.0
                    prop_eff = min(n_draft, rem[u] - 1)
                    acc_drafts = min(j, a - 1)
                    seq.spec_proposed += prop_eff
                    seq.spec_accepted += acc_drafts
                    round_prop += prop_eff
                    round_acc += acc_drafts
                    proposer.observe_commit(seq, seen0, acc, d_row)
                    if self.journal is not None:
                        journal_toks[u] = hist
                    if a:
                        first = seq.stamp_first_token(now)
                        if obs is not None:
                            obs.on_token_commit(seq, now, first, n=a)
                            # traced requests get a spec-round mark on
                            # their fleet track (no-op for untraced
                            # sequences)
                            obs.on_spec_commit(seq, acc_drafts, prop_eff)
                    if len(out[u]) >= budgets[u] or (
                            eos_token_id is not None
                            and acc[-1] == eos_token_id):
                        live.discard(u)
                if self.journal is not None:
                    self.journal.tokens(journal_toks)
            if obs is not None:
                obs.on_spec(round_prop, round_acc)
                obs.after_commit(self._step_counter)
        if obs is not None:
            obs.on_loop_exit()
        if live:
            # irreducible pressure / context cap: finish the stragglers
            # on the incremental pipelined path (which can shed)
            lu = sorted(live)
            res = self._decode_pipelined_impl(
                lu, [last[u] for u in lu],
                [budgets[u] - len(out[u]) for u in lu],
                eos_token_id=eos_token_id)
            for u in lu:
                out[u].extend(res.get(u) or [])
        return out

    # ------------------------------------------------------------------ #
    # convenience generate loop
    # ------------------------------------------------------------------ #

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None,
                 sampling: Optional[InferenceConfig] = None,
                 seed: int = 0) -> List[List[int]]:
        """Continuous-batching generation: prompts enter the scheduler
        together; decode steps fuse with any remaining prefill chunks.
        Decoding batches ``config.decode_loop_steps`` tokens per device
        call through the fused decode loop when the KV pool covers them
        — greedy AND sampled (the per-slot on-device sampler, seeds
        derived per-uid from ``seed``); KV pressure and tails run the
        pipelined/per-step put() paths. Only a runner without the
        sampler programs falls back to host-side sampling over full
        logits."""
        rng = np.random.default_rng(seed)
        greedy = sampling is None or sampling.greedy
        uids = list(range(len(prompts)))
        if max_new_tokens <= 0:
            return [[] for _ in uids]
        sp_map = None
        if not greedy and hasattr(self.runner, "step_sample_fb"):
            # on-device sampled generation: attach per-seq params at
            # admission; every decode path below then selects tokens
            # in-program (greedy-shaped host loop, zero host sampling)
            from .sampling import derive_seed
            sp_map = {u: SamplingParams(
                temperature=sampling.temperature, top_k=sampling.top_k,
                top_p=sampling.top_p, seed=derive_seed(seed, u))
                for u in uids}
        on_device = greedy or sp_map is not None
        live = set(uids)
        outputs: Dict[int, List[int]] = {u: [] for u in uids}
        last_tok: Dict[int, int] = {}

        def drop_rejected():
            # load-shed / deadline-aborted requests leave the loop with
            # whatever they got — their structured record stays in
            # self.rejections for the caller (no crash, no livelock)
            for u in list(live):
                if u in self.rejections:
                    live.discard(u)

        results = self.put(uids, [list(p) for p in prompts],
                           _greedy=on_device, sampling=sp_map)
        drop_rejected()
        for u in uids:
            if u not in results:
                live.discard(u)
                continue
            nxt = self._sample(results[u], sampling, rng)
            outputs[u].append(nxt)
            if (eos_token_id is not None and nxt == eos_token_id) or \
                    max_new_tokens <= 1:
                live.discard(u)
                self.flush(u)
            else:
                last_tok[u] = nxt
        N = self.config.decode_loop_steps
        # the fused loop serves SAMPLED decoding too (on-device sampler)
        can_loop = N > 1 and hasattr(self.runner, "decode_loop")

        def finish_chunk(u, toks):
            toks = toks[:max_new_tokens - len(outputs[u])]
            if not toks:
                return
            if eos_token_id is not None and eos_token_id in toks:
                cut = toks.index(eos_token_id)
                outputs[u].extend(toks[:cut + 1])
                live.discard(u)
                self.flush(u)
            else:
                outputs[u].extend(toks)
                last_tok[u] = toks[-1]
                if len(outputs[u]) >= max_new_tokens:
                    live.discard(u)
                    self.flush(u)

        while live:
            self._try_resume()
            lu = sorted(live)
            # pause/resume lets sequences progress unevenly: loop-chunk by
            # the least remaining budget; shorter tails take the put() path
            need = min(max_new_tokens - len(outputs[u]) for u in lu)
            if can_loop and need >= N and len(lu) <= self.config.max_seqs:
                # evict-then-loop (VERDICT r3 Weak #5): under KV pressure,
                # pause LRU block-holders and KEEP the fused loop running
                # on the remainder instead of collapsing to the per-token
                # put() path; paused sequences resume on later iterations
                outs = None
                ready = [u for u in lu if self.state.sequences[u].status
                         is not SequenceStatus.PAUSED]
                while ready:
                    try:
                        outs = self.decode_batch(
                            ready, [last_tok[u] for u in ready], N,
                            sampling=sampling, eos_token_id=eos_token_id)
                        break
                    except OutOfBlocksError:
                        if not self._relieve_kv_pressure():
                            break
                        ready = [u for u in ready
                                 if self.state.sequences[u].status
                                 is not SequenceStatus.PAUSED]
                if outs:
                    for u in list(outs):
                        finish_chunk(u, outs[u])
                    continue
            if on_device and self.pipeline_depth > 0 \
                    and hasattr(self.runner, "step_greedy_fb"):
                # overlapped pipeline tail: per-step decode with device
                # token feedback — plan/dispatch run ahead, commits (and
                # EOS detection + rollback) lag by pipeline_depth steps;
                # sampled sequences ride the same pipeline through the
                # per-slot sampler program
                outs = self.decode_pipelined(
                    lu, [last_tok[u] for u in lu],
                    [max_new_tokens - len(outputs[u]) for u in lu],
                    eos_token_id=eos_token_id)
                for u in lu:
                    finish_chunk(u, outs[u])
                drop_rejected()
                continue
            # tails / tiny budgets / truly starved pools: token-at-a-time
            results = self.put(lu, [[last_tok[u]] for u in lu],
                               _greedy=on_device)
            drop_rejected()
            for u in lu:
                if u not in results:
                    live.discard(u)
                    continue
                nxt = self._sample(results[u], sampling, rng)
                outputs[u].append(nxt)
                if (eos_token_id is not None and nxt == eos_token_id) or \
                        len(outputs[u]) >= max_new_tokens:
                    live.discard(u)
                    self.flush(u)
                else:
                    last_tok[u] = nxt
        return [outputs[u] for u in uids]

    @staticmethod
    def _sample(logits, cfg: Optional[InferenceConfig],
                rng: np.random.Generator) -> int:
        if isinstance(logits, (int, np.integer)):
            return int(logits)              # on-device greedy already sampled
        if cfg is None or cfg.greedy:
            return int(np.argmax(logits))
        x = logits.astype(np.float64) / max(cfg.temperature, 1e-6)
        if cfg.top_k > 0:
            kth = np.partition(x, -cfg.top_k)[-cfg.top_k]
            x = np.where(x < kth, -np.inf, x)
        if cfg.top_p < 1.0:
            order = np.argsort(-x)
            probs = np.exp(x[order] - x[order[0]])
            probs /= probs.sum()
            keep = np.cumsum(probs) <= cfg.top_p
            keep[0] = True
            cut = order[~keep]
            x[cut] = -np.inf
        p = np.exp(x - x.max())
        p /= p.sum()
        return int(rng.choice(len(p), p=p))
