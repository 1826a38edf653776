"""Ragged engine configuration.

Analogue of the reference's ``RaggedInferenceEngineConfig``
(``inference/v2/config_v2.py``): state-manager sizing + scheduler knobs. The
shape-defining fields (``max_seqs``, ``chunk_size``, ``max_blocks_per_seq``)
are compile-time constants — one XLA program serves every step.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

from ...config.config_utils import ConfigModel


@dataclass
class RaggedInferenceConfig(ConfigModel):
    # scheduler shape (static): slots per batch × max tokens per slot per step
    max_seqs: int = 8                 # reference: max_ragged_sequence_count
    chunk_size: int = 128             # Dynamic-SplitFuse token chunk per seq
    # KV pool
    block_size: int = 64              # reference KVCacheConfig block granularity
    num_blocks: int = 256             # pool size (blocks of block_size tokens)
    max_blocks_per_seq: int = 32      # static width of the block table
    dtype: str = "bfloat16"
    # KV pool storage dtype. "auto" = the compute dtype. "int8": symmetric
    # per-(token, kv-head) quantized pool (kv_quant.py) — halves the
    # decode step's dominant HBM-traffic term and doubles the sequences a
    # fixed pool holds; scales ride a [L, 2, KV, slots] side array (~3%).
    kv_cache_dtype: str = "auto"
    # "auto": Pallas paged-flash kernel on TPU (per-step HBM traffic = live
    # blocks only), dense gather elsewhere (interpret-mode Pallas would be a
    # Python-loop per layer per step off-TPU). "paged_flash"/"dense" force.
    attention_impl: str = "auto"
    # Tensor-parallel serving over the 'model' mesh axis (inference/v2/
    # tp.py): weights follow the tp_rules column/row classification, the
    # KV pool + decode ring are head-sharded (per-chip KV bytes ∝ 1/tp),
    # and each layer pays exactly two all-reduces plus one pre-sampling
    # logits gather. num_heads and kv_heads must divide by tp_size.
    tp_size: int = 1
    # Sequence-parallel serving over the 'seq' mesh axis (inference/v2/
    # seq_parallel.py, docs/serving.md "Long-context serving"): the KV
    # pool is SEQUENCE-sharded — one sequence's blocks span chips
    # round-robin by chain ordinal (block o lives on chip o % seq_size),
    # so per-chip pool bytes stay FLAT as a request's context grows past
    # what one chip's pool holds. Prefill chunks shard their query slice
    # over the axis (context-parallel prefill: each chip attends its
    # slice against the full paged history via a ring pass over the
    # per-chip KV shards); decode broadcasts q and combines per-chip
    # partial flash-softmax stats with one small all-gather per layer.
    # Weights replicate over the axis. seq_size=1 traces the exact
    # pre-seq programs (the parity oracle).
    # Mutually exclusive with tp_size > 1 for now; requires the dense
    # attention path and num_blocks / max_blocks_per_seq divisible by
    # seq_size.
    seq_size: int = 1
    # Expert-parallel serving over the 'expert' mesh axis (inference/v2/
    # expert_parallel.py, docs/serving.md "Expert-parallel MoE serving"):
    # the stacked expert weights (layer_*/moe/{wi_gate,wi_up,wo}) shard
    # block-wise over ep_size chips (expert e lives on chip
    # e // (E/ep_size)) so per-chip expert bytes ∝ 1/ep — the capacity
    # lever for sparse models whose FULL expert set outgrows one chip's
    # HBM. _moe_mlp becomes a dispatch → grouped-GEMM → combine pipeline:
    # router logits everywhere, ONE packed all-to-all routes token rows
    # to their experts' home chips, each chip runs the grouped expert
    # GEMM over only its resident experts' contiguous rows, and a second
    # all-to-all returns the gate-weighted outputs — exactly 2 a2a per
    # MoE layer, inside both the SplitFuse prefill step and the fused
    # decode loop. Composes with tp_size > 1 (ep×tp mesh: attention
    # shards over 'model', experts over 'expert'); mutually exclusive
    # with seq_size > 1. num_experts must divide by ep_size. ep_size=1
    # traces the exact pre-ep single-chip programs (the parity oracle).
    ep_size: int = 1
    # Overlapped expert dispatch/combine (the PR 6 decomposed-collective
    # shape): "chunked" splits each a2a's capacity slots into
    # ep_comm_chunks independent slices so chunk k's expert GEMM runs
    # under chunk k+1's dispatch a2a. "off" is the single-a2a parity
    # oracle — token streams are identical either way (per-row GEMM
    # results and the slot-ordered combine don't depend on chunking).
    ep_comm_overlap: str = "off"
    # Chunk count for ep_comm_overlap="chunked" (capacity slots per
    # destination are rounded up to a multiple of this).
    ep_comm_chunks: int = 2
    # Dispatch capacity slack: each chip reserves
    # ceil(rows * ep_capacity_factor / ep_size) slots per destination
    # chip (rows = tokens * top_k), capped at rows. Rows routed past a
    # destination's slots are DROPPED (their gate weight is lost), the
    # standard fixed-capacity MoE trade; factor >= ep_size is provably
    # dropless (every destination can absorb every row) — the default
    # 2.0 makes the flagship ep=2 geometry exact, which the ep=1 vs
    # ep=2 parity oracle relies on.
    ep_capacity_factor: float = 2.0
    # Route the TP all-reduces through int8 quantized comm (EQuARX-class
    # for bandwidth-bound decode). With tp_comm_overlap off this is the
    # legacy monolithic int8 all-gather; with overlap on, quant/dequant
    # fuses into every ring hop with per-chunk scales. Greedy token
    # parity across tp sizes is NOT guaranteed with this on.
    tp_quantized_comm: bool = False
    # Decomposed, compute-overlappable TP collectives (comm/comm.py,
    # docs/serving.md "Decomposed TP collectives"): replace each per-layer
    # monolithic all-reduce with ring reduce-scatter + ring all-gather
    # ppermute hops XLA can hide under adjacent GEMMs.
    #   "off"           — one psum per site (the parity oracle);
    #   "rs_ag"         — tp-1 RS hops + tp-1 AG hops per site;
    #   "rs_ag_chunked" — additionally split the activation into
    #                     tp_comm_chunks independent ring pipelines
    #                     (k = chunks*(tp-1) hops per phase per site).
    # The env knob DSTPU_TP_OVERLAP (off|rs_ag|rs_ag_chunked[:k])
    # overrides at engine construction — the operational kill-switch.
    tp_comm_overlap: str = "off"
    # Chunk count for tp_comm_overlap="rs_ag_chunked" (k independent ring
    # pipelines per all-reduce site; hidden_size must divide by
    # tp_size * tp_comm_chunks). DSTPU_TP_OVERLAP_CHUNKS overrides.
    tp_comm_chunks: int = 2
    # Cap on the SplitFuse prefill chunk actually scheduled (and on the
    # compiled prefill program's token dim): min(chunk_size, cap).
    # 512-token chunks OOM prefill activations at max_seqs >= 384
    # (PROFILE.md serving levers); 256 keeps the transient bounded.
    # 0 disables the cap.
    prefill_chunk_cap: int = 256
    # Automatic prefix caching (prefix_cache.py): a content-addressed,
    # parent-linked index over full KV blocks with per-block refcounts.
    # put() matches each fresh prompt's longest cached block chain and
    # skips those prefill chunks entirely (the sequence's table points at
    # the shared device blocks); a partial-tail match is served by one
    # copy-on-write block copy. Refcount-0 blocks STAY cached and are
    # LRU-evicted only under allocator pressure. Greedy decode is
    # token-identical with this on or off (the cached rows are exactly
    # what a fresh prefill would write — positions start at 0 and KV
    # content is deterministic, int8 pool payloads and scales included).
    prefix_cache: bool = False
    # Cap on cached blocks (0 = bounded by the pool only): at the cap an
    # insert evicts one cold block, or is skipped when everything cached
    # is still referenced.
    prefix_cache_max_blocks: int = 0
    # Eviction order among refcount-0 cached blocks: "lru" (least
    # recently released, default) or "fifo" (oldest insertion).
    prefix_cache_policy: str = "lru"
    # Hierarchical KV (docs/serving.md "Hierarchical KV"): a host-RAM
    # prefix-cache tier of up to this many blocks (0 = off). With it on,
    # reserve pressure DEMOTES refcount-0 cached blocks (one batched
    # non-blocking device->host gather per reserve call) instead of
    # destroying them; a later match on a demoted chain PROMOTES the
    # links back through fresh device blocks with the H2D scatters
    # dispatched ahead of the sequence's remaining prefill chunks — a
    # demoted hit is still a hit, just a slower one. Content is only
    # lost past this cap (its own LRU/FIFO, prefix_cache_policy order).
    # Token streams are identical tier on/off.
    prefix_cache_host_blocks: int = 0
    # Overlapped serving pipeline depth: how many scheduled steps may be
    # in flight on the device at once. The serve loop splits into plan
    # (host: scheduler + batch staging, runs ahead) / dispatch (enqueue
    # the compiled step without blocking — JAX async dispatch keeps the
    # result as an in-flight future) / commit (apply step k's readback
    # while step k+1 executes), so host-side bookkeeping overlaps device
    # compute instead of sitting in its idle gap. Greedy decode feeds the
    # next step's token slots from a device-resident last-token buffer
    # (no host round-trip in the steady pure-decode state); EOS is
    # reconciled on the delayed readback with explicit rollback.
    # 0 = fully synchronous (the parity oracle).
    serve_pipeline_depth: int = 2
    # ---- serve-side resilience (drain.py, docs/resilience.md) ---------
    # Per-request wall-clock deadline in seconds, stamped at admission
    # (0 = no deadlines). An expired request is ABORTED mid-pipeline with
    # a structured rejection (engine.rejections) instead of being served
    # late — its KV blocks and prefix-cache refcounts are released
    # exactly, deferred past any in-flight step that still writes them.
    request_deadline_s: float = 0.0
    # Bounded retry for a serve-step dispatch that fails with a
    # TRANSIENT (I/O-class) error: retries with exponential backoff from
    # serve_retry_backoff_s, then raises ServeStepError. The plan phase's
    # host state is untouched by a failed dispatch, so redispatching the
    # same planned step is always safe.
    serve_step_retries: int = 2
    serve_retry_backoff_s: float = 0.05
    # Graceful load-shedding: when the scheduler starves with the KV pool
    # exhausted even after prefix-cache eviction AND pausing every idle
    # holder, abort the cheapest-to-redo victim (not-yet-started first,
    # then largest demand) with a structured rejection instead of
    # crashing the serve loop. False restores the hard RuntimeError.
    serve_shed: bool = True
    # Write-ahead replay journal path ("" = off): one JSONL record per
    # admission / committed step / flush, flushed to the OS per record —
    # a hard-crashed replica's committed token chains survive and
    # manifest_from_journal() rebuilds the replay manifest. Env:
    # DSTPU_SERVE_JOURNAL (+ DSTPU_SERVE_JOURNAL_FSYNC=1 for machine-loss
    # durability).
    serve_journal: str = ""

    # ---- speculative decoding (speculative.py, docs/serving.md) -------
    # Draft-and-verify multi-token decode for GREEDY sequences: a
    # proposer emits up to spec_k candidate tokens per sequence per
    # round, ONE fused verify program scores all K+1 positions
    # (decode_loop with draft-fed inputs), and rejected tokens roll back
    # through the deferred trim_blocks discipline. Token-identical to
    # non-speculative greedy by construction.
    #   "off"   — no speculation (the parity oracle);
    #   "ngram" — model-free self-drafting: propose the continuation of
    #             the last n-gram's previous occurrence in the
    #             sequence's own history (prompt lookup decoding);
    #   "draft" — a config-paired small draft model (attach via
    #             engine.attach_draft; e.g. gpt2 drafting for llama).
    # Sampled (temperature > 0) sequences bypass speculation.
    spec_decode: str = "off"
    # Draft tokens proposed per sequence per round (the verify program
    # scores spec_k + 1 positions).
    spec_k: int = 4
    # n-gram width the "ngram" proposer matches against the sequence's
    # own history (falls back n, n-1, .., 1).
    spec_ngram: int = 3

    # sampling defaults for the built-in generate loop
    greedy: bool = True
    temperature: float = 1.0
    top_k: int = 0
    # fused greedy decode: tokens generated per device call via the
    # on-device scan (engine.decode_greedy). Collapses per-token host
    # round-trips — the decode wall whenever host<->chip latency is
    # non-trivial. 0/1 disables (every token through put()).
    decode_loop_steps: int = 16
    # Dynamic-SplitFuse FORWARD budget: total tokens per mixed step
    # (decode rows always fit; prefill chunks — split mid-chunk if needed
    # — fill up to this). The actual SplitFuse semantics: a near-constant
    # forward size regardless of arrival pattern. 0 = max_seqs*chunk_size
    # (every slot can carry a full chunk — prefill activation memory then
    # scales with max_seqs, which OOMs big-slot configs). 32768 keeps the
    # prefill activation transient bounded (~370 MB at llama-1.1B width)
    # while amortizing per-forward weight reads and host round-trips.
    max_batch_tokens: int = 32768

    def __post_init__(self):
        if self.max_seqs <= 0 or self.chunk_size <= 0:
            raise ValueError("max_seqs and chunk_size must be positive")
        if self.block_size <= 0 or self.num_blocks <= 0:
            raise ValueError("block_size and num_blocks must be positive")
        if self.kv_cache_dtype not in ("auto", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'auto' or 'int8', got "
                f"{self.kv_cache_dtype!r}")
        if self.tp_size < 1:
            raise ValueError(f"tp_size must be >= 1, got {self.tp_size}")
        if self.seq_size < 1:
            raise ValueError(
                f"seq_size must be >= 1, got {self.seq_size}")
        if self.seq_size > 1:
            if self.tp_size > 1:
                # composing the model and seq axes needs 2-D pool specs
                # and a double logits reduction — future work; fail at
                # config time rather than mis-shard silently
                raise ValueError(
                    "seq_size > 1 with tp_size > 1 is not supported yet "
                    "— pick one sharding axis per engine")
            if self.num_blocks % self.seq_size:
                raise ValueError(
                    f"num_blocks ({self.num_blocks}) must divide by "
                    f"seq_size ({self.seq_size}) — the pool shards "
                    f"round-robin by block index")
            if self.max_blocks_per_seq % self.seq_size:
                # the block-table gather takes chain ordinals o ≡ r
                # (mod seq) per chip — a ragged table width would leave
                # the last ordinals unreachable from their home chip
                raise ValueError(
                    f"max_blocks_per_seq ({self.max_blocks_per_seq}) "
                    f"must divide by seq_size ({self.seq_size})")
            if self.attention_impl not in ("dense", "auto"):
                raise ValueError(
                    f"seq_size > 1 requires the dense attention path "
                    f"(the paged-flash kernel indexes a single-chip "
                    f"pool layout), got attention_impl="
                    f"{self.attention_impl!r}")
        if self.ep_size < 1:
            raise ValueError(f"ep_size must be >= 1, got {self.ep_size}")
        if self.ep_size > 1 and self.seq_size > 1:
            # the expert axis composes with tp (ep×tp mesh), not with
            # the sequence axis: the seq pool sharding and the expert
            # dispatch both want to own the token dim — fail at config
            # time with the knob names rather than mis-shard silently
            raise ValueError(
                "ep_size > 1 with seq_size > 1 is not supported — the "
                "expert axis composes with tp_size (ep×tp), not with "
                "the sequence axis; pick ep_size or seq_size")
        if self.ep_comm_overlap not in ("off", "chunked"):
            raise ValueError(
                f"ep_comm_overlap must be 'off' or 'chunked', got "
                f"{self.ep_comm_overlap!r}")
        if self.ep_comm_chunks < 1:
            raise ValueError(
                f"ep_comm_chunks must be >= 1, got {self.ep_comm_chunks}")
        if self.ep_capacity_factor <= 0:
            raise ValueError(
                f"ep_capacity_factor must be > 0, got "
                f"{self.ep_capacity_factor}")
        from ...comm import TP_OVERLAP_MODES
        if self.tp_comm_overlap not in TP_OVERLAP_MODES:
            raise ValueError(
                f"tp_comm_overlap must be one of {TP_OVERLAP_MODES}, "
                f"got {self.tp_comm_overlap!r}")
        if self.tp_comm_chunks < 1:
            raise ValueError(
                f"tp_comm_chunks must be >= 1, got {self.tp_comm_chunks}")
        if self.prefill_chunk_cap < 0:
            raise ValueError(
                f"prefill_chunk_cap must be >= 0 (0 = uncapped), got "
                f"{self.prefill_chunk_cap}")
        if self.prefix_cache_policy not in ("lru", "fifo"):
            raise ValueError(
                f"prefix_cache_policy must be 'lru' or 'fifo', got "
                f"{self.prefix_cache_policy!r}")
        if self.prefix_cache_max_blocks < 0:
            raise ValueError(
                f"prefix_cache_max_blocks must be >= 0 (0 = pool-bounded), "
                f"got {self.prefix_cache_max_blocks}")
        if self.prefix_cache_host_blocks < 0:
            raise ValueError(
                f"prefix_cache_host_blocks must be >= 0 (0 = host tier "
                f"off), got {self.prefix_cache_host_blocks}")
        if self.serve_pipeline_depth < 0:
            raise ValueError(
                f"serve_pipeline_depth must be >= 0 (0 = synchronous), "
                f"got {self.serve_pipeline_depth}")
        if self.request_deadline_s < 0:
            raise ValueError(
                f"request_deadline_s must be >= 0 (0 = no deadlines), "
                f"got {self.request_deadline_s}")
        if self.serve_step_retries < 0:
            raise ValueError(
                f"serve_step_retries must be >= 0, got "
                f"{self.serve_step_retries}")
        if self.serve_retry_backoff_s < 0:
            raise ValueError(
                f"serve_retry_backoff_s must be >= 0, got "
                f"{self.serve_retry_backoff_s}")
        if self.spec_decode not in ("off", "ngram", "draft"):
            raise ValueError(
                f"spec_decode must be 'off', 'ngram' or 'draft', got "
                f"{self.spec_decode!r}")
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        if self.spec_ngram < 1:
            raise ValueError(
                f"spec_ngram must be >= 1, got {self.spec_ngram}")

    def validate(self, model_cfg=None) -> None:
        """Config × model validation the field checks can't see — called
        at ENGINE CONSTRUCTION (before any program traces) so an
        unsupported combo fails with the knob names, not a
        NotImplementedError from deep inside a trace. Safe to call with
        ``model_cfg=None`` (pure-config use); ``__post_init__`` already
        ran the field-local checks."""
        if model_cfg is None:
            return
        from ...models.mixtral import MixtralConfig
        is_moe = isinstance(model_cfg, MixtralConfig)
        kinds = getattr(model_cfg, "layer_kinds", ())
        # a model with recurrent layers keeps per-sequence state that
        # cannot be rewound, copied or sharded yet; a latent-attention
        # model keeps ONE plane a layer, a row that is key and value at
        # once, which nothing that reads K and V planes, shards by kv
        # heads or scales a head has been carried over. What would need
        # either refuses here, by name; a model with both kinds of layer
        # gives both reasons
        why = []
        recurrent = [k for k in kinds
                     if k not in ("attn", "mla", "swa", "sparse", None)]
        if recurrent:
            why.append(functools.partial(stateful_refusal,
                                         kind=recurrent[0]))
        if "mla" in kinds:
            why.append(latent_refusal)
        if "swa" in kinds:
            # a sliding-window layer's rows live in a slot of the window
            # pool, which no block, manifest, shard or scale covers
            why.append(windowed_refusal)
        if "sparse" in kinds:
            # a block-selected layer's compressed keys live in a plane
            # beside the pool, which no manifest, scale or shard covers
            why.append(selecting_refusal)
        for on, feature in (
                (self.prefix_cache, "prefix_cache"),
                (self.spec_decode != "off", "spec_decode"),
                (self.kv_cache_dtype == "int8", "kv_cache_dtype='int8'"),
                (self.tp_size > 1, "tp_size > 1"),
                (self.seq_size > 1, "seq_size > 1"),
                (self.ep_size > 1, "ep_size > 1")):
            if on and why:
                raise ValueError("; ".join(r(feature) for r in why))
        if "sparse" in kinds:
            # the fused loop's sparse call attends over EVERY ring row
            # without asking the selection: right only while the loop's
            # own rows all lie in the blocks a query is forced to read
            # (the window_size / block_size blocks ending at its own,
            # which hold at least this many positions back)
            sp = model_cfg.sparse
            reach = sp.window_size - sp.block_size + 1
            if self.decode_loop_steps > reach:
                raise ValueError(
                    f"decode_loop_steps={self.decode_loop_steps} is past "
                    f"the {reach} positions a block-selected ('sparse') "
                    f"layer's forced window always holds (window_size "
                    f"{sp.window_size} - block_size {sp.block_size} + 1): "
                    f"the fused loop's ring rows would be read whether "
                    f"selected or not (set decode_loop_steps <= {reach})")
        if is_moe and self.tp_size > 1 and self.ep_size == 1:
            # tp alone would replicate the full expert set on every chip
            # AND trip the dense-branch all-reduce accounting — for MoE
            # runners tp requires the expert axis (attention shards over
            # 'model', experts over 'expert')
            raise ValueError(
                f"MoE serving with tp_size={self.tp_size} requires the "
                f"expert axis: set ep_size > 1 (ep×tp mesh — attention "
                f"shards over tp, experts over ep) or serve at "
                f"tp_size=1")
        if getattr(model_cfg, "qk_norm", False) and self.tp_size > 1:
            # the norm runs over the whole q / k projection, which tp
            # shards by heads: a chip would normalise by its own heads only
            raise ValueError(
                f"qk_norm models normalise q and k over the whole "
                f"projection; tp_size={self.tp_size} shards it by heads "
                f"(serve at tp_size=1, or over the expert axis alone)")
        if self.ep_size > 1:
            if not is_moe:
                raise ValueError(
                    f"ep_size={self.ep_size} shards stacked expert "
                    f"weights, and {type(model_cfg).__name__} has none "
                    f"— the expert axis is MoE-only (set ep_size=1)")
            if model_cfg.num_experts % self.ep_size:
                raise ValueError(
                    f"num_experts ({model_cfg.num_experts}) must divide "
                    f"by ep_size ({self.ep_size}) — experts shard "
                    f"block-wise over their home chips")

    @property
    def max_context(self) -> int:
        return self.max_blocks_per_seq * self.block_size

    @property
    def effective_chunk(self) -> int:
        """Prefill chunk length the scheduler (and the compiled prefill
        program's token dim) actually uses.

        With ``seq_size > 1`` the chunk is rounded UP to the next
        multiple of the seq axis: the context-parallel prefill slices
        the compiled token dim into ``seq_size`` equal query shards, so
        a non-divisible chunk would either truncate tokens or hand one
        chip a zero-width slice. Padding (the trailing slice carries
        masked pad tokens on short chunks) keeps every shard's shape
        static and nonzero."""
        c = min(self.chunk_size, self.prefill_chunk_cap) \
            if self.prefill_chunk_cap > 0 else self.chunk_size
        if self.seq_size > 1:
            c = -(-c // self.seq_size) * self.seq_size
        return c

    @property
    def prefill_rows(self) -> int:
        """Rows longer than one token a step may carry, and the slot
        dimension of the prefill program: a step of ~512 tokens already
        passes the v5e's ridge (240 FLOP a byte on bf16 weights), so more
        rows add latency and no throughput. Follows the ROUNDED chunk."""
        return min(max(2, self.effective_chunk // 128), self.max_seqs)

    @property
    def token_budget(self) -> int:
        if self.max_batch_tokens and self.max_batch_tokens > 0:
            return min(self.max_batch_tokens,
                       self.max_seqs * self.chunk_size)
        return self.max_seqs * self.chunk_size


def stateful_refusal(feature: str, kind: str = "kda") -> str:
    """The one wording of every refusal a model with recurrent layers
    makes: the feature, and the layer kind that stands in its way."""
    return (f"{feature} is not supported for a model with recurrent "
            f"({kind!r}) layers: it needs a snapshot, a rewind or a shard "
            f"of the per-sequence recurrent state, which the state pool "
            f"cannot give yet")


def latent_refusal(feature: str) -> str:
    """The one wording of every refusal a latent-attention model makes:
    the feature that cannot run over its one-plane cache yet."""
    return (f"{feature} is not supported for a model with latent "
            f"('mla') attention layers: its cache keeps one plane a layer "
            f"(the latent row is key and value at once), and this path "
            f"has not been carried over that plane yet")


def windowed_refusal(feature: str) -> str:
    """The one wording of every refusal a model with sliding-window
    ('swa') layers makes: the feature that cannot run over the rows its
    window layers keep in a sequence slot of the window pool."""
    return (f"{feature} is not supported for a model with sliding-window "
            f"('swa') layers: their rows live in a sequence slot of the "
            f"window pool (the last window's rows, overwritten in place), "
            f"which no block, manifest, shard or scale carries yet")


def selecting_refusal(feature: str) -> str:
    """The one wording of every refusal a model with block-selected
    ('sparse') attention layers makes: the feature that cannot run over
    the compressed-key plane its selection scores against."""
    return (f"{feature} is not supported for a model with block-selected "
            f"('sparse') attention layers: their compressed keys live in "
            f"a plane beside the paged pool (a row a kernel_stride "
            f"positions of every block), which no manifest, scale or "
            f"shard carries yet")
