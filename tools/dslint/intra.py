"""dslint per-file rules — DSL001 (hot-path host sync), DSL002
(undonated jit), DSL003 (raw shard_map import) — plus the HOT_PATHS
registry and the blocking-sync predicate DSL007(c) reuses."""

from __future__ import annotations

import ast
from typing import List, Mapping, Optional, Tuple

from .core import FileIndex, Finding, _dotted, _node_lines

#: overlap-critical functions (relative path suffix -> function names):
#: host work here runs AHEAD of the device — one blocking readback
#: serializes the whole serve pipeline. Nested defs are covered.
HOT_PATHS: Mapping[str, Tuple[str, ...]] = {
    # the serve-resilience hooks (_pre_commit .. abort) run INSIDE the
    # plan-ahead window on every pipeline iteration: deadline sweeps,
    # retry wrappers, shed/abort bookkeeping and the commit-side fault
    # hook must stay pure host work — one readback there re-serializes
    # the pipeline the drain layer is supposed to leave untouched
    # handoff_out/handoff_in are the disagg migration halves (ISSUE
    # 17): per-seq gathers and the restore scatter are enqueue-only
    # device work — the ONE sanctioned blocking materialize is the
    # pool's batched device_get in _migrate_prefill (allow-commented)
    "deepspeed_tpu/inference/v2/engine_v2.py":
        ("_drive_pipeline", "_plan_step", "_dispatch_step",
         "_staging_bufs", "_match_prefix", "_register_prefix",
         "_pre_commit", "_dispatch_with_retry", "_expire_deadlines",
         "abort", "_shed_starved", "handoff_out", "handoff_in"),
    # the per-slot sampling stager fills pre-allocated numpy buffers
    # inside the plan phase (engine _plan_step calls it per slot):
    # host stores over ints/floats only
    "deepspeed_tpu/inference/v2/sampling.py":
        ("stage_slot", "seed_of", "derive_seed"),
    # the speculative propose/accept half runs BETWEEN verify
    # dispatches on the decode hot path: n-gram matching, acceptance
    # prefix comparison and draft-rollback bookkeeping are pure host
    # list/dict walks — a device sync here would serialize every
    # speculation round behind a readback it does not need
    "deepspeed_tpu/inference/v2/speculative.py":
        ("accept_length", "propose", "propose_batch", "observe_commit"),
    # the write-ahead replay journal appends on the COMMIT path of every
    # serve step: buffered file writes over host ints only — a device
    # sync here would gate every committed token on the journal
    "deepspeed_tpu/inference/v2/drain.py":
        ("_write", "admit", "tokens", "finish"),
    # the seq-axis attention builders (ISSUE 18) trace inside every
    # warm prefill/decode program build: ring reconstruction of the
    # paged history and the split-K stat merge are pure trace-time code
    # (lax.ppermute / lax.all_gather) — a host sync here would stall
    # every retrace of the long-context serve path. slot_rows is
    # deliberately NOT registered: it is the host-side gather-index
    # helper (numpy over host ints, no device handles in reach).
    "deepspeed_tpu/inference/v2/seq_parallel.py":
        ("ring_all_gather", "combine_decode_stats"),
    "deepspeed_tpu/inference/v2/model_runner.py":
        ("_build_programs", "_seq_local_ctx", "_seq_paged_attention",
         "_seq_dense_ring_attention"),
    # the prefix-cache match/hash path runs inside put()'s plan-ahead
    # window (before and between _drive_pipeline fills): pure host dict
    # walks plus non-blocking CoW dispatch — a blocking readback here
    # would serialize the pipeline exactly like one in _plan_step. The
    # hierarchical-KV halves (pop_demotable/demote/promote/evict_host)
    # run inside reserve on the same window: demotion gathers must stay
    # batched, dispatch-only deferred work (materialize happens at the
    # commit boundary), never a blocking host sync
    "deepspeed_tpu/inference/v2/prefix_cache.py":
        ("match", "acquire", "release_block", "insert", "evict",
         "pop_demotable", "demote", "promote", "evict_host"),
    "deepspeed_tpu/inference/v2/state_manager.py":
        ("match_prefix", "register_prefix", "release_blocks"),
    # reserve is called by ensure_blocks inside every plan; with the
    # host tier armed it dispatches the batched demotion gather and the
    # promotion path dispatches restore scatters — enqueue-only device
    # work, the D2H device_get lives in finalize_demotions at the
    # commit boundary (deliberately NOT registered: it is the one
    # sanctioned blocking site, after a step readback already proved
    # the gathers complete)
    # gather_blocks/restore are the handoff's device halves: exact-
    # length gather dispatch and the batched restore scatter — both
    # enqueue-only (the materialize lives in the pool's one batched
    # device_get)
    "deepspeed_tpu/inference/v2/kv_cache.py":
        ("reserve", "_demote", "promote_block", "promote_blocks",
         "gather_blocks", "restore"),
    # the decomposed TP collective builders trace inside every runner
    # program build (and inside MoE training steps): a blocking host sync
    # here would stall every retrace of the serve/train hot path — these
    # must stay pure trace-time code (shard_map discipline: they are
    # axis-level ops used inside jax_compat-built shard_map regions and
    # import no shard_map themselves; DSL003 still covers the file)
    "deepspeed_tpu/comm/comm.py":
        ("overlap_all_reduce", "decomposed_all_reduce",
         "ring_reduce_scatter", "ring_all_gather",
         "_ring_reduce_scatter_impl", "_ring_all_gather_impl"),
    # the telemetry record paths run INSIDE the serve pipeline's
    # plan-ahead/commit window on every step and token: pre-bound
    # counter/gauge/histogram arithmetic and ring appends over host
    # floats only — one device readback here would tax every committed
    # token (docs/observability.md "Overhead methodology")
    # the step-time-attribution boundaries (on_loop_enter/exit, the
    # commit-apply bracket, the fused-dispatch bracket) and the
    # trace-context span taggers run on the same per-step/per-token
    # windows: perf_counter reads + pre-bound histogram observes + ring
    # appends only — a device sync here would inflate the very host-gap
    # component the layer exists to measure
    "deepspeed_tpu/telemetry/serve.py":
        ("on_admit", "on_sched", "on_token_commit", "on_span",
         "on_loop_enter", "on_loop_exit", "_close_step", "on_retry",
         "on_reject", "on_abort", "on_flush", "on_spec",
         "on_spec_commit", "on_promote", "on_handoff_out",
         "on_handoff_in", "on_handoff_replay", "_req_span",
         "_req_event"),
    # the engines' brackets (one per plan/dispatch/commit boundary and
    # per train_batch phase) wrap the hot paths above: a TraceMe, two
    # clock reads, a dict add and the observer's on_span
    "deepspeed_tpu/telemetry/trace.py":
        ("span", "set", "count", "void", "__enter__", "__exit__"),
    # the TRAIN observer's step brackets run inside every train_batch
    # (ISSUE 15): perf_counter reads, attribute stores and pre-bound
    # histogram observes only — a device sync here would inflate the
    # very components the attribution layer measures. The sanctioned
    # readbacks (the device_execute bracket in engine.train_batch, the
    # sentinel's scalar reads of the previous step's ready values)
    # carry explicit allow comments naming why they are deliberate.
    "deepspeed_tpu/telemetry/train.py":
        ("on_step_enter", "on_span", "previous_loss", "on_step_abort",
         "on_between", "on_step_exit", "_sentinel", "_finish_step"),
    # train_batch itself is the engine bracket site: the two
    # block_until_ready calls (the device_execute bracket, which waits
    # for the step BEFORE the one just dispatched, and the watchdog's
    # step_end) are the sanctioned blocking sites and carry allow
    # comments; everything else must stay pure host work
    "deepspeed_tpu/runtime/engine.py": ("train_batch",),
    "deepspeed_tpu/telemetry/registry.py":
        ("inc", "set", "observe", "quantile", "sample",
         "maybe_sample"),
    "deepspeed_tpu/telemetry/flight_recorder.py":
        ("phase", "record", "event"),
    # the open-loop loadgen's per-iteration driver brackets the engine's
    # overlapped pipeline (admit due arrivals, run a short decode
    # burst): a blocking host sync here would serialize the very hot
    # path whose capacity the run is measuring (bin/dstpu_loadgen;
    # benchmark/jobs/open_loop.py drives the same loop shape), and stall
    # the arrival clock the open-loop invariant protects
    "deepspeed_tpu/telemetry/loadgen.py":
        ("_admit_due", "_decode_burst", "_door_reject"),
    # the admission controller's poll/door/reject hooks run per driver
    # iteration and per offered request BETWEEN the engine's overlapped
    # pipeline fills: windowed-quantile deltas, AIMD arithmetic and
    # typed-rejection minting are pure host work over pre-bound metric
    # handles — one device readback here would serialize the very door
    # that exists to keep the engine's pipeline full under overload
    "deepspeed_tpu/serving/admission.py": ("poll", "tick", "door",
                                           "reject"),
    # the replica-pool router's score/select run on the fleet admission
    # path between the engines' overlapped pipelines: scoring reads
    # host-side metadata only (prefix-trie walk, dict sizes, streaming-
    # histogram quantiles) — one device sync here would gate EVERY
    # replica's admission behind one readback
    "deepspeed_tpu/serving/router.py": ("select", "score"),
    # the pool's engine-shaped surface dispatches to per-replica worker
    # threads; its own bookkeeping (routing groups, stash splicing, the
    # replica scoring accessors) must stay pure host work — a sync in
    # put/decode grouping would serialize the whole fleet's round
    # _mint_trace/_route run per admission between the engines'
    # pipelines: trace minting is two dict stores, the routing-decision
    # span is pure host scoring plus one ring append
    # _migrate_prefill is the disagg handoff splice: routing walks and
    # handoff dispatch are pure host work; its ONE batched device_get
    # (the exposed-cost materialize) is the sanctioned blocking site
    # and carries an allow comment
    "deepspeed_tpu/serving/pool.py":
        ("put", "decode_pipelined", "_take_stash", "_run_groups",
         "_mint_trace", "_route", "prefix_overlap",
         "prefix_overlap_tiered", "queue_frac", "slo_headroom",
         "_migrate_prefill"),
}

_SYNC_ATTRS = ("block_until_ready", "item")
_NUMPY_SYNC_FNS = ("asarray", "array")


def sync_call_msg(node: ast.Call,
                  aliases: Mapping[str, str]) -> Optional[str]:
    """The DSL001 blocking-sync predicate: a message when ``node`` is a
    call that blocks the host on the device, else None. Shared with
    DSL007(c) (sync while a lock is held)."""
    msg = None
    if isinstance(node.func, ast.Attribute) \
            and node.func.attr in _SYNC_ATTRS:
        msg = f".{node.func.attr}() blocks on the device"
    dotted = _dotted(node.func, aliases)
    if dotted == "jax.device_get":
        msg = "jax.device_get blocks on the device"
    elif dotted and dotted.split(".")[0] == "numpy" \
            and dotted.split(".")[-1] in _NUMPY_SYNC_FNS:
        msg = (f"{dotted} on a device array is a blocking host "
               f"readback (use jnp.asarray for host->device)")
    elif isinstance(node.func, ast.Name) \
            and node.func.id in ("int", "float") and node.args \
            and isinstance(node.args[0],
                           (ast.Call, ast.Subscript, ast.Attribute)):
        msg = (f"{node.func.id}(...) scalar coercion of a "
               f"non-trivial expression may force a device sync")
    return msg


def _check_hot_fn(fn: ast.AST, fi: FileIndex,
                  findings: List[Tuple[Finding, range]]) -> None:
    hot = fn.name
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        msg = sync_call_msg(node, fi.aliases)
        if msg:
            findings.append((Finding(
                "DSL001", fi.relpath, node.lineno,
                f"in hot path '{hot}': {msg}"), _node_lines(node)))


def file_findings(fi: FileIndex,
                  hot_paths: Mapping[str, Tuple[str, ...]]
                  ) -> List[Finding]:
    """DSL001-003 for one indexed file (suppressions applied)."""
    if fi.error is not None:
        return [fi.error]
    assert fi.tree is not None
    raw: List[Tuple[Finding, range]] = []
    relpath = fi.relpath

    # DSL001 — hot-path host-sync hygiene
    hot_fns: Tuple[str, ...] = ()
    for suffix, names in hot_paths.items():
        if relpath.endswith(suffix):
            hot_fns = names
            break
    if hot_fns:
        for node in ast.walk(fi.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in hot_fns:
                _check_hot_fn(node, fi, raw)

    # DSL002 — undonated jax.jit in inference/v2
    if "deepspeed_tpu/inference/v2/" in relpath:
        for node in ast.walk(fi.tree):
            if isinstance(node, ast.Call) \
                    and _dotted(node.func, fi.aliases) == "jax.jit":
                kw = {k.arg for k in node.keywords}
                if not kw & {"donate_argnums", "donate_argnames"}:
                    raw.append((Finding(
                        "DSL002", relpath, node.lineno,
                        "jax.jit without donate_argnums/donate_argnames "
                        "(serving buffers are large — donate, or justify "
                        "with # dslint: allow(DSL002): why)"),
                        _node_lines(node)))

    # DSL003 — the legacy jax.experimental.shard_map, anywhere
    for node in ast.walk(fi.tree):
        hit = None
        if isinstance(node, ast.Import):
            if any(a.name.startswith("jax.experimental.shard_map")
                   for a in node.names):
                hit = "import jax.experimental.shard_map"
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            if node.module \
                    and node.module.startswith(
                        "jax.experimental.shard_map"):
                hit = f"from {node.module} import ..."
            elif node.module == "jax.experimental" \
                    and any(a.name == "shard_map" for a in node.names):
                hit = "from jax.experimental import shard_map"
        if hit:
            raw.append((Finding(
                "DSL003", relpath, node.lineno,
                f"{hit}: the tree targets jax.shard_map (take shard_map "
                f"from utils/jax_compat)"),
                _node_lines(node)))

    return [f for f, lines in raw if not fi.suppressed(lines, f.rule)]
