"""Declarative collective-budget registries — ONE source of truth for
the seq/TP hop budgets (ISSUE 18) and the static collective-site map.

This module is deliberately jax-free and the two registries are PURE
LITERALS: the runtime (the ``test_seq_parallel.py`` and
``test_moe_serving.py`` budget tests) imports them through
:func:`budget_args`, while ``tools/dslint`` (rule DSL008)
``ast.literal_eval``s the same assignments without importing the
package — a budget edited in only one place is impossible, and lint
runs without jax. Keep every value a literal; dslint fails the build
otherwise.

``HOP_BUDGETS`` — RUNTIME hop counts per audited program, the
:class:`~deepspeed_tpu.analysis.program_audit.CollectiveBudget` shape.
Values may be the symbolic strings ``"seq-1"`` / ``"seq"`` (resolved
against the live seq-shard width by :func:`budget_args`) or plain ints.
Keys may pin a comm dtype as ``"kind@dtype"``.

``SITE_BUDGETS`` — STATIC distinct collective call sites (by primitive
kind) reachable from each registered program-builder function through
the intra-repo call graph, the DSL008 contract. Counting sites, not
hops: layers x steps x ring-width multiplicities are HOP_BUDGETS'
domain; the static shape that generates them is pinned here. Calls
into ``comm/comm.py`` are the decomposed-collective layer's own domain
and form the audit boundary (its wrappers count as their kind at the
call site).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

#: program name -> CollectiveBudget field spec (pure literal; values
#: "seq-1"/"seq" resolve against the seq width in budget_args)
HOP_BUDGETS = {
    # warm prefill/decode step under the seq shard: per layer ONE
    # fresh-KV all-gather + (seq-1) ring ppermute hops; per program ONE
    # owner-logits psum (tied unembed adds no logits gather)
    "seq-step": {
        "axis": "seq",
        "per_layer": {"all_gather": 1, "ppermute": "seq-1"},
        "per_program": {"all_reduce": 1},
    },
    # the fused decode loop: ONE packed stat-combine all-gather per
    # layer per executed step, zero per-program collectives (every chip
    # computes identical merged logits)
    "seq-decode-loop": {
        "axis": "seq",
        "per_layer": {"all_gather": 1},
    },
    # the ownership-masked flush scatter is chip-local: zero comm
    "seq-flush": {
        "axis": "seq",
        "per_layer": {},
        "per_program": {},
    },
    # int8 pool: the ring doubles per hop (one int8 data ppermute + one
    # f32 scale-plane ppermute, the PR 6 quantized-collective shape)
    # while the fresh-KV exchange stays ONE compute-dtype all-gather
    "seq-step-int8": {
        "axis": "seq",
        "per_layer": {"ppermute@int8": "seq-1",
                      "ppermute@float32": "seq-1",
                      "all_gather@float32": 1},
        "per_program": {"all_reduce": 1},
    },
    # expert-parallel MoE serving (ISSUE 20): per MoE layer exactly TWO
    # all_to_all hops — routed-row dispatch + weighted-output combine
    # (sharded_moe.grouped_moe_ffn_ep_serve); attention/norms/lm_head
    # replicate on the ep-only mesh, so those are the ONLY collectives
    "ep-step": {
        "axis": "expert",
        "per_layer": {"all_to_all": 2},
        "per_program": {},
    },
    # same pipeline chunked over ep_comm_chunks slices: each of the two
    # logical hops splits into `chunks` runtime hops (chunk k's expert
    # GEMMs run under chunk k+1's exchange) — still 2 call SITES
    "ep-step-overlap": {
        "axis": "expert",
        "per_layer": {"all_to_all": "2*chunks"},
        "per_program": {},
    },
    # fused decode loop: the scan body carries the same 2 hops/MoE layer,
    # trip-weighted by the auditor (steps = n_steps)
    "ep-decode-loop": {
        "axis": "expert",
        "per_layer": {"all_to_all": 2},
    },
}

#: audited file -> builder qualname -> {collective kind: distinct
#: reachable call sites}. An empty file entry means "audited, zero
#: collectives allowed" (tp.py is shard planning only).
SITE_BUDGETS = {
    "deepspeed_tpu/inference/v2/model_runner.py": {
        "tp_all_reduce": {"psum": 1, "all_gather": 2},
        "tp_gather_logits": {"all_gather": 1},
        "_linear": {"psum": 1, "all_gather": 2},
        "_seq_paged_attention": {"all_gather": 1, "ppermute": 1},
        "_seq_dense_ring_attention": {"all_gather": 1},
        "paged_attention": {"all_gather": 2, "ppermute": 1},
        "RaggedRunnerBase._build_programs": {"psum": 1, "all_gather": 1},
        "_gpt2_ragged_step": {"psum": 1, "all_gather": 4, "ppermute": 1},
    },
    "deepspeed_tpu/inference/v2/seq_parallel.py": {
        "ring_all_gather": {"ppermute": 1},
        "combine_decode_stats": {"all_gather": 1},
    },
    "deepspeed_tpu/inference/v2/tp.py": {},
    "deepspeed_tpu/inference/v2/expert_parallel.py": {},
    "deepspeed_tpu/inference/v2/llama_runner.py": {
        # reaches the serve dispatch/combine pair in sharded_moe.py; the
        # Python chunk loop re-uses the SAME two sites at any chunks
        "_moe_mlp": {"all_to_all": 2},
    },
    "deepspeed_tpu/moe/sharded_moe.py": {
        # training EP layer: one shared a2a helper site (dispatch and
        # combine both trace through it)
        "grouped_moe_ffn_ep": {"all_to_all": 1},
        # serving EP pipeline: distinct dispatch + combine sites
        "grouped_moe_ffn_ep_serve": {"all_to_all": 2},
    },
    "deepspeed_tpu/parallel/ring_attention.py": {
        "ring_attention": {"ppermute": 6},
    },
}


def _resolve(value: Any, seq: int, chunks: int = 1) -> int:
    if value == "seq-1":
        return seq - 1
    if value == "seq":
        return seq
    if value == "2*chunks":
        return 2 * chunks
    return int(value)


def budget_args(name: str, *, num_layers: int, seq: int = 1,
                steps: int = 1, chunks: int = 1,
                label: Optional[str] = None) -> Dict[str, Any]:
    """Kwargs for ``CollectiveBudget(**...)`` from a HOP_BUDGETS entry,
    with the symbolic ``"seq-1"``/``"seq"`` values resolved against the
    live seq width and ``"2*chunks"`` against the EP overlap chunk
    count. ``label`` overrides the budget's display name."""
    spec = HOP_BUDGETS[name]
    return {
        "name": label or name,
        "num_layers": num_layers,
        "steps": steps,
        "axis": spec.get("axis", "model"),
        "per_layer": {k: _resolve(v, seq, chunks)
                      for k, v in spec.get("per_layer", {}).items()},
        "per_program": {k: _resolve(v, seq, chunks)
                        for k, v in spec.get("per_program", {}).items()},
    }
