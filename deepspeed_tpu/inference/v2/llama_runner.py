"""Ragged paged-KV runner for the Llama family (and Mixtral MoE).

Analogue of the reference's llama_v2 / mistral / mixtral v2 containers
(``inference/v2/model_implementations/{llama_v2,mistral,mixtral}/``): RoPE
applied at each token's absolute position, GQA KV stored at kv-head width,
SwiGLU MLP (or top-k routed MoE for Mixtral), RMSNorm, last-token logits.
Shares the fixed-shape RaggedBatch contract of ``model_runner.py``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ...models.llama import LlamaConfig, apply_rope
from ...models.mixtral import MixtralConfig
from .config import RaggedInferenceConfig
from .model_runner import (RaggedBatch, RaggedRunnerBase, paged_attention,
                           tp_all_reduce, woq_mm)


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return y * scale


class LlamaRaggedRunner(RaggedRunnerBase):
    """All runner plumbing (jitted step / greedy step / fused decode loop,
    WOQ dequant-in-jit) comes from RaggedRunnerBase; ``step_fn`` is bound at
    the bottom of this module. Matmul sites dispatch through ``woq_mm``,
    so fused fp6 weights (quantized_weights.fused_gemm) stream through
    the Pallas 6-bit GEMM instead of a full dequant."""

    supports_fused_woq = True


def _moe_mlp(p_moe, h, cfg: MixtralConfig, dtype,
             icfg: RaggedInferenceConfig = None, valid=None):
    """Grouped-GEMM MoE for the ragged path: tokens sort by their routed
    expert and each expert multiplies only its rows via
    ``jax.lax.ragged_dot`` (sharded_moe.grouped_moe_ffn) — E/k x fewer
    FLOPs than the round-2 dense-every-expert path. Matches the
    reference's CUTLASS grouped GEMM
    (inference/v2/kernels/cutlass_ops/moe_gemm/).

    Inside an ``expert``-axis shard_map (``cfg.ep_size > 1`` engines)
    the routed rows instead travel the dispatch→grouped-GEMM→combine
    pipeline of ``grouped_moe_ffn_ep_serve``: router logits computed
    everywhere from the replicated gate, tokens exchanged to their
    experts' home chips and back with exactly TWO ``all_to_all`` hops
    per layer (chunked over ``icfg.ep_comm_chunks`` slices when
    ``ep_comm_overlap='chunked'`` so chunk k's expert GEMMs run under
    chunk k+1's exchange). ``p_moe`` then holds this chip's [E/ep, ...]
    expert stacks while the gate stays full-width.

    Returns (y [S, C, M], rows): with ``valid`` [S, C] given, ``rows``
    [E] int32 counts the routed rows of VALID positions per expert
    (padding positions are computed like the others and left out of the
    count only); None without it and on the expert-parallel path."""
    from ...moe.sharded_moe import grouped_moe_ffn
    from ...ops.kernels.fp6_gemm import Fp6GemmWeight, fp6_gemm_unpack
    from .expert_parallel import EP_AXIS, ep_axis_active
    S, C, M = h.shape
    gate_w = p_moe["gate"]
    if isinstance(gate_w, Fp6GemmWeight):
        # the router weight [hidden, E] is fused-packable (E % 4 == 0)
        # but tiny — unpack rather than kernel-dispatch the [*, E] GEMV
        gate_w = fp6_gemm_unpack(gate_w)
    logits = h.astype(jnp.float32).reshape(S * C, M) @ gate_w
    if "wi_gate" in p_moe:                                    # SwiGLU experts
        weights = (p_moe["wi_gate"], p_moe["wi_up"], p_moe["wo"])
    else:
        weights = (p_moe["wi"], p_moe["wo"])
    norm = getattr(cfg, "norm_topk_prob", True)
    if ep_axis_active():
        from ...moe.sharded_moe import (ep_serve_capacity,
                                        grouped_moe_ffn_ep_serve)
        ep = jax.lax.axis_size(EP_AXIS)
        chunks = int(icfg.ep_comm_chunks) \
            if icfg is not None and icfg.ep_comm_overlap == "chunked" else 1
        factor = float(icfg.ep_capacity_factor) if icfg is not None else 2.0
        cap = ep_serve_capacity(S * C, cfg.experts_top_k, ep, factor,
                                chunks)
        y, _ = grouped_moe_ffn_ep_serve(
            h.reshape(S * C, M), logits, cfg.experts_top_k, weights,
            jax.nn.silu, dtype, EP_AXIS, cfg.num_experts, cap,
            normalize_weights=norm, chunks=chunks)
        return y.reshape(S, C, M), None
    y, _ = grouped_moe_ffn(
        h.reshape(S * C, M), logits, cfg.experts_top_k, weights,
        jax.nn.silu, dtype, normalize_weights=norm)
    rows = None
    if valid is not None:
        # the same top-k the grouped path takes of the same logits
        _, top_idx = jax.lax.top_k(logits, cfg.experts_top_k)
        rows = jnp.zeros((cfg.num_experts,), jnp.int32).at[top_idx].add(
            valid.reshape(S * C, 1).astype(jnp.int32))
    return y.reshape(S, C, M), rows


def _llama_ragged_step(params, kv, batch: RaggedBatch, *,
                       model_cfg: LlamaConfig, cfg: RaggedInferenceConfig,
                       dtype):
    S, C = batch.tokens.shape
    H = model_cfg.num_heads
    KV = model_cfg.num_kv_heads
    D = model_cfg.head_dim
    scale = 1.0 / (D ** 0.5)
    is_moe = isinstance(model_cfg, MixtralConfig)

    pos = batch.start_pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    valid_q = jnp.arange(C, dtype=jnp.int32)[None, :] < batch.n_tokens[:, None]

    x = params["embed"]["embedding"][batch.tokens].astype(dtype)

    for li in range(model_cfg.num_layers):
        p = params[f"layer_{li}"]
        h = _rms(x, p["input_norm"]["scale"],
                 model_cfg.rms_eps).astype(dtype)
        pa = p["attn"]
        q = woq_mm(h, pa["q_proj"]["kernel"], dtype)
        k = woq_mm(h, pa["k_proj"]["kernel"], dtype)
        v = woq_mm(h, pa["v_proj"]["kernel"], dtype)
        if model_cfg.qkv_bias:
            q = q + pa["q_proj"]["bias"].astype(dtype)
            k = k + pa["k_proj"]["bias"].astype(dtype)
            v = v + pa["v_proj"]["bias"].astype(dtype)
        if model_cfg.qk_norm:
            q = _rms(q, pa["q_norm"]["scale"], model_cfg.rms_eps).astype(dtype)
            k = _rms(k, pa["k_norm"]["scale"], model_cfg.rms_eps).astype(dtype)
        q = q.reshape(S, C, H, D)
        k = k.reshape(S, C, KV, D)
        v = v.reshape(S, C, KV, D)
        q = apply_rope(q, pos, model_cfg.rope_theta)
        k = apply_rope(k, pos, model_cfg.rope_theta)

        kv, y = paged_attention(kv, li, q, k, v, batch, cfg, pos, valid_q,
                                scale, dtype,
                                sliding_window=model_cfg.sliding_window)
        y = woq_mm(y, pa["o_proj"]["kernel"], dtype)
        y = tp_all_reduce(y, cfg)           # TP collective 1 (row-parallel)
        x = x + y

        h = _rms(x, p["post_attn_norm"]["scale"],
                 model_cfg.rms_eps).astype(dtype)
        if is_moe:
            # the fused decode loop's kv carries a count of routed rows
            counted = getattr(kv, "moe_rows", None) is not None
            y, rows = _moe_mlp(p["moe"], h, model_cfg, dtype, cfg,
                               valid=valid_q if counted else None)
            if rows is not None:
                kv = kv._replace(moe_rows=kv.moe_rows + rows)
            if getattr(model_cfg, "shared_expert_size", 0):
                # qwen2-moe always-on shared expert (sigmoid scalar gate)
                gate = woq_mm(h, p["shared_gate_proj"]["kernel"], dtype)
                up = woq_mm(h, p["shared_up_proj"]["kernel"], dtype)
                shared = woq_mm(jax.nn.silu(gate) * up,
                                p["shared_down_proj"]["kernel"], dtype)
                sg = jax.nn.sigmoid(
                    (h @ p["shared_expert_gate"]["kernel"].astype(dtype)
                     ).astype(jnp.float32))
                y = y + shared * sg.astype(dtype)
            x = x + y
        else:
            pm = p["mlp"]
            gate = woq_mm(h, pm["gate_proj"]["kernel"], dtype)
            up = woq_mm(h, pm["up_proj"]["kernel"], dtype)
            m = jax.nn.silu(gate) * up
            m = woq_mm(m, pm["down_proj"]["kernel"], dtype)
            x = x + tp_all_reduce(m, cfg)   # TP collective 2 (row-parallel)

    x = _rms(x, params["final_norm"]["scale"], model_cfg.rms_eps)
    last = jnp.maximum(batch.n_tokens - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    from ...ops.kernels.fp6_gemm import Fp6GemmWeight
    if model_cfg.tie_embeddings:
        # embedding tables are never fused-packed (the quantizer's
        # structural exclusion — the token gather needs a dense array)
        w_out = params["embed"]["embedding"].T
    else:
        w_out = params["lm_head"]["kernel"]
        if isinstance(w_out, Fp6GemmWeight):
            return woq_mm(x_last.astype(jnp.float32), w_out,
                          jnp.float32), kv
    logits = x_last.astype(jnp.float32) @ w_out.astype(jnp.float32)
    return logits, kv


LlamaRaggedRunner.step_fn = staticmethod(_llama_ragged_step)
