"""Plain reference of the OLMoE decoder: logits of a whole sequence.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, written from the published
modelling code of ``model_type: olmoe`` (the ``config.json`` named in the
configuration file; OLMoE, arXiv:2409.02060). A layer is

    h = rms(x, input_norm)
    q = rms(h Wq, q_norm);  k = rms(h Wk, k_norm);  v = h Wv
        (each norm over the WHOLE projection with one learned scale of its
        width, before the split into heads and before the rotary positions)
    x = x + causal_attention(rope(q), rope(k), v, scale 1/sqrt(head)) Wo
    h = rms(x, post_attn_norm)
    p = softmax(h Wrouter) over ALL experts; the k largest p are kept AS
        THEY ARE (``norm_topk_prob`` false: no renormalisation)
    x = x + sum_e p_e Wdown_e (silu(Wgate_e h) * Wup_e h)

then a final RMSNorm and an untied output head. The experts are a plain
loop: every token goes through every expert and the result is masked by
the top-k set, one expert's weights upcast to float32 at a time (the
8-layer tree of the benchmark is 7.1 GB in bfloat16 and is never cast
whole). No grouped matmul, no cache, no batching tricks; nothing of the
program under test is imported. ``clip_qkv`` is null in the published
config and is not implemented. Departures from the published
description: none.

It reads the parameter tree ``models/mixtral.py`` defines.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta):
    """x: [B, T, H, D]; positions 0..T-1; halves rotated as a pair."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]       # [T, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, h, *, num_heads, num_kv_heads, rope_theta, rms_eps):
    B, T, C = h.shape
    D = C // num_heads
    q = _rms(h @ p["q_proj"]["kernel"].astype(F32), p["q_norm"]["scale"],
             rms_eps).reshape(B, T, num_heads, D)
    k = _rms(h @ p["k_proj"]["kernel"].astype(F32), p["k_norm"]["scale"],
             rms_eps).reshape(B, T, num_kv_heads, D)
    v = (h @ p["v_proj"]["kernel"].astype(F32)).reshape(B, T, num_kv_heads,
                                                        D)
    q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    rep = num_heads // num_kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(D))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    y = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return y.reshape(B, T, C) @ p["o_proj"]["kernel"].astype(F32)


def _sparse_mlp(p, h, top_k: int):
    """Every expert on every token, masked by the top-k set."""
    probs = jax.nn.softmax(h @ p["gate"].astype(F32), axis=-1)   # [B, T, E]
    kth = jnp.sort(probs, axis=-1)[..., -top_k][..., None]
    keep = jnp.where(probs >= kth, probs, 0.0)

    def one_expert(acc, expert):
        w_gate, w_up, w_down, weight = expert
        out = (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) \
            @ w_down.astype(F32)
        return acc + weight[..., None] * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (p["wi_gate"], p["wi_up"], p["wo"],
                         jnp.moveaxis(keep, -1, 0)))
    return y


def hidden_states(params, tokens, *, num_heads: int, num_kv_heads: int,
                  top_k: int, rope_theta: float, rms_eps: float,
                  layers: int = None):
    """The residual stream [B, T, C] after ``layers`` whole layers (all
    of them when None)."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"].astype(F32)[tokens]
        n_layers = sum(1 for k in params if k.startswith("layer_"))
        for i in range(n_layers if layers is None else layers):
            p = params[f"layer_{i}"]
            x = x + _attention(
                p["attn"], _rms(x, p["input_norm"]["scale"], rms_eps),
                num_heads=num_heads, num_kv_heads=num_kv_heads,
                rope_theta=rope_theta, rms_eps=rms_eps)
            x = x + _sparse_mlp(
                p["moe"], _rms(x, p["post_attn_norm"]["scale"], rms_eps),
                top_k)
        return x


def logits(params, tokens, at, *, num_heads: int, num_kv_heads: int,
           top_k: int, rope_theta: float, rms_eps: float):
    """Logits [B, n, vocab] at the positions ``at`` [B, n] of ``tokens``
    [B, T] (tokens to the right of a position never reach it)."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, num_heads=num_heads,
                          num_kv_heads=num_kv_heads, top_k=top_k,
                          rope_theta=rope_theta, rms_eps=rms_eps)
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        x = _rms(x, params["final_norm"]["scale"], rms_eps)
        return x @ params["lm_head"]["kernel"].astype(F32)
