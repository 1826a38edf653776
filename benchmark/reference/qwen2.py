"""Plain reference of the Qwen2 decoder: logits of a whole sequence.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: RMSNorm, q/k/v projections
with bias, rotary positions (rotate-half, theta from the config),
grouped-query dense causal attention, SwiGLU, tied or untied unembedding;
no kernels, no cache, no batching tricks. Follows the Qwen2 technical
report (arXiv:2407.10671) and the ``config.json`` named in the
configuration file. It reads the parameter tree ``models/llama.py``
defines and upcasts each weight where it is used.
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


def _proj(x, p):
    y = x @ p["kernel"].astype(F32)
    return y + p["bias"].astype(F32) if "bias" in p else y


def logits(params, tokens, at, *, num_heads: int, num_kv_heads: int,
           rope_theta: float, rms_eps: float, tie_embeddings: bool):
    """Logits [B, n, vocab] at the positions ``at`` [B, n] of ``tokens``
    [B, T] (tokens to the right of a position never reach it)."""
    with jax.default_matmul_precision("highest"):
        B, T = tokens.shape
        x = params["embed"]["embedding"].astype(F32)[tokens]
        C = x.shape[-1]
        D = C // num_heads
        rep = num_heads // num_kv_heads
        causal = jnp.tril(jnp.ones((T, T), bool))
        n_layers = sum(1 for k in params if k.startswith("layer_"))
        for i in range(n_layers):
            p = params[f"layer_{i}"]
            h = _rms(x, p["input_norm"]["scale"], rms_eps)
            q = _proj(h, p["attn"]["q_proj"]).reshape(B, T, num_heads, D)
            k = _proj(h, p["attn"]["k_proj"]).reshape(B, T, num_kv_heads, D)
            v = _proj(h, p["attn"]["v_proj"]).reshape(B, T, num_kv_heads, D)
            q, k = _rope(q, rope_theta), _rope(k, rope_theta)
            k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(D))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            y = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            x = x + _proj(y.reshape(B, T, C), p["attn"]["o_proj"])
            h = _rms(x, p["post_attn_norm"]["scale"], rms_eps)
            m = jax.nn.silu(_proj(h, p["mlp"]["gate_proj"])) \
                * _proj(h, p["mlp"]["up_proj"])
            x = x + _proj(m, p["mlp"]["down_proj"])
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        x = _rms(x, params["final_norm"]["scale"], rms_eps)
        w = params["embed"]["embedding"].astype(F32).T if tie_embeddings \
            else params["lm_head"]["kernel"].astype(F32)
        return x @ w
