"""Plain reference of the GPT-2 decoder: forward pass and next-token loss.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: dense causal attention, the
whole ``[T, vocab]`` logits, an unchunked cross-entropy; no kernels, no
remat, no cache. Follows Radford et al. 2019 (pre-LN blocks, learned
positions, tanh-approximated GELU, tied unembedding). It reads the
parameter tree ``models/gpt2.py`` trains (``wte``, ``wpe``, ``h_<i>``,
``ln_f``) and upcasts each weight where it is used, so the tree may stay
in the dtype it is trained in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32) \
        + p["bias"].astype(F32)


def _dense(x, p):
    return x @ p["kernel"].astype(F32) + p["bias"].astype(F32)


def loss(params, tokens, num_heads: int, eps: float = 1e-5):
    """Mean next-token cross-entropy of ``tokens`` [B, T+1] (int32)."""
    with jax.default_matmul_precision("highest"):
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        B, T = inputs.shape
        x = params["wte"]["embedding"].astype(F32)[inputs] \
            + params["wpe"]["embedding"].astype(F32)[jnp.arange(T)][None]
        C = x.shape[-1]
        D = C // num_heads
        causal = jnp.tril(jnp.ones((T, T), bool))
        n_layers = sum(1 for k in params if k.startswith("h_"))
        for i in range(n_layers):
            p = params[f"h_{i}"]
            h = _ln(x, p["ln_1"], eps)
            q, k, v = jnp.split(_dense(h, p["attn"]["c_attn"]), 3, axis=-1)
            q, k, v = (a.reshape(B, T, num_heads, D) for a in (q, k, v))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(D))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            y = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            x = x + _dense(y.reshape(B, T, C), p["attn"]["c_proj"])
            h = _ln(x, p["ln_2"], eps)
            h = jax.nn.gelu(_dense(h, p["mlp"]["c_fc"]), approximate=True)
            x = x + _dense(h, p["mlp"]["c_proj"])
        x = _ln(x, params["ln_f"], eps)
        logits = x @ params["wte"]["embedding"].astype(F32).T
        logp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        return -picked.mean()
