"""Phi (phi-1.5/phi-2) causal transformer (flax.linen).

Parity target: the reference's v2 inference Phi containers
(``inference/v2/model_implementations/phi/``): parallel attention+MLP over
one shared LayerNorm, PARTIAL rotary embedding (``rotary_dim`` < head_dim —
only the leading slice rotates), biased projections, GELU MLP, untied LM
head with bias. Phi-3 is llama-architecture and maps to
:mod:`deepspeed_tpu.models.llama` via the registry.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from .llama import apply_rope


@dataclasses.dataclass(frozen=True)
class PhiConfig:
    vocab_size: int = 51200
    max_seq_len: int = 2048
    num_layers: int = 24
    num_heads: int = 32
    hidden_size: int = 2048
    intermediate_size: int = 8192
    rotary_fraction: float = 0.5        # partial_rotary_factor
    rope_theta: float = 10000.0
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def rotary_dim(self) -> int:
        d = int(self.head_dim * self.rotary_fraction)
        return d - d % 2

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        return PhiConfig(**kw)


def apply_partial_rope(x, positions, theta, rotary_dim):
    rot, keep = x[..., :rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([apply_rope(rot, positions, theta), keep], axis=-1)


class PhiAttention(nn.Module):
    cfg: PhiConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        H, D = cfg.num_heads, cfg.head_dim
        dense = lambda feats, name: nn.Dense(
            feats, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            use_bias=True, name=name)
        q = dense(C, "q_proj")(x).reshape(B, T, H, D)
        k = dense(C, "k_proj")(x).reshape(B, T, H, D)
        v = dense(C, "v_proj")(x).reshape(B, T, H, D)
        pos = jnp.arange(T)[None, :]
        q = apply_partial_rope(q, pos, cfg.rope_theta, cfg.rotary_dim)
        k = apply_partial_rope(k, pos, cfg.rope_theta, cfg.rotary_dim)
        y = jax.nn.dot_product_attention(q, k, v, is_causal=True)
        return dense(C, "dense")(y.reshape(B, T, C))


class PhiBlock(nn.Module):
    cfg: PhiConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype,
                         name="input_layernorm")(x)
        attn = PhiAttention(cfg, name="self_attn")(h)
        mlp = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="fc1")(h)
        mlp = nn.gelu(mlp)
        mlp = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="fc2")(mlp)
        return x + attn + mlp                     # parallel residual


class Phi(nn.Module):
    cfg: PhiConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed_tokens")(tokens)
        from ._lm_utils import constrain_activations
        x = constrain_activations(x)
        from ._lm_utils import layer_class
        for i in range(cfg.num_layers):
            x = layer_class(self, PhiBlock, f"layer_{i}",
                            cfg.remat)(cfg, name=f"layer_{i}")(x)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         param_dtype=cfg.param_dtype,
                         name="final_layernorm")(x)
        return nn.Dense(cfg.vocab_size, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype, use_bias=True,
                        name="lm_head")(x.astype(jnp.float32))


def make_model(cfg: PhiConfig):
    from ._lm_utils import make_causal_lm
    return make_causal_lm(Phi(cfg), cfg)
