"""Falcon causal transformer (flax.linen).

Parity target: the reference's v2 inference Falcon containers
(``inference/v2/model_implementations/falcon/``): rotary attention with
multi-query (7B) or grouped-query + separate attn/mlp norms (40B
``new_decoder_architecture``), PARALLEL attention+MLP residual blocks,
bias-free projections, 4x GELU MLP, tied embeddings.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from .llama import apply_rope


@dataclasses.dataclass(frozen=True)
class FalconConfig:
    vocab_size: int = 65024
    max_seq_len: int = 2048
    num_layers: int = 32
    num_heads: int = 71
    num_kv_heads: int = 1              # MQA (falcon-7b); 8 on 40b
    hidden_size: int = 4544
    rope_theta: float = 10000.0
    alibi: bool = False                # falcon-rw family: ALiBi, no rotary
    layer_norm_eps: float = 1e-5
    parallel_attn: bool = True
    new_decoder_architecture: bool = False   # 40b: separate attn/mlp norms
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("num_kv_heads", 1)
        kw.setdefault("hidden_size", 64)
        return FalconConfig(**kw)


class FalconAttention(nn.Module):
    cfg: FalconConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dense = lambda feats, name: nn.Dense(
            feats, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            use_bias=False, name=name)
        q = dense(H * D, "q_proj")(x).reshape(B, T, H, D)
        k = dense(KV * D, "k_proj")(x).reshape(B, T, KV, D)
        v = dense(KV * D, "v_proj")(x).reshape(B, T, KV, D)
        bias = None
        if cfg.alibi:
            from ._lm_utils import alibi_bias
            bias = alibi_bias(H, T, T).astype(x.dtype)
        else:
            pos = jnp.arange(T)[None, :]
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)
        if KV != H:
            k = jnp.repeat(k, H // KV, axis=2)
            v = jnp.repeat(v, H // KV, axis=2)
        y = jax.nn.dot_product_attention(q, k, v, bias=bias, is_causal=True)
        return dense(C, "dense")(y.reshape(B, T, H * D))


class FalconMLP(nn.Module):
    cfg: FalconConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = nn.Dense(4 * cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, use_bias=False,
                     name="dense_h_to_4h")(x)
        h = nn.gelu(h)
        return nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, use_bias=False,
                        name="dense_4h_to_h")(h)


class FalconBlock(nn.Module):
    cfg: FalconConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        ln = lambda name: nn.LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        if cfg.new_decoder_architecture:
            attn_in = ln("ln_attn")(x)
            mlp_in = ln("ln_mlp")(x)
        else:
            attn_in = ln("input_layernorm")(x)
            mlp_in = attn_in if cfg.parallel_attn else None
        attn_out = FalconAttention(cfg, name="self_attention")(attn_in)
        if cfg.parallel_attn or cfg.new_decoder_architecture:
            return x + attn_out + FalconMLP(cfg, name="mlp")(mlp_in)
        x = x + attn_out
        return x + FalconMLP(cfg, name="mlp")(
            ln("post_attention_layernorm")(x))


class Falcon(nn.Module):
    cfg: FalconConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="word_embeddings")
        from ._lm_utils import constrain_activations
        x = constrain_activations(embed(tokens))
        from ._lm_utils import layer_class
        for i in range(cfg.num_layers):
            x = layer_class(self, FalconBlock, f"layer_{i}",
                            cfg.remat)(cfg, name=f"layer_{i}")(x)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         param_dtype=cfg.param_dtype, name="ln_f")(x)
        if cfg.tie_embeddings:
            return embed.attend(x.astype(jnp.float32))
        return nn.Dense(cfg.vocab_size, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype, use_bias=False,
                        name="lm_head")(x.astype(jnp.float32))


def make_model(cfg: FalconConfig):
    from ._lm_utils import make_causal_lm
    return make_causal_lm(Falcon(cfg), cfg)
