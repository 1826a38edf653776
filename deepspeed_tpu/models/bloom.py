"""BLOOM causal transformer (flax.linen).

Parity target: the reference's BLOOM v1-injection container
(``module_inject/containers/bloom.py``, policy ``replace_policy.py``):
ALiBi attention (no positional embeddings), fused per-head-interleaved
query_key_value projection, embedding LayerNorm
(``word_embeddings_layernorm``), sequential pre-LN residual blocks, biased
GELU MLP, tied unembed.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ._lm_utils import alibi_bias


@dataclasses.dataclass(frozen=True)
class BloomConfig:
    vocab_size: int = 250880
    max_seq_len: int = 2048            # ALiBi: no hard positional limit
    num_layers: int = 30
    num_heads: int = 32
    hidden_size: int = 4096
    layer_norm_eps: float = 1e-5
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
        kw.setdefault("hidden_size", 64)
        return BloomConfig(**kw)


class BloomAttention(nn.Module):
    cfg: BloomConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        H, D = cfg.num_heads, cfg.head_dim
        # q/k/v as separate Dense params; the HF loader splits BLOOM's fused
        # per-head-interleaved query_key_value into these (hf_loader
        # _split_bloom_fused)
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            use_bias=True, name=name)
        q = dense(H * D, "q_proj")(x).reshape(B, T, H, D)
        k = dense(H * D, "k_proj")(x).reshape(B, T, H, D)
        v = dense(H * D, "v_proj")(x).reshape(B, T, H, D)
        bias = alibi_bias(H, T, T).astype(x.dtype)
        y = jax.nn.dot_product_attention(q, k, v, bias=bias, is_causal=True)
        return dense(C, "dense")(y.reshape(B, T, C))


class BloomBlock(nn.Module):
    cfg: BloomConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        ln = lambda name: nn.LayerNorm(  # noqa: E731
            epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        x = x + BloomAttention(cfg, name="self_attention")(
            ln("input_layernorm")(x))
        h = ln("post_attention_layernorm")(x)
        h = nn.Dense(4 * cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="dense_h_to_4h")(h)
        h = nn.gelu(h)
        h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="dense_4h_to_h")(h)
        return x + h


class Bloom(nn.Module):
    cfg: BloomConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="word_embeddings")
        from ._lm_utils import constrain_activations
        x = constrain_activations(embed(tokens))
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype,
                         name="word_embeddings_layernorm")(x)
        from ._lm_utils import layer_class
        for i in range(cfg.num_layers):
            x = layer_class(self, BloomBlock, f"layer_{i}",
                            cfg.remat)(cfg, name=f"layer_{i}")(x)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         param_dtype=cfg.param_dtype, name="ln_f")(x)
        if cfg.tie_embeddings:
            return embed.attend(x.astype(jnp.float32))
        return nn.Dense(cfg.vocab_size, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype, use_bias=False,
                        name="lm_head")(x.astype(jnp.float32))


def make_model(cfg: BloomConfig):
    from ._lm_utils import make_causal_lm
    return make_causal_lm(Bloom(cfg), cfg)
