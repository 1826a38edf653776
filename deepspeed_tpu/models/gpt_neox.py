"""GPT-NeoX and GPT-J causal transformers (flax.linen).

Parity targets: the reference's v1-injection containers
``module_inject/containers/gptneox.py`` and ``gptj.py``:

  GPT-NeoX — partial rotary (``rotary_pct`` of head_dim, rotate-half
    convention), fused per-head-interleaved query_key_value, PARALLEL
    attn+mlp residual (``use_parallel_residual``) with separate
    input/post_attention layernorms, biased GELU MLP, untied ``embed_out``.
  GPT-J — partial rotary with the INTERLEAVED (even/odd pair) rotation
    convention, separate bias-free q/k/v/out projections, parallel residual
    sharing ONE layernorm, biased fc_in/fc_out MLP, untied biased lm_head.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from .llama import apply_rope, rope_frequencies
from .phi import apply_partial_rope


def apply_rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                           theta: float) -> jnp.ndarray:
    """GPT-J rotary convention: each (even, odd) lane PAIR rotates together
    (vs the rotate-half split llama/neox use)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # [D/2]
    ang = positions[..., None].astype(jnp.float32) * freqs   # [..., T, D/2]
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def apply_partial_rope_interleaved(x, positions, theta, rotary_dim):
    rot, keep = x[..., :rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate(
        [apply_rope_interleaved(rot, positions, theta), keep], axis=-1)


@dataclasses.dataclass(frozen=True)
class GPTNeoXConfig:
    vocab_size: int = 50432
    max_seq_len: int = 2048
    num_layers: int = 44
    num_heads: int = 64
    hidden_size: int = 6144
    intermediate_size: int = 24576
    rotary_pct: float = 0.25
    rope_theta: float = 10000.0
    layer_norm_eps: float = 1e-5
    use_parallel_residual: bool = True
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.rotary_pct)

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        return GPTNeoXConfig(**kw)


class GPTNeoXBlock(nn.Module):
    cfg: GPTNeoXConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        H, D = cfg.num_heads, cfg.head_dim
        ln = lambda name: nn.LayerNorm(  # noqa: E731
            epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            use_bias=True, name=name)

        attn_in = ln("input_layernorm")(x)
        q = dense(H * D, "q_proj")(attn_in).reshape(B, T, H, D)
        k = dense(H * D, "k_proj")(attn_in).reshape(B, T, H, D)
        v = dense(H * D, "v_proj")(attn_in).reshape(B, T, H, D)
        pos = jnp.arange(T)[None, :]
        q = apply_partial_rope(q, pos, cfg.rope_theta, cfg.rotary_dim)
        k = apply_partial_rope(k, pos, cfg.rope_theta, cfg.rotary_dim)
        y = jax.nn.dot_product_attention(q, k, v, is_causal=True)
        attn_out = dense(C, "dense")(y.reshape(B, T, C))

        def mlp(h):
            h = dense(cfg.intermediate_size, "dense_h_to_4h")(h)
            return dense(C, "dense_4h_to_h")(nn.gelu(h))

        if cfg.use_parallel_residual:
            return x + attn_out + mlp(ln("post_attention_layernorm")(x))
        x = x + attn_out
        return x + mlp(ln("post_attention_layernorm")(x))


class GPTNeoX(nn.Module):
    cfg: GPTNeoXConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed_in")
        from ._lm_utils import constrain_activations
        x = constrain_activations(embed(tokens))
        from ._lm_utils import layer_class
        for i in range(cfg.num_layers):
            x = layer_class(self, GPTNeoXBlock, f"layer_{i}",
                            cfg.remat)(cfg, name=f"layer_{i}")(x)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         param_dtype=cfg.param_dtype,
                         name="final_layer_norm")(x)
        if cfg.tie_embeddings:
            return embed.attend(x.astype(jnp.float32))
        return nn.Dense(cfg.vocab_size, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype, use_bias=False,
                        name="embed_out")(x.astype(jnp.float32))


@dataclasses.dataclass(frozen=True)
class GPTJConfig:
    vocab_size: int = 50400
    max_seq_len: int = 2048
    num_layers: int = 28
    num_heads: int = 16
    hidden_size: int = 4096
    intermediate_size: int = 16384
    rotary_dim: int = 64
    rope_theta: float = 10000.0
    layer_norm_eps: float = 1e-5
    tie_embeddings: bool = False
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
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("rotary_dim", 8)
        return GPTJConfig(**kw)


class GPTJBlock(nn.Module):
    cfg: GPTJConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        H, D = cfg.num_heads, cfg.head_dim
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="ln_1")(x)
        dense = lambda feats, name, bias: nn.Dense(  # noqa: E731
            feats, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            use_bias=bias, name=name)
        q = dense(H * D, "q_proj", False)(h).reshape(B, T, H, D)
        k = dense(H * D, "k_proj", False)(h).reshape(B, T, H, D)
        v = dense(H * D, "v_proj", False)(h).reshape(B, T, H, D)
        pos = jnp.arange(T)[None, :]
        q = apply_partial_rope_interleaved(q, pos, cfg.rope_theta,
                                           cfg.rotary_dim)
        k = apply_partial_rope_interleaved(k, pos, cfg.rope_theta,
                                           cfg.rotary_dim)
        y = jax.nn.dot_product_attention(q, k, v, is_causal=True)
        attn_out = dense(C, "out_proj", False)(y.reshape(B, T, C))
        # parallel residual sharing ln_1's output
        m = dense(cfg.intermediate_size, "fc_in", True)(h)
        m = dense(C, "fc_out", True)(nn.gelu(m))
        return x + attn_out + m


class GPTJ(nn.Module):
    cfg: GPTJConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="wte")
        x = embed(tokens)
        from ._lm_utils import layer_class
        for i in range(cfg.num_layers):
            x = layer_class(self, GPTJBlock, f"layer_{i}",
                            cfg.remat)(cfg, name=f"layer_{i}")(x)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         param_dtype=cfg.param_dtype, name="ln_f")(x)
        if cfg.tie_embeddings:
            return embed.attend(x.astype(jnp.float32))
        return nn.Dense(cfg.vocab_size, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype, use_bias=True,
                        name="lm_head")(x.astype(jnp.float32))


def make_model_neox(cfg: GPTNeoXConfig):
    from ._lm_utils import make_causal_lm
    return make_causal_lm(GPTNeoX(cfg), cfg)


def make_model_gptj(cfg: GPTJConfig):
    from ._lm_utils import make_causal_lm
    return make_causal_lm(GPTJ(cfg), cfg)
