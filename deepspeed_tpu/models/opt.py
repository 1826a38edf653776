"""OPT causal transformer (flax.linen).

Parity target: the reference's v2 inference OPT containers
(``inference/v2/model_implementations/opt/``) and v1 OPT injection policy
(``module_inject/containers/opt.py``): learned positional embeddings with
the OPT +2 offset, pre-LN decoder blocks, biased projections, ReLU MLP,
final LayerNorm, tied LM head by default.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OPTConfig:
    vocab_size: int = 50272
    max_seq_len: int = 2048
    num_layers: int = 12
    num_heads: int = 12
    hidden_size: int = 768
    ffn_dim: int = 3072
    layer_norm_eps: float = 1e-5
    do_layer_norm_before: bool = True      # False on opt-350m (post-LN)
    word_embed_proj_dim: Optional[int] = None   # opt-350m: 512 != hidden
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False

    #: OPT's learned positions start at index 2 (pad-token legacy)
    POSITION_OFFSET = 2

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
        kw.setdefault("ffn_dim", 128)
        return OPTConfig(**kw)


class OPTAttention(nn.Module):
    cfg: OPTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        H, D = cfg.num_heads, cfg.head_dim
        dense = lambda name: nn.Dense(
            C, dtype=cfg.dtype, param_dtype=cfg.param_dtype, use_bias=True,
            name=name)
        q = dense("q_proj")(x).reshape(B, T, H, D)
        k = dense("k_proj")(x).reshape(B, T, H, D)
        v = dense("v_proj")(x).reshape(B, T, H, D)
        y = jax.nn.dot_product_attention(q, k, v, is_causal=True)
        return dense("out_proj")(y.reshape(B, T, C))


class OPTBlock(nn.Module):
    cfg: OPTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        ln = lambda name: nn.LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        attn_ln = ln("self_attn_layer_norm")
        if cfg.do_layer_norm_before:                  # pre-LN (most OPTs)
            x = x + OPTAttention(cfg, name="self_attn")(attn_ln(x))
        else:                                          # post-LN (opt-350m)
            x = attn_ln(x + OPTAttention(cfg, name="self_attn")(x))
        mlp_ln = ln("final_layer_norm")
        h = mlp_ln(x) if cfg.do_layer_norm_before else x
        h = nn.Dense(cfg.ffn_dim, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="fc1")(h)
        h = nn.relu(h)
        h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="fc2")(h)
        x = x + h
        return x if cfg.do_layer_norm_before else mlp_ln(x)


class OPT(nn.Module):
    cfg: OPTConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        B, T = tokens.shape
        embed_dim = cfg.word_embed_proj_dim or cfg.hidden_size
        embed = nn.Embed(cfg.vocab_size, embed_dim, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed_tokens")
        pos = nn.Embed(cfg.max_seq_len + cfg.POSITION_OFFSET,
                       cfg.hidden_size, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="embed_positions")
        x = embed(tokens)
        if embed_dim != cfg.hidden_size:               # opt-350m project_in
            x = nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="project_in")(x)
        x = x + pos(jnp.arange(T) + cfg.POSITION_OFFSET)
        from ._lm_utils import constrain_activations
        x = constrain_activations(x)
        from ._lm_utils import layer_class
        for i in range(cfg.num_layers):
            x = layer_class(self, OPTBlock, f"layer_{i}",
                            cfg.remat)(cfg, name=f"layer_{i}")(x)
        if cfg.do_layer_norm_before:                   # post-LN has no final
            x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                             param_dtype=cfg.param_dtype,
                             name="final_layer_norm")(x)
        if embed_dim != cfg.hidden_size:               # opt-350m project_out
            x = nn.Dense(embed_dim, use_bias=False, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="project_out")(x)
        if cfg.tie_embeddings:
            return embed.attend(x.astype(jnp.float32))
        return nn.Dense(cfg.vocab_size, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype, use_bias=False,
                        name="lm_head")(x.astype(jnp.float32))


def make_model(cfg: OPTConfig):
    from ._lm_utils import make_causal_lm
    return make_causal_lm(OPT(cfg), cfg)
