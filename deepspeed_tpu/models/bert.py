"""BERT-style bidirectional encoder (flax.linen) with an MLM head.

Parity target: the reference's BERT pretraining headline workload
(BASELINE.md rows 1-2: BERT-large seq128/seq512 throughput) and its
BERT/DistilBERT inference containers (``module_inject/containers/bert.py``).
Post-LN encoder (original BERT), learned position + type embeddings, GELU
MLP, tied MLM decoder.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    max_seq_len: int = 512
    type_vocab_size: int = 2
    num_layers: int = 12
    num_heads: int = 12
    hidden_size: int = 768
    intermediate_size: int = 3072
    layer_norm_eps: float = 1e-12
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        return BertConfig(**kw)

    @staticmethod
    def bert_large(**kw):
        kw.setdefault("num_layers", 24)
        kw.setdefault("num_heads", 16)
        kw.setdefault("hidden_size", 1024)
        kw.setdefault("intermediate_size", 4096)
        return BertConfig(**kw)


class BertLayer(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, attention_mask=None):
        cfg = self.cfg
        B, T, C = x.shape
        H, D = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        dense = lambda feats, name: nn.Dense(
            feats, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)
        q = dense(C, "query")(x).reshape(B, T, H, D)
        k = dense(C, "key")(x).reshape(B, T, H, D)
        v = dense(C, "value")(x).reshape(B, T, H, D)
        mask = None
        if attention_mask is not None:        # [B, T] 1=keep
            mask = attention_mask[:, None, None, :].astype(bool)
        y = jax.nn.dot_product_attention(q, k, v, mask=mask)
        y = dense(C, "attn_out")(y.reshape(B, T, C))
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="attn_norm")(x + y)
        h = nn.gelu(dense(cfg.intermediate_size, "intermediate")(x),
                    approximate=False)
        h = dense(C, "output")(h)
        return nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                            name="out_norm")(x + h)


class Bert(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, tokens, token_type_ids=None, attention_mask=None):
        cfg = self.cfg
        B, T = tokens.shape
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="word_embeddings")
        wpe = nn.Embed(cfg.max_seq_len, cfg.hidden_size, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="position_embeddings")
        x = wte(tokens) + wpe(jnp.arange(T)[None, :])
        if cfg.type_vocab_size > 0:       # distilbert has no token types
            wtt = nn.Embed(cfg.type_vocab_size, cfg.hidden_size,
                           dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                           name="token_type_embeddings")
            if token_type_ids is None:
                token_type_ids = jnp.zeros_like(tokens)
            x = x + wtt(token_type_ids)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="embed_norm")(x)
        from ._lm_utils import layer_class
        for i in range(cfg.num_layers):
            x = layer_class(self, BertLayer, f"layer_{i}",
                            cfg.remat)(cfg, name=f"layer_{i}")(x, attention_mask)
        # MLM head: transform + tied decoder
        x = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="mlm_transform")(x)
        x = nn.gelu(x, approximate=False)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="mlm_norm")(x)
        logits = wte.attend(x.astype(jnp.float32))
        bias = self.param("mlm_bias", nn.initializers.zeros,
                          (cfg.vocab_size,), jnp.float32)
        return logits + bias


def make_model(cfg: BertConfig, mask_token_id: int = 103,
               mask_prob: float = 0.15):
    """(model, init_fn, loss_fn): MLM loss over randomly masked positions
    (batch = {"tokens": [B, T] int32}; masking drawn from the step rng)."""
    model = Bert(cfg)

    def init_fn(rng, batch_size: int = 2, seq_len: Optional[int] = None):
        T = seq_len or min(cfg.max_seq_len, 64)
        return model.init(rng, jnp.zeros((batch_size, T), jnp.int32))["params"]

    def loss_fn(params, batch, rng):
        tokens = batch["tokens"]
        mask = jax.random.bernoulli(rng, mask_prob, tokens.shape)
        inputs = jnp.where(mask, mask_token_id, tokens)
        logits = model.apply({"params": params}, inputs)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
        denom = jnp.maximum(mask.sum(), 1)
        return jnp.where(mask, nll, 0.0).sum() / denom

    return model, init_fn, loss_fn
