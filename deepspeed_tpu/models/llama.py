"""Llama-family causal transformer (flax.linen).

Covers the reference's v2 inference model zoo members that share this block
structure — llama_v2, llama_v3, mistral, qwen2 (``inference/v2/
model_implementations/{llama_v2,mistral,qwen_v2}/``) — via config:
RMSNorm, RoPE, GQA attention, SwiGLU MLP, optional sliding-window mask
(mistral), optional qkv bias (qwen2), optional projection-wide QK-norm
(olmoe), untied LM head.

TPU-first: bf16 compute / f32 params, MXU-shaped projections, optional remat
per block; stable param names so TP rules and the ragged runner can address
q/k/v/o and gate/up/down projections.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32            # < num_heads => GQA
    hidden_size: int = 4096
    intermediate_size: int = 11008
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    sliding_window: Optional[int] = None   # mistral local attention
    qkv_bias: bool = False                 # qwen2
    # True (olmoe): RMSNorm over the WHOLE q and k projections (one learned
    # scale of the projection's width, not per head), before the split
    # into heads and before RoPE. "head" (mellum; the qwen3 convention):
    # one RMSNorm over each head's own lanes, one learned scale of the
    # head's width shared by the heads, after the split and before RoPE
    qk_norm: Any = False
    tie_embeddings: bool = False
    # LM-head cross-entropy knobs (models/_lm_utils.lm_head_xent):
    # "chunked" scan or the streaming "fused" Pallas kernel
    xent_impl: str = "chunked"
    xent_chunks: int = 8
    xent_remat: bool = True
    xent_ignore_index: Optional[int] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("num_kv_heads", 2)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        return LlamaConfig(**kw)

    @staticmethod
    def llama2_7b(**kw):
        return LlamaConfig(**kw)

    @staticmethod
    def llama3_8b(**kw):
        kw.setdefault("vocab_size", 128256)
        kw.setdefault("max_seq_len", 8192)
        kw.setdefault("num_kv_heads", 8)
        kw.setdefault("intermediate_size", 14336)
        kw.setdefault("rope_theta", 500000.0)
        return LlamaConfig(**kw)

    @staticmethod
    def mistral_7b(**kw):
        kw.setdefault("vocab_size", 32000)
        kw.setdefault("max_seq_len", 8192)
        kw.setdefault("num_kv_heads", 8)
        kw.setdefault("intermediate_size", 14336)
        kw.setdefault("sliding_window", 4096)
        return LlamaConfig(**kw)


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies for rotary embedding, shape [head_dim // 2]."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def yarn_frequencies(head_dim: int, theta: float, factor: float,
                     original_max: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0):
    """YaRN's inverse frequencies [head_dim // 2], float32, as
    ``transformers`` computes them (``truncate`` at its default): a
    frequency that turns more than ``beta_fast`` times over the
    ``original_max`` positions is kept, one that turns less than
    ``beta_slow`` times is divided by ``factor``, and a linear ramp over
    the frequency's INDEX blends the two between. Built with numpy, once,
    at trace time: a table of the program, not a branch of the step."""
    import math
    import numpy as np

    def dim_of(turns):
        return head_dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    i = np.arange(head_dim // 2, dtype=np.float32)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    plain = (1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                             / head_dim)).astype(np.float32)
    return ((1.0 - ramp) * plain + ramp * plain / factor).astype(np.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               inv_freq=None, scale=None) -> jnp.ndarray:
    """Rotary position embedding. x: [..., T, H, D]; positions: [..., T].
    ``inv_freq`` [D/2] stands in for ``theta``'s own frequencies (a scaled
    code's table, ``yarn_frequencies``) and ``scale`` multiplies cos and
    sin both (YaRN's ``attention_factor``)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta) if inv_freq is None \
        else jnp.asarray(inv_freq, jnp.float32)            # [D/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., T, D/2]
    cos = jnp.cos(ang)[..., None, :]                       # [..., T, 1, D/2]
    sin = jnp.sin(ang)[..., None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                       jnp.float32)
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + self.eps)
        return (y * w).astype(self.dtype)


class LlamaAttention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dense = lambda feats, name: nn.Dense(
            feats, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            use_bias=cfg.qkv_bias, name=name)
        q = dense(H * D, "q_proj")(x)
        k = dense(KV * D, "k_proj")(x)
        if cfg.qk_norm:
            q = RMSNorm(cfg.rms_eps, cfg.dtype, name="q_norm")(q)
            k = RMSNorm(cfg.rms_eps, cfg.dtype, name="k_norm")(k)
        q = q.reshape(B, T, H, D)
        k = k.reshape(B, T, KV, D)
        v = dense(KV * D, "v_proj")(x).reshape(B, T, KV, D)
        pos = jnp.arange(T)[None, :]
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)

        impl = cfg.attention_impl
        if impl == "auto":
            impl = ("flash" if jax.default_backend() == "tpu"
                    and jax.device_count() == 1 else "xla")
        if impl == "flash":
            from deepspeed_tpu.ops.kernels import flash_attention
            y = flash_attention(q, k, v, causal=True,
                                window=cfg.sliding_window, layout="BTHD")
        elif impl == "xla":
            if KV != H:
                k = jnp.repeat(k, H // KV, axis=2)
                v = jnp.repeat(v, H // KV, axis=2)
            mask = None
            if cfg.sliding_window is not None:
                i = jnp.arange(T)[:, None]
                j = jnp.arange(T)[None, :]
                mask = (j > i - cfg.sliding_window)[None, None]
            y = jax.nn.dot_product_attention(q, k, v, mask=mask,
                                             is_causal=True)
        else:
            raise ValueError(f"attention_impl must be 'auto', 'flash' or "
                             f"'xla', got {cfg.attention_impl!r}")
        y = y.reshape(B, T, H * D)
        return nn.Dense(C, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                        use_bias=False, name="o_proj")(y)


class LlamaMLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda feats, name: nn.Dense(
            feats, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            use_bias=False, name=name)
        gate = dense(cfg.intermediate_size, "gate_proj")(x)
        up = dense(cfg.intermediate_size, "up_proj")(x)
        return dense(cfg.hidden_size, "down_proj")(nn.silu(gate) * up)


class LlamaBlock(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        x = x + LlamaAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x))
        x = x + LlamaMLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x))
        return x


class Llama(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed")
        x = embed(tokens)
        from ._lm_utils import constrain_activations
        x = constrain_activations(x)
        from ._lm_utils import layer_class
        for i in range(cfg.num_layers):
            x = layer_class(self, LlamaBlock, f"layer_{i}",
                            cfg.remat)(cfg, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.rms_eps, jnp.float32, name="final_norm")(x)
        if return_hidden:
            # training loss path: the caller fuses the LM head into the
            # chunked/streaming cross-entropy instead of [B, T, V] logits
            return x
        if cfg.tie_embeddings:
            return embed.attend(x.astype(jnp.float32))
        head = nn.Dense(cfg.vocab_size, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype, use_bias=False,
                        name="lm_head")
        return head(x.astype(jnp.float32))


def make_model(cfg: LlamaConfig):
    """(model, init_fn, loss_fn) with the engine's ``(params, batch, rng)``
    loss contract — batch = {"tokens": [B, T+1] int32}."""
    model = Llama(cfg)

    def init_fn(rng, batch_size: int = 2, seq_len: Optional[int] = None):
        T = seq_len or min(cfg.max_seq_len, 64)
        return model.init(rng, jnp.zeros((batch_size, T), jnp.int32))["params"]

    def loss_fn(params, batch, rng):
        from ._lm_utils import lm_head_xent
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        # final_norm emits fp32; cast back to the compute dtype so the
        # unembed chunk/tile matmuls ride the bf16 MXU path (f32 accum
        # happens inside the xent implementations regardless)
        hidden = model.apply({"params": params}, inputs,
                             return_hidden=True).astype(cfg.dtype)
        if cfg.tie_embeddings:
            return lm_head_xent(hidden, params["embed"]["embedding"],
                                targets, cfg)
        # untied: the NATURAL [C, V] Dense kernel — the dispatch contracts
        # it directly (chunked) or transposes once per step (fused)
        return lm_head_xent(hidden, params["lm_head"]["kernel"], targets,
                            cfg, head_layout="cv")

    return model, init_fn, loss_fn
