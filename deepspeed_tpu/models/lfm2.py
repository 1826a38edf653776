"""LFM2 family (``model_type: lfm2`` and its sparse sibling ``lfm2_moe``):
a decoder whose mixers are gated SHORT CONVOLUTIONS, three to one softmax
layer by ``layer_types``, and whose feed-forward is dense on the leading
``num_dense_layers`` layers and sparse after them (``lfm2_moe``; the dense
``lfm2`` has no sparse layer and is the same code).

* block, pre-norm with one residual stream and two branches, no bias
  anywhere: ``x += Mixer_l(RMSNorm(x)); x += FFN_l(RMSNorm(x))``; a final
  RMSNorm (the family's ``embedding_norm``) and a head TIED to the
  embedding.
* ``"conv"`` layers: ``B | C | u = W_in h`` (one projection to three times
  the hidden size, in that order), ``v = B * u``, a depthwise causal
  convolution of ``conv_taps`` taps over ``v`` along the sequence (zero
  before its start; no bias and NO activation), ``y = W_out (C * conv)``.
  Across calls a sequence carries its last ``conv_taps - 1`` values of
  ``v`` and NOTHING else: there is no matrix state.
* ``"attn"`` layers: GQA (``num_heads`` query heads over ``num_kv_heads``),
  ``qk_norm == "head"`` (one RMSNorm over each head's own ``head_dim``
  lanes of q and of k, one learned scale shared by the heads, after the
  split and before the rotary code), rotate-half over all ``head_dim``
  lanes, scores times ``head_dim ** -0.5``, causal.
* feed-forward, by ``ffn_kinds``: ``"dense"`` SwiGLU of width
  ``dense_intermediate_size`` or ``"moe"``: sigmoid scores over all
  ``num_experts`` in float32, the ``experts_top_k`` largest of ``score +
  sel_bias`` taken (the bias in the selection only), their scores
  renormalised as ``w / (sum w + router_norm_eps)`` and scaled by
  ``routed_scaling``; SwiGLU experts of width ``intermediate_size``; no
  shared expert.

``experts_held`` < ``num_experts`` is one chip's share of a layer
(``models/solar_open2.py`` says how; ``SolarSparseBlock`` is that
module's, shared, as ``DenseMLP`` is ``models/pangu_ultra_moe.py``'s).

Serving keeps a ``"conv"`` layer's carried inputs in the convolution part
of the state pool, which for this family has no state part
(``inference/v2/kv_cache.py``); the flax module is the definition of the
tree the ragged runner serves, and its forward runs the convolution over
the whole sequence, dense attention and every held expert densely, for
small sizes (tests, shape inference).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ._lm_utils import make_causal_lm
from .llama import RMSNorm, apply_rope
from .mixtral import MixtralConfig
from .pangu_ultra_moe import DenseMLP, _dense
from .solar_open2 import SolarSparseBlock, short_conv

#: ``layer_types`` -> the runner's mixer kind
LAYER_TYPES = {"conv": "conv", "full_attention": "attn"}


@dataclasses.dataclass(frozen=True)
class Lfm2Config(MixtralConfig):
    attn_head_dim: int = 64
    #: "conv" or "attn", a layer
    layer_kinds: Tuple[str, ...] = ()
    #: "dense" or "moe", a layer
    ffn_kinds: Tuple[str, ...] = ()
    conv_taps: int = 3                   # conv_L_cache
    dense_intermediate_size: int = 11776
    qk_norm: str = "head"
    tie_embeddings: bool = True
    norm_topk_prob: bool = True
    router_score: str = "sigmoid"
    router_bias: bool = True             # use_expert_bias
    routed_scaling: float = 1.0
    #: what the renormalisation adds to the chosen scores' sum
    router_norm_eps: float = 1e-6
    experts_held: Optional[int] = None   # None = all of them
    experts_first: int = 0

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 256)
        kw.setdefault("num_layers", 5)
        kw.setdefault("num_heads", 4)
        kw.setdefault("num_kv_heads", 2)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("attn_head_dim", 16)
        kw.setdefault("intermediate_size", 32)
        kw.setdefault("dense_intermediate_size", 96)
        kw.setdefault("num_experts", 8)
        kw.setdefault("experts_top_k", 2)
        kw.setdefault("rope_theta", 1e6)
        n = kw["num_layers"]
        kw.setdefault("layer_kinds", tuple(
            "attn" if i % 4 == 1 else "conv" for i in range(n)))
        kw.setdefault("ffn_kinds", ("dense",) + ("moe",) * (n - 1))
        return Lfm2Config(**kw)


def param_counts(cfg: Lfm2Config) -> Tuple[int, int]:
    """(parameters of the model as configured, parameters one token
    passes through): the embedding once (the head is tied), the mixers,
    the dense layers, the routers, and of the experts all that are held
    against ``experts_top_k``."""
    M, D = cfg.hidden_size, cfg.head_dim
    expert = 3 * M * cfg.intermediate_size
    mixer = {"conv": 4 * M * M + cfg.conv_taps * M,
             "attn": 2 * M * D * (cfg.num_heads + cfg.num_kv_heads) + 2 * D}
    fixed = cfg.vocab_size * M + M
    n_moe = 0
    for kind, ffn in zip(cfg.layer_kinds, cfg.ffn_kinds):
        fixed += mixer[kind] + 2 * M
        if ffn == "dense":
            fixed += 3 * M * cfg.dense_intermediate_size
        else:
            n_moe += 1
            fixed += M * cfg.num_experts \
                + (cfg.num_experts if cfg.router_bias else 0)
    return (fixed + n_moe * cfg.held * expert,
            fixed + n_moe * cfg.experts_top_k * expert)


def gated_conv_inputs(p, h, dtype):
    """What the short convolution of a conv layer takes and what gates
    its output: (v = B * u [B, T, M] float32, C [B, T, M] float32, w
    [K, M] float32 the taps). The one projection takes ``dtype`` operands
    and gives float32; the gate's product is not rounded on its way into
    the convolution."""
    f32 = jnp.float32
    bcu = jnp.matmul(h, p["in_proj"].astype(dtype),
                     preferred_element_type=f32)
    b, c, u = jnp.split(bcu, 3, axis=-1)
    return b * u, c, p["conv_w"].astype(f32)


def gated_conv_output(p, c, y, dtype):
    """``W_out (C * conv)``: the convolution's output y [B, T, M] float32
    under its gate, rounded to ``dtype`` for the output projection."""
    return (c * y).astype(dtype) @ p["out_proj"].astype(dtype)


class GatedConvMixer(nn.Module):
    cfg: Lfm2Config

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        B, T, M = h.shape
        K = cfg.conv_taps
        kern = lambda name, shape: self.param(             # noqa: E731
            name, nn.initializers.lecun_normal(), shape, cfg.param_dtype)
        p = {"in_proj": kern("in_proj", (M, 3 * M)),
             "out_proj": kern("out_proj", (M, M)),
             "conv_w": self.param("conv_w", nn.initializers.normal(K ** -0.5),
                                  (K, M), cfg.param_dtype)}
        v, c, w = gated_conv_inputs(p, h.astype(cfg.dtype), cfg.dtype)
        y, _ = short_conv(v, w, jnp.zeros((B, K - 1, M), jnp.float32))
        return gated_conv_output(p, c, y, cfg.dtype)


class Lfm2Attention(nn.Module):
    cfg: Lfm2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, M = x.shape
        H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = _dense(cfg, H * D, "q_proj")(x).reshape(B, T, H, D)
        k = _dense(cfg, KV * D, "k_proj")(x).reshape(B, T, KV, D)
        v = _dense(cfg, KV * D, "v_proj")(x).reshape(B, T, KV, D)
        q = RMSNorm(cfg.rms_eps, cfg.dtype, name="q_norm")(q)
        k = RMSNorm(cfg.rms_eps, cfg.dtype, name="k_norm")(k)
        pos = jnp.arange(T)[None, :]
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
        mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        qg = q.reshape(B, T, KV, H // KV, D).astype(jnp.float32)
        s = jnp.einsum("bikgd,bjkd->bkgij", qg,
                       k.astype(jnp.float32)) * D ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        y = jnp.einsum("bkgij,bjkd->bikgd", p, v.astype(jnp.float32))
        return _dense(cfg, M, "o_proj")(
            y.reshape(B, T, H * D).astype(cfg.dtype))


class Lfm2Block(nn.Module):
    cfg: Lfm2Config
    kind: str
    ffn: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
        if self.kind == "attn":
            x = x + Lfm2Attention(cfg, name="attn")(h)
        else:
            x = x + GatedConvMixer(cfg, name="conv")(h)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x)
        if self.ffn == "dense":
            return x + DenseMLP(cfg, name="mlp")(h)
        return x + SolarSparseBlock(cfg, name="moe")(h)


class Lfm2(nn.Module):
    cfg: Lfm2Config

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed")
        x = embed(tokens)
        for i, (kind, ffn) in enumerate(zip(cfg.layer_kinds,
                                            cfg.ffn_kinds)):
            x = Lfm2Block(cfg, kind, ffn, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.rms_eps, jnp.float32, name="final_norm")(x)
        if return_hidden:
            return x
        if cfg.tie_embeddings:
            return embed.attend(x.astype(jnp.float32))
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype,
                        name="lm_head")(x.astype(jnp.float32))


def make_model(cfg: Lfm2Config):
    """(model, init_fn, loss_fn), the registry's contract. Training the
    family is not this module's claim: the loss is the plain
    cross-entropy of the plain forward."""
    return make_causal_lm(Lfm2(cfg), cfg)
