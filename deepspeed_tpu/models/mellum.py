"""Mellum family (``model_type: mellum``): a sparse decoder whose attention
layers are of TWO kinds, by ``layer_types``: three sliding-window layers to
one full layer, each kind with its own rotary code.

* block, pre-norm with one residual stream and two branches:
  ``x += Attn_l(RMSNorm(x)); x += MoE(RMSNorm(x))``; no bias anywhere.
* attention, layer ``l``: GQA (``num_heads`` query heads over
  ``num_kv_heads``), ``qk_norm == "head"``: one RMSNorm over each head's
  own ``head_dim`` lanes of q and of k (one learned scale of ``head_dim``
  shared by the heads), after the split into heads and before the rotary
  code; rotate-half over all ``head_dim`` lanes; scores times
  ``head_dim ** -0.5``, causal.
* ``"swa"`` (``sliding_attention``): key ``j`` is visible to query ``i``
  iff ``0 <= i - j < sliding_window`` (the query's own key among the
  ``sliding_window``); plain rotary at ``rope_theta``.
* ``"attn"`` (``full_attention``): every ``j <= i``; YaRN
  (``full_rope``: ``models/llama.py::yarn_frequencies``, cos and sin both
  times ``attention_factor``, so the rotary part of a score carries its
  square).
* feed-forward, every layer: ``softmax`` over all ``num_experts`` router
  outputs in float32, the ``experts_top_k`` largest, renormalised
  (``norm_topk_prob``); SwiGLU experts of width ``intermediate_size``; no
  shared expert.

``experts_held`` < ``num_experts`` is one chip's share of a layer
(``models/solar_open2.py`` says how; ``SolarSparseBlock`` is that
module's, shared).

Serving keeps a ``"swa"`` layer's rows in a bounded window pool beside the
paged pool (``inference/v2/kv_cache.py``); the flax module is the
definition of the tree the ragged runner serves, and its forward runs
dense attention under each layer's own mask and every held expert densely,
for small sizes (tests, shape inference).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ._lm_utils import make_causal_lm
from .llama import RMSNorm, apply_rope, yarn_frequencies
from .mixtral import MixtralConfig
from .solar_open2 import SolarSparseBlock

#: ``layer_types`` -> the runner's mixer kind
LAYER_TYPES = {"sliding_attention": "swa", "full_attention": "attn"}


@dataclasses.dataclass(frozen=True)
class YarnRope:
    """A ``rope_parameters`` section of ``rope_type`` ``yarn``."""
    factor: float
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class MellumConfig(MixtralConfig):
    attn_head_dim: int = 128
    #: "swa" or "attn", a layer
    layer_kinds: Tuple[str, ...] = ()
    sliding_window: Optional[int] = 1024
    qk_norm: str = "head"
    #: the full layers' position code (None: plain rotary, as the sliding
    #: layers always have)
    full_rope: Optional[YarnRope] = None
    norm_topk_prob: bool = True
    router_score: str = "softmax"
    router_bias: bool = False
    routed_scaling: float = 1.0
    experts_held: Optional[int] = None   # None = all of them
    experts_first: int = 0

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    def rope_of(self, kind: str):
        """(inv_freq [head_dim / 2] or None, scale or None) of a layer
        kind's rotary code, for ``apply_rope``."""
        r = self.full_rope
        if kind != "attn" or r is None:
            return None, None
        return yarn_frequencies(
            self.head_dim, self.rope_theta, r.factor, r.original_max,
            r.beta_fast, r.beta_slow), r.attention_factor

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 256)
        kw.setdefault("num_layers", 4)
        kw.setdefault("num_heads", 4)
        kw.setdefault("num_kv_heads", 2)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("attn_head_dim", 16)
        kw.setdefault("intermediate_size", 32)
        kw.setdefault("num_experts", 8)
        kw.setdefault("experts_top_k", 2)
        kw.setdefault("sliding_window", 8)
        kw.setdefault("full_rope", YarnRope(4.0, 32, 8.0, 1.0, 1.1386))
        kw.setdefault("layer_kinds", tuple(
            "attn" if i % 4 == 3 else "swa"
            for i in range(kw["num_layers"])))
        return MellumConfig(**kw)


def param_counts(cfg: MellumConfig) -> Tuple[int, int]:
    """(parameters of the model as configured, parameters one token
    passes through): embedding and head, attention, the routers, and of
    the experts all that are held against ``experts_top_k``."""
    M, D = cfg.hidden_size, cfg.head_dim
    expert = 3 * M * cfg.intermediate_size
    n = len(cfg.layer_kinds)
    attn = 2 * M * D * (cfg.num_heads + cfg.num_kv_heads) + 2 * D
    fixed = 2 * cfg.vocab_size * M + M \
        + n * (attn + 2 * M + M * cfg.num_experts)
    return (fixed + n * cfg.held * expert,
            fixed + n * cfg.experts_top_k * expert)


class MellumAttention(nn.Module):
    cfg: MellumConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, M = x.shape
        H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dense = lambda feats, name: nn.Dense(              # noqa: E731
            feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        q = dense(H * D, "q_proj")(x).reshape(B, T, H, D)
        k = dense(KV * D, "k_proj")(x).reshape(B, T, KV, D)
        v = dense(KV * D, "v_proj")(x).reshape(B, T, KV, D)
        q = RMSNorm(cfg.rms_eps, cfg.dtype, name="q_norm")(q)
        k = RMSNorm(cfg.rms_eps, cfg.dtype, name="k_norm")(k)
        pos = jnp.arange(T)[None, :]
        inv_freq, scale = cfg.rope_of(self.kind)
        q = apply_rope(q, pos, cfg.rope_theta, inv_freq, scale)
        k = apply_rope(k, pos, cfg.rope_theta, inv_freq, scale)
        i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        mask = j <= i
        if self.kind == "swa":
            mask &= j > i - cfg.sliding_window
        qg = q.reshape(B, T, KV, H // KV, D).astype(jnp.float32)
        s = jnp.einsum("bikgd,bjkd->bkgij", qg,
                       k.astype(jnp.float32)) * D ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        y = jnp.einsum("bkgij,bjkd->bikgd", p, v.astype(jnp.float32))
        return dense(M, "o_proj")(y.reshape(B, T, H * D).astype(cfg.dtype))


class MellumBlock(nn.Module):
    cfg: MellumConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        x = x + MellumAttention(cfg, self.kind, name="attn")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x))
        return x + SolarSparseBlock(cfg, name="moe")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x))


class Mellum(nn.Module):
    cfg: MellumConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed")(tokens)
        for i, kind in enumerate(cfg.layer_kinds):
            x = MellumBlock(cfg, kind, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.rms_eps, jnp.float32, name="final_norm")(x)
        if return_hidden:
            return x
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype,
                        name="lm_head")(x.astype(jnp.float32))


def make_model(cfg: MellumConfig):
    """(model, init_fn, loss_fn), the registry's contract. Training the
    family (window layers in the flash path) is not this module's claim:
    the loss is the plain cross-entropy of the plain forward."""
    return make_causal_lm(Mellum(cfg), cfg)
