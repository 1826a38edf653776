"""Olmo-Hybrid family (``model_type: olmo_hybrid``): a DENSE hybrid decoder
whose layers follow ``layer_types``, three ``linear_attention`` to one
``full_attention``, with the norms on the branches' OUTPUTS.

* block, ``block_norms = "post"``: no norm in front of a branch, one on
  what it returns, ``x += RMSNorm(Mixer(x)); x += RMSNorm(FFN(x))`` (the
  tree's ``attn_branch_norm`` / ``mlp_branch_norm``, the names the
  sandwich arrangement gives its output norms), then a final norm and an
  untied head.
* ``"gdn"`` layers (``linear_attention``): the gated delta rule with ONE
  decay a head (``ops/kernels/delta_rule.py``, the scalar forms): q / k /
  v each through its own causal depthwise convolution of ``gdn_conv`` taps
  and SiLU (``solar_open2.kda_conv_inputs``, shared), q and k
  L2-normalised a head and q scaled by ``d_k ** -0.5``, a step size
  ``beta = 2 sigmoid(W_b h)`` (the 2 is ``gdn_neg_eigval``; both shared
  with ``solar_open2``), the head's decay ``g = -exp(A_log) softplus(W_a h
  + dt_bias)`` (the state-space form: one number a head, where KDA has one
  a channel through a low rank), keys ``gdn_key_dim`` and values
  ``gdn_value_dim`` wide (a state ``[d_k, d_v]`` a head, not square), a
  per-head RMSNorm on the output gated by ``SiLU(W_g h)`` at FULL rank
  before ``W_o``.
* ``"attn"`` layers (``full_attention``): multi-head softmax attention,
  RMSNorm over the WHOLE q and k projections (``qk_norm`` true, OLMoE's
  form), NO position code (``use_rope`` false: the config's ``rope_theta``
  is null), no gate, no bias.
* feed-forward: a dense SwiGLU of ``intermediate_size`` in every layer.

The flax module is the definition of the tree the ragged runner serves
(``inference/v2/llama_runner.py`` reads the same names); its forward runs
the token-by-token recurrence and dense attention, for small sizes (tests,
shape inference), not for speed.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ._lm_utils import make_causal_lm
from .llama import LlamaConfig, RMSNorm
from .pangu_ultra_moe import DenseMLP, _dense
from .solar_open2 import (delta_beta, kda_conv_inputs, l2_normed,
                          short_conv)

#: ``layer_types`` -> the runner's mixer kind
LAYER_TYPES = {"linear_attention": "gdn", "full_attention": "attn"}


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig(LlamaConfig):
    #: "gdn" or "attn", a layer
    layer_kinds: Tuple[str, ...] = ()
    #: where a block's norms stand: on the branches' outputs alone
    block_norms: str = "post"
    qk_norm: bool = True                 # over the whole projection
    use_rope: bool = False               # rope_theta null
    rms_eps: float = 1e-6
    gdn_heads: int = 30
    gdn_key_dim: int = 96
    gdn_value_dim: int = 192
    gdn_conv: int = 4
    gdn_neg_eigval: bool = True          # beta = 2 sigmoid(.)

    @property
    def dense_intermediate_size(self) -> int:
        return self.intermediate_size

    @property
    def gdn_conv_width(self) -> int:
        """Lanes of q | k | v through the short convolution."""
        return self.gdn_heads * (2 * self.gdn_key_dim + self.gdn_value_dim)

    @staticmethod
    def tiny(**kw):
        """The awkward geometry kept small: heads no multiple of 8, keys
        and values of two widths, neither a whole tile."""
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 256)
        kw.setdefault("num_layers", 4)
        kw.setdefault("num_heads", 6)
        kw.setdefault("num_kv_heads", 6)
        kw.setdefault("hidden_size", 96)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("gdn_heads", 6)
        kw.setdefault("gdn_key_dim", 12)
        kw.setdefault("gdn_value_dim", 24)
        kw.setdefault("layer_kinds", tuple(
            "attn" if i % 4 == 3 else "gdn"
            for i in range(kw["num_layers"])))
        return OlmoHybridConfig(**kw)


def mixer_param_count(cfg: OlmoHybridConfig, kind: str) -> int:
    M = cfg.hidden_size
    if kind == "attn":
        qo = cfg.num_heads * cfg.head_dim
        kv = cfg.num_kv_heads * cfg.head_dim
        return M * (2 * qo + 2 * kv) + qo + kv
    H, dv = cfg.gdn_heads, cfg.gdn_value_dim
    return (M * cfg.gdn_conv_width + cfg.gdn_conv * cfg.gdn_conv_width
            + 2 * M * H * dv + 2 * M * H + 2 * H + dv)


def param_counts(cfg: OlmoHybridConfig) -> Tuple[int, int]:
    """(parameters of the model as configured, parameters one token
    passes through): the same number, the model is dense. Embedding and
    head, the final norm, and a layer's mixer, two norms and SwiGLU."""
    M = cfg.hidden_size
    n = 2 * cfg.vocab_size * M + M
    for kind in cfg.layer_kinds:
        n += mixer_param_count(cfg, kind) + 2 * M \
            + 3 * M * cfg.intermediate_size
    return n, n


def gdn_recurrence_inputs(p, h, y, cfg: OlmoHybridConfig, dtype):
    """From the activated convolution y [B, T, H (2 d_k + d_v)] float32
    and the block's input h to the recurrence's inputs: (q, k
    [B, T, H, d_k], v [B, T, H, d_v], g [B, T, H] float32 the head's log
    decay, beta [B, T, H] float32). Nothing that feeds the recurrence is
    rounded to ``dtype`` on the way (``solar_open2.kda_conv_inputs`` says
    why)."""
    B, T, _ = h.shape
    H, dk, dv = cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    f32 = jnp.float32
    q, k, v = jnp.split(y, (H * dk, 2 * H * dk), axis=-1)
    q = l2_normed(q.reshape(B, T, H, dk)) * dk ** -0.5
    k = l2_normed(k.reshape(B, T, H, dk))
    a = jnp.matmul(h, p["a_proj"].astype(dtype), preferred_element_type=f32)
    g = -jnp.exp(p["A_log"].astype(f32)) \
        * jax.nn.softplus(a + p["dt_bias"].astype(f32))
    return q, k, v.reshape(B, T, H, dv), g, \
        delta_beta(p, h, cfg.gdn_neg_eigval, dtype)


def gdn_output(p, o, h, cfg: OlmoHybridConfig, dtype):
    """o [B, T, H, d_v] float32 -> the mixer's output [B, T, M]: the
    per-head RMSNorm, the full-rank SiLU gate, the output projection."""
    B, T, H, dv = o.shape
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.rms_eps) \
        * p["o_norm"].astype(jnp.float32)
    gate = jax.nn.silu(jnp.matmul(h, p["g_proj"].astype(dtype),
                                  preferred_element_type=jnp.float32))
    y = (o.reshape(B, T, H * dv) * gate).astype(dtype)
    return y @ p["o_proj"].astype(dtype)


class GatedDeltaMixer(nn.Module):
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        B, T, M = h.shape
        H, dk, dv, K = (cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim,
                        cfg.gdn_conv)
        kern = lambda name, shape: self.param(             # noqa: E731
            name, nn.initializers.lecun_normal(), shape, cfg.param_dtype)
        conv = lambda name, w: self.param(                 # noqa: E731
            name, nn.initializers.normal(K ** -0.5), (K, w), cfg.param_dtype)
        p = {"q_proj": kern("q_proj", (M, H * dk)),
             "k_proj": kern("k_proj", (M, H * dk)),
             "v_proj": kern("v_proj", (M, H * dv)),
             "g_proj": kern("g_proj", (M, H * dv)),
             "o_proj": kern("o_proj", (H * dv, M)),
             "a_proj": kern("a_proj", (M, H)),
             "b_proj": kern("b_proj", (M, H)),
             "q_conv": conv("q_conv", H * dk),
             "k_conv": conv("k_conv", H * dk),
             "v_conv": conv("v_conv", H * dv),
             "A_log": self.param("A_log", nn.initializers.zeros, (H,),
                                 jnp.float32),
             "dt_bias": self.param("dt_bias", nn.initializers.zeros, (H,),
                                   jnp.float32),
             "o_norm": self.param("o_norm", nn.initializers.ones, (dv,),
                                  jnp.float32)}
        from ..ops.kernels.delta_rule import kda_recurrent
        h = h.astype(cfg.dtype)
        pre, w = kda_conv_inputs(p, h, cfg.dtype)
        y, _ = short_conv(pre, w, jnp.zeros((B, K - 1, pre.shape[-1]),
                                            jnp.float32))
        q, k, v, g, beta = gdn_recurrence_inputs(p, h, jax.nn.silu(y), cfg,
                                                 cfg.dtype)
        # the definition: the head's decay in every channel of its keys
        o, _ = kda_recurrent(q, k, v, jnp.broadcast_to(g[..., None], q.shape),
                             beta, jnp.zeros((B, H, dk, dv), jnp.float32))
        return gdn_output(p, o, h, cfg, cfg.dtype)


class NoPEAttention(nn.Module):
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, M = x.shape
        H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        norm = lambda name: RMSNorm(cfg.rms_eps, cfg.dtype,  # noqa: E731
                                    name=name)
        q = norm("q_norm")(_dense(cfg, H * D, "q_proj")(x))
        k = norm("k_norm")(_dense(cfg, KV * D, "k_proj")(x))
        v = _dense(cfg, KV * D, "v_proj")(x)
        q = q.reshape(B, T, H, D)
        k, v = (jnp.repeat(t.reshape(B, T, KV, D), H // KV, axis=2)
                for t in (k, v))
        y = jax.nn.dot_product_attention(q, k, v, is_causal=True)
        return _dense(cfg, M, "o_proj")(y.reshape(B, T, H * D))


class OlmoHybridBlock(nn.Module):
    cfg: OlmoHybridConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_eps, cfg.dtype,  # noqa: E731
                                    name=name)
        h = x.astype(cfg.dtype)
        y = NoPEAttention(cfg, name="attn")(h) if self.kind == "attn" \
            else GatedDeltaMixer(cfg, name="gdn")(h)
        x = x + norm("attn_branch_norm")(y)
        y = DenseMLP(cfg, name="mlp")(x.astype(cfg.dtype))
        return x + norm("mlp_branch_norm")(y)


class OlmoHybrid(nn.Module):
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed")(tokens)
        for i, kind in enumerate(cfg.layer_kinds):
            x = OlmoHybridBlock(cfg, kind, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.rms_eps, jnp.float32, name="final_norm")(x)
        if return_hidden:
            return x
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype,
                        name="lm_head")(x.astype(jnp.float32))


def make_model(cfg: OlmoHybridConfig):
    """(model, init_fn, loss_fn), the registry's contract. Training the
    family (the backward scan of the delta rule) is not this module's
    claim: the loss is the plain cross-entropy of the plain forward."""
    return make_causal_lm(OlmoHybrid(cfg), cfg)
