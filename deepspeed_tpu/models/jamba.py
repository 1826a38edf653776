"""Jamba family (``model_type: jamba``): a hybrid decoder of Mamba-1 mixers
and a few softmax attention layers, a feed-forward in EVERY layer.

* layer ``i`` is attention where ``i % attn_layer_period ==
  attn_layer_offset`` and Mamba otherwise (:func:`kinds_from_periods`; the
  family's modelling code). Every layer is pre-norm with two branches,
  ``x += Mixer(RMSNorm(x)); x += FFN(RMSNorm(x))``, then a final norm and a
  head TIED to the embedding when the config says so.
* ``"mamba1"`` layers: the selective scan with a decay a (channel, state)
  pair (``ops/kernels/selective_scan.py`` has the recurrence). ``[x~ | z] =
  h W_in``; ``x = SiLU(conv(x~) + b)``, a causal depthwise convolution of
  ``mamba_conv`` taps over the ``mamba_inner`` channels of ``x~`` ALONE
  (in Mamba-2, ``nemotron_h.py``, B and C pass through it too); ``[dt~ | B
  | C] = x W_x`` out of the convolution's OUTPUT; RMSNorms with learned
  scales on ``dt~``, ``B`` and ``C`` (Jamba's own); ``dt = softplus(dt~
  W_dt + b_dt)`` a channel, through the rank-``mamba_dt_rank``
  bottleneck; ``A = -exp(A_log)``; the skip ``D x``; the gate ``y *
  SiLU(z)`` with NO norm after it; ``W_out``.
* ``"attn"`` layers: grouped-query softmax attention with NO position
  code (``use_rope`` false: position reaches the model through the Mamba
  layers), no bias, no gate: ``solar_open2.GatedNoPEAttention`` ungated.
* feed-forward: a dense SwiGLU of ``intermediate_size`` in every layer
  (``num_experts`` 1). Jamba's SPARSE feed-forwards (``num_experts`` > 1
  on the layers ``expert_layer_period / _offset`` name) are refused by
  name in the registry: another change's.

The tree keeps a Mamba layer's tensors bare under ``layer_i/mamba``;
``A_log`` is stored ``[state, channels]``, the checkpoint's transposed, as
the state pool and the kernels lay a state out.

The flax module is the definition of the tree the ragged runner serves;
its forward runs the token-by-token recurrence and dense attention, for
small sizes (tests, shape inference).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ._lm_utils import make_causal_lm
from .llama import LlamaConfig, RMSNorm
from .pangu_ultra_moe import DenseMLP
from .solar_open2 import GatedNoPEAttention, conv_silu


def kinds_from_periods(layers: int, period: int, offset: int):
    """``layer_kinds`` of ``layers`` layers: attention where ``i % period
    == offset``, Mamba-1 otherwise."""
    return tuple("attn" if i % period == offset else "mamba1"
                 for i in range(layers))


@dataclasses.dataclass(frozen=True)
class JambaConfig(LlamaConfig):
    #: "mamba1" or "attn", a layer
    layer_kinds: Tuple[str, ...] = ()
    use_rope: bool = False
    attn_gate: bool = False
    rms_eps: float = 1e-6
    tie_embeddings: bool = True
    mamba_expand: int = 2
    mamba_state: int = 16
    mamba_conv: int = 4
    mamba_dt_rank: int = 160

    @property
    def residual_dtype(self):
        """The serving residual stream is float32, as the other hybrid
        families': the norms read an unrounded stream."""
        return jnp.float32

    @property
    def dense_intermediate_size(self) -> int:
        return self.intermediate_size

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 256)
        kw.setdefault("num_layers", 4)
        kw.setdefault("num_heads", 4)
        kw.setdefault("num_kv_heads", 1)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("mamba_state", 4)
        kw.setdefault("mamba_dt_rank", 8)
        kw.setdefault("layer_kinds",
                      kinds_from_periods(kw["num_layers"], 4, 2))
        return JambaConfig(**kw)


def mixer_param_count(cfg: JambaConfig, kind: str) -> int:
    M = cfg.hidden_size
    if kind == "attn":
        return 2 * M * (cfg.num_heads + cfg.num_kv_heads) * cfg.head_dim
    E, N, R, K = (cfg.mamba_inner, cfg.mamba_state, cfg.mamba_dt_rank,
                  cfg.mamba_conv)
    return (M * 2 * E + (K + 1) * E + E * (R + 2 * N) + (R + 2 * N)
            + R * E + E + E * N + E + E * M)


def param_counts(cfg: JambaConfig) -> Tuple[int, int]:
    """(parameters of the model as configured, parameters one token
    passes through): the same number, the model is dense. The embedding
    (once where the head is tied to it), the final norm, and a layer's
    mixer, two norms and SwiGLU."""
    M = cfg.hidden_size
    n = (1 if cfg.tie_embeddings else 2) * cfg.vocab_size * M + M
    for kind in cfg.layer_kinds:
        n += mixer_param_count(cfg, kind) + 2 * M \
            + 3 * M * cfg.intermediate_size
    return n, n


def mamba1_conv_inputs(p, h, cfg: JambaConfig, dtype):
    """The input projection of a Mamba-1 layer, split: (x~ [B, T, E] what
    the short convolution takes, z [B, T, E] the gate's pre-activation),
    float32.

    The matmul takes ``dtype`` operands and gives float32: what feeds the
    recurrence is not rounded to ``dtype`` on the way, because a rounding
    of the step compounds over every later position of the sequence."""
    xz = jnp.matmul(h, p["in_proj"].astype(dtype),
                    preferred_element_type=jnp.float32)
    return jnp.split(xz, 2, -1)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def mamba1_recurrence_inputs(p, x, cfg: JambaConfig, dtype):
    """From the activated convolution x [B, T, E] float32 to the rest of
    the recurrence's inputs: (dt [B, T, E] after the softplus, B and C
    [B, T, N]), float32: the projection of x, the three RMSNorms, the
    step size through its bottleneck WITH its bias."""
    f32 = jnp.float32
    R, N = cfg.mamba_dt_rank, cfg.mamba_state
    dbc = jnp.matmul(x.astype(dtype), p["x_proj"].astype(dtype),
                     preferred_element_type=f32)
    dt, Bm, Cm = jnp.split(dbc, [R, R + N], -1)
    dt = _rms(dt, p["dt_norm"], cfg.rms_eps)
    Bm = _rms(Bm, p["b_norm"], cfg.rms_eps)
    Cm = _rms(Cm, p["c_norm"], cfg.rms_eps)
    dt = jnp.matmul(dt.astype(dtype), p["dt_proj"].astype(dtype),
                    preferred_element_type=f32)
    return jax.nn.softplus(dt + p["dt_bias"].astype(f32)), Bm, Cm


def mamba1_output(p, y, z, dtype):
    """y [B, T, E] float32 (the skip term in it), z [B, T, E] -> the
    mixer's output [B, T, M]: the gate (no norm after it), ``W_out``."""
    return (y * jax.nn.silu(z)).astype(dtype) @ p["out_proj"].astype(dtype)


class Mamba1Mixer(nn.Module):
    cfg: JambaConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        Bsz, T, M = h.shape
        E, N, R, K = (cfg.mamba_inner, cfg.mamba_state, cfg.mamba_dt_rank,
                      cfg.mamba_conv)
        kern = lambda name, shape: self.param(             # noqa: E731
            name, nn.initializers.lecun_normal(), shape, cfg.param_dtype)
        vec = lambda name, init, shape: self.param(        # noqa: E731
            name, init, shape, jnp.float32)
        ones, zeros = nn.initializers.ones, nn.initializers.zeros
        p = {"in_proj": kern("in_proj", (M, 2 * E)),
             "x_proj": kern("x_proj", (E, R + 2 * N)),
             "dt_proj": kern("dt_proj", (R, E)),
             "out_proj": kern("out_proj", (E, M)),
             "conv_w": self.param("conv_w",
                                  nn.initializers.normal(K ** -0.5),
                                  (K, E), cfg.param_dtype),
             "conv_b": vec("conv_b", zeros, (E,)),
             "dt_bias": vec("dt_bias", zeros, (E,)),
             "A_log": vec("A_log", zeros, (N, E)),
             "D": vec("D", ones, (E,)),
             "dt_norm": vec("dt_norm", ones, (R,)),
             "b_norm": vec("b_norm", ones, (N,)),
             "c_norm": vec("c_norm", ones, (N,))}
        from ..ops.kernels.selective_scan import mamba1_recurrent
        f32 = jnp.float32
        h = h.astype(cfg.dtype)
        x, z = mamba1_conv_inputs(p, h, cfg, cfg.dtype)
        x, _ = conv_silu(x, p["conv_w"].astype(f32),
                         jnp.zeros((Bsz, K - 1, E), f32),
                         p["conv_b"].astype(f32))
        dt, Bm, Cm = mamba1_recurrence_inputs(p, x, cfg, cfg.dtype)
        y, _ = mamba1_recurrent(x, dt, -jnp.exp(p["A_log"]), Bm, Cm, p["D"],
                                jnp.zeros((Bsz, N, E), f32))
        return mamba1_output(p, y, z, cfg.dtype)


class JambaBlock(nn.Module):
    cfg: JambaConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_eps, cfg.dtype,  # noqa: E731
                                    name=name)
        h = norm("input_norm")(x)
        x = x + (GatedNoPEAttention(cfg, name="attn")(h)
                 if self.kind == "attn"
                 else Mamba1Mixer(cfg, name="mamba")(h))
        return x + DenseMLP(cfg, name="mlp")(norm("post_attn_norm")(x))


class Jamba(nn.Module):
    cfg: JambaConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed")
        x = embed(tokens)
        for i, kind in enumerate(cfg.layer_kinds):
            x = JambaBlock(cfg, kind, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.rms_eps, jnp.float32, name="final_norm")(x)
        if return_hidden:
            return x
        if cfg.tie_embeddings:
            return embed.attend(x.astype(jnp.float32))
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype,
                        name="lm_head")(x.astype(jnp.float32))


def make_model(cfg: JambaConfig):
    """(model, init_fn, loss_fn), the registry's contract. Training the
    family (the backward of the selective scan) is not this module's
    claim: the loss is the plain cross-entropy of the plain forward."""
    return make_causal_lm(Jamba(cfg), cfg)
