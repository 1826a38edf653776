"""Kimi-Linear family (``model_type: kimi_linear``): a hybrid decoder whose
layer list holds gated delta-rule layers AND latent-attention layers, three
of the first to one of the second, a dense layer before the sparse ones.

* block, pre-norm with one residual stream and no branch norms:
  ``x += Mixer(RMSNorm(x)); x += FFN(RMSNorm(x))``.
* ``"kda"`` layers: gated delta-rule linear attention exactly as
  ``models/solar_open2.py`` states it (``kda_inputs`` / ``kda_output`` /
  ``KDAMixer`` are that module's, shared), with ``kda_neg_eigval`` false:
  the step size is ``beta = sigmoid(W_b h)``.
* ``"mla"`` layers: multi-head latent attention as
  ``models/pangu_ultra_moe.py`` states it (``LatentAttention`` is that
  module's, shared) with a FULL-RANK query (``q_lora_rank`` None: one
  ``q_proj``, no query norm) and NO position code (``use_rope`` false,
  the config's ``mla_use_nope``): the ``qk_rope_head_dim`` lanes of the
  query and the one key row all heads share are used as they come out of
  their projections. A cache keeps ``[c_kv ; k_r]`` a token.
* feed-forward, by ``ffn_kinds``: ``"dense"`` SwiGLU of width
  ``dense_intermediate_size`` or ``"moe"``: sigmoid scores over all
  ``num_experts``, the ``experts_top_k`` largest of ``score + sel_bias``
  taken (one group: no grouping), their scores renormalised and scaled by
  ``routed_scaling``, plus one always-on ungated shared expert.

The config is ``PanguUltraMoEConfig`` with the recurrent layers' sizes
beside the latent ones': one class of fields for the runner's two mixers,
``head_dim`` / ``latent_row`` / ``held`` / ``residual_dtype`` inherited.
``experts_held`` < ``num_experts`` is one chip's share of a layer
(``models/solar_open2.py`` says how).

The flax module is the definition of the tree the ragged runner serves;
its forward runs the token-by-token recurrence, the EXPANDED attention and
every held expert densely, for small sizes (tests, shape inference).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ._lm_utils import make_causal_lm
from .llama import RMSNorm
from .pangu_ultra_moe import (DenseMLP, LatentAttention, PanguUltraMoEConfig,
                              _dense)
from .solar_open2 import KDAMixer, SolarSparseBlock, mixer_param_count


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig(PanguUltraMoEConfig):
    q_lora_rank: Optional[int] = None    # a full-rank query
    dense_intermediate_size: int = 9216
    sandwich_norm: bool = False
    use_rope: bool = False               # mla_use_nope
    router_bias: bool = True             # e_score_correction_bias
    routed_scaling: float = 2.446
    kda_heads: int = 32
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_rank: int = 128                  # the decay's and the gate's rank
    kda_neg_eigval: bool = False         # beta = sigmoid(.)

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 256)
        kw.setdefault("num_layers", 4)
        kw.setdefault("num_heads", 4)
        kw.setdefault("num_kv_heads", 1)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("kv_lora_rank", 128)
        kw.setdefault("qk_nope_head_dim", 16)
        kw.setdefault("qk_rope_head_dim", 8)
        kw.setdefault("v_head_dim", 16)
        kw.setdefault("kda_heads", 4)
        kw.setdefault("kda_head_dim", 16)
        kw.setdefault("kda_rank", 16)
        kw.setdefault("intermediate_size", 32)
        kw.setdefault("shared_expert_size", 32)
        kw.setdefault("dense_intermediate_size", 96)
        kw.setdefault("num_experts", 8)
        kw.setdefault("experts_top_k", 2)
        n = kw["num_layers"]
        kw.setdefault("layer_kinds", tuple(
            "mla" if i % 4 == 3 else "kda" for i in range(n)))
        kw.setdefault("ffn_kinds", ("dense",) + ("moe",) * (n - 1))
        return KimiLinearConfig(**kw)


def mla_param_count(cfg: KimiLinearConfig) -> int:
    H = cfg.num_heads
    return (cfg.hidden_size * H * (cfg.qk_nope_head_dim
                                   + cfg.qk_rope_head_dim)
            + cfg.hidden_size * cfg.head_dim + cfg.kv_lora_rank
            + cfg.kv_lora_rank * H * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + H * cfg.v_head_dim * cfg.hidden_size)


def param_counts(cfg: KimiLinearConfig) -> Tuple[int, int]:
    """(parameters of the model as configured, parameters one token
    passes through): embedding and head, the mixers, the dense layers,
    and of the routed experts all that are held against
    ``experts_top_k``."""
    M = cfg.hidden_size
    expert = 3 * M * cfg.intermediate_size
    fixed = 2 * cfg.vocab_size * M + M
    n_moe = 0
    for kind, ffn in zip(cfg.layer_kinds, cfg.ffn_kinds):
        fixed += 2 * M + (mla_param_count(cfg) if kind == "mla"
                          else mixer_param_count(cfg, "kda"))
        if ffn == "dense":
            fixed += 3 * M * cfg.dense_intermediate_size
        else:
            n_moe += 1
            fixed += M * cfg.num_experts + cfg.num_experts \
                + 3 * M * cfg.shared_expert_size
    return (fixed + n_moe * cfg.held * expert,
            fixed + n_moe * cfg.experts_top_k * expert)


class KimiLinearBlock(nn.Module):
    cfg: KimiLinearConfig
    kind: str
    ffn: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
        if self.kind == "mla":
            x = x + LatentAttention(cfg, name="attn")(h)
        else:
            x = x + KDAMixer(cfg, name="kda")(h)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x)
        if self.ffn == "dense":
            return x + DenseMLP(cfg, name="mlp")(h)
        W = cfg.shared_expert_size
        y = SolarSparseBlock(cfg, name="moe")(h)
        y = y + _dense(cfg, cfg.hidden_size, "shared_down_proj")(
            nn.silu(_dense(cfg, W, "shared_gate_proj")(h))
            * _dense(cfg, W, "shared_up_proj")(h))
        return x + y


class KimiLinear(nn.Module):
    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed")(tokens)
        for i, (kind, ffn) in enumerate(zip(cfg.layer_kinds,
                                            cfg.ffn_kinds)):
            x = KimiLinearBlock(cfg, kind, ffn, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.rms_eps, jnp.float32, name="final_norm")(x)
        if return_hidden:
            return x
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype,
                        name="lm_head")(x.astype(jnp.float32))


def make_model(cfg: KimiLinearConfig):
    """(model, init_fn, loss_fn), the registry's contract. Training the
    family (the backward scan of the delta rule, the backward through the
    latent projections at scale) is not this module's claim: the loss is
    the plain cross-entropy of the plain forward."""
    return make_causal_lm(KimiLinear(cfg), cfg)
