"""Mixtral-style MoE transformer: Llama block with the dense MLP swapped for
the framework's expert-parallel ``MoE`` layer.

Parity target: the reference's mixtral / qwen_v2_moe containers
(``inference/v2/model_implementations/mixtral/``) and the training-side MoE
integration (``deepspeed/moe/layer.py:17``). The MoE block here is the same
``deepspeed_tpu.moe.MoE`` used standalone, so EP sharding, capacity gating,
and the aux-loss plumbing behave identically in both places.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..moe.layer import MoE
from .llama import LlamaAttention, LlamaConfig, RMSNorm


@dataclasses.dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_experts: int = 8
    experts_top_k: int = 2
    capacity_factor: float = 2.0
    drop_tokens: bool = False          # mixtral routes all tokens
    router_aux_loss_coef: float = 0.02
    shared_expert_size: int = 0        # qwen2-moe always-on expert width
    gated_experts: bool = True         # SwiGLU experts (HF mixtral layout)
    # True (mixtral): softmax over the selected top-k (renormalized).
    # False (qwen2-moe default): softmax over ALL experts, top-k taken
    # without renormalization.
    norm_topk_prob: bool = True

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("num_kv_heads", 2)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("num_experts", 4)
        return MixtralConfig(**kw)

    @staticmethod
    def mixtral_8x7b(**kw):
        kw.setdefault("vocab_size", 32000)
        kw.setdefault("max_seq_len", 32768)
        kw.setdefault("num_kv_heads", 8)
        kw.setdefault("intermediate_size", 14336)
        kw.setdefault("rope_theta", 1e6)
        return MixtralConfig(**kw)


class MixtralBlock(nn.Module):
    cfg: MixtralConfig
    ep_mesh: Any = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        cfg = self.cfg
        x = x + LlamaAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x))
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x)
        y, l_aux = MoE(
            d_model=cfg.hidden_size, num_experts=cfg.num_experts,
            k=cfg.experts_top_k, hidden=cfg.intermediate_size,
            capacity_factor=cfg.capacity_factor,
            eval_capacity_factor=cfg.capacity_factor,
            drop_tokens=cfg.drop_tokens, ep_mesh=self.ep_mesh,
            dtype=cfg.dtype, activation=nn.silu,
            gated=cfg.gated_experts,
            normalize_weights=cfg.norm_topk_prob, name="moe")(x=h, train=train)
        self.sow("losses", "moe_aux", l_aux)
        if cfg.shared_expert_size:
            # qwen2-moe: an always-on SwiGLU expert gated by a sigmoid
            # (HF Qwen2MoeSparseMoeBlock shared_expert + shared_expert_gate)
            dense = lambda feats, name: nn.Dense(  # noqa: E731
                feats, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                use_bias=False, name=name)
            gate = dense(cfg.shared_expert_size, "shared_gate_proj")(h)
            up = dense(cfg.shared_expert_size, "shared_up_proj")(h)
            shared = dense(cfg.hidden_size, "shared_down_proj")(
                nn.silu(gate) * up)
            sgate = jax.nn.sigmoid(
                dense(1, "shared_expert_gate")(h).astype(jnp.float32))
            y = y + shared * sgate.astype(cfg.dtype)
        return x + y


class Mixtral(nn.Module):
    cfg: MixtralConfig
    ep_mesh: Any = None

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 return_hidden: bool = False):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed")
        x = embed(tokens)
        from ._lm_utils import constrain_activations
        x = constrain_activations(x)
        from ._lm_utils import layer_class
        for i in range(cfg.num_layers):
            x = layer_class(self, MixtralBlock, f"layer_{i}",
                            cfg.remat, static_argnums=(2,))(
                cfg, self.ep_mesh, name=f"layer_{i}")(x, train)
        x = RMSNorm(cfg.rms_eps, jnp.float32, name="final_norm")(x)
        head = nn.Dense(cfg.vocab_size, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype, use_bias=False,
                        name="lm_head")
        if return_hidden:
            # training loss path: the caller fuses the head into the
            # chunked/streaming cross-entropy (lm_head params exist from
            # init, which traces the logits path)
            return x
        return head(x.astype(jnp.float32))


def make_model(cfg: MixtralConfig, ep_mesh=None):
    """(model, init_fn, loss_fn); the LM loss adds the router aux loss scaled
    by ``router_aux_loss_coef`` (the reference folds l_aux the same way)."""
    model = Mixtral(cfg, ep_mesh)

    def init_fn(rng, batch_size: int = 2, seq_len: Optional[int] = None):
        T = seq_len or min(cfg.max_seq_len, 64)
        variables = model.init({"params": rng, "gating": rng},
                               jnp.zeros((batch_size, T), jnp.int32))
        return variables["params"]

    def loss_fn(params, batch, rng):
        from ._lm_utils import lm_head_xent
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        hidden, aux = model.apply(
            {"params": params}, inputs, rngs={"gating": rng},
            mutable=["losses"], return_hidden=True)
        moe_aux = sum(jnp.sum(v) for v in
                      jax.tree_util.tree_leaves(aux.get("losses", {})))
        # head fused into the chunked/streaming xent — [B, T, V] fp32
        # logits never materialize (the MoE flagship's vocab is 32k)
        nll = lm_head_xent(hidden.astype(cfg.dtype),
                           params["lm_head"]["kernel"], targets, cfg,
                           head_layout="cv")
        return nll + cfg.router_aux_loss_coef * moe_aux

    return model, init_fn, loss_fn
