"""openPangu-Ultra-MoE family (``model_type: pangu_ultra_moe``): a decoder
whose attention is multi-head LATENT attention (MLA), whose first layers
are dense and the rest sparse, and whose branches are normed on both sides.

* block, with ``sandwich_norm``: ``a = x + N2(Attn(N1(x)))``,
  ``y = a + N4(FFN(N3(a)))``: four RMSNorms a layer (``input_norm``,
  ``attn_branch_norm``, ``post_attn_norm`` = the norm before the
  feed-forward, ``mlp_branch_norm``).
* attention (``layer_kinds`` all ``"mla"``): ``c_q = RMS(h W_qa)``,
  ``q = c_q W_qb`` -> heads of (nope + rope); ``(c_kv, k_r) = h W_kva``,
  ``c_kv = RMS(c_kv)``; rotary positions (rotate-half) on ``q_rope`` and
  on the ONE ``k_r`` all heads share; ``(k_nope, v) = c_kv W_kvb``;
  scores ``(q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)``, causal
  softmax, ``W_o``. A cache keeps ``[c_kv ; k_r]`` only (``head_dim``
  lanes a token and layer: the latent row is key and value at once); the
  serving runner multiplies ``W_kvb`` into the query and the output
  instead of expanding the cache (``inference/v2/llama_runner.py``).
* feed-forward, by ``ffn_kinds``: ``"dense"`` SwiGLU of width
  ``dense_intermediate_size`` or ``"moe"``: sigmoid scores over all
  ``num_experts`` in float32, the ``experts_top_k`` largest renormalised
  and scaled by ``routed_scaling``, experts of width
  ``intermediate_size``, plus one always-on ungated shared expert.
* ``nextn_layers`` multi-token-prediction modules (``mtp_<i>``):
  ``h' = W_eh [RMS(h) ; RMS(E x_{t+1})]``, one more sparse block, a norm
  and the model's own head. Their logits are no part of the model's.

``experts_held`` < ``num_experts`` is one chip's share of a layer whose
experts are divided over chips (``models/solar_open2.py`` says how).

The flax module is the definition of the tree the ragged runner serves;
its forward runs the EXPANDED attention and every held expert densely, for
small sizes (tests, shape inference), not for speed.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .llama import RMSNorm, apply_rope
from .mixtral import MixtralConfig
from .solar_open2 import SolarSparseBlock


@dataclasses.dataclass(frozen=True)
class PanguUltraMoEConfig(MixtralConfig):
    q_lora_rank: Optional[int] = 1536    # None: a full-rank query
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    #: mixer kind of each layer (all "mla") and its feed-forward kind
    layer_kinds: Tuple[str, ...] = ()
    ffn_kinds: Tuple[str, ...] = ()          # "dense" or "moe"
    dense_intermediate_size: int = 18432
    sandwich_norm: bool = True
    router_score: str = "sigmoid"
    router_bias: bool = False
    routed_scaling: float = 2.5
    shared_expert_gated: bool = False
    experts_held: Optional[int] = None       # None = all of them
    experts_first: int = 0
    nextn_layers: int = 0

    @property
    def head_dim(self) -> int:
        """Lanes of the ONE row a token and layer keeps: the latent and
        the shared rotary key (``num_kv_heads`` is 1)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def block_norms(self) -> str:
        """Where a block's norms stand, as the runner reads it ("pre",
        "sandwich" or "post"): the family's published key is the
        boolean."""
        return "sandwich" if self.sandwich_norm else "pre"

    @property
    def latent_row(self) -> int:
        """The row as stored: whole 128-lane groups, the tail zero."""
        return -(-self.head_dim // 128) * 128

    @property
    def residual_dtype(self):
        return jnp.float32

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 256)
        kw.setdefault("num_layers", 3)
        kw.setdefault("num_heads", 8)
        kw.setdefault("num_kv_heads", 1)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("q_lora_rank", 48)
        kw.setdefault("kv_lora_rank", 128)
        kw.setdefault("qk_nope_head_dim", 16)
        kw.setdefault("qk_rope_head_dim", 8)
        kw.setdefault("v_head_dim", 16)
        kw.setdefault("intermediate_size", 32)
        kw.setdefault("shared_expert_size", 32)
        kw.setdefault("dense_intermediate_size", 96)
        kw.setdefault("num_experts", 8)
        kw.setdefault("experts_top_k", 2)
        n = kw["num_layers"]
        kw.setdefault("layer_kinds", ("mla",) * n)
        kw.setdefault("ffn_kinds", ("dense",) + ("moe",) * (n - 1))
        return PanguUltraMoEConfig(**kw)


def param_counts(cfg: PanguUltraMoEConfig) -> Tuple[int, int]:
    """(parameters of the model as configured without its MTP modules,
    parameters one token passes through)."""
    M, H = cfg.hidden_size, cfg.num_heads
    mla = (M * cfg.q_lora_rank + cfg.q_lora_rank
           + cfg.q_lora_rank * H * (cfg.qk_nope_head_dim
                                    + cfg.qk_rope_head_dim)
           + M * cfg.head_dim + cfg.kv_lora_rank
           + cfg.kv_lora_rank * H * (cfg.qk_nope_head_dim + cfg.v_head_dim)
           + H * cfg.v_head_dim * M)
    norms = (4 if cfg.sandwich_norm else 2) * M
    expert = 3 * M * cfg.intermediate_size
    fixed = 2 * cfg.vocab_size * M + M
    n_moe = 0
    for kind in cfg.ffn_kinds:
        fixed += mla + norms
        if kind == "dense":
            fixed += 3 * M * cfg.dense_intermediate_size
        else:
            n_moe += 1
            fixed += M * cfg.num_experts + 3 * M * cfg.shared_expert_size
    return (fixed + n_moe * cfg.held * expert,
            fixed + n_moe * cfg.experts_top_k * expert)


def _dense(cfg, f, name):
    return nn.Dense(f, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name=name)


class LatentAttention(nn.Module):
    """MLA in its expanded form: per-head keys and values from W_kvb.
    ``q_lora_rank`` None is a full-rank query (one ``q_proj``, no query
    norm) and ``use_rope`` false leaves the ``qk_rope_head_dim`` lanes
    without a position code (``models/kimi_linear.py`` is both)."""
    cfg: PanguUltraMoEConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        B, T, M = h.shape
        H, r = cfg.num_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        if cfg.q_lora_rank:
            cq = RMSNorm(cfg.rms_eps, cfg.dtype, name="q_a_norm")(
                _dense(cfg, cfg.q_lora_rank, "q_a_proj")(h))
            q = _dense(cfg, H * (dn + dr), "q_b_proj")(cq)
        else:
            q = _dense(cfg, H * (dn + dr), "q_proj")(h)
        q = q.reshape(B, T, H, dn + dr)
        ckv = _dense(cfg, r + dr, "kv_a_proj")(h)
        c = RMSNorm(cfg.rms_eps, cfg.dtype, name="kv_a_norm")(ckv[..., :r])
        k_r, q_r = ckv[..., None, r:], q[..., dn:]
        if getattr(cfg, "use_rope", True):
            pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
            k_r = apply_rope(k_r, pos, cfg.rope_theta)
            q_r = apply_rope(q_r, pos, cfg.rope_theta)
        kv = _dense(cfg, H * (dn + dv), "kv_b_proj")(c).reshape(
            B, T, H, dn + dv)
        qf = jnp.concatenate([q[..., :dn], q_r], -1)
        kf = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (B, T, H, dr))], -1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf).astype(jnp.float32) \
            * (dn + dr) ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
        y = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., dn:])
        return _dense(cfg, M, "o_proj")(y.reshape(B, T, H * dv))


class DenseMLP(nn.Module):
    cfg: PanguUltraMoEConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        W = cfg.dense_intermediate_size
        return _dense(cfg, cfg.hidden_size, "down_proj")(
            nn.silu(_dense(cfg, W, "gate_proj")(h))
            * _dense(cfg, W, "up_proj")(h))


class PanguBlock(nn.Module):
    cfg: PanguUltraMoEConfig
    ffn: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_eps, cfg.dtype,  # noqa: E731
                                    name=name)
        y = LatentAttention(cfg, name="attn")(norm("input_norm")(x))
        if cfg.sandwich_norm:
            y = norm("attn_branch_norm")(y)
        x = x + y
        h = norm("post_attn_norm")(x)
        if self.ffn == "dense":
            y = DenseMLP(cfg, name="mlp")(h)
        else:
            y = SolarSparseBlock(cfg, name="moe")(h)
            W = cfg.shared_expert_size
            y = y + _dense(cfg, cfg.hidden_size, "shared_down_proj")(
                nn.silu(_dense(cfg, W, "shared_gate_proj")(h))
                * _dense(cfg, W, "shared_up_proj")(h))
        if cfg.sandwich_norm:
            y = norm("mlp_branch_norm")(y)
        return x + y


class MTPModule(nn.Module):
    """One multi-token-prediction module: reads the model's last hidden
    stream at position t and the embedding of token t+1, predicts t+2."""
    cfg: PanguUltraMoEConfig

    @nn.compact
    def __call__(self, hidden, next_emb):
        cfg = self.cfg
        h = jnp.concatenate([
            RMSNorm(cfg.rms_eps, cfg.dtype, name="hnorm")(hidden),
            RMSNorm(cfg.rms_eps, cfg.dtype, name="enorm")(next_emb)], -1)
        x = _dense(cfg, cfg.hidden_size, "eh_proj")(h)
        x = PanguBlock(cfg, "moe", name="block")(x)
        return RMSNorm(cfg.rms_eps, jnp.float32, name="final_norm")(x)


class PanguUltraMoE(nn.Module):
    cfg: PanguUltraMoEConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False,
                 mtp: bool = False):
        """Logits [B, T, V]; with ``mtp`` also the first MTP module's
        logits [B, T - 1, V] (position t predicts token t + 2)."""
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed")
        x = embed(tokens)
        for i, ffn in enumerate(cfg.ffn_kinds):
            x = PanguBlock(cfg, ffn, name=f"layer_{i}")(x)
        head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype, name="lm_head")
        # every module is built (so ``init`` gives the whole tree); the
        # first one's logits are what ``mtp`` returns
        extra = [MTPModule(cfg, name=f"mtp_{i}")(x[:, :-1],
                                                 embed(tokens[:, 1:]))
                 for i in range(cfg.nextn_layers)]
        h = RMSNorm(cfg.rms_eps, jnp.float32, name="final_norm")(x)
        if return_hidden:
            return h
        logits = head(h.astype(jnp.float32))
        if mtp:
            return logits, head(extra[0].astype(jnp.float32))
        return logits


def make_model(cfg: PanguUltraMoEConfig):
    """(model, init_fn, loss_fn), the registry's contract. Training the
    family (the backward pass through MLA at scale, the MTP loss) is not
    this module's claim: the loss is the plain cross-entropy."""
    model = PanguUltraMoE(cfg)

    def init_fn(rng, batch_size: int = 2, seq_len: Optional[int] = None):
        T = seq_len or min(cfg.max_seq_len, 16)
        return model.init(rng, jnp.zeros((batch_size, T), jnp.int32))["params"]

    def loss_fn(params, batch, rng):
        tokens = batch["tokens"]
        logits = model.apply({"params": params}, tokens[:, :-1])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1))

    return model, init_fn, loss_fn
