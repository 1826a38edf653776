"""Solar-Open2 family (``model_type: solar_open2``): a hybrid decoder whose
layers follow a per-layer list of mixer kinds.

* ``"attn"`` layers (``gqa_layers``): softmax GQA attention with NO
  position code at all (``use_rope`` false) and an elementwise sigmoid
  output gate, ``y = W_o (attn * sigmoid(W_g h))``.
* ``"kda"`` layers (the others): gated delta-rule linear attention
  (``ops/kernels/delta_rule.py``): q / k / v through a causal depthwise
  convolution of ``kda_conv`` taps and SiLU, q and k L2-normalised per
  head, a per-channel decay ``g = -exp(A) softplus(W_f2 W_f1 h + b)``
  (low rank), a step size ``beta = 2 sigmoid(W_b h)`` (the 2 is
  ``kda_allow_neg_eigval``), a per-head RMSNorm on the output and a
  low-rank sigmoid gate before ``W_o``.
* every layer ends in a sparse block: sigmoid router scores over all
  ``num_experts``, the ``experts_top_k`` largest of ``score + bias``
  taken, their scores renormalised and scaled by ``routed_scaling``,
  plus one always-on, ungated shared expert.

``experts_held`` < ``num_experts`` is one chip's share of a layer whose
experts are divided over chips: the router still scores and selects over
all experts, the layer holds (and computes) experts
``experts_first .. experts_first + experts_held`` and the others add
nothing here (they add their part on their own chips).

The flax module is the training-side definition of the tree the ragged
runner serves (``inference/v2/llama_runner.py`` reads the same names); its
forward runs the token-by-token recurrence and every held expert densely,
for small sizes (tests, shape inference), not for speed.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .llama import RMSNorm
from .mixtral import MixtralConfig


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config(MixtralConfig):
    attn_head_dim: int = 128
    #: mixer kind of each layer, "attn" or "kda"
    layer_kinds: Tuple[str, ...] = ()
    use_rope: bool = False
    attn_gate: bool = True
    kda_heads: int = 64
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_rank: int = 128              # the decay's and the gate's low rank
    kda_neg_eigval: bool = True      # beta = 2 sigmoid(.)
    router_score: str = "sigmoid"
    router_bias: bool = True         # selection-only bias
    routed_scaling: float = 1.0
    shared_expert_gated: bool = False
    experts_held: Optional[int] = None   # None = all of them
    experts_first: int = 0

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim

    @property
    def residual_dtype(self):
        """The serving residual stream is float32 (the runner's default
        is the compute dtype): the norms read an unrounded stream."""
        return jnp.float32

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("num_layers", 4)
        kw.setdefault("num_heads", 4)
        kw.setdefault("num_kv_heads", 2)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("attn_head_dim", 16)
        kw.setdefault("intermediate_size", 32)
        kw.setdefault("shared_expert_size", 32)
        kw.setdefault("num_experts", 8)
        kw.setdefault("experts_top_k", 2)
        kw.setdefault("kda_heads", 4)
        kw.setdefault("kda_head_dim", 16)
        kw.setdefault("kda_rank", 16)
        kw.setdefault("layer_kinds", tuple(
            "attn" if i % 4 == 0 else "kda"
            for i in range(kw["num_layers"])))
        return SolarOpen2Config(**kw)


def mixer_param_count(cfg: SolarOpen2Config, kind: str) -> int:
    M = cfg.hidden_size
    if kind == "attn":
        qo = cfg.num_heads * cfg.head_dim
        kv = cfg.num_kv_heads * cfg.head_dim
        return M * (2 * qo + 2 * kv) + (M * qo if cfg.attn_gate else 0)
    w = cfg.kda_heads * cfg.kda_head_dim
    return (4 * M * w + 3 * cfg.kda_conv * w + 2 * (M + w) * cfg.kda_rank
            + w + cfg.kda_heads + M * cfg.kda_heads + cfg.kda_head_dim)


def param_counts(cfg: SolarOpen2Config) -> Tuple[int, int]:
    """(parameters of the model as configured, parameters one token
    passes through): embedding and head, the mixers, and of the routed
    experts all that are held against ``experts_top_k``."""
    M, W = cfg.hidden_size, cfg.intermediate_size
    expert = 3 * M * W
    shared = 3 * M * cfg.shared_expert_size
    fixed = 2 * cfg.vocab_size * M + M
    for kind in cfg.layer_kinds:
        fixed += mixer_param_count(cfg, kind) + 2 * M + shared \
            + M * cfg.num_experts + cfg.num_experts
    n = len(cfg.layer_kinds)
    return (fixed + n * cfg.held * expert,
            fixed + n * cfg.experts_top_k * expert)


def short_conv(x, w, prev):
    """Causal depthwise convolution along the sequence. x [B, T, C];
    w [K, C] (tap K-1 multiplies the current position); prev [B, K-1, C]
    the inputs before x[:, 0]. Returns (y [B, T, C], the padded inputs
    [B, K-1+T, C], whose last K-1 rows are the next call's ``prev``)."""
    K = w.shape[0]
    xp = jnp.concatenate([prev.astype(x.dtype), x], axis=1)
    T = x.shape[1]
    y = sum(xp[:, j:j + T] * w[j].astype(x.dtype) for j in range(K))
    return y, xp


def conv_silu(pre, w, prev, bias=None):
    """The activated short convolution of a recurrent layer: pre
    [B, T, C] float32, w [K, C], prev [B, K-1, C] (:func:`short_conv`),
    bias [C] or None. Returns (silu(conv + bias) [B, T, C], the padded
    inputs)."""
    y, padded = short_conv(pre, w, prev.astype(jnp.float32))
    if bias is not None:
        y = y + bias
    return jax.nn.silu(y), padded


def kda_conv_inputs(p, h, dtype):
    """What the short convolution of a KDA layer takes: (pre
    [B, T, 3*H*dk] float32 the projections of q | k | v, w [K, 3*H*dk]
    float32 their taps).

    The matmuls take ``dtype`` operands and give float32: what feeds the
    recurrence (the convolution, the decay's pre-activation, the step
    size) is not rounded to ``dtype`` on the way, because a rounding of
    the decay compounds over every later position of the sequence."""
    f32 = jnp.float32
    pre = jnp.concatenate(
        [jnp.matmul(h, p[n].astype(dtype), preferred_element_type=f32)
         for n in ("q_proj", "k_proj", "v_proj")], -1)
    w = jnp.concatenate([p["q_conv"], p["k_conv"], p["v_conv"]], -1)
    return pre, w.astype(f32)


def l2_normed(t):
    """t / |t|_2 over the last axis (a head's lanes), 1e-6 under the
    root."""
    return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)


def delta_beta(p, h, neg_eigval: bool, dtype):
    """The delta rule's step size a head, float32: ``sigmoid(W_b h)``,
    doubled where the family allows negative eigenvalues."""
    beta = jax.nn.sigmoid(jnp.matmul(h, p["b_proj"].astype(dtype),
                                     preferred_element_type=jnp.float32))
    return 2.0 * beta if neg_eigval else beta


def kda_recurrence_inputs(p, h, y, cfg: SolarOpen2Config, dtype):
    """From the activated convolution y [B, T, 3*H*dk] float32 and the
    normed residual h to the recurrence's inputs: (q, k [B, T, H, dk],
    v [B, T, H, dv], g [B, T, H, dk] float32, beta [B, T, H] float32)."""
    B, T, _ = h.shape
    H, d = cfg.kda_heads, cfg.kda_head_dim
    f32 = jnp.float32
    mm = lambda x, w: jnp.matmul(x, w.astype(dtype),     # noqa: E731
                                 preferred_element_type=f32)
    q, k, v = (t.reshape(B, T, H, d) for t in jnp.split(y, 3, axis=-1))
    q, k = l2_normed(q) * d ** -0.5, l2_normed(k)
    f = jnp.matmul(mm(h, p["f_a"]), p["f_b"].astype(f32),
                   precision=jax.lax.Precision.HIGHEST)
    g = -jnp.exp(p["A_log"].astype(f32))[:, None] \
        * jax.nn.softplus(f.reshape(B, T, H, d)
                          + p["dt_bias"].astype(f32).reshape(H, d))
    return q, k, v, g, delta_beta(p, h, cfg.kda_neg_eigval, dtype)


def kda_inputs(p, h, cfg: SolarOpen2Config, conv_prev, dtype):
    """From the normed residual h [B, T, M] to the recurrence's inputs
    (:func:`kda_recurrence_inputs`) and the padded conv inputs
    [B, K-1+T, 3*H*dk]. ``conv_prev`` [B, K-1, 3*H*dk] holds the last
    inputs of q | k | v."""
    pre, w = kda_conv_inputs(p, h, dtype)
    y, padded = conv_silu(pre, w, conv_prev)
    return kda_recurrence_inputs(p, h, y, cfg, dtype) + (padded,)


def kda_output(p, o, h, cfg: SolarOpen2Config, dtype):
    """o [B, T, H, dv] float32 -> the mixer's output [B, T, M]."""
    B, T, H, d = o.shape
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.rms_eps) \
        * p["o_norm"].astype(jnp.float32)
    gate = jax.nn.sigmoid(jnp.matmul(
        jnp.matmul(h, p["g_a"].astype(dtype),
                   preferred_element_type=jnp.float32),
        p["g_b"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST))
    y = (o.reshape(B, T, H * d) * gate).astype(dtype)
    return y @ p["o_proj"].astype(dtype)


class KDAMixer(nn.Module):
    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        B, T, M = h.shape
        H, d, K, r = (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv,
                      cfg.kda_rank)
        w = H * d
        kern = lambda name, shape: self.param(             # noqa: E731
            name, nn.initializers.lecun_normal(), shape, cfg.param_dtype)
        conv = lambda name: self.param(                    # noqa: E731
            name, nn.initializers.normal(K ** -0.5), (K, w),
            cfg.param_dtype)
        p = {"q_proj": kern("q_proj", (M, w)), "k_proj": kern("k_proj", (M, w)),
             "v_proj": kern("v_proj", (M, w)), "o_proj": kern("o_proj", (w, M)),
             "f_a": kern("f_a", (M, r)), "f_b": kern("f_b", (r, w)),
             "g_a": kern("g_a", (M, r)), "g_b": kern("g_b", (r, w)),
             "b_proj": kern("b_proj", (M, H)),
             "q_conv": conv("q_conv"), "k_conv": conv("k_conv"),
             "v_conv": conv("v_conv"),
             "A_log": self.param("A_log", nn.initializers.zeros, (H,),
                                 jnp.float32),
             "dt_bias": self.param("dt_bias", nn.initializers.zeros, (w,),
                                   jnp.float32),
             "o_norm": self.param("o_norm", nn.initializers.ones, (d,),
                                  jnp.float32)}
        from ..ops.kernels.delta_rule import kda_recurrent
        q, k, v, g, beta, _ = kda_inputs(
            p, h, cfg, jnp.zeros((B, K - 1, 3 * w), h.dtype), cfg.dtype)
        o, _ = kda_recurrent(q, k, v, g, beta,
                             jnp.zeros((B, H, d, d), jnp.float32))
        return kda_output(p, o, h, cfg, cfg.dtype)


class GatedNoPEAttention(nn.Module):
    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        B, T, M = h.shape
        H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dense = lambda f, name: nn.Dense(                  # noqa: E731
            f, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        q = dense(H * D, "q_proj")(h).reshape(B, T, H, D)
        k = dense(KV * D, "k_proj")(h).reshape(B, T, KV, D)
        v = dense(KV * D, "v_proj")(h).reshape(B, T, KV, D)
        k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
        y = jax.nn.dot_product_attention(q, k, v, is_causal=True)
        y = y.reshape(B, T, H * D)
        if cfg.attn_gate:
            y = y * jax.nn.sigmoid(
                dense(H * D, "g_proj")(h).astype(jnp.float32)
            ).astype(cfg.dtype)
        return dense(M, "o_proj")(y)


class SolarSparseBlock(nn.Module):
    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        B, T, M = h.shape
        E, W, n = cfg.num_experts, cfg.intermediate_size, cfg.held
        init = nn.initializers.lecun_normal()
        gate = self.param("gate", init, (M, E), jnp.float32)
        bias = self.param("sel_bias", nn.initializers.zeros, (E,),
                          jnp.float32) if cfg.router_bias else None
        wi_gate = self.param("wi_gate", init, (n, M, W), cfg.param_dtype)
        wi_up = self.param("wi_up", init, (n, M, W), cfg.param_dtype)
        wo = self.param("wo", init, (n, W, M), cfg.param_dtype)
        x = h.reshape(B * T, M)
        from ..moe.sharded_moe import route_topk
        idx, wts, _ = route_topk(x.astype(jnp.float32) @ gate,
                              cfg.experts_top_k, score=cfg.router_score,
                              bias=bias, normalize=cfg.norm_topk_prob,
                              scale=cfg.routed_scaling,
                              norm_eps=getattr(cfg, "router_norm_eps",
                                               1e-20))
        # [N, E]: each token's weight on every expert; this share's slice
        dense_w = jnp.zeros((B * T, E), jnp.float32).at[
            jnp.arange(B * T)[:, None], idx].add(wts)
        dense_w = jax.lax.dynamic_slice_in_dim(dense_w, cfg.experts_first,
                                               n, axis=1)
        xe = x.astype(cfg.dtype)
        up = jnp.einsum("nm,emw->enw", xe, wi_up.astype(cfg.dtype))
        gt = jnp.einsum("nm,emw->enw", xe, wi_gate.astype(cfg.dtype))
        out = jnp.einsum("enw,ewm->enm", jax.nn.silu(gt) * up,
                         wo.astype(cfg.dtype))
        y = jnp.einsum("enm,ne->nm", out.astype(jnp.float32), dense_w)
        return y.astype(cfg.dtype).reshape(B, T, M)


class SolarOpen2Block(nn.Module):
    cfg: SolarOpen2Config
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
        if self.kind == "attn":
            x = x + GatedNoPEAttention(cfg, name="attn")(h)
        else:
            x = x + KDAMixer(cfg, name="kda")(h)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x)
        y = SolarSparseBlock(cfg, name="moe")(h)
        if cfg.shared_expert_size:
            dense = lambda f, name: nn.Dense(              # noqa: E731
                f, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name=name)
            y = y + dense(cfg.hidden_size, "shared_down_proj")(
                nn.silu(dense(cfg.shared_expert_size, "shared_gate_proj")(h))
                * dense(cfg.shared_expert_size, "shared_up_proj")(h))
        return x + y


class SolarOpen2(nn.Module):
    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed")(tokens)
        for i, kind in enumerate(cfg.layer_kinds):
            x = SolarOpen2Block(cfg, kind, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.rms_eps, jnp.float32, name="final_norm")(x)
        if return_hidden:
            return x
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype,
                        name="lm_head")(x.astype(jnp.float32))


def make_model(cfg: SolarOpen2Config):
    """(model, init_fn, loss_fn), the registry's contract. Training the
    family (the backward pass of the scan at scale) is not this module's
    claim: the loss is the plain cross-entropy of the plain forward."""
    model = SolarOpen2(cfg)

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
