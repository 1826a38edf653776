"""Nemotron-H family (``model_type: nemotron_h``): a hybrid decoder whose
layers are ONE branch each, a mixer alone or a feed-forward alone, read
letter by letter from ``hybrid_override_pattern``.

* every layer is ``x += f(RMSNorm(x))``: one norm, one branch, one add.
  The two per-layer lists the runner reads say which: ``layer_kinds[i]`` is
  a mixer kind or None, ``ffn_kinds[i]`` a feed-forward kind or None, and
  the tree holds the norm of the branch that is there (``input_norm``
  before a mixer, ``post_attn_norm`` before a feed-forward).
* ``M``, ``"mamba2"``: a selective state-space mixer (Mamba-2;
  ``ops/kernels/ssd.py`` has the recurrence). ``[z | xBC | dt] = h W_in``;
  ``xBC`` through a causal depthwise convolution of ``mamba_conv`` taps
  WITH bias and SiLU, then split ``[x | B | C]``; ``dt = softplus(dt +
  dt_bias)``; ``a = -exp(A_log)`` a head; the state ``[heads, head_dim,
  state]`` a sequence; the skip ``D x``; the gate ``y * silu(z)`` BEFORE
  an RMSNorm over each of ``mamba_groups`` groups of channels; ``W_out``.
* ``*``, ``"attn"``: softmax GQA with no position code at all
  (``use_rope`` false), no bias, no gate.
* ``E``, ``"moe"``: sigmoid scores over all ``num_experts`` in float32,
  the ``experts_top_k`` largest of ``score + sel_bias`` taken (one group),
  their scores renormalised and scaled by ``routed_scaling``; UNGATED
  experts ``W_down relu(W_up h)^2`` (two matrices, ``mlp_act`` relu2);
  plus one always-on shared expert of the same form.
* ``-`` (a dense ``relu2`` feed-forward) is refused by name: no published
  pattern the benchmark runs has one and the runner's dense branch is
  SwiGLU.

``experts_held`` < ``num_experts`` is one chip's share of a layer
(``models/solar_open2.py`` says how). The tree stores each held expert at
``expert_width_stored``, the width rounded up to whole 128-lane groups
(1856 -> 1920), with zero columns of ``W_up`` and zero rows of ``W_down``:
exact, since ``relu(0)^2 = 0`` meets a zero row, and what lets the
grouped decode kernel stream the stacks in lane-aligned chunks
(``ops/kernels/grouped_ffn.py``; PERF.md section 6, PR 44, has the
reading). ``pad_experts`` brings a stack at the published width to it.

The flax module is the definition of the tree the ragged runner serves;
its forward runs the token-by-token recurrence and every held expert
densely, for small sizes (tests, shape inference).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ._lm_utils import make_causal_lm
from .llama import RMSNorm
from .mixtral import MixtralConfig
from .solar_open2 import GatedNoPEAttention, conv_silu

#: the pattern's letters: (mixer kind, feed-forward kind) of a layer
PATTERN = {"M": ("mamba2", None), "*": ("attn", None), "E": (None, "moe")}


def kinds_from_pattern(pattern: str):
    """(layer_kinds, ffn_kinds) of ``hybrid_override_pattern``."""
    bad = sorted(set(pattern) - set(PATTERN))
    if bad:
        raise ValueError(
            f"nemotron_h hybrid_override_pattern letters {bad} are not "
            f"supported (M: Mamba-2, E: sparse feed-forward, *: attention; "
            f"'-' is a dense relu2 feed-forward, which no pattern served "
            f"here has)")
    pairs = [PATTERN[c] for c in pattern]
    return tuple(m for m, _ in pairs), tuple(f for _, f in pairs)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(MixtralConfig):
    attn_head_dim: int = 128
    #: a mixer kind ("mamba2", "attn") or None, a layer
    layer_kinds: Tuple[Optional[str], ...] = ()
    #: a feed-forward kind ("moe") or None, a layer
    ffn_kinds: Tuple[Optional[str], ...] = ()
    use_rope: bool = False
    attn_gate: bool = False
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_groups: int = 8
    mamba_state: int = 128
    mamba_conv: int = 4
    mamba_chunk: int = 128
    gated_experts: bool = False
    mlp_act: str = "relu2"
    router_score: str = "sigmoid"
    router_bias: bool = True             # selection-only bias
    routed_scaling: float = 2.5
    shared_expert_gated: bool = False
    experts_held: Optional[int] = None   # None = all of them
    experts_first: int = 0

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim

    @property
    def residual_dtype(self):
        """The serving residual stream is float32, as the two other
        hybrid families': the norms read an unrounded stream."""
        return jnp.float32

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_width(self) -> int:
        """Channels through the convolution: x | B | C."""
        return self.mamba_inner + 2 * self.mamba_groups * self.mamba_state

    @property
    def expert_width_stored(self) -> int:
        """``intermediate_size`` rounded up to whole 128-lane groups."""
        return -(-self.intermediate_size // 128) * 128

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 256)
        kw.setdefault("num_heads", 4)
        kw.setdefault("num_kv_heads", 2)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("attn_head_dim", 16)
        kw.setdefault("mamba_heads", 4)
        kw.setdefault("mamba_head_dim", 8)
        kw.setdefault("mamba_groups", 2)
        kw.setdefault("mamba_state", 16)
        kw.setdefault("mamba_chunk", 8)
        kw.setdefault("intermediate_size", 40)
        kw.setdefault("shared_expert_size", 48)
        kw.setdefault("num_experts", 8)
        kw.setdefault("experts_top_k", 2)
        kinds, ffn = kinds_from_pattern(kw.pop("pattern", "MEM*EME"))
        kw.setdefault("layer_kinds", kinds)
        kw.setdefault("ffn_kinds", ffn)
        kw.setdefault("num_layers", len(kw["layer_kinds"]))
        return NemotronHConfig(**kw)


def pad_experts(wi, wo):
    """Expert stacks ``wi [E, M, F]``, ``wo [E, F, M]`` at the published
    width to the stored one (F rounded up to whole 128-lane groups): zero
    columns of ``wi``, zero rows of ``wo``. numpy stacks (a checkpoint's)
    stay numpy."""
    extra = (-wi.shape[-1]) % 128
    if extra == 0:
        return wi, wo
    xp = np if isinstance(wi, np.ndarray) else jnp
    return (xp.pad(wi, ((0, 0), (0, 0), (0, extra))),
            xp.pad(wo, ((0, 0), (0, extra), (0, 0))))


def param_counts(cfg: NemotronHConfig) -> Tuple[int, int]:
    """(parameters of the model as configured, parameters one token
    passes through), at the PUBLISHED expert width (the stored zeros are
    not parameters): embedding and head, the mixers, the routers and
    shared experts, and of the routed experts all that are held against
    ``experts_top_k``."""
    M = cfg.hidden_size
    expert = 2 * M * cfg.intermediate_size
    d_in, H = cfg.mamba_inner, cfg.mamba_heads
    mamba = M * (2 * d_in + 2 * cfg.mamba_groups * cfg.mamba_state + H) \
        + (cfg.mamba_conv + 1) * cfg.mamba_conv_width + 3 * H + d_in \
        + d_in * M
    attn = 2 * M * (cfg.num_heads + cfg.num_kv_heads) * cfg.head_dim
    fixed = 2 * cfg.vocab_size * M + M
    n_moe = 0
    for kind, ffn in zip(cfg.layer_kinds, cfg.ffn_kinds):
        fixed += M + {"mamba2": mamba, "attn": attn, None: 0}[kind]
        if ffn == "moe":
            n_moe += 1
            fixed += M * cfg.num_experts + cfg.num_experts \
                + 2 * M * cfg.shared_expert_size
    return (fixed + n_moe * cfg.held * expert,
            fixed + n_moe * cfg.experts_top_k * expert)


def mamba2_conv_inputs(p, h, cfg: NemotronHConfig, dtype):
    """The one input projection of a Mamba-2 layer, split: (z [B, T, d_in]
    the gate's pre-activation, xbc [B, T, d_in + 2 G N] what the short
    convolution takes, dt [B, T, H] before its bias), all float32.

    The matmul takes ``dtype`` operands and gives float32: what feeds the
    recurrence is not rounded to ``dtype`` on the way, because a rounding
    of the step compounds over every later position of the sequence."""
    d_in = cfg.mamba_inner
    zxbcdt = jnp.matmul(h, p["in_proj"].astype(dtype),
                        preferred_element_type=jnp.float32)
    return jnp.split(zxbcdt, [d_in, d_in + cfg.mamba_conv_width], -1)


def mamba2_recurrence_inputs(p, xbc, dt, cfg: NemotronHConfig):
    """From the activated convolution xbc [B, T, d_in + 2 G N] and the
    step's pre-activation dt [B, T, H] to the recurrence's inputs:
    (x [B, T, H, P], B and C [B, T, H, N] a HEAD (each group's repeated
    over its heads), dt [B, T, H] after the softplus), all float32."""
    Bsz, T, _ = xbc.shape
    H, P = cfg.mamba_heads, cfg.mamba_head_dim
    G, N, d_in = cfg.mamba_groups, cfg.mamba_state, cfg.mamba_inner
    x, Bm, Cm = jnp.split(xbc, [d_in, d_in + G * N], -1)
    heads = lambda t: jnp.repeat(                          # noqa: E731
        t.reshape(Bsz, T, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    return x.reshape(Bsz, T, H, P), heads(Bm), heads(Cm), dt


def mamba2_inputs(p, h, cfg: NemotronHConfig, conv_prev, dtype):
    """From the normed residual h [B, T, M] to (z, the recurrence's inputs
    (:func:`mamba2_recurrence_inputs`), the padded conv inputs
    [B, K-1+T, d_in + 2 G N]). ``conv_prev`` [B, K-1, ..] holds the last
    inputs of x | B | C."""
    f32 = jnp.float32
    z, xbc, dt = mamba2_conv_inputs(p, h, cfg, dtype)
    xbc, padded = conv_silu(xbc, p["conv_w"].astype(f32), conv_prev,
                            p["conv_b"].astype(f32))
    return (z,) + mamba2_recurrence_inputs(p, xbc, dt, cfg) + (padded,)


def mamba2_output(p, y, z, cfg: NemotronHConfig, dtype):
    """y [B, T, H, P] float32 (the skip term in it), z [B, T, d_in] ->
    the mixer's output [B, T, M]: the gate first, then the RMSNorm over
    each group of ``d_in / groups`` channels."""
    Bsz, T = y.shape[:2]
    G = cfg.mamba_groups
    y = y.reshape(Bsz, T, G, -1) * jax.nn.silu(z).reshape(Bsz, T, G, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.rms_eps)
    y = y.reshape(Bsz, T, -1) * p["norm"].astype(jnp.float32)
    return y.astype(dtype) @ p["out_proj"].astype(dtype)


class Mamba2Mixer(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        Bsz, T, M = h.shape
        H, P, N, K = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state,
                      cfg.mamba_conv)
        d_in, W = cfg.mamba_inner, cfg.mamba_conv_width
        kern = lambda name, shape: self.param(             # noqa: E731
            name, nn.initializers.lecun_normal(), shape, cfg.param_dtype)
        vec = lambda name, init, n: self.param(            # noqa: E731
            name, init, (n,), jnp.float32)
        p = {"in_proj": kern("in_proj", (M, d_in + W + H)),
             "out_proj": kern("out_proj", (d_in, M)),
             "conv_w": self.param("conv_w",
                                  nn.initializers.normal(K ** -0.5),
                                  (K, W), cfg.param_dtype),
             "conv_b": vec("conv_b", nn.initializers.zeros, W),
             "dt_bias": vec("dt_bias", nn.initializers.zeros, H),
             "A_log": vec("A_log", nn.initializers.zeros, H),
             "D": vec("D", nn.initializers.ones, H),
             "norm": vec("norm", nn.initializers.ones, d_in)}
        from ..ops.kernels.ssd import mamba2_recurrent
        z, x, Bm, Cm, dt, _ = mamba2_inputs(
            p, h, cfg, jnp.zeros((Bsz, K - 1, W), h.dtype), cfg.dtype)
        y, _ = mamba2_recurrent(x, dt, -jnp.exp(p["A_log"]), Bm, Cm,
                                jnp.zeros((Bsz, H, P, N), jnp.float32))
        return mamba2_output(p, y + p["D"][:, None] * x, z, cfg, cfg.dtype)


class NemotronSparseBlock(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        Bsz, T, M = h.shape
        E, W, n = cfg.num_experts, cfg.expert_width_stored, cfg.held
        init = nn.initializers.lecun_normal()
        gate = self.param("gate", init, (M, E), jnp.float32)
        bias = self.param("sel_bias", nn.initializers.zeros, (E,),
                          jnp.float32)
        F = cfg.intermediate_size       # the stored tail starts as zeros
        wi = self.param("wi", lambda *a: init(*a).at[..., F:].set(0),
                        (n, M, W), cfg.param_dtype)
        wo = self.param("wo", lambda *a: init(*a).at[:, F:].set(0),
                        (n, W, M), cfg.param_dtype)
        x = h.reshape(Bsz * T, M)
        from ..moe.sharded_moe import route_topk
        idx, wts, _ = route_topk(x.astype(jnp.float32) @ gate,
                                 cfg.experts_top_k, score=cfg.router_score,
                                 bias=bias, normalize=cfg.norm_topk_prob,
                                 scale=cfg.routed_scaling)
        dense_w = jnp.zeros((Bsz * T, E), jnp.float32).at[
            jnp.arange(Bsz * T)[:, None], idx].add(wts)
        dense_w = jax.lax.dynamic_slice_in_dim(dense_w, cfg.experts_first,
                                               n, axis=1)
        up = jnp.einsum("nm,emw->enw", x.astype(cfg.dtype),
                        wi.astype(cfg.dtype))
        out = jnp.einsum("enw,ewm->enm", relu2(up), wo.astype(cfg.dtype))
        y = jnp.einsum("enm,ne->nm", out.astype(jnp.float32), dense_w)
        return y.astype(cfg.dtype).reshape(Bsz, T, M)


class NemotronHBlock(nn.Module):
    cfg: NemotronHConfig
    kind: Optional[str]
    ffn: Optional[str]

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        if self.kind is not None:
            h = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
            x = x + (GatedNoPEAttention(cfg, name="attn")(h)
                     if self.kind == "attn"
                     else Mamba2Mixer(cfg, name="mamba")(h))
        if self.ffn is not None:
            h = RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x)
            dense = lambda f, name: nn.Dense(              # noqa: E731
                f, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name=name)
            x = x + NemotronSparseBlock(cfg, name="moe")(h) \
                + dense(cfg.hidden_size, "shared_down_proj")(
                    relu2(dense(cfg.shared_expert_size,
                                "shared_up_proj")(h)))
        return x


class NemotronH(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed")(tokens)
        for i, (kind, ffn) in enumerate(zip(cfg.layer_kinds,
                                            cfg.ffn_kinds)):
            x = NemotronHBlock(cfg, kind, ffn, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.rms_eps, jnp.float32, name="final_norm")(x)
        if return_hidden:
            return x
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype,
                        name="lm_head")(x.astype(jnp.float32))


def make_model(cfg: NemotronHConfig):
    """(model, init_fn, loss_fn), the registry's contract. Training the
    family (the backward scan of the state-space layers at scale) is not
    this module's claim: the loss is the plain cross-entropy of the plain
    forward."""
    return make_causal_lm(NemotronH(cfg), cfg)
