"""MiniCPM-SALA family (``model_type: minicpm_sala``): a dense decoder whose
mixers follow ``mixer_types`` letter for letter, a quarter of them SPARSE
softmax attention that reads 64 selected blocks of 64 keys a query
(``minicpm4``: InfLLM-v2 block selection) and three quarters Lightning
linear attention over a constant-size state (``lightning-attn``).

* block, pre-norm, one residual stream, the family's muP scalings: ``x_0 =
  scale_emb E[token]``; ``x += r Mixer_l(RMSNorm(x))``; ``x += r
  SwiGLU(RMSNorm(x))`` with ``r = scale_depth / sqrt(num_hidden_layers)`` of
  the PUBLISHED depth (``depth_published``: a constant of the model, a cut
  in depth keeps it); logits ``= W_head (RMSNorm(x_L) / (hidden_size /
  dim_model_base))``.
* ``"lightning"``: ``q, k, v`` of ``lightning_heads`` heads of
  ``head_dim``; one RMSNorm over each head's lanes of q and of k (a learned
  scale of ``head_dim`` shared by the heads); rotate-half rotary code on q
  and k (``lightning_use_rope``); a head's state ``S [d_k, d_v]`` (float32,
  zero at a sequence's start) follows ``S_t = lambda_h S_{t-1} + k_t
  v_t^T``, ``o_t = S_t^T q_t / sqrt(head_dim)``, ``lambda_h = exp(-2^(-8 h
  / H))``, ``h = 1..H`` (:func:`lightning_log_decay`); an RMSNorm over each
  head's lanes of ``o`` under a learned scale of the whole width; ``y = W_o
  (o * sigmoid(W_g h))``. It IS ``ops/kernels/ssd.py``'s recurrence at ``dt
  = 1``, ``a = log lambda``, ``x = v``, ``B = k``, ``C = q / sqrt(d)``,
  ``D = 0``.
* ``"sparse"``: GQA, the same per-head q / k norms, NO position code
  (``attn_use_rope`` false), a sigmoid output gate. A query whose context
  ``n = t + 1`` is below ``dense_len`` attends causally over every key.
  Past it: compressed keys ``Kc_j = mean(k[stride j : stride j + kernel])``
  for every window wholly at or before ``t``; ``p_hj = softmax_j(q_h . Kc_j
  / sqrt(d))`` a query head; ``P_j`` their sum over the kv head's group; a
  block's score the maximum of ``P_j`` over the windows that overlap it;
  the first ``init_blocks`` blocks and the ``window_size / block_size``
  blocks ending at ``t``'s own are taken whatever their score; the
  ``topk`` best blocks (ties to the lower index) are the selection, one a
  kv head; softmax attention over the selected blocks' keys at or before
  ``t``. (:class:`SparseConfig`; the sizes are MiniCPM4.1's published
  ``sparse_config``, the family's convention: the catalog row has none.)

The flax module is the definition of the tree the ragged runner serves, and
its forward runs the definition at small sizes (tests, shape inference):
token-by-token recurrence, a dense selection a query.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ._lm_utils import make_causal_lm
from .llama import LlamaConfig, RMSNorm, apply_rope

#: ``mixer_types`` -> the runner's mixer kind
MIXER_TYPES = {"minicpm4": "sparse", "lightning-attn": "lightning"}


@dataclasses.dataclass(frozen=True)
class SparseConfig:
    """The block selection's sizes, in keys."""
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def __post_init__(self):
        s = self.kernel_stride
        if self.kernel_size != 2 * s or self.block_size % s \
                or self.window_size % self.block_size:
            raise ValueError(
                f"sparse_config: kernel_size must be 2 x kernel_stride (a "
                f"compressed key is the mean of two cached group means) and "
                f"block_size / window_size whole multiples of the stride / "
                f"the block, got {self}")

    @property
    def local_blocks(self) -> int:
        return self.window_size // self.block_size

    @property
    def rows_selected(self) -> int:
        """Keys a selection reads: ``topk`` whole blocks."""
        return self.topk * self.block_size


@dataclasses.dataclass(frozen=True)
class MiniCPMSALAConfig(LlamaConfig):
    attn_head_dim: int = 128
    #: "sparse" or "lightning", a layer
    layer_kinds: Tuple[str, ...] = ()
    qk_norm: Any = "head"
    use_rope: bool = False              # the SPARSE layers' (attn_use_rope)
    attn_gate: bool = True
    lightning_heads: int = 32
    lightning_rope: bool = True
    lightning_chunk: int = 128
    sparse: SparseConfig = SparseConfig()
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    #: the model's published depth (the residual scale reads it)
    depth_published: int = 32
    residual_dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim

    @property
    def embed_scale(self) -> float:
        return float(self.scale_emb)

    @property
    def residual_scale(self) -> float:
        return float(self.scale_depth) / math.sqrt(self.depth_published)

    @property
    def logit_divisor(self) -> float:
        return self.hidden_size / self.dim_model_base

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 512)
        kw.setdefault("num_layers", 4)
        kw.setdefault("num_heads", 4)
        kw.setdefault("num_kv_heads", 2)
        kw.setdefault("lightning_heads", 4)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("attn_head_dim", 16)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("dim_model_base", 16)
        kw.setdefault("depth_published", kw["num_layers"])
        kw.setdefault("lightning_chunk", 16)
        kw.setdefault("sparse", SparseConfig(4, 2, 8, 4, 1, 16, 64))
        kw.setdefault("layer_kinds", tuple(
            "sparse" if i % 4 == 0 else "lightning"
            for i in range(kw["num_layers"])))
        return MiniCPMSALAConfig(**kw)


def lightning_log_decay(heads: int) -> jnp.ndarray:
    """``log lambda_h = -2^(-8 h / H)``, ``h = 1..H`` [H] float32: the
    ALiBi slopes of the Lightning-Attention family, the same in every
    layer (no per-layer factor)."""
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return -jnp.exp2(-8.0 * h / heads)


def _head_norm(x, scale, eps):
    """RMSNorm over the last axis (a head's lanes), float32."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) \
        * scale


def lightning_inputs(p, h, pos, cfg: MiniCPMSALAConfig, dtype):
    """The recurrence's operands of one Lightning layer, as
    ``ops/kernels/ssd.py`` names them: h [S, C, M] -> (x = v [S, C, H, d],
    B = k, C = q / sqrt(d)), float32, q and k normed a head and rotated."""
    S, C, _ = h.shape
    H, d = cfg.lightning_heads, cfg.head_dim
    proj = lambda n: (h @ p[n]["kernel"].astype(dtype)).reshape(  # noqa: E731
        S, C, H, d)
    q = _head_norm(proj("q_proj"), p["q_norm"]["scale"], cfg.rms_eps)
    k = _head_norm(proj("k_proj"), p["k_norm"]["scale"], cfg.rms_eps)
    if cfg.lightning_rope:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    return proj("v_proj").astype(jnp.float32), k, q * d ** -0.5


def lightning_output(p, o, h, cfg: MiniCPMSALAConfig, dtype):
    """o [S, C, H, d] float32 -> the layer's output [S, C, M]: the norm a
    head under the whole-width scale, the sigmoid gate, ``W_o``."""
    S, C, H, d = o.shape
    o = _head_norm(o, p["o_norm"]["scale"].reshape(H, d), cfg.rms_eps)
    gate = jax.nn.sigmoid(
        (h @ p["g_proj"]["kernel"].astype(dtype)).astype(jnp.float32))
    y = (o.reshape(S, C, H * d) * gate).astype(dtype)
    return y @ p["o_proj"]["kernel"].astype(dtype)


def topk_mask(score, k: int):
    """The ``k`` largest of ``score`` [..., N] float32 as a bool mask,
    ties to the lower index, ``-inf`` never taken: what ``lax.top_k``
    would index, without the sort (a [4, 128, 2, 640] sort a query tile
    was 6 % of a refill round on the chip). The k-th largest value is
    found bit by bit over the floats' order-preserving integer keys: 32
    counts."""
    bits = jax.lax.bitcast_convert_type(score, jnp.int32)
    key = jnp.where(bits >= 0, bits, bits ^ jnp.int32(0x7FFFFFFF))
    key = jax.lax.bitcast_convert_type(key, jnp.uint32) \
        ^ jnp.uint32(0x80000000)
    t = jnp.zeros(score.shape[:-1] + (1,), jnp.uint32)
    for b in range(31, -1, -1):
        cand = t | jnp.uint32(1 << b)
        enough = jnp.sum(key >= cand, axis=-1, keepdims=True) >= k
        t = jnp.where(enough, cand, t)
    above = key > t
    tied = key == t
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (tied & (jnp.cumsum(tied, axis=-1) <= room))) \
        & (score > -jnp.inf)


def block_scores(scores, pos, sp: SparseConfig, num_blocks: int):
    """Steps 3-5 of the selection but for the top-k: scores [..., G, J],
    pos [...] as :func:`select_blocks` -> a block's score [..., NB]
    float32 (``+inf`` forced, ``-inf`` past the query's own block)."""
    r = sp.block_size // sp.kernel_stride
    w = sp.kernel_size // sp.kernel_stride
    j = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    valid = (sp.kernel_stride * j + sp.kernel_size - 1
             <= pos[..., None])[..., None, :]
    p = jax.nn.softmax(jnp.where(valid, scores, -jnp.inf), axis=-1)
    P = jnp.where(valid[..., 0, :], jnp.sum(jnp.where(valid, p, 0.0), -2),
                  -jnp.inf)                                  # [..., J]
    nd = P.ndim - 1
    score = jax.lax.reduce_window(
        P, -jnp.inf, jax.lax.max, (1,) * nd + (r + w - 1,),
        (1,) * nd + (r,), ((0, 0),) * nd + ((w - 1, 0),))    # [..., NB]
    b = jnp.arange(num_blocks, dtype=jnp.int32)
    tb = (pos // sp.block_size)[..., None]
    forced = (b < sp.init_blocks) | (b > tb - sp.local_blocks)
    score = jnp.where(forced, jnp.inf, score)
    return jnp.where(b <= tb, score, -jnp.inf)


def select_blocks(scores, pos, sp: SparseConfig, num_blocks: int):
    """The selection of one kv head's queries from their compressed
    scores. scores [..., G, J] float32: query head ``g``'s scaled score
    against compressed key ``j`` (window ``[stride j, stride j + kernel)``),
    ``J = num_blocks x block_size / stride``; pos [...] int32 the query's
    position. Returns blocks [..., K] int32, ``K = min(topk, num_blocks)``,
    ascending, ``-1`` where fewer exist at or before ``pos``."""
    return blocks_of_scores(block_scores(scores, pos, sp, num_blocks), sp)


def blocks_of_scores(score, sp: SparseConfig):
    """The top-k of a block's scores [..., NB] (``block_scores``', or the
    selection kernel's) as :func:`select_blocks` returns it."""
    num_blocks = score.shape[-1]
    K = min(sp.topk, num_blocks)
    # the mask, then each chosen block to its rank's place (ascending):
    # no sort (``lax.top_k`` over [96, 2, 640] was a sort of 0.47 ms a
    # layer and step on the chip)
    mask = topk_mask(score, K)
    rank = jnp.cumsum(mask, axis=-1) - 1
    b = jnp.arange(num_blocks, dtype=jnp.int32)
    place = mask[..., None] & (rank[..., None]
                               == jnp.arange(K, dtype=jnp.int32))
    idx = jnp.sum(jnp.where(place, b[:, None], 0), axis=-2)
    taken = jnp.sum(mask, axis=-1, keepdims=True)
    return jnp.where(jnp.arange(K, dtype=jnp.int32) < taken, idx,
                     -1).astype(jnp.int32)


def sparse_attention_dense(q, k, v, sp: SparseConfig):
    """The definition at small sizes: q [T, H, d], k, v [T, KV, d] of ONE
    sequence (float32) -> o [T, H, d]. Every query against every key under
    the mask its selection (or, below ``dense_len``, causality alone)
    gives."""
    T, H, d = q.shape
    KV = k.shape[1]
    G = H // KV
    s, ks, bs = sp.kernel_stride, sp.kernel_size, sp.block_size
    NB = -(-T // bs)
    J = NB * bs // s
    kp = jnp.pad(k, ((0, J * s + ks - T), (0, 0), (0, 0)))
    idx = s * jnp.arange(J)[:, None] + jnp.arange(ks)[None, :]
    kc = jnp.mean(kp[idx], axis=1)                           # [J, KV, d]
    qg = q.reshape(T, KV, G, d)
    cs = jnp.einsum("tkgd,jkd->tkgj", qg, kc) * d ** -0.5
    pos = jnp.arange(T, dtype=jnp.int32)
    blocks = select_blocks(cs, pos[:, None], sp, NB)         # [T, KV, K]
    chosen = (blocks[..., None] == jnp.arange(NB)).any(-2)   # [T, KV, NB]
    j = jnp.arange(T)
    mask = (j[None, :] <= pos[:, None])[:, None, :] \
        & (chosen[:, :, j // bs] | (pos + 1 < sp.dense_len)[:, None, None])
    sc = jnp.einsum("tkgd,jkd->tkgj", qg, k) * d ** -0.5
    p = jax.nn.softmax(jnp.where(mask[:, :, None, :], sc, -jnp.inf), -1)
    return jnp.einsum("tkgj,jkd->tkgd", p, v).reshape(T, H, d)


def param_count(cfg: MiniCPMSALAConfig) -> int:
    """Parameters of the model as configured."""
    M, d, F = cfg.hidden_size, cfg.head_dim, cfg.intermediate_size
    H, KV, LH = cfg.num_heads, cfg.num_kv_heads, cfg.lightning_heads
    sparse = M * d * (3 * H + 2 * KV) + 2 * d
    lightning = 5 * M * d * LH + 2 * d + d * LH
    n = sum(sparse if k == "sparse" else lightning for k in cfg.layer_kinds)
    return n + len(cfg.layer_kinds) * (3 * M * F + 2 * M) \
        + 2 * cfg.vocab_size * M + M


class _HeadNorm(nn.Module):
    """RMSNorm over each head's lanes under a learned scale of the whole
    width ``[H * d]`` (the Lightning layers' output norm)."""
    eps: float

    @nn.compact
    def __call__(self, o):
        H, d = o.shape[-2:]
        w = self.param("scale", nn.initializers.ones, (H * d,), jnp.float32)
        return _head_norm(o, w.reshape(H, d), self.eps)


class _Mixer(nn.Module):
    cfg: MiniCPMSALAConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, M = x.shape
        d = cfg.head_dim
        lightning = self.kind == "lightning"
        H = cfg.lightning_heads if lightning else cfg.num_heads
        KV = H if lightning else cfg.num_kv_heads
        dense = lambda feats, name: nn.Dense(              # noqa: E731
            feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        q = dense(H * d, "q_proj")(x).reshape(B, T, H, d)
        k = dense(KV * d, "k_proj")(x).reshape(B, T, KV, d)
        v = dense(KV * d, "v_proj")(x).reshape(B, T, KV, d)
        gate = jax.nn.sigmoid(dense(H * d, "g_proj")(x).astype(jnp.float32))
        q = RMSNorm(cfg.rms_eps, jnp.float32, name="q_norm")(q)
        k = RMSNorm(cfg.rms_eps, jnp.float32, name="k_norm")(k)
        v = v.astype(jnp.float32)
        if lightning:
            from ..ops.kernels.ssd import mamba2_recurrent
            if cfg.lightning_rope:
                pos = jnp.arange(T)[None, :]
                q = apply_rope(q, pos, cfg.rope_theta)
                k = apply_rope(k, pos, cfg.rope_theta)
            o, _ = mamba2_recurrent(
                v, jnp.ones((B, T, H), jnp.float32), lightning_log_decay(H),
                k, q * d ** -0.5, jnp.zeros((B, H, d, d), jnp.float32))
            o = _HeadNorm(cfg.rms_eps, name="o_norm")(o)
        else:
            o = jax.vmap(lambda a, b, c: sparse_attention_dense(
                a, b, c, cfg.sparse))(q, k, v)
        y = (o.reshape(B, T, H * d) * gate).astype(cfg.dtype)
        return dense(M, "o_proj")(y)


class _MLP(nn.Module):
    cfg: MiniCPMSALAConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        dense = lambda feats, name: nn.Dense(              # noqa: E731
            feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        m = jax.nn.silu(dense(cfg.intermediate_size, "gate_proj")(h)) \
            * dense(cfg.intermediate_size, "up_proj")(h)
        return dense(cfg.hidden_size, "down_proj")(m)


class _Block(nn.Module):
    cfg: MiniCPMSALAConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        r = cfg.residual_scale
        name = "lin" if self.kind == "lightning" else "attn"
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
        x = x + r * _Mixer(cfg, self.kind, name=name)(h).astype(x.dtype)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="post_attn_norm")(x)
        return x + r * _MLP(cfg, name="mlp")(h).astype(x.dtype)


class MiniCPMSALA(nn.Module):
    cfg: MiniCPMSALAConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="embed")(tokens)
        x = x.astype(jnp.float32) * cfg.embed_scale
        for i, kind in enumerate(cfg.layer_kinds):
            x = _Block(cfg, kind, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.rms_eps, jnp.float32, name="final_norm")(x) \
            / cfg.logit_divisor
        if return_hidden:
            return x
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype,
                        name="lm_head")(x.astype(jnp.float32))


def make_model(cfg: MiniCPMSALAConfig):
    """(model, init_fn, loss_fn), the registry's contract. Training the
    family is not this module's claim: the loss is the plain cross-entropy
    of the plain forward."""
    return make_causal_lm(MiniCPMSALA(cfg), cfg)
