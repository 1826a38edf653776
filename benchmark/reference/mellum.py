"""Plain reference of the Mellum 2 decoder: logits of a whole sequence.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, written from the published
``config.json`` of ``model_type: mellum`` (named in the configuration
file) and ISSUE 48's equations. Every layer is pre-norm with two
branches, no bias anywhere:

    x = x + Attn_l(rms(x));   x = x + MoE(rms(x))

then a final RMSNorm and an untied output head.

*Attention, layer l.* ``q = h Wq`` [H heads x D], ``k = h Wk``, ``v = h Wv``
[KV heads x D]; q and k normed A HEAD (an RMSNorm over each head's D lanes,
one learned scale of D shared by the heads) before the rotary code;
rotate-half over all D lanes; scores ``q k^T D^-1/2``, causal; query head h
reads kv head ``h // (H / KV)``; dense softmax over the whole sequence
under the layer's own mask, a block of heads at a time.

* a ``sliding`` layer: key j is visible to query i iff ``0 <= i - j <
  window`` (``window`` keys, the query's own among them); plain rotary,
  ``inv_freq_i = theta^(-2i/D)``.
* a ``full`` layer: every ``j <= i``; YaRN as ``transformers`` computes it:
  ``dim(n) = D ln(original / (2 pi n)) / (2 ln theta)``, ``low =
  max(floor(dim(beta_fast)), 0)``, ``high = min(ceil(dim(beta_slow)), D -
  1)``, ``ramp_i = clip((i - low) / (high - low), 0, 1)`` for i in 0 ..
  D/2 - 1, ``inv_freq_i = (1 - ramp_i) theta^(-2i/D) + ramp_i
  theta^(-2i/D) / factor``; cos and sin BOTH times ``attention_factor``.

*Sparse feed-forward.* ``p = softmax(h Wg)`` over ALL experts in float32,
the k largest kept and renormalised to sum 1; expert i is ``Wdown_i
(silu(Wgate_i h) * Wup_i h)``; every HELD expert runs on every token and
is masked by that choice, one expert's weights upcast at a time. No shared
expert.

The chip's share (``benchmark/configs/mellum2-12b-a2.5b.json``): the tree
holds experts ``first .. first + held`` of each layer, the layers of one
pipeline stage and the vocabulary slice it was built with; an expert held
elsewhere adds nothing here, in the engine and in this reference alike.
Those are the cut's departures from the published model: 8 of 28 layers,
32 of 64 experts a layer, 49,152 of 98,304 vocabulary rows. No cache, no
chunking, no kernel, no batching; nothing of the program under test is
imported.

Departures from the published description (each also under ``assumed`` in
the configuration file): none in the equations; what the config leaves
open and is assumed is the per-head QK-norm, the pre-norm two-branch block,
the float32 softmax router, YaRN's ``truncate`` default and rotate-half,
the window counted as ``transformers`` counts it, and the absent MTP head.
The keyword switches of :func:`hidden_states` are the WRONG models the
cell's ``why`` measures.

It reads the parameter tree ``models/mellum.py`` defines.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope_table(head_dim: int, theta: float, yarn=None):
    """(inv_freq [D/2], the factor on cos and sin). ``yarn`` = (factor,
    original_max, beta_fast, beta_slow, attention_factor) or None."""
    i = jnp.arange(head_dim // 2, dtype=F32)
    plain = theta ** (-2.0 * i / head_dim)
    if yarn is None:
        return plain, 1.0
    factor, original, beta_fast, beta_slow, attention_factor = yarn

    def dim(n):
        return head_dim * math.log(original / (2 * math.pi * n)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), head_dim - 1)
    ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return (1.0 - ramp) * plain + ramp * plain / factor, attention_factor


def _rope(x, inv_freq, factor):
    """x [B, T, H, D] at positions 0 .. T-1; halves rotated as a pair."""
    D = x.shape[-1]
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq[None]
    cos = (jnp.cos(ang) * factor)[None, :, None]
    sin = (jnp.sin(ang) * factor)[None, :, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, h, *, num_heads, kv_heads, rms_eps, window, inv_freq,
               factor, head_norm=True, head_block=8):
    B, T, _ = h.shape
    k_ = lambda n: p[n]["kernel"].astype(F32)           # noqa: E731
    D = k_("q_proj").shape[1] // num_heads
    q = (h @ k_("q_proj")).reshape(B, T, num_heads, D)
    k = (h @ k_("k_proj")).reshape(B, T, kv_heads, D)
    v = (h @ k_("v_proj")).reshape(B, T, kv_heads, D)
    if head_norm:
        q = _rms(q, p["q_norm"]["scale"], rms_eps)
        k = _rms(k, p["k_norm"]["scale"], rms_eps)
    q, k = _rope(q, inv_freq, factor), _rope(k, inv_freq, factor)
    k, v = (jnp.repeat(t, num_heads // kv_heads, axis=2) for t in (k, v))
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (i - j < window)
    outs = []
    for h0 in range(0, num_heads, head_block):
        hs = slice(h0, h0 + head_block)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, :, hs], k[:, :, hs])
        s = jnp.where(mask, s * D ** -0.5, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                               v[:, :, hs]))
    y = jnp.concatenate(outs, axis=2).reshape(B, T, num_heads * D)
    return y @ k_("o_proj")


def _sparse_mlp(p, h, *, top_k, first):
    """Every held expert on every token, masked by the top-k of ALL and
    renormalised over the k."""
    probs = jax.nn.softmax(h @ p["gate"].astype(F32), axis=-1)  # [B, T, E]
    kth = jnp.sort(probs, axis=-1)[..., -top_k][..., None]
    keep = jnp.where(probs >= kth, probs, 0.0)
    keep = keep / keep.sum(-1, keepdims=True)
    keep = jax.lax.dynamic_slice_in_dim(keep, first, p["wo"].shape[0],
                                        axis=-1)

    def one_expert(acc, expert):
        w_gate, w_up, w_down, weight = expert
        out = (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) \
            @ w_down.astype(F32)
        return acc + weight[..., None] * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (p["wi_gate"], p["wi_up"], p["wo"],
                         jnp.moveaxis(keep, -1, 0)))
    return y


def hidden_states(params, tokens, *, sliding, num_heads: int, kv_heads: int,
                  window: int, rope_theta: float, yarn, top_k: int,
                  rms_eps: float, experts_first: int = 0, layers: int = None,
                  window_on: str = "sliding", yarn_on: bool = True,
                  attention_factor_on: bool = True, head_norm: bool = True):
    """The residual stream [B, T, C] after ``layers`` whole layers (all of
    them when None), before the final norm. ``sliding`` [layers] says
    which layers are sliding ones. The wrong models: ``window_on`` "none"
    (the window left out of the sliding layers) or "all" (applied to the
    full layers too), ``yarn_on`` false (plain rotary on the full
    layers), ``attention_factor_on`` false, ``head_norm`` false."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"].astype(F32)[tokens]
        D = params["layer_0"]["attn"]["q_norm"]["scale"].shape[0]
        plain = rope_table(D, rope_theta)
        full = rope_table(D, rope_theta, yarn if yarn_on else None)
        if not attention_factor_on:
            full = (full[0], 1.0)
        n_layers = sum(1 for k in params if k.startswith("layer_"))
        for i in range(n_layers if layers is None else layers):
            p = params[f"layer_{i}"]
            slide = bool(sliding[i])
            windowed = {"sliding": slide, "none": False,
                        "all": True}[window_on]
            inv_freq, factor = plain if slide else full
            x = x + _attention(
                p["attn"], _rms(x, p["input_norm"]["scale"], rms_eps),
                num_heads=num_heads, kv_heads=kv_heads, rms_eps=rms_eps,
                window=window if windowed else None, inv_freq=inv_freq,
                factor=factor, head_norm=head_norm)
            x = x + _sparse_mlp(
                p["moe"], _rms(x, p["post_attn_norm"]["scale"], rms_eps),
                top_k=top_k, first=experts_first)
        return x


def logits(params, tokens, at, **dims):
    """Logits [B, n, vocab] at the positions ``at`` [B, n] of ``tokens``
    [B, T] (tokens to the right of a position never reach it)."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, **dims)
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        x = _rms(x, params["final_norm"]["scale"], dims["rms_eps"])
        return x @ params["lm_head"]["kernel"].astype(F32)
