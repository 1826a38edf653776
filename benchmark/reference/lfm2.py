"""Plain reference of the LFM2 decoder (``lfm2_moe`` and the dense
``lfm2``): logits of a whole sequence.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. The conv layer, the attention
layer, the norms and the wiring were read from the family's own published
code (``transformers`` 4.57.6, ``models/lfm2/modeling_lfm2.py``, the dense
sibling: ``tests/unit/test_lfm2.py`` holds this file to it to 1e-4); the
sparse block is the ``lfm2_moe`` family's published one, which is not in
that version, and is ``assumed`` in the configuration file. ISSUE 59's
equations:

``x_0 = E[token]``. Layer ``l``, pre-norm, one residual stream, no bias
anywhere:

    h = x + mixer_l(RMSNorm_op(x));   x' = h + ffn_l(RMSNorm_ffn(h))

Output: ``logits = RMSNorm_out(x_L) E^T`` (the family calls the final norm
``embedding_norm``; the head is TIED). ``norm_eps`` 1e-5.

*``conv`` mixer.* ``B | C | u = W_in z`` (hidden -> 3 x hidden, in that
order), ``v_t = B_t * u_t``, ``c_t = sum_{j=0..K-1} w[j] * v_{t-(K-1)+j}``
(depthwise, causal, ``K = conv_L_cache`` = 3 taps, tap ``K - 1`` on the
current position, zero before the sequence's start, NO bias and NO
activation), ``y_t = W_out (C_t * c_t)``. Here the explicit K-term sum
over the whole sequence. Across steps a served sequence carries ``v_{t-2},
v_{t-1}`` and nothing else.

*``full_attention`` mixer.* ``H`` query / ``KV`` kv heads of ``D`` lanes,
``q = RMSNorm_D(W_q z)``, ``k = RMSNorm_D(W_k z)`` A HEAD (one learned
scale of ``D`` shared by the heads), then rotate-half RoPE at ``theta``
over all ``D`` lanes (``inv_freq_i = theta^(-2i/D)``), causal softmax at
scale ``D^-1/2``, query head h reads kv head ``h // (H / KV)``, ``W_out``;
dense over the whole sequence, a block of 8 heads at a time.

*dense ffn.* ``W_2 (silu(W_1 x) * W_3 x)``.

*sparse ffn.* ``s = sigmoid(W_g x)`` over ALL experts in float32;
selection = the top-k of ``s + b`` (``b`` in the selection ONLY); weights
``w = s[sel] / (sum s[sel] + 1e-6)``, times ``routed_scaling``; ``y =
sum_i w_i W_2^i (silu(W_1^i x) * W_3^i x)``: every HELD expert runs on
every token and is masked by that choice, one expert's weights upcast at a
time. No shared expert.

The chip's share: the tree holds experts ``first .. first + held`` of each
layer (all 64 in ``benchmark/configs/lfm2-24b-a2b.json``), the layers of
one pipeline stage and the whole vocabulary; an expert held elsewhere adds
nothing here, in the engine and in this reference alike. No cache, no
chunking, no kernel, no batching; nothing of the program under test is
imported.

The keyword switches of :func:`hidden_states` are the WRONG models the
cell's check is measured against (``tools/chip_parity.py --config
lfm2-24b-a2b``).

It reads the parameter tree ``models/lfm2.py`` defines.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta: float, pair_dim=None):
    """x [B, T, H, D] at positions 0 .. T-1; halves of a head's D lanes
    rotated as a pair. ``pair_dim`` (a wrong model): the lanes paired as
    if a head were ``pair_dim`` wide."""
    B, T, H, D = x.shape
    P = min(pair_dim or D, H * D)       # (a toy's heads are narrower)
    x = x.reshape(B, T, H * D // P, P)
    inv_freq = theta ** (-2.0 * jnp.arange(P // 2, dtype=F32) / P)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :P // 2], x[..., P // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).reshape(B, T, H, D)


def _conv_mixer(p, z, *, silu=False, window=None, reset=None):
    """The gated short convolution over the whole sequence. The wrong
    models: ``silu`` (an activation on the convolution), ``window`` (the
    K taps laid at the FRONT of a wider window: ``window`` 4 reads one
    position too far back), ``reset`` = (first, period): a position p >=
    first with (p - first) % period == 0 sees zeros for the inputs before
    it, as a flush that lost the carried inputs would."""
    T = z.shape[1]
    b, c, u = jnp.split(z @ p["in_proj"].astype(F32), 3, axis=-1)
    v = b * u
    w = p["conv_w"].astype(F32)                            # [K, M]
    K = w.shape[0]
    back = (window or K) - 1                # how far the first tap reaches
    pos = jnp.arange(T)
    y = jnp.zeros_like(v)
    for j in range(K):
        d = back - j                        # this tap reads v_{t - d}
        term = jnp.pad(v, ((0, 0), (d, 0), (0, 0)))[:, :T] * w[j]
        if reset is not None and d > 0:
            first, period = reset
            # positions since the last lost carry: a tap that reaches
            # past it reads zero
            since = jnp.where(pos >= first, (pos - first) % period, T)
            term = jnp.where((since < d)[None, :, None], 0.0, term)
        y = y + term
    if silu:
        y = jax.nn.silu(y)
    return (c * y) @ p["out_proj"].astype(F32)


def _attention(p, z, *, num_heads, kv_heads, rms_eps, theta, pair_dim=None,
               head_block=8):
    B, T, _ = z.shape
    k_ = lambda n: p[n]["kernel"].astype(F32)           # noqa: E731
    D = k_("q_proj").shape[1] // num_heads
    q = (z @ k_("q_proj")).reshape(B, T, num_heads, D)
    k = (z @ k_("k_proj")).reshape(B, T, kv_heads, D)
    v = (z @ k_("v_proj")).reshape(B, T, kv_heads, D)
    q = _rms(q, p["q_norm"]["scale"], rms_eps)
    k = _rms(k, p["k_norm"]["scale"], rms_eps)
    q, k = _rope(q, theta, pair_dim), _rope(k, theta, pair_dim)
    k, v = (jnp.repeat(t, num_heads // kv_heads, axis=2) for t in (k, v))
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    outs = []
    for h0 in range(0, num_heads, head_block):
        hs = slice(h0, h0 + head_block)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, :, hs], k[:, :, hs])
        s = jnp.where(mask, s * D ** -0.5, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                               v[:, :, hs]))
    y = jnp.concatenate(outs, axis=2).reshape(B, T, num_heads * D)
    return y @ k_("o_proj")


def _dense_mlp(p, z):
    k_ = lambda n: p[n]["kernel"].astype(F32)           # noqa: E731
    return (jax.nn.silu(z @ k_("gate_proj")) * (z @ k_("up_proj"))) \
        @ k_("down_proj")


def _sparse_mlp(p, z, *, top_k, first, scaling, norm_eps=1e-6, biased=True,
                renorm=True, seq_block=2):
    """Every held expert on every token, masked by the top-k of ALL by
    ``score + bias`` and weighted by the scores renormalised over the k;
    one expert upcast at a time, ``seq_block`` sequences at a time. The
    wrong models: ``biased`` false (selection by the score alone),
    ``renorm`` false."""
    s = jax.nn.sigmoid(z @ p["gate"].astype(F32))              # [B, T, E]
    pick = s + p["sel_bias"].astype(F32) if biased and "sel_bias" in p \
        else s
    kth = jnp.sort(pick, axis=-1)[..., -top_k][..., None]
    keep = jnp.where(pick >= kth, s, 0.0)
    if renorm:
        keep = keep / (keep.sum(-1, keepdims=True) + norm_eps)
    keep = jax.lax.dynamic_slice_in_dim(keep * scaling, first,
                                        p["wo"].shape[0], axis=-1)

    def block(zb, kb):
        def one_expert(acc, expert):
            w_gate, w_up, w_down, weight = expert
            out = (jax.nn.silu(zb @ w_gate.astype(F32))
                   * (zb @ w_up.astype(F32))) @ w_down.astype(F32)
            return acc + weight[..., None] * out, None

        y, _ = jax.lax.scan(one_expert, jnp.zeros_like(zb),
                            (p["wi_gate"], p["wi_up"], p["wo"],
                             jnp.moveaxis(kb, -1, 0)))
        return y

    B = z.shape[0]
    return jnp.concatenate([block(z[i:i + seq_block], keep[i:i + seq_block])
                            for i in range(0, B, seq_block)], axis=0)


def hidden_states(params, tokens, *, kinds, ffn_kinds, num_heads: int,
                  kv_heads: int, rope_theta: float, top_k: int,
                  rms_eps: float, routed_scaling: float = 1.0,
                  experts_first: int = 0, layers: int = None,
                  conv_silu: bool = False, conv_window: int = None,
                  conv_reset=None, select_biased: bool = True,
                  renorm: bool = True, rope_pair_dim: int = None):
    """The residual stream [B, T, C] after ``layers`` whole layers (all of
    them when None), before the final norm. ``kinds`` [layers] of "conv" /
    "attn", ``ffn_kinds`` of "dense" / "moe". The wrong models:
    ``conv_silu``, ``conv_window`` 4, ``conv_reset`` (first, period),
    ``select_biased`` false, ``renorm`` false, ``rope_pair_dim`` 128."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens].astype(F32)
        n = len(kinds) if layers is None else layers
        for i in range(n):
            p = params[f"layer_{i}"]
            z = _rms(x, p["input_norm"]["scale"], rms_eps)
            if kinds[i] == "conv":
                x = x + _conv_mixer(p["conv"], z, silu=conv_silu,
                                    window=conv_window, reset=conv_reset)
            else:
                x = x + _attention(
                    p["attn"], z, num_heads=num_heads, kv_heads=kv_heads,
                    rms_eps=rms_eps, theta=rope_theta,
                    pair_dim=rope_pair_dim)
            z = _rms(x, p["post_attn_norm"]["scale"], rms_eps)
            if ffn_kinds[i] == "dense":
                x = x + _dense_mlp(p["mlp"], z)
            else:
                x = x + _sparse_mlp(
                    p["moe"], z, top_k=top_k, first=experts_first,
                    scaling=routed_scaling, biased=select_biased,
                    renorm=renorm)
        return x


def logits(params, tokens, at, **dims):
    """Logits [B, n, vocab] at the positions ``at`` [B, n] of ``tokens``
    [B, T] (tokens to the right of a position never reach it); the head is
    the embedding, transposed (a tree with an ``lm_head`` is an untied
    sibling's)."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, **dims)
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        x = _rms(x, params["final_norm"]["scale"], dims["rms_eps"])
        if "lm_head" in params:
            return x @ params["lm_head"]["kernel"].astype(F32)
        return x @ params["embed"]["embedding"].astype(F32).T
