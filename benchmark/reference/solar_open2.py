"""Plain reference of the Solar-Open2 decoder: logits of a whole sequence.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, written from the published
``config.json`` of ``model_type: solar_open2`` (named in the configuration
file) and the description of gated delta-rule attention with per-channel
decay (Kimi Delta Attention, arXiv:2510.26692). A layer is

    x = x + Mixer(rms(x, input_norm));  x = x + MoE(rms(x, post_attn_norm))

*Softmax layer* (``gqa_layers``): ``q = h Wq`` (H heads of D), ``k = h Wk``,
``v = h Wv`` (KV heads of D), NO rotary and no other position code, dense
causal attention at scale D^-1/2, each key/value head serving H / KV query
heads; ``y = (attn * sigmoid(h Wg)) Wo``.

*KDA layer* (every other layer), per head with key and value width d:
``q~, k~, v~ = h Wq, h Wk, h Wv``, each through a causal depthwise
convolution of 4 taps along the sequence (zeros before position 0), then
SiLU; q and k L2-normalised per head, q scaled by d^-1/2;
``g_t = -exp(A_h) softplus(W_f2 (W_f1 h_t) + b)`` per channel,
``a_t = exp(g_t)``; ``beta_t = 2 sigmoid(W_b h_t)`` per head;

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,        S_0 = 0

token by token (a ``lax.scan`` over positions);
``y_t = (rms_head(o_t) * sigmoid(W_g2 (W_g1 h_t))) Wo``.

*MoE* (every layer): ``s = sigmoid(h Wr)`` over ALL experts in float32; the
k experts with the largest ``s + b_sel`` are taken; their weights are
``s_i / sum of the chosen s`` times ``routed_scaling``; every held expert
runs on every token and is masked by that choice, one expert's weights
upcast to float32 at a time; plus the shared expert, always on, ungated.

The chip's share (``benchmark/configs/solar-open2-250b.json``): the tree
holds experts ``first .. first + held`` of each layer and the vocabulary
slice it was built with; an expert held elsewhere adds nothing here, in
the engine and in this reference alike. No cache, no chunking, no kernel;
nothing of the program under test is imported.

Departures from the published description (each also listed under
``assumed`` in the configuration file):
* the softmax layer's gate is taken as an elementwise sigmoid of a full
  projection of the layer input (``use_gqa_gate`` says only that there is
  one);
* the router's score is taken as a sigmoid with a selection-only bias, by
  the convention of the family whose keys the config uses;
* the L2 normalisation adds 1e-6 under the root.

It reads the parameter tree ``models/solar_open2.py`` defines.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta):
    """Rotary positions, ONLY for the wrong model the cell's ``why``
    measures (the family has none). x: [B, T, H, D]."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, h, *, num_heads, num_kv_heads, rope_theta=None):
    B, T, _ = h.shape
    q = h @ p["q_proj"]["kernel"].astype(F32)
    D = q.shape[-1] // num_heads
    q = q.reshape(B, T, num_heads, D)
    k = (h @ p["k_proj"]["kernel"].astype(F32)).reshape(B, T, num_kv_heads, D)
    v = (h @ p["v_proj"]["kernel"].astype(F32)).reshape(B, T, num_kv_heads, D)
    if rope_theta is not None:
        q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    rep = num_heads // num_kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(D))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    y = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    y = y.reshape(B, T, num_heads * D)
    y = y * jax.nn.sigmoid(h @ p["g_proj"]["kernel"].astype(F32))
    return y @ p["o_proj"]["kernel"].astype(F32)


def _conv_silu(x, w):
    """Causal depthwise convolution (tap K-1 on the current position,
    zeros before position 0), then SiLU. x [B, T, C]; w [K, C]."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, j:j + T] * w[j].astype(F32)
                           for j in range(K)))


def _kda(p, h, *, heads, rms_eps, state_dtype=F32, l2_norm=True,
         beta_scale=2.0):
    """``state_dtype`` / ``l2_norm`` / ``beta_scale`` exist for the cell's
    ``correct.why``: what a bfloat16 state, a missing normalisation or a
    beta without its 2 would do to the logits."""
    B, T, _ = h.shape
    q, k, v = (_conv_silu(h @ p[n + "_proj"].astype(F32), p[n + "_conv"])
               for n in "qkv")
    d = q.shape[-1] // heads
    q, k, v = (t.reshape(B, T, heads, d) for t in (q, k, v))
    if l2_norm:
        q, k = (t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)
                for t in (q, k))
    q = q * d ** -0.5
    f = (h @ p["f_a"].astype(F32)) @ p["f_b"].astype(F32)
    g = -jnp.exp(p["A_log"].astype(F32))[:, None] * jax.nn.softplus(
        f.reshape(B, T, heads, d) + p["dt_bias"].astype(F32).reshape(heads, d))
    beta = beta_scale * jax.nn.sigmoid(h @ p["b_proj"].astype(F32))

    def one(S, x):
        q_t, k_t, v_t, g_t, b_t = x                     # [B, H, d] / [B, H]
        S = jnp.exp(g_t)[..., None] * S                 # Diag(a) S
        u = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S))
        S = (S + k_t[..., None] * u[..., None, :]).astype(state_dtype)
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S.astype(F32))

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(one, jnp.zeros((B, heads, d, d), state_dtype), xs)
    o = jnp.moveaxis(o, 0, 1)                           # [B, T, H, d]
    o = _rms(o, p["o_norm"], rms_eps)
    gate = jax.nn.sigmoid((h @ p["g_a"].astype(F32)) @ p["g_b"].astype(F32))
    return (o.reshape(B, T, heads * d) * gate) @ p["o_proj"].astype(F32)


def _sparse_mlp(p, h, *, top_k, first, scaling, router="sigmoid"):
    """Every held expert on every token, masked by the top-k of ALL."""
    logits = h @ p["gate"].astype(F32)                  # [B, T, E]
    if router == "sigmoid":
        s = jax.nn.sigmoid(logits)
        pick = s + p["sel_bias"].astype(F32)
    else:                     # a softmax router, for the cell's ``why``
        s = pick = jax.nn.softmax(logits, axis=-1)
    kth = jnp.sort(pick, axis=-1)[..., -top_k][..., None]
    keep = jnp.where(pick >= kth, s, 0.0)
    keep = keep / keep.sum(-1, keepdims=True) * scaling
    held = p["wi_gate"].shape[0]
    keep = jax.lax.dynamic_slice_in_dim(keep, first, held, axis=-1)

    def one_expert(acc, expert):
        w_gate, w_up, w_down, weight = expert
        out = (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) \
            @ w_down.astype(F32)
        return acc + weight[..., None] * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (p["wi_gate"], p["wi_up"], p["wo"],
                         jnp.moveaxis(keep, -1, 0)))
    return y


def _shared(p, h):
    k = lambda n: p[n]["kernel"].astype(F32)            # noqa: E731
    return (jax.nn.silu(h @ k("shared_gate_proj")) * (h @ k("shared_up_proj"))) \
        @ k("shared_down_proj")


def hidden_states(params, tokens, *, num_heads: int, num_kv_heads: int,
                  kda_heads: int, top_k: int, rms_eps: float,
                  experts_first: int = 0, routed_scaling: float = 1.0,
                  layers: int = None, **variant):
    """The residual stream [B, T, C] after ``layers`` whole layers (all
    of them when None). ``variant`` reaches ``_kda`` / ``_sparse_mlp`` /
    the rotary switch: the wrong models the cell's ``why`` measures."""
    rope = variant.pop("rope_theta", None)
    router = variant.pop("router", "sigmoid")
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"].astype(F32)[tokens]
        n_layers = sum(1 for k in params if k.startswith("layer_"))
        for i in range(n_layers if layers is None else layers):
            p = params[f"layer_{i}"]
            h = _rms(x, p["input_norm"]["scale"], rms_eps)
            if "attn" in p:
                x = x + _attention(p["attn"], h, num_heads=num_heads,
                                   num_kv_heads=num_kv_heads,
                                   rope_theta=rope)
            else:
                x = x + _kda(p["kda"], h, heads=kda_heads, rms_eps=rms_eps,
                             **variant)
            h = _rms(x, p["post_attn_norm"]["scale"], rms_eps)
            x = x + _sparse_mlp(p["moe"], h, top_k=top_k,
                                first=experts_first, scaling=routed_scaling,
                                router=router) + _shared(p, h)
        return x


def logits(params, tokens, at, **dims):
    """Logits [B, n, vocab] at the positions ``at`` [B, n] of ``tokens``
    [B, T] (tokens to the right of a position never reach it)."""
    rms_eps = dims["rms_eps"]
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, **dims)
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        x = _rms(x, params["final_norm"]["scale"], rms_eps)
        return x @ params["lm_head"]["kernel"].astype(F32)
