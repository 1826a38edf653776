"""Plain reference of the Olmo-Hybrid decoder: logits of a whole sequence.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, written from the published
``config.json`` of ``model_type: olmo_hybrid`` (named in the configuration
file: its keys are all this machine has of the publication) and the
description of the gated delta rule with a scalar decay (Gated Delta
Networks, arXiv:2412.06464, whose reference module the ``linear_*`` keys
name). With M the hidden size and no bias anywhere, a layer is

    x = x + rms_M(Mixer(x));   x = x + rms_M(W_down(silu(W_gate x) * W_up x))

(the norm on each branch's OUTPUT, none on its input), then a final norm
and an untied head.

*linear_attention* layer, H heads of key width d_k and value width d_v:
``q~, k~, v~ = x Wq, x Wk, x Wv``, each through its own causal depthwise
convolution of 4 taps along the sequence (zeros before position 0, no
bias), then SiLU; q and k L2-normalised a head, q scaled by d_k^-1/2;
``beta_t = 2 sigmoid(W_b x_t)`` a head (the 2: ``linear_allow_neg_eigval``);
``g_t = -exp(A_log) softplus(W_a x_t + dt_bias)`` ONE number a head,
``a_t = exp(g_t)``;

    S_t = (I - beta_t k_t k_t^T) a_t S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,        S_0 = 0          (S [d_k, d_v])

token by token (a ``lax.scan`` over positions);
``y_t = (rms_head(o_t; w) * silu(W_g x_t)) Wo`` with ``W_g`` at full rank.

*full_attention* layer, H heads of D and as many K/V heads:
``q = rms(x Wq; w_q)``, ``k = rms(x Wk; w_k)`` over the WHOLE projection,
``v = x Wv``; NO rotary and no other position code; dense causal softmax
at scale D^-1/2, a block of heads at a time; ``y = attn Wo``.

The cut (``benchmark/configs/olmo-hybrid-7b.json``): the first 8 of 32
layers, two whole periods, every width and the whole vocabulary as
published. No cache, no chunking, no kernel; nothing of the program under
test is imported.

Departures from the published description: none known. ASSUMED, because
the catalog row holds keys and no prose (each also under ``assumed`` in
the configuration file): the norm on the branch output for both layer
kinds and the QK-norm over the whole projection (the Olmo-2 / Olmo-3
convention); no position code (the null ``rope_theta``); no convolution
bias; the SiLU output gate and the per-head RMSNorm of the gated delta
rule's reference module; the L2 normalisation's 1e-6 under the root;
``head_dim`` = hidden / heads.

It reads the parameter tree ``models/olmo_hybrid.py`` defines.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta):
    """Rotary positions (rotate-half pairs), ONLY for the wrong model the
    cell's ``why`` measures: the family has none. x [B, T, H, D]."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _conv_silu(x, w):
    """Causal depthwise convolution (tap K-1 on the current position,
    zeros before position 0), then SiLU. x [B, T, C]; w [K, C]."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, j:j + T] * w[j].astype(F32)
                           for j in range(K)))


def _gated_delta(p, x, *, heads, rms_eps, beta_scale=2.0,
                 channel_decay=False):
    """``beta_scale`` (1: the step size without its 2) and
    ``channel_decay`` (the head's decay replaced by one a CHANNEL, each
    channel's rate drawn independently within a factor e of the head's)
    are wrong models the cell's ``why`` measures."""
    B, T, _ = x.shape
    q, k, v = (_conv_silu(x @ p[n + "_proj"].astype(F32), p[n + "_conv"])
               for n in "qkv")
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    q, k = (t.reshape(B, T, heads, dk) for t in (q, k))
    v = v.reshape(B, T, heads, dv)
    q, k = (t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)
            for t in (q, k))
    q = q * dk ** -0.5
    g = -jnp.exp(p["A_log"].astype(F32)) * jax.nn.softplus(
        x @ p["a_proj"].astype(F32) + p["dt_bias"].astype(F32))  # [B, T, H]
    g = jnp.broadcast_to(g[..., None], (B, T, heads, dk))
    if channel_decay:
        g = g * jnp.exp(jax.random.uniform(jax.random.PRNGKey(0),
                                           (heads, dk), F32, -1.0, 1.0))
    beta = beta_scale * jax.nn.sigmoid(x @ p["b_proj"].astype(F32))

    def one(S, step):
        q_t, k_t, v_t, g_t, b_t = step           # [B, H, d] / [B, H]
        S = jnp.exp(g_t)[..., None] * S                   # a_t S
        u = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(one, jnp.zeros((B, heads, dk, dv), F32), xs)
    o = _rms(jnp.moveaxis(o, 0, 1), p["o_norm"], rms_eps)  # [B, T, H, dv]
    gate = jax.nn.silu(x @ p["g_proj"].astype(F32))
    return (o.reshape(B, T, heads * dv) * gate) @ p["o_proj"].astype(F32)


def _attention(p, x, *, num_heads, rms_eps, head_block=6, rope_theta=None):
    """``rope_theta`` (rotary switched on) is a wrong model the cell's
    ``why`` measures."""
    B, T, _ = x.shape
    w = lambda n: p[n]["kernel"].astype(F32)              # noqa: E731
    q = _rms(x @ w("q_proj"), p["q_norm"]["scale"], rms_eps)
    k = _rms(x @ w("k_proj"), p["k_norm"]["scale"], rms_eps)
    v = x @ w("v_proj")
    D = q.shape[-1] // num_heads
    q, k, v = (t.reshape(B, T, num_heads, D) for t in (q, k, v))
    if rope_theta is not None:
        q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    causal = jnp.tril(jnp.ones((T, T), bool))
    outs = []
    for h0 in range(0, num_heads, head_block):
        hs = slice(h0, h0 + head_block)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, :, hs], k[:, :, hs]) \
            * D ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                               v[:, :, hs]))
    y = jnp.concatenate(outs, axis=2).reshape(B, T, num_heads * D)
    return y @ w("o_proj")


def _swiglu(p, x):
    w = lambda n: p[n]["kernel"].astype(F32)              # noqa: E731
    return (jax.nn.silu(x @ w("gate_proj")) * (x @ w("up_proj"))) \
        @ w("down_proj")


def hidden_states(params, tokens, *, num_heads: int, gdn_heads: int,
                  rms_eps: float, layers: int = None,
                  norm_at: str = "output", beta_scale: float = 2.0,
                  channel_decay: bool = False, rope_theta=None):
    """The residual stream [B, T, M] after ``layers`` whole layers (all
    of them when None), before the final norm. ``norm_at`` "input" (each
    branch's norm moved in front of it, the pre-norm arrangement on the
    same weights), ``beta_scale``, ``channel_decay`` and ``rope_theta``
    are the wrong models the cell's ``why`` measures."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"].astype(F32)[tokens]
        n_layers = sum(1 for k in params if k.startswith("layer_"))
        for i in range(n_layers if layers is None else layers):
            p = params[f"layer_{i}"]

            def branch(f, scale):
                if norm_at == "input":
                    return f(_rms(x, scale, rms_eps))
                return _rms(f(x), scale, rms_eps)

            if "attn" in p:
                mixer = lambda h: _attention(             # noqa: E731
                    p["attn"], h, num_heads=num_heads, rms_eps=rms_eps,
                    rope_theta=rope_theta)
            else:
                mixer = lambda h: _gated_delta(           # noqa: E731
                    p["gdn"], h, heads=gdn_heads, rms_eps=rms_eps,
                    beta_scale=beta_scale, channel_decay=channel_decay)
            x = x + branch(mixer, p["attn_branch_norm"]["scale"])
            x = x + branch(lambda h: _swiglu(p["mlp"], h),
                           p["mlp_branch_norm"]["scale"])
        return x


def logits(params, tokens, at, **dims):
    """Logits [B, n, vocab] at the positions ``at`` [B, n] of ``tokens``
    [B, T] (tokens to the right of a position never reach it)."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, **dims)
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        x = _rms(x, params["final_norm"]["scale"], dims["rms_eps"])
        return x @ params["lm_head"]["kernel"].astype(F32)
