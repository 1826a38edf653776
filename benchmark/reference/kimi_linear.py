"""Plain reference of the Kimi-Linear decoder: logits of a whole sequence.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, written from the published
``config.json`` of ``model_type: kimi_linear`` (named in the configuration
file), the description of Kimi Delta Attention (arXiv:2510.26692) and of
multi-head latent attention (DeepSeek-V2, arXiv:2405.04434, section 2.1).
A layer is pre-norm with one residual stream and no branch norms:

    x = x + Mixer(rms(x, input_norm));  x = x + FFN(rms(x, post_attn_norm))

*KDA layer* (``linear_attn_config.kda_layers``), per head with key and
value width d: ``q~, k~, v~ = h Wq, h Wk, h Wv``, each through a causal
depthwise convolution of 4 taps along the sequence (zeros before position
0), then SiLU; q and k L2-normalised per head, q scaled by d^-1/2;
``g_t = -exp(A_h) softplus(W_f2 (W_f1 h_t) + b)`` per channel,
``a_t = exp(g_t)``; ``beta_t = sigmoid(W_b h_t)`` per head;

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,        S_0 = 0

token by token (a ``lax.scan`` over positions);
``y_t = (rms_head(o_t) * sigmoid(W_g2 (W_g1 h_t))) Wo``.

*MLA layer* (``full_attn_layers``), in the EXPANDED form (no cache, no
absorption): ``q = h W_q`` -> H heads of (nope + rope), no low-rank query
and no query norm; ``(c_kv, k_r) = h W_kva``; ``c = rms(c_kv)``;
``(k_nope, v) = c W_kvb`` per head; NO rotary on ``q``'s rope lanes nor on
the ONE ``k_r`` every head shares (``mla_use_nope``); scores
``(q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)``; dense causal
softmax over the whole sequence, a block of heads at a time;
``y = concat_h(p v) W_o``.

*Feed-forward*: the first layer (the one whose tree holds ``mlp``) a dense
SwiGLU; the others ``s = sigmoid(h W_r)`` over ALL experts in float32, the
k experts with the largest ``s + b_sel`` taken (one group), weights
``s_i / sum of the chosen s`` times ``routed_scaling``; every held expert
runs on every token and is masked by that choice, one expert's weights
upcast to float32 at a time; plus the shared expert, always on, ungated.

The chip's share (``benchmark/configs/kimi-linear-48b-a3b.json``): the tree
holds experts ``first .. first + held`` of each sparse layer, the layers
of one pipeline stage and the vocabulary slice it was built with; an
expert held elsewhere adds nothing here, in the engine and in this
reference alike. Those are the cut's departures from the published model:
8 of 27 layers (two whole periods), 64 of 256 experts a layer, 40,960 of
163,840 vocabulary rows. No cache, no chunking, no kernel; nothing of the
program under test is imported.

Departures from the published description (each also listed under
``assumed`` in the configuration file): the decay's and the gate's low
rank (``head_dim``); ``beta`` without a factor 2; the L2 normalisation's
1e-6 under the root and q's scale d^-1/2; the per-head RMSNorm before the
output gate; the norm on ``c_kv`` and none on the query; the scale
(nope + rope)^-1/2; the selection bias taking part in the choice alone.

It reads the parameter tree ``models/kimi_linear.py`` defines.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta):
    """Rotary positions (rotate-half pairs), ONLY for the wrong model the
    cell's ``why`` measures: the family's latent layers have none.
    x [B, T, H, d] at positions 0 .. T-1."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _conv_silu(x, w):
    """Causal depthwise convolution (tap K-1 on the current position,
    zeros before position 0), then SiLU. x [B, T, C]; w [K, C]."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, j:j + T] * w[j].astype(F32)
                           for j in range(K)))


def _kda(p, h, *, heads, rms_eps, beta_scale=1.0):
    """``beta_scale`` is a wrong model the cell's ``why`` measures."""
    B, T, _ = h.shape
    q, k, v = (_conv_silu(h @ p[n + "_proj"].astype(F32), p[n + "_conv"])
               for n in "qkv")
    d = q.shape[-1] // heads
    q, k, v = (t.reshape(B, T, heads, d) for t in (q, k, v))
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
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(one, jnp.zeros((B, heads, d, d), F32), xs)
    o = _rms(jnp.moveaxis(o, 0, 1), p["o_norm"], rms_eps)   # [B, T, H, d]
    gate = jax.nn.sigmoid((h @ p["g_a"].astype(F32)) @ p["g_b"].astype(F32))
    return (o.reshape(B, T, heads * d) * gate) @ p["o_proj"].astype(F32)


def _latent_attention(p, h, *, num_heads, nope, rope, v_dim, rank, rms_eps,
                      head_block=8, rope_theta=None, latent_norm=True):
    """``rope_theta`` (rotary applied) and ``latent_norm`` are the wrong
    models the cell's ``why`` measures."""
    B, T, _ = h.shape
    H = num_heads
    k = lambda n: p[n]["kernel"].astype(F32)            # noqa: E731
    q = (h @ k("q_proj")).reshape(B, T, H, nope + rope)
    ckv = h @ k("kv_a_proj")
    c = ckv[..., :rank]
    if latent_norm:
        c = _rms(c, p["kv_a_norm"]["scale"], rms_eps)
    k_r, q_r = ckv[..., None, rank:], q[..., nope:]     # k_r [B, T, 1, rope]
    if rope_theta is not None:
        k_r, q_r = _rope(k_r, rope_theta), _rope(q_r, rope_theta)
    kv = (c @ k("kv_b_proj")).reshape(B, T, H, nope + v_dim)
    causal = jnp.tril(jnp.ones((T, T), bool))
    outs = []
    for h0 in range(0, H, head_block):
        hs = slice(h0, h0 + head_block)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, :, hs, :nope],
                       kv[:, :, hs, :nope]) \
            + jnp.einsum("bqhd,bkd->bhqk", q_r[:, :, hs], k_r[:, :, 0])
        s = jnp.where(causal, s * (nope + rope) ** -0.5, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                               kv[:, :, hs, nope:]))
    y = jnp.concatenate(outs, axis=2).reshape(B, T, H * v_dim)
    return y @ k("o_proj")


def _swiglu(p, h, names=("gate_proj", "up_proj", "down_proj")):
    k = lambda n: p[n]["kernel"].astype(F32)            # noqa: E731
    return (jax.nn.silu(h @ k(names[0])) * (h @ k(names[1]))) @ k(names[2])


def _sparse_mlp(p, h, *, top_k, first, scaling):
    """Every held expert on every token, masked by the top-k of ALL."""
    s = jax.nn.sigmoid(h @ p["gate"].astype(F32))       # [B, T, E]
    pick = s + p["sel_bias"].astype(F32)
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


def hidden_states(params, tokens, *, num_heads: int, nope: int, rope: int,
                  v_dim: int, rank: int, kda_heads: int, top_k: int,
                  rms_eps: float, experts_first: int = 0,
                  routed_scaling: float = 1.0, layers: int = None,
                  beta_scale: float = 1.0, latent_as_kda: bool = False,
                  **latent_variant):
    """The residual stream [B, T, C] after ``layers`` whole layers (all
    of them when None), before the final norm. ``beta_scale``,
    ``latent_as_kda`` (a latent layer run as a delta-rule layer, on the
    weights of the recurrent layer before it) and ``latent_variant``
    (``rope_theta``, ``latent_norm``) are the wrong models the cell's
    ``why`` measures."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"].astype(F32)[tokens]
        n_layers = sum(1 for k in params if k.startswith("layer_"))
        last_kda = None
        for i in range(n_layers if layers is None else layers):
            p = params[f"layer_{i}"]
            h = _rms(x, p["input_norm"]["scale"], rms_eps)
            last_kda = p.get("kda", last_kda)
            if "attn" in p and not latent_as_kda:
                x = x + _latent_attention(
                    p["attn"], h, num_heads=num_heads, nope=nope, rope=rope,
                    v_dim=v_dim, rank=rank, rms_eps=rms_eps,
                    **latent_variant)
            else:
                x = x + _kda(last_kda, h, heads=kda_heads, rms_eps=rms_eps,
                             beta_scale=beta_scale)
            h = _rms(x, p["post_attn_norm"]["scale"], rms_eps)
            if "mlp" in p:
                x = x + _swiglu(p["mlp"], h)
            else:
                x = x + _sparse_mlp(p["moe"], h, top_k=top_k,
                                    first=experts_first,
                                    scaling=routed_scaling) \
                    + _swiglu(p, h, ("shared_gate_proj", "shared_up_proj",
                                     "shared_down_proj"))
        return x


def logits(params, tokens, at, **dims):
    """Logits [B, n, vocab] at the positions ``at`` [B, n] of ``tokens``
    [B, T] (tokens to the right of a position never reach it)."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, **dims)
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        x = _rms(x, params["final_norm"]["scale"], dims["rms_eps"])
        return x @ params["lm_head"]["kernel"].astype(F32)
