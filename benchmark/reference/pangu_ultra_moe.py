"""Plain reference of the openPangu-Ultra-MoE decoder: logits of a whole
sequence.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, written from the published
``config.json`` of ``model_type: pangu_ultra_moe`` (named in the
configuration file) and the description of multi-head latent attention
(DeepSeek-V2, arXiv:2405.04434, section 2.1). A layer, with
``sandwich_norm``, is

    a = x + N2(Attn(N1(x)));    y = a + N4(FFN(N3(a)))

*Attention*, in the EXPANDED form the papers state (no cache, no
absorption): ``c_q = rms(h W_qa)``; ``q = c_q W_qb`` -> H heads of
(nope + rope); ``(c_kv, k_r) = h W_kva``; ``c_kv = rms(c_kv)``; rotary
positions (rotate-half pairs, ``rope_theta``, no scaling) on each head's
``q_rope`` and on the ONE ``k_r`` every head shares; ``(k_nope, v) = c_kv
W_kvb`` per head; scores ``(q_nope . k_nope + q_rope . k_r) / sqrt(nope +
rope)``; dense causal softmax over the whole sequence, a block of heads at
a time so that a 2.5k-token sequence fits; ``y = concat_h(p v) W_o``.

*Feed-forward*: the first layers (those whose tree holds ``mlp``) a dense
SwiGLU; the others ``s = sigmoid(h W_r)`` over ALL experts in float32, the
k largest taken, weights ``s_i / sum of the chosen s`` times
``routed_scaling``; every held expert runs on every token and is masked by
that choice, one expert's weights upcast to float32 at a time; plus the
shared expert, always on, ungated.

*MTP* (``mtp_logits``): ``h' = W_eh [rms(h_t) ; rms(E x_{t+1})]`` with
``h`` the stream before the final norm, one more sparse block, a norm, the
model's own head.

The chip's share (``benchmark/configs/openpangu-ultra-moe-718b.json``):
the tree holds experts ``first .. first + held`` of each sparse layer and
the vocabulary slice it was built with; an expert held elsewhere adds
nothing here, in the engine and in this reference alike. Nothing of the
program under test is imported.

Departures from the published description (each also listed under
``assumed`` in the configuration file): the placement of the sandwich
norms (one on each branch's output, before the residual add); the two
latent norms; the rotate-half pairing; the scale (nope + rope)^-1/2; the
router's sigmoid score without groups or a selection bias; the order
``[h ; e]`` under ``W_eh``.

It reads the parameter tree ``models/pangu_ultra_moe.py`` defines.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta):
    """x [B, T, H, d] at positions 0 .. T-1, rotate-half pairs."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv     # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, h, *, num_heads, nope, rope, v_dim, rank, rope_theta,
               rms_eps, head_block=8, scale=None, rope_on_key=True,
               latent_norm=True):
    """``scale``, ``rope_on_key`` and ``latent_norm`` are the wrong models
    the cell's ``why`` measures."""
    B, T, _ = h.shape
    H = num_heads
    k = lambda n: p[n]["kernel"].astype(F32)            # noqa: E731
    cq = _rms(h @ k("q_a_proj"), p["q_a_norm"]["scale"], rms_eps)
    q = (cq @ k("q_b_proj")).reshape(B, T, H, nope + rope)
    ckv = h @ k("kv_a_proj")
    c = ckv[..., :rank]
    if latent_norm:
        c = _rms(c, p["kv_a_norm"]["scale"], rms_eps)
    k_r = ckv[..., None, rank:]                         # [B, T, 1, rope]
    if rope_on_key:
        k_r = _rope(k_r, rope_theta)
    q_r = _rope(q[..., nope:], rope_theta)
    kv = (c @ k("kv_b_proj")).reshape(B, T, H, nope + v_dim)
    sm = (nope + rope) ** -0.5 if scale is None else scale
    causal = jnp.tril(jnp.ones((T, T), bool))
    outs = []
    for h0 in range(0, H, head_block):
        hs = slice(h0, h0 + head_block)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, :, hs, :nope],
                       kv[:, :, hs, :nope]) \
            + jnp.einsum("bqhd,bkd->bhqk", q_r[:, :, hs], k_r[:, :, 0])
        s = jnp.where(causal, s * sm, -jnp.inf)
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
    kth = jnp.sort(s, axis=-1)[..., -top_k][..., None]
    keep = jnp.where(s >= kth, s, 0.0)
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


def _block(p, x, *, top_k, rms_eps, experts_first, routed_scaling,
           sandwich=True, **attn):
    y = _attention(p["attn"], _rms(x, p["input_norm"]["scale"], rms_eps),
                   rms_eps=rms_eps, **attn)
    if sandwich:
        y = _rms(y, p["attn_branch_norm"]["scale"], rms_eps)
    x = x + y
    h = _rms(x, p["post_attn_norm"]["scale"], rms_eps)
    if "mlp" in p:
        y = _swiglu(p["mlp"], h)
    else:
        y = _sparse_mlp(p["moe"], h, top_k=top_k, first=experts_first,
                        scaling=routed_scaling) \
            + _swiglu(p, h, ("shared_gate_proj", "shared_up_proj",
                             "shared_down_proj"))
    if sandwich:
        y = _rms(y, p["mlp_branch_norm"]["scale"], rms_eps)
    return x + y


def hidden_states(params, tokens, *, layers: int = None, **dims):
    """The residual stream [B, T, C] after ``layers`` whole layers (all
    of them when None), before the final norm."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"].astype(F32)[tokens]
        n_layers = sum(1 for k in params if k.startswith("layer_"))
        for i in range(n_layers if layers is None else layers):
            x = _block(params[f"layer_{i}"], x, **dims)
        return x


def logits(params, tokens, at, **dims):
    """Logits [B, n, vocab] at the positions ``at`` [B, n] of ``tokens``
    [B, T] (tokens to the right of a position never reach it)."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, **dims)
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        x = _rms(x, params["final_norm"]["scale"], dims["rms_eps"])
        return x @ params["lm_head"]["kernel"].astype(F32)


def mtp_logits(params, hidden, next_tokens, *, module: int = 0, **dims):
    """The ``module``-th multi-token-prediction module's logits
    [B, T, vocab]: ``hidden`` [B, T, C] is the model's stream before its
    final norm at positions t, ``next_tokens`` [B, T] the tokens at t + 1;
    row t scores the token at t + 2."""
    p = params[f"mtp_{module}"]
    eps = dims["rms_eps"]
    with jax.default_matmul_precision("highest"):
        e = params["embed"]["embedding"].astype(F32)[next_tokens]
        x = jnp.concatenate([_rms(hidden, p["hnorm"]["scale"], eps),
                             _rms(e, p["enorm"]["scale"], eps)], -1) \
            @ p["eh_proj"]["kernel"].astype(F32)
        x = _block(p["block"], x, **dims)
        x = _rms(x, p["final_norm"]["scale"], eps)
        return x @ params["lm_head"]["kernel"].astype(F32)
