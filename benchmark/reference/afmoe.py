"""Plain reference of the AFMoE decoder (Arcee Trinity-Mini, ``model_type:
afmoe``) for TRAINING: the loss of a batch, each token's NLL, and the
gradient of the loss in every parameter (``jax.grad`` of this file).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. ``afmoe`` is not in the local
``transformers`` (4.57.6: ``arcee``, the dense sibling, and ``deepseek_v3``,
whose router this family's is at one group), so EVERY line below is
``assumed``, from the family's published modelling code as remembered, and
listed with its reason in ``benchmark/configs/trinity-mini-26b-a3b.json``.

    x_0 = sqrt(hidden) E[token]                       (mup_enabled)
    h   = x + N_post_attn(Attn_l(N_in(x)))            four RMSNorms a layer,
    x'  = h + N_post_mlp(FFN_l(N_pre_mlp(h)))         one residual stream
    logits = W_head N_out(x_L)                        head untied, eps 1e-5

*Attention, every layer.* ``q = W_q z`` (H heads of D), ``k = W_k z``, ``v =
W_v z`` (KV heads of D), ``g = W_g z`` (hidden -> H D), no bias; q and k
normed A HEAD (RMSNorm over each head's D lanes, one learned scale of D
shared by the heads); rotate-half RoPE at ``rope_theta`` over all D lanes
on ``sliding_attention`` layers ONLY, NO position code on
``full_attention`` layers; causal softmax at ``D^-1/2``, on a sliding
layer over keys ``0 <= i - j < window`` (the query's own key among the
``window``); query head h reads kv head ``h // (H / KV)``; ``y = W_o (attn
* sigmoid(g))``. Dense masked softmax, a block of queries at a time under
``jax.checkpoint`` so that the gradient of an 8,192-token sequence fits.

*Dense feed-forward* (layers ``< num_dense``): ``W_2 (silu(W_1 x) * W_3
x)``.

*Sparse feed-forward* (the rest): ``s = sigmoid(W_r x)`` over all E experts
in float32, no bias on ``W_r``; selection = the top-k of ``s + b`` (``b``
takes part in the SELECTION only; one group); ``w = route_scale * s[sel] /
(sum s[sel] + 1e-20)``; ``y = Shared(x) + sum_i w_i W_2^i (silu(W_1^i x) *
W_3^i x)``, ``Shared`` one ungated SwiGLU of the experts' width. Every HELD
expert runs on every token and is masked by that choice.

*Balance.* No auxiliary loss: after a step ``b <- b + load_balance_coeff *
sign(mean_e(c) - c_e)`` with ``c_e`` the rows expert ``e`` was chosen for
in that step (:func:`bias_update`; DeepSeek-V3's auxiliary-loss-free rule,
the family's trainer's). ``b`` has no gradient.

*Loss.* Mean next-token cross-entropy over the (sliced) vocabulary.

The chip's share: ``held = (first, count)``: the tree holds experts
``first .. first + count`` of each sparse layer; an expert held elsewhere
adds nothing here, in the program and in this reference alike. The
reference is GIVEN the share; it routes over all E.

``wrong`` names the WRONG models the cell's check is measured against
(:data:`WRONG`): each changes one line above.

It reads the parameter tree ``deepspeed_tpu/models/afmoe.py`` defines and
imports nothing of the program under test.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: the wrong models: one equation each
WRONG = (
    "no_window",            # every layer attends in full
    "window_off_by_one",    # 0 <= i - j <= window
    "rope_on_full",         # the full layers rotate too
    "no_gate",              # y = W_o attn
    "unbiased_selection",   # top-k of s, not of s + b
    "no_renorm",            # w = route_scale * s[sel]
    "route_scale_one",      # w = s[sel] / sum
    "no_shared",            # the shared expert dropped
    "no_post_norms",        # h = x + Attn(N_in(x)); x' = h + FFN(N_pre(h))
    "no_mup",               # x_0 = E[token]
)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta):
    """Rotate-half over all lanes of ``x`` [B, T, heads, D], positions
    0 .. T - 1."""
    D = x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(D // 2, dtype=F32) / D)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _w(p, name):
    return p[name]["kernel"].astype(F32)


def _swiglu(p, x):
    return (jax.nn.silu(x @ _w(p, "gate_proj")) * (x @ _w(p, "up_proj"))) \
        @ _w(p, "down_proj")


def _attention(p, z, *, num_heads, kv_heads, window, rope, gate, theta, eps,
               q_block):
    """``window`` None: full. ``rope``: rotate q and k."""
    B, T, _ = z.shape
    D = p["q_norm"]["scale"].shape[0]
    q = (z @ _w(p, "q_proj")).reshape(B, T, num_heads, D)
    k = (z @ _w(p, "k_proj")).reshape(B, T, kv_heads, D)
    v = (z @ _w(p, "v_proj")).reshape(B, T, kv_heads, D)
    q = _rms(q, p["q_norm"]["scale"], eps)
    k = _rms(k, p["k_norm"]["scale"], eps)
    if rope:
        q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, num_heads // kv_heads, axis=2)
    v = jnp.repeat(v, num_heads // kv_heads, axis=2)

    @jax.checkpoint
    def block(qb, k, v, i0):
        s = jnp.einsum("bihd,bjhd->bhij", qb, k) * D ** -0.5
        i = i0 + jnp.arange(qb.shape[1])[:, None]
        j = jnp.arange(T)[None, :]
        mask = j <= i
        if window is not None:
            mask &= i - j < window
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhij,bjhd->bihd", a, v)

    if T % q_block:
        q_block = T
    nb = T // q_block
    y = jax.lax.map(
        lambda a: block(a[0], k, v, a[1]),
        (q.reshape(B, nb, q_block, num_heads, D).swapaxes(0, 1),
         jnp.arange(nb) * q_block))
    y = y.swapaxes(0, 1)
    y = y.reshape(B, T, num_heads * D)
    if gate:
        y = y * jax.nn.sigmoid(z @ _w(p, "gate_proj"))
    return y @ _w(p, "o_proj")


def route(x, gate_w, bias, *, top_k, route_norm=True, route_scale=1.0,
          biased=True):
    """The router of one layer over rows ``x`` [S, M]: (chosen experts [S,
    k] int32, their weights [S, k] float32). ``deepseek_v3``'s at ``n_group
    = topk_group = 1``."""
    s = jax.nn.sigmoid(x.astype(F32) @ gate_w.astype(F32))
    _, sel = jax.lax.top_k(s + bias.astype(F32) if biased else s, top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return sel, w * route_scale


def _sparse(p, x, *, top_k, route_norm, route_scale, biased, held):
    """(the routed experts' part of the layer's output over rows ``x`` [S,
    M], the rows each of the E experts was chosen for [E] int32)."""
    S, E = x.shape[0], p["gate"].shape[1]
    sel, w = route(x, p["gate"], p["select_bias"], top_k=top_k,
                   route_norm=route_norm, route_scale=route_scale,
                   biased=biased)
    dense_w = jnp.zeros((S, E), F32).at[jnp.arange(S)[:, None], sel].add(w)
    counts = jnp.zeros((E,), jnp.int32).at[sel.reshape(-1)].add(1)
    first, count = held
    here = jax.lax.dynamic_slice_in_dim(dense_w, first, count, axis=1)

    @jax.checkpoint
    def expert(acc, e):
        wg, wu, wd, col = e
        y = (jax.nn.silu(x @ wg.astype(F32)) * (x @ wu.astype(F32))) \
            @ wd.astype(F32)
        return acc + y * col[:, None], None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (p["wi_gate"], p["wi_up"], p["wo"], here.T))
    return y, counts


def hidden_states(params, tokens, *, sliding: Tuple[bool, ...],
                  num_dense: int, num_heads: int, kv_heads: int, window: int,
                  rope_theta: float, top_k: int, route_norm: bool,
                  route_scale: float, rms_eps: float, held, mup: bool = True,
                  q_block: int = 512, wrong: Tuple[str, ...] = ()):
    """(the stream after the final norm [B, T, M], [per-expert rows of
    each sparse layer]). ``sliding``: a layer, whether it is a
    ``sliding_attention`` one."""
    bad = sorted(set(wrong) - set(WRONG))
    if bad:
        raise ValueError(f"no wrong model {bad}; have {WRONG}")
    with jax.default_matmul_precision("highest"):
        emb = params["embed"]["embedding"].astype(F32)
        x = emb[tokens]
        if mup and "no_mup" not in wrong:
            x = x * emb.shape[1] ** 0.5
        B, T, M = x.shape
        post = "no_post_norms" not in wrong
        counts = []
        for l, slides in enumerate(sliding):
            p = params[f"layer_{l}"]
            norm = lambda name, v: _rms(v, p[name]["scale"],  # noqa: E731
                                        rms_eps)
            windowed = slides and "no_window" not in wrong
            y = _attention(
                p["attn"], norm("input_norm", x), num_heads=num_heads,
                kv_heads=kv_heads,
                window=None if not windowed else
                window + ("window_off_by_one" in wrong),
                rope=slides or "rope_on_full" in wrong,
                gate="no_gate" not in wrong, theta=rope_theta, eps=rms_eps,
                q_block=q_block)
            x = x + (norm("post_attn_norm", y) if post else y)
            h = norm("pre_mlp_norm", x)
            if l < num_dense:
                y = _swiglu(p["mlp"], h)
            else:
                y, c = _sparse(
                    p["moe"], h.reshape(B * T, M), top_k=top_k,
                    route_norm=route_norm and "no_renorm" not in wrong,
                    route_scale=1.0 if "route_scale_one" in wrong
                    else route_scale,
                    biased="unbiased_selection" not in wrong, held=held)
                y = y.reshape(B, T, M)
                counts.append(c)
                if "no_shared" not in wrong:
                    y = y + _swiglu(p["shared"], h)
            x = x + (norm("post_mlp_norm", y) if post else y)
        return _rms(x, params["final_norm"]["scale"], rms_eps), counts


def nll_and_counts(params, tokens, **dims):
    """``tokens`` [B, T + 1] -> (each position's next-token NLL [B, T],
    [per-expert rows of each sparse layer] over the inputs: what
    :func:`bias_update` moves each layer's bias by), one forward."""
    hidden, counts = hidden_states(params, tokens[:, :-1], **dims)

    @jax.checkpoint
    def nll(h, head, targets):
        with jax.default_matmul_precision("highest"):
            logp = jax.nn.log_softmax(h @ head.astype(F32), axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]

    return nll(hidden, params["lm_head"]["kernel"], tokens[:, 1:]), counts


def per_token_nll(params, tokens, **dims):
    return nll_and_counts(params, tokens, **dims)[0]


def loss(params, tokens, **dims):
    """Mean next-token cross-entropy of ``tokens`` [B, T + 1]."""
    return per_token_nll(params, tokens, **dims).mean()


def grads(params, tokens, **dims):
    """The gradient of :func:`loss` in every parameter (``select_bias``'s
    is zero: it takes part in a selection only)."""
    return jax.grad(functools.partial(loss, **dims))(params, tokens)


def grads_nll_counts(params, tokens, **dims):
    """(:func:`grads`, each position's NLL [B, T], the per-expert rows of
    each sparse layer) of ``tokens`` [B, T + 1], one forward and one
    backward."""
    def f(p):
        nll, counts = nll_and_counts(p, tokens, **dims)
        return nll.mean(), (nll, counts)

    g, (nll, counts) = jax.grad(f, has_aux=True)(params)
    return g, nll, counts


def expert_counts(params, tokens, **dims):
    return nll_and_counts(params, tokens, **dims)[1]


def bias_update(bias, counts, coeff: float):
    """``b + coeff * sign(mean_e(c) - c_e)``."""
    c = counts.astype(F32)
    return bias.astype(F32) + coeff * jnp.sign(c.mean() - c)
