"""Plain reference of the Nemotron-H decoder: logits of a whole sequence.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, written from the published
``config.json`` of ``model_type: nemotron_h`` (named in the configuration
file), the description of Mamba-2 (Dao and Gu, arXiv:2405.21060, the SSD
recurrence with a scalar decay a head) and of the Nemotron-H family (NVIDIA,
arXiv:2504.03624). EVERY layer is one branch,

    x = x + f(rms(x))          one norm, one branch, one add

and the tree says which: a layer that holds ``mamba`` is a Mamba-2 mixer,
one that holds ``attn`` attention, one that holds ``moe`` a sparse
feed-forward. After the last layer a final RMSNorm, then the head.

*Mamba-2 mixer*, H heads of P, G groups, state N, K taps:
``[z | xBC | dt] = h W_in``; ``xBC`` through a causal depthwise
convolution of K taps along the sequence (zeros before position 0) WITH
bias, then SiLU; ``xBC = [x | B | C]``, x ``[H, P]``, B and C ``[G, N]``,
head h reading group ``h // (H / G)``; ``dt = softplus(dt + dt_bias)`` a
head, no clamp; ``a = -exp(A_log)`` a head;

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t,     S_0 = 0
    y_t = S_t C_t + D x_t

token by token (a ``lax.scan`` over positions);
``y <- rms_group(y * silu(z)) * w_norm``: the gate FIRST, then an RMS over
each of the G groups of ``H P / G`` channels; ``out = y W_out``.

*Attention*: GQA, no bias, causal, scale ``head_dim^-1/2``, NO position
code; dense causal softmax over the whole sequence, a block of heads at a
time.

*Sparse feed-forward*: ``s = sigmoid(h W_g)`` over ALL experts in float32,
the k experts with the largest ``s + b_sel`` taken (one group), weights
``s_i / (sum of the chosen s + 1e-20)`` times ``routed_scaling``; expert i
is ``W_down,i relu(W_up,i h)^2``, two matrices, no gate matrix; every held
expert runs on every token and is masked by that choice, one expert's
weights upcast to float32 at a time; plus the shared expert of the same
form, always on.

The chip's share (``benchmark/configs/nemotron-3-nano-30b-a3b.json``): the
tree holds experts ``first .. first + held`` of each sparse layer, the
layers of one pipeline stage and the vocabulary slice it was built with;
an expert held elsewhere adds nothing here, in the engine and in this
reference alike. Those are the cut's departures from the published model:
13 of 52 layers, 64 of 128 experts a layer, 65,536 of 131,072 vocabulary
rows. The tree stores an expert at its width rounded up to whole 128-lane
groups (zeros); this reference reads the first ``expert_width`` columns and
rows, the published 1856. No cache, no chunking, no kernel; nothing of
the program under test is imported.

Departures from the published description (each also listed under
``assumed`` in the configuration file): none in the equations; what the
config leaves open and is assumed is the router's form (sigmoid, the bias
in the selection only, the 1e-20), the gate before the grouped norm, the
inner width ``H P`` (``expand`` unused) and the absence of a rotary code.

It reads the parameter tree ``models/nemotron_h.py`` defines.
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
    cell's ``why`` measures: the family's attention has none.
    x [B, T, H, d] at positions 0 .. T-1."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mamba2(p, h, *, heads, groups, state, rms_eps, skip_term=True,
            gate_first=True, conv_bias=True):
    """``skip_term`` (D x), ``gate_first`` and ``conv_bias`` false are the
    wrong models the cell's ``why`` measures."""
    B, T, _ = h.shape
    H, G, N = heads, groups, state
    d_in = p["out_proj"].shape[0]
    P = d_in // H
    zxbcdt = h @ p["in_proj"].astype(F32)
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * G * N], -1)
    w = p["conv_w"].astype(F32)                          # [K, C]
    K = w.shape[0]
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = sum(xp[:, j:j + T] * w[j] for j in range(K))
    if conv_bias:
        xbc = xbc + p["conv_b"].astype(F32)
    xbc = jax.nn.silu(xbc)
    x = xbc[..., :d_in].reshape(B, T, H, P)
    Bm = xbc[..., d_in:d_in + G * N].reshape(B, T, G, N)
    Cm = xbc[..., d_in + G * N:].reshape(B, T, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))  # [B, T, H]
    a = -jnp.exp(p["A_log"].astype(F32))                 # [H]

    def one(S, xs):
        x_t, B_t, C_t, dt_t = xs            # [B,H,P] [B,G,N] [B,G,N] [B,H]
        B_t, C_t = (jnp.repeat(t, H // G, axis=1) for t in (B_t, C_t))
        S = jnp.exp(dt_t * a)[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x, Bm, Cm, dt))
    _, y = jax.lax.scan(one, jnp.zeros((B, H, P, N), F32), xs)
    y = jnp.moveaxis(y, 0, 1)                            # [B, T, H, P]
    if skip_term:
        y = y + p["D"].astype(F32)[:, None] * x
    y = y.reshape(B, T, d_in)

    def group_norm(t):
        t = t.reshape(B, T, G, d_in // G)
        t = t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + rms_eps)
        return t.reshape(B, T, d_in) * p["norm"].astype(F32)

    y = group_norm(y * jax.nn.silu(z)) if gate_first \
        else group_norm(y) * jax.nn.silu(z)
    return y @ p["out_proj"].astype(F32)


def _attention(p, h, *, num_heads, kv_heads, head_block=8, rope_theta=None):
    """``rope_theta`` (rotary applied) is a wrong model the cell's ``why``
    measures."""
    B, T, _ = h.shape
    k_ = lambda n: p[n]["kernel"].astype(F32)           # noqa: E731
    D = k_("q_proj").shape[1] // num_heads
    q = (h @ k_("q_proj")).reshape(B, T, num_heads, D)
    k = (h @ k_("k_proj")).reshape(B, T, kv_heads, D)
    v = (h @ k_("v_proj")).reshape(B, T, kv_heads, D)
    if rope_theta is not None:
        q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    k, v = (jnp.repeat(t, num_heads // kv_heads, axis=2) for t in (k, v))
    causal = jnp.tril(jnp.ones((T, T), bool))
    outs = []
    for h0 in range(0, num_heads, head_block):
        hs = slice(h0, h0 + head_block)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, :, hs], k[:, :, hs])
        s = jnp.where(causal, s * D ** -0.5, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                               v[:, :, hs]))
    y = jnp.concatenate(outs, axis=2).reshape(B, T, num_heads * D)
    return y @ k_("o_proj")


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _sparse_mlp(p, h, *, top_k, first, scaling, width):
    """Every held expert on every token, masked by the top-k of ALL."""
    s = jax.nn.sigmoid(h @ p["gate"].astype(F32))       # [B, T, E]
    pick = s + p["sel_bias"].astype(F32)
    kth = jnp.sort(pick, axis=-1)[..., -top_k][..., None]
    keep = jnp.where(pick >= kth, s, 0.0)
    keep = keep / (keep.sum(-1, keepdims=True) + 1e-20) * scaling
    held = p["wi"].shape[0]
    keep = jax.lax.dynamic_slice_in_dim(keep, first, held, axis=-1)

    def one_expert(acc, expert):
        w_up, w_down, weight = expert
        out = _relu2(h @ w_up[:, :width].astype(F32)) \
            @ w_down[:width].astype(F32)
        return acc + weight[..., None] * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (p["wi"], p["wo"], jnp.moveaxis(keep, -1, 0)))
    return y


def hidden_states(params, tokens, *, num_heads: int, kv_heads: int,
                  mamba_heads: int, groups: int, state: int, top_k: int,
                  rms_eps: float, expert_width: int, experts_first: int = 0,
                  routed_scaling: float = 1.0, layers: int = None,
                  rope_theta: float = None, **mamba_variant):
    """The residual stream [B, T, C] after ``layers`` whole layers (all
    of them when None), before the final norm. ``rope_theta`` and
    ``mamba_variant`` (``skip_term``, ``gate_first``, ``conv_bias``) are
    the wrong models the cell's ``why`` measures."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"].astype(F32)[tokens]
        n_layers = sum(1 for k in params if k.startswith("layer_"))
        for i in range(n_layers if layers is None else layers):
            p = params[f"layer_{i}"]
            if "mamba" in p:
                h = _rms(x, p["input_norm"]["scale"], rms_eps)
                x = x + _mamba2(p["mamba"], h, heads=mamba_heads,
                                groups=groups, state=state, rms_eps=rms_eps,
                                **mamba_variant)
            if "attn" in p:
                h = _rms(x, p["input_norm"]["scale"], rms_eps)
                x = x + _attention(p["attn"], h, num_heads=num_heads,
                                   kv_heads=kv_heads, rope_theta=rope_theta)
            if "moe" in p:
                h = _rms(x, p["post_attn_norm"]["scale"], rms_eps)
                shared = _relu2(h @ p["shared_up_proj"]["kernel"].astype(F32)) \
                    @ p["shared_down_proj"]["kernel"].astype(F32)
                x = x + _sparse_mlp(p["moe"], h, top_k=top_k,
                                    first=experts_first,
                                    scaling=routed_scaling,
                                    width=expert_width) + shared
        return x


def logits(params, tokens, at, **dims):
    """Logits [B, n, vocab] at the positions ``at`` [B, n] of ``tokens``
    [B, T] (tokens to the right of a position never reach it)."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, **dims)
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        x = _rms(x, params["final_norm"]["scale"], dims["rms_eps"])
        return x @ params["lm_head"]["kernel"].astype(F32)
