"""Plain reference of the Jamba decoder: logits of a whole sequence.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, written from the published
``config.json`` of ``model_type: jamba`` (named in the configuration file:
its keys are all this machine has of the publication) and the descriptions
of Mamba (Gu and Dao, arXiv:2312.00752, section 3 and algorithm 2) and
Jamba (AI21, arXiv:2403.19887). With M the hidden size, a layer is

    x = x + Mixer(rms_M(x; w_in));   x = x + W_down(silu(W_gate h) * W_up h),
                                      h = rms_M(x; w_ff)

then a final norm and the head TIED to the embedding.

*Mamba* layer (Mamba-1 as Jamba has it), E = 2 M channels, state N, rank
R, h the normed stream:

    [x~ | z] = h W_in
    x = silu(conv_K(x~) + b_conv)          causal, depthwise, over x~ ALONE,
                                           zeros before position 0
    [dt~ | B | C] = x W_x                  (E -> R + N + N)
    dt~, B, C <- rms_R(dt~; w_dt), rms_N(B; w_b), rms_N(C; w_c)
    dt = softplus(dt~ W_dt + b_dt)         a step size a channel
    A = -exp(A_log)                        a decay a (state, channel) pair
    h_t[n, e] = exp(dt_t[e] A[n, e]) h_{t-1}[n, e] + dt_t[e] x_t[e] B_t[n]
    y_t[e] = sum_n h_t[n, e] C_t[n] + D[e] x_t[e],        h_0 = 0
    Mixer = (y * silu(z)) W_out            no norm after the gate

token by token (a ``lax.scan`` over positions).

*attention* layer, H query heads on KV key/value heads of D = M / H:
``q = h Wq``, ``k = h Wk``, ``v = h Wv``, no bias, NO rotary and no other
position code; dense causal softmax at scale D^-1/2, a block of heads at a
time; ``Mixer = attn Wo``.

No cache, no chunking, no kernel; nothing of the program under test is
imported. The served bfloat16 leaves are upcast where they are used, a
layer at a time, so that the float32 copy of one layer is all that stands
beside the engine's weights.

Departures from the published description: none known. What the
catalog row's keys do not settle (each also under ``assumed`` in the
configuration file): which layers attend (``i % period == offset``);
``head_dim`` = hidden / heads; no position code; the three inner norms as
RMSNorms with learned scales at ``rms_norm_eps``; ``dt_proj``'s bias; no
clamp on ``dt``. Each is what ``transformers``' own ``models/jamba`` does:
``tests/unit/test_jamba_reference.py`` holds this file to a tiny
``JambaForCausalLM`` whose checkpoint went through the loader's names.

It reads the parameter tree ``models/jamba.py`` defines (``A_log`` stored
``[N, E]``).
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


def _mamba1(p, h, *, dt_rank, state, rms_eps, inner_norms=True,
            shared_decay=False, state_dtype=F32, state_reset=None,
            conv_bias=True):
    """The wrong models the cell's ``why`` measures: ``inner_norms`` false
    (the three RMSNorms left out), ``shared_decay`` (ONE decay a channel
    for all its states, ``A[0]`` broadcast: Mamba-2's form), ``state_dtype``
    (the scan CARRIES the state in it: a pair of casts inside one program
    would round nothing, XLA keeps the excess precision), ``state_reset`` =
    (first, period): a position p >= first with (p - first) % period == 0
    starts from a zero state, as a loop that lost the state the loop
    before it left would, ``conv_bias`` false."""
    B, T, _ = h.shape
    R, N = dt_rank, state
    x, z = jnp.split(h @ p["in_proj"].astype(F32), 2, -1)
    w = p["conv_w"].astype(F32)                          # [K, E]
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    x = sum(xp[:, j:j + T] * w[j] for j in range(K))
    if conv_bias:
        x = x + p["conv_b"].astype(F32)
    x = jax.nn.silu(x)
    dt, Bm, Cm = jnp.split(x @ p["x_proj"].astype(F32), [R, R + N], -1)
    if inner_norms:
        dt = _rms(dt, p["dt_norm"], rms_eps)
        Bm = _rms(Bm, p["b_norm"], rms_eps)
        Cm = _rms(Cm, p["c_norm"], rms_eps)
    dt = jax.nn.softplus(dt @ p["dt_proj"].astype(F32)
                         + p["dt_bias"].astype(F32))     # [B, T, E]
    A = -jnp.exp(p["A_log"].astype(F32))                 # [N, E]
    if shared_decay:
        A = jnp.broadcast_to(A[:1], A.shape)
    lost = jnp.zeros((T,), bool)
    if state_reset is not None:
        first, period = state_reset
        pos = jnp.arange(T)
        lost = (pos >= first) & ((pos - first) % period == 0)

    def one(S, xs):
        x_t, dt_t, B_t, C_t, lost_t = xs     # [B,E] [B,E] [B,N] [B,N] []
        S = jnp.where(lost_t, 0.0, S.astype(F32))
        S = (jnp.exp(dt_t[:, None, :] * A) * S
             + (dt_t * x_t)[:, None, :] * B_t[:, :, None]).astype(state_dtype)
        return S, jnp.einsum("bne,bn->be", S.astype(F32), C_t)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, Bm, Cm)) + (lost,)
    _, y = jax.lax.scan(one, jnp.zeros((B, N, A.shape[1]), state_dtype), xs)
    y = jnp.moveaxis(y, 0, 1) + p["D"].astype(F32) * x
    return (y * jax.nn.silu(z)) @ p["out_proj"].astype(F32)


def _attention(p, h, *, num_heads, kv_heads, head_block=10, rope_theta=None):
    """``rope_theta`` (a rotary code applied) is a wrong model the cell's
    ``why`` measures."""
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


def _swiglu(p, x):
    w = lambda n: p[n]["kernel"].astype(F32)              # noqa: E731
    return (jax.nn.silu(x @ w("gate_proj")) * (x @ w("up_proj"))) \
        @ w("down_proj")


def hidden_states(params, tokens, *, num_heads: int, kv_heads: int,
                  dt_rank: int, state: int, rms_eps: float,
                  layers: int = None, rope_theta: float = None,
                  **mamba_variant):
    """The residual stream [B, T, M] after ``layers`` whole layers (all
    of them when None), before the final norm. ``rope_theta`` and
    ``mamba_variant`` (:func:`_mamba1`'s) are the wrong models the cell's
    ``why`` measures."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens].astype(F32)
        n_layers = sum(1 for k in params if k.startswith("layer_"))
        for i in range(n_layers if layers is None else layers):
            p = params[f"layer_{i}"]
            h = _rms(x, p["input_norm"]["scale"], rms_eps)
            if "attn" in p:
                x = x + _attention(p["attn"], h, num_heads=num_heads,
                                   kv_heads=kv_heads, rope_theta=rope_theta)
            else:
                x = x + _mamba1(p["mamba"], h, dt_rank=dt_rank, state=state,
                                rms_eps=rms_eps, **mamba_variant)
            x = x + _swiglu(p["mlp"],
                            _rms(x, p["post_attn_norm"]["scale"], rms_eps))
        return x


def logits(params, tokens, at, **dims):
    """Logits [B, n, vocab] at the positions ``at`` [B, n] of ``tokens``
    [B, T] (tokens to the right of a position never reach it)."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, **dims)
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        x = _rms(x, params["final_norm"]["scale"], dims["rms_eps"])
        head = params["lm_head"]["kernel"].astype(F32) \
            if "lm_head" in params else params["embed"]["embedding"].astype(F32).T
        return x @ head
