"""Plain reference of the MiniCPM-SALA family (``model_type: minicpm_sala``,
openbmb/MiniCPM-SALA's ``config.json``): float32, ``highest`` matmul
precision, ONE sequence at a time, token-by-token recurrence, the block
selection over the WHOLE key sequence; no cache, no kernels, no batching,
nothing imported from ``deepspeed_tpu``. Queries and feed-forward rows go in
blocks (``lax.map``) only so that a 20k-token sequence fits a chip.

The layer equations. Lines marked *assumed* are not confirmed by the
catalog row (``config`` + ``described_as``); the configuration file lists
each under ``assumed`` with its reason.

Common: pre-norm, one residual stream. ``x_0 = scale_emb E[token]``
(``scale_emb`` 12); ``h = RMSNorm(x)`` (eps 1e-6); ``x <- x + r mixer(h)``;
``h = RMSNorm(x)``; ``x <- x + r W_down(silu(W_gate h) * W_up h)``, ``r =
scale_depth / sqrt(depth_published)`` = 1.4 / sqrt(32) (the PUBLISHED depth:
a constant of the model, kept by a cut); logits ``= W_head (RMSNorm(x_L) /
(hidden_size / dim_model_base))`` = ``/ 16``, untied.

Lightning layer (``lightning-attn``, H heads = H kv heads of d):
``q, k, v = W_q h, W_k h, W_v h``; ``q, k <- RMSNorm over each head's d``
(``qk_norm``; *assumed* a learned scale of d shared by the heads, the
family's MiniCPM4 code); ``q, k <- rotary(theta, rotate-half)``
(``lightning_use_rope``); per head ``S_t = lambda_h S_{t-1} + k_t v_t^T``,
``o_t = S_t^T q_t / sqrt(d)`` (``lightning_scale``), ``S`` float32 ``[d,
d]``, zero at the start; ``lambda_h = exp(-2^(-8 h / H))``, ``h = 1..H``
(*assumed*: the Lightning-Attention family's ALiBi slopes, the same in every
layer; MiniMax's per-layer factor not applied); ``o <- RMSNorm over each
head's d`` under a learned ``[H d]`` scale (``use_output_norm``; *assumed*
per head); ``y = W_o (o * sigmoid(W_g h))`` (``use_output_gate``; *assumed*
a full-width gate). No activation on q, k, v (*assumed*; MiniMax's silu not
applied: the layers were converted from softmax layers with QK-norm).

Sparse layer (``minicpm4``, H query / KV kv heads of d, a group of G = H /
KV query heads a kv head): ``q, k, v`` projected, the same per-head QK-norm,
NO position code (``attn_use_rope`` false). The selection's sizes are not in
the catalog row; MiniCPM4.1's published ``sparse_config`` is taken, all
seven *assumed*: ``kernel_size`` 32, ``kernel_stride`` 16, ``block_size``
64, ``topk`` 64, ``init_blocks`` 1, ``window_size`` 2,048, ``dense_len``
8,192. For the query at position ``t``, context ``n = t + 1``:

1. ``n < dense_len``: causal softmax attention over keys ``0..t``, scale
   ``1 / sqrt(d)``. (*Assumed*, a departure: by the QUERY's own context, so
   the rule is causal and the same under any chunking and in decode; the
   published code decides once a forward call by the call's length.)
2. else ``Kc_j = mean(k[16 j : 16 j + 32])`` for every window wholly at or
   before ``t`` (``16 j + 31 <= t``); no parameters.
3. ``p_hj = softmax_j(q_h . Kc_j / sqrt(d))`` a query head, exact, float32
   (*assumed*: not the published code's coarse log-sum-exp approximation);
   ``P_j`` = the sum of ``p_hj`` over the group's G heads.
4. block score ``B_b = max P_j`` over the windows that overlap block ``b``
   (``j`` in ``4 b - 1 .. 4 b + 3``, clipped), for ``b <= floor(t / 64)``.
5. forced: block 0 and the 32 blocks ending at ``t``'s own score ``+inf``;
   the 64 highest-scoring blocks (ties to the lower index; all of them
   while there are at most 64): one selection a kv head, shared by its G
   query heads.
6. ``o_h = softmax over the selected blocks' keys at positions <= t of (q_h
   . k / sqrt(d)) V``.
7. ``y = W_o (o * sigmoid(W_g h))`` (``attn_use_output_gate``; *assumed*
   full width).

Keywords past ``rms_eps`` switch ONE thing wrong, for
``tools/chip_parity.py``'s table of what the cell's limits catch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, pos, theta):
    """Rotate-half. x [T, H, d]; pos [T]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def _w(p):
    return p["kernel"].astype(F32)


def _in_blocks(fn, rows, xs):
    """``fn`` over leading-axis blocks of ``rows`` rows of every array of
    ``xs`` (padded to whole blocks; the padding's results dropped)."""
    T = xs[0].shape[0]
    n = -(-T // rows)
    pad = n * rows - T
    cut = [jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
        n, rows, *x.shape[1:]) for x in xs]
    out = jax.lax.map(lambda a: fn(*a), tuple(cut))
    return out.reshape(n * rows, *out.shape[2:])[:T]


def _lightning(p, h, pos, heads, d, eps, theta, rope, decay_one):
    T = h.shape[0]
    q = _rms((h @ _w(p["q_proj"])).reshape(T, heads, d),
             p["q_norm"]["scale"], eps)
    k = _rms((h @ _w(p["k_proj"])).reshape(T, heads, d),
             p["k_norm"]["scale"], eps)
    v = (h @ _w(p["v_proj"])).reshape(T, heads, d)
    if rope:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    hh = jnp.arange(1, heads + 1, dtype=F32)
    lam = jnp.ones((heads,), F32) if decay_one \
        else jnp.exp(-jnp.exp2(-8.0 * hh / heads))

    def step(S, qkv):
        q_t, k_t, v_t = qkv
        S = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t) * d ** -0.5
    _, o = jax.lax.scan(step, jnp.zeros((heads, d, d), F32), (q, k, v))
    o = _rms(o, p["o_norm"]["scale"].reshape(heads, d), eps)
    gate = jax.nn.sigmoid(h @ _w(p["g_proj"]))
    return (o.reshape(T, heads * d) * gate) @ _w(p["o_proj"])


def _sparse(p, h, pos, H, KV, d, eps, sp, theta, rope, selection, rows):
    T = h.shape[0]
    G = H // KV
    stride, kernel, bs = sp["kernel_stride"], sp["kernel_size"], \
        sp["block_size"]
    q = _rms((h @ _w(p["q_proj"])).reshape(T, H, d),
             p["q_norm"]["scale"], eps)
    k = _rms((h @ _w(p["k_proj"])).reshape(T, KV, d),
             p["k_norm"]["scale"], eps)
    v = (h @ _w(p["v_proj"])).reshape(T, KV, d)
    if rope:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    NB = -(-T // bs)
    J = max((T - kernel) // stride + 1, 1)
    win = stride * jnp.arange(J)[:, None] + jnp.arange(kernel)[None, :]
    kc = jnp.mean(k[jnp.minimum(win, T - 1)], axis=1)        # [J, KV, d]
    j = jnp.arange(J)
    b = jnp.arange(NB)
    # window j = keys [stride j, stride j + kernel) overlaps block b
    overlap = (stride * j[None, :] < bs * (b[:, None] + 1)) \
        & (stride * j[None, :] + kernel > bs * b[:, None])   # [NB, J]
    key = jnp.arange(T)

    def block(qb, tb):
        """qb [rows, H, d], tb [rows] -> o [rows, H, d]."""
        qg = qb.reshape(-1, KV, G, d)
        seen = (stride * j[None, :] + kernel - 1 <= tb[:, None])
        cs = jnp.einsum("tkgd,jkd->tkgj", qg, kc) * d ** -0.5
        pj = jax.nn.softmax(
            jnp.where(seen[:, None, None, :], cs, -jnp.inf), axis=-1)
        P = jnp.where(seen[:, None, :], jnp.sum(pj, axis=2), -jnp.inf)
        B = jnp.max(jnp.where(overlap[None, None], P[:, :, None, :],
                              -jnp.inf), axis=-1)            # [t, KV, NB]
        own = (tb // bs)[:, None]
        forced = (b[None, :] < sp["init_blocks"]) \
            | (b[None, :] > own - sp["window_size"] // bs)
        B = jnp.where(forced[:, None, :], jnp.inf, B)
        B = jnp.where((b[None, :] <= own)[:, None, :], B, -jnp.inf)
        # the topk best, ties to the lower index (a stable sort)
        order = jnp.argsort(-B, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        chosen = (rank < sp["topk"]) & (B > -jnp.inf)        # [t, KV, NB]
        if selection == "dense":
            chosen = jnp.ones_like(chosen)
        chosen = chosen | (tb + 1 < sp["dense_len"])[:, None, None]
        mask = chosen[:, :, key // bs] \
            & (key[None, :] <= tb[:, None])[:, None, :]
        sc = jnp.einsum("tkgd,jkd->tkgj", qg, k) * d ** -0.5
        pa = jax.nn.softmax(
            jnp.where(mask[:, :, None, :], sc, -jnp.inf), axis=-1)
        return jnp.einsum("tkgj,jkd->tkgd", pa, v).reshape(-1, H, d)

    o = _in_blocks(block, min(rows, T), (q, pos))
    gate = jax.nn.sigmoid(h @ _w(p["g_proj"]))
    return (o.reshape(T, H * d) * gate) @ _w(p["o_proj"])


def selected_blocks(q, k, t, sparse):
    """The selection of ONE query, for the tests of the selection itself:
    q [H, d] the (normed) query at position ``t``, k [T, KV, d] the
    sequence's (normed) keys. Returns a bool [KV, NB]."""
    sp = sparse
    T, KV, d = k.shape
    G = q.shape[0] // KV
    s, ks, bs = sp["kernel_stride"], sp["kernel_size"], sp["block_size"]
    NB = t // bs + 1
    out = []
    for kv in range(KV):
        js = [j for j in range(T) if s * j + ks - 1 <= t]
        cs = jnp.stack([jnp.stack([
            jnp.dot(q[kv * G + g], jnp.mean(k[s * j:s * j + ks, kv], 0))
            * d ** -0.5 for j in js]) for g in range(G)])
        pj = jnp.sum(jax.nn.softmax(cs, axis=-1), axis=0)
        P = dict(zip(js, [float(x) for x in pj]))
        score = []
        for b in range(NB):
            over = [P[j] for j in js
                    if s * j < bs * (b + 1) and s * j + ks > bs * b]
            forced = b < sp["init_blocks"] \
                or b > t // bs - sp["window_size"] // bs
            score.append(float("inf") if forced
                         else max(over) if over else float("-inf"))
        order = sorted(range(NB), key=lambda b: (-score[b], b))
        out.append([b in order[:sp["topk"]] for b in range(NB)])
    return jnp.asarray(out)


def logits_one(params, toks, where, *, sparse_layers, num_heads, kv_heads,
               lightning_heads, head_dim, rope_theta, sparse, scale_emb,
               residual_scale, logit_divisor, rms_eps, rows: int = 128,
               selection: str = "topk", topk=None, window_size=None,
               init_blocks=None, sparse_rope: bool = False,
               lightning_rope: bool = True, decay_one: bool = False,
               mup: bool = True):
    """ONE sequence: toks [T], where [n] positions -> logits [n, vocab]
    float32. params: the tree of ``deepspeed_tpu/models/minicpm_sala.py``
    (``layer_i/{attn | lin}``, ``mlp``, norms, ``embed``, ``lm_head``);
    ``sparse_layers``: a bool a layer."""
    sp = dict(sparse)
    for key, val in (("topk", topk), ("window_size", window_size),
                     ("init_blocks", init_blocks)):
        if val is not None:
            sp[key] = val
    if not mup:
        scale_emb, residual_scale, logit_divisor = 1.0, 1.0, 1.0
    with jax.default_matmul_precision("highest"):
        T = toks.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        x = params["embed"]["embedding"][toks].astype(F32) * scale_emb
        for li, is_sparse in enumerate(sparse_layers):
            p = params[f"layer_{li}"]
            h = _rms(x, p["input_norm"]["scale"], rms_eps)
            if is_sparse:
                y = _sparse(p["attn"], h, pos, num_heads, kv_heads,
                            head_dim, rms_eps, sp, rope_theta, sparse_rope,
                            selection, rows)
            else:
                y = _lightning(p["lin"], h, pos, lightning_heads, head_dim,
                               rms_eps, rope_theta, lightning_rope,
                               decay_one)
            x = x + residual_scale * y
            h = _rms(x, p["post_attn_norm"]["scale"], rms_eps)
            pm = p["mlp"]
            y = _in_blocks(
                lambda hb, pm=pm: (
                    jax.nn.silu(hb @ _w(pm["gate_proj"]))
                    * (hb @ _w(pm["up_proj"]))) @ _w(pm["down_proj"]),
                min(1024, T), (h,))
            x = x + residual_scale * y
        x = _rms(x[where], params["final_norm"]["scale"], rms_eps) \
            / logit_divisor
        return x @ _w(params["lm_head"])


def logits(params, tokens, at, **dims):
    """tokens [B, T]; at [B, n] positions -> logits [B, n, vocab]
    float32: :func:`logits_one` a sequence, one after another."""
    return jnp.stack([logits_one(params, tokens[b], at[b], **dims)
                      for b in range(tokens.shape[0])])
