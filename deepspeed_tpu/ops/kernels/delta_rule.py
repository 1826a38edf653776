"""Gated delta-rule linear attention (KDA): the recurrence in two forms.

Per head, with key width ``dk`` and value width ``dv``, a state
``S [dk, dv]`` (float32, zero at the start of a sequence) follows

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,            a_t = exp(g_t) in (0, 1) per channel of dk

which is ``S_t = Diag(a_t) S_{t-1} + k_t u_t^T`` with
``u_t = beta_t (v_t - S_{t-1}^T (a_t * k_t))``. A position with
``beta_t = 0`` and ``g_t = 0`` leaves the state as it was, which is how
the callers mask padding.

* :func:`kda_recurrent` — token by token (``lax.scan``): the definition,
  and what tier-1 holds the other two to.
* :func:`kda_step` / :func:`kda_decode_update` — one token for decode.
  Memory-bound: the state is read once and written once. The second is
  the Pallas form that updates the serving state pool in place, the rows
  picked by slot through scalar prefetch; ``kda_step`` is its jnp twin
  (the CPU path).
* :func:`kda_chunked` — prefill. Chunks of ``chunk`` positions from the
  WY / UT-transform representation: with ``G`` the running sum of ``g``
  inside a chunk, ``A_ti = sum_c k_tc k_ic exp(G_tc - G_ic)`` (``i < t``)
  and ``(I + Diag(beta) A) U = Diag(beta) (V - (K * exp G) S_0)`` give
  every ``u`` of the chunk by one triangular solve; the outputs and the
  next state are matmuls of ``U``. The decay ratios are never formed as
  ``exp(G_t) / exp(G_i)``: inside a sub-block of ``sub`` positions they
  are the pairwise ``exp(G_t - G_i)`` (always <= 1), and across
  sub-blocks both factors are taken against the row's sub-block start,
  so a decay near 0 underflows to the right answer instead of dividing
  by zero. It is an algorithm, not a different model.
* :func:`kda_prefill` / :func:`kda_chunk_prefill` — the chunked form as
  one Pallas kernel a layer, on the pool's transposed states: the state
  stays in VMEM across a row's chunks, the decay tables, the triangular
  solve and the state's update never leave it. ``kda_chunked`` is its
  jnp twin (the CPU path, and every shape off the kernel's grain:
  :func:`kda_prefill_uses_kernel`).

The serving state pool is one array a layer, ``[rows, H, dv, dk]``: XLA's
gather and scatter of 4 MB rows stalled the chip at offsets past 2^30
bytes of one array (PERF.md, PR 32), which a layer stays under up to 255
rows. Its layout is ``[.., dv, dk]`` (the transpose): a vector over
``dk`` (decay, key, query) then lies along the lanes of a state tile and
broadcasts over its sublanes for free; only ``v`` needs turning.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def kda_step(q, k, v, g, beta, S):
    """One token. q, k, g [.., dk]; v [.., dv]; beta [..]; S [.., dk, dv]
    (all float32). Returns (o [.., dv], S_new)."""
    Sd = jnp.exp(g)[..., None] * S
    kS = jnp.sum(k[..., None] * Sd, axis=-2)
    S_new = Sd + (beta[..., None] * k)[..., None] * (v - kS)[..., None, :]
    return jnp.sum(q[..., None] * S_new, axis=-2), S_new


def kda_recurrent(q, k, v, g, beta, S0):
    """The definition. q, k, g [B, T, H, dk]; v [B, T, H, dv];
    beta [B, T, H]; S0 [B, H, dk, dv]. Returns (o [B, T, H, dv], S_T)."""
    def one(S, x):
        o, S = kda_step(*x, S)
        return S, o
    xs = tuple(jnp.moveaxis(a.astype(F32), 1, 0) for a in (q, k, v, g, beta))
    S, o = jax.lax.scan(one, S0.astype(F32), xs)
    return jnp.moveaxis(o, 0, 1), S


def _chunk(S, x, *, sub):
    """One chunk of :func:`kda_chunked`: x = (q, k, v, g, beta) with
    [B, H, L, d] leaves, S [B, H, dk, dv]."""
    q, k, v, g, beta = x
    L, dk = q.shape[-2], q.shape[-1]
    ns = L // sub
    lead = q.shape[:-2]
    G = jnp.cumsum(g, axis=-2)                          # inclusive, <= 0
    Gs = G.reshape(*lead, ns, sub, dk)
    # the running sum just before each sub-block: its reference point
    R = jnp.concatenate([jnp.zeros_like(Gs[..., :1, -1, :]),
                         Gs[..., :-1, -1, :]], axis=-2)  # [.., ns, dk]
    row = jnp.exp(Gs - R[..., None, :])                  # <= 1
    ks = k.reshape(*lead, ns, sub, dk)
    qs = q.reshape(*lead, ns, sub, dk)
    # columns of EARLIER sub-blocks against this sub-block's reference
    col = jnp.exp(jnp.minimum(R[..., :, None, :] - G[..., None, :, :], 0.0))
    earlier = (jnp.arange(L)[None, :]
               < (jnp.arange(ns) * sub)[:, None]).astype(F32)
    kcol = k[..., None, :, :] * col * earlier[..., None]  # [.., ns, L, dk]
    a_x = jnp.einsum("...icd,...ijd->...icj", ks * row, kcol, precision=_HI)
    qk_x = jnp.einsum("...icd,...ijd->...icj", qs * row, kcol, precision=_HI)
    # inside a sub-block: the pairwise ratios themselves
    D = jnp.exp(jnp.minimum(Gs[..., :, None, :] - Gs[..., None, :, :], 0.0))
    a_in = jnp.sum(ks[..., :, None, :] * D * ks[..., None, :, :], axis=-1)
    qk_in = jnp.sum(qs[..., :, None, :] * D * ks[..., None, :, :], axis=-1)
    own = jnp.eye(ns, dtype=F32)[:, None, :, None]       # [ns, 1, ns, 1]

    def whole(cross, inside):
        full = cross.reshape(*lead, ns, sub, ns, sub) \
            + inside[..., :, :, None, :] * own
        return full.reshape(*lead, L, L)

    t = jnp.arange(L)
    A = whole(a_x, a_in) * (t[:, None] > t[None, :])
    QK = whole(qk_x, qk_in) * (t[:, None] >= t[None, :])
    eG = jnp.exp(G)
    M = jnp.eye(L, dtype=F32) + beta[..., :, None] * A
    rhs = beta[..., None] * jnp.concatenate([k * eG, v], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(M, rhs, lower=True,
                                            unit_diagonal=True)
    W, U0 = sol[..., :dk], sol[..., dk:]
    U = U0 - jnp.einsum("...lk,...kv->...lv", W, S, precision=_HI)
    o = jnp.einsum("...lk,...kv->...lv", q * eG, S, precision=_HI) \
        + jnp.einsum("...lj,...jv->...lv", QK, U, precision=_HI)
    GL = G[..., -1:, :]
    S = jnp.swapaxes(jnp.exp(GL), -1, -2) * S \
        + jnp.einsum("...lk,...lv->...kv", k * jnp.exp(GL - G), U,
                     precision=_HI)
    return S, o


def kda_chunked(q, k, v, g, beta, S0, *, chunk: int = 64, sub: int = 16):
    """The chunked form: same arguments and results as
    :func:`kda_recurrent`. ``T`` is padded up to whole chunks with
    positions that change nothing."""
    B, T, H, _ = q.shape
    L = min(chunk, T)
    sub = min(sub, L)
    if L % sub:
        sub = L
    pad = (-T) % L
    xs = []
    for a in (q, k, v, g, beta):
        a = jnp.moveaxis(a.astype(F32), 1, 2)           # [B, H, T, ..]
        if pad:
            a = jnp.pad(a, ((0, 0), (0, 0), (0, pad)) + ((0, 0),)
                        * (a.ndim - 3))
        a = a.reshape(B, H, (T + pad) // L, L, *a.shape[3:])
        xs.append(jnp.moveaxis(a, 2, 0))                # chunks leading
    S, o = jax.lax.scan(functools.partial(_chunk, sub=sub),
                        S0.astype(F32), tuple(xs))
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, T + pad, -1)[:, :, :T]
    return jnp.moveaxis(o, 1, 2), S


# --------------------------------------------------------------------- #
# prefill: one Pallas kernel a layer, the state carried in VMEM
# --------------------------------------------------------------------- #

_CHUNK, _SUB = 64, 16
_PREFILL_HEADS = 8     # heads a grid step: one sublane tile a position
BF16 = jnp.bfloat16


def kda_prefill_uses_kernel(T: int, H: int, dk: int, dv: int,
                            backend: Optional[str] = None) -> bool:
    """Whether :func:`kda_prefill` runs the Pallas chunk kernel: on the
    TPU, at ``T`` a whole number of 64-position chunks, ``dk`` and ``dv``
    whole 128-lane groups and the heads a whole number of head blocks.
    The mixer dispatches on it and the engine counts by it."""
    backend = backend or jax.default_backend()
    return (backend == "tpu" and T >= _CHUNK and T % _CHUNK == 0
            and dk % 128 == 0 and dv % 128 == 0
            and H % _PREFILL_HEADS == 0)


def _dot(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=F32)


_NT = ((1,), (1,))          # a @ b^T
_TN = ((0,), (0,))          # a^T @ b


def _own(x, i):
    """Row ``i`` of every sub-block, over its sub-block's rows."""
    x4 = x.reshape((x.shape[0] // _SUB, _SUB) + x.shape[1:])
    return jnp.broadcast_to(x4[:, i:i + 1], x4.shape).reshape(x.shape)


def _chunk_consts():
    """What the heads of a grid step share: the 0 / 1 rows that sum ``g``
    over a span of positions (bfloat16, exact; three times along the
    contraction for ``g``'s three parts), and the tables' masks."""
    L, sub = _CHUNK, _SUB
    t = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    t0 = t // sub * sub
    # up to the row; the same from its sub-block's start; after the row;
    # and for each later sub-block what lies between the row and that
    # sub-block's start
    spans = [j <= t, (j <= t) & (j >= t0), j > t] + [
        ((j > t) & (j < n))[:n] for n in range(sub, L, sub)]
    spans = jnp.concatenate([x.astype(BF16) for x in spans])
    return {"spans": jnp.concatenate([spans] * 3, axis=1),
            "eye": (j == t).astype(F32),
            "col": j - t0, "row": (t - t0)[:, :1]}


def _chunk_head(q, k, v, g, beta, St, spans, c):
    """One chunk of one head inside the kernel: :func:`_chunk` with the
    same reference points, the state transposed. q, k, g [L, dk];
    v [L, dv]; beta [L, 1]; St [dv, dk]; ``spans`` and ``c`` of
    :func:`_chunk_consts`.
    Every exponent is the sum of the ``g`` it spans, by one exact matmul
    against 0 / 1 rows (``g`` in three bfloat16 parts), never a
    difference of two running sums. Returns (o [L, dv], St)."""
    L, dv = v.shape
    sub = _SUB
    hi = g.astype(BF16)
    mid = (g - hi.astype(F32)).astype(BF16)
    lo = (g - hi.astype(F32) - mid.astype(F32)).astype(BF16)
    sums = jax.lax.dot_general(spans, jnp.concatenate([hi, mid, lo]),
                               ((((1,), (0,))), ((), ())),
                               preferred_element_type=F32)
    # the running sum, the same from the row's sub-block start (<= 0:
    # against the sub-block's reference point), what follows the row
    G, Gl, Gaft = sums[:L], sums[L:2 * L], sums[2 * L:3 * L]
    eG = jnp.exp(G)
    row = jnp.exp(Gl)
    kb = k * beta                         # Diag(beta) A, not A, below
    kr, qr = kb * row, q * row
    # earlier sub-blocks' columns against this sub-block's reference
    n_x, qk_x = [jnp.zeros((sub, L), F32)], [jnp.zeros((sub, L), F32)]
    at = 3 * L
    for n in range(sub, L, sub):
        kcol = jnp.concatenate([k[:n] * jnp.exp(sums[at:at + n]),
                                jnp.zeros((L - n, k.shape[1]), F32)])
        at += n
        x = _dot(jnp.concatenate([kr[n:n + sub], qr[n:n + sub]]), kcol, _NT)
        n_x.append(x[:sub])
        qk_x.append(x[sub:])
    N_x, QK_x = jnp.concatenate(n_x), jnp.concatenate(qk_x)
    # inside a sub-block the pairwise ratios, a column of every
    # sub-block at a time; the same pass inverts the diagonal blocks of
    # I + Diag(beta) A by forward substitution, row i final at step i
    Ti, QKd = c["eye"], jnp.zeros((L, L), F32)
    for i in range(sub):
        kD = _own(k, i) * jnp.exp(Gl - _own(Gl, i))
        n_i = jnp.sum(kb * kD, axis=-1, keepdims=True)
        qk_i = jnp.sum(q * kD, axis=-1, keepdims=True)
        QKd = jnp.where(c["col"] == i, qk_i, QKd)
        Ti = Ti - jnp.where(c["row"] > i, n_i, 0.0) * _own(Ti, i)
    QK = QK_x + jnp.where((c["col"] >= 0) & (c["col"] <= c["row"]), QKd, 0.0)
    # (I + Diag(beta) A) U = Diag(beta) (V - (K exp G) S0), sub-block by
    # sub-block: Ti holds the inverses of the diagonal blocks
    xs = _dot(jnp.concatenate([k * eG, q * eG]), St, _NT)       # [2L, dv]
    YN = _dot(Ti, jnp.concatenate([beta * v - beta * xs[:L], N_x], axis=1))
    Y, Nb = YN[:, :dv], YN[:, dv:]
    U = [Y[:sub]]
    for n in range(sub, L, sub):
        done = jnp.concatenate(U + [jnp.zeros((L - n, dv), F32)])
        U.append(Y[n:n + sub] - _dot(Nb[n:n + sub], done))
    U = jnp.concatenate(U)
    o = xs[L:] + _dot(QK, U)
    St = St * eG[L - 1:] + _dot(U, k * jnp.exp(Gaft), _TN)
    return o, St


def _prefill_kernel(n_ref, q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref,
                    o_ref, s_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    # a chunk wholly past the row's positions leaves the state alone
    live = pl.program_id(2) * _CHUNK < n_ref[pl.program_id(0)]

    @pl.when(live)
    def _():
        # the block's heads side by side (one trace, batched): a head's
        # chunk is a chain of small dependent matmuls, and the chip fills
        # one chain's waits with another's work (6.6 ms a layer one head
        # at a time, 2.5 all eight: PERF.md, PR 42). A head's rows lie a
        # sublane tile apart: strided loads and stores
        hs = range(s_ref.shape[0])
        each = lambda ref: jnp.stack([ref[:, h, :] for h in hs])  # noqa: E731
        b = b_ref[...]
        c = _chunk_consts()
        # the span rows batched with the heads (a copy a head): their
        # matmul then gives each head's sums as the rest takes them;
        # shared, the heads come out as a middle axis and every use of
        # a sum is a relayout
        spans = c.pop("spans")
        o, St = jax.vmap(lambda *x: _chunk_head(*x, c))(
            each(q_ref), each(k_ref), each(v_ref), each(g_ref),
            jnp.stack([b[:, h:h + 1] for h in hs]), s_ref[...],
            jnp.broadcast_to(spans, (len(hs),) + spans.shape))
        for h in hs:
            o_ref[:, h, :] = o[h]
        s_ref[...] = St

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


# jitted under its own name, as the decode update below is: the device
# trace then names the Mosaic call after it
@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_chunk_prefill(q, k, v, g, beta, St0, lengths, *, interpret=False):
    """The chunked form as one Pallas call. q, k, g [B, T, H, dk],
    v [B, T, H, dv], beta [B, T, H] (float32), St0 [B, H, dv, dk] the
    TRANSPOSED states, as the pool holds them; ``lengths`` [B] int32:
    row b's positions from ``lengths[b]`` on change nothing (beta 0,
    g 0 there), and a chunk that holds only such is neither fetched nor
    computed (its outputs are zeros). The grid is (row, block of heads,
    chunk), the chunks innermost and in order: a row's states stay in
    VMEM from its first chunk to its last. The operands are taken as
    they lie (a block is 64 positions of 8 heads, one sublane tile a
    position): no copy to a per-head layout on either side. Returns
    (o [B, T, H, dv], St [B, H, dv, dk])."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    hb, L = _PREFILL_HEADS, _CHUNK
    # the last chunk with a position of row b: later steps name it
    # again, and a block named twice in a row is fetched once
    at = lambda b, c, n: jnp.minimum(                     # noqa: E731
        c, jnp.maximum((n[b] + L - 1) // L - 1, 0))
    seq = lambda d: pl.BlockSpec(                         # noqa: E731
        (None, L, hb, d), lambda b, j, c, n: (b, at(b, c, n), j, 0))
    st = pl.BlockSpec((None, hb, dv, dk), lambda b, j, c, n: (b, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, H // hb, T // L),
        in_specs=[seq(dk), seq(dk), seq(dv), seq(dk),
                  pl.BlockSpec((None, None, L, hb),
                               lambda b, j, c, n: (b, j, at(b, c, n), 0)),
                  st],
        out_specs=[pl.BlockSpec((None, L, hb, dv),
                                lambda b, j, c, n: (b, c, j, 0)), st])
    return pl.pallas_call(
        _prefill_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, T, H, dv), F32),
                   jax.ShapeDtypeStruct(St0.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, k, v, g,
      # the step sizes by block of heads: [B, H / hb, T, hb]
      jnp.moveaxis(beta.reshape(B, T, H // hb, hb), 2, 1), St0)


def kda_prefill(q, k, v, g, beta, St0, lengths=None, *,
                impl: Optional[str] = None):
    """A prefill chunk of every row on TRANSPOSED states St0
    [B, H, dv, dk] (the pool's layout): the Pallas kernel where
    :func:`kda_prefill_uses_kernel` says so, :func:`kda_chunked`
    elsewhere. ``lengths`` [B] (None: every position counts) promises
    that row b's positions from ``lengths[b]`` on have ``beta = 0`` and
    ``g = 0`` and that nobody reads their outputs. Returns
    (o [B, T, H, dv] float32, St). ``impl`` ("pallas", "interpret",
    "xla") is for the tests."""
    B, T, H, dk = q.shape
    if impl is None:
        impl = "pallas" if kda_prefill_uses_kernel(
            T, H, dk, v.shape[-1]) else "xla"
    if impl == "xla":
        o, S = kda_chunked(q, k, v, g, beta, jnp.swapaxes(St0, -1, -2))
        return o, jnp.swapaxes(S, -1, -2)
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    return kda_chunk_prefill(q, k, v, g, beta, St0.astype(F32),
                             lengths.astype(jnp.int32),
                             interpret=impl == "interpret")


# --------------------------------------------------------------------- #
# decode: the state pool updated in place
# --------------------------------------------------------------------- #

_HEADS = 8       # heads a grid step: 8 x 64 KiB of state in, as much out


def _decode_kernel(slots_ref, q_ref, k_ref, kb_ref, a_ref, v_ref, s_ref,
                   so_ref, o_ref):
    del slots_ref                            # used by the index maps only
    St = s_ref[...]                          # [hb, dv, dk]: S transposed
    hb, dv, dk = St.shape
    Sd = St * a_ref[...][:, None, :]
    kS = jnp.sum(Sd * k_ref[...][:, None, :], axis=-1, keepdims=True)
    # v as a column over the tile's sublanes: rows of v, turned
    v_col = jnp.swapaxes(
        jnp.broadcast_to(v_ref[...][:, None, :], (hb, dk, dv)), 1, 2)
    Sn = Sd + (v_col - kS) * kb_ref[...][:, None, :]
    so_ref[...] = Sn
    o_ref[...] = jnp.sum(Sn * q_ref[...][:, None, :], axis=-1)


# jitted under its own name: the device trace names a Mosaic call after
# the function that encloses it, and the benchmark's readers find the
# decode state-update by this one
@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode_state_update(state, slots, q, k, kb, a, v, *,
                            interpret=False):
    S, H, dk = q.shape
    dv = v.shape[-1]
    hb = _HEADS if H % _HEADS == 0 else H
    vec = lambda d: pl.BlockSpec(                        # noqa: E731
        (None, hb, d), lambda i, j, *_: (i, j, 0))
    st = pl.BlockSpec((None, hb, dv, dk),
                      lambda i, j, slots: (slots[i], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(S, H // hb),
        in_specs=[vec(dk), vec(dk), vec(dk), vec(dk), vec(dv), st],
        out_specs=[st, vec(dv)])
    return pl.pallas_call(
        _decode_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((S, H, dv), F32)],
        # operand 6 (after the prefetched slots) is the pool
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(slots, q, k, kb, a, v, state)


def kda_decode_update(state, slots, q, k, v, g, beta, *,
                      impl: Optional[str] = None) -> Tuple[jnp.ndarray,
                                                           jnp.ndarray]:
    """One decode token for every row, on one layer's state pool in place.

    state [rows, H, dv, dk] float32 (transposed states);
    ``slots`` [S] int32 the pool row of each batch row
    (distinct for live rows; a row with ``beta = 0, g = 0`` writes back
    what it read); q, k, g [S, H, dk], v [S, H, dv], beta [S, H].
    Returns (o [S, H, dv] float32, state). ``impl``: "pallas" (the TPU
    default: one read and one write of each state), "xla" (elsewhere:
    gather, :func:`kda_step`, scatter)."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    if impl == "xla":
        St = state[slots]                                # [S, H, dv, dk]
        o, Sn = kda_step(q, k, v, g, beta, jnp.swapaxes(St, -1, -2))
        return o, state.at[slots].set(jnp.swapaxes(Sn, -1, -2))
    state, o = kda_decode_state_update(
        state, slots.astype(jnp.int32), q, k, beta[..., None] * k,
        jnp.exp(g), v, interpret=impl == "interpret")
    return o, state


# --------------------------------------------------------------------- #
# ONE decay a head (the gated delta rule of the state-space lineage):
# the recurrence above with a_t the same in every channel of a head's
# keys, keys and values of two widths. g [.., H] is the head's log decay.
# The definition and the chunked twin are the channel forms on a
# broadcast decay; the two Pallas kernels below are the scalar forms.
# --------------------------------------------------------------------- #

def _over_keys(g, q):
    """The head's log decay g [.., H] in every channel of q [.., H, dk]."""
    return jnp.broadcast_to(g[..., None].astype(F32), q.shape)


def gdn_state_shape(H: int, dk: int, dv: int) -> Tuple[int, int]:
    """A sequence's states of one layer as the serving pool holds them:
    ``[dk, H * dv]``, keys along the sublanes and (head, value) along the
    lanes. Where ``dk`` is a whole number of sublane tiles and ``H * dv``
    of lane tiles the array tiles whole (96 = 12 x 8, 30 x 192 = 45 x
    128), so the decode step moves the state's bytes and no more;
    ``[H, dv, dk]``, KDA's layout, would store 96 lanes as 128."""
    return (dk, H * dv)


def _from_pool(St, H: int):
    """[B, dk, H * dv] (the pool's rows) -> [B, H, dk, dv]."""
    B, dk, W = St.shape
    return jnp.moveaxis(St.reshape(B, dk, H, W // H), 2, 1)


def _to_pool(S):
    """[B, H, dk, dv] -> [B, dk, H * dv]."""
    B, H, dk, dv = S.shape
    return jnp.moveaxis(S, 1, 2).reshape(B, dk, H * dv)


def _lane_group(dv: int) -> int:
    """Heads whose values fill whole 128-lane tiles side by side."""
    return 128 // math.gcd(dv, 128)


def gdn_decode_uses_kernel(H: int, dk: int, dv: int,
                           backend: Optional[str] = None) -> bool:
    """Whether :func:`gdn_decode_update` runs the Pallas update: on the
    TPU, keys a whole number of sublane tiles and the heads a whole
    number of lane groups (two heads of 192 values are three tiles)."""
    backend = backend or jax.default_backend()
    return backend == "tpu" and dk % 8 == 0 and H % _lane_group(dv) == 0


_DECODE_BLOCK_BYTES = 1 << 20      # of state a grid step reads, and writes


def _decode_heads(H: int, dk: int, dv: int) -> int:
    """Heads a grid step of the decode update: the most whole lane groups
    that divide the heads and keep a step's state under a megabyte (10 of
    30 at 96 x 192: 737 KB in, as much out, twice for the pipeline)."""
    group = _lane_group(dv)
    fit = [n for n in range(group, H + 1, group)
           if H % n == 0 and n * dk * dv * 4 <= _DECODE_BLOCK_BYTES]
    return max(fit) if fit else group


def _gdn_decode_kernel(slots_ref, qk_ref, row_ref, s_ref, so_ref, o_ref, *,
                       dv: int, group: int):
    del slots_ref                            # used by the index maps only
    dk, W = s_ref.shape                      # W = heads of the step x dv
    gw = group * dv                          # a group's lanes: whole tiles
    lane = jax.lax.broadcasted_iota(jnp.int32, (dk, gw), 1)
    q_t, k_t = qk_ref[0], qk_ref[1]          # [dk, heads]: a head a column

    def over_lanes(x, first):
        """The columns of heads ``first ..`` over their own values'
        lanes: [dk, gw]."""
        out = jnp.broadcast_to(x[:, first:first + 1], (dk, gw))
        for i in range(1, group):
            out = jnp.where(lane >= i * dv, jnp.broadcast_to(
                x[:, first + i:first + i + 1], (dk, gw)), out)
        return out

    for n in range(W // gw):
        at = slice(n * gw, (n + 1) * gw)
        kc, qc = over_lanes(k_t, n * group), over_lanes(q_t, n * group)
        v, a, beta = (row_ref[i:i + 1, at] for i in range(3))
        Sd = s_ref[:, at] * a
        kS = jnp.sum(Sd * kc, axis=0, keepdims=True)
        Sn = Sd + kc * ((v - kS) * beta)
        so_ref[:, at] = Sn
        o_ref[:, at] = jnp.sum(Sn * qc, axis=0, keepdims=True)


# jitted under its own name: the device trace names the Mosaic call after
# it, and the benchmark's reader finds the update by this one
@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_decode_state_update(state, slots, q, k, v, a, beta, *,
                            interpret=False):
    """state [rows, dk, H * dv] float32 (:func:`gdn_state_shape`), in
    place; q, k [S, H, dk], v [S, H, dv], a (the decay, exp g) and beta
    [S, H], all float32. The grid is (row, block of heads): a step reads
    and writes ``[dk, heads x dv]`` of one slot's state, whole tiles. What
    lies along the lanes (v, the decay, the step size, the output) is a
    row of ``heads x dv`` lanes; what lies along the sublanes (q, k) a
    column a head, spread over the head's lanes inside. Returns (state,
    o [S, H, dv])."""
    S, H, dk = q.shape
    dv = v.shape[-1]
    hb = _decode_heads(H, dk, dv)
    # [S, blocks, 2, dk, hb]: q and k of a block's heads as columns
    qk = jnp.moveaxis(jnp.stack([q, k], 1).reshape(S, 2, H // hb, hb, dk),
                      (2, 3), (1, 4))
    rows = jnp.stack([v.reshape(S, H * dv), jnp.repeat(a, dv, axis=-1),
                      jnp.repeat(beta, dv, axis=-1)], 1)   # [S, 3, H dv]
    st = pl.BlockSpec((None, dk, hb * dv),
                      lambda i, j, slots: (slots[i], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(S, H // hb),
        in_specs=[pl.BlockSpec((None, None, 2, dk, hb),
                               lambda i, j, *_: (i, j, 0, 0, 0)),
                  pl.BlockSpec((None, 3, hb * dv),
                               lambda i, j, *_: (i, 0, j)), st],
        out_specs=[st, pl.BlockSpec((None, 1, hb * dv),
                                    lambda i, j, *_: (i, 0, j))])
    state, o = pl.pallas_call(
        functools.partial(_gdn_decode_kernel, dv=dv,
                          group=_lane_group(dv)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((S, 1, H * dv), F32)],
        # operand 3 (after the prefetched slots) is the pool
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(slots, qk, rows, state)
    return state, o.reshape(S, H, dv)


def gdn_decode_update(state, slots, q, k, v, g, beta, *,
                      impl: Optional[str] = None):
    """One decode token for every row on one layer's state pool
    ``[rows, dk, H * dv]`` in place, as :func:`kda_decode_update` with the
    head's log decay g [S, H] (``-inf`` wipes what the slot held). Returns
    (o [S, H, dv] float32, state). ``impl``: "pallas" where
    :func:`gdn_decode_uses_kernel` says so, "xla" elsewhere (gather,
    :func:`kda_step`, scatter), "interpret" for the tests."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[-1]
    if impl is None:
        impl = "pallas" if gdn_decode_uses_kernel(H, dk, dv) else "xla"
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    if impl == "xla":
        o, Sn = kda_step(q, k, v, _over_keys(g, q), beta,
                         _from_pool(state[slots], H))
        return o, state.at[slots].set(_to_pool(Sn))
    state, o = gdn_decode_state_update(
        state, slots.astype(jnp.int32), q, k, v, jnp.exp(g), beta,
        interpret=impl == "interpret")
    return o, state


def gdn_prefill_uses_kernel(T: int, H: int, dk: int, dv: int,
                            backend: Optional[str] = None) -> bool:
    """Whether :func:`gdn_prefill` runs the Pallas chunk kernel: on the
    TPU, at ``T`` a whole number of 64-position chunks and widths of
    whole sublane tiles (a block's last two dimensions are a chunk's
    positions and a head's whole width, whatever it is; the heads are
    blocked by any number that divides them). The mixer dispatches on it
    and the engine counts by it."""
    backend = backend or jax.default_backend()
    return (backend == "tpu" and T >= _CHUNK and T % _CHUNK == 0
            and dk % 8 == 0 and dv % 8 == 0)


def _prefill_heads(H: int) -> int:
    """Heads a grid step of the chunk kernel: the most that divide the
    heads up to a sublane tile's eight (6 of 30)."""
    return max(n for n in range(1, _PREFILL_HEADS + 1) if H % n == 0)


def _gdn_consts():
    """What the heads of a grid step share: the chunk's triangles and the
    sub-blocks' own columns."""
    L, sub = _CHUNK, _SUB
    t = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    t0 = t // sub * sub
    return {"incl": (j <= t).astype(F32), "strict": (j < t).astype(F32),
            "after": (j > t).astype(F32), "eye": (j == t).astype(F32),
            "cross": (j < t0).astype(F32),
            "col": j - t0, "row": (t - t0)[:, :1]}


def _gdn_chunk_head(q, k, v, gb, S, c):
    """One chunk of one head with ONE decay a position: q, k [L, dk];
    v [L, dv]; gb [2, L] the log decay and the step size as ROWS; S
    [dk, dv]; ``c`` of :func:`_gdn_consts`. With a scalar decay the two
    [L, L] tables are plain matmuls under one mask of ``exp(G_t - G_i)``,
    where the channel form needs its sub-blocks' reference points. Every
    exponent is a sum of the ``g`` it spans (a matmul against a 0 / 1
    triangle: terms of one sign, no difference of running sums). The
    diagonal sub-blocks of ``I + Diag(beta) A`` are inverted by forward
    substitution, the rest block by block, as :func:`_chunk_head` does.
    Returns (o [L, dv], S)."""
    L, dv = v.shape
    sub = _SUB
    g_in = c["incl"] * gb[0:1]                # g_j where j <= t
    G = jnp.sum(g_in, axis=1, keepdims=True)               # [L, 1]
    Gaft = jnp.sum(c["after"] * gb[0:1], axis=1, keepdims=True)
    beta = jnp.sum(c["eye"] * gb[1:2], axis=1, keepdims=True)
    # sum of g over i < j <= t (0 above the diagonal, masked below)
    D = jnp.exp(_dot(g_in, c["strict"]))
    eG = jnp.exp(G)
    X = _dot(jnp.concatenate([k * beta, q]), k, _NT)       # [2L, L]
    N = X[:L] * D * c["strict"]               # Diag(beta) A
    QK = X[L:] * D * c["incl"]
    Ti = c["eye"]
    for i in range(sub):
        n_i = jnp.sum(jnp.where(c["col"] == i, N, 0.0), axis=1,
                      keepdims=True)
        Ti = Ti - jnp.where(c["row"] > i, n_i, 0.0) * _own(Ti, i)
    xs = _dot(jnp.concatenate([k * eG, q * eG]), S)        # [2L, dv]
    Y = _dot(Ti, beta * v - beta * xs[:L])
    Nb = _dot(Ti, N * c["cross"])
    U = [Y[:sub]]
    for n in range(sub, L, sub):
        done = jnp.concatenate(U + [jnp.zeros((L - n, dv), F32)])
        U.append(Y[n:n + sub] - _dot(Nb[n:n + sub], done))
    U = jnp.concatenate(U)
    o = xs[L:] + _dot(QK, U)
    S = S * eG[L - 1:] + _dot(k * jnp.exp(Gaft), U, _TN)
    return o, S


def _gdn_prefill_kernel(n_ref, q_ref, k_ref, v_ref, gb_ref, s0_ref, o_ref,
                        s_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    # a chunk wholly past the row's positions leaves the state alone
    live = pl.program_id(2) * _CHUNK < n_ref[pl.program_id(0)]

    @pl.when(live)
    def _():
        # the block's heads side by side, for :func:`_prefill_kernel`'s
        # reason; the operands are head-major, so a block is the batch
        c = _gdn_consts()
        o, S = jax.vmap(lambda *x: _gdn_chunk_head(*x, c))(
            q_ref[...], k_ref[...], v_ref[...], gb_ref[...], s_ref[...])
        o_ref[...] = o
        s_ref[...] = S

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_chunk_prefill(q, k, v, g, beta, S0, lengths, *, interpret=False):
    """The scalar-decay chunked form as one Pallas call, HEAD-MAJOR: q, k
    [B, H, T, dk], v [B, H, T, dv], g, beta [B, H, T] (float32), S0
    [B, H, dk, dv]; ``lengths`` as :func:`kda_chunk_prefill`'s. The grid
    is (row, block of heads, chunk), the chunks innermost and in order,
    the states in VMEM from a row's first chunk to its last. A block's
    last two dimensions are a chunk's 64 positions and a head's WHOLE
    width, so any width goes and any number of heads that divides them
    (:func:`_prefill_heads`). Returns (o [B, H, T, dv], S)."""
    B, H, T, dk = q.shape
    dv = v.shape[-1]
    hb, L = _prefill_heads(H), _CHUNK
    at = lambda b, c, n: jnp.minimum(                     # noqa: E731
        c, jnp.maximum((n[b] + L - 1) // L - 1, 0))
    seq = lambda d: pl.BlockSpec(                         # noqa: E731
        (None, hb, L, d), lambda b, j, c, n: (b, j, at(b, c, n), 0))
    st = pl.BlockSpec((None, hb, dk, dv), lambda b, j, c, n: (b, j, 0, 0))
    # the log decay and the step size of a chunk as two rows of 64
    gb = jnp.stack([g, beta], 2).reshape(B, H, 2, T // L, L)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, H // hb, T // L),
        in_specs=[seq(dk), seq(dk), seq(dv),
                  pl.BlockSpec((None, hb, None, 2, L),
                               lambda b, j, c, n: (b, j, at(b, c, n), 0, 0)),
                  st],
        out_specs=[pl.BlockSpec((None, hb, L, dv),
                                lambda b, j, c, n: (b, j, c, 0)), st])
    return pl.pallas_call(
        _gdn_prefill_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, T, dv), F32),
                   jax.ShapeDtypeStruct(S0.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, k, v, jnp.moveaxis(gb, 2, 3), S0)


def gdn_prefill(q, k, v, g, beta, St0, lengths=None, *,
                impl: Optional[str] = None):
    """A prefill chunk of every row on the pool's states St0
    [B, dk, H * dv] (:func:`gdn_state_shape`): q, k [B, T, H, dk],
    v [B, T, H, dv], g, beta [B, T, H]. The Pallas kernel where
    :func:`gdn_prefill_uses_kernel` says so, :func:`kda_chunked` on the
    broadcast decay elsewhere; ``lengths`` and ``impl`` as
    :func:`kda_prefill`'s. Returns (o [B, T, H, dv] float32, St)."""
    B, T, H, dk = q.shape
    if impl is None:
        impl = "pallas" if gdn_prefill_uses_kernel(
            T, H, dk, v.shape[-1]) else "xla"
    S0 = _from_pool(St0.astype(F32), H)
    if impl == "xla":
        o, S = kda_chunked(q, k, v, _over_keys(g, q), beta, S0)
        return o, _to_pool(S)
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    heads_first = lambda x: jnp.moveaxis(x.astype(F32), 2, 1)  # noqa: E731
    o, S = gdn_chunk_prefill(
        *(heads_first(x) for x in (q, k, v, g, beta)), S0,
        lengths.astype(jnp.int32), interpret=impl == "interpret")
    return jnp.moveaxis(o, 1, 2), _to_pool(S)
