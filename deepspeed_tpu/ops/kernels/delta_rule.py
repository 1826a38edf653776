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

The serving state pool is one array a layer, ``[rows, H, dv, dk]``: XLA's
gather and scatter of 4 MB rows stalled the chip at offsets past 2^30
bytes of one array (PERF.md, PR 32), which a layer stays under up to 255
rows. Its layout is ``[.., dv, dk]`` (the transpose): a vector over
``dk`` (decay, key, query) then lies along the lanes of a state tile and
broadcasts over its sublanes for free; only ``v`` needs turning.
"""

from __future__ import annotations

import functools
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
