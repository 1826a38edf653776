"""Selective state-space layer (Mamba-2, the SSD form): the recurrence in
three forms.

Per head, with head width ``P`` and state width ``N``, a state ``S [P, N]``
(float32, zero at the start of a sequence) follows

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t                (the caller adds D x_t)

``a < 0`` a scalar a head, ``dt_t >= 0`` a scalar a head and position,
``x_t [P]``, ``B_t``, ``C_t [N]`` (shared by the heads of a group; the
callers hand them over a HEAD, the groups already repeated). A position
with ``dt_t = 0`` leaves the state as it was, which is how the callers
mask padding.

* :func:`mamba2_recurrent` — token by token (``lax.scan``): the
  definition, and what tier-1 holds the other two to.
* :func:`mamba2_step` / :func:`mamba2_decode_update` — one token for
  decode. Memory-bound: the state is read once and written once. The second
  is the Pallas form that updates the serving state pool in place, the rows
  picked by slot through scalar prefetch (the twin of
  ``delta_rule.kda_decode_state_update``); ``mamba2_step`` is its jnp twin
  (the CPU path).
* :func:`mamba2_prefill` — chunks of ``chunk`` positions (the config's
  ``chunk_size``, 128): inside a chunk ``Y = ((C B^T) o L) (dt X)`` with
  ``L_ts = exp(sum of dt a over (s, t])`` for ``s <= t``; what the state
  at the chunk's start adds, ``exp(cum_t) C_t S_0``; and the chunk's own
  state, carried to the next. ``jax.numpy`` on every backend (a Pallas
  chunk kernel is a later step, as it was for the delta rule). Every
  exponent is a sum of ``dt a <= 0`` over a span, so every factor is
  ``<= 1``: nothing is formed as a quotient of two running products.

The serving state pool is one array a layer, ``[rows, H, P, N]``, the state
width along the lanes (``delta_rule.py`` says why one array a layer).
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


def mamba2_step(x, dt, a, B, C, S):
    """One token. x [.., H, P]; dt [.., H]; a [H]; B, C [.., H, N];
    S [.., H, P, N] (all float32). Returns (y [.., H, P], S_new)."""
    S_new = jnp.exp(dt * a)[..., None, None] * S \
        + (dt[..., None] * x)[..., None] * B[..., None, :]
    return jnp.sum(S_new * C[..., None, :], axis=-1), S_new


def mamba2_recurrent(x, dt, a, B, C, S0):
    """The definition. x [B, T, H, P]; dt [B, T, H]; a [H]; B, C
    [B, T, H, N]; S0 [B, H, P, N]. Returns (y [B, T, H, P], S_T)."""
    a = a.astype(F32)

    def one(S, xs):
        x_t, dt_t, B_t, C_t = xs
        y, S = mamba2_step(x_t, dt_t, a, B_t, C_t, S)
        return S, y
    xs = tuple(jnp.moveaxis(t.astype(F32), 1, 0) for t in (x, dt, B, C))
    S, y = jax.lax.scan(one, S0.astype(F32), xs)
    return jnp.moveaxis(y, 0, 1), S


def _chunk(a, S, xs):
    """One chunk of :func:`mamba2_prefill`: xs = (x [B, L, H, P],
    dt [B, L, H], B, C [B, L, H, N]); S [B, H, P, N]."""
    x, dt, Bm, Cm = xs
    L = x.shape[1]
    cum = jnp.cumsum(dt * a, axis=1)                     # [B, L, H], <= 0
    t = jnp.arange(L)
    span = cum[:, :, None, :] - cum[:, None, :, :]       # [B, t, s, H]
    causal = (t[:, None] >= t[None, :])[None, :, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, span, 0.0)), 0.0)
    cb = jnp.einsum("bthn,bshn->btsh", Cm, Bm, precision=_HI)
    xdt = x * dt[..., None]
    y = jnp.einsum("btsh,bshp->bthp", cb * decay, xdt, precision=_HI) \
        + jnp.exp(cum)[..., None] * jnp.einsum(
            "bthn,bhpn->bthp", Cm, S, precision=_HI)
    after = jnp.exp(cum[:, -1:, :] - cum)                # [B, L, H], <= 1
    S = jnp.exp(cum[:, -1, :])[..., None, None] * S + jnp.einsum(
        "bshp,bshn->bhpn", xdt * after[..., None], Bm, precision=_HI)
    return S, y


@functools.partial(jax.jit, static_argnames=("chunk",))
def mamba2_prefill(x, dt, a, B, C, S0, *, chunk: int = 128):
    """The chunked (SSD) form: same arguments and results as
    :func:`mamba2_recurrent`, as one named program of a prefill step
    (traced and lowered once for all the state-space layers of a step).
    ``T`` is padded up to whole chunks with positions that change
    nothing (``dt`` 0); so must the caller's own padding be."""
    Bsz, T = x.shape[:2]
    L = min(chunk, T)
    pad = (-T) % L
    xs = []
    for t in (x, dt, B, C):
        t = t.astype(F32)
        if pad:
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape(Bsz, (T + pad) // L, L, *t.shape[2:])
        xs.append(jnp.moveaxis(t, 1, 0))                 # chunks leading
    S, y = jax.lax.scan(functools.partial(_chunk, a.astype(F32)),
                        S0.astype(F32), tuple(xs))
    y = jnp.moveaxis(y, 0, 1).reshape(Bsz, T + pad, *y.shape[3:])
    return y[:, :T], S


# --------------------------------------------------------------------- #
# decode: the state pool updated in place
# --------------------------------------------------------------------- #

#: heads a grid step: at the published 64 heads of [64, 128] a sequence's
#: whole state of a layer, 2 MB in and as much out. A step's fixed cost is
#: a third of a 256 KB block's transfer: 8 heads a step read 58 % of the
#: bytes' bound in the cell, 64 three quarters (PERF.md section 6, PR 44)
_HEADS = 64


def _decode_kernel(slots_ref, x_ref, dx_ref, bdt_ref, c_ref, a_ref, s_ref,
                   so_ref, y_ref):
    del slots_ref                            # used by the index maps only
    St = s_ref[...]                          # [hb, P, N]
    hb, P, N = St.shape
    # x as a column over the tile's sublanes: rows of x (padded to whole
    # lane groups by the caller), turned
    x_col = jnp.swapaxes(
        jnp.broadcast_to(x_ref[...][:, None, :],
                         (hb, N, x_ref.shape[-1])), 1, 2)[:, :P, :]
    Sn = St * a_ref[...][:, None, :] + x_col * bdt_ref[...][:, None, :]
    so_ref[...] = Sn
    y_ref[...] = jnp.sum(Sn * c_ref[...][:, None, :], axis=-1) + dx_ref[...]


# jitted under its own name: the device trace names a Mosaic call after
# the function that encloses it, and the benchmark's readers find the
# decode state-update by this one
@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba2_decode_state_update(state, slots, x, dx, bdt, c, a, *,
                               interpret=False):
    """ONE Pallas call a state-space layer and decode step. state
    [rows, H, P, N] float32; ``slots`` [S] int32; x [S, H, Pp] the inputs
    padded with zeros to whole 128-lane groups; dx [S, H, P] the skip term
    ``D x``; bdt, c, a [S, H, N]: ``dt B``, ``C`` and the decay
    ``exp(dt a)`` over the state's lanes. Decay, rank-one update, the
    ``C`` contraction and ``D x`` in one pass: each state is read once and
    written once, in place. Returns (state, y [S, H, P])."""
    S, H, P = dx.shape
    N = c.shape[-1]
    hb = _HEADS if H % _HEADS == 0 else H
    vec = lambda d: pl.BlockSpec(                        # noqa: E731
        (None, hb, d), lambda i, j, *_: (i, j, 0))
    st = pl.BlockSpec((None, hb, P, N),
                      lambda i, j, slots: (slots[i], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(S, H // hb),
        in_specs=[vec(x.shape[-1]), vec(P), vec(N), vec(N), vec(N), st],
        out_specs=[st, vec(P)])
    return pl.pallas_call(
        _decode_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((S, H, P), F32)],
        # operand 6 (after the prefetched slots) is the pool
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(slots, x, dx, bdt, c, a, state)


def mamba2_decode_update(state, slots, x, dt, a, B, C, D, *, wipe=None,
                         impl: Optional[str] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One decode token for every row, on one layer's state pool in place.

    state [rows, H, P, N] float32; ``slots`` [S] int32 the pool row of
    each batch row (distinct for live rows; a row with ``dt = 0`` writes
    back what it read); x [S, H, P]; dt [S, H]; a, D [H]; B, C [S, H, N];
    ``wipe`` [S] bool: rows that start from a zero state whatever the
    slot held (decay 0). Returns (y [S, H, P] float32 WITH the skip term
    ``D x``, state). ``impl``: "pallas" (the TPU default), "interpret",
    "xla" (elsewhere: gather, :func:`mamba2_step`, scatter)."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    x, dt, a, B, C = (t.astype(F32) for t in (x, dt, a, B, C))
    dx = D.astype(F32)[:, None] * x
    if impl == "xla":
        S0 = state[slots]
        if wipe is not None:
            S0 = jnp.where(wipe[:, None, None, None], 0.0, S0)
        y, Sn = mamba2_step(x, dt, a, B, C, S0)
        return y + dx, state.at[slots].set(Sn)
    decay = jnp.exp(dt * a)
    if wipe is not None:
        decay = jnp.where(wipe[:, None], 0.0, decay)
    N = B.shape[-1]
    state, y = mamba2_decode_state_update(
        state, slots.astype(jnp.int32),
        jnp.pad(x, ((0, 0), (0, 0), (0, (-x.shape[-1]) % 128))), dx,
        dt[..., None] * B, C,
        jnp.broadcast_to(decay[..., None], decay.shape + (N,)),
        interpret=impl == "interpret")
    return y, state
