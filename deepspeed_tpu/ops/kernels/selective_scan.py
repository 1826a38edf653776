"""Selective state-space layer (Mamba-1, the S6 scan): the recurrence in
three forms.

With ``E`` channels and state width ``N``, a state ``h [N, E]`` (float32,
zero at the start of a sequence) follows, a channel ``e`` and state ``n``,

    h_t[n, e] = exp(dt_t[e] A[n, e]) h_{t-1}[n, e] + dt_t[e] x_t[e] B_t[n]
    y_t[e]    = sum_n h_t[n, e] C_t[n] + D[e] x_t[e]

``A < 0`` a decay for EVERY (state, channel) pair (``A = -exp(A_log)``,
handed over transposed, ``[N, E]``), ``dt_t >= 0`` a step size a channel
and position, ``x_t [E]``, ``B_t``, ``C_t [N]`` shared by all channels.
Mamba-2 (``ssd.py``) has ONE decay a head, which is what lets a chunk be
matmuls over an ``[L, L]`` table of decays; here there are ``N E``
different ones and no such table: a chunk is walked position by position.
A position with ``dt_t = 0`` leaves the state as it was, which is how the
callers mask padding. Every exponent is ``dt A <= 0``, so every factor is
``<= 1``: nothing is formed as a quotient of two running products.

* :func:`mamba1_recurrent` — token by token (``lax.scan``): the
  definition, and what tier-1 holds the other forms to.
* :func:`mamba1_step` / :func:`mamba1_decode_update` — one token for
  decode. Memory-bound: the state is read once and written once.
  :func:`mamba1_decode_state_update` is the Pallas form that updates the
  serving state pool in place, the rows picked by slot through scalar
  prefetch; ``mamba1_step`` is its jnp twin (the CPU path).
* :func:`mamba1_prefill` — a prefill chunk. :func:`mamba1_chunk_scan` is
  its Pallas form: a grid over (row, block of positions), the positions
  innermost, the row's state ``[N, E]`` in VMEM scratch from the pool's
  state to its new one, the positions of a block walked in order a
  strip of channels at a time (a strip's state stays in registers), one
  read of ``x``, ``dt``, ``B``, ``C`` and one write of ``y`` a position.
  Bound by the vector unit: ~9 operations and one exponential a state
  element and position, which no MXU helps with. Elsewhere
  :func:`mamba1_recurrent`.

The serving state pool is one array a layer, ``[rows, N, E]``
(:func:`mamba1_state_shape`): the channels along the lanes, the state
width along the sublanes (16 x 5,120 is 2 x 40 whole tiles; with ``N`` on
the lanes, as ``ssd.py`` lays its 128-wide states, 16 would fill an
eighth of a tile). ``B_t`` and ``C_t`` reach the kernels as columns
``[.., N, 1]``: a ``[N]`` vector meets ``[N, channels]`` tiles along the
sublanes, a broadcast along the lanes. The pool's LAST row is the idle
row (``kv_cache.py``): rows the wrappers pad a batch with point there.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
#: channels a strip: the state of a strip, ``[16, 512]``, is 8 registers
_STRIP = 512
#: positions a grid step of the chunk kernel (x, dt and y blocks of
#: ``[64, E]`` float32, two buffers each: 7.9 MB at 5,120 channels)
_POSITIONS = 64
#: positions a trip of the chunk kernel's loop: one sublane tile of x / dt
_TRIP = 8
_VMEM_LIMIT = 48 * 1024 * 1024


def mamba1_state_shape(channels: int, state: int) -> Tuple[int, int]:
    """How the pool lays out a slot's state: ``[state, channels]``."""
    return (state, channels)


def mamba1_step(x, dt, A, B, C, D, h):
    """One token. x, dt [.., E]; A [N, E]; B, C [.., N]; D [E];
    h [.., N, E] (all float32). Returns (y [.., E] WITH the skip term
    ``D x``, h_new)."""
    h_new = jnp.exp(dt[..., None, :] * A) * h \
        + (dt * x)[..., None, :] * B[..., :, None]
    return jnp.sum(h_new * C[..., :, None], axis=-2) + D * x, h_new


def mamba1_recurrent(x, dt, A, B, C, D, h0):
    """The definition. x, dt [B, T, E]; A [N, E]; B, C [B, T, N]; D [E];
    h0 [B, N, E]. Returns (y [B, T, E] with the skip term, h_T)."""
    A, D = A.astype(F32), D.astype(F32)

    def one(h, xs):
        x_t, dt_t, B_t, C_t = xs
        y, h = mamba1_step(x_t, dt_t, A, B_t, C_t, D, h)
        return h, y
    xs = tuple(jnp.moveaxis(t.astype(F32), 1, 0) for t in (x, dt, B, C))
    h, y = jax.lax.scan(one, h0.astype(F32), xs)
    return jnp.moveaxis(y, 0, 1), h


def _strips(E: int):
    """Static lane windows of ``_STRIP`` channels over ``E``."""
    return [slice(c, c + _STRIP) for c in range(0, E, _STRIP)]


def kernel_shape(E: int, N: int) -> bool:
    """Whether the Pallas forms take ``E`` channels of ``N`` states:
    whole lane tiles of channels in whole strips, whole sublane tiles of
    states."""
    return E % _STRIP == 0 and N % 8 == 0


def mamba1_decode_uses_kernel(E: int, N: int,
                              backend: Optional[str] = None) -> bool:
    """Whether a decode step runs :func:`mamba1_decode_state_update`: on
    the TPU, at a :func:`kernel_shape`."""
    return (backend or jax.default_backend()) == "tpu" and kernel_shape(E, N)


def mamba1_prefill_uses_kernel(T: int, heads: int, N: int, E: int,
                               backend: Optional[str] = None) -> bool:
    """Whether a prefill chunk of ``T`` positions runs
    :func:`mamba1_chunk_scan` (the arguments as the engine's table of
    predicates by kind hands them over: positions, the state spec's
    ``heads`` (1), ``d_k`` = N, ``d_v`` = E): on the TPU, at a
    :func:`kernel_shape`, over whole blocks of positions."""
    del heads
    return (backend or jax.default_backend()) == "tpu" \
        and kernel_shape(E, N) and T % _POSITIONS == 0


# --------------------------------------------------------------------- #
# decode: the state pool updated in place
# --------------------------------------------------------------------- #


def _decode_kernel(slots_ref, wipe_ref, x_ref, dt_ref, b_ref, c_ref, a_ref,
                   d_ref, s_ref, so_ref, y_ref):
    del slots_ref                            # used by the index maps only
    i = pl.program_id(0)
    r = i % x_ref.shape[0]                   # this row of the vectors' block
    keep = wipe_ref[i] == 0
    Bc, Cc = b_ref[...], c_ref[...]          # [N, 1]
    for lanes in _strips(x_ref.shape[-1]):
        x = x_ref[pl.ds(r, 1), lanes]        # [1, w]
        dt = dt_ref[pl.ds(r, 1), lanes]
        h = jnp.where(keep, s_ref[:, lanes], 0.0)
        h = jnp.exp(dt * a_ref[:, lanes]) * h + (dt * x) * Bc
        so_ref[:, lanes] = h
        y_ref[pl.ds(r, 1), lanes] = jnp.sum(h * Cc, axis=0, keepdims=True) \
            + d_ref[:, lanes] * x


# jitted under its own name: the device trace names a Mosaic call after
# the function that encloses it, and the benchmark's readers find the
# decode state-update by this one
@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba1_decode_state_update(state, slots, wipe, x, dt, B, C, A, D, *,
                               interpret=False):
    """ONE Pallas call a Mamba-1 layer and decode step. state [rows, N, E]
    float32; ``slots``, ``wipe`` [S] int32 (a row with ``wipe`` non-zero
    starts from a zero state whatever its slot held); x, dt [S, E]; B, C
    [S, N, 1]; A [N, E]; D [1, E] (all float32). Decay, rank-one update,
    the ``C`` contraction and ``D x`` in one pass a row: each state is
    read once and written once, in place; a row with ``dt = 0`` writes
    back what it read. ``S`` is a multiple of 8 or one block of rows.
    Returns (state, y [S, E])."""
    S, E = x.shape
    N = A.shape[0]
    rb = 8 if S % 8 == 0 else S
    vec = pl.BlockSpec((rb, E), lambda i, *_: (i // rb, 0))
    col = pl.BlockSpec((None, N, 1), lambda i, *_: (i, 0, 0))
    whole = lambda n: pl.BlockSpec((n, E), lambda i, *_: (0, 0))  # noqa: E731
    st = pl.BlockSpec((None, N, E), lambda i, slots, wipe: (slots[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(S,),
        in_specs=[vec, vec, col, col, whole(N), whole(1), st],
        out_specs=[st, vec])
    return pl.pallas_call(
        _decode_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((S, E), F32)],
        # operand 8 (after the prefetched slots and wipe) is the pool
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(slots, wipe, x, dt, B, C, A, D, state)


def _impl(impl: Optional[str], uses_kernel: bool) -> str:
    if impl is None:
        return "pallas" if uses_kernel else "xla"
    return impl


def _padded_rows(S: int, idle: int, slots, wipe, *rows):
    """A batch of ``S`` rows padded to whole blocks of 8 with rows that
    change nothing (zeros: ``dt`` 0) and point at the pool's idle row."""
    pad = (-S) % 8 if S > 8 else 0
    if not pad:
        return (slots, wipe) + rows
    grow = lambda t, v=0: jnp.pad(                          # noqa: E731
        t, ((0, pad),) + ((0, 0),) * (t.ndim - 1), constant_values=v)
    return (grow(slots, idle), grow(wipe)) + tuple(grow(t) for t in rows)


def mamba1_decode_update(state, slots, x, dt, A, B, C, D, *, wipe=None,
                         impl: Optional[str] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One decode token for every row, on one layer's state pool in place.

    state [rows, N, E] float32; ``slots`` [S] int32 the pool row of each
    batch row (distinct for live rows; a row with ``dt = 0`` writes back
    what it read); x, dt [S, E]; A [N, E]; B, C [S, N]; D [E]; ``wipe``
    [S] bool: rows that start from a zero state whatever the slot held.
    Returns (y [S, E] float32 WITH the skip term ``D x``, state).
    ``impl``: "pallas" (the TPU default at a :func:`kernel_shape`),
    "interpret", "xla" (elsewhere: gather, :func:`mamba1_step`,
    scatter)."""
    S, E = x.shape
    impl = _impl(impl, mamba1_decode_uses_kernel(E, A.shape[0]))
    x, dt, A, B, C, D = (t.astype(F32) for t in (x, dt, A, B, C, D))
    if wipe is None:
        wipe = jnp.zeros((S,), bool)
    if impl == "xla":
        h0 = jnp.where(wipe[:, None, None], 0.0, state[slots])
        y, h = mamba1_step(x, dt, A, B, C, D, h0)
        return y, state.at[slots].set(h)
    slots, wipe, x, dt, B, C = _padded_rows(
        S, state.shape[0] - 1, slots.astype(jnp.int32),
        wipe.astype(jnp.int32), x, dt, B, C)
    state, y = mamba1_decode_state_update(
        state, slots, wipe, x, dt, B[..., None], C[..., None], A,
        D[None, :], interpret=impl == "interpret")
    return y[:S], state


# --------------------------------------------------------------------- #
# prefill: a chunk walked in order, the state in VMEM
# --------------------------------------------------------------------- #


def _chunk_kernel(slots_ref, wipe_ref, x_ref, dt_ref, b_ref, c_ref, a_ref,
                  d_ref, s_ref, so_ref, y_ref, h_ref):
    del slots_ref                            # used by the index maps only
    i, k = pl.program_id(0), pl.program_id(1)
    tb = x_ref.shape[0]

    @pl.when(k == 0)
    def _():
        h_ref[...] = jnp.where(wipe_ref[i] == 0, s_ref[...], 0.0)

    for lanes in _strips(x_ref.shape[-1]):
        A, D = a_ref[:, lanes], d_ref[:, lanes]

        def trip(j, h):           # (run inside this turn of the loop)
            t0 = pl.multiple_of(j * _TRIP, _TRIP)
            x8 = x_ref[pl.ds(t0, _TRIP), lanes]          # [8, w]
            dt8 = dt_ref[pl.ds(t0, _TRIP), lanes]
            dtx8 = dt8 * x8
            dx8 = D * x8
            for u in range(_TRIP):
                dt = dt8[u:u + 1]
                h = jnp.exp(dt * A) * h + dtx8[u:u + 1] * b_ref[t0 + u]
                y_ref[pl.ds(t0 + u, 1), lanes] = jnp.sum(
                    h * c_ref[t0 + u], axis=0, keepdims=True) \
                    + dx8[u:u + 1]
            return h

        h_ref[:, lanes] = jax.lax.fori_loop(0, tb // _TRIP, trip,
                                            h_ref[:, lanes])

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        so_ref[...] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba1_chunk_scan(state, slots, wipe, x, dt, B, C, A, D, *,
                      interpret=False):
    """ONE Pallas call a Mamba-1 layer and prefill step. state
    [rows, N, E] float32; ``slots``, ``wipe`` [S] int32; x, dt [S, T, E];
    B, C [S, T, N, 1]; A [N, E]; D [1, E] (all float32); ``T`` whole
    blocks of ``_POSITIONS``. A row's state goes from its slot of the
    pool (zero where ``wipe``) through the row's ``T`` positions in order
    and back to its slot, in place; a position with ``dt = 0`` leaves it
    as it was, so a row of padding alone writes back what it read.
    Returns (state, y [S, T, E] with the skip term)."""
    S, T, E = x.shape
    N = A.shape[0]
    tb = _POSITIONS
    vec = pl.BlockSpec((None, tb, E), lambda i, k, *_: (i, k, 0))
    col = pl.BlockSpec((None, tb, N, 1), lambda i, k, *_: (i, k, 0, 0))
    whole = lambda n: pl.BlockSpec(                         # noqa: E731
        (n, E), lambda i, k, *_: (0, 0))
    st = pl.BlockSpec((None, N, E),
                      lambda i, k, slots, wipe: (slots[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(S, T // tb),
        in_specs=[vec, vec, col, col, whole(N), whole(1), st],
        out_specs=[st, vec],
        scratch_shapes=[pltpu.VMEM((N, E), F32)])
    return pl.pallas_call(
        _chunk_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((S, T, E), F32)],
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(slots, wipe, x, dt, B, C, A, D, state)


def mamba1_prefill(state, slots, x, dt, A, B, C, D, *, wipe=None, live=None,
                   impl: Optional[str] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A prefill chunk for every row, on one layer's state pool in place.

    state [rows, N, E] float32; ``slots`` [S]; x, dt [S, T, E] (``dt`` 0
    at padded positions); A [N, E]; B, C [S, T, N]; D [E]; ``wipe`` [S]
    bool: rows that start from a zero state; ``live`` [S] bool: rows
    whose slot takes the new state (the others keep what they had).
    Returns (y [S, T, E] float32 with the skip term, state). ``impl`` as
    :func:`mamba1_decode_update`'s; "xla" is gather,
    :func:`mamba1_recurrent`, scatter."""
    S, T, E = x.shape
    impl = _impl(impl, mamba1_prefill_uses_kernel(T, 1, A.shape[0], E))
    x, dt, A, B, C, D = (t.astype(F32) for t in (x, dt, A, B, C, D))
    if wipe is None:
        wipe = jnp.zeros((S,), bool)
    if impl == "xla":
        h_in = state[slots]
        y, h = mamba1_recurrent(
            x, dt, A, B, C, D, jnp.where(wipe[:, None, None], 0.0, h_in))
        if live is not None:
            h = jnp.where(live[:, None, None], h, h_in)
        return y, state.at[slots].set(h)
    if live is not None:
        # a row that is not live changes nothing: no step, no wipe
        dt = jnp.where(live[:, None, None], dt, 0.0)
        wipe = wipe & live
    state, y = mamba1_chunk_scan(
        state, slots.astype(jnp.int32), wipe.astype(jnp.int32), x, dt,
        B[..., None], C[..., None], A, D[None, :],
        interpret=impl == "interpret")
    return y, state
