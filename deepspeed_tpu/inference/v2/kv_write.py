"""The one writer of fresh rows into the paged pool.

Every path that stores rows (a step's K/V or latent rows, the fused
loop's ring at flush, the ``seq``-sharded exchange) calls
:func:`store_rows`, over the paged pool or, for a sliding-window layer,
over the window pool (the same form, the same writer, another table). A sequence's rows are consecutive positions, so
inside a block they are consecutive pool slots: with the static tile
``t = gcd(n, block_size)`` (:func:`tile_rows`: halved for very wide
rows) the positions are cut at multiples of ``t``
and every piece lies in ONE block, one contiguous window of ``t`` rows.
The pool is addressed as whole windows, ``[L * P * slots / t, t, W]``
(``t`` divides ``block_size``, so also the slots), under ONE flat index,
like a single row's store: XLA updates the donated pool in place and a
window moves as one copy of ``t x W`` lanes where the row scatter looped
over every position. (A window on the slots axis itself made XLA re-lay
the pool, two whole-pool copies; an index of three parts over a 4.77 GB
pool halted the chip in the read-back: my chip runs, PR 36.)

Exactness. A first position that is no multiple of ``t`` (the ring's
flush, a decode row riding a prefill step, a budget-cut chunk) shifts
the sequence's rows by ``start % t`` inside its windows, which then
number ``n // t + 1``; every window is read, the real rows laid over
what it holds, and written back, so a row outside ``[start, start +
count)`` keeps its bytes: the pool after this writer equals the pool
after the row scatter on every slot but the trash block's. Windows with
no real row (idle sequences, the spare window of an aligned start, a
foreign block under ``seq``) go to the trash block, which holds
``block_size >= t`` rows. ``n == 1`` gives ``t == 1``: one window a
row, nothing to shift or to read back, the row scatter itself.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from .kv_quant import pool_parts, quantize_rows, repack

_IN_BOUNDS = jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS
#: ``x[p, s, r : r + t, :]`` for every index row ``(p, s, r)`` [P, S, K, 3]
_WINDOWS = jax.lax.GatherDimensionNumbers(
    offset_dims=(3, 4), collapsed_slice_dims=(0, 1),
    start_index_map=(0, 1, 2))


#: the most elements of one window (512 KiB of bfloat16): the TPU
#: compiler's gather takes a larger one in lane pieces of a RE-LAID pool,
#: a whole-pool copy a piece (5.6 GB at a 7.56 GB pool of 3,840-lane rows
#: under 256-row windows, which no longer compiled; 128 rows of OLMoE's
#: 2,048 lanes, the widest until then, sit on the limit; PERF.md, PR 65)
_WINDOW_ELEMENTS = 1 << 18


def tile_rows(n: int, block_size: int, row: int = 0) -> int:
    """Rows a window carries for steps of ``n`` positions a sequence, in
    a pool whose rows are ``row`` lanes: their greatest common divisor
    with the block, halved while a window passes
    :data:`_WINDOW_ELEMENTS`."""
    t = math.gcd(n, block_size)
    while t % 2 == 0 and t * row > _WINDOW_ELEMENTS:
        t //= 2
    return t


def runs_issued(start: int, count: int, n: int, block_size: int,
                row: int = 0) -> int:
    """Windows :func:`store_rows` writes for one sequence's ``count`` real
    rows from position ``start`` (trash windows not counted): the
    engine's ``kv_write_runs``, the writer's own arithmetic."""
    if count <= 0:
        return 0
    t = tile_rows(n, block_size, row)
    return (start + count - 1) // t - start // t + 1


class WritePlan(NamedTuple):
    """Where one step's rows go, the same for every layer: made once a
    program by :func:`write_plan`, so a layer's store traces a gather, a
    select and a scatter and none of the index arithmetic."""
    index: Any        # [P, S, K] window of layer 0's planes, tiled (below)
    src_index: Any    # [P, S, K, 3] (plane, s, first row + t); None: t == 1
    real: Any         # [S, K, t] the window's row is one of the step's


def write_plan(start, count, tables, n, block_size, pool_shape, shard=None
               ) -> WritePlan:
    """The windows of sequences whose next ``n`` positions start at
    ``start`` [S], the first ``count`` [S] of them real (0: an idle row),
    through ``tables`` [S, MAXB], in a pool of ``pool_shape`` [L, P, slots,
    W]. ``shard`` = (size, rank) under the ``seq`` mesh: block ``b`` lives
    on chip ``b % size`` as its block ``b // size``; a window of a foreign
    block goes to the local trash block."""
    P, slots = pool_shape[1], pool_shape[2]
    S, bs, i32 = start.shape[0], block_size, jnp.int32
    t = tile_rows(n, bs, pool_shape[3])
    K = n // t + (t > 1)
    # window k of sequence s holds positions [wpos, wpos + t), wpos a
    # multiple of t; its row i is the step's row first + i
    first = jnp.arange(K, dtype=i32)[None, :] * t - (start % t)[:, None]
    wpos = start[:, None] + first                            # [S, K]
    src_row = first[:, :, None] + jnp.arange(t, dtype=i32)   # [S, K, t]
    real = (src_row >= 0) & (src_row < count[:, None, None])
    blk = jnp.take_along_axis(
        tables, jnp.minimum(wpos // bs, tables.shape[1] - 1), axis=1)
    live = real.any(axis=-1)
    if shard is not None:
        live &= (blk % shard[0]) == shard[1]
        blk = blk // shard[0]
    win = jnp.where(live, blk * bs + wpos % bs, slots - bs) // t
    plane = jnp.arange(P, dtype=i32)[:, None, None]
    return WritePlan(
        plane * (slots // t) + win[None],
        jnp.stack(jnp.broadcast_arrays(
            plane, jnp.arange(S, dtype=i32)[None, :, None],
            (first + t)[None]), axis=-1) if t > 1 else None,
        real)


def _take(x, index):
    """``x[i]`` for every ``i`` of ``index`` [..., 1]: whole windows
    (``x.shape[1:]``) under one flat leading index."""
    batch = index.ndim - 1
    return jax.lax.gather(
        x, index, jax.lax.GatherDimensionNumbers(
            offset_dims=tuple(range(batch, batch + x.ndim - 1)),
            collapsed_slice_dims=(0,), start_index_map=(0,)),
        (1,) + x.shape[1:], mode=_IN_BOUNDS)


def _put(x, index, windows):
    """``x`` with ``x[i] = windows[...]`` for every ``i`` of ``index``."""
    batch = index.ndim - 1
    return jax.lax.scatter(
        x, index, windows, jax.lax.ScatterDimensionNumbers(
            update_window_dims=tuple(range(batch, batch + x.ndim - 1)),
            inserted_window_dims=(0,), scatter_dims_to_operand_dims=(0,)),
        mode=_IN_BOUNDS)


def store_rows(kv, li, rows, plan: WritePlan, kv_heads=1,
               window: bool = False):
    """``kv`` with ``rows`` [P, S, n, W] stored in layer ``li`` at
    ``plan``'s windows. Over an int8 pool the rows are quantized a (row,
    kv head) (``quantize_rows``) and their scales stored beside them.
    ``window``: into the window pool of a model with sliding-window
    layers, at a plan made over that pool's shape and the slots' tables
    (a run may wrap to the slot's first block: a window of ``t`` rows
    lies in one logical block, so it never straddles the wrap)."""
    data, scales = pool_parts(kv, window)
    P, S, n, W = rows.shape
    K, t = plan.real.shape[1:]
    windows = data.shape[2] // t
    index = (plan.index + li * (P * windows))[..., None]     # [P, S, K, 1]
    if t > 1:
        # the step's rows cut at the windows' edges: a shift by start % t
        src = jax.lax.gather(
            jax.lax.pad(rows, jnp.zeros((), rows.dtype),
                        ((0, 0, 0), (0, 0, 0), (t, t, 0), (0, 0, 0))),
            plan.src_index, _WINDOWS, (1, 1, t, W), mode=_IN_BOUNDS)
    else:
        src = rows[:, :, :, None]                            # [P, S, K, t, W]
    if scales is not None:
        q, sc = quantize_rows(src.reshape(-1, W), kv_heads)
        src = q.reshape(src.shape)
        # [KV, N] -> a window's t scales a kv head, as the scales plane
        # [L, P, KV, slots] lies: rows of t under one flat index too
        sc = jnp.moveaxis(sc.reshape(kv_heads, P, S, K, t), 0, 3)
        sc_index = (index // windows * (kv_heads * windows) + index % windows
                    + jnp.arange(kv_heads, dtype=jnp.int32) * windows
                    )[..., None]                             # [P, S, K, KV, 1]
        tiled = scales.reshape(-1, t)
        if t > 1:
            sc = jnp.where(plan.real[None, :, :, None], sc,
                           _take(tiled, sc_index))
        scales = _put(tiled, sc_index, sc).reshape(scales.shape)
    src = src.astype(data.dtype)
    tiled = data.reshape(-1, t, W)
    if t > 1:
        # rows that are not the step's keep what the window holds
        src = jnp.where(plan.real[None, ..., None], src, _take(tiled, index))
    return repack(kv, _put(tiled, index, src).reshape(data.shape), scales,
                  window)
