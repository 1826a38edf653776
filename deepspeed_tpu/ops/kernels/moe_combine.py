"""Rows of the expert-sorted order rejoin their tokens: ``moe_combine``.

``moe/sharded_moe.py::grouped_moe_ffn`` sorts the routed rows by expert
with a STABLE sort over the flat slot index ``t * k + j``, and top-k picks
distinct experts. So inside one expert's group the rows are in token
order and a token sends an expert at most one row: for a tile of ``Tt``
consecutive tokens and one expert the rows that belong to the tile are
ONE contiguous run of the sorted order, at most ``Tt`` long. Where each
row lies is a cumulative sum over the token axis of a one-hot compare
(:func:`rows_of`), so nothing here is indexed row by row: XLA's
scatter-add walks 32,768 rows one by one (2.93 ms on a v5e where the
bytes need 0.25).

A grid step owns a token tile and ``GROUP`` experts. It copies the
experts' runs from HBM in aligned chunks of ``CHUNK`` rows, one chunk of
every expert a round, so that a round's rows stand one behind the other
as the ``GROUP * CHUNK`` contraction rows of ONE matmul (a run's own
chunk is 16 deep: an eighth of the MXU), and adds ``P @ rows`` into a
float32 tile: ``P[t, (e, r)]`` is token ``t``'s weight for expert ``e``
where its row is row ``r`` of the chunk, and zero elsewhere. A chunk
starts on a tile of the sorted order, not on the run: the rows around a
run belong to other runs and match no token of this one (a token's row
index is compared, never its presence in the tile). The tile is rounded
once, after the last expert.

Rows past the last group are whatever ``ragged_dot`` left there (zeros
on the CPU, anything on the TPU): a chunk that reaches past ``total`` is
zeroed there before it multiplies, since ``0 * nan`` is no zero.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows a copy moves: one packed bfloat16 tile of the sorted order
CHUNK = 16
#: most experts a grid step walks side by side: GROUP * CHUNK
#: contraction rows
GROUP = 16
#: bytes of a grid step's float32 tile, and of one slot of its rounds'
#: rows (two slots): the tokens and the experts of a step follow them
_TILE_BYTES = 1 << 21
#: most scalars the run starts may take in SMEM
_SMEM_WORDS = 1 << 15


def token_tile(S: int, M: int) -> int:
    """Tokens a grid step owns: 256 up to 2,048-wide rows (``P`` is built
    a round: its cost grows with the square of the tile)."""
    return max(8, min(256, _TILE_BYTES // (4 * M) // 8 * 8,
                      -(-S // 8) * 8))


def group(n: int, M: int, dtype) -> int:
    """Experts a grid step walks side by side: 16 at 2,048-wide bfloat16
    rows, or all ``n`` if they are fewer."""
    return max(1, min(GROUP, n, _TILE_BYTES // (
        CHUNK * M * jnp.dtype(dtype).itemsize)))


def fits(S: int, n: int, M: int, rows: int, dtype) -> bool:
    """Whether the kernel takes a call, backend apart: a floating row of
    whole lanes, whole chunks of rows, and run starts that fit SMEM."""
    if not (jnp.issubdtype(jnp.dtype(dtype), jnp.floating)
            and M % 128 == 0 and rows % CHUNK == 0 and rows >= CHUNK):
        return False
    G = group(n, M, dtype)
    return -(-S // token_tile(S, M)) * -(-n // G) * G <= _SMEM_WORDS


def rows_of(chose: jnp.ndarray, sizes: jnp.ndarray) -> jnp.ndarray:
    """[S, n]: the row of the sorted order that token ``t`` sends expert
    ``e`` if it chose it (``chose`` [S, n]): the expert's group start
    (``sizes`` [n]: the groups' rows) plus the tokens before ``t`` that
    chose it. A cumulative sum over a compare, no gather."""
    sel = chose.astype(jnp.int32)
    return (jnp.cumsum(sizes) - sizes)[None, :] \
        + jnp.cumsum(sel, axis=0) - sel


def _kernel(base_ref, rounds_ref, total_ref, w_ref, q_ref, ys_ref, out_ref,
            buf, sem, acc, *, nrows: int):
    i, g = pl.program_id(0), pl.program_id(1)
    ng = pl.num_programs(1)
    G = w_ref.shape[-1]
    K = G * CHUNK
    step = i * ng + g
    nr = rounds_ref[step]
    total = total_ref[0]

    @pl.when(g == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    def chunk_start(c, e):
        # a round past an expert's run copies the array's last chunk at
        # most: no token's row is there, so nothing of it is added
        return pl.multiple_of(
            jnp.minimum(base_ref[step * G + e] + c * CHUNK, nrows - CHUNK),
            CHUNK)

    def chunk(slot, e):
        return buf.at[slot, pl.ds(pl.multiple_of(e * CHUNK, CHUNK), CHUNK)]

    def copy(c, slot, e):
        return pltpu.make_async_copy(
            ys_ref.at[pl.ds(chunk_start(c, e), CHUNK)], chunk(slot, e),
            sem.at[slot, e])

    def every_expert(do):
        # a loop, not G copies of its body: a call is traced, lowered and
        # compiled eight times a sparse layer
        jax.lax.fori_loop(0, G, lambda e, carry: do(e) or carry, 0)

    # column e * CHUNK + r of a round stands for row r of expert e's chunk
    col = jax.lax.broadcasted_iota(jnp.int32, (G, K), 1)
    spread = (col // CHUNK == jax.lax.broadcasted_iota(
        jnp.int32, (G, K), 0)).astype(jnp.float32)
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    # float32 rows are multiplied whole; a bfloat16 product is exact as is
    rows_dot = dot if buf.dtype == jnp.float32 else functools.partial(
        jnp.dot, preferred_element_type=jnp.float32)
    wx = dot(w_ref[...].astype(jnp.float32), spread)        # [Tt, K]
    # a token's row, counted from its expert's first chunk, less r: the
    # round in which column (e, r) holds it, times CHUNK
    at = dot(q_ref[...].astype(jnp.float32), spread) - (
        jax.lax.broadcasted_iota(jnp.int32, (1, K), 1) % CHUNK
    ).astype(jnp.float32)

    @pl.when(nr > 0)
    def _():
        every_expert(lambda e: copy(0, 0, e).start())

    def one_round(c, carry):
        slot = c % 2

        @pl.when(c + 1 < nr)
        def _():
            every_expert(lambda e: copy(c + 1, 1 - slot, e).start())

        def arrived(e):
            copy(c, slot, e).wait()
            start = chunk_start(c, e)

            @pl.when(start + CHUNK > total)
            def _():
                rows = chunk(slot, e)
                rid = start + jax.lax.broadcasted_iota(
                    jnp.int32, (CHUNK, 1), 0)
                rows[...] = jnp.where(rid < total, rows[...],
                                      jnp.zeros(rows.shape, rows.dtype))

        every_expert(arrived)
        p = jnp.where(at == (c * CHUNK).astype(jnp.float32), wx, 0.0)
        acc[...] += rows_dot(p.astype(buf.dtype), buf[slot])
        return carry

    jax.lax.fori_loop(0, nr, one_round, 0)

    @pl.when(g == ng - 1)
    def _():
        out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def moe_combine(ys: jnp.ndarray, weight: jnp.ndarray, row: jnp.ndarray,
                sizes: jnp.ndarray, out_dtype, *,
                interpret: bool = False) -> jnp.ndarray:
    """``out[t] = sum over e of weight[t, e] * ys[row[t, e]]`` in float32,
    rounded once to ``out_dtype``: [S, M]. One named program, traced and
    lowered once for the calls of a step that share its shapes (a sparse
    layer's step makes eight).

    ``ys`` [rows, M]: rows sorted by expert, a group's rows in token
    order, the ``n`` experts' groups first. ``weight`` [S, n] float32, zero
    where a token did not choose the expert; ``row`` [S, n] as
    :func:`rows_of` gives it from ``sizes`` [n], the groups' rows: every
    row with a weight lies under ``rows``. Rows past ``sizes.sum()`` are
    read as zeros."""
    nrows, M = ys.shape
    S, n = weight.shape
    Tt = token_tile(S, M)
    tiles = -(-S // Tt)
    G = group(n, M, ys.dtype)
    ng = -(-n // G)
    pad_s, pad_n = tiles * Tt - S, ng * G - n
    total = jnp.sum(sizes).astype(jnp.int32)
    # a padded token or expert has no weight, so no row
    weight = jnp.pad(weight.astype(jnp.float32), ((0, pad_s), (0, pad_n)))
    by_tile = jnp.pad(row, ((0, pad_s), (0, pad_n))).reshape(tiles, Tt, -1)
    sel = (weight != 0).reshape(tiles, Tt, -1)
    # the run of a (tile, expert): from the first to the last row that a
    # token of the tile sends the expert with a weight; its chunks start
    # at ``base``, and a grid step makes as many rounds as its longest run
    lo = jnp.min(jnp.where(sel, by_tile, nrows), axis=1)     # [tiles, n]
    hi = jnp.max(jnp.where(sel, by_tile, -1), axis=1)
    base = jnp.minimum(lo // CHUNK * CHUNK, nrows - CHUNK)
    chunks = jnp.where(hi >= 0, hi // CHUNK - base // CHUNK + 1, 0)
    rounds = jnp.max(chunks.reshape(tiles, ng, G), axis=-1)
    q = by_tile - base[:, None, :]

    def grouped(x):             # [tiles * Tt, ng * G] -> [ng, tiles * Tt, G]
        return x.reshape(tiles * Tt, ng, G).transpose(1, 0, 2)

    plane = pl.BlockSpec((None, Tt, G), lambda i, g, *_: (g, i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, nrows=nrows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles, ng),
            in_specs=[plane, plane, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((Tt, M), lambda i, g, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, G * CHUNK, M), ys.dtype),
                            pltpu.SemaphoreType.DMA((2, G)),
                            pltpu.VMEM((Tt, M), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tiles * Tt, M), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_combine",
    )(base.reshape(-1).astype(jnp.int32), rounds.reshape(-1).astype(jnp.int32),
      total.reshape(1), grouped(weight), grouped(q.astype(jnp.int32)), ys)
    return out[:S]
