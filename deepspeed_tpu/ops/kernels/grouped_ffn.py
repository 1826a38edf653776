"""The routed experts' feed-forward of a decode step as one grouped kernel.

At a few rows an expert the work is a weight stream: every expert that
is hit has its matrices read once, and the multiplies hide under the
read. ``jax.lax.ragged_dot`` (three calls a layer, XLA's own grouped
matmul) walks the ROWS; this kernel walks the GROUPS:

* rows arrive sorted by expert with every group laid out at a multiple
  of the row tile (:func:`group_layout`), so a *visit* (one grid step)
  owns one row tile of one expert and its output rows;
* an expert's matrices come by manual DMA in contiguous row chunks, two
  scratch slots a matrix: the next chunk (and the next visit's first) is
  in flight while this one multiplies;
* gate, up and down in one pass: ``g`` and ``u`` accumulate in float32
  over the hidden chunks, ``h = act(g) * u`` is rounded once to the
  operand type, and the down projection accumulates in float32 over the
  width chunks. Nothing of [rows, width] reaches HBM;
* a group with no row has no visit and costs no read; a group with more
  rows than one tile takes more visits (its matrices streamed again);
  rows in no held group are never laid out, so never read.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...telemetry.trace import region

#: rows a visit at decode shapes: one packed bfloat16 tile (two float32)
ROW_TILE = 16
#: the row tiles a visit may take; past the last the work is no weight
#: stream any more (the chip's ridge is ~240 rows an expert)
_ROW_TILES = (16, 32, 64, 128)
#: bytes of one weight chunk in flight (a slot); two slots a matrix
_CHUNK_BYTES = 1 << 20


def visits_bound(rows: int, groups: int, tile: int = ROW_TILE) -> int:
    """Most visits ``rows`` routed rows over ``groups`` groups can take:
    sum of ceil(size / tile) <= (rows + groups x (tile - 1)) / tile."""
    return max(1, (rows + groups * (tile - 1)) // tile)


def group_layout(eid, groups: int, tile: int = ROW_TILE):
    """Where each routed row goes when every group starts at a multiple of
    ``tile``. ``eid`` [R] int32: the (local) group of each routed row,
    ``groups`` for a row in no held group. Returns (dest [R] int32: the
    row's place in the padded layout, ``visits x tile`` (out of range) for
    a row in no group; gid [visits] int32: the group each visit serves,
    the last real visit's group repeated behind it; nvis [1] int32; sizes
    [groups] int32)."""
    R = eid.shape[0]
    V = visits_bound(R, groups, tile)
    sizes = jnp.bincount(eid, length=groups + 1).astype(jnp.int32)[:groups]
    tiles = (sizes + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)
    nvis = tile_end[-1]
    first_row = jnp.cumsum(sizes) - sizes           # in the sorted order
    order = jnp.argsort(eid, stable=True)
    e_sorted = jnp.take(eid, order)
    held = e_sorted < groups
    e_safe = jnp.minimum(e_sorted, groups - 1)
    rank = jnp.arange(R, dtype=jnp.int32) - jnp.take(first_row, e_safe)
    dest_sorted = jnp.where(
        held, (jnp.take(tile_end - tiles, e_safe)) * tile + rank, V * tile)
    dest = jnp.zeros((R,), jnp.int32).at[order].set(dest_sorted)
    # visit v serves the first group whose tiles end beyond v
    v = jnp.minimum(jnp.arange(V, dtype=jnp.int32), jnp.maximum(nvis - 1, 0))
    gid = jnp.minimum(jnp.searchsorted(tile_end, v, side="right"),
                      groups - 1).astype(jnp.int32)
    return dest, gid, nvis.reshape(1).astype(jnp.int32), sizes


def _chunk_rows(rows: int, cols: int, itemsize: int) -> int:
    """Rows of a [rows, cols] matrix a chunk holds: the largest divisor of
    ``rows`` that is a multiple of 128 (a lane-aligned slice of the
    operand beside it) within ``_CHUNK_BYTES``; all of it if none."""
    best = None
    for c in range(128, rows + 1, 128):
        if rows % c == 0 and c * cols * itemsize <= _CHUNK_BYTES:
            best = c
    if best is None:
        best = 128 if rows % 128 == 0 else rows
    return best


def _kernel(gid_ref, nvis_ref, x_ref, *rest, K1, K2, tm, tf, gated,
            activation):
    if gated:
        wg_hbm, wu_hbm, wo_hbm, o_ref, gbuf, ubuf, obuf, sems = rest
    else:
        wg_hbm, wo_hbm, o_ref, gbuf, obuf, sems = rest
        wu_hbm = ubuf = None
    v = pl.program_id(0)
    nvis = nvis_ref[0]

    def up_copies(e, c, slot):
        cps = [pltpu.make_async_copy(
            wg_hbm.at[e, pl.ds(c * tm, tm)], gbuf.at[slot], sems.at[0, slot])]
        if gated:
            cps.append(pltpu.make_async_copy(
                wu_hbm.at[e, pl.ds(c * tm, tm)], ubuf.at[slot],
                sems.at[1, slot]))
        return cps

    def down_copy(e, c, slot):
        return [pltpu.make_async_copy(
            wo_hbm.at[e, pl.ds(c * tf, tf)], obuf.at[slot], sems.at[2, slot])]

    def start(cps):
        for cp in cps:
            cp.start()

    def wait(cps):
        for cp in cps:
            cp.wait()

    @pl.when(v < nvis)
    def _visit():
        e = gid_ref[v]

        @pl.when(v == 0)
        def _first():
            start(up_copies(e, 0, 0))

        g = u = None
        for c in range(K1):
            # one chunk ahead, always: the stream never waits for us
            if c + 1 < K1:
                start(up_copies(e, c + 1, (c + 1) % 2))
            else:
                start(down_copy(e, 0, 0))
            wait(up_copies(e, c, c % 2))
            xc = x_ref[:, c * tm:(c + 1) * tm]
            gp = jnp.dot(xc, gbuf[c % 2], preferred_element_type=jnp.float32)
            g = gp if g is None else g + gp
            if gated:
                up = jnp.dot(xc, ubuf[c % 2],
                             preferred_element_type=jnp.float32)
                u = up if u is None else u + up
        h = activation(g) * u if gated else activation(g)
        h = h.astype(x_ref.dtype)
        acc = None
        for c in range(K2):
            if c + 1 < K2:
                start(down_copy(e, c + 1, (c + 1) % 2))
            else:
                @pl.when(v + 1 < nvis)
                def _next():
                    start(up_copies(gid_ref[v + 1], 0, 0))
            wait(down_copy(e, c, c % 2))
            part = jnp.dot(h[:, c * tf:(c + 1) * tf], obuf[c % 2],
                           preferred_element_type=jnp.float32)
            acc = part if acc is None else acc + part
        o_ref[...] = acc.astype(o_ref.dtype)


# jitted under its own name: the device trace names a Mosaic call after
# the function that encloses it, and a program whose layers share shapes
# traces this body once
@functools.partial(jax.jit, static_argnames=("activation", "interpret"))
def grouped_ffn_decode(xs, gid, nvis, weights, *, activation,
                       interpret=False):
    """xs [V * T, M] rows in :func:`group_layout`'s order at row tile T;
    gid [V], nvis [1]; weights (wi, wo) or (wi_gate, wi_up, wo) stacked
    [G, ...] in xs's dtype. Returns ys [V * T, M]: every laid-out row
    through its group's feed-forward (rows of visits past ``nvis`` are
    left as they were allocated)."""
    P, M = xs.shape
    V = gid.shape[0]
    T = P // V
    gated = len(weights) == 3
    wo = weights[-1]
    F = wo.shape[1]
    isz = xs.dtype.itemsize
    tm, tf = _chunk_rows(M, F, isz), _chunk_rows(F, M, isz)
    K1, K2 = M // tm, F // tf

    def row(i, gid, nvis):
        # visits past the last real one stay on its tile: nothing moves
        return (jnp.minimum(i, jnp.maximum(nvis[0] - 1, 0)), 0)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    scratch = [pltpu.VMEM((2, tm, F), xs.dtype)]
    if gated:
        scratch.append(pltpu.VMEM((2, tm, F), xs.dtype))
    scratch += [pltpu.VMEM((2, tf, M), xs.dtype),
                pltpu.SemaphoreType.DMA((3, 2))]
    need = sum(2 * a * b * isz for a, b in
               [(tm, F)] * (2 if gated else 1) + [(tf, M)])
    # what the shapes need and no round number: the rest of VMEM is where
    # XLA prefetches the dense weights of the operations around the call
    need += 4 * T * M * isz + T * (2 * F + 2 * M) * 4 + (4 << 20)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(V,),
        in_specs=[pl.BlockSpec((T, M), row)] + [any_spec] * len(weights),
        out_specs=pl.BlockSpec((T, M), row),
        scratch_shapes=scratch)
    return pl.pallas_call(
        functools.partial(_kernel, K1=K1, K2=K2, tm=tm, tf=tf, gated=gated,
                          activation=activation),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, M), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=need),
        interpret=interpret,
    )(gid, nvis, xs, *weights)


def row_tile(rows: int, experts: int) -> int:
    """Rows a visit for ``rows`` routed rows over ``experts`` experts: the
    smallest tile that holds the rows an expert expects, so that an
    expert's matrices are streamed about once (decode steps, 2-4 rows an
    expert: 16; a refill step at 51: 64), the largest when none does
    (:func:`fits` keeps such a step off the kernel)."""
    return next((t for t in _ROW_TILES if rows <= t * experts),
                _ROW_TILES[-1])


def kernel_impl(rows: int, experts: int, weights, dtype) -> Optional[str]:
    """Which implementation a sparse layer of this shape takes in serving:
    "pallas" on a TPU backend when the routed rows are a weight stream
    (an expert's expected rows fit the largest row tile, under the chip's
    ridge of ~240 rows an expert) over plain floating stacks whose widths
    tile;
    None (``ragged_dot``) otherwise, and anywhere but on a TPU."""
    if jax.default_backend() != "tpu":
        return None
    return "pallas" if fits(rows, experts, weights, dtype) else None


def fits(rows: int, experts: int, weights, dtype) -> bool:
    """The shape rule of :func:`kernel_impl`, backend apart. A packed or
    integer stack (no ``dtype`` of the compute type) does not fit."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) \
            or any(getattr(w, "dtype", None) != dtype for w in weights):
        return False
    M, F = weights[-1].shape[2], weights[-1].shape[1]
    return rows <= _ROW_TILES[-1] * experts \
        and M % 128 == 0 and F % 128 == 0


def layout_and_run(tokens, eid, weights, activation, dtype, *,
                   tile: int = ROW_TILE,
                   interpret: bool) -> jnp.ndarray:
    """Every routed row through its held expert. tokens [S, M]; eid [R]
    int32 with R = S x k, row r belonging to token ``r // k``, its local
    group or ``G`` for an expert held elsewhere. Returns ys [R, M], each
    row's output, zeros for a row in no group."""
    R = eid.shape[0]
    k = R // tokens.shape[0]
    G = weights[0].shape[0]
    dest, gid, nvis, _ = group_layout(eid, G, tile)
    P = gid.shape[0] * tile
    src = jnp.full((P,), tokens.shape[0], jnp.int32).at[dest].set(
        jnp.arange(R, dtype=jnp.int32) // k, mode="drop")
    xs = jnp.take(tokens.astype(dtype), src, axis=0, mode="fill",
                  fill_value=0)
    with region("moe_experts"):
        ys = grouped_ffn_decode(xs, gid, nvis, tuple(weights),
                                activation=activation, interpret=interpret)
    return jnp.take(ys, dest, axis=0, mode="fill", fill_value=0)
