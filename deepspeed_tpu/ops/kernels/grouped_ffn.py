"""The routed experts' feed-forward of a serve step as one grouped kernel.

At a few rows an expert (a decode step) the work is a weight stream:
every expert that is hit has its matrices read once, and the multiplies
hide under the read. At the chip's ridge (a refill step at 256 rows an
expert) reading them once and multiplying cost about the same, and both
still hide under each other as long as an expert is ONE visit.
``jax.lax.ragged_dot`` (three calls a layer, XLA's own grouped matmul)
walks the ROWS; this kernel walks the GROUPS:

* rows arrive sorted by expert with every group laid out at a multiple
  of the row tile (:func:`group_layout`), so a group's row tiles lie one
  behind the other; a *visit* (one grid step) owns ONE expert and a span
  of its row tiles, every tile of the group while they are within the
  span cap (:func:`span_cap`: 128 rows, the last of ``_ROW_TILES``, for a
  call that expects no more an expert; 512, the last of
  ``_TALL_HEIGHTS``, for one that does);
* an expert's matrices come by manual DMA in contiguous row chunks, two
  scratch slots a matrix: the next chunk (and the next visit's first) is
  in flight while this one multiplies. A chunk, once in VMEM, multiplies
  every row of the span as ONE operand, of the smallest height of
  ``_ROW_TILES`` (and ``_TALL_HEIGHTS`` under the taller cap) that holds
  the span (the matrices are the MXU's
  stationary operand: two products of 16 rows cost twice one of 32), so
  the matrices of a hit expert are streamed once however many row tiles
  it has;
* the kernel brings a visit's rows itself, tile by tile (the next
  visit's while this one's down projection runs), and writes back only
  the visit's own tiles: a neighbouring group's rows inside the operand's
  height are computed and dropped;
* gate, up and down in one pass: ``g`` and ``u`` accumulate in float32
  over the hidden chunks, ``h = act(g) * u`` is rounded once to the
  operand type, and the down projection accumulates in float32 over the
  width chunks. Nothing of [rows, width] reaches HBM;
* a group with no row has no visit and costs no read; only a group with
  more rows than the span cap takes more visits (its matrices streamed
  again: :func:`streams` counts them); rows in no held group are never
  laid out, so never read.
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
#: the row tiles a layout may take and the heights of a visit's operand;
#: the last is the span cap of a call that expects no more rows an expert
#: (a weight stream: the chip's ridge is ~240 rows an expert)
_ROW_TILES = (16, 32, 64, 128)
#: the heights beyond, for a call that expects more (a refill step at the
#: ridge, 256 rows an expert): a padded operand row is MXU time there, so
#: they rise by a tile and not by doubling; the last is that call's cap
_TALL_HEIGHTS = (256, 384, 512)
#: bytes of one weight chunk in flight (a slot); two slots a matrix
_CHUNK_BYTES = 1 << 20


def visits_bound(rows: int, groups: int, tile: int = ROW_TILE) -> int:
    """Most visits ``rows`` routed rows over ``groups`` groups can take:
    sum of ceil(size / tile) <= (rows + groups x (tile - 1)) / tile."""
    return max(1, (rows + groups * (tile - 1)) // tile)


def span_cap(rows: int, experts: int) -> int:
    """Rows a visit spans at most, for ``rows`` routed rows over
    ``experts`` experts: 128 (the last row tile) while an expert expects
    no more, 512 past it, so that an expert at the ridge is still ONE
    visit and its matrices stream once. At 512 rows a visit the
    multiplies outweigh the stream 2 : 1, and a group beyond takes a
    second visit at little cost."""
    return _ROW_TILES[-1] if rows <= _ROW_TILES[-1] * experts \
        else _TALL_HEIGHTS[-1]


def streams(sizes, tile: int = ROW_TILE, cap: int = _ROW_TILES[-1]):
    """Times each group's matrices are streamed, [groups] int32 from the
    groups' row counts: once for a group with a row, once more for every
    span cap of rows (in whole tiles) beyond the first. The visits of
    :func:`group_layout`, and what ``moe_expert_reads`` sums."""
    tiles = (sizes + tile - 1) // tile
    per = cap // tile
    return ((tiles + per - 1) // per).astype(jnp.int32)


#: rows a block of :func:`group_layout`'s running count: one 0 / 1
#: triangle of this side sums a block's rows on the MXU, and a block's
#: count of a group (at most this) is still a whole number in bfloat16
_COUNT_BLOCK = 128


def group_layout(eid, groups: int, tile: int = ROW_TILE,
                 cap: int = _ROW_TILES[-1]):
    """Where each routed row goes when every group starts at a multiple of
    ``tile``. ``eid`` [R] int32: the (local) group of each routed row,
    ``groups`` for a row in no held group. Returns (dest [R] int32: the
    row's place in the padded layout, ``V x tile`` (out of range) for a
    row in no group, ``V = visits_bound(R, groups, tile)``; visits, three
    [V] int32 lists: the group each visit serves, its first row tile and
    how many of the group's tiles it spans (:func:`streams` visits a
    group), the last real visit repeated behind it; nvis [1] int32; sizes
    [groups] int32).

    Counted, not sorted: a row's rank in its group (what a stable sort by
    group would give it) is the number of rows of that group before it,
    and a group's size the count at the last row. Both come from ONE
    compare of ``eid`` against the groups and its running sum down the
    rows, taken in blocks of ``_COUNT_BLOCK`` rows as two products with
    0 / 1 triangles (inside a block, then over the blocks before it;
    exact: 0 / 1 and counts up to 128 in bfloat16, sums in float32). A
    scatter of R integers is R serial updates on the TPU, and the sort,
    the gathers through its order and the scatter back were 96 us at
    2,048 rows over 32 groups where this is 12 (PERF.md section 6,
    PR 57); the visit lists read their group by a compare too."""
    R = eid.shape[0]
    V = visits_bound(R, groups, tile)
    ids = jnp.arange(groups, dtype=jnp.int32)
    blocks = -(-R // _COUNT_BLOCK)
    e = jnp.pad(eid, (0, blocks * _COUNT_BLOCK - R),
                constant_values=groups).reshape(blocks, _COUNT_BLOCK)
    onehot = e[..., None] == ids                    # [blocks, 128, groups]
    # a product with a lower triangle of ones is a running sum
    within = jnp.einsum("ij,bjg->big",
                        jnp.tri(_COUNT_BLOCK, dtype=jnp.bfloat16),
                        onehot.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    totals = within[:, -1]                          # [blocks, groups]
    before = jnp.dot(jnp.tri(blocks, k=-1, dtype=jnp.bfloat16),
                     totals.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    sizes = (before[-1] + totals[-1]).astype(jnp.int32)
    tiles = (sizes + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)
    # a row's place: its group's first row and its rank there, both read
    # through the row's own one-hot
    place = within + before[:, None] + (
        (tile_end - tiles) * tile - 1).astype(jnp.float32)
    dest = jnp.where(
        e < groups,
        jnp.sum(jnp.where(onehot, place, 0), axis=-1).astype(jnp.int32),
        V * tile).reshape(-1)[:R]
    # visit v serves the first group whose visits end beyond v, from the
    # tile its earlier visits of that group stopped at
    per = cap // tile
    visits = streams(sizes, tile, cap)
    vis_end = jnp.cumsum(visits)
    nvis = vis_end[-1]
    v = jnp.minimum(jnp.arange(V, dtype=jnp.int32), jnp.maximum(nvis - 1, 0))
    gid = jnp.minimum(jnp.sum(vis_end <= v[:, None], axis=1, dtype=jnp.int32),
                      groups - 1)
    of = gid[:, None] == ids                        # [V, groups]

    def pick(per_group):
        return jnp.sum(jnp.where(of, per_group, 0), axis=1, dtype=jnp.int32)

    done = (v - pick(vis_end - visits)) * per
    first = pick(tile_end - tiles) + done
    ntile = jnp.clip(pick(tiles) - done, 0, per)
    return dest, (gid, first, ntile), nvis.reshape(1).astype(jnp.int32), sizes


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


def _kernel(gid_ref, first_ref, ntile_ref, nvis_ref, x_hbm, *rest, K1, K2,
            tm, tf, T, heights, gated, activation):
    rest = list(rest)
    wg_hbm = rest.pop(0)
    wu_hbm = rest.pop(0) if gated else None
    wo_hbm, y_hbm, xbuf, ybuf, gbuf = (rest.pop(0) for _ in range(5))
    ubuf = rest.pop(0) if gated else None
    obuf, sems, rsems = (rest.pop(0) for _ in range(3))
    if rest:
        # a taller span's float32 sums and its h, in scratch of their own
        gacc = rest.pop(0)
        uacc = rest.pop(0) if gated else None
        hbuf, yacc = rest
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

    def tile(j, first=0):
        return pl.ds(pl.multiple_of((first + j) * T, T), T)

    def rows_in(w, j):
        """Row tile j of visit w: [T, M] at a multiple of T in HBM, to
        the same place of the span in VMEM."""
        return pltpu.make_async_copy(x_hbm.at[tile(j, first_ref[w])],
                                     xbuf.at[tile(j)], rsems.at[0])

    def rows_out(w, j):
        return pltpu.make_async_copy(ybuf.at[tile(j)],
                                     y_hbm.at[tile(j, first_ref[w])],
                                     rsems.at[1])

    def each_tile(w, do):
        """``do(w, j)`` for every row tile j of visit w."""
        def one(j, carry):
            do(w, j)
            return carry
        jax.lax.fori_loop(0, ntile_ref[w], one, 0)

    def next_rows():
        # this visit's rows are read: the next visit's come in under the
        # down projection
        @pl.when(v + 1 < nvis)
        def _next_rows():
            each_tile(v + 1, lambda w, j: rows_in(w, j).start())

    def next_visit():
        @pl.when(v + 1 < nvis)
        def _next():
            start(up_copies(gid_ref[v + 1], 0, 0))

    def rows_landed():
        # the last visit's rows went out under this visit's stream: they
        # have long landed when this one's take their place in ``ybuf``
        @pl.when(v > 0)
        def _last_out():
            each_tile(v - 1, lambda w, j: rows_out(w, j).wait())

    def tall_span(H):
        """A span of more than one tile: the same pass over the expert's
        chunks in the same order, as LOOPS over the chunks with the sums
        in scratch, so the code of one chunk step a height and not of
        K1 + K2. Three more unrolled bodies beside the one-tile span's
        cost the kernel a third of its speed at Solar's and Pangu's
        widths with not one of them taken (PERF.md section 6, PR 45)."""
        e = gid_ref[v]

        def stream(K, copies, turn, multiply):
            """Chunk c of K multiplies with c + 1 in flight; ``turn``
            starts what follows the last."""
            def step(c, carry):
                slot = c % 2
                pl.when(c + 1 < K)(lambda: start(copies(e, c + 1, 1 - slot)))
                pl.when(c + 1 == K)(turn)
                wait(copies(e, c, slot))
                multiply(c, slot)
                return carry
            jax.lax.fori_loop(0, K, step, 0)

        def add(acc, x, w):
            # the product on the LEFT: the compiler then carries the running
            # sum through the MXU's 128-deep passes, one chain over all of
            # the contraction as a plain matmul sums it; ``acc + dot`` sums
            # each chunk apart first and reads a little further from the
            # plain reference (PERF.md section 6, PR 45)
            acc[:H] = jnp.dot(x, w, preferred_element_type=jnp.float32) \
                + acc[:H]

        def lanes(c, n):
            return pl.ds(pl.multiple_of(c * n, n), n)

        def up(c, slot):
            xc = xbuf[:H, lanes(c, tm)]
            add(gacc, xc, gbuf[slot])
            if gated:
                add(uacc, xc, ubuf[slot])

        def down(c, slot):
            add(yacc, hbuf[:H, lanes(c, tf)], obuf[slot])

        for acc in (gacc, uacc, yacc):
            if acc is not None:
                acc[:H] = jnp.zeros((H, acc.shape[1]), jnp.float32)
        stream(K1, up_copies, lambda: start(down_copy(e, 0, 0)), up)
        h = activation(gacc[:H]) * uacc[:H] if gated \
            else activation(gacc[:H])
        hbuf[:H] = h.astype(hbuf.dtype)
        next_rows()
        stream(K2, down_copy, next_visit, down)
        rows_landed()
        ybuf[:H] = yacc[:H].astype(ybuf.dtype)

    def span(H):
        """The visit's rows as one operand of H rows through gate, up
        and down: one pass over the expert's chunks."""
        if H > heights[0]:
            return tall_span(H)
        e = gid_ref[v]
        g = u = None
        for c in range(K1):
            # one chunk ahead, always: the stream never waits for us
            if c + 1 < K1:
                start(up_copies(e, c + 1, (c + 1) % 2))
            else:
                start(down_copy(e, 0, 0))
            wait(up_copies(e, c, c % 2))
            xc = xbuf[:H, c * tm:(c + 1) * tm]
            gp = jnp.dot(xc, gbuf[c % 2], preferred_element_type=jnp.float32)
            g = gp if g is None else g + gp
            if gated:
                up = jnp.dot(xc, ubuf[c % 2],
                             preferred_element_type=jnp.float32)
                u = up if u is None else u + up
        h = activation(g) * u if gated else activation(g)
        h = h.astype(xbuf.dtype)
        next_rows()
        acc = None
        for c in range(K2):
            if c + 1 < K2:
                start(down_copy(e, c + 1, (c + 1) % 2))
            else:
                next_visit()
            wait(down_copy(e, c, c % 2))
            part = jnp.dot(h[:, c * tf:(c + 1) * tf], obuf[c % 2],
                           preferred_element_type=jnp.float32)
            acc = part if acc is None else acc + part
        rows_landed()
        ybuf[:H] = acc.astype(ybuf.dtype)

    @pl.when(v < nvis)
    def _visit():
        @pl.when(v == 0)
        def _first():
            start(up_copies(gid_ref[v], 0, 0))
            each_tile(v, lambda w, j: rows_in(w, j).start())

        each_tile(v, lambda w, j: rows_in(w, j).wait())
        rows = ntile_ref[v] * T
        below = 0
        for H in heights:
            # the smallest height that holds the span; one tile is the
            # first branch, what a step at 3-4 rows an expert always takes
            pl.when((rows > below) & (rows <= H))(
                functools.partial(span, H))
            below = H
        each_tile(v, lambda w, j: rows_out(w, j).start())

        @pl.when(v == nvis - 1)
        def _drain():
            each_tile(v, lambda w, j: rows_out(w, j).wait())


def _heights(T: int, V: int, cap: int = _ROW_TILES[-1]):
    """The operand heights a call's visits choose from: ``_ROW_TILES``
    (and ``_TALL_HEIGHTS`` under a taller cap) from the layout's tile up
    to the span cap, no further than holds the whole layout."""
    hs = [h for h in _ROW_TILES + _TALL_HEIGHTS if T <= h <= cap]
    enough = next((i for i, h in enumerate(hs) if h >= V * T), len(hs) - 1)
    return tuple(hs[:enough + 1])


def vmem_need(T: int, V: int, M: int, F: int, itemsize: int,
              gated: bool, cap: int = _ROW_TILES[-1]) -> int:
    """Bytes of VMEM a call asks for (its ``vmem_limit_bytes``): the two
    chunk slots a matrix, the rows in and out of the tallest span, that
    span's float32 intermediates and 4 MB. What the shapes need and no
    round number: the rest of VMEM is where XLA prefetches the dense
    weights of the operations around the call."""
    tm, tf = _chunk_rows(M, F, itemsize), _chunk_rows(F, M, itemsize)
    H = _heights(T, V, cap)[-1]
    need = sum(2 * a * b * itemsize for a, b in
               [(tm, F)] * (2 if gated else 1) + [(tf, M)])
    return need + 2 * H * M * itemsize + H * (2 * F + 2 * M) * 4 + (4 << 20)


# jitted under its own name: the device trace names a Mosaic call after
# the function that encloses it, and a program whose layers share shapes
# traces this body once
@functools.partial(jax.jit,
                   static_argnames=("activation", "cap", "interpret"))
def grouped_ffn_decode(xs, visits, nvis, weights, *, activation,
                       cap=_ROW_TILES[-1], interpret=False):
    """xs [V * T, M] rows in :func:`group_layout`'s order at row tile T
    and span cap ``cap``; visits (gid, first, ntile) [V] each, nvis [1];
    weights (wi, wo) or
    (wi_gate, wi_up, wo) stacked [G, ...] in xs's dtype. Returns ys
    [V * T, M]: every laid-out row through its group's feed-forward (rows
    of no visit's tiles are left as they were allocated)."""
    P, M = xs.shape
    V = visits[0].shape[0]
    T = P // V
    gated = len(weights) == 3
    wo = weights[-1]
    F = wo.shape[1]
    isz = xs.dtype.itemsize
    tm, tf = _chunk_rows(M, F, isz), _chunk_rows(F, M, isz)
    K1, K2 = M // tm, F // tf
    heights = _heights(T, V, cap)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    H = heights[-1]
    scratch = [pltpu.VMEM((H, M), xs.dtype)] * 2 \
        + [pltpu.VMEM((2, tm, F), xs.dtype)] * (2 if gated else 1) \
        + [pltpu.VMEM((2, tf, M), xs.dtype),
           pltpu.SemaphoreType.DMA((3, 2)), pltpu.SemaphoreType.DMA((2,))]
    if len(heights) > 1:
        scratch += [pltpu.VMEM((H, F), jnp.float32)] * (2 if gated else 1) \
            + [pltpu.VMEM((H, F), xs.dtype), pltpu.VMEM((H, M), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(V,),
        in_specs=[any_spec] * (1 + len(weights)),
        out_specs=any_spec,
        scratch_shapes=scratch)
    return pl.pallas_call(
        functools.partial(_kernel, K1=K1, K2=K2, tm=tm, tf=tf, T=T,
                          heights=heights, gated=gated,
                          activation=activation),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, M), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_need(T, V, M, F, isz, gated, cap)),
        interpret=interpret,
    )(*visits, nvis, xs, *weights)


def row_tile(rows: int, experts: int) -> int:
    """The layout's row tile for ``rows`` routed rows over ``experts``
    experts: the smallest that holds the rows an expert expects, so that
    the padding a group brings stays under its rows (decode steps, 2-12
    rows an expert: 16; a refill step at 51: 64), the largest when none
    does (a refill step at the ridge, 256 rows an expert). A visit spans
    all of a group's tiles up to :func:`span_cap` rows, so a group over
    its tile costs rows of padding and no second stream."""
    return next((t for t in _ROW_TILES if rows <= t * experts),
                _ROW_TILES[-1])


def kernel_impl(weights, dtype) -> Optional[str]:
    """Which implementation a sparse layer takes in serving: "pallas" on
    a TPU backend over plain floating stacks whose widths tile, however
    many rows an expert expects (:func:`row_tile` and :func:`span_cap`
    follow them, from a decode step's 2-4 to a refill step's 256 at the
    chip's ridge); None (``ragged_dot``) otherwise, and anywhere but on a
    TPU."""
    if jax.default_backend() != "tpu":
        return None
    return "pallas" if fits(weights, dtype) else None


def fits(weights, dtype) -> bool:
    """The rule of :func:`kernel_impl`, backend apart: operand types and
    widths. A packed or integer stack (no ``dtype`` of the compute type)
    does not fit."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) \
            or any(getattr(w, "dtype", None) != dtype for w in weights):
        return False
    M, F = weights[-1].shape[2], weights[-1].shape[1]
    return M % 128 == 0 and F % 128 == 0


def layout_and_run(tokens, eid, weights, activation, dtype, *,
                   tile: int = ROW_TILE, cap: int = _ROW_TILES[-1],
                   interpret: bool) -> jnp.ndarray:
    """Every routed row through its held expert. tokens [S, M]; eid [R]
    int32 with R = S x k, row r belonging to token ``r // k``, its local
    group or ``G`` for an expert held elsewhere. Returns ys [R, M], each
    row's output, zeros for a row in no group."""
    R = eid.shape[0]
    k = R // tokens.shape[0]
    G = weights[0].shape[0]
    dest, visits, nvis, _ = group_layout(eid, G, tile, cap)
    # the rows' places as a plain array, as a scatter used to leave them:
    # fused into their consumers, XLA plans the gather below otherwise
    # and a refill step's runs three times as long (PERF.md section 6,
    # PR 57)
    dest = jax.lax.optimization_barrier(dest)
    P = visits[0].shape[0] * tile
    src = jnp.full((P,), tokens.shape[0], jnp.int32).at[dest].set(
        jnp.arange(R, dtype=jnp.int32) // k, mode="drop")
    xs = jnp.take(tokens.astype(dtype), src, axis=0, mode="fill",
                  fill_value=0)
    with region("moe_experts"):
        ys = grouped_ffn_decode(xs, visits, nvis, tuple(weights),
                                activation=activation, cap=cap,
                                interpret=interpret)
    return jnp.take(ys, dest, axis=0, mode="fill", fill_value=0)
