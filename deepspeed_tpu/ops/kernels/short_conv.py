"""The short causal convolution of a recurrent layer at a decode step, on
the serving pool of carried inputs, in place.

A recurrent layer (KDA's ``q | k | v``, Mamba-2's ``x | B | C``, LFM2's
gated ``B * u``) passes its ``W`` projected channels through a depthwise
causal convolution of ``K`` taps, with a bias or without, and through an
activation or none (SiLU for KDA and Mamba-2 at ``K = 4``; none for LFM2
at ``K = 3``), before what follows. Across steps a sequence carries its
last ``K - 1`` inputs ``p_0 .. p_{K-2}``; at a decode step (one new input
``x`` a row) the layer's work on them is

    y = act((..(w_0 p_0 + w_1 p_1) + ..) + w_{K-1} x [+ bias])
    p_0, .., p_{K-2} <- p_1, .., p_{K-2}, x rounded to the pool's dtype

``models.solar_open2.short_conv`` with ``llama_runner``'s gather and
scatter says the same in ``jax.numpy`` (every prefill chunk, every other
backend, and what tier-1 holds this kernel to, bit for bit: float32
elementwise arithmetic in the same order, the same one rounding).
:func:`short_conv_decode_step` is the Pallas form, ONE body with ``K``
read from the taps' shape and the activation a static: each row's carried
inputs are read once from its slot, applied, shifted and written back to
the same slot.

The pool is ``[layers, rows, (K - 1) W / 128, 128]`` (:func:`pool_shape`):
a slot's ``K - 1`` inputs of ``W`` channels in the order ``[K - 1, W]``
has them, as whole ``(16, 128)`` tiles of bfloat16, so that a slot is one
contiguous slab for a DMA and tap ``j`` is its sublane rows
``[j W / 128, (j + 1) W / 128)``. (As ``[.., K - 1, W]`` the v5e compiler
tiles the 3 rows in fours, a third more bytes to hold and to move, and a
slot's channels lie along the lanes, where a kernel that takes a row a
time would use one sublane of eight.)

The step's inputs arrive as ``[S, W]`` float32, which the TPU holds in
tiles of 8 rows by 128 channels: the same bytes as ``[S / 8, W / 128, 8,
128]``. The kernel takes that view (the transpose in front of the call is
a relabelling of the tiles, not a copy: XLA compiles it to a bitcast
inside the fusion that produces the inputs) and turns 16 lane groups of 8
rows at a time into 8 rows of 16 lane groups (a transpose of whole
sublanes, and the same back for the outputs), so that a row's channels
lie as its slot has them. 16 rows a grid step, their slots' slabs by
manual DMA three buffers deep: the next step's come in and the last
step's go out under this step's arithmetic.
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
_ROWS = 8        # batch rows of one sublane tile of the inputs
_GROUPS = (2, 1)  # such tiles a grid step: 16 rows where the rows divide
_LANES = 128
_BUFS = 3        # a step's rows in, the step before's out, the next one's in


def pool_shape(layers: int, rows: int, taps: int, width: int
               ) -> Tuple[int, int, int, int]:
    """The pool of carried inputs: a slot's ``[taps - 1, width]`` in that
    order, cut into rows of 128 lanes (of ``width`` where it is no
    multiple of 128: the toy shapes of the CPU tests)."""
    lanes = _LANES if width % _LANES == 0 else width
    return (layers, rows, (taps - 1) * width // lanes, lanes)


def whole_width(width: int, dtype) -> int:
    """``width`` channels rounded up to the next width whose taps are whole
    tiles of the pool's dtype (:func:`decode_uses_kernel`'s grain: 11,520
    channels of bfloat16, 90 rows of 128 lanes a tap, become 12,288, 96
    rows = 6 tiles). A layer whose channels fall short asks the pool for
    this width and carries zeros in the rest (``llama_runner._short_conv``
    pads the step's inputs and the taps). A width that is no whole number
    of lane rows (the toy shapes) stays as it is."""
    unit = _LANES * 8 * 4 // jnp.dtype(dtype).itemsize
    return width if width % _LANES else -(-width // unit) * unit


def decode_uses_kernel(S: int, width: int, dtype,
                       backend: Optional[str] = None) -> bool:
    """Whether a decode step of ``S`` rows runs
    :func:`short_conv_decode_step`: on the TPU, at whole sublane tiles of
    rows and, a tap, whole tiles of the pool's dtype. The mixers dispatch
    on it and the engine counts by it."""
    backend = backend or jax.default_backend()
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    return (backend == "tpu" and S % _ROWS == 0
            and width % (_LANES * sublanes) == 0)


def _chunk(wc: int) -> int:
    """Sublane rows of a tap the body works on at a time: one bfloat16
    tile (all of them at a toy shape)."""
    return 16 if wc % 16 == 0 else wc


#: the activations a layer may ask for, by name (None: none)
ACTIVATIONS = {"silu": jax.nn.silu}


def _kernel(si_ref, slots_ref, fresh_ref, live_ref, x_ref, w_ref, *rest,
            taps, has_bias, activation):
    rest = list(rest)
    b_ref = rest.pop(0) if has_bias else None
    pool_in, pool_out, y_ref, buf, rsem, wsem = rest
    i, n = pl.program_id(0), pl.num_programs(0)
    groups, wc, R8, _ = x_ref.shape
    R = groups * R8
    si = si_ref[0]

    def slab(step, r, out):
        """The copy of row ``r`` of grid step ``step``: its slot's slab
        into the step's buffer, or out of it."""
        here = buf.at[step % _BUFS, r]
        slot = slots_ref[step * R + r]
        if out:
            return pltpu.make_async_copy(here, pool_out.at[si, slot],
                                         wsem.at[step % _BUFS])
        return pltpu.make_async_copy(pool_in.at[si, slot], here,
                                     rsem.at[step % _BUFS])

    def each_row(do):
        # a loop and not R copies of ``do``: the body is traced and
        # lowered once (a program's set-up is mostly Python tracing and
        # lowering: R copies cost a warm start 5 s, PERF.md PR 46)
        jax.lax.fori_loop(0, R, lambda r, carry: (do(r), carry)[1], 0)

    def reads(step, op):
        each_row(lambda r: op(slab(step, r, False)))

    def writes(step, op):
        # a row that is not live leaves its slot as it was
        def one(r):
            @pl.when(live_ref[step * R + r] != 0)
            def _():
                op(slab(step, r, True))
        each_row(one)

    start, wait = (lambda cp: cp.start()), (lambda cp: cp.wait())

    @pl.when(i == 0)
    def _first():
        reads(0, start)

    # the buffer these land in was written out under the last step's work
    @pl.when(i + 1 < n)
    def _next():
        reads(i + 1, start)

    reads(i, wait)
    mine = buf.at[i % _BUFS]

    def zero_fresh(r):
        # a fresh row starts from zero inputs, whatever the slot held
        @pl.when(fresh_ref[i * R + r] != 0)
        def _():
            mine[r] = jnp.zeros(mine.shape[1:], buf.dtype)
    each_row(zero_fresh)

    cn = _chunk(wc)

    def chunk(c, carry):
        """``cn`` lane groups of every row of the step, a sublane tile of
        rows at a time."""
        rows = pl.ds(pl.multiple_of(c * cn, cn), cn)
        at = lambda j: pl.ds(pl.multiple_of(j * wc + c * cn, cn), cn)  # noqa
        ws = [w_ref[j, rows, :] for j in range(taps)]
        bias = b_ref[rows, :] if has_bias else None
        for g in range(groups):
            tile = pl.ds(g * R8, R8)
            prev = [mine[tile, at(j), :] for j in range(taps - 1)]
            # the inputs hold a sublane tile as 8 rows x 128 channels; a
            # slot holds lane groups down the sublanes: turned here, and
            # back for the outputs
            x = jnp.swapaxes(x_ref[g, rows, :, :], 0, 1)
            acc = prev[0].astype(F32) * ws[0]
            for j in range(1, taps - 1):
                acc = acc + prev[j].astype(F32) * ws[j]
            acc = acc + x * ws[taps - 1]
            if has_bias:
                acc = acc + bias
            if activation is not None:
                acc = ACTIVATIONS[activation](acc)
            y_ref[g, rows, :, :] = jnp.swapaxes(acc, 0, 1)
            for j in range(1, taps - 1):
                mine[tile, at(j - 1), :] = prev[j]
            mine[tile, at(taps - 2), :] = x.astype(buf.dtype)
        return carry

    jax.lax.fori_loop(0, wc // cn, chunk, 0)
    writes(i, start)

    @pl.when(i > 0)
    def _last_out():
        writes(i - 1, wait)

    @pl.when(i == n - 1)
    def _drain():
        writes(i, wait)


# jitted under its own name: the device trace names a Mosaic call after
# the function that encloses it, and a program traces and lowers this once
# for all its recurrent layers (the layer is an operand)
@functools.partial(jax.jit, static_argnames=("activation", "interpret"))
def short_conv_decode_step(conv_pool, si, slots, x, w, bias, fresh, live, *,
                           activation="silu", interpret=False):
    """One decode token for every row, on the pool of carried inputs in
    place.

    conv_pool :func:`pool_shape`; ``si`` the layer (a scalar: every layer
    of a program shares this body); ``slots`` [S] the pool row of each
    batch row (distinct for live rows); x [S, W] float32 the step's
    inputs; w [K, W] float32 (tap K - 1 multiplies ``x``); bias [W]
    float32 or None; ``fresh`` [S] rows that start from zero inputs
    whatever the slot held; ``live`` [S]: a row that is not leaves its
    slot as it was; ``activation`` a name of :data:`ACTIVATIONS` or None.
    Returns (conv_pool, y [S, W] float32 after the activation)."""
    S, W = x.shape
    K = w.shape[0]
    _, _, prows, L = conv_pool.shape
    wc = W // L
    assert prows == (K - 1) * wc, (conv_pool.shape, w.shape)
    R = _ROWS if S % _ROWS == 0 else S
    G = S // R
    groups = next(n for n in _GROUPS if G % n == 0)
    tiles = lambda t: t.astype(F32).reshape(-1, wc, L)        # noqa: E731
    whole = lambda *shape: pl.BlockSpec(                       # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    rows = pl.BlockSpec((groups, wc, R, L), lambda i, *_: (i, 0, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    operands = [x.astype(F32).reshape(G, R, wc, L).transpose(0, 2, 1, 3),
                tiles(w)]
    in_specs = [rows, whole(K, wc, L)]
    if bias is not None:
        operands.append(tiles(bias)[0])
        in_specs.append(whole(wc, L))
    i32 = lambda t: jnp.asarray(t).astype(jnp.int32)           # noqa: E731
    slabs = (_BUFS, groups * R, prows, L)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(G // groups,),
        in_specs=in_specs + [any_spec], out_specs=[any_spec, rows],
        scratch_shapes=[pltpu.VMEM(slabs, conv_pool.dtype),
                        pltpu.SemaphoreType.DMA((_BUFS,)),
                        pltpu.SemaphoreType.DMA((_BUFS,))])
    # the slabs, the inputs' and outputs' blocks and the weights twice
    # each (the pipeline's two slots), and room for the body's own
    vmem = math.prod(slabs) * conv_pool.dtype.itemsize \
        + 2 * (2 * groups * R + K + 1) * W * 4 + (4 << 20)
    pool, y = pl.pallas_call(
        functools.partial(_kernel, taps=K, has_bias=bias is not None,
                          activation=activation),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(conv_pool.shape, conv_pool.dtype),
                   jax.ShapeDtypeStruct((G, wc, R, L), F32)],
        # the pool is the last operand, after the four prefetched scalars
        input_output_aliases={4 + len(operands): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(i32(si).reshape(1), i32(slots), i32(fresh), i32(live), *operands,
      conv_pool)
    return pool, y.transpose(0, 2, 1, 3).reshape(S, W)
