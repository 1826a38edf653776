"""A layer's per-head QK-norm and rotary code as ONE call with a backward
of its own: ``qk_norm_rope``.

For each head's ``D`` lanes of ``q`` and of ``k``, in float32 inside the
call: ``n = x * rsqrt(mean(x^2) + eps)``, ``z = n * scale`` (one float32
``[D]`` scale for ``q``, one for ``k``) and, where a table is given, the
rotate-half code over all ``D`` lanes, ``y = [z1 cos - z2 sin, z1 sin + z2
cos]``. The operand is read in its own dtype and the result written in it:
ONE rounding, where ``RMSNorm`` then ``apply_rope`` round twice (the
norm's output, then the rotation's).

The rotation needs no lane split: with ``cos`` widened to ``D`` lanes and
``sin`` signed ``[-sin, +sin]`` (:func:`rotary_table`), ``y = z * cos +
roll(z, D / 2) * sin``; its transposition is the rotation by the negative
angle, ``dz = dy * cos - roll(dy, D / 2) * sin``.

The backward is one pass over ``(x, dy)``: ``dz`` as above, ``dscale =
sum(dz * n)`` over rows and heads, ``dn = dz * scale``, ``dx = r * (dn - n
* mean(dn * n))`` with ``r`` and ``n`` recomputed from ``x``. The
residuals are the operands themselves: autodiff of the composition keeps,
copies and reduces float32 arrays of the operand's size (``[2, 8192, 32,
128]`` in the sparse train cell: 48 ms of a 552 ms step).

The operands are the projections' own rows, ``[B, T, H * D]`` (a head is
a ``D``-lane group of a row: no relayout in front), and the results are
head-major, ``[B, H, T, D]``, what the flash kernels read
(``flash_attention(layout="BHTD")``): the call's output index map IS the
transposition, where XLA put two copies of ``q`` between a row-major
result and the flash call (0.41 ms each on a v5e at ``[2, 8192, 4096]``).
The backward reads the cotangents head-major and writes rows.

Where :func:`uses_kernel` holds, each pass is a Pallas call, ``q`` and
``k`` through one body, a grid step a block of rows sized by bytes and a
loop over its heads (one ``[rows, D]`` slab at a time, not a copy of the
body a head: code size is time on the v5e); the backward writes
``dscale`` as an ``[8, D]`` partial sum a grid step for XLA to add up.
Everywhere else the same ``custom_vjp`` runs the ``jax.numpy`` twin of
the same forward and the same backward.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
#: rows a block is a multiple of: a packed bfloat16 tile's sublanes
_ROWS = 16
#: bytes of ``q`` a grid step reads, and writes: its rows follow them
_BLOCK_BYTES = 1 << 21
#: heads a trip of a block's loop holds side by side
_HEADS_A_TRIP = 4


def rotary_table(T: int, D: int, theta: float):
    """(cos, sin) float32 ``[T, D]`` of positions ``0 .. T - 1`` at
    ``models.llama.rope_frequencies``' angles: ``cos`` twice over, ``sin``
    signed ``[-sin, +sin]``."""
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def row_block(T: int, width: int, dtype) -> int:
    """Rows of a grid step: the most whole tiles that divide ``T`` and
    keep a block of ``width`` lanes under ``_BLOCK_BYTES`` (256 at the
    cell's 4,096 bfloat16 lanes); 0 where ``T`` is no whole tiles."""
    most = min(T, _BLOCK_BYTES // (width * jnp.dtype(dtype).itemsize))
    return next((n for n in range(most // _ROWS * _ROWS, 0, -_ROWS)
                 if T % n == 0), 0)


def fits(T: int, H: int, D: int, dtype) -> bool:
    """Whether the kernel takes a call, backend apart: a floating operand,
    heads of whole lane tiles, ``T`` whole row blocks."""
    return bool(jnp.issubdtype(jnp.dtype(dtype), jnp.floating)
                and D % _LANES == 0 and row_block(T, H * D, dtype))


def uses_kernel(T: int, H: int, D: int, dtype) -> bool:
    """The compiled kernel: one TPU device (where ``AfmoeAttention`` takes
    the flash kernels) and a call that :func:`fits`."""
    return jax.default_backend() == "tpu" and jax.device_count() == 1 \
        and fits(T, H, D, dtype)


# ---------------------------------------------------------------------------
# the mathematics, on [rows.., D] float32 slabs: the kernel's body a head
# and the twin's whole array share it
# ---------------------------------------------------------------------------


def _normed(x, eps):
    """(r, n): the reciprocal root mean square a row, and ``x * r``."""
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return r, x * r


def _forward(x, scale, eps, cos_sin, roll):
    z = _normed(x.astype(jnp.float32), eps)[1] * scale
    if cos_sin is not None:
        z = z * cos_sin[0] + roll(z) * cos_sin[1]
    return z.astype(x.dtype)


def _backward(x, dy, scale, eps, cos_sin, roll):
    """(dx, dz * n): the second is ``dscale`` before its sum over rows."""
    r, n = _normed(x.astype(jnp.float32), eps)
    dz = dy.astype(jnp.float32)
    if cos_sin is not None:
        dz = dz * cos_sin[0] - roll(dz) * cos_sin[1]
    t = dz * n
    dx = r * (dz * scale - n * jnp.mean(t * scale, axis=-1, keepdims=True))
    return dx.astype(x.dtype), t


def _twin(x, D, cos_sin):
    """``x`` [B, T, heads * D] a head at a time, the table beside it, and
    the rotation's roll."""
    B, T, W = x.shape
    if cos_sin is not None:
        cos_sin = tuple(t[:, None, :] for t in cos_sin)
    return (x.reshape(B, T, W // D, D), cos_sin,
            functools.partial(jnp.roll, shift=D // 2, axis=-1))


def _twin_fwd(x, scale, eps, cos_sin):
    x, cos_sin, roll = _twin(x, scale.shape[0], cos_sin)
    return jnp.swapaxes(_forward(x, scale, eps, cos_sin, roll), 1, 2)


def _twin_bwd(x, dy, scale, eps, cos_sin):
    shape = x.shape
    x, cos_sin, roll = _twin(x, scale.shape[0], cos_sin)
    dx, t = _backward(x, jnp.swapaxes(dy, 1, 2), scale, eps, cos_sin, roll)
    return dx.reshape(shape), jnp.sum(t, axis=(0, 1, 2))


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _heads(ref, D, body, carry):
    """``body(h, lanes, carry)`` over the heads of a ``[rows, H * D]`` block:
    a loop, not H copies, of up to ``_HEADS_A_TRIP`` heads a trip (a
    head's work is one chain through a reduction and a reciprocal root:
    the scheduler fills its waits with the next head's; one head a trip
    ran a sliding layer's forward in 0.585 ms on a v5e, four in 0.481)."""
    heads = ref.shape[-1] // D
    n = next(n for n in range(min(_HEADS_A_TRIP, heads), 0, -1)
             if heads % n == 0)

    def trip(i, carry):
        for j in range(n):
            h = i * n + j
            carry = body(h, pl.ds(pl.multiple_of(h * D, D), D), carry)
        return carry
    return jax.lax.fori_loop(0, heads // n, trip, carry)


def _table(refs):
    """The step's (cos, sin) blocks, read where they are used: a table
    held over the heads' loop would be held in registers."""
    return None if refs is None else (refs[0][...], refs[1][...])


def _fwd_kernel(*refs, eps: float, D: int, rotate: bool):
    table = refs[:2] if rotate else None
    qs_ref, ks_ref, q_ref, k_ref, qo_ref, ko_ref = refs[2 * rotate:]
    roll = functools.partial(pltpu.roll, shift=D // 2, axis=1)

    def one(x_ref, s_ref, o_ref):
        def head(h, lanes, carry):
            o_ref[h] = _forward(x_ref[:, lanes], s_ref[...], eps,
                                _table(table), roll)
            return carry
        _heads(x_ref, D, head, 0)

    one(q_ref, qs_ref, qo_ref)
    one(k_ref, ks_ref, ko_ref)


def _bwd_kernel(*refs, eps: float, D: int, rotate: bool):
    table = refs[:2] if rotate else None
    (qs_ref, ks_ref, q_ref, k_ref, dqo_ref, dko_ref,
     dq_ref, dk_ref, dqs_ref, dks_ref) = refs[2 * rotate:]
    roll = functools.partial(pltpu.roll, shift=D // 2, axis=1)

    def one(x_ref, dy_ref, s_ref, dx_ref, ds_ref):
        def head(h, lanes, acc):
            dx, t = _backward(x_ref[:, lanes], dy_ref[h], s_ref[...], eps,
                              _table(table), roll)
            dx_ref[:, lanes] = dx
            # the rows' sum down to one tile of sublanes: adds of whole
            # registers, no reduction across them
            return acc + jnp.sum(t.reshape(-1, 8, D), axis=0)
        ds_ref[...] = _heads(x_ref, D, head, jnp.zeros((8, D), jnp.float32))

    one(q_ref, dqo_ref, qs_ref, dq_ref, dqs_ref)
    one(k_ref, dko_ref, ks_ref, dk_ref, dks_ref)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _call(q, k, q_scale, k_scale, cos_sin, cotangents=(), *, eps, interpret):
    """One pass over the rows ``q`` [B, T, H * D] and ``k`` [B, T, KV * D]:
    the forward -> [B, H, T, D], [B, KV, T, D], or with ``cotangents`` (of
    those shapes) the backward -> the operands' cotangents in the
    operands' shapes and the scales'. A grid step is a block of rows of
    one sequence, the batch innermost, so that a step's table block is the
    one before's. One named program a pass, traced and lowered once for
    the layers of a step that share its shapes."""
    B, T, W = q.shape
    D = q_scale.shape[0]
    rotate = cos_sin is not None
    tb = row_block(T, W, q.dtype)
    grid = (T // tb, B)
    const = [pl.BlockSpec((tb, D), lambda i, b: (i, 0))] * (2 * rotate) \
        + [pl.BlockSpec((1, D), lambda i, b: (0, 0))] * 2
    rows = [pl.BlockSpec((None, tb, x.shape[-1]), lambda i, b: (b, i, 0))
            for x in (q, k)]
    by_head = [pl.BlockSpec((None, x.shape[-1] // D, tb, D),
                            lambda i, b: (b, 0, i, 0)) for x in (q, k)]
    if cotangents:
        kernel, in_specs = _bwd_kernel, const + rows + by_head
        out_specs = rows + [pl.BlockSpec((None, None, 8, D),
                                         lambda i, b: (i, b, 0, 0))] * 2
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k)] \
            + [jax.ShapeDtypeStruct(grid + (8, D), jnp.float32)] * 2
    else:
        kernel, in_specs, out_specs = _fwd_kernel, const + rows, by_head
        out_shape = [jax.ShapeDtypeStruct((B, x.shape[-1] // D, T, D),
                                          x.dtype) for x in (q, k)]
    out = pl.pallas_call(
        functools.partial(kernel, eps=eps, D=D, rotate=rotate),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name="qk_norm_rope",
    )(*(cos_sin or ()), q_scale.reshape(1, D), k_scale.reshape(1, D), q, k,
      *cotangents)
    if not cotangents:
        return tuple(out)
    dq, dk, dqs, dks = out
    return dq, dk, dqs.sum((0, 1, 2)), dks.sum((0, 1, 2))


# ---------------------------------------------------------------------------
# the operation
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _qk_norm_rope(impl, eps, q, k, q_scale, k_scale, cos_sin):
    if impl is None:
        return (_twin_fwd(q, q_scale, eps, cos_sin),
                _twin_fwd(k, k_scale, eps, cos_sin))
    return _call(q, k, q_scale, k_scale, cos_sin, eps=eps,
                 interpret=impl == "interpret")


def _vjp_fwd(impl, eps, q, k, q_scale, k_scale, cos_sin):
    return (_qk_norm_rope(impl, eps, q, k, q_scale, k_scale, cos_sin),
            (q, k, q_scale, k_scale, cos_sin))


def _vjp_bwd(impl, eps, res, ct):
    q, k, q_scale, k_scale, cos_sin = res
    if impl is None:
        dq, dqs = _twin_bwd(q, ct[0], q_scale, eps, cos_sin)
        dk, dks = _twin_bwd(k, ct[1], k_scale, eps, cos_sin)
    else:
        dq, dk, dqs, dks = _call(q, k, q_scale, k_scale, cos_sin, tuple(ct),
                                 eps=eps, interpret=impl == "interpret")
    # the table is positions alone: nothing reads its cotangent
    return (dq, dk, dqs, dks,
            jax.tree_util.tree_map(jnp.zeros_like, cos_sin))


_qk_norm_rope.defvjp(_vjp_fwd, _vjp_bwd)


def impl_of(T: int, H: int, D: int, dtype,
            interpret: bool = False) -> Optional[str]:
    """How a call runs: "pallas" where :func:`uses_kernel` holds,
    "interpret" where the caller asks for the kernel interpreted and the
    call :func:`fits`, None (the ``jax.numpy`` twin) anywhere else."""
    if interpret:
        return "interpret" if fits(T, H, D, dtype) else None
    return "pallas" if uses_kernel(T, H, D, dtype) else None


def qk_norm_rope(q: jnp.ndarray, k: jnp.ndarray, q_scale: jnp.ndarray,
                 k_scale: jnp.ndarray, eps: float, cos_sin=None, *,
                 interpret: bool = False):
    """``(q', k')`` head-major, ``[B, H, T, D]`` and ``[B, KV, T, D]``
    (``flash_attention``'s ``layout="BHTD"``), from the projections' rows
    ``q`` [B, T, H * D] and ``k`` [B, T, KV * D]: each head's ``D`` lanes
    RMS-normed, scaled by ``q_scale`` / ``k_scale`` (float32 [D]) and,
    with ``cos_sin`` (:func:`rotary_table`'s pair, float32 [T, D] each),
    rotated by their position; float32 inside, rounded once to the
    operands' dtype. Differentiable in ``q``, ``k`` and the scales,
    through a backward of its own (module docstring)."""
    D = q_scale.shape[0]
    _, T, W = q.shape
    return _qk_norm_rope(impl_of(T, W // D, D, q.dtype, interpret),
                         float(eps), q, k, q_scale.astype(jnp.float32),
                         k_scale.astype(jnp.float32), cos_sin)
