"""Flash attention (Pallas TPU) — forward + full backward.

TPU-native replacement for the capability class of the reference's fused
attention kernels (``csrc/transformer/`` softmax/attention fusions and the
training transformer block, SURVEY.md §2.6): online-softmax tiling keeps the
S×S score matrix out of HBM, so activation memory is O(S) and the matmuls
stay MXU-shaped (block_q × d, block_k × d tiles).

Layout: kernels operate on (batch, heads, seq, head_dim). The public wrapper
accepts BTHD (flax convention) or BHTD, pads sequence lengths to block
multiples (masked), and broadcasts GQA KV heads.

Backward follows the standard FlashAttention-2 recipe: forward additionally
emits logsumexp; dq is accumulated over KV blocks, dk/dv over Q blocks, with
delta = rowsum(dO * O) precomputed outside the kernels.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
_LANES = 128


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale, causal, block_q, block_k, kv_len, causal_offset):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[:] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[:] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    run = True
    if causal:
        # skip blocks strictly above the (bottom-right-aligned) diagonal
        run = ki * block_k <= qi * block_q + (block_q - 1) + causal_offset

    @pl.when(run)
    def _compute():
        col = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = col < kv_len
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, row + causal_offset >= col)
        _online_softmax_block(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                              mask, sm_scale)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        # fully-masked padded rows have l == 0; emit zeros, lse = -inf
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # lse carried as (..., tq, 1): a trailing unit lane dim keeps the
        # block shape Mosaic-tileable ((block_q, 1) is legal; (1, block_q)
        # as the last two dims of a 3-D block is not).
        lse_ref[0, 0] = (m_scr[:, :1] + jnp.log(l_safe))


# --------------------------------------------------------------------------- #
# block-sparse variant: a (h, nq, nk) int32 layout in SMEM (scalar prefetch)
# gates each grid step — masked blocks skip the MXU work entirely (the
# "splash"-style sparsity path used by ops/sparse_attention.py)
# --------------------------------------------------------------------------- #


def _online_softmax_block(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                          s_mask, sm_scale):
    """One flash block update (shared by the dense and sparse kernels):
    scores for the current (q, k) tile, ``s_mask`` applied, online-softmax
    accumulators advanced. Matmul operands stay in their storage dtype
    (bf16 runs the MXU at full rate) with fp32 accumulation."""
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(s_mask, s, _NEG_INF)
    m_prev = m_scr[:]
    l_prev = l_scr[:]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next[:, :1])
    l_scr[:] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    m_scr[:] = m_next
    v = v_ref[0, 0]
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[:] = acc_scr[:] * alpha[:, :1] + pv


def _fwd_sparse_kernel(mask_ref, fetch_ref, q_ref, k_ref, v_ref, o_ref,
                       m_scr, l_scr, acc_scr, *, sm_scale, block_q, block_k,
                       kv_len, nq, nk):
    hi = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    del fetch_ref  # consumed by the k/v index maps

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[:] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[:] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    run = mask_ref[hi * nq * nk + qi * nk + ki] > 0

    @pl.when(run)
    def _compute():
        col = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        _online_softmax_block(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                              col < kv_len, sm_scale)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _sparse_fetch_schedule(block_mask: np.ndarray) -> np.ndarray:
    """Per grid step, the KV block index to have resident: allowed steps
    fetch their own block; masked steps repeat the previous allowed index so
    the block revisit costs no DMA (the splash-attention fetch trick)."""
    bm = np.asarray(block_mask) > 0
    h, nq, nk = bm.shape
    fetch = np.zeros((h, nq, nk), np.int32)
    for hi in range(h):
        for qi in range(nq):
            cur = int(np.argmax(bm[hi, qi])) if bm[hi, qi].any() else 0
            for j in range(nk):
                if bm[hi, qi, j]:
                    cur = j
                fetch[hi, qi, j] = cur
    return fetch


def _fwd_sparse(q, k, v, block_mask, sm_scale, block_q, block_k, kv_len,
                interpret):
    b, h, tq, d = q.shape
    tk = k.shape[2]
    nq, nk = tq // block_q, tk // block_k
    kernel = functools.partial(
        _fwd_sparse_kernel, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, kv_len=kv_len, nq=nq, nk=nk)

    def kv_index(bb, hh, i, j, mask_ref, fetch_ref):
        del mask_ref
        return (bb, hh, fetch_ref[hh * nq * nk + i * nk + j], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, i, j, *_: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, i, j, *_: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    fetch = _sparse_fetch_schedule(block_mask)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, tq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(block_mask.reshape(-1).astype(np.int32), fetch.reshape(-1), q, k, v)


def flash_attention_sparse(q, k, v, block_mask, *, sm_scale=None,
                           block_q: int = 128, block_k: int = 128,
                           layout: str = "BTHD",
                           interpret: Optional[bool] = None):
    """Block-sparse flash attention (forward): ``block_mask`` is a
    (heads, ceil(T/block_q), ceil(T/block_k)) boolean/int layout — masked
    blocks are skipped on the MXU. Used by ops/sparse_attention.py when the
    layout sparsity pays for the kernel switch. Inference-oriented (no VJP);
    training paths use the masked XLA attention."""
    if interpret is None:
        from . import default_interpret
        interpret = default_interpret()
    if layout == "BTHD":
        q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    elif layout != "BHTD":
        raise ValueError(f"unknown layout {layout!r}")
    b, h, tq, d = q.shape
    hk = k.shape[1]
    if hk != h:
        if h % hk:
            raise ValueError(f"GQA requires q_heads % kv_heads == 0 ({h}/{hk})")
        k = jnp.repeat(k, h // hk, axis=1)
        v = jnp.repeat(v, h // hk, axis=1)
    tk = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, _round_up(tq, _LANES))
    block_k = min(block_k, _round_up(tk, _LANES))
    tq_p, tk_p = _round_up(tq, block_q), _round_up(tk, block_k)
    if tq_p - tq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, tq_p - tq), (0, 0)))
    if tk_p - tk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, tk_p - tk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, tk_p - tk), (0, 0)))
    nq, nk = tq_p // block_q, tk_p // block_k
    try:
        # the layout is STATIC: it parameterizes the compiled grid (fetch
        # schedule is host-side) — a traced mask cannot work here
        bm = np.asarray(block_mask)
    except jax.errors.TracerArrayConversionError as e:
        raise ValueError(
            "flash_attention_sparse needs a static (host/numpy) block_mask; "
            "it determines the compiled fetch schedule and cannot be a "
            "traced value") from e
    if bm.shape != (h, nq, nk):
        raise ValueError(
            f"block_mask shape {bm.shape} != (heads={h}, nq={nq}, nk={nk}) "
            f"for block_q={block_q}, block_k={block_k}")
    o = _fwd_sparse(q, k, v, bm, float(sm_scale), block_q, block_k, tk,
                    interpret)
    o = o[:, :, :tq, :]
    if layout == "BTHD":
        o = jnp.swapaxes(o, 1, 2)
    return o


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, kv_len, causal_offset,
         interpret, group=1):
    b, h, tq, d = q.shape
    tk = k.shape[2]
    nq, nk = tq // block_q, tk // block_k
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=kv_len,
        causal_offset=causal_offset)
    grid = (b, h, nq, nk)
    out_shape = [
        jax.ShapeDtypeStruct((b, h, tq, d), q.dtype),
        jax.ShapeDtypeStruct((b, h, tq, 1), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)),
            # GQA: K/V stay (b, h//group, t, d); the index map broadcasts a
            # KV head across its q-head group — no materialized repeat
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, sm_scale, causal, block_q, block_k, kv_len,
                   causal_offset):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, dq_scr.dtype)

    run = True
    if causal:
        run = ki * block_k <= qi * block_q + (block_q - 1) + causal_offset

    @pl.when(run)
    def _compute():
        # matmul operands stay in storage dtype (bf16 MXU) w/ f32 accumulation
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                                   # (bq, 1)
        delta = delta_ref[0, 0]                               # (bq, 1)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        col = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = col < kv_len
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, row + causal_offset >= col)
        # padded q rows have lse == -inf; exp(s - lse) would be inf there
        mask = jnp.logical_and(mask, jnp.isfinite(lse))
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        dq_scr[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, sm_scale, causal, block_q, block_k, kv_len,
                    causal_offset, nq):
    # GQA grouped accumulation: the grid's innermost dim fuses (q-head in
    # group, q block) as gq = qh * nq + qi, so ONE kv head's dk/dv
    # accumulates over every q head it serves before the block is written
    # (init at the first step, finish at the last). group == 1 reduces to
    # the ungrouped order exactly.
    ki = pl.program_id(2)
    gq = pl.program_id(3)
    ng = pl.num_programs(3)
    qi = gq % nq

    @pl.when(gq == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, dk_scr.dtype)
        dv_scr[:] = jnp.zeros(dv_scr.shape, dv_scr.dtype)

    run = True
    if causal:
        run = qi * block_q + (block_q - 1) + causal_offset >= ki * block_k

    @pl.when(run)
    def _compute():
        # matmul operands stay in storage dtype (bf16 MXU) w/ f32 accumulation
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                                   # (bq, 1)
        delta = delta_ref[0, 0]                               # (bq, 1)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        col = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = col < kv_len
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, row + causal_offset >= col)
        mask = jnp.logical_and(mask, jnp.isfinite(lse))
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)            # (bq, bk)
        dv_scr[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)    # (bq, bk)
        dk_scr[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(gq == ng - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(causal, sm_scale, block_q, block_k, kv_len, causal_offset, interpret,
         res, g, dlse=None, group=1):
    q, k, v, o, lse = res
    do = g[0]
    b, h, tq, d = q.shape
    hk = k.shape[1]
    tk = k.shape[2]
    nq, nk = tq // block_q, tk // block_k

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                    # (b, h, tq, 1)
    if dlse is not None:
        # lse is a differentiable output here (ring attention combines
        # per-round partials by lse). Its cotangent folds into the FA-2
        # backward exactly: ds = p*(dp - delta) gains + p*dlse, i.e. the
        # same kernels run with delta' = delta - dlse.
        delta = delta - dlse.astype(jnp.float32)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, kv_len=kv_len,
                          causal_offset=causal_offset),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv: grid walks KV heads (hk = h // group); the innermost dim fuses
    # (q-head in group, q block) so each kv head's cotangent sums its whole
    # q-head group in-scratch — the index maps pick the q-side head as
    # hh * group + gq // nq and the q block as gq % nq.
    q_spec = pl.BlockSpec(
        (1, 1, block_q, d),
        lambda b, hh, i, gq: (b, hh * group + gq // nq, gq % nq, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda b, hh, i, gq: (b, hh, i, 0))
    row_spec = pl.BlockSpec(
        (1, 1, block_q, 1),
        lambda b, hh, i, gq: (b, hh * group + gq // nq, gq % nq, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, kv_len=kv_len,
                          causal_offset=causal_offset, nq=nq),
        grid=(b, hk, nk, group * nq),
        in_specs=[
            q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, hh, i, gq: (b, hh, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, hh, i, gq: (b, hh, i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, kv_len, causal_offset,
           interpret, group):
    o, _ = _fwd(q, k, v, causal, sm_scale, block_q, block_k, kv_len,
                causal_offset, interpret, group)
    return o


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, kv_len,
               causal_offset, interpret, group):
    o, lse = _fwd(q, k, v, causal, sm_scale, block_q, block_k, kv_len,
                  causal_offset, interpret, group)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, kv_len, causal_offset,
               interpret, group, res, g):
    return _bwd(causal, sm_scale, block_q, block_k, kv_len, causal_offset,
                interpret, res, (g,), group=group)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_lse(q, k, v, causal, sm_scale, block_q, block_k, kv_len,
               causal_offset, interpret, group):
    """(o, lse) with lse a differentiable output (used by ring attention)."""
    return _fwd(q, k, v, causal, sm_scale, block_q, block_k, kv_len,
                causal_offset, interpret, group)


def _flash_lse_fwd(q, k, v, causal, sm_scale, block_q, block_k, kv_len,
                   causal_offset, interpret, group):
    o, lse = _fwd(q, k, v, causal, sm_scale, block_q, block_k, kv_len,
                  causal_offset, interpret, group)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(causal, sm_scale, block_q, block_k, kv_len, causal_offset,
                   interpret, group, res, cts):
    do, dlse = cts
    return _bwd(causal, sm_scale, block_q, block_k, kv_len, causal_offset,
                interpret, res, (do,), dlse=dlse, group=group)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# ---------------------------------------------------------------------------
# public wrapper
# ---------------------------------------------------------------------------


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, sm_scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    layout: str = "BTHD",
                    interpret: Optional[bool] = None,
                    return_lse: bool = False):
    """Tiled online-softmax attention; differentiable (custom VJP).

    Args:
      q: (B, T, H, D) [layout="BTHD", flax convention] or (B, H, T, D).
      k, v: same layout; KV head count may divide H (GQA — heads broadcast).
      causal: lower-triangular mask.
      sm_scale: softmax scale, default 1/sqrt(D).
      block_q/block_k: tile sizes (clamped to the padded sequence). 512/512
        measured ~1.25x faster than XLA fused attention at T=512 and ~1.9x
        at T=2048 on v5e (fwd+bwd); 128/128 is ~2x SLOWER — small tiles
        leave the MXU idle between grid steps.
      interpret: run the Pallas interpreter (defaults to True off-TPU).
      return_lse: also return the per-row logsumexp (B, H, Tq) fp32 — itself
        differentiable, so callers (ring attention) can combine partials.
    """
    if interpret is None:
        from . import default_interpret
        interpret = default_interpret()
    if layout == "BTHD":
        q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    elif layout != "BHTD":
        raise ValueError(f"unknown layout {layout!r}")

    b, h, tq, d = q.shape
    hk = k.shape[1]
    if hk != h:
        if h % hk:
            raise ValueError(f"GQA requires q_heads % kv_heads == 0 ({h}/{hk})")
    # GQA KV heads are broadcast inside the kernels via h -> h // group
    # BlockSpec index maps (dk/dv use a grouped accumulation grid), so K/V
    # are never materialized per q-head — hk-headed tiles stream straight
    # from HBM and the cotangents come back hk-headed.
    group = h // hk
    tk = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    block_q = min(block_q, _round_up(tq, _LANES))
    block_k = min(block_k, _round_up(tk, _LANES))
    tq_p, tk_p = _round_up(tq, block_q), _round_up(tk, block_k)
    pad_q, pad_k = tq_p - tq, tk_p - tk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    # bottom-right-aligned causal diagonal (matches jnp.tril(..., k=tk-tq)
    # and jax.nn.dot_product_attention): decode-style tq < tk attends the
    # whole prefix.
    args = (q, k, v, causal, float(sm_scale), block_q, block_k, tk,
            tk - tq, interpret, group)
    if return_lse:
        o, lse = _flash_lse(*args)
        lse = lse[..., 0]                                  # (b, h, tq_p)
        if pad_q:
            lse = lse[:, :, :tq]
    else:
        o = _flash(*args)
    if pad_q:
        o = o[:, :, :tq, :]
    if layout == "BTHD":
        o = jnp.swapaxes(o, 1, 2)
    return (o, lse) if return_lse else o


def sharded_flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            mesh, *, causal: bool = True,
                            sm_scale: Optional[float] = None,
                            layout: str = "BTHD",
                            block_q: int = 512, block_k: int = 512,
                            batch_axes=("data", "data_inner"),
                            head_axis: str = "model",
                            interpret: Optional[bool] = None) -> jnp.ndarray:
    """``flash_attention`` under ``shard_map``: batch over the data axes,
    heads over the model axis, full sequence local. This is the DP/ZeRO/TP
    wrapping (batch and heads are embarrassingly parallel for attention) —
    Pallas custom calls carry no GSPMD rules, so without this a multi-device
    jit would replicate q/k/v around the kernel. SP meshes go through
    ``parallel/ulysses.py`` / ``parallel/ring_attention.py`` instead, which
    use the kernel as their local attention.

    Falls back to fewer sharded dims when sizes don't divide. q/k/v are
    (B, T, H, D) for layout="BTHD" (flax convention) or (B, H, T, D).
    """
    from ...utils.jax_compat import shard_map
    from jax.sharding import PartitionSpec as P

    if layout == "BTHD":
        b_dim, h_dim = 0, 2
    elif layout == "BHTD":
        b_dim, h_dim = 0, 1
    else:
        raise ValueError(f"unknown layout {layout!r}")

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    bat = tuple(a for a in batch_axes
                if sizes.get(a, 1) > 1 and q.shape[b_dim] % sizes[a] == 0)
    bsz = int(np.prod([sizes[a] for a in bat])) if bat else 1
    if bat and q.shape[b_dim] % bsz:
        bat = bat[:1]
        bsz = sizes[bat[0]]
    hd = (head_axis if head_axis and sizes.get(head_axis, 1) > 1
          and q.shape[h_dim] % sizes[head_axis] == 0
          and k.shape[h_dim] % sizes[head_axis] == 0 else None)

    spec = [None, None, None, None]
    spec[b_dim] = bat if bat else None
    spec[h_dim] = hd
    pspec = P(*spec)
    if pspec == P(None, None, None, None):
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               layout=layout, block_q=block_q,
                               block_k=block_k, interpret=interpret)

    def local(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=causal, sm_scale=sm_scale,
                               layout=layout, block_q=block_q,
                               block_k=block_k, interpret=interpret)

    return shard_map(local, mesh=mesh, in_specs=(pspec, pspec, pspec),
                     out_specs=pspec, check_vma=False)(q, k, v)


def attention_reference(q, k, v, *, causal=True, sm_scale=None,
                        layout="BTHD"):
    """Pure-jnp reference used by the kernel parity tests."""
    if layout == "BTHD":
        q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    b, h, tq, d = q.shape
    hk = k.shape[1]
    if hk != h:
        k = jnp.repeat(k, h // hk, axis=1)
        v = jnp.repeat(v, h // hk, axis=1)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        tk = k.shape[2]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    o = o.astype(q.dtype)
    if layout == "BTHD":
        o = jnp.swapaxes(o, 1, 2)
    return o
