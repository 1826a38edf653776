"""Evoformer (triangle) attention — Pallas TPU forward kernel.

Kernel-level answer to the reference's ``csrc/deepspeed4science/
evoformer_attn/`` (14.9k LoC of CUTLASS fwd+bwd): flash-style online
softmax over [B, N, S, H, D] MSA/triangle attention with the two
canonical additive bias layouts fused into the score tiles —

  mask bias  [B, N, 1, 1, Sk]  (per-row key mask, broadcast over H, Sq)
  pair bias  [B, 1, H, Sq, Sk] (triangle bias, broadcast over N)

so the [B, N, H, Sq, Sk] score tensor never exists in HBM (the reason
the reference kernel exists — AlphaFold-scale shapes blow memory).

Backward is recompute-based (VERDICT r4 #9): a ``jax.custom_vjp`` whose
bwd replays the chunked jnp path (``ops.evoformer_attn``) under the same
numerics — one extra fwd's FLOPs, zero extra resident memory, and the
kernel stays fwd-only (the CUTLASS bwd's 10k LoC is exactly what remat
deletes on TPU).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _fwd_kernel(q_ref, k_ref, v_ref, mb_ref, pb_ref, o_ref,
                m_scr, l_scr, acc_scr, *, scale, block_q, block_k, kv_len):
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[:] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[:] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if mb_ref is not None:
        s = s + mb_ref[0].astype(jnp.float32)          # [1, Tk] row bias
    if pb_ref is not None:
        s = s + pb_ref[0, 0].astype(jnp.float32)       # [Tq, Tk] pair bias
    col = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    s = jnp.where(col < kv_len, s, _NEG_INF)

    m_prev, l_prev = m_scr[:], l_scr[:]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_cur)
    # fully-masked rows (mask/pair bias -inf across every key) keep the
    # running max at -inf; clamping to a finite floor stops alpha from
    # becoming exp(-inf - -inf) = NaN while exp(-inf - floor) stays 0, so
    # the l==0 guard below sees clean zeros and emits 0 output rows
    m_next = jnp.maximum(m_next, -1e30)
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next[:, :1])
    l_scr[:] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    m_scr[:] = m_next
    v = v_ref[0, 0]
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[:] = acc_scr[:] * alpha[:, :1] + pv

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)           # fully-masked rows
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _evo_fwd_pallas(q4, k4, v4, mb2, pb4, *, n_rows, scale, block_q,
                    block_k, interpret):
    """q4/k4/v4: [BN, H, S, D]; mb2: [BN, Sk] or None; pb4: [B, H, Sq, Sk]
    or None (B = BN // n_rows)."""
    BN, H, Sq, D = q4.shape
    Sk = k4.shape[2]
    Tq = min(block_q, _round_up(Sq, 8))
    Tk = min(block_k, _round_up(Sk, 128))
    Sq2, Sk2 = _round_up(Sq, Tq), _round_up(Sk, Tk)
    if Sq2 != Sq:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, Sq2 - Sq), (0, 0)))
    if Sk2 != Sk:
        k4 = jnp.pad(k4, ((0, 0), (0, 0), (0, Sk2 - Sk), (0, 0)))
        v4 = jnp.pad(v4, ((0, 0), (0, 0), (0, Sk2 - Sk), (0, 0)))
        if mb2 is not None:
            mb2 = jnp.pad(mb2, ((0, 0), (0, Sk2 - Sk)))
    if pb4 is not None and (Sq2 != Sq or Sk2 != Sk):
        pb4 = jnp.pad(pb4, ((0, 0), (0, 0), (0, Sq2 - Sq), (0, Sk2 - Sk)))
    nq, nk = Sq2 // Tq, Sk2 // Tk

    in_specs = [
        pl.BlockSpec((1, 1, Tq, D), lambda bn, h, qi, ki: (bn, h, qi, 0)),
        pl.BlockSpec((1, 1, Tk, D), lambda bn, h, qi, ki: (bn, h, ki, 0)),
        pl.BlockSpec((1, 1, Tk, D), lambda bn, h, qi, ki: (bn, h, ki, 0)),
    ]
    args = [q4, k4, v4]
    if mb2 is not None:
        # carried [BN, 1, Sk]: a (1, Tk) block of a 2-D [BN, Sk] array has
        # a sublane dim that neither divides by 8 nor spans the array,
        # which the Mosaic lowering refuses; a unit middle dim does span
        in_specs.append(
            pl.BlockSpec((1, 1, Tk), lambda bn, h, qi, ki: (bn, 0, ki)))
        args.append(mb2[:, None, :])
    if pb4 is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, Tq, Tk),
            lambda bn, h, qi, ki: (bn // n_rows, h, qi, ki)))
        args.append(pb4)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=Tq, block_k=Tk, kv_len=Sk)
    if mb2 is None or pb4 is None:
        # bind absent refs as None positionally
        base = kernel

        def kernel(q_ref, k_ref, v_ref, *rest):
            refs = list(rest[:-4])       # bias refs before outputs/scratch
            out_scr = rest[-4:]
            mb_ref = refs.pop(0) if mb2 is not None else None
            pb_ref = refs.pop(0) if pb4 is not None else None
            return base(q_ref, k_ref, v_ref, mb_ref, pb_ref, *out_scr)

    out = pl.pallas_call(
        kernel,
        grid=(BN, H, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, Tq, D),
                               lambda bn, h, qi, ki: (bn, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BN, H, Sq2, D), q4.dtype),
        scratch_shapes=[pltpu.VMEM((Tq, 128), jnp.float32),
                        pltpu.VMEM((Tq, 128), jnp.float32),
                        pltpu.VMEM((Tq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*args)
    return out[:, :, :Sq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _evo_core(q, k, v, mask_bias, pair_bias, n_rows, scale, block_q,
              block_k, interpret):
    """[B, N, S, H, D] evoformer attention, Pallas fwd / recompute bwd.
    mask_bias [B, N, Sk] or None; pair_bias [B, H, Sq, Sk] or None."""
    B, N, Sq, H, D = q.shape
    to4 = lambda t: t.reshape(B * N, t.shape[2], H, D).swapaxes(1, 2)
    mb2 = (None if mask_bias is None
           else mask_bias.reshape(B * N, mask_bias.shape[-1]))
    o4 = _evo_fwd_pallas(to4(q), to4(k), to4(v), mb2, pair_bias,
                         n_rows=N, scale=scale, block_q=block_q,
                         block_k=block_k, interpret=interpret)
    return o4.swapaxes(1, 2).reshape(B, N, Sq, H, D)


def _evo_ref(q, k, v, mask_bias, pair_bias, scale):
    """Chunked jnp reference (identical math) used for the backward."""
    from ..evoformer_attn import DS4Sci_EvoformerAttention
    B, N, _, H, _ = q.shape
    biases = []
    if mask_bias is not None:
        biases.append(mask_bias[:, :, None, None, :])
    if pair_bias is not None:
        biases.append(pair_bias[:, None])
    return DS4Sci_EvoformerAttention(q, k, v, biases, use_kernel=False)


def _evo_fwd_rule(q, k, v, mask_bias, pair_bias, n_rows, scale, block_q,
                  block_k, interpret):
    out = _evo_core(q, k, v, mask_bias, pair_bias, n_rows, scale, block_q,
                    block_k, interpret)
    return out, (q, k, v, mask_bias, pair_bias)


def _evo_bwd_rule(n_rows, scale, block_q, block_k, interpret, res, g):
    q, k, v, mask_bias, pair_bias = res
    diff = (q, k, v) if mask_bias is None and pair_bias is None else \
        ((q, k, v, pair_bias) if mask_bias is None else
         ((q, k, v, mask_bias) if pair_bias is None else
          (q, k, v, mask_bias, pair_bias)))

    def ref(*args):
        qq, kk, vv = args[:3]
        rest = list(args[3:])
        mb = rest.pop(0) if mask_bias is not None else None
        pb = rest.pop(0) if pair_bias is not None else None
        return _evo_ref(qq, kk, vv, mb, pb, scale)

    _, vjp = jax.vjp(ref, *diff)
    grads = list(vjp(g))
    gq, gk, gv = grads[:3]
    rest = grads[3:]
    gmb = rest.pop(0) if mask_bias is not None else None
    gpb = rest.pop(0) if pair_bias is not None else None
    return gq, gk, gv, gmb, gpb


_evo_core.defvjp(_evo_fwd_rule, _evo_bwd_rule)


def evoformer_flash(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    mask_bias: Optional[jnp.ndarray] = None,
                    pair_bias: Optional[jnp.ndarray] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused evoformer attention: q/k/v [B, N, S, H, D]; ``mask_bias``
    [B, N, Sk] (additive, the reference's [B, N, 1, 1, Sk] squeezed) and
    ``pair_bias`` [B, H, Sq, Sk] (the [B, 1, H, Sq, Sk] squeezed).
    Differentiable; backward recomputes through the chunked jnp path."""
    if interpret is None:
        from . import default_interpret
        interpret = default_interpret()
    B, N, Sq, H, D = q.shape
    scale = 1.0 / (D ** 0.5)
    return _evo_core(q, k, v, mask_bias, pair_bias, N, scale, block_q,
                     block_k, interpret)
