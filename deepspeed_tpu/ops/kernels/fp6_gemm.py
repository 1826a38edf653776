"""Fused FP6 (e3m2) weight-only GEMM — Pallas TPU.

Kernel answer to the reference's FP6 serving path
(``deepspeed/inference/v2/kernels/core_ops/cuda_linear/`` — ~2k LoC of
CUDA that dequantizes 6-bit minifloat weights inside the GEMM): weights
stream through HBM at REAL 6 bits/value (3 byte-planes per 4 codes) and
are decoded to the compute dtype tile-by-tile in VMEM, feeding the MXU —
decode-bound GEMV/GEMM reads 2.67x fewer weight bytes than bf16.

Storage layout (``fp6_gemm_pack``): a [K, N] weight becomes
  bytes3 [3, K, N/4] uint8 — byte planes of the 24-bit word packing the
      4 codes for true columns (j, j+N/4, j+N/2, j+3N/4);
  scale  [4, N/4] f32     — per-column scales, plane-major,
so the kernel's output tile [Mt, 4, Jt] reshapes to the true [M, N]
column order with no gather (row-major (p, j) == column p*N/4+j).

Serving-dtype entry: ``inference/quantization.py`` with
``num_bits: 6`` stores FPQuantizedTensor leaves (generic bit-packed
form); this kernel is the fused fast path for 2-D matmul weights.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_E, _M = 3, 2                      # e3m2
_BIAS = 2 ** (_E - 1) - 1          # 3
_MAX = 2.0 ** _BIAS * (2.0 - 2.0 ** (-_M))      # 14.0


class Fp6GemmWeight(NamedTuple):
    bytes3: jnp.ndarray            # [3, K, N/4] uint8
    scale: jnp.ndarray             # [4, N/4] f32
    shape: Tuple[int, int]         # (K, N)


jax.tree_util.register_pytree_node(
    Fp6GemmWeight,
    lambda t: ((t.bytes3, t.scale), (t.shape,)),
    lambda aux, ch: Fp6GemmWeight(*ch, *aux),
)


def fp6_gemm_pack(w: jnp.ndarray) -> Fp6GemmWeight:
    """Quantize a [K, N] weight (N % 4 == 0) to the GEMM layout with
    per-column scales."""
    from ..fp_quantizer import _minifloat_encode
    K, N = w.shape
    if N % 4:
        raise ValueError(f"N ({N}) must be divisible by 4")
    J = N // 4
    wf = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=0), 1e-12) / _MAX  # [N]
    codes = _minifloat_encode(wf / scale[None, :], _E, _M)  # [K, N] int16
    planes = [codes[:, p * J:(p + 1) * J].astype(jnp.uint32)
              for p in range(4)]
    word = (planes[0] | (planes[1] << 6) | (planes[2] << 12)
            | (planes[3] << 18))                            # [K, J]
    bytes3 = jnp.stack([word & 0xFF, (word >> 8) & 0xFF,
                        (word >> 16) & 0xFF]).astype(jnp.uint8)
    return Fp6GemmWeight(bytes3=bytes3,
                         scale=scale.reshape(4, J), shape=(K, N))


def _decode_plane(word, p):
    """fp6 e3m2 decode of plane ``p`` from 24-bit words (f32 out) — the
    shared minifloat decode (pure jnp, Pallas-safe), so the fused kernel
    can never diverge from fp_dequantize/fp6_gemm_unpack."""
    from ..fp_quantizer import _minifloat_decode
    return _minifloat_decode((word >> (6 * p)) & 0x3F, _E, _M)


def _fp6_kernel(x_ref, b_ref, s_ref, o_ref, a0, a1, a2, a3):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    accs = (a0, a1, a2, a3)

    @pl.when(ki == 0)
    def _init():
        for a in accs:
            a[:] = jnp.zeros(a.shape, a.dtype)

    b = b_ref[...].astype(jnp.int32)                 # [3, Kt, Jt]
    word = b[0] | (b[1] << 8) | (b[2] << 16)         # [Kt, Jt]
    x = x_ref[...]                                   # [Mt, Kt]
    for p in range(4):
        w = _decode_plane(word, p) * s_ref[p:p + 1, :]
        accs[p][:] = accs[p][:] + jax.lax.dot_general(
            x, w.astype(x.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        for p in range(4):
            o_ref[:, p, :] = accs[p][:].astype(o_ref.dtype)


def _pick_tile(dim: int, prefs=(512, 256, 128)) -> int:
    for t in prefs:
        if dim % t == 0:
            return t
    return 0


def fp6_matmul(x: jnp.ndarray, fw: Fp6GemmWeight,
               interpret=None) -> jnp.ndarray:
    """``x @ W`` with W stored fp6-packed. x: [..., K] in bf16/f32.
    Falls back to full dequant + XLA dot when K or N/4 has no
    MXU-aligned tile divisor."""
    if interpret is None:
        from . import default_interpret
        interpret = default_interpret()
    K, N = fw.shape
    J = N // 4
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    Kt, Jt = _pick_tile(K), _pick_tile(J)
    if not Kt or not Jt or M == 0:
        return (x @ fp6_gemm_unpack(fw).astype(x.dtype)).reshape(
            *lead, N)
    Mt = min(256, ((M + 7) // 8) * 8)
    M2 = ((M + Mt - 1) // Mt) * Mt
    if M2 != M:
        x2 = jnp.pad(x2, ((0, M2 - M), (0, 0)))

    out = pl.pallas_call(
        _fp6_kernel,
        grid=(M2 // Mt, J // Jt, K // Kt),
        in_specs=[
            pl.BlockSpec((Mt, Kt), lambda mi, ji, ki: (mi, ki)),
            pl.BlockSpec((3, Kt, Jt), lambda mi, ji, ki: (0, ki, ji)),
            pl.BlockSpec((4, Jt), lambda mi, ji, ki: (0, ji)),
        ],
        out_specs=pl.BlockSpec((Mt, 4, Jt),
                               lambda mi, ji, ki: (mi, 0, ji)),
        out_shape=jax.ShapeDtypeStruct((M2, 4, J), x.dtype),
        scratch_shapes=[pltpu.VMEM((Mt, Jt), jnp.float32)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x2, fw.bytes3, fw.scale)
    # [M, 4, J] row-major == true column order p*J + j
    return out.reshape(M2, N)[:M].reshape(*lead, N)


def fp6_gemm_unpack(fw: Fp6GemmWeight) -> jnp.ndarray:
    """Full f32 decode of the GEMM layout (fallback / reference)."""
    b = fw.bytes3.astype(jnp.int32)
    word = b[0] | (b[1] << 8) | (b[2] << 16)         # [K, J]
    cols = [_decode_plane(word, p) * fw.scale[p][None, :]
            for p in range(4)]
    return jnp.concatenate(cols, axis=1)             # [K, N]
