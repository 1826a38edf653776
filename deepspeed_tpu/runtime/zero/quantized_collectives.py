"""ZeRO++ quantized collectives — manual-mode qwZ / qgZ.

Capability parity with the reference's ZeRO++ comm compression
(``runtime/zero/partition_parameters.py`` CUDAQuantizer allgather path for
quantized weights, ``runtime/comm/coalesced_collectives.py:31``
``all_to_all_quant_reduce`` for quantized gradients, kernels in
``csrc/quantization/`` — SURVEY.md §2.3 "ZeRO++ features" row).

Design. Under plain pjit, ZeRO's gather/reduce collectives are placed by XLA
and always run at full precision — there is no seam to compress them. So
ZeRO++ runs the micro-gradient computation in **manual mode**: a
``shard_map`` over the ``data`` axis (all other mesh axes stay automatic),
inside which

  - every data-sharded param shard goes through :func:`gather_param` — a
    per-device custom-VJP whose forward is an int8/int4 ``all_gather``
    (**qwZ**) and whose backward is a quantized all-to-all + local
    dequant-sum reduce-scatter (**qgZ**, the reference's single-hop
    dequant-reduce-requant schedule) or a plain ``psum_scatter``;
  - replicated params go through :func:`replicate_param`, whose backward is
    the DP-grad ``psum`` the automatic partitioner would have inserted.

This is also the framework's manual-collective escape hatch (SURVEY.md §7
hard part 1) — the same seam serves explicit comm scheduling at scale.

Quantization granularity is a per-row (last-dim) symmetric scale; int4 packs
two nibbles per byte when the row length is even.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...ops.kernels.quantization import (
    pack_int4, sym_quantize_rowwise, unpack_int4)


def shard_map(f, mesh, in_specs, out_specs, axis_names=()):
    """shard_map with partial-manual axes and no replication check."""
    from ...utils.jax_compat import shard_map as _sm
    return _sm(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
               check_vma=False, axis_names=axis_names)


# --------------------------------------------------------------------------- #
# comm-precision helpers
# --------------------------------------------------------------------------- #


def _quant_for_comm(x: jnp.ndarray, bits: int):
    q, scale = sym_quantize_rowwise(x, bits)
    packed = bits == 4 and x.shape[-1] % 2 == 0
    if packed:
        q = pack_int4(q)
    return q, scale, packed


def _dequant_from_comm(q, scale, packed, dtype):
    if packed:
        q = unpack_int4(q)
    return (q.astype(jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------- #
# per-device collectives (to be used INSIDE shard_map manual regions)
# --------------------------------------------------------------------------- #


@functools.lru_cache(maxsize=None)
def _make_param_gather(dim: int, axes: Tuple[str, ...], world: int,
                       weight_bits: Optional[int], grad_bits: Optional[int]):
    """custom-VJP gather of a param shard along ``dim`` over manual ``axes``.

    fwd: (quantized) all_gather — qwZ when weight_bits set.
    bwd: per-device grad contributions reduce-scattered — quantized
         all-to-all + dequant-sum when grad_bits set (qgZ), else psum_scatter.
    """

    def _gather(local):
        if weight_bits is None:
            return jax.lax.all_gather(local, axes, axis=dim, tiled=True)
        q, scale, packed = _quant_for_comm(local, weight_bits)
        # non-tiled gather keeps a leading world axis so per-row scales stay
        # aligned with their value rows for any rank (incl. 1-D params)
        gq = jax.lax.all_gather(q, axes)               # (W, *q.shape)
        gs = jax.lax.all_gather(scale, axes)           # (W, *scale.shape)
        deq = _dequant_from_comm(gq, gs, packed, local.dtype)  # (W, *local)
        out = jnp.moveaxis(deq, 0, dim)
        return out.reshape(local.shape[:dim] +
                           (world * local.shape[dim],) +
                           local.shape[dim + 1:])

    def _reduce_scatter(ct):
        if grad_bits is None:
            return jax.lax.psum_scatter(ct, axes, scatter_dimension=dim,
                                        tiled=True)
        shape = ct.shape
        chunk = shape[dim] // world
        parts = jnp.moveaxis(
            ct.reshape(shape[:dim] + (world, chunk) + shape[dim + 1:]),
            dim, 0)                                  # (world, ..., chunk, ...)
        q, scale, packed = _quant_for_comm(parts, grad_bits)
        q = jax.lax.all_to_all(q, axes, split_axis=0, concat_axis=0)
        scale = jax.lax.all_to_all(scale, axes, split_axis=0, concat_axis=0)
        deq = _dequant_from_comm(q, scale, packed, jnp.float32)
        return deq.sum(axis=0).astype(ct.dtype)      # (..., chunk, ...)

    @jax.custom_vjp
    def gather(x):
        return _gather(x)

    gather.defvjp(lambda x: (_gather(x), None),
                  lambda _, ct: (_reduce_scatter(ct),))
    return gather


@functools.lru_cache(maxsize=None)
def _make_replicated_prep(axes: Tuple[str, ...]):
    """Identity with bwd = psum over the manual axes: the DP gradient
    reduction for params that ZeRO keeps replicated (persistence threshold)."""

    @jax.custom_vjp
    def prep(x):
        return x

    prep.defvjp(lambda x: (x, None),
                lambda _, ct: (jax.lax.psum(ct, axes),))
    return prep


def _manual_entry(spec: Optional[P], manual_axes: Sequence[str]):
    """(dim, axes∩manual) of the first dim sharded over a manual axis."""
    if spec is None:
        return None
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        hit = tuple(a for a in axes if a in manual_axes)
        if hit:
            if len(hit) != len(axes):
                return "mixed"                       # manual+auto on one dim
            return dim, hit
    return None


def strip_to_manual(spec: Optional[P], manual_axes: Sequence[str],
                    ndim: int) -> P:
    """Project a PartitionSpec onto the manual axes (for shard_map in_specs);
    auto axes are left unmentioned and stay compiler-managed."""
    if spec is None:
        return P()
    entries = list(spec) + [None] * (ndim - len(spec))
    out = []
    for entry in entries:
        if entry is None:
            out.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        hit = tuple(a for a in axes if a in manual_axes)
        if len(hit) != len(axes):
            # dim sharded jointly over manual+auto axes: leave it fully
            # automatic (prep_params refuses such leaves anyway)
            out.append(None)
        else:
            out.append(hit[0] if len(hit) == 1 else tuple(hit))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def prep_params(params_local, specs, manual_axes: Tuple[str, ...], world: int,
                weight_bits: Optional[int], grad_bits: Optional[int]):
    """Inside the manual region: gather every sharded param (qwZ fwd / qgZ
    bwd) and attach the DP-psum backward to replicated ones. Returns the
    full-parameter tree the model computes with."""

    def leaf(x, spec):
        entry = _manual_entry(spec if isinstance(spec, P) else None,
                              manual_axes)
        if entry == "mixed":
            raise ValueError(
                f"param dim sharded over manual+auto axes jointly ({spec}); "
                "ZeRO++ manual mode requires zero axes on their own dim")
        if entry is None:
            return _make_replicated_prep(manual_axes)(x)
        dim, axes = entry
        return _make_param_gather(dim, axes, world, weight_bits, grad_bits)(x)

    return jax.tree_util.tree_map(
        leaf, params_local, specs, is_leaf=lambda s: isinstance(s, P))
