"""The compressed-key plane of a model with block-selected attention
layers (``KVPool.index``): the cache the selection scores against.

One row a ``stride`` consecutive positions, a sparse layer and all kv
heads: the MEAN of those positions' K rows (a compressed key of
``kernel_size = 2 x stride`` keys is the mean of two consecutive rows:
page-aligned, no parameters). ``stride`` divides the pool's block size, so
a group lies in one block and block ``b`` owns rows ``b x block_size /
stride ..`` of the plane: the paged pool's own block table addresses it,
and nothing is allocated beside the pool's blocks.

It is written where K rows are written, FROM them: after a step's store or
a fused loop's flush, :func:`refresh` recomputes the mean of every group
the stored positions touch from the pool's rows and stores it in place (a
group that is not yet full holds a mean nobody reads: a window is scored
only once both its groups lie wholly at or before the query). Inside the
fused loop the pool and the plane are read-only and the loop's own keys
ride the ring: their group sums ride ``RingKV.idx`` (:func:`ring_groups`,
:func:`ring_add`), started from the settled rows of the group the ring
begins in, and :func:`group_scores` lays them over the plane's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .kv_quant import pool_parts


def _k_rows(data, li, src):
    """Layer ``li``'s K rows ``src`` of the pool, gathered from the WHOLE
    array under one flat index (a ``data[li, 0]`` operand would make XLA
    copy that plane out of the pool first)."""
    L, P, slots, W = data.shape
    return data.reshape(L * P * slots, W)[li * (P * slots) + src]


def ring_group_count(steps: int, stride: int) -> int:
    """Groups a loop of ``steps`` positions can touch from any start."""
    return -(-steps // stride) + 1


def _group_rows(tables, group, stride: int, block_size: int):
    """The plane row of each group [S, N] through ``tables`` [S, MAXB]."""
    per = block_size // stride
    blk = jnp.take_along_axis(
        tables, jnp.minimum(group // per, tables.shape[1] - 1), axis=1)
    return blk * per + group % per


def refresh(kv, li, xi, start, count, tables, n: int, block_size: int,
            stride: int):
    """``kv`` with the plane's layer ``xi`` (the sparse layer whose K rows
    are the pool's layer ``li``) recomputed for every group that positions
    ``[start, start + count)`` [S] touch (``n`` the most a sequence stores
    in this program; ``count`` 0: none). Rows of untouched groups go to
    the trash block's."""
    data = pool_parts(kv)[0]
    index = kv.index
    NG = ring_group_count(n, stride)
    i32 = jnp.int32
    group = start[:, None] // stride + jnp.arange(NG, dtype=i32)[None, :]
    live = (group * stride < (start + count)[:, None]) \
        & (count > 0)[:, None]
    row = _group_rows(tables, group, stride, block_size)         # [S, NG]
    src = row[..., None] * stride + jnp.arange(stride, dtype=i32)
    mean = jnp.mean(_k_rows(data, li, src).astype(jnp.float32), axis=2)
    trash = index.shape[1] - 1
    rows = index.shape[1]
    flat = index.reshape(-1, index.shape[-1]).at[
        xi * rows + jnp.where(live, row, trash)].set(mean.astype(index.dtype))
    return kv._replace(index=flat.reshape(index.shape))


def ring_groups(kv_data, pool_layers, start, active, tables, steps: int,
                block_size: int, stride: int):
    """The fused loop's ``RingKV.idx`` at entry: [Ls, S, NG, KV*D]
    float32 zeros but for group 0, which holds the sum of the settled K
    rows of the group ``start`` lies in (positions ``[start // stride x
    stride, start)``)."""
    data = pool_parts(kv_data)[0]
    Ls = kv_data.index.shape[0]
    S = start.shape[0]
    i32 = jnp.int32
    g0 = start // stride
    row = _group_rows(tables, g0[:, None], stride, block_size)[:, 0]
    off = jnp.arange(stride, dtype=i32)[None, :]
    src = row[:, None] * stride + off                            # [S, stride]
    settled = (g0[:, None] * stride + off < start[:, None]) \
        & (active > 0)[:, None]
    rows = jnp.stack([_k_rows(data, li, src) for li in pool_layers]).astype(
        jnp.float32)                                         # [Ls,S,stride,W]
    first = jnp.sum(jnp.where(settled[None, :, :, None], rows, 0.0), axis=2)
    out = jnp.zeros((Ls, S, ring_group_count(steps, stride),
                     data.shape[-1]), jnp.float32)
    return out.at[:, :, 0].set(first)


def ring_add(idx, li: int, k, pos, settled, live, stride: int):
    """``idx`` with this step's K rows ``k`` [S, W] added to layer
    ``li``'s group of position ``pos`` [S] (``settled`` [S]: the loop's
    first position a sequence; idle rows add nothing)."""
    rel = pos // stride - settled // stride
    rel = jnp.clip(rel, 0, idx.shape[2] - 1)
    add = jnp.where(live[:, None], k.astype(jnp.float32), 0.0)
    return idx.at[li, jnp.arange(k.shape[0]), rel].add(add)


def group_scores(kv, li: int, q, tables, block_size: int, stride: int,
                 idx=None, settled=None):
    """Every query head against every group MEAN of its sequence: q [S, C,
    KV, G, D] -> [S, C, KV, G, J] float32, ``J = MAXB x block_size /
    stride``. The plane's rows are gathered a whole block at a time
    through ``tables`` (a block's rows are whole tiles; a row's lanes are
    never re-laid: a ``[.., per x W]`` view of the plane was a copy of all
    of it a layer and step) and scored at their full width against
    queries that are zero outside their kv head's lanes. With ``idx`` (the
    fused loop's), the groups from ``settled // stride`` on read the
    loop's sums. On a TPU at whole-tile shapes the layer does not call
    this: ``sparse_attention.block_select_scores`` walks the live blocks
    inside one kernel, and this is its twin."""
    index = kv.index
    per = block_size // stride
    S, C, KV, G, D = q.shape
    W = index.shape[-1]
    nb = index.shape[1] // per
    M = index.reshape(-1, per, W)[li * nb + tables].reshape(S, -1, W)
    own_lanes = jnp.eye(KV, dtype=q.dtype)[:, None, :, None]
    qw = (q[:, :, :, :, None, :] * own_lanes).reshape(S, C, KV * G, W)
    sc = jnp.einsum("schw,sjw->schj", qw.astype(M.dtype), M,
                    preferred_element_type=jnp.float32)
    if idx is not None:
        sums = idx[li] / stride                              # [S, NG, W]
        rs = jnp.einsum("schw,snw->schn", qw.astype(jnp.float32), sums,
                        precision=jax.lax.Precision.HIGHEST)
        NG = sums.shape[1]
        rel = jnp.arange(sc.shape[-1], dtype=jnp.int32)[None, :] \
            - (settled // stride)[:, None]                   # [S, J]
        # laid over the plane's by a 0/1 table (a gather an element would
        # be 8 M lookups a layer and step)
        place = (rel[:, None, :]
                 == jnp.arange(NG, dtype=jnp.int32)[None, :, None]
                 ).astype(jnp.float32)                       # [S, NG, J]
        own = (rel >= 0) & (rel < NG)
        sc = jnp.where(own[:, None, None, :],
                       jnp.einsum("schn,snj->schj", rs, place,
                                  precision=jax.lax.Precision.HIGHEST), sc)
    return sc.reshape(S, C, KV, G, -1)
