"""Block-selected attention over the paged pool (Pallas TPU): a query reads
a LIST of selected key blocks, not every live tile.

The selection is ``models/minicpm_sala.select_blocks`` (compressed scores,
group sum, window maximum, forced blocks, top-k); this module holds what
reads the pool and the compressed-key plane under it, each form with its
``jax.numpy`` twin (what every CPU run takes, and what tier-1 holds the
kernel to):

* :func:`block_select_scores` -- the selection's block scores of a tile
  of queries: a sequence's live plane blocks walked once through the
  block table, everything up to the forced blocks in VMEM (its twin is
  ``minicpm_sala.block_scores`` over ``index_plane.group_scores``); the
  top-k of them stays ``jax.numpy``.
* :func:`sparse_decode_attention` -- a pure-decode call. Per (sequence, kv
  head) a sorted list of ``K`` selection blocks of ``sb`` rows; the kernel
  copies exactly those rows of K and of V (one kv head's ``D`` lanes of
  each, by manual DMA through the rows the caller worked out from the block
  table, the next grid step's in flight while this one computes), the kv
  head's group of query rows against them under a position mask, online
  softmax over the list's chunks, and the fused loop's ring as one more
  round (the ring's rows are the newest positions, always inside the
  selection's forced local window). It streams ``K x sb`` rows a sequence,
  kv head and layer WHATEVER the context. The grid, the scratch slots and
  the ring round are ``paged_attention._decode_kernel``'s.
* :func:`sparse_prefill_attention` -- a prefill chunk, every query with its
  own selection. A first, exact form: for a tile of queries the kernel
  visits the UNION of the pool blocks any query (of either kv head) of the
  tile selected, each under the per-query mask (expanded from the [queries,
  blocks] selection by one small matmul against a 0/1 table), and skips
  pool blocks no query of the tile selected; it returns the blocks visited
  beside the blocks selected. A query below ``dense_len`` selects every
  block at or before its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import decode_group

_NEG_INF = float("-inf")
_LANES = 128
_FAR = jnp.iinfo(jnp.int32).max
#: pool rows of one (sequence, kv head) a grid step of the decode kernel
#: holds: a chunk of the selection's list
_DECODE_CHUNK_ROWS = 512
_VMEM_MARGIN = 8 << 20


def decode_uses_kernel(head_dim: int, sel_block: int) -> bool:
    """Whether a pure-decode call of this shape runs the Pallas kernel:
    on a TPU, at heads of whole 128-lane groups and selection blocks of
    whole sublane tiles (platform and shape decide, nothing a user
    sets)."""
    return jax.default_backend() == "tpu" and head_dim % _LANES == 0 \
        and sel_block % 16 == 0


def selection_rows(blocks, tables, pool_block: int, sel_block: int,
                   trash_row: int):
    """blocks [S, KV, K] (ascending, -1 unused) -> (rows [S, KV, K] the
    pool row of each block's first row through ``tables`` [S, MAXB], an
    unused entry the trash block's; colpos [S, KV, K * sel_block] the
    position of every listed row, an unused entry's past any length)."""
    first = jnp.maximum(blocks, 0) * sel_block
    blk = jnp.take_along_axis(
        tables[:, None, :], jnp.minimum(first // pool_block,
                                        tables.shape[1] - 1), axis=2)
    rows = jnp.where(blocks >= 0, blk * pool_block + first % pool_block,
                     trash_row)
    col = first[..., None] + jnp.arange(sel_block, dtype=jnp.int32)
    col = jnp.where(blocks[..., None] >= 0, col, _FAR)
    return rows.astype(jnp.int32), col.reshape(*blocks.shape[:2], -1)


def sparse_decode_reference(q, pool, layer: int, rows, colpos, start_pos,
                            lens, *, sel_block: int, sm_scale: float,
                            ring=None, ring_count=None, ring_layer=None):
    """The twin: gather the listed rows, mask, softmax. q [S, H, D]; pool
    [L, 2, slots, KV*D]; rows / colpos as :func:`selection_rows`;
    ``lens`` [S] the settled rows (0: idle, emits zeros); the ring [R, L',
    2, S, KV*D] holds the ``ring_count`` newest positions. Returns [S, H,
    D] float32."""
    S, H, D = q.shape
    KV = rows.shape[1]
    G = H // KV
    idx = (rows[..., None] + jnp.arange(sel_block, dtype=jnp.int32)
           ).reshape(S, KV, -1)                              # [S, KV, W]

    def plane(x):
        return jnp.stack([pool[layer, x][idx[:, kv]][..., kv * D:(kv + 1) * D]
                          for kv in range(KV)], axis=1).astype(jnp.float32)
    kk, vv = plane(0), plane(1)                              # [S, KV, W, D]
    mask = (colpos < lens[:, None, None]) \
        & (colpos <= start_pos[:, None, None])
    if ring is not None:
        R = ring.shape[0]
        rl = layer if ring_layer is None else ring_layer

        def rplane(x):
            return jnp.moveaxis(ring[:, rl, x], 0, 1).reshape(
                S, R, KV, D).swapaxes(1, 2).astype(jnp.float32)
        kk = jnp.concatenate([kk, rplane(0)], axis=2)
        vv = jnp.concatenate([vv, rplane(1)], axis=2)
        rmask = (jnp.arange(R) < ring_count)[None, None, :] \
            & (lens > 0)[:, None, None]
        mask = jnp.concatenate(
            [mask, jnp.broadcast_to(rmask, (S, KV, R))], axis=2)
    qg = q.reshape(S, KV, G, D).astype(jnp.float32)
    sc = jnp.einsum("skgd,skwd->skgw", qg, kk) * sm_scale
    sc = jnp.where(mask[:, :, None, :], sc, _NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)                      # idle rows
    return jnp.einsum("skgw,skwd->skgd", p, vv).reshape(S, H, D)


def _sparse_decode_kernel(rows_ref, starts_ref, lens_ref, rcount_ref,
                          layer_ref, q_ref, col_ref, kp_hbm, vp_hbm, *rest,
                          G, KV, D, H, Hp, K, CB, sb, sm_scale, R):
    """Grid step (i, c): the G sequences of group i over chunk c of their
    lists (CB blocks of sb rows a kv head)."""
    del vp_hbm                      # the one pool, read through kp_hbm
    rest = list(rest)
    rk_ref = rv_ref = None
    if R is not None:
        rk_ref, rv_ref = rest[:2]
        rest = rest[2:]
    o_ref, k_scr, v_scr, sems, m_scr, l_scr, acc_scr = rest
    i = pl.program_id(0)
    c = pl.program_id(1)
    ng = pl.num_programs(0)
    NCH = K // CB
    u = i * NCH + c
    slot = jax.lax.rem(u, 2)
    grp = H // KV
    W = CB * sb
    M = G * Hp
    KVD = KV * D

    def copies(gi, ci, sl, wait):
        def per_seq(g, carry):
            s = gi * G + g

            @pl.when(lens_ref[s] > 0)
            def _live():
                for kv in range(KV):
                    def per_block(b, carry, kv=kv):
                        src = pl.multiple_of(
                            rows_ref[(s * KV + kv) * K + ci * CB + b], sb)
                        dst = pl.multiple_of(
                            ((g * KV + kv) * CB + b) * sb, sb)
                        for x, scr in ((0, k_scr), (1, v_scr)):
                            cp = pltpu.make_async_copy(
                                kp_hbm.at[layer_ref[0], x, pl.ds(src, sb),
                                          pl.ds(kv * D, D)],
                                scr.at[sl, pl.ds(dst, sb)], sems.at[sl, x])
                            cp.wait() if wait else cp.start()
                        return carry
                    jax.lax.fori_loop(0, CB, per_block, 0)
            return carry
        jax.lax.fori_loop(0, G, per_seq, 0)

    @pl.when(u == 0)
    def _first():
        # V's scratch must stay finite under p == 0: an idle sequence
        # copies nothing into its rows
        k_scr[...] = jnp.zeros(k_scr.shape, k_scr.dtype)
        v_scr[...] = jnp.zeros(v_scr.shape, v_scr.dtype)
        copies(i, c, slot, wait=False)

    @pl.when(u + 1 < ng * NCH)
    def _next():
        last = c + 1 == NCH
        copies(jnp.where(last, i + 1, i), jnp.where(last, 0, c + 1),
               1 - slot, wait=False)

    copies(i, c, slot, wait=True)

    @pl.when(c == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    def online(sc, pv_of):
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_next = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_next), m_next, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe),
                          0.0)
        p = jnp.exp(sc - m_safe)
        l_scr[...] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + pv_of(p)

    parts = []
    for g in range(G):
        s = i * G + g
        for kv in range(KV):
            kb = k_scr[slot, pl.ds(((g * KV + kv) * CB) * sb, W), :]
            qh = q_ref[g][kv * grp:(kv + 1) * grp, kv * D:(kv + 1) * D]
            sc = jax.lax.dot_general(
                qh, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [grp, W]
            col = col_ref[g][kv:kv + 1, :]                       # [1, W]
            live = jnp.logical_and(col < lens_ref[s], col <= starts_ref[s])
            parts.append(jnp.where(live, sc, _NEG_INF))
        if Hp > H:
            parts.append(jnp.full((Hp - H, W), _NEG_INF, jnp.float32))

    def pool_pv(p):
        outs = []
        for g in range(G):
            for kv in range(KV):
                r0 = g * Hp + kv * grp
                vb = v_scr[slot, pl.ds(((g * KV + kv) * CB) * sb, W), :]
                pv = jax.lax.dot_general(
                    p[r0:r0 + grp].astype(vb.dtype), vb,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)         # [grp, D]
                # into the kv head's own lanes of the [., KV*D] rows
                outs.append(jnp.concatenate(
                    [jnp.zeros((grp, kv * D), jnp.float32)] * (kv > 0)
                    + [pv]
                    + [jnp.zeros((grp, (KV - 1 - kv) * D), jnp.float32)]
                    * (kv < KV - 1), axis=1))
            if Hp > H:
                outs.append(jnp.zeros((Hp - H, KVD), jnp.float32))
        return jnp.concatenate(outs, axis=0)
    online(jnp.concatenate(parts, axis=0), pool_pv)

    @pl.when(c == NCH - 1)
    def _finish():
        if R is not None:
            # paged_attention._decode_kernel's ring round: ring row r of
            # sequence g sits at plane row r * G + g
            qa = q_ref[...].reshape(M, KVD)
            rk = rk_ref[...].reshape(R * G, KVD)
            rv = rv_ref[...].reshape(R * G, KVD)
            rsc = jax.lax.dot_general(
                qa, rk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            cc = jax.lax.broadcasted_iota(jnp.int32, (M, R * G), 1)
            r = cc // G
            own = (cc - r * G) == jax.lax.broadcasted_iota(
                jnp.int32, (M, R * G), 0) // Hp
            len_rows = jnp.concatenate(
                [jnp.full((Hp, 1), lens_ref[i * G + g], jnp.int32)
                 for g in range(G)], axis=0)
            rmask = jnp.logical_and(
                jnp.logical_and(own, r < rcount_ref[0]), len_rows > 0)
            online(jnp.where(rmask, rsc, _NEG_INF),
                   lambda p: jax.lax.dot_general(
                       p.astype(rv.dtype), rv, (((1,), (0,)), ((), ())),
                       preferred_element_type=jnp.float32))
        l = l_scr[:, :1]
        out = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
        for g in range(G):
            o_ref[g] = out[g * Hp:g * Hp + H].astype(o_ref.dtype)


def sparse_decode_call(qw, kp, vp, ring, rows, colpos, start_pos, lens,
                       ring_count, layers, *, H, KV, D, sb, sm_scale,
                       out_dtype, interpret):
    """The sparse decode kernel's one Mosaic call
    (``_sparse_decode_call`` is its jitted, named form)."""
    S, Hp, KVD = qw.shape
    K = rows.shape[2]
    CB = max(1, min(K, _DECODE_CHUNK_ROWS // sb))
    while K % CB:
        CB -= 1
    NCH = K // CB
    W = CB * sb
    G = decode_group(S)
    R = None if ring is None else ring.shape[0]
    kernel = functools.partial(
        _sparse_decode_kernel, G=G, KV=KV, D=D, H=H, Hp=Hp, K=K, CB=CB,
        sb=sb, sm_scale=sm_scale, R=R)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((G, Hp, KVD), lambda i, c, *_: (i, 0, 0)),
                pl.BlockSpec((G, KV, W), lambda i, c, *_: (i, 0, c)),
                hbm, hbm]
    operands = [qw, colpos, kp, vp]
    if R is not None:
        in_specs += [
            pl.BlockSpec((R, None, None, G, KVD),
                         lambda i, c, *refs, x=x: (0, refs[4][1], x, i, 0))
            for x in (0, 1)]
        operands += [ring, ring]
    item = kp.dtype.itemsize
    vmem = 4 * G * KV * W * D * item + _VMEM_MARGIN
    if R is not None:
        vmem += 4 * R * G * KVD * ring.dtype.itemsize
    M = G * Hp
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(S // G, NCH), in_specs=in_specs,
        out_specs=pl.BlockSpec((G, H, KVD), lambda i, c, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, G * KV * W, D), kp.dtype),
            pltpu.VMEM((2, G * KV * W, D), kp.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((M, _LANES), jnp.float32),
            pltpu.VMEM((M, _LANES), jnp.float32),
            pltpu.VMEM((M, KVD), jnp.float32)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, KVD), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret, name="sparse_decode",
    )(rows.reshape(-1), start_pos, lens, ring_count, layers, *operands)


# jitted under its own name: every sparse layer of a program (and every
# step of its scan) calls ONE traced, once-lowered function, and the device
# trace names the Mosaic call after it
_sparse_decode_call = jax.jit(sparse_decode_call, static_argnames=(
    "H", "KV", "D", "sb", "sm_scale", "out_dtype", "interpret"))


def sparse_decode_attention(q, pool, layer: int, rows, colpos, start_pos,
                            lens, *, sel_block: int, sm_scale: float,
                            ring=None, ring_count=None, ring_layer=None,
                            interpret: bool = False):
    """The kernel; arguments and result as :func:`sparse_decode_reference`
    (the result in ``q``'s dtype). q joins the pool's dtype; the pool and
    the ring ride in whole."""
    S, H, D = q.shape
    KV = rows.shape[1]
    g = H // KV
    KVD = KV * D
    sel = (jnp.arange(KV)[None, :] == (jnp.arange(H) // g)[:, None])
    qw = (q[:, :, None, :] * sel[None, :, :, None].astype(q.dtype)
          ).reshape(S, H, KVD).astype(pool.dtype)
    Hp = -(-H // 16) * 16
    if Hp != H:
        qw = jnp.pad(qw, ((0, 0), (0, Hp - H), (0, 0)))
    has_ring = ring is not None
    rl = layer if ring_layer is None else ring_layer
    out = _sparse_decode_call(
        qw, pool, pool, ring, rows, colpos, start_pos.astype(jnp.int32),
        lens.astype(jnp.int32),
        (jnp.reshape(ring_count, (1,)).astype(jnp.int32) if has_ring
         else jnp.zeros((1,), jnp.int32)),
        jnp.asarray([layer, rl if has_ring else 0], jnp.int32),
        H=H, KV=KV, D=D, sb=int(sel_block), sm_scale=float(sm_scale),
        out_dtype=jnp.dtype(q.dtype), interpret=bool(interpret))
    head_win = (jnp.arange(H) // g)[:, None] * D + jnp.arange(D)[None, :]
    return jnp.take_along_axis(out, head_win[None], axis=2)


# --------------------------------------------------------------------- #
# prefill: a selection a query
# --------------------------------------------------------------------- #


def sparse_prefill_reference(q, pool, layer: int, tables, start_pos,
                             seq_lens, chosen, *, block_size: int,
                             sel_block: int, sm_scale: float):
    """The twin: the whole context gathered through the table, every
    query under its own mask. q [S, C, H, D]; chosen [S, C, KV, NB] bool
    over selection blocks of ``sel_block`` rows (NB x sel_block =
    MAXB x block_size). Returns (o [S, C, H, D] float32, counts [2]
    int32: blocks selected, blocks visited -- here the same)."""
    S, C, H, D = q.shape
    KV = chosen.shape[2]
    G = H // KV
    T = tables.shape[1] * block_size
    j = jnp.arange(T, dtype=jnp.int32)
    idx = tables[:, j // block_size] * block_size + j % block_size
    kk = pool[layer, 0][idx].reshape(S, T, KV, D).astype(jnp.float32)
    vv = pool[layer, 1][idx].reshape(S, T, KV, D).astype(jnp.float32)
    pos = start_pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    mask = chosen[..., j // sel_block] \
        & (j[None, None, :] <= pos[..., None])[:, :, None, :] \
        & (j[None, :] < seq_lens[:, None])[:, None, None, :]
    qg = q.reshape(S, C, KV, G, D).astype(jnp.float32)
    sc = jnp.einsum("sckgd,stkd->sckgt", qg, kk) * sm_scale
    p = jax.nn.softmax(jnp.where(mask[:, :, :, None, :], sc, _NEG_INF), -1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    o = jnp.einsum("sckgt,stkd->sckgd", p, vv).reshape(S, C, H, D)
    n = _selected_count(chosen, pos, seq_lens, sel_block)
    return o, jnp.stack([n, n])


def _selected_count(chosen, pos, seq_lens, sel_block):
    """Blocks the real queries selected at or before their own."""
    b = jnp.arange(chosen.shape[-1], dtype=jnp.int32)
    real = (pos < seq_lens[:, None])[:, :, None, None]
    at = (b[None, None, :] <= (pos // sel_block)[..., None])[:, :, None, :]
    return jnp.sum(chosen & at & real, dtype=jnp.int32)


def _sparse_prefill_kernel(starts_ref, fetch_ref, logical_ref, nvis_ref,
                           lens_ref, q_ref, sel_ref, k_ref, v_ref, o_ref,
                           m_scr, l_scr, acc_scr, *, pbs, sb, Cb, nCb, H,
                           KV, D, sm_scale):
    s = pl.program_id(0)
    qc = pl.program_id(1)
    j = pl.program_id(2)
    nb = pl.num_programs(2)
    sq = s * nCb + qc
    g = H // KV
    r = pbs // sb

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[:] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[:] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    @pl.when(j < nvis_ref[sq])
    def _compute():
        jv = logical_ref[sq * nb + j]
        q = q_ref[0]                                   # [H*Cb, D]
        kb, vb = k_ref[0], v_ref[0]                    # [pbs, KV*D]
        sel = sel_ref[0, 0]                            # [KV*Cb, NBp]
        NBp = sel.shape[1]
        # the selection's columns of this pool block, a 0/1 table
        table = (jax.lax.broadcasted_iota(jnp.int32, (NBp, pbs), 0)
                 == jv * r + jax.lax.broadcasted_iota(
                     jnp.int32, (NBp, pbs), 1) // sb).astype(sel.dtype)
        picked = jax.lax.dot_general(
            sel, table, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [KV*Cb, pbs]
        c_of_row = jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (g * Cb, pbs), 0), Cb)
        pos_q = starts_ref[s] + qc * Cb + c_of_row
        col = jv * pbs + jax.lax.broadcasted_iota(
            jnp.int32, (g * Cb, pbs), 1)
        causal = jnp.logical_and(col <= pos_q, col < lens_ref[s])
        parts = []
        for kvh in range(KV):
            rows = slice(kvh * g * Cb, (kvh + 1) * g * Cb)
            sc = jax.lax.dot_general(
                q[rows], kb[:, kvh * D:(kvh + 1) * D],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            own = jnp.broadcast_to(
                picked[kvh * Cb:(kvh + 1) * Cb][None], (g, Cb, pbs)
            ).reshape(g * Cb, pbs) > 0.5
            parts.append(jnp.where(jnp.logical_and(causal, own), sc,
                                   _NEG_INF))
        scores = jnp.concatenate(parts, axis=0)        # [H*Cb, pbs]
        m_prev, l_prev = m_scr[:], l_scr[:]
        m_next = jnp.maximum(m_prev,
                             jnp.max(scores, axis=1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_next), m_next, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe),
                          0.0)
        p = jnp.exp(jnp.where(jnp.isfinite(scores),
                              scores - m_safe[:, :1], _NEG_INF))
        l_scr[:] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = m_next
        pv = jnp.concatenate([
            jax.lax.dot_general(
                p[kvh * g * Cb:(kvh + 1) * g * Cb].astype(vb.dtype),
                vb[:, kvh * D:(kvh + 1) * D], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            for kvh in range(KV)], axis=0)             # [H*Cb, D]
        acc_scr[:] = acc_scr[:] * alpha[:, :1] + pv

    @pl.when(j == nb - 1)
    def _finish():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def sparse_prefill_attention(q, pool, layer: int, tables, start_pos,
                             seq_lens, chosen, *, block_size: int,
                             sel_block: int, sm_scale: float,
                             interpret: bool = False):
    """The kernel; arguments and results as
    :func:`sparse_prefill_reference` (o in ``q``'s dtype, and the second
    count is what the kernel's tiles visited: for each real query and kv
    head the selection blocks of every pool block its tile walked)."""
    S, C, H, D = q.shape
    KV = chosen.shape[2]
    L, planes, slots, KVD = pool.shape
    bs = block_size
    pbs = next(d for d in range(min(bs, 256), 0, -1)
               if bs % d == 0 and d % sel_block == 0)
    factor = bs // pbs
    r = pbs // sel_block
    maxb_v = tables.shape[1] * factor
    NB = chosen.shape[-1]
    assert NB == maxb_v * r, (NB, maxb_v, r)
    # the query tile, by paged_attention's own budget
    kv_tile_bytes = 4 * pbs * KVD * 2
    row_bytes = (2 * _LANES + D) * 4 + 4 * D * q.dtype.itemsize
    row_budget = max(1 << 20, 8 * (1 << 20) - kv_tile_bytes)
    Cb = min(C, max(8, (row_budget // (H * row_bytes)) // 8 * 8))
    nCb = -(-C // Cb)
    Cpad = nCb * Cb
    pos = start_pos[:, None] + jnp.arange(Cpad, dtype=jnp.int32)[None, :]
    real = jnp.arange(Cpad)[None, :] < (seq_lens - start_pos)[:, None]
    if Cpad != C:
        chosen = jnp.pad(chosen, ((0, 0), (0, Cpad - C), (0, 0), (0, 0)))
    # a tile's visit list: pool blocks any real query of it (either kv
    # head) selected, at or before the tile's last position
    tile = (chosen & real[:, :, None, None]).reshape(
        S, nCb, Cb, KV, maxb_v, r).any(axis=(2, 3, 5))       # [S,nCb,NBv]
    jv = jnp.arange(maxb_v, dtype=jnp.int32)
    last = start_pos[:, None] + (jnp.arange(nCb)[None, :] + 1) * Cb - 1
    tile = tile & (jv[None, None, :] * pbs <= last[..., None]) \
        & (jv[None, None, :] * pbs < seq_lens[:, None, None])
    nvis = jnp.sum(tile, axis=-1, dtype=jnp.int32)           # [S, nCb]
    order = jnp.argsort(~tile, axis=-1, stable=True).astype(jnp.int32)
    # dead steps revisit the last block visited: no new DMA
    logical = jnp.take_along_axis(
        order, jnp.minimum(jv[None, None, :],
                           jnp.maximum(nvis[..., None] - 1, 0)), axis=-1)
    fetch = jnp.take_along_axis(
        tables.astype(jnp.int32)[:, None, :], logical // factor,
        axis=2) * factor + logical % factor
    NBp = -(-NB // _LANES) * _LANES
    sel = chosen.reshape(S, nCb, Cb, KV, NB).swapaxes(2, 3).reshape(
        S, nCb, KV * Cb, NB)
    sel = jnp.pad(sel, ((0, 0),) * 3 + ((0, NBp - NB),)).astype(pool.dtype)
    qw = q.swapaxes(1, 2).astype(pool.dtype)                 # [S, H, C, D]
    if Cpad != C:
        qw = jnp.pad(qw, ((0, 0), (0, 0), (0, Cpad - C), (0, 0)))
    qw = qw.reshape(S, H, nCb, Cb, D).swapaxes(1, 2).reshape(
        S, nCb * H * Cb, D)

    def kv_index(s, qc, j, *pref, x):
        return (layer, x, pref[1][(s * nCb + qc) * maxb_v + j], 0, 0)
    pool5 = pool.reshape(L, planes, slots // pbs, pbs, KVD)
    kernel = functools.partial(
        _sparse_prefill_kernel, pbs=pbs, sb=sel_block, Cb=Cb, nCb=nCb, H=H,
        KV=KV, D=D, sm_scale=float(sm_scale))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(S, nCb, maxb_v),
        in_specs=[
            pl.BlockSpec((1, H * Cb, D), lambda s, qc, j, *_: (s, qc, 0)),
            pl.BlockSpec((1, 1, KV * Cb, NBp),
                         lambda s, qc, j, *_: (s, qc, 0, 0)),
            pl.BlockSpec((None, None, 1, pbs, KVD),
                         functools.partial(kv_index, x=0)),
            pl.BlockSpec((None, None, 1, pbs, KVD),
                         functools.partial(kv_index, x=1))],
        out_specs=pl.BlockSpec((1, H * Cb, D),
                               lambda s, qc, j, *_: (s, qc, 0)),
        scratch_shapes=[pltpu.VMEM((H * Cb, _LANES), jnp.float32),
                        pltpu.VMEM((H * Cb, _LANES), jnp.float32),
                        pltpu.VMEM((H * Cb, D), jnp.float32)])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qw.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name="sparse_prefill",
    )(start_pos.astype(jnp.int32), fetch.reshape(-1), logical.reshape(-1),
      nvis.reshape(-1), seq_lens.astype(jnp.int32), qw, sel, pool5, pool5)
    out = out.reshape(S, nCb, H, Cb, D).swapaxes(1, 2).reshape(
        S, H, Cpad, D)[:, :, :C]
    queries = jnp.sum(real.reshape(S, nCb, Cb), axis=-1, dtype=jnp.int32)
    visited = jnp.sum(nvis * queries, dtype=jnp.int32) * (r * KV)
    return jnp.moveaxis(out, 1, 2), jnp.stack(
        [_selected_count(chosen[:, :C], pos[:, :C], seq_lens, sel_block),
         visited])


# --------------------------------------------------------------------- #
# the selection: a tile of queries against the compressed-key plane
# --------------------------------------------------------------------- #

#: a lane tile of the selection kernel: 16 query heads of a kv head x 8
#: COLUMNS, lane ``h * 8 + column``; a column is one (query, kv head)
_SEL_HEADS = 16
_SEL_COLS = 8
_SEL_LANES = _SEL_HEADS * _SEL_COLS
#: groups a pass of the kernel's loops holds
_SEL_CHUNK = 256
#: output lanes: the columns of 16 consecutive tiles
_SEL_SUPER = _LANES // _SEL_COLS


def select_uses_kernel(head_dim: int, heads_per_kv: int, kv_heads: int,
                       queries: int, pool_block: int, stride: int,
                       sel_block: int) -> bool:
    """Whether a selection of this shape scores through the Pallas kernel
    (:func:`block_select_scores`) on this platform: the sparse kernels'
    own rule, a kv head's query heads inside one lane tile, one query a
    sequence or whole tiles of eight, plane blocks of whole bfloat16
    tiles."""
    return decode_uses_kernel(head_dim, sel_block) \
        and select_fits(heads_per_kv, kv_heads, queries, pool_block, stride) \
        and (pool_block // stride) % 16 == 0


def select_fits(heads_per_kv: int, kv_heads: int, queries: int,
                pool_block: int, stride: int) -> bool:
    """The shapes the selection kernel's layout holds, on any platform
    (interpret mode included): a chunk of its loops is whole plane
    blocks."""
    return heads_per_kv <= _SEL_HEADS \
        and (queries % _SEL_COLS == 0
             or (queries == 1 and _SEL_COLS % kv_heads == 0)) \
        and _SEL_CHUNK % (pool_block // stride) == 0


def _block_select_kernel(tables_ref, nblk_ref, nch_ref, off_ref, layer_ref,
                         q_ref, pos_ref, plane_hbm, *rest, G, KV, D, Cq,
                         MAXB, per, NB, NG, CH, WIN, PAD, heads, stride,
                         ks, r, w, init_blocks, local_blocks, hs):
    """Grid step (i, t): tile t of the queries of sequence group i (G
    sequences x Cq queries x KV heads = ``nlt`` lane tiles of 8 columns)
    against the group's plane rows, which step (i, 0) waits for and which
    stay in VMEM for the group's other tiles."""
    rest = list(rest)
    ring_ref = rest.pop(0) if NG else None
    o_ref, m_scr, sem, g_scr, p_scr = rest
    i = pl.program_id(0)
    t = pl.program_id(1)
    ns, nqt = pl.num_programs(0), pl.num_programs(1)
    u = i * nqt + t
    slot = jax.lax.rem(i, 2)
    nlt = G * KV * Cq // _SEL_COLS
    ppl = _SEL_COLS // Cq              # (sequence, kv head) pairs a tile
    W = KV * D
    i32, f32 = jnp.int32, jnp.float32

    def chunk(c):
        return pl.ds(pl.multiple_of(c * CH, CH), CH)

    def copies(gi, sl, wait):
        for g in range(G):
            s = gi * G + g
            rows_of = lambda rows: m_scr.at[              # noqa: E731
                sl, rows, pl.ds(g * W, W)]

            def per_block(b, carry, s=s, rows_of=rows_of):
                src = pl.multiple_of(tables_ref[s * MAXB + b] * per, per)
                cp = pltpu.make_async_copy(
                    plane_hbm.at[layer_ref[0], pl.ds(src, per), :],
                    rows_of(pl.ds(pl.multiple_of(b * per, per), per)),
                    sem.at[sl])
                cp.wait() if wait else cp.start()
                return carry

            def per_chunk(c, carry, rows_of=rows_of):
                # a DMA semaphore counts bytes: ONE wait for the copies
                # of a whole chunk's blocks (0.06 ms of a 0.62 ms call)
                pltpu.make_async_copy(rows_of(chunk(c)), rows_of(chunk(c)),
                                      sem.at[sl]).wait()
                return carry
            n = nblk_ref[s]
            whole = n // (CH // per) if wait else 0
            jax.lax.fori_loop(0, whole, per_chunk, 0)
            jax.lax.fori_loop(whole * (CH // per), n, per_block, 0)

    @pl.when(u == 0)
    def _first():
        # a pair's lanes meet every other pair's rows under a zero weight:
        # no row may ever hold a NaN
        def clear(c, carry):
            for sl in range(2):
                m_scr[sl, chunk(c), :] = jnp.zeros((CH, G * W), m_scr.dtype)
            return carry
        jax.lax.fori_loop(0, m_scr.shape[1] // CH, clear, 0)
        # the window before group 0 is never a score
        p_scr[:, :PAD, :] = jnp.full((nlt, PAD, _SEL_LANES), _NEG_INF, f32)
        copies(i, slot, wait=False)

    @pl.when(t == 0)
    def _turn():
        @pl.when(i + 1 < ns)
        def _next():
            copies(i + 1, 1 - slot, wait=False)
        copies(i, slot, wait=True)

    nch = nch_ref[u]
    lane = jax.lax.broadcasted_iota(i32, (1, _SEL_LANES), 1)
    mine = (u % _SEL_SUPER) == (lane >> 3)              # this tile's lanes
    rows = jax.lax.broadcasted_iota(i32, (CH, _SEL_LANES), 0)

    @pl.when(nch == 0)
    def _idle():
        for lt in range(nlt):
            o_ref[0, lt] = jnp.where(mine, _NEG_INF, o_ref[0, lt])

    @pl.when(nch > 0)
    def _tile():
        for lt in range(nlt):
            qr = q_ref[0, 0, lt]                              # [128, D]
            if ppl > 1:
                pair = (jax.lax.broadcasted_iota(
                    i32, (_SEL_LANES, 1), 0) & (_SEL_COLS - 1)) // Cq
                qr = jnp.concatenate(
                    [jnp.where(pair == p, qr, jnp.zeros_like(qr))
                     for p in range(ppl)], axis=1)         # [128, ppl * D]
            k0 = lt * ppl * D
            qm = qr.astype(m_scr.dtype)

            def score(c, carry):
                g_scr[lt, chunk(c), :] = jax.lax.dot_general(
                    m_scr[slot, chunk(c), k0:k0 + ppl * D], qm,
                    (((1,), (1,)), ((), ())), preferred_element_type=f32)
                return carry
            jax.lax.fori_loop(0, nch, score, 0)

            if NG:
                # the loop's own groups, float32, over the plane's from
                # each sequence's first unsettled group on
                sums = jnp.concatenate(
                    [ring_ref[g] for g in range(G)], axis=1) / stride
                rs = jax.lax.dot_general(
                    sums, qr.astype(f32), (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=f32)               # [NG, 128]
                seq_of = (lane & (_SEL_COLS - 1)) // KV
                for g in range(G):
                    off = off_ref[i * G + g]
                    base = pl.multiple_of((off // 8) * 8, 8)
                    d = off - base
                    place = (jax.lax.broadcasted_iota(i32, (WIN, NG), 0)
                             == jax.lax.broadcasted_iota(i32, (WIN, NG), 1)
                             + d).astype(f32)
                    put = jax.lax.dot_general(
                        place, rs, (((1,), (0,)), ((), ())),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=f32)           # [WIN, 128]
                    rel = jax.lax.broadcasted_iota(
                        i32, (WIN, _SEL_LANES), 0) - d
                    own = (rel >= 0) & (rel < NG) & (seq_of == g)
                    g_scr[lt, pl.ds(base, WIN), :] = jnp.where(
                        own, put, g_scr[lt, pl.ds(base, WIN), :])

            pos = pos_ref[0, 0, lt, 0:1, :]                   # [1, 128]
            tb = pos_ref[0, 0, lt, 1:2, :]

            def valid_of(c):
                return (c * CH + rows) * stride + (ks - 1) <= pos

            def window(c, m):
                # a window's score from two neighbouring groups
                x = g_scr[lt, pl.ds(pl.multiple_of(c * CH, CH), CH + 8), :]
                sc = ((x + pltpu.roll(x, CH + 7, 0)) * hs)[:CH]
                sc = jnp.where(valid_of(c), sc, _NEG_INF)
                g_scr[lt, chunk(c), :] = sc
                return jnp.maximum(m, jnp.max(sc, axis=0, keepdims=True))
            m = jax.lax.fori_loop(
                0, nch, window, jnp.full((1, _SEL_LANES), _NEG_INF, f32))
            m = jnp.where(jnp.isfinite(m), m, 0.0)

            def expo(c, l):
                e = jnp.exp(g_scr[lt, chunk(c), :] - m)
                g_scr[lt, chunk(c), :] = e
                return l + jnp.sum(e, axis=0, keepdims=True)
            l = jax.lax.fori_loop(
                0, nch, expo, jnp.zeros((1, _SEL_LANES), f32))
            l = jnp.where(l == 0.0, 1.0, l)

            def group_sum(c, carry):
                p = g_scr[lt, chunk(c), :] / l
                if heads < _SEL_HEADS:
                    p = jnp.where((lane >> 3) < heads, p, 0.0)
                for sh in (8, 16, 32, 64):                    # over h
                    p = p + pltpu.roll(p, sh, 1)
                p_scr[lt, pl.ds(pl.multiple_of(c * CH + PAD, 8), CH), :] = \
                    jnp.where(valid_of(c), p, _NEG_INF)
                return carry
            jax.lax.fori_loop(0, nch, group_sum, 0)

            sc = None
            for k in range(-(w - 1), r):
                v = p_scr[lt, pl.ds(PAD + k, NB, stride=r), :]
                sc = v if sc is None else jnp.maximum(sc, v)
            b = jax.lax.broadcasted_iota(i32, (NB, _SEL_LANES), 0)
            forced = (b < init_blocks) | (b > tb - local_blocks)
            sc = jnp.where(b <= tb, jnp.where(forced, jnp.inf, sc),
                           _NEG_INF)
            o_ref[0, lt] = jnp.where(mine, sc, o_ref[0, lt])


def block_select_call(tables, nblk, nch, off, layer, qrows, posrows, index,
                      ring, *, G, KV, Cq, per, NB, heads, stride, ks, r, w,
                      init_blocks, local_blocks, hs, interpret):
    """The selection kernel's one Mosaic call (``_block_select_call`` is
    its jitted, named form)."""
    NS, NQT, nlt, _, D = qrows.shape
    MAXB = tables.shape[0] // (NS * G)
    W = KV * D
    CH = _SEL_CHUNK
    Jp = -(-MAXB * per // CH) * CH
    NG = 0 if ring is None else ring.shape[2]
    WIN = -(-(NG + 7) // 8) * 8 if NG else 8
    PAD = 8
    assert CH % r == 0 and CH % per == 0 and w - 1 <= PAD
    kernel = functools.partial(
        _block_select_kernel, G=G, KV=KV, D=D, Cq=Cq, MAXB=MAXB, per=per,
        NB=NB, NG=NG, CH=CH, WIN=WIN, PAD=PAD, heads=heads, stride=stride,
        ks=ks, r=r, w=w, init_blocks=init_blocks,
        local_blocks=local_blocks, hs=hs)
    in_specs = [
        pl.BlockSpec((1, 1, nlt, _SEL_LANES, D),
                     lambda i, t, *_: (i, t, 0, 0, 0)),
        pl.BlockSpec((1, 1, nlt, 8, _SEL_LANES),
                     lambda i, t, *_: (i, t, 0, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY)]
    operands = [qrows, posrows, index]
    if NG:
        in_specs.append(pl.BlockSpec(
            (None, G, NG, W), lambda i, t, *refs: (refs[4][1], i, 0, 0)))
        operands.append(ring)
    NU = NS * NQT
    item = index.dtype.itemsize
    vmem = 2 * Jp * G * W * item + 4 * nlt * (Jp + WIN + PAD) * _LANES * 4 \
        + 4 * nlt * NB * _LANES * 4 + 8 * CH * max(_LANES, G * W) * 4 \
        + _VMEM_MARGIN
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(NS, NQT), in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, nlt, NB, _LANES),
            lambda i, t, *_: ((i * NQT + t) // _SEL_SUPER, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, Jp, G * W), index.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((nlt, Jp + WIN, _SEL_LANES), jnp.float32),
            pltpu.VMEM((nlt, Jp + PAD, _SEL_LANES), jnp.float32)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (-(-NU // _SEL_SUPER), nlt, NB, _LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret, name="block_select",
    )(tables, nblk, nch, off, layer, *operands)


# one traced, once-lowered function the sparse layers of a program share
# (see ``_sparse_decode_call``); NO reader's pattern matches its name
_block_select_call = jax.jit(block_select_call, static_argnames=(
    "G", "KV", "Cq", "per", "NB", "heads", "stride", "ks", "r", "w",
    "init_blocks", "local_blocks", "hs", "interpret"))


def block_select_scores(q, index, layer: int, tables, pos, n_tokens, sp, *,
                        pool_block: int, sm_scale: float, num_blocks: int,
                        ring_sums=None, ring_layer=None, settled=None,
                        interpret: bool = False):
    """The selection's block scores as ONE kernel over the compressed-key
    plane: what ``minicpm_sala.block_scores`` makes of
    ``index_plane.group_scores``, without the gathered plane or a score a
    query head in HBM. q [S, C, KV, Hg, D]; index [Ls, R, KV*D] the
    plane, ``layer`` its sparse layer; tables [S, MAXB]; pos [S, C] the
    queries' positions, of which a sequence's first ``n_tokens`` [S] are
    real (0: the row does nothing). In a fused loop ``ring_sums`` is
    ``RingKV.idx`` [Ls, S, NG, KV*D] float32 and ``settled`` [S] the
    loop's first position a sequence: the groups from ``settled //
    stride`` on read the loop's sums. Returns [S, C, KV, num_blocks]
    float32: ``+inf`` forced, ``-inf`` past the query's own block and for
    a query that is not real.

    The kernel walks a sequence's plane blocks through the block table
    itself (the next sequence group's copies in flight), only those at or
    before the tile's last position, scores a kv head's query heads
    against that head's lanes, and holds the window sums, the softmax
    over the groups, the sum over the group's heads, the window maximum
    and the forced blocks in VMEM."""
    S, C, KV, Hg, D = q.shape
    i32 = jnp.int32
    stride, ks, sb = sp.kernel_stride, sp.kernel_size, sp.block_size
    per = pool_block // stride
    r, w = sb // stride, ks // stride
    MAXB = tables.shape[1]
    Cq = 1 if C == 1 else _SEL_COLS
    G = _SEL_COLS // KV if C == 1 else 1
    nlt = G * KV * Cq // _SEL_COLS
    NS, NQT = -(-S // G), C // Cq
    Sp = NS * G
    real = jnp.arange(C, dtype=i32)[None, :] < n_tokens[:, None]
    posq = jnp.where(real, pos.astype(i32), -1)                  # [S, C]
    live = n_tokens > 0
    if ring_sums is None:
        nblk = jnp.where(live, jnp.max(posq, axis=1) // pool_block + 1, 0)
        off = jnp.zeros((S,), i32)
    else:
        nblk = jnp.where(live, -(-settled // pool_block), 0)
        off = jnp.clip(settled // stride, 0, MAXB * per - 1)
    nblk = jnp.clip(nblk, 0, MAXB)

    def rows(x, fill=0, axis=0):
        """``x`` with its sequence axis padded to whole groups."""
        if x is None or Sp == S:
            return x
        width = [(0, 0)] * x.ndim
        width[axis] = (0, Sp - S)
        return jnp.pad(x, width, constant_values=fill)
    # columns of a grid step in the order (sequence, kv head, query),
    # eight a lane tile; a tile's lane is h * 8 + column
    qp = jnp.pad(rows(q), ((0, 0),) * 3 + ((0, _SEL_HEADS - Hg), (0, 0)))
    qrows = qp.reshape(NS, G, NQT, Cq, KV, _SEL_HEADS, D).transpose(
        0, 2, 1, 4, 3, 5, 6).reshape(NS, NQT, nlt, _SEL_COLS, _SEL_HEADS, D)
    qrows = qrows.swapaxes(3, 4).reshape(NS, NQT, nlt, _SEL_LANES, D)
    pc = jnp.broadcast_to(
        rows(posq, -1).reshape(NS, G, NQT, Cq)[..., None],
        (NS, G, NQT, Cq, KV)).transpose(0, 2, 1, 4, 3).reshape(
            NS, NQT, nlt, _SEL_COLS)
    nch = jnp.minimum(
        -(-(jnp.max(pc, axis=(2, 3)) // stride + 1) // _SEL_CHUNK),
        -(-MAXB * per // _SEL_CHUNK))
    # a lane tile's rows: its columns' positions and their own blocks
    posrows = jnp.tile(jnp.stack(
        [pc, jnp.where(pc >= 0, pc // sb, -1)], axis=3),
        (1, 1, 1, 1, _SEL_HEADS))
    posrows = jnp.pad(posrows, ((0, 0),) * 3 + ((0, 6), (0, 0)))
    rl = layer if ring_layer is None else ring_layer
    out = _block_select_call(
        rows(tables.astype(i32)).reshape(-1), rows(nblk.astype(i32)),
        nch.reshape(-1).astype(i32), rows(off.astype(i32)),
        jnp.asarray([layer, rl], i32), qrows, posrows, index,
        rows(ring_sums, axis=1),
        G=G, KV=KV, Cq=Cq, per=per, NB=num_blocks, heads=Hg, stride=stride,
        ks=ks, r=r, w=w, init_blocks=sp.init_blocks,
        local_blocks=sp.local_blocks, hs=0.5 * float(sm_scale),
        interpret=bool(interpret))                    # [NUs, nlt, NB, 128]
    NUs = out.shape[0]
    out = out.transpose(1, 0, 3, 2).reshape(
        nlt, NUs * _SEL_SUPER, _SEL_COLS, num_blocks)[:, :NS * NQT]
    # (lane tile, column) back to (sequence, kv head, query)
    out = out.reshape(nlt, NS, NQT, _SEL_COLS, num_blocks).transpose(
        1, 2, 0, 3, 4).reshape(NS, NQT, G, KV, Cq, num_blocks)
    return out.transpose(0, 2, 1, 4, 3, 5).reshape(
        Sp, C, KV, num_blocks)[:S]
