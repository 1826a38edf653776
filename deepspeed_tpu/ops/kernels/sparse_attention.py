"""Block-selected attention over the paged pool (Pallas TPU): a query reads
a LIST of selected key blocks, not every live tile.

The selection itself (compressed scores, group sum, window maximum, forced
blocks, top-k) is ``models/minicpm_sala.select_blocks``; this module holds
what reads the pool under it, each form with its ``jax.numpy`` twin (what
every CPU run takes, and what tier-1 holds the kernel to):

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
