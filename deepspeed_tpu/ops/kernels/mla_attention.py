"""Decode attention over a paged LATENT cache (multi-head latent attention
in its absorbed form).

A latent-attention layer keeps ONE row a token: ``[c_kv ; k_r ; 0]``, the
normed latent (``latent`` lanes), the shared rotary key, and a zero tail
up to a whole 128-lane group. With ``W_UK`` multiplied into the query and
``W_UV`` into the output (the caller's two small batched matmuls) a decode
step is, per sequence, ``scores = q_abs [H, W] x rows^T``, a softmax, and
``o_lat = p x rows[:, :latent]``: the row is key and value at once, so each
tile is fetched ONCE and used for both products, and all H heads share it.
At 128 heads over a 576-lane row both limits of the v5e coincide (1.41 ns a
row by bytes and by FLOPs).

The kernel follows ``paged_attention._decode_kernel``'s scheme: G
sequences a grid step, the live 128-row tiles of each by manual DMA through
the block table into one of two scratch slots, the next step's copies
started before this step waits for its own, float32 softmax statistics,
the layer picked by scalar prefetch inside the DMA source so every layer
of a program shares one Mosaic body. The fused decode loop's ring (this
loop's rows) is SEQUENCE-major for a latent cache, ``[L, 1, S, R, W]``: a
BlockSpec hands the kernel a [R, W] slab a sequence and the ring is one
more round of G small products at the last chunk. (The K/V ring is
step-major, ``[R, L, 2, S, KVD]``, and its kernel multiplies all G
sequences' queries against all G sequences' rows and masks the cross
terms: at 128 heads those cross terms cost a fifth of this kernel.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
_LANES = 128
TILE_ROWS = 256
# rows of one sequence a grid step holds (a chunk), and what the call asks
# of VMEM beyond its scratch, its blocks and a round's [G*H, chunk] scores
_CHUNK_ROWS = 512
# sequences a grid step serves
_GROUP = 8
# scratch slots: a step waits for its own tiles with the next SLOTS - 1
# steps' copies already in flight (3 and 4 measured no faster: PERF.md)
_SLOTS = 2
_VMEM_MARGIN = 8 << 20


def _plan(S: int, ctx_rows: int, ts: int):
    """(G, CR, NCH): sequences a grid step, context rows a sequence and
    step (whole tiles, even chunks), chunks a context."""
    G = max(d for d in range(1, _GROUP + 1) if S % d == 0)
    cap = max(ts, _CHUNK_ROWS // ts * ts)
    nch = -(-ctx_rows // cap)
    cr = -(-ctx_rows // (nch * ts)) * ts
    return G, cr, nch


def _kernel(tables_ref, lens_ref, rcount_ref, layer_ref, q_ref,
            pool_hbm, *rest, G, CR, NCH, NG, NS, ts, bs, maxb, H, W, LAT, R):
    """Grid step (i, c): the G sequences of group i over context rows
    [c*CR, (c+1)*CR)."""
    rest = list(rest)
    ring_ref = rest.pop(0) if R is not None else None
    o_ref, kv_scr, sems, m_scr, l_scr, acc_scr = rest

    i = pl.program_id(0)
    c = pl.program_id(1)
    u = i * NCH + c
    slot = jax.lax.rem(u, NS)
    tpb = bs // ts                     # tiles a block
    TPC = CR // ts                     # tiles a chunk
    M = G * H

    def copies(gi, ci, sl, wait):
        """Start (or wait for) the live tiles of group gi, chunk ci."""
        def per_seq(g, carry):
            s = gi * G + g
            t_hi = jnp.minimum((lens_ref[s] + ts - 1) // ts,
                               jnp.minimum((ci + 1) * TPC, maxb * tpb))

            def per_tile(t, carry):
                b = t // tpb
                src = pl.multiple_of(
                    tables_ref[s * maxb + b] * bs + (t - b * tpb) * ts, ts)
                dst = pl.multiple_of(g * CR + (t - ci * TPC) * ts, ts)
                cp = pltpu.make_async_copy(
                    pool_hbm.at[layer_ref[0], 0, pl.ds(src, ts)],
                    kv_scr.at[sl, pl.ds(dst, ts)], sems.at[sl])
                cp.wait() if wait else cp.start()
                return carry
            return jax.lax.fori_loop(ci * TPC, t_hi, per_tile, carry)
        jax.lax.fori_loop(0, G, per_seq, 0)

    # p == 0 under a masked column, but 0 * NaN poisons p @ rows: the
    # scratch must be finite where no tile ever landed. Zeroed once a call;
    # after that it only ever receives pool rows (finite: zeros or what a
    # step wrote), so a stale tile is old finite rows under p == 0
    def start(v):
        """Start the copies of grid step ``v`` into its slot."""
        gi = v // NCH
        copies(gi, v - gi * NCH, jax.lax.rem(v, NS), wait=False)

    @pl.when(u == 0)
    def _zero():
        def z(j, carry):
            for sl in range(NS):
                kv_scr[sl, pl.ds(pl.multiple_of(j * ts, ts), ts), :] = \
                    jnp.zeros((ts, W), kv_scr.dtype)
            return carry
        jax.lax.fori_loop(0, G * TPC, z, 0)
        for v in range(min(NS - 1, NG * NCH)):
            start(v)

    @pl.when(u + NS - 1 < NG * NCH)
    def _ahead():
        start(u + NS - 1)

    copies(i, c, slot, wait=True)

    @pl.when(c == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    def online(sc, pv_of):
        """One online-softmax round: sc [M, w] masked scores, pv_of(p)
        the [M, LAT] f32 product of the probabilities with the rows."""
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_next = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        # a row can be fully masked so far (idle slot): m_next stays -inf
        # and exp(-inf - -inf) would be nan
        m_safe = jnp.where(jnp.isfinite(m_next), m_next, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe),
                          0.0)
        p = jnp.exp(sc - m_safe)
        l_scr[...] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + pv_of(p)

    grp_len = lens_ref[i * G]
    for g in range(1, G):
        grp_len = jnp.maximum(grp_len, lens_ref[i * G + g])

    @pl.when(grp_len > c * CR)
    def _pool_round():
        # one fetch of a tile serves both products: all W lanes for the
        # scores, the first LAT for the values. The G sequences' products
        # are G independent matmuls in one basic block (the scheduler
        # interleaves them), then ONE softmax round over [G*H, CR].
        # Live rows only: below the settled length, which also makes them
        # causal (the query sits at or after the last settled row; in ring
        # mode rows lens..pos live in the ring and the pool's are stale).
        # The mask is one [1, CR] row a sequence, added: per element the
        # round then costs an add, not an iota and three compares
        col = c * CR + jax.lax.broadcasted_iota(jnp.int32, (1, CR), 1)
        sc = jnp.concatenate([
            jax.lax.dot_general(
                q_ref[g], kv_scr[slot, pl.ds(g * CR, CR), :],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # [H, CR]
            + jnp.where(col < lens_ref[i * G + g], 0.0, _NEG_INF)
            for g in range(G)], axis=0)                    # [M, CR]

        def pv_of(p):
            p = p.astype(kv_scr.dtype)
            return jnp.concatenate([
                jax.lax.dot_general(
                    p[g * H:(g + 1) * H],
                    kv_scr[slot, pl.ds(g * CR, CR), pl.ds(0, LAT)],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)    # [H, LAT]
                for g in range(G)], axis=0)
        online(sc, pv_of)

    @pl.when(c == NCH - 1)
    def _finish():
        if R is not None:
            # the loop's own rows, a [R, W] slab a sequence (the latent ring
            # is sequence-major): row r holds the token at position
            # start - (rcount - 1) + r. One round over all R rows; the
            # lens gate keeps idle slots fully masked
            live = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1) \
                < rcount_ref[0]
            rsc = jnp.concatenate([
                jax.lax.dot_general(
                    q_ref[g], ring_ref[g], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)        # [H, R]
                + jnp.where(jnp.logical_and(live, lens_ref[i * G + g] > 0),
                            0.0, _NEG_INF)
                for g in range(G)], axis=0)                    # [M, R]

            def ring_pv(p):
                p = p.astype(ring_ref.dtype)
                return jnp.concatenate([
                    jax.lax.dot_general(
                        p[g * H:(g + 1) * H], ring_ref[g, :, pl.ds(0, LAT)],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)    # [H, LAT]
                    for g in range(G)], axis=0)
            online(rsc, ring_pv)
        l = l_scr[:, :1]
        out = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)   # idle slots: 0
        o_ref[...] = out.reshape(G, H, LAT).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_size", "latent", "sm_scale", "interpret"))
def mla_decode_attention(q, pool, ring, tables, seq_lens, ring_count,
                         layers, *, block_size: int, latent: int,
                         sm_scale: float, interpret: bool = False):
    """One decode step of absorbed latent attention over the paged cache.

    q [S, H, W]: each head's absorbed query ``[q_nope W_UK^T ; q_rope ; 0]``
    in the pool's dtype. pool [L, 1, slots, W]: the WHOLE latent pool
    (a Pallas operand is a whole buffer; the layer is ``layers[0]``, picked
    inside the DMA source). ring: None, or the fused loop's whole
    [L, 1, S, R, W] carry (sequence-major: a [R, W] slab a sequence), its
    layer ``layers[1]``, ``ring_count`` [1] rows of each slab live.
    tables [S, MAXB]; seq_lens [S] the rows settled in the pool, all of
    them at or before the query (0: an idle slot, which emits zeros; in
    ring mode the ring's rows are not among them).
    Returns o_lat [S, H, latent]: the probabilities over the latent part
    of the rows (``W_UV`` is the caller's). Jitted under this name: the
    device trace names the Mosaic call after it."""
    S, H, W = q.shape
    bs = block_size
    maxb = tables.shape[1]
    ts = tile_rows(bs)
    G, CR, NCH = _plan(S, maxb * bs, ts)
    R = None if ring is None else ring.shape[3]
    kernel = functools.partial(
        _kernel, G=G, CR=CR, NCH=NCH, NG=S // G, NS=_SLOTS, ts=ts, bs=bs,
        maxb=maxb, H=H, W=W, LAT=latent, R=R)
    item = pool.dtype.itemsize
    in_specs = [pl.BlockSpec((G, H, W), lambda i, c, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY)]
    # the scale rides in the query: one multiply a query lane, not one a
    # score
    operands = [(q.astype(jnp.float32) * sm_scale).astype(q.dtype), pool]
    M = G * H
    # two scratch slots, q and output blocks twice, the softmax state, and
    # the scores of one round in float32 with their exponentials
    vmem = (_SLOTS * G * CR * W * item + 2 * M * W * item
            + 2 * M * latent * item
            + M * (2 * _LANES + latent) * 4 + 10 * M * CR + _VMEM_MARGIN)
    if R is not None:
        # the BlockSpec picks (layer, the one plane, the group's sequences)
        in_specs.append(pl.BlockSpec(
            (None, None, G, R, W),
            lambda i, c, *refs: (refs[3][1], 0, i, 0, 0)))
        operands.append(ring)
        vmem += 2 * R * G * W * ring.dtype.itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S // G, NCH),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((G, H, latent), lambda i, c, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((_SLOTS, G * CR, W), pool.dtype),
            pltpu.SemaphoreType.DMA((_SLOTS,)),
            pltpu.VMEM((M, _LANES), jnp.float32),
            pltpu.VMEM((M, _LANES), jnp.float32),
            pltpu.VMEM((M, latent), jnp.float32)])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, latent), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(tables.reshape(-1).astype(jnp.int32), seq_lens.astype(jnp.int32),
      jnp.reshape(ring_count, (1,)).astype(jnp.int32),
      layers.astype(jnp.int32), *operands)


def tile_rows(block_size: int) -> int:
    """Rows of one copy: a tile inside a block, or a block narrower than
    (or not a multiple of) a tile whole."""
    return TILE_ROWS if block_size % TILE_ROWS == 0 else block_size


def decode_rows_fetched(seq_len: int, block_size: int) -> int:
    """Latent rows one decode call streams for a sequence of ``seq_len``
    settled tokens: whole copy tiles up to the length."""
    ts = tile_rows(block_size)
    return -(-seq_len // ts) * ts


def mla_attention_reference(q, rows, mask, latent: int, sm_scale: float):
    """Plain absorbed attention: q [S, C, H, W], rows [S, T, W] the
    context's latent rows, mask [S, C, T]. Returns o_lat [S, C, H, latent]
    in q's dtype; the dense path of the runner and the kernel's oracle."""
    s = jnp.einsum("schw,stw->shct", q, rows).astype(jnp.float32) * sm_scale
    s = jnp.where(mask[:, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p).astype(q.dtype)   # idle rows
    return jnp.einsum("shct,str->schr", p, rows[..., :latent])
