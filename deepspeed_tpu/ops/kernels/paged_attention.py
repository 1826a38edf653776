"""Paged-KV flash attention (Pallas TPU) — the FastGen decode hot loop.

TPU-native analogue of the reference's blocked flash decode
(``inference/v2/kernels/ragged_ops/blocked_flash/``, wired at
``inference/v2/model_implementations/inference_transformer_base.py``): flash
attention reads K/V DIRECTLY through per-sequence block tables, so each step
touches only the blocks a sequence actually occupies. The block tables ride
scalar prefetch (their values drive the K/V BlockSpec index maps), and dead
grid steps (past a sequence's live block count) repeat the previous block
index — a revisited block costs no DMA.

Layout contract (matches BlockedKVCache): the flat KV pool is
``[slots, KV_heads * D]`` with ``slots = (num_blocks + 1) * block_size`` —
one LANE-ALIGNED row per token. The earlier ``[slots, KV, D]`` layout let
XLA pad the trailing ``(4, 64)`` dims to the (8, 128) tile — 4x the HBM
footprint AND 4x the DMA traffic on the serving hot path. A 3-D pool is
still accepted and viewed flat (same bytes, contiguous reshape).

GQA is handled by LANE WINDOWING instead of a per-kv-head matmul unroll:
the caller expands q so the row for head h carries its values in lane
window ``(h // group) * D .. + D`` and zeros elsewhere; one
``[H*Cb, KV*D] x [KV*D, width]`` matmul then yields every head's scores
(cross-head lanes contract against zeros), and the P*V product emits
``[H*Cb, KV*D]`` rows from which the caller slices each head's window.
This keeps the MXU on one large operand per grid step — at decode the old
per-head unroll fed it [1, 64] slivers.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
_LANES = 128


def _paged_kernel(starts_ref, fetch_ref, lo_ref, hi_ref, slopes_ref, *rest,
                  bs, Cb, nCb, H, KV, D, sm_scale, use_alibi, window, R,
                  windowed, quant=False):
    if R is None:
        if quant:
            (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr,
             acc_scr) = rest
        else:
            q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = rest
            ks_ref = vs_ref = None
        rcount_ref = lens_ref = rk_ref = rv_ref = None
    else:
        if quant:
            (rcount_ref, lens_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
             rk_ref, rv_ref, o_ref, m_scr, l_scr, acc_scr) = rest
        else:
            (rcount_ref, lens_ref, q_ref, k_ref, v_ref, rk_ref, rv_ref,
             o_ref, m_scr, l_scr, acc_scr) = rest
            ks_ref = vs_ref = None
    s = pl.program_id(0)
    qc = pl.program_id(1)
    j = pl.program_id(2)
    nb = pl.num_programs(2)
    sq = s * nCb + qc
    g = H // KV

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[:] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[:] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    def _attend(kb, vb, width, mask, dist, ks=None, vs=None):
        """One online-softmax round over ``width`` columns. kb/vb are
        [width, KV*D] token rows; mask/dist are [H*Cb, width]. Rows are
        head-major (row h*Cb + c <-> head h, tile pos c).

        windowed (decode, Cb==1): q rows are lane-windowed per head
        (module docstring) and ONE [H, KV*D] x [KV*D, width] matmul covers
        every head — at Cb=1 per-head operands would be single-row MXU
        slivers. grouped (prefill): per-kv-head [g*Cb, D] matmuls against
        64-lane slices of the flat rows — no zero-lane FLOP inflation
        (windowing would cost KV x the useful MACs, ruinous for MHA).

        ks/vs ([KV, width], int8 pool only): per-(token, kv-head) dequant
        scales — K scales multiply score columns, V scales multiply
        probability columns (exact; constant along the contracted D axis).
        The ring round passes None (the ring is never quantized)."""
        q = q_ref[0]                  # [H*Cb, KV*D] windowed / [H*Cb, D]
        if quant and kb.dtype == jnp.int8:
            kb = kb.astype(q.dtype)
        g = H // KV

        def _exp_rows(s):
            """[KV, width] -> [H*Cb, width] head-major row expansion."""
            return jnp.broadcast_to(
                s[:, None, :], (KV, g * Cb, width)).reshape(H * Cb, width)

        if use_alibi:
            slope_rows = jnp.concatenate(
                [jnp.full((Cb, 1), slopes_ref[h], jnp.float32)
                 for h in range(H)], axis=0)           # [HCb, 1]
        if windowed:
            sc = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if ks is not None:
                sc = sc * _exp_rows(ks)
            if use_alibi:
                sc = sc - slope_rows * dist
            scores = jnp.where(mask, sc, _NEG_INF)     # [HCb, width]
        else:
            parts = []
            for kvh in range(KV):
                rows = slice(kvh * g * Cb, (kvh + 1) * g * Cb)
                kh = kb[:, kvh * D:(kvh + 1) * D]      # [width, D]
                sc = jax.lax.dot_general(
                    q[rows], kh, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                if ks is not None:
                    sc = sc * ks[kvh:kvh + 1, :]
                if use_alibi:
                    sc = sc - slope_rows[rows] * dist[rows]
                parts.append(jnp.where(mask[rows], sc, _NEG_INF))
            scores = jnp.concatenate(parts, axis=0)    # [HCb, width]

        m_prev, l_prev = m_scr[:], l_scr[:]
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        # a row can be fully masked in its first processed block (sliding
        # window): m_next stays -inf there, and exp(-inf - -inf) would be
        # nan — clamp through a finite stand-in (p comes out 0 either way)
        m_safe = jnp.where(jnp.isfinite(m_next), m_next, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        p = jnp.exp(jnp.where(jnp.isfinite(scores),
                              scores - m_safe[:, :1], _NEG_INF))
        l_scr[:] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = m_next
        if quant and vb.dtype == jnp.int8:
            vb = vb.astype(q.dtype)
        if vs is not None:
            p = p * _exp_rows(vs)
        if windowed:
            pv = jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)    # [HCb, KV*D]
        else:
            pv = jnp.concatenate([
                jax.lax.dot_general(
                    p[kvh * g * Cb:(kvh + 1) * g * Cb].astype(vb.dtype),
                    vb[:, kvh * D:(kvh + 1) * D], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                for kvh in range(KV)], axis=0)         # [HCb, D]
        acc_scr[:] = acc_scr[:] * alpha[:, :1] + pv

    @pl.when(jnp.logical_and(j >= lo_ref[sq], j < hi_ref[sq]))
    def _compute():
        # per-row query positions at the head-major row layout [H*Cb, bs]:
        # row r <-> (head r // Cb, tile pos r % Cb) — built directly at
        # full width (Mosaic cannot concatenate i1 mask vregs)
        c_of_row = jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (H * Cb, bs), 0), Cb)
        pos_q = starts_ref[s] + qc * Cb + c_of_row     # [HCb, bs]
        col = j * bs + jax.lax.broadcasted_iota(jnp.int32, (H * Cb, bs), 1)
        causal = col <= pos_q
        if R is not None:
            # ring mode: the pool only holds SETTLED rows; positions
            # lens..pos_q live in the ring, and the pool rows there are
            # stale — mask them out column-exactly (hi is block-granular)
            causal = jnp.logical_and(causal, col < lens_ref[s])
        if window is not None:                         # mistral sliding window
            causal = jnp.logical_and(causal, col > pos_q - window)
        _attend(k_ref[0], v_ref[0], bs, causal,
                (pos_q - col).astype(jnp.float32),
                ks=ks_ref[0] if quant else None,
                vs=vs_ref[0] if quant else None)

    if R is not None:
        # decode-loop ring round: this step's (and the loop's prior) K/V
        # live in a small per-sequence ring buffer that is only flushed
        # into the pool after the fused loop — ring row r holds the token
        # at absolute position (start_pos - (rcount-1) + r)
        @pl.when(j == nb - 1)
        def _ring():
            r = jax.lax.broadcasted_iota(jnp.int32, (H * Cb, R), 1)
            dist = (rcount_ref[0] - 1 - r).astype(jnp.float32)
            # lens gate keeps idle slots (seq_lens == 0) fully masked so
            # they emit zeros — their ring rows hold garbage K/V
            mask = jnp.logical_and(r < rcount_ref[0], lens_ref[s] > 0)
            if window is not None:
                mask = jnp.logical_and(mask, dist < window)
            _attend(rk_ref[0], rv_ref[0], R, mask, dist)

    @pl.when(j == nb - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)           # idle slots emit zeros
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _decode_grouped_kernel(starts_ref, fetch_ref, lens_ref, rcount_ref,
                           contig_ref, layer_ref, slopes_ref, q_ref,
                           kp_hbm, vp_hbm, rk_ref, rv_ref, *rest, G, bs,
                           ts, H, KV, D, sm_scale, use_alibi, window, R,
                           ring5d, use_pool_full, quant, sc_full):
    """Grouped decode: G sequences per grid step (VERDICT r3 #4 decode
    roofline work). The BlockSpec path pays one grid step per (sequence,
    layer) — at S=256 x 22 layers that is ~11k grid steps per decode step,
    and the fixed cost per step IS the decode wall. Here each grid step
    copies G sequences' whole contexts (linear layout: one contiguous
    block each) into VMEM and computes G full softmaxes. When the G blocks
    are CONSECUTIVE in the pool (the common serving steady state —
    sequences admitted in order), ONE [G*bs]-row DMA replaces the G
    per-sequence copies: the per-DMA issue cost, not the bytes, dominates
    at these sizes. ``contig_ref[i]`` carries the host-side run check."""
    if quant:
        (sck_hbm, scv_hbm, o_ref, k_scr, v_scr, ks_scr, vs_scr, sems,
         ssem) = rest
    else:
        o_ref, k_scr, v_scr, sems = rest
        sck_hbm = scv_hbm = ks_scr = vs_scr = ssem = None
    i = pl.program_id(0)
    KVD = KV * D

    if not use_pool_full:
        def k_src(off, n):
            return kp_hbm.at[pl.ds(off, n)]

        def v_src(off, n):
            return vp_hbm.at[pl.ds(off, n)]
    else:
        # the WHOLE [L, 2, slots, KVD] pool rides into the kernel and the
        # layer index lands here, in the DMA source — slicing pool[li, 0/1]
        # at the model level materialized a full per-layer pool copy for
        # the Pallas operand (the device trace measured those copies at
        # ~45 % of the decode step). The layer arrives via SCALAR PREFETCH
        # (layer_ref), not as a Python constant: all layers then share ONE
        # Mosaic binary instead of compiling L structurally-identical
        # kernels.
        def k_src(off, n):
            return kp_hbm.at[layer_ref[0], 0, pl.ds(off, n)]

        def v_src(off, n):
            return kp_hbm.at[layer_ref[0], 1, pl.ds(off, n)]

    if quant:
        # int8 pool: the [KV, rows] scale windows ride separate (tiny, ~3%)
        # DMAs; dequantization happens on scores/probabilities, never on
        # the K/V tiles (kv_quant.py design)
        if sc_full:
            def ks_src(off, n):
                return sck_hbm.at[layer_ref[0], 0, :, pl.ds(off, n)]

            def vs_src(off, n):
                return scv_hbm.at[layer_ref[0], 1, :, pl.ds(off, n)]
        else:
            def ks_src(off, n):
                return sck_hbm.at[:, pl.ds(off, n)]

            def vs_src(off, n):
                return scv_hbm.at[:, pl.ds(off, n)]

    @pl.when(contig_ref[i] == 1)
    def _copy_contig():
        off = fetch_ref[i * G] * bs
        pltpu.make_async_copy(k_src(off, G * bs), k_scr, sems.at[0]).start()
        pltpu.make_async_copy(v_src(off, G * bs), v_scr, sems.at[1]).start()
        if quant:
            pltpu.make_async_copy(ks_src(off, G * bs), ks_scr,
                                  ssem.at[0]).start()
            pltpu.make_async_copy(vs_src(off, G * bs), vs_scr,
                                  ssem.at[1]).start()
        pltpu.make_async_copy(k_src(off, G * bs), k_scr, sems.at[0]).wait()
        pltpu.make_async_copy(v_src(off, G * bs), v_scr, sems.at[1]).wait()
        if quant:
            pltpu.make_async_copy(ks_src(off, G * bs), ks_scr,
                                  ssem.at[0]).wait()
            pltpu.make_async_copy(vs_src(off, G * bs), vs_scr,
                                  ssem.at[1]).wait()

    @pl.when(contig_ref[i] == 0)
    def _copy_tiled():
        # seq_len-bounded block reads (PROFILE.md serving lever): the
        # per-sequence copy is tiled at ``ts`` rows and HBM reads stop at
        # the sequence's settled length — with the linear layout a
        # 640-slot block holding a 130-token context streams 1 tile, not
        # 5. Dead tiles are ZEROED instead of copied: masked scores drop
        # them, but stale/uninitialized VMEM can hold NaN bit patterns and
        # 0 * NaN would poison the p@v matmul.
        nt = bs // ts

        def tile_live(g, t):
            return t * ts < lens_ref[i * G + g]

        for g in range(G):
            off = fetch_ref[i * G + g] * bs
            for t in range(nt):
                row = g * bs + t * ts

                @pl.when(tile_live(g, t))
                def _dma(off=off, t=t, row=row, g=g):
                    pltpu.make_async_copy(
                        k_src(off + t * ts, ts),
                        k_scr.at[pl.ds(row, ts)], sems.at[2 * g]).start()
                    pltpu.make_async_copy(
                        v_src(off + t * ts, ts),
                        v_scr.at[pl.ds(row, ts)],
                        sems.at[2 * g + 1]).start()
                    if quant:
                        pltpu.make_async_copy(
                            ks_src(off + t * ts, ts),
                            ks_scr.at[:, pl.ds(row, ts)],
                            ssem.at[2 + 2 * g]).start()
                        pltpu.make_async_copy(
                            vs_src(off + t * ts, ts),
                            vs_scr.at[:, pl.ds(row, ts)],
                            ssem.at[3 + 2 * g]).start()

                @pl.when(jnp.logical_not(tile_live(g, t)))
                def _zero(row=row):
                    k_scr[pl.ds(row, ts)] = jnp.zeros((ts, k_scr.shape[1]),
                                                      k_scr.dtype)
                    v_scr[pl.ds(row, ts)] = jnp.zeros((ts, v_scr.shape[1]),
                                                      v_scr.dtype)
                    if quant:
                        ks_scr[:, pl.ds(row, ts)] = jnp.zeros(
                            (KV, ts), ks_scr.dtype)
                        vs_scr[:, pl.ds(row, ts)] = jnp.zeros(
                            (KV, ts), vs_scr.dtype)
        for g in range(G):
            off = fetch_ref[i * G + g] * bs
            for t in range(nt):
                row = g * bs + t * ts

                @pl.when(tile_live(g, t))
                def _wait(off=off, t=t, row=row, g=g):
                    pltpu.make_async_copy(
                        k_src(off + t * ts, ts),
                        k_scr.at[pl.ds(row, ts)], sems.at[2 * g]).wait()
                    pltpu.make_async_copy(
                        v_src(off + t * ts, ts),
                        v_scr.at[pl.ds(row, ts)],
                        sems.at[2 * g + 1]).wait()
                    if quant:
                        pltpu.make_async_copy(
                            ks_src(off + t * ts, ts),
                            ks_scr.at[:, pl.ds(row, ts)],
                            ssem.at[2 + 2 * g]).wait()
                        pltpu.make_async_copy(
                            vs_src(off + t * ts, ts),
                            vs_scr.at[:, pl.ds(row, ts)],
                            ssem.at[3 + 2 * g]).wait()

    # scores per sequence (the matmuls are irreducibly [H, ...] slivers),
    # but ONE batched softmax over the whole group's [G*H, bs(+R)] rows —
    # the per-seq VPU passes (iota/mask/exp/sum), not the DMAs, were the
    # measured wall of the per-seq variant
    def ring_plane(ref, g):
        # ring5d: ref block is [R, 1, 1, G, KVD] (the full decode-loop
        # carry, layer/kv planes picked by the BlockSpec) -> [R, KVD]
        return ref[:, 0, 0, g] if ring5d else ref[g]

    grp = H // KV

    def _exp_heads(s):
        """[KV, w] per-kv-head scales -> [H, w] head rows (head h uses
        kv head h // grp)."""
        return jnp.broadcast_to(
            s[:, None, :], (KV, grp, s.shape[1])).reshape(H, s.shape[1])

    parts = []
    rparts = []
    for g in range(G):
        q = q_ref[g]                                   # [H, KVD] windowed
        kb = k_scr[pl.ds(g * bs, bs)]                  # [bs, KVD]
        if quant:
            kb = kb.astype(q.dtype)
        sc_g = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [H, bs]
        if quant:
            # K dequant scale is constant along the contracted D axis, so
            # it factors out of the matmul onto the score columns (exact)
            sc_g = sc_g * _exp_heads(ks_scr[:, g * bs:(g + 1) * bs])
        parts.append(sc_g)
        if R is not None:
            rparts.append(jax.lax.dot_general(
                q, ring_plane(rk_ref, g), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))   # [H, R]
    sc = jnp.concatenate(parts, axis=0) * sm_scale     # [G*H, bs]

    # per-row (seq, head) metadata at [G*H, 1]
    def per_seq(vals_fn):
        return jnp.concatenate(
            [jnp.full((H, 1), vals_fn(i * G + g), jnp.float32)
             for g in range(G)], axis=0)
    pos_rows = per_seq(lambda s: starts_ref[s].astype(jnp.float32))
    len_rows = per_seq(lambda s: lens_ref[s].astype(jnp.float32))
    col = jax.lax.broadcasted_iota(jnp.int32, (G * H, bs), 1) \
        .astype(jnp.float32)
    dist = pos_rows - col
    mask = col < len_rows
    if window is not None:
        mask = jnp.logical_and(mask, dist < window)
    if use_alibi:
        slope_rows = jnp.concatenate(
            [slopes_ref[...][:, None] for _ in range(G)], axis=0)
        sc = sc - slope_rows * dist
    sc = jnp.where(mask, sc, _NEG_INF)
    if R is not None:
        rsc = jnp.concatenate(rparts, axis=0) * sm_scale   # [G*H, R]
        r = jax.lax.broadcasted_iota(jnp.int32, (G * H, R), 1) \
            .astype(jnp.float32)
        rdist = rcount_ref[0].astype(jnp.float32) - 1.0 - r
        rmask = jnp.logical_and(r < rcount_ref[0], len_rows > 0)
        if window is not None:
            rmask = jnp.logical_and(rmask, rdist < window)
        if use_alibi:
            rsc = rsc - slope_rows * rdist
        rsc = jnp.where(rmask, rsc, _NEG_INF)
        full = jnp.concatenate([sc, rsc], axis=1)      # [G*H, bs + R]
    else:
        full = sc
    m = jnp.max(full, axis=1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(jnp.where(jnp.isfinite(full), full - m_safe, _NEG_INF))
    l = jnp.sum(p, axis=1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)               # idle slots emit 0

    for g in range(G):
        vb = v_scr[pl.ds(g * bs, bs)]
        rows = slice(g * H, (g + 1) * H)
        pg = p[rows, :bs]
        if quant:
            # V dequant scale folds onto the probability columns
            pg = pg * _exp_heads(vs_scr[:, g * bs:(g + 1) * bs])
            vb = vb.astype(q_ref.dtype)
        pv = jax.lax.dot_general(
            pg.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [H, KVD]
        if R is not None:
            rvb = ring_plane(rv_ref, g)
            pv = pv + jax.lax.dot_general(
                p[rows, bs:].astype(rvb.dtype), rvb,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        o_ref[g] = (pv / l_safe[rows]).astype(o_ref.dtype)


def _flash_decode_grouped(qw, kp_flat, vp_flat, fetch, start_pos, seq_lens,
                          *, bs, H, KV, D, sm_scale, slopes, use_alibi,
                          window, ring_k, ring_v, ring_full, ring_layer,
                          ring_count, pool_full, pool_layer, scales_full,
                          k_scales, v_scales, out_dtype, interpret):
    """Grouped-decode dispatch: qw [S, H, KV*D] lane-windowed; whole
    contexts (linear layout, one block per sequence) stream via manual
    DMA, G sequences per grid step. The decode-loop ring arrives as the
    FULL [R, L, 2, S, KVD] carry — the BlockSpec picks this layer's k/v
    planes, so no per-layer slice/transpose ever materializes in HBM."""
    S = qw.shape[0]
    KVD = KV * D
    quant = kp_flat.dtype == jnp.int8
    if quant and not interpret and (KVD % 128 or bs % 128):
        # the manual-DMA path slices [off : off+n] windows out of larger
        # arrays: int8 rows need (32, 128)-tile-aligned slice shapes and
        # the f32 scale windows need 128-lane-aligned offsets/widths —
        # block offsets are block_id * block_size, so block_size % 128
        # covers both. Real serving shapes (KV*D >= 512, linear-layout
        # blocks sized to max context) satisfy this naturally.
        raise ValueError(
            f"int8 grouped decode requires KV*D ({KVD}) and block_size "
            f"({bs}) to be multiples of 128 (Mosaic DMA tiling); use an "
            f"aligned block_size or attention_impl='dense'")
    itemsize = kp_flat.dtype.itemsize
    # VMEM budget: k+v scratch is G * bs * KVD * itemsize * 2 (+ the
    # [KV, G*bs] f32 scale scratches in int8 mode)
    budget = 10 << 20
    per_seq = 2 * bs * KVD * itemsize + (2 * KV * bs * 4 if quant else 0)
    G = max(1, min(8, budget // max(1, per_seq)))
    while S % G:
        G -= 1
    if ring_full is not None:
        R = ring_full.shape[0]
        ring5d = True
    elif ring_k is not None:
        R = ring_k.shape[1]
        ring5d = False
    else:
        R = None
        ring5d = False

    # shape and layer range were checked by flash_paged_attention
    use_pool_full = pool_full is not None and pool_layer is not None
    if ring5d:
        if ring_full.ndim != 5 or ring_full.shape[2] != 2:
            raise ValueError(
                f"ring_full must be [R, L, 2, S, KVD], got "
                f"{ring_full.shape}")
        if not 0 <= int(ring_layer) < ring_full.shape[1]:
            raise ValueError(
                f"ring_layer {ring_layer} out of range for L = "
                f"{ring_full.shape[1]}")
        # over an int8 pool the ring stays in the COMPUTE dtype (= qw's);
        # otherwise it must share the pool's dtype (never cast)
        expect = qw.dtype if quant else (
            pool_full.dtype if use_pool_full else kp_flat.dtype)
        if ring_full.dtype != expect:
            raise ValueError(
                f"ring_full dtype {ring_full.dtype} != expected {expect} "
                f"(the grouped kernel does not cast the full ring)")
    # copy-tile rows for the seq_len-bounded path: the largest 128-multiple
    # dividing bs (DMA offsets stay (int8: 32, else 8/16)x128-tile aligned);
    # blocks under 128 rows stream whole (already small)
    ts = next((d for d in (256, 128) if bs % d == 0), bs)
    kernel = functools.partial(
        _decode_grouped_kernel, G=G, bs=bs, ts=ts, H=H, KV=KV, D=D,
        sm_scale=float(sm_scale), use_alibi=use_alibi, window=window, R=R,
        ring5d=ring5d, use_pool_full=use_pool_full, quant=quant,
        sc_full=scales_full is not None)

    in_specs = [
        pl.BlockSpec((G, H, KVD), lambda i, *_: (i, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    if use_pool_full:
        # the un-sliced [L, 2, slots, KVD] pool; the layer offset lives in
        # the kernel's DMA source (vp operand is a placeholder)
        operands = [qw.reshape(S, H, KVD), pool_full,
                    jnp.zeros((8, _LANES), pool_full.dtype)]
    else:
        operands = [qw.reshape(S, H, KVD), kp_flat, vp_flat]
    if ring5d:
        # the layer index comes from scalar prefetch (refs[5]) so the ring
        # index maps — like the pool DMA source — stay layer-invariant and
        # every layer shares one compiled kernel
        rk_spec = pl.BlockSpec(
            (R, 1, 1, G, KVD), lambda i, *refs: (0, refs[5][0], 0, i, 0))
        rv_spec = pl.BlockSpec(
            (R, 1, 1, G, KVD), lambda i, *refs: (0, refs[5][0], 1, i, 0))
        in_specs += [rk_spec, rv_spec]
        operands += [ring_full, ring_full]
    elif R is not None:
        ring_spec = pl.BlockSpec((G, R, KVD), lambda i, *_: (i, 0, 0))
        in_specs += [ring_spec, ring_spec]
        operands += [ring_k.astype(kp_flat.dtype),
                     ring_v.astype(vp_flat.dtype)]
    else:
        # dummy tiny operands keep one kernel signature
        z = jnp.zeros((S, 8, KVD), kp_flat.dtype)
        in_specs += [pl.BlockSpec((G, 8, KVD), lambda i, *_: (i, 0, 0))] * 2
        operands += [z, z]
    if quant:
        # int8 scale windows: the full [L, 2, KV, slots] array rides twice
        # (k/v planes picked in-kernel) or the per-layer [KV, slots] pair
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        if scales_full is not None:
            operands += [scales_full, scales_full]
        else:
            operands += [k_scales.astype(jnp.float32),
                         v_scales.astype(jnp.float32)]

    # host-side run check: a group whose G block ids are consecutive AND
    # whose sequences are all within ONE copy tile of full takes the
    # single-DMA fast path (the tiled copy could save at most ts rows per
    # sequence there — not worth G x nt DMA issues in the near-full
    # steady state); shorter groups go through the tiled copy so HBM
    # reads stop at each sequence's settled length (seq_len-bounded
    # block reads)
    fg = fetch.astype(jnp.int32).reshape(S // G, G)
    contig = jnp.all(
        fg == fg[:, :1] + jnp.arange(G, dtype=jnp.int32)[None, :],
        axis=1)
    near_full = jnp.all(
        seq_lens.astype(jnp.int32).reshape(S // G, G) > bs - ts, axis=1)
    contig = jnp.logical_and(contig, near_full).astype(jnp.int32)

    scr_dtype = pool_full.dtype if use_pool_full else kp_flat.dtype
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(S // G,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((G, H, KVD), lambda i, *_: (i, 0, 0)),
        scratch_shapes=(
            [pltpu.VMEM((G * bs, KVD), scr_dtype),
             pltpu.VMEM((G * bs, KVD), scr_dtype)]
            + ([pltpu.VMEM((KV, G * bs), jnp.float32)] * 2 if quant else [])
            + [pltpu.SemaphoreType.DMA((2 * G,))]
            + ([pltpu.SemaphoreType.DMA((2 * G + 2,))] if quant else [])
        ),
    )
    layer_idx = int(pool_layer) if use_pool_full else (
        int(ring_layer) if ring5d else 0)
    if use_pool_full and ring5d and int(pool_layer) != int(ring_layer):
        raise ValueError("pool_layer and ring_layer must match (one layer "
                         "index drives both prefetch-indexed operands)")
    prefetch = [start_pos.astype(jnp.int32), fetch.astype(jnp.int32),
                seq_lens.astype(jnp.int32),
                (jnp.reshape(ring_count, (1,)).astype(jnp.int32)
                 if ring_count is not None else jnp.zeros((1,), jnp.int32)),
                contig, jnp.full((1,), layer_idx, jnp.int32), slopes]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, KVD), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, *operands)
    return out[:, None]                                 # [S, 1, H, KVD]


def flash_paged_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                          v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                          start_pos: jnp.ndarray, seq_lens: jnp.ndarray,
                          *, block_size: int,
                          sm_scale: Optional[float] = None,
                          alibi_slopes: Optional[jnp.ndarray] = None,
                          sliding_window: Optional[int] = None,
                          ring_k: Optional[jnp.ndarray] = None,
                          ring_v: Optional[jnp.ndarray] = None,
                          ring_count: Optional[jnp.ndarray] = None,
                          ring_full: Optional[jnp.ndarray] = None,
                          ring_layer: int = 0,
                          pool_full: Optional[jnp.ndarray] = None,
                          pool_layer: Optional[int] = None,
                          scales_full: Optional[jnp.ndarray] = None,
                          k_scales: Optional[jnp.ndarray] = None,
                          v_scales: Optional[jnp.ndarray] = None,
                          num_kv_heads: Optional[int] = None,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """Flash attention over paged KV.

    Args:
      q: [S, C, H, D] — C query tokens per slot (1 for pure decode;
        SplitFuse prefill chunks are larger). The step's K/V must ALREADY
        be in the pool (causal masking handles the chunk interior), except
        in ring mode where the loop's tokens live in ring_k/ring_v.
      k_pool/v_pool: [slots, KV*D] flat token rows (or [slots, KV, D],
        viewed flat) with slots = (num_blocks + 1) * block_size (trailing
        trash block).
      block_tables: [S, MAXB] int32 — pool block id per sequence block.
      start_pos: [S] int32 — absolute position of q[s, 0].
      seq_lens: [S] int32 — settled context length (0 marks an idle slot,
        which emits zeros). In ring mode this EXCLUDES the ring tokens.
      ring_k/ring_v: optional [S, R, KV*D] decode-loop ring buffers;
        ring_count: tokens valid in the ring.
      ring_full/ring_layer: the PREFERRED ring form — the full
        [R, L, 2, S, KV*D] decode-loop carry plus this call's (static)
        layer index; the grouped decode path selects the layer/kv planes
        in its BlockSpec, so no per-layer slice/transpose materializes.
        Must share the pool's dtype (never cast).
      pool_full/pool_layer: the PREFERRED pool form, decode and prefill
        — the un-sliced [L, 2, slots, KV*D] pool plus the (static) layer
        index. Both kernels then take the WHOLE pool as their operand and
        pick (layer, k/v) themselves: the grouped path inside its DMA
        source, the BlockSpec path in its index map. A Pallas operand is
        a whole buffer, so a model-level pool[layer, 0/1] slice makes XLA
        copy that plane out of the pool before every call (2 L planes a
        step = the pool read and written once). When both full forms are
        given the two layer indices must match. k_pool/v_pool remain
        required but then give only shape and dtype (dead code under
        jit); alone, they are the operands — the form for a caller that
        holds one layer's planes.
      alibi_slopes: optional [H] f32 — in-kernel ALiBi bias (falcon/bloom).
      scales_full / k_scales+v_scales: int8-pool dequantization scales
        (kv_quant.py layout): ``scales_full`` [L, 2, KV, slots] rides whole
        with the layer picked in-kernel; ``k_scales``/``v_scales``
        [KV, slots] are the per-layer form for direct callers. Scales are
        per (token-row, kv-head); the kernel multiplies SCORE columns by
        the K scale and probability columns by the V scale — exact, and no
        dequantized K/V tile ever materializes. q then stays in its own
        (compute) dtype, and the decode-ring stays unquantized.

    Returns [S, C, H, D] attention outputs in q.dtype. HBM traffic per
    step is O(sum of live blocks) of UNPADDED rows.
    """
    if interpret is None:
        from . import default_interpret
        interpret = default_interpret()
    S, C, H, D = q.shape
    if k_pool.ndim == 3:
        KV = k_pool.shape[1]
        k_pool = k_pool.reshape(k_pool.shape[0], -1)
        v_pool = v_pool.reshape(v_pool.shape[0], -1)
    else:
        if num_kv_heads is None:
            raise ValueError("num_kv_heads required with a flat 2-D pool")
        KV = num_kv_heads
    slots, KVD = k_pool.shape
    if KVD != KV * D:
        raise ValueError(f"pool rows {KVD} != KV*D = {KV * D}")
    bs = block_size
    if H % KV:
        raise ValueError(f"GQA requires H % KV == 0 ({H}/{KV})")
    if slots % bs:
        raise ValueError(
            f"pool slots ({slots}) must be a multiple of block_size ({bs}); "
            f"allocate (num_blocks+1)*block_size with a trailing trash block")
    maxb = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    g = H // KV
    use_pool_full = pool_full is not None and pool_layer is not None
    if use_pool_full:
        if pool_full.ndim != 4 or pool_full.shape[1:] != (2, slots, KVD) \
                or pool_full.dtype != k_pool.dtype:
            raise ValueError(
                f"pool_full must be {k_pool.dtype}[L, 2, {slots}, {KVD}], "
                f"got {pool_full.dtype}{list(pool_full.shape)}")
        if not 0 <= int(pool_layer) < pool_full.shape[0]:
            raise ValueError(
                f"pool_layer {pool_layer} out of range for L = "
                f"{pool_full.shape[0]}")

    # int8 pool: scales required; normalize to the per-layer [KV, slots]
    # form for the BlockSpec (prefill) path — the grouped decode path
    # prefers scales_full (layer picked inside the DMA source)
    quant = k_pool.dtype == jnp.int8
    if quant:
        if scales_full is not None:
            if scales_full.ndim != 4 or scales_full.shape[1] != 2 \
                    or scales_full.shape[2] != KV \
                    or scales_full.shape[3] != slots:
                raise ValueError(
                    f"scales_full must be [L, 2, {KV}, {slots}], got "
                    f"{scales_full.shape}")
            li = int(pool_layer) if pool_layer is not None else 0
            if k_scales is None:
                k_scales = scales_full[li, 0]
                v_scales = scales_full[li, 1]
        if k_scales is None or v_scales is None:
            raise ValueError(
                "an int8 k_pool needs scales (scales_full or "
                "k_scales+v_scales, see kv_quant.py)")
        if k_scales.shape != (KV, slots):
            raise ValueError(
                f"k_scales must be [{KV}, {slots}], got {k_scales.shape}")
        compute_dt = q.dtype if q.dtype != jnp.int8 else jnp.bfloat16
    elif scales_full is not None or k_scales is not None:
        raise ValueError("KV scales passed but the pool is not int8")
    else:
        compute_dt = k_pool.dtype

    # processing granularity decouples from the allocator's block size:
    # decode (C==1, scratch is tiny) streams each block whole — one DMA per
    # sequence with the linear one-block-per-seq layout; prefill processes
    # blocks in sub-tiles so KV tiles + the H*Cb softmax scratch fit VMEM.
    if C == 1:
        # whole blocks, but capped so a K/V tile stays ~<=2 MB of VMEM
        # (large linear block_size x wide rows would blow the budget)
        cap = max(256, (2 << 20) // (KVD * k_pool.dtype.itemsize))
        pbs = next(d for d in range(min(bs, cap), 0, -1) if bs % d == 0)
    else:
        pbs = next(d for d in range(min(bs, 256), 0, -1) if bs % d == 0)
    factor = bs // pbs
    maxb_v = maxb * factor
    nb_pool = slots // pbs

    # query-chunk tiling: scratch rows are H*Cb, so bound Cb to keep the
    # online-softmax state (m/l at 128 lanes + f32 acc over KV*D) plus the
    # pipelined KV tiles well under the 16 MB VMEM budget
    kv_tile_bytes = 4 * pbs * KVD * 2                   # 2x dbl-buffer, k+v
    row_bytes = (2 * _LANES + KVD) * 4 + 4 * KVD * q.dtype.itemsize
    row_budget = max(1 << 20, 8 * (1 << 20) - kv_tile_bytes)
    Cb = min(C, max(8, (row_budget // (H * row_bytes)) // 8 * 8))
    nCb = -(-C // Cb)

    nlive = jnp.minimum((seq_lens + pbs - 1) // pbs,
                        maxb_v).astype(jnp.int32)
    qcs = jnp.arange(nCb, dtype=jnp.int32)[None, :]         # [1, nCb]
    # per-(seq, q-chunk) live range: blocks past the chunk's last query
    # position are dead by causality (big win for early prefill chunks)
    chunk_end = start_pos[:, None] + (qcs + 1) * Cb         # exclusive
    hi = jnp.minimum(nlive[:, None], (chunk_end - 1) // pbs + 1)
    hi = jnp.maximum(hi, 0).astype(jnp.int32)               # [S, nCb]
    # sliding window: blocks entirely below every query's window are dead
    if sliding_window is not None:
        first_q = start_pos[:, None] + qcs * Cb
        lo = jnp.maximum(first_q - sliding_window + 1, 0) // pbs
        lo = jnp.minimum(lo.astype(jnp.int32), jnp.maximum(hi - 1, 0))
    else:
        lo = jnp.zeros_like(hi)
    # dead steps re-fetch a live block: no new DMA
    jj = jnp.arange(maxb_v, dtype=jnp.int32)[None, :]
    jjc = jnp.clip(jj, 0, jnp.maximum(nlive[:, None] - 1, 0))
    fetch = (jnp.take_along_axis(block_tables.astype(jnp.int32),
                                 jjc // factor, axis=1) * factor
             + jjc % factor)

    use_alibi = alibi_slopes is not None
    slopes = (jnp.asarray(alibi_slopes, jnp.float32) if use_alibi
              else jnp.zeros((H,), jnp.float32))

    # ring_full [R, L, 2, S, KVD] + ring_layer: the kernel's BlockSpec
    # selects the layer/kv planes itself (the grouped path) — no per-layer
    # host-side slice/transpose ever materializes. ring_k/ring_v
    # [S, R, KVD] remain for the legacy per-sequence path.
    has_ring = ring_k is not None or ring_full is not None
    if has_ring and C != 1:
        raise ValueError("ring decode requires C == 1 (pure decode steps)")
    if ring_k is not None and ring_k.shape[2] != KVD:
        raise ValueError(f"ring rows must be flat [S, R, {KVD}]")
    if ring_full is not None and ring_full.shape[4] != KVD:
        raise ValueError(f"ring_full must be [R, L, 2, S, {KVD}]")
    R = (ring_k.shape[1] if ring_k is not None
         else ring_full.shape[0] if ring_full is not None else None)

    windowed = C == 1
    if windowed:
        # lane-window q: row (h, c) carries q[s, c, h] in lane window
        # (h // g) * D, zeros elsewhere — one matmul covers every head
        # (module docstring). Tiny next to KV traffic at decode.
        sel = (jnp.arange(KV)[None, :] == (jnp.arange(H) // g)[:, None])
        qw = (q.swapaxes(1, 2)[:, :, :, None, :]
              * sel[None, :, None, :, None].astype(q.dtype))  # [S,H,C,KV,D]
        qw = qw.reshape(S, H, C, KVD).astype(compute_dt)
        row_lanes = KVD
        if maxb_v == 1 and (quant or KVD % _LANES == 0):
            # linear layout, whole context in one block: the grouped
            # kernel processes several sequences per grid step with manual
            # async DMAs — the per-grid-step fixed cost was the decode wall.
            # Its DMAs slice row windows out of the HBM pool, which Mosaic
            # only takes at 128-lane rows: narrower bf16 rows (one 64-wide
            # kv head per chip under tp) go through the BlockSpec path
            # below, whose blocks span the whole row. int8 rows that narrow
            # have no path at all and raise inside the grouped dispatch.
            out = _flash_decode_grouped(
                qw.reshape(S, H, KVD), k_pool, v_pool, fetch[:, 0],
                start_pos, seq_lens, bs=pbs, H=H, KV=KV, D=D,
                sm_scale=sm_scale, slopes=slopes, use_alibi=use_alibi,
                window=(int(sliding_window) if sliding_window is not None
                        else None),
                ring_k=ring_k, ring_v=ring_v,
                ring_full=ring_full, ring_layer=int(ring_layer),
                ring_count=(ring_count if has_ring else None),
                pool_full=pool_full, pool_layer=pool_layer,
                # the full-scales form indexes layers with the same
                # prefetched layer id as the full pool — without pool_full
                # that id defaults to 0, so fall back to the (already
                # layer-sliced) per-layer scales instead
                scales_full=(scales_full
                             if quant and pool_full is not None
                             and pool_layer is not None else None),
                k_scales=k_scales if quant else None,
                v_scales=v_scales if quant else None,
                out_dtype=q.dtype, interpret=interpret)
            out = out.reshape(S, 1, H, KVD).swapaxes(1, 2)  # [S, H, 1, KVD]
            head_win = (jnp.arange(H) // g)[:, None] * D \
                + jnp.arange(D)[None, :]
            out = jnp.take_along_axis(out, head_win[None, :, None, :],
                                      axis=3)
            return jnp.moveaxis(out, 1, 2)              # [S, 1, H, D]
    else:
        qw = q.swapaxes(1, 2).astype(compute_dt)       # [S, H, C, D]
        row_lanes = D

    kernel = functools.partial(
        _paged_kernel, bs=pbs, Cb=Cb, nCb=nCb, H=H, KV=KV, D=D,
        sm_scale=float(sm_scale), use_alibi=use_alibi,
        window=int(sliding_window) if sliding_window is not None else None,
        R=R, windowed=windowed, quant=quant)

    n_pref = 7 if has_ring else 5

    def _kv_block(s, qc, j, *pref):
        fetch_ref, lo_ref, hi_ref = pref[1], pref[2], pref[3]
        # clamp into this (s, qc)'s live range so dead grid steps (incl.
        # the ring round) revisit a fetched block (no DMA) instead of
        # pulling a new one
        sq = s * nCb + qc
        jc = jnp.clip(j, lo_ref[sq], jnp.maximum(hi_ref[sq] - 1, 0))
        return fetch_ref[s * maxb_v + jc]

    def block_index(s, qc, j, *pref):
        return (_kv_block(s, qc, j, *pref), 0, 0)

    # q rows for chunk qc must be one contiguous [H*Cb] row block: reorder
    # chunk-major (pad C up to nCb*Cb first; padded rows compute garbage
    # nobody reads — their rows are sliced off after the call)
    Cpad = nCb * Cb
    if nCb == 1:
        qw = qw.reshape(S, H * C, row_lanes)
    else:
        if Cpad != C:
            qw = jnp.pad(qw, ((0, 0), (0, 0), (0, Cpad - C), (0, 0)))
        qw = qw.reshape(S, H, nCb, Cb, row_lanes).swapaxes(1, 2).reshape(
            S, nCb * H * Cb, row_lanes)
    q_spec = pl.BlockSpec((1, H * Cb, row_lanes),
                          lambda s, qc, j, *_: (s, qc, 0))
    o_spec = pl.BlockSpec((1, H * Cb, row_lanes),
                          lambda s, qc, j, *_: (s, qc, 0))

    if use_pool_full:
        # the WHOLE pool is the K and the V operand, viewed
        # [L, 2, nb, pbs, KVD] (the same split of the slots axis, no data
        # moves); the index map picks (layer, k/v, block) and the squeezed
        # leading axes leave the kernel its [1, pbs, KVD] refs. A Pallas
        # operand is a whole buffer, so handing it pool[layer, x] made XLA
        # copy that plane out of the pool first: 2 L planes = the whole
        # pool read and written once per step
        li = int(pool_layer)
        pool5 = pool_full.reshape(pool_full.shape[0], 2, nb_pool, pbs, KVD)
        in_specs = [q_spec] + [
            pl.BlockSpec(
                (None, None, 1, pbs, KVD),
                lambda s, qc, j, *pref, x=x:
                    (li, x, _kv_block(s, qc, j, *pref), 0, 0))
            for x in (0, 1)]
        operands = [qw, pool5, pool5]
    else:
        # direct callers that hold one layer's planes only
        in_specs = [q_spec] + [pl.BlockSpec((1, pbs, KVD), block_index)] * 2
        operands = [qw, k_pool.reshape(nb_pool, pbs, KVD),
                    v_pool.reshape(nb_pool, pbs, KVD)]
    if quant:
        # per-layer [KV, slots] scales re-laid [nb, KV, pbs] so a block's
        # minor dims are (KV, pbs) proper tiles; the same clamped block
        # index feeds both the KV tile and its scale window
        ksb = k_scales.astype(jnp.float32).reshape(
            KV, nb_pool, pbs).swapaxes(0, 1)
        vsb = v_scales.astype(jnp.float32).reshape(
            KV, nb_pool, pbs).swapaxes(0, 1)
        in_specs += [pl.BlockSpec((1, KV, pbs), block_index)] * 2
        operands += [ksb, vsb]
    grid = (S, nCb, maxb_v + 1 if has_ring else maxb_v)
    if has_ring:
        if ring_k is None:
            # legacy per-sequence path fed from the 5-D ring: materialize
            # the per-layer planes (the grouped fast path above avoids it)
            ring_k = jnp.moveaxis(ring_full[:, ring_layer, 0], 0, 1)
            ring_v = jnp.moveaxis(ring_full[:, ring_layer, 1], 0, 1)
        ring_spec = pl.BlockSpec((1, R, KVD),
                                 lambda s, qc, j, *_: (s, 0, 0))
        in_specs += [ring_spec, ring_spec]
        operands += [ring_k.astype(compute_dt),
                     ring_v.astype(compute_dt)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pref,
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        scratch_shapes=[
            pltpu.VMEM((H * Cb, _LANES), jnp.float32),
            pltpu.VMEM((H * Cb, _LANES), jnp.float32),
            pltpu.VMEM((H * Cb, row_lanes), jnp.float32),
        ],
    )
    prefetch = [start_pos.astype(jnp.int32), fetch.reshape(-1),
                lo.reshape(-1), hi.reshape(-1), slopes]
    if has_ring:
        prefetch.append(jnp.reshape(ring_count, (1,)).astype(jnp.int32))
        prefetch.append(seq_lens.astype(jnp.int32))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qw.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*prefetch, *operands)
    # undo chunk-major row order, then (windowed mode) slice each head's
    # lane window out of the [KV*D]-wide accumulator rows
    if nCb > 1:
        out = out.reshape(S, nCb, H, Cb, row_lanes).swapaxes(1, 2).reshape(
            S, H, Cpad, row_lanes)[:, :, :C]
    else:
        out = out.reshape(S, H, C, row_lanes)
    if windowed:
        head_win = (jnp.arange(H) // g)[:, None] * D \
            + jnp.arange(D)[None, :]
        out = jnp.take_along_axis(out, head_win[None, :, None, :], axis=3)
    return jnp.moveaxis(out, 1, 2)                      # [S, C, H, D]
