"""Ragged model runners — paged-KV forward passes over fixed-shape batches.

Analogue of the reference's v2 model implementations + ragged kernels
(``inference/v2/model_implementations/``, ``inference/v2/kernels/ragged_ops/``:
kv rotary/copy, blocked flash, logits_gather). One jitted ``step`` does, per
layer: KV append (one scatter into the flat blocked cache), context gather
through the block table (one take), masked attention, MLP — then gathers
logits for each slot's last scheduled token only (the reference's
``logits_gather``).

Shapes are compile-time constant: ``[max_seqs, chunk_size]`` queries against
``[max_seqs, max_context]`` gathered KV. Padded query positions scatter into
the trash block (the pool's last) so they can never corrupt live
sequences' KV.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...models.gpt2 import GPT2Config
from ...parallel.tp_rules import MODEL_AXIS
from ...telemetry.trace import region
from ...utils.jax_compat import manual_axes, shard_map
from .config import RaggedInferenceConfig
from .kv_quant import KVPool, RingKV, pool_parts
from .kv_write import store_rows, write_plan
from .sampling import SAMPLE_CANDIDATES
from .seq_parallel import (SEQ_AXIS, combine_decode_stats, ring_all_gather,
                           seq_axis_active)


# --------------------------------------------------------------------- #
# on-device per-slot token selection (sampling.py has the host half)
# --------------------------------------------------------------------- #


def _sample_keys(seeds, positions):
    """Per-slot threefry keys as a pure function of (seed, absolute
    position of the token being selected) — no key state in any carry,
    so streams are identical across pipeline depths, fused-vs-per-step
    paths and drain/replay restarts (sampling.py has the contract)."""
    def one(s, p):
        return jax.random.fold_in(jax.random.PRNGKey(s), p)
    return jax.vmap(one)(seeds, positions)


def _select_tokens(logits, keys, temps, top_ks, top_ps, *, cand):
    """Per-slot temperature/top-k/top-p categorical [S, V] -> [S].

    A slot with ``temps[i] <= 0`` short-circuits to ``argmax`` — the
    temperature→0 parity oracle (bit-identical to the greedy programs,
    including first-index tie-breaks: both ``argmax`` and ``top_k``
    rank ties by index). Sampling draws from a STATIC ``cand``-wide
    candidate set (the top-``cand`` logits; top-p re-normalizes within
    it) via the gumbel trick, so the per-step noise tensor is
    [S, cand], never [S, V].
    """
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    vals, idxs = jax.lax.top_k(logits, cand)            # [S, cand]
    x = (vals / jnp.maximum(temps[:, None], 1e-6)).astype(jnp.float32)
    ar = jnp.arange(cand, dtype=jnp.int32)[None, :]
    x = jnp.where((top_ks[:, None] > 0) & (ar >= top_ks[:, None]),
                  -jnp.inf, x)
    p = jax.nn.softmax(x, axis=-1)
    mass_before = jnp.cumsum(p, axis=-1) - p
    x = jnp.where(mass_before < top_ps[:, None], x, -jnp.inf)  # keeps rank 0
    g = jax.vmap(lambda k: jax.random.gumbel(k, (cand,), jnp.float32))(keys)
    choice = jnp.argmax(x + g, axis=-1)
    samp = jnp.take_along_axis(idxs, choice[:, None], axis=-1)[:, 0]
    return jnp.where(temps <= 0.0, greedy_tok, samp.astype(jnp.int32))


def _chosen_logprob(logits, tok):
    """log p(tok) under the UNMODIFIED model distribution (raw softmax
    of the full-width logits) — the convention ``logprobs=True``
    requests surface (docs/serving.md)."""
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logits.astype(jnp.float32), tok[:, None].astype(jnp.int32),
        axis=-1)[:, 0]
    return picked - lse


class RaggedBatch(NamedTuple):
    """Device-side view of one scheduled step (all shapes static)."""
    tokens: jnp.ndarray        # [S, C] int32 (padded with 0)
    start_pos: jnp.ndarray     # [S] int32 — absolute pos of tokens[s, 0]
    n_tokens: jnp.ndarray      # [S] int32 — valid tokens this step (0 = idle)
    block_tables: jnp.ndarray  # [S, MAXB] int32 (padded with 0)
    # [S] int32 — each row's row of the recurrent state pool (idle rows:
    # the pool's last, idle row); None for a model without recurrent layers
    state_slots: Any = None
    # where this step's rows go in the pool (kv_write.WritePlan): the step
    # programs make it once for all layers; None (a mixer called with a
    # bare batch, as the parity tests do) = made at each store
    write_plan: Any = None
    # a model with sliding-window layers: the table [S, MAXB] of a window
    # layer, a FUNCTION of ``state_slots`` (``window_tables``), and the
    # step's plan over the window pool; both made once a program, or at
    # each use from a bare batch
    window_tables: Any = None
    window_plan: Any = None


def window_tables(state_slots, kv, cfg: RaggedInferenceConfig):
    """The block table [S, MAXB] every sliding-window layer reads and
    writes through: the sequence in slot ``s`` keeps logical block ``b``
    in block ``s * R + b % R`` of the window pool ``kv.window``, R the
    blocks a slot owns (``kv_cache.window_blocks``, read back from the
    pool's shape). Nothing is allocated: the table is arithmetic on the
    slot, so it is built where the step's other tables arrive."""
    R = kv.window.shape[2] // (cfg.block_size * (cfg.max_seqs + 1))
    b = jnp.arange(cfg.max_blocks_per_seq, dtype=jnp.int32)
    return state_slots[:, None].astype(jnp.int32) * R + (b % R)[None, :]


def _tables(batch, kv, cfg, window: bool):
    """The table a layer goes by: the allocator's, or the slot's."""
    if not window:
        return batch.block_tables
    if batch.window_tables is not None:
        return batch.window_tables
    return window_tables(batch.state_slots,
                         kv.pool if isinstance(kv, RingKV) else kv, cfg)


def _store_step_rows(kv, li, rows, batch, cfg, kv_heads=1, shard=None,
                     window: bool = False):
    """This step's fresh ``rows`` [P, S, C, W] into layer ``li`` of the
    pool (of the window pool with ``window``): the one writer
    (kv_write.py), at the batch's plan."""
    plan = batch.window_plan if window else batch.write_plan
    if plan is None:
        plan = write_plan(batch.start_pos, batch.n_tokens,
                          _tables(batch, kv, cfg, window), rows.shape[2],
                          cfg.block_size, pool_parts(kv, window)[0].shape,
                          shard)
    return store_rows(kv, li, rows, plan, kv_heads, window)


# --------------------------------------------------------------------- #
# tensor-parallel seams (inference/v2/tp.py) — every helper is an exact
# no-op outside the TP shard_map region, so single-device programs are
# byte-identical to the pre-TP engine
# --------------------------------------------------------------------- #


def tp_all_reduce(y, cfg: "RaggedInferenceConfig" = None):
    """One of the two canonical per-layer TP collectives: sum the
    row-parallel partial products over the ``model`` axis.

    Schedule selected by ``cfg.tp_comm_overlap`` (docs/serving.md):

      "off" — the monolithic parity oracle: a plain psum, or (with
        ``cfg.tp_quantized_comm``) the legacy monolithic int8 all-gather
        (symmetric per-row scales via the ZeRO++ comm helpers).
      "rs_ag" / "rs_ag_chunked" — the decomposed schedule
        (``comm.decomposed_all_reduce``): chunked ring reduce-scatter +
        ring all-gather ppermute hops XLA can hide under adjacent GEMMs;
        ``tp_quantized_comm`` then fuses int8 quant/dequant with
        per-chunk scales into every hop (EQuARX-grade) instead of
        quantizing once globally.
    """
    if MODEL_AXIS not in manual_axes():
        return y
    quant = cfg is not None and getattr(cfg, "tp_quantized_comm", False)
    mode = getattr(cfg, "tp_comm_overlap", "off") if cfg is not None \
        else "off"
    if mode != "off":
        from ... import comm
        chunks = getattr(cfg, "tp_comm_chunks", 2) \
            if mode == "rs_ag_chunked" else 1
        return comm.decomposed_all_reduce(
            y, axis_name=MODEL_AXIS, chunks=chunks,
            quant_bits=8 if quant else None, log_name="tp_all_reduce")
    if quant:
        from ...runtime.zero.quantized_collectives import (
            _dequant_from_comm, _quant_for_comm)
        q, scale, packed = _quant_for_comm(y, 8)
        gq = jax.lax.all_gather(q, MODEL_AXIS)
        gs = jax.lax.all_gather(scale, MODEL_AXIS)
        return _dequant_from_comm(gq, gs, packed, jnp.float32) \
            .sum(axis=0).astype(y.dtype)
    return jax.lax.psum(y, MODEL_AXIS)


def tp_gather_logits(logits, vocab_size: int):
    """The single pre-sampling collective: all-gather vocab-sharded logits
    to full width. Identity when the unembed was replicated (tied
    embeddings) or outside the TP region."""
    if MODEL_AXIS not in manual_axes() or logits.shape[-1] == vocab_size:
        return logits
    return jax.lax.all_gather(logits, MODEL_AXIS, axis=logits.ndim - 1,
                              tiled=True)


def tp_alibi_slopes(num_heads_local: int):
    """ALiBi slopes for THIS chip's heads. Slope values depend on the
    GLOBAL head index, so inside the TP region the full slope vector is
    built and this chip's window sliced out; single-device this is plainly
    ``alibi_slopes(H)``."""
    from ...models._lm_utils import alibi_slopes
    if MODEL_AXIS not in manual_axes():
        return alibi_slopes(num_heads_local)
    tp = jax.lax.axis_size(MODEL_AXIS)
    full = jnp.asarray(alibi_slopes(num_heads_local * tp), jnp.float32)
    r = jax.lax.axis_index(MODEL_AXIS)
    return jax.lax.dynamic_slice(full, (r * num_heads_local,),
                                 (num_heads_local,))


def _linear(x, p, dtype, row_parallel: bool = False,
            cfg: "RaggedInferenceConfig" = None):
    """Dense apply over a flax {kernel[, bias]} param dict (shared by the
    OPT/Falcon/Phi/Bloom/NeoX/GPT-J runners). ``row_parallel`` marks the
    two per-layer TP reduction sites: the partial product is all-reduced
    BEFORE the (replicated) bias is added once."""
    y = x @ p["kernel"].astype(dtype)
    if row_parallel:
        y = tp_all_reduce(y, cfg)
    if "bias" in p:
        y = y + p["bias"].astype(dtype)
    return y


def _layer_norm(x, p, eps=1e-5):   # GPT2Config.layer_norm_eps default
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gather_ctx(pool, li, batch, cfg, S, KV, D, dtype, window=False):
    """[S, max_context, KV, D] context gathered through the block tables
    (``window``: a sliding-window layer's, through the slots' tables out
    of the window pool; a position whose row has been overwritten lies
    below the window and is masked by the caller). A quantized KVPool is
    dequantized per gathered row (dense/debug path only — the Pallas
    kernel scales scores/probabilities instead)."""
    data, scales = pool_parts(pool, window)
    bs = cfg.block_size
    j = jnp.arange(cfg.max_context, dtype=jnp.int32)
    ctx_idx = _tables(batch, pool, cfg, window)[:, j // bs] * bs + j % bs
    k_ctx = data[li, 0][ctx_idx].reshape(S, -1, KV, D)
    v_ctx = data[li, 1][ctx_idx].reshape(S, -1, KV, D)
    if scales is None:
        return k_ctx.astype(dtype), v_ctx.astype(dtype)
    ks = scales[li, 0].T[ctx_idx]                      # [S, T, KV]
    vs = scales[li, 1].T[ctx_idx]
    k_ctx = (k_ctx.astype(jnp.float32) * ks[..., None]).astype(dtype)
    v_ctx = (v_ctx.astype(jnp.float32) * vs[..., None]).astype(dtype)
    return k_ctx, v_ctx


def _grouped_dense_attention(q, k_ctx, v_ctx, mask, dist, scale, dtype,
                             alibi_slopes):
    """Masked grouped-GQA attention core, shared by the dense (non-kernel)
    paths. q [S, C, H, D]; k/v_ctx [S, T', KV, D]; mask/dist [S, C, T'] (or
    [S, 1, T'] broadcasting over C). KV stays at native width — repeating
    to H heads would multiply the gathered-context traffic by H/KV."""
    S, C, H, D = q.shape
    KV = k_ctx.shape[2]
    g = H // KV
    qg = q.reshape(S, C, KV, g, D)
    s_att = jnp.einsum("sckgd,stkd->skgct", qg, k_ctx) * scale
    s_att = s_att.astype(jnp.float32)
    if alibi_slopes is not None:
        s_att = s_att - alibi_slopes.reshape(KV, g)[None, :, :, None, None] \
            * dist[:, None, None, :, :]
    s_att = jnp.where(mask[:, None, None, :, :], s_att, -jnp.inf)
    p_att = jax.nn.softmax(s_att, axis=-1).astype(dtype)
    # fully-masked rows (idle slots) produce NaN softmax garbage that is
    # never read; keep numerics finite
    p_att = jnp.where(jnp.isnan(p_att), 0, p_att)
    return jnp.einsum("skgct,stkd->sckgd", p_att, v_ctx).reshape(
        S, C, H * D)


def _dense_ring_attention(pool, ring, li, q, batch, cfg, settled_lens,
                          rcount, scale, dtype, alibi_slopes,
                          sliding_window, window, ring_layer):
    """Ring-mode attention without the Pallas kernel (off-TPU path): the
    gathered settled context and the ring concatenate along the context
    axis, with the settled part masked column-exactly at settled_lens.
    ``window``: layer ``li`` of the window pool; ``ring_layer``: its row
    of the ring."""
    S, C, H, D = q.shape
    KV = ring.shape[4] // D
    T = cfg.max_context
    k_ctx, v_ctx = _gather_ctx(pool, li, batch, cfg, S, KV, D, dtype,
                               window)
    R = ring.shape[0]
    ring_k = jnp.moveaxis(ring[:, ring_layer, 0], 0, 1).reshape(S, R, KV, D)
    ring_v = jnp.moveaxis(ring[:, ring_layer, 1], 0, 1).reshape(S, R, KV, D)
    k_full = jnp.concatenate([k_ctx, ring_k.astype(dtype)], axis=1)
    v_full = jnp.concatenate([v_ctx, ring_v.astype(dtype)], axis=1)
    # columns: [0, T) settled (valid below settled_lens), [T, T+R) ring
    # (valid below rcount); ring row r sits dist = rcount-1-r behind query
    jr = jnp.arange(T + R, dtype=jnp.int32)
    dist = jnp.where(jr < T,
                     batch.start_pos[:, None] - jr[None, :],
                     rcount - 1 - (jr[None, :] - T)).astype(jnp.float32)
    mask = jnp.where(jr[None, :] < T,
                     jr[None, :] < settled_lens[:, None],
                     (jr[None, :] - T) < rcount)
    if sliding_window is not None:
        mask = jnp.logical_and(mask, dist < sliding_window)
    return _grouped_dense_attention(q, k_full, v_full, mask[:, None],
                                    dist[:, None], scale, dtype,
                                    alibi_slopes)


def _seq_local_ctx(data, scales, li, tables, cfg, sz, r, dtype,
                   dequant: bool):
    """THIS chip's context slab under the seq-sharded pool: the rows of
    its local blocks, ordered by local chain index — local column
    ``j_loc`` holds chain ordinal ``(j_loc // bs) * sz + r``. Returns
    ``(k_loc, v_loc, kv_scales_or_None, j_g)`` with ``j_g`` the global
    context column of each local column. With ``dequant`` the int8 rows
    come back dequantized to ``dtype`` (decode stats path); otherwise
    raw, so the prefill ring can ship int8 + scale planes separately."""
    bs = cfg.block_size
    nb_loc = cfg.max_blocks_per_seq // sz
    jl = jnp.arange(nb_loc * bs, dtype=jnp.int32)
    o_cols = (jl // bs) * sz + r           # chain ordinal per local col
    blk = tables[:, o_cols]                # [S, T_loc] global block ids
    rows = (blk // sz) * bs + (jl % bs)[None, :]
    k_loc = data[li, 0][rows]              # [S, T_loc, KV*D]
    v_loc = data[li, 1][rows]
    j_g = o_cols * bs + jl % bs
    if scales is None:
        return k_loc.astype(dtype), v_loc.astype(dtype), None, j_g
    ks = scales[li, 0].T[rows]             # [S, T_loc, KV]
    vs = scales[li, 1].T[rows]
    if dequant:
        # rows are flat [KV*D]; scales are per-kv-head — unflatten,
        # scale, reflatten so callers keep the [S, T_loc, KV*D] shape
        S, T = k_loc.shape[:2]
        KV = ks.shape[-1]
        k_loc = (k_loc.reshape(S, T, KV, -1).astype(jnp.float32)
                 * ks[..., None]).reshape(S, T, -1).astype(dtype)
        v_loc = (v_loc.reshape(S, T, KV, -1).astype(jnp.float32)
                 * vs[..., None]).reshape(S, T, -1).astype(dtype)
        return k_loc, v_loc, None, j_g
    return k_loc, v_loc, jnp.concatenate([ks, vs], axis=-1), j_g


def _seq_paged_attention(kv, li, q, k, v, batch, cfg, pos, scale, dtype,
                         alibi_slopes, sliding_window):
    """Context-parallel paged attention: the per-step program's attention
    under the ``seq`` shard_map. ``q``/``k``/``v`` are THIS chip's query
    slice (the step wrapper sliced the chunk chip-major), the pool is
    this chip's round-robin block shard. Three moves, exactly budgeted:

      1. fresh-KV exchange — ONE packed all-gather of ``[k|v]`` in the
         compute dtype reassembles the whole chunk's K/V on every chip;
         each chip then stores ONLY the windows whose block it owns
         (``blk % sz == r``) into its local shard, everything else to its
         local trash block (kv_write.py). Over an int8 pool every chip
         quantizes the full chunk identically, so pool bytes are
         bit-identical to seq=1's.
      2. full-context reconstruction — each chip gathers its local slab
         and a ring of ``sz - 1`` ppermute hops (two per hop over int8:
         data + scale planes) stacks every shard by origin; a static
         reshape/transpose restores exact global position order, and
         dequant happens after, so the reconstructed context is
         bit-identical to the single-chip gather.
      3. the EXACT existing dense grouped-GQA core over (local queries x
         full context) — per-query-slice outputs are therefore bitwise
         equal to the seq=1 program's corresponding columns.

    Returns (kv, y[S, C_local, H*D])."""
    S, C_loc, H, D = q.shape
    KV = k.shape[2]
    bs = cfg.block_size
    sz = jax.lax.axis_size(SEQ_AXIS)
    r = jax.lax.axis_index(SEQ_AXIS)
    C = C_loc * sz
    # the step wrapper shifted start/n by r*C_loc; undo for global views
    n_g = batch.n_tokens + r * C_loc
    start_g = batch.start_pos - r * C_loc
    # ---- 1. fresh-KV exchange + ownership-masked store ----
    fresh = jnp.concatenate([k.reshape(S, C_loc, KV * D),
                             v.reshape(S, C_loc, KV * D)], axis=-1)
    allf = jax.lax.all_gather(fresh, SEQ_AXIS)     # [sz, S, C_loc, 2KVD]
    allf = jnp.moveaxis(allf, 0, 1).reshape(S, C, 2 * KV * D)
    k_all = allf[..., :KV * D]
    v_all = allf[..., KV * D:]
    kv = _store_step_rows(
        kv, li, jnp.stack([k_all, v_all]),
        batch._replace(start_pos=start_g, n_tokens=n_g), cfg, KV, (sz, r))
    data, scales = pool_parts(kv)
    # ---- 2. ring reconstruction of the full context ----
    nb_loc = cfg.max_blocks_per_seq // sz
    T = nb_loc * sz * bs
    k_loc, v_loc, sc_loc, _ = _seq_local_ctx(
        data, scales, li, batch.block_tables, cfg, sz, r, dtype,
        dequant=False)
    slab = jnp.concatenate([k_loc, v_loc], axis=-1)

    def _reorder(st):                    # [sz, S, T_loc, X] -> [S, T, X]
        X = st.shape[-1]
        st = st.reshape(sz, S, nb_loc, bs, X)
        # origin o's slab column (nb, off) IS global position
        # (nb*sz + o)*bs + off — interleave shards block-round-robin
        return jnp.moveaxis(st, 0, 2).reshape(S, T, X)

    ctx = _reorder(ring_all_gather(slab))          # sz-1 ppermute hops
    k_ctx = ctx[..., :KV * D].reshape(S, T, KV, D)
    v_ctx = ctx[..., KV * D:].reshape(S, T, KV, D)
    if scales is None:
        k_ctx = k_ctx.astype(dtype)
        v_ctx = v_ctx.astype(dtype)
    else:
        # int8 scale planes ride the ring as a second per-hop ppermute
        # (the PR 6 quantized-collective shape); dequant AFTER
        # reconstruction = the single-chip gather's exact math
        sc = _reorder(ring_all_gather(sc_loc))     # [S, T, 2KV]
        k_ctx = (k_ctx.astype(jnp.float32)
                 * sc[..., :KV, None]).astype(dtype)
        v_ctx = (v_ctx.astype(jnp.float32)
                 * sc[..., KV:, None]).astype(dtype)
    # ---- 3. the unchanged dense core over the local query slice ----
    j = jnp.arange(T, dtype=jnp.int32)
    dist = (pos[:, :, None] - j[None, None, :]).astype(jnp.float32)
    mask = j[None, None, :] <= pos[:, :, None]
    if sliding_window is not None:
        mask = jnp.logical_and(mask, dist < sliding_window)
    y = _grouped_dense_attention(q, k_ctx, v_ctx, mask, dist, scale,
                                 dtype, alibi_slopes)
    return kv, y


def _seq_dense_ring_attention(pool, ring, li, q, batch, cfg, settled_lens,
                              rcount, scale, dtype, alibi_slopes,
                              sliding_window):
    """Sequence-sharded decode attention for the fused loop: the decode
    query is REPLICATED over ``seq`` (the whole batch is), each chip
    computes partial flash-softmax stats (m, l, acc) over its LOCAL
    settled blocks, and ONE small packed all-gather per layer
    (``combine_decode_stats``) merges them exactly — the FlashDecoding
    split-K identity with the seq shards as the split. The loop's ring
    rows are replicated too (identical fresh K/V on every chip), so
    their stats merge locally with zero extra collectives. Exact up to
    float reassociation (the TP=2 precedent); token parity holds."""
    S, C, H, D = q.shape
    KV = ring.shape[4] // D
    sz = jax.lax.axis_size(SEQ_AXIS)
    r = jax.lax.axis_index(SEQ_AXIS)
    data, scales = pool_parts(pool)
    k_loc, v_loc, _, j_g = _seq_local_ctx(
        data, scales, li, batch.block_tables, cfg, sz, r, dtype,
        dequant=True)
    T_loc = k_loc.shape[1]
    k_loc = k_loc.reshape(S, T_loc, KV, D)
    v_loc = v_loc.reshape(S, T_loc, KV, D)
    g = H // KV
    qg = q.reshape(S, C, KV, g, D)

    def _stats(kk, vv, mask, dist):
        """Partial flash stats over one context piece: kk/vv
        [S, T', KV, D], mask/dist [S, T'] (broadcast over heads and C).
        An empty mask yields (0, 0, -inf) — exactly nothing to merge."""
        s_att = jnp.einsum("sckgd,stkd->skgct", qg, kk) * scale
        s_att = s_att.astype(jnp.float32)
        if alibi_slopes is not None:
            s_att = s_att - alibi_slopes.reshape(KV, g)[
                None, :, :, None, None] * dist[:, None, None, None, :]
        s_att = jnp.where(mask[:, None, None, None, :], s_att, -jnp.inf)
        m = jnp.max(s_att, axis=-1)                       # [S, KV, g, C]
        p = jnp.exp(s_att - jnp.where(jnp.isinf(m), 0.0, m)[..., None])
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("skgct,stkd->skgcd", p,
                         vv.astype(jnp.float32))
        return acc, l, m

    dist_s = (batch.start_pos[:, None] - j_g[None, :]).astype(jnp.float32)
    mask_s = j_g[None, :] < settled_lens[:, None]
    if sliding_window is not None:
        mask_s = jnp.logical_and(mask_s, dist_s < sliding_window)
    num, den, m_c = combine_decode_stats(
        *_stats(k_loc, v_loc, mask_s, dist_s))   # 1 all-gather per layer

    R = ring.shape[0]
    ring_k = jnp.moveaxis(ring[:, li, 0], 0, 1).reshape(S, R, KV, D)
    ring_v = jnp.moveaxis(ring[:, li, 1], 0, 1).reshape(S, R, KV, D)
    jr = jnp.arange(R, dtype=jnp.int32)
    dist_r = jnp.broadcast_to((rcount - 1 - jr)[None, :].astype(
        jnp.float32), (S, R))
    mask_r = jnp.broadcast_to((jr < rcount)[None, :], (S, R))
    if sliding_window is not None:
        mask_r = jnp.logical_and(mask_r, dist_r < sliding_window)
    acc_r, l_r, m_r = _stats(ring_k.astype(dtype), ring_v.astype(dtype),
                             mask_r, dist_r)
    # exact streaming-softmax merge of the (already cross-chip) settled
    # partial with the local ring partial
    m_t = jnp.maximum(m_c, m_r)
    m_ts = jnp.where(jnp.isinf(m_t), 0.0, m_t)
    wc = jnp.exp(m_c - m_ts)
    wr = jnp.exp(m_r - m_ts)
    num = num * wc[..., None] + acc_r * wr[..., None]
    den = den * wc + l_r * wr
    y = jnp.where(den[..., None] > 0,
                  num / jnp.maximum(den, 1e-30)[..., None], 0.0)
    return jnp.moveaxis(y, 3, 1).reshape(S, C, H * D).astype(dtype)


def _attention_impl(cfg: RaggedInferenceConfig) -> str:
    """``cfg.attention_impl`` resolved: "auto" is "paged_flash" on a TPU
    and "dense" elsewhere (interpret-mode Pallas off-TPU would run a
    Python-loop interpreter per layer/step)."""
    impl = cfg.attention_impl
    if impl == "auto":
        impl = "paged_flash" if jax.default_backend() == "tpu" else "dense"
    if impl not in ("paged_flash", "dense"):
        raise ValueError(
            f"attention_impl must be 'auto', 'paged_flash' or 'dense', "
            f"got {cfg.attention_impl!r}")
    return impl


def paged_attention(kv, li, q, k, v, batch: "RaggedBatch",
                    cfg: RaggedInferenceConfig, pos, valid_q, scale, dtype,
                    alibi_slopes=None, sliding_window=None,
                    window_pool: bool = False, ring_layer=None):
    """Append this step's K/V through the block tables, then attend.

    Shared by every ragged runner. q: [S, C, H, D]; k/v: [S, C, KV, D]
    (KV may divide H — GQA). Dispatches on ``cfg.attention_impl``:

      "auto" — "paged_flash" on TPU, "dense" elsewhere.
      "paged_flash" — Pallas flash kernel reading K/V straight through the
        block tables (ops/kernels/paged_attention.py): per-step HBM traffic
        is the LIVE blocks only, no ``max_context`` wall. (Reference:
        inference/v2/kernels/ragged_ops/blocked_flash/.)
      "dense" — gather [S, max_context] context and mask (fallback/debug;
        the round-1 path the kernel replaces).

    ``kv`` is either the pool array, or — inside the fused decode loop —
    a ``(pool, ring, t, rcount)`` tuple (RaggedRunnerBase._decode_loop):
    the pool is then READ-ONLY and this step's K/V goes into the small
    ring buffer at index ``t`` (a cheap dynamic-update-slice instead of
    the TPU scatter slow path), attended by the kernel's ring round. The
    runners thread ``kv`` opaquely, so every family gets the fast path.

    ``window_pool``: layer ``li`` is a sliding-window layer of a model
    that bounds such layers' cache: its rows live in the WINDOW pool
    (``KVPool.window``, the same form) and nowhere else, written and read
    through the slots' tables (``window_tables``), ``li`` its index among
    those layers; the kernels, the writer and the masks are the paged
    pool's. The fused loop's ring holds every attention layer's rows in
    the model's order, so such a model names the layer's row of the ring
    apart (``ring_layer``; ``li`` where None).

    Returns (kv, y[S, C, H*D] in ``dtype``).
    """
    S, C, H, D = q.shape
    KV = k.shape[2]
    impl = _attention_impl(cfg)
    seq_on = seq_axis_active()
    if seq_on:
        # the Pallas kernel indexes a single-chip pool layout; under the
        # seq shard the dense paths reconstruct/merge across chips
        # (config validation already rejects an EXPLICIT paged_flash)
        impl = "dense"

    if isinstance(kv, RingKV):
        ring, t = kv.ring, kv.t
        rl = li if ring_layer is None else ring_layer
        # ring[t, rl, 0/1] <- this step's K/V: the ring is R-LEADING so the
        # per-step write is a leading-index dynamic-update-slice (in-place
        # in the scan carry; a trailing index forced a ring copy per layer).
        # The ring stays UNQUANTIZED (compute dtype) even over an int8
        # pool — its rows are rewritten every loop and quantized at flush.
        with region("kv_write"):
            ring = ring.at[t, rl, 0].set(
                k.reshape(S, KV * D).astype(ring.dtype))
            ring = ring.at[t, rl, 1].set(
                v.reshape(S, KV * D).astype(ring.dtype))
        kv = kv._replace(ring=ring)
        settled_lens = jnp.where(batch.n_tokens > 0,
                                 batch.start_pos - t, 0)
        return kv, _attend(kv, li, q, KV, batch, cfg, pos, settled_lens,
                           scale, dtype, impl, alibi_slopes, sliding_window,
                           window_pool, ring_layer)

    if seq_on:
        return _seq_paged_attention(kv, li, q, k, v, batch, cfg, pos,
                                    scale, dtype, alibi_slopes,
                                    sliding_window)

    with region("kv_write"):
        kv = _store_step_rows(
            kv, li,
            jnp.stack([k.reshape(S, C, KV * D), v.reshape(S, C, KV * D)]),
            batch, cfg, KV, window=window_pool)
    seq_lens = jnp.where(batch.n_tokens > 0,
                         batch.start_pos + batch.n_tokens, 0) \
        if impl == "paged_flash" else None
    return kv, _attend(kv, li, q, KV, batch, cfg, pos, seq_lens, scale,
                       dtype, impl, alibi_slopes, sliding_window,
                       window_pool)


def _attend(kv, li, q, KV, batch: "RaggedBatch",
            cfg: RaggedInferenceConfig, pos, lens, scale, dtype, impl,
            alibi_slopes=None, sliding_window=None,
            window_pool: bool = False, ring_layer=None):
    """The attend-only half of ``paged_attention``, its rows already
    stored: in plane ``li`` of the pool ``kv`` (``lens`` the sequences'
    whole lengths, read by the kernel alone), or, ``kv`` a ``RingKV``,
    in the ring (``lens`` the SETTLED lengths). ``impl`` is
    ``_attention_impl``'s answer after the caller's own overrides. A
    sequence whose ``lens`` is 0 is attended over nothing, which is how
    ``sparse_paged_attention`` sends the call only the sequences below
    ``dense_len``. Returns y [S, C, H*D] in ``dtype``."""
    S, C, H, D = q.shape
    ring_mode = isinstance(kv, RingKV)
    pool = kv.pool if ring_mode else kv
    data, scales = pool_parts(pool, window_pool)
    if impl == "paged_flash":
        from ...ops.kernels import flash_paged_attention
        # q joins the pool's storage dtype so the kernel's matmuls stay
        # single-dtype (f32 accumulation inside); the pool itself is NEVER
        # cast, copied or sliced — that would re-introduce the full-pool
        # traffic this kernel exists to avoid, on the pool the store
        # just updated in place. Over an int8 pool q stays in the compute
        # dtype; the kernel scales scores/probabilities by the side-array
        # scales.
        # In the fused loop the WHOLE pool and ring ride through: both
        # kernels select (layer, k/v) themselves. As operands, data[li, x]
        # slices made XLA copy every layer's K and V plane out of the pool
        # each step (the device trace measured them at ~45% of the
        # decode step), and ring[:, li, x].swapaxes added 44 strided
        # 17 MB transposes
        y = flash_paged_attention(
            q.astype(data.dtype if scales is None else dtype),
            data, li, _tables(batch, kv, cfg, window_pool), batch.start_pos,
            lens, block_size=cfg.block_size, num_kv_heads=KV,
            sm_scale=scale, alibi_slopes=alibi_slopes,
            sliding_window=sliding_window, scales=scales,
            **(dict(ring=kv.ring, ring_count=kv.rcount,
                    ring_layer=ring_layer) if ring_mode else {}))
        return y.reshape(S, C, H * D).astype(dtype)
    if ring_mode:
        rl = li if ring_layer is None else ring_layer
        if seq_axis_active():
            y = _seq_dense_ring_attention(
                pool, kv.ring, li, q, batch, cfg, lens, kv.rcount, scale,
                dtype, alibi_slopes, sliding_window)
        else:
            y = _dense_ring_attention(
                pool, kv.ring, li, q, batch, cfg, lens, kv.rcount, scale,
                dtype, alibi_slopes, sliding_window, window_pool, rl)
        return y.reshape(S, C, H * D).astype(dtype)

    k_ctx, v_ctx = _gather_ctx(kv, li, batch, cfg, S, KV, D, dtype,
                               window_pool)
    j = jnp.arange(cfg.max_context, dtype=jnp.int32)
    dist = (pos[:, :, None] - j[None, None, :]).astype(jnp.float32)
    mask = j[None, None, :] <= pos[:, :, None]          # [S, C, T]
    if sliding_window is not None:
        mask = jnp.logical_and(mask, dist < sliding_window)
    return _grouped_dense_attention(q, k_ctx, v_ctx, mask, dist, scale,
                                    dtype, alibi_slopes)


def _scores_in_tiles(scores_of, pos, sp, num_blocks: int, tile: int):
    """``block_scores`` over the chunk's queries a tile at a time (the
    scores of a whole [4, 512] chunk against 2,560 groups would be 0.7
    GB): ``scores_of(lo)`` gives the scaled compressed scores [S, tile,
    KV, G, J] of queries ``lo .. lo + tile``. Returns [S, C, KV, NB]
    float32."""
    from ...models.minicpm_sala import block_scores
    S, C = pos.shape

    def one(lo):
        p = jax.lax.dynamic_slice_in_dim(pos, lo, tile, axis=1)
        return block_scores(scores_of(lo), p[:, :, None], sp, num_blocks)
    out = jax.lax.map(one, jnp.arange(0, C, tile, dtype=jnp.int32))
    return jnp.moveaxis(out, 0, 1).reshape(S, C, *out.shape[3:])


def sparse_paged_attention(kv, li, xi, q, k, v, batch: "RaggedBatch",
                           cfg: RaggedInferenceConfig, pos, valid_q, scale,
                           dtype, sp):
    """``paged_attention``'s sibling for a block-selected layer (``sp``
    its ``SparseConfig``): append this step's K/V to plane ``li`` of the
    paged pool (or the fused loop's ring), bring the compressed-key plane
    ``xi`` up to date with them, SELECT each query's blocks (region
    ``attn_select``) and attend over them alone (``attn_sparse``).

    A query whose context is below ``sp.dense_len`` attends over every
    key: a decode step sends such sequences through the paged decode
    kernel and the others through the sparse one, each under a
    ``lax.cond`` that skips the call no sequence needs; a prefill chunk
    takes the BlockSpec paged kernel unchanged while every query is below
    it, else the block-union kernel, where such a query selects every
    block. A block's SCORES (the plane's live rows through the block
    table, window sums, softmax, group sum, window maximum, forced blocks)
    are one kernel too, ``sparse_attention.block_select_scores``; the
    top-k of them and the list's rows stay ``jax.numpy``. The kernels run
    on a TPU at whole-tile shapes (``sparse_attention.decode_uses_kernel``
    / ``select_uses_kernel``; platform and shape decide), their
    ``jax.numpy`` twins elsewhere. Returns (kv, y [S, C, H*D])."""
    from ...models.minicpm_sala import (block_scores, blocks_of_scores,
                                        topk_mask)
    from ...ops.kernels import default_interpret, sparse_attention as sa
    from . import index_plane
    S, C, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    bs, stride, sb = cfg.block_size, sp.kernel_stride, sp.block_size
    NB = cfg.max_context // sb
    impl = _attention_impl(cfg)
    use_kernel = impl == "paged_flash" and (
        sa.decode_uses_kernel(D, sb) or default_interpret())
    select_kernel = impl == "paged_flash" and (
        sa.select_uses_kernel(D, G, KV, C, bs, stride, sb)
        or (default_interpret() and sa.select_fits(G, KV, C, bs, stride)))
    ring_mode = isinstance(kv, RingKV)
    live = batch.n_tokens > 0
    tables = batch.block_tables
    qg = q.reshape(S, C, KV, G, D)
    idx = settled = None
    if ring_mode:
        pool, ring, t, rcount = kv[:4]
        settled = batch.start_pos - t
        with region("kv_write"):
            k_row = k.reshape(S, KV * D).astype(ring.dtype)
            ring = ring.at[t, li, 0].set(k_row)
            ring = ring.at[t, li, 1].set(
                v.reshape(S, KV * D).astype(ring.dtype))
            idx = index_plane.ring_add(kv.idx, xi, k_row, batch.start_pos,
                                       settled, live, stride)
        kv = kv._replace(ring=ring, idx=idx)
        lens = jnp.where(live, settled, 0)
    else:
        with region("kv_write"):
            kv = _store_step_rows(
                kv, li, jnp.stack([k.reshape(S, C, KV * D),
                                   v.reshape(S, C, KV * D)]),
                batch, cfg, KV)
            kv = index_plane.refresh(kv, li, xi, batch.start_pos,
                                     batch.n_tokens, tables, C, bs, stride)
        pool = kv
        lens = jnp.where(live, batch.start_pos + batch.n_tokens, 0)
    data = pool_parts(pool)[0]
    is_dense = pos + 1 < sp.dense_len                        # [S, C]

    def scores(qq):
        """Scaled compressed scores: a window is two group means."""
        gs = index_plane.group_scores(pool, xi, qq, tables, bs, stride,
                                      idx, settled)
        nxt = jnp.concatenate([gs[..., 1:], gs[..., :1]], axis=-1)
        return (gs + nxt) * (0.5 * scale)

    def kernel_scores():
        """A block's scores [S, C, KV, NB] from the selection kernel."""
        return sa.block_select_scores(
            qg, pool.index, xi, tables, pos, batch.n_tokens, sp,
            pool_block=bs, sm_scale=float(scale), num_blocks=NB,
            ring_sums=idx, settled=settled, interpret=default_interpret())

    def dense(lens_d):
        """Every key at or before the query: the paged pool's own call."""
        return _attend(kv, li, q, KV, batch, cfg, pos, lens_d, scale,
                       dtype, impl).astype(dtype)

    zeros = lambda: jnp.zeros((S, C, H * D), dtype)         # noqa: E731
    if C == 1:
        dense_row, sparse_row = is_dense[:, 0], ~is_dense[:, 0]

        def sparse():
            with region("attn_select"):
                score = kernel_scores()[:, 0] if select_kernel \
                    else block_scores(scores(qg)[:, 0], pos[:, :1], sp, NB)
                rows, col = sa.selection_rows(
                    blocks_of_scores(score, sp), tables, bs, sb,
                    data.shape[2] - bs)
            lens_s = jnp.where(sparse_row, lens, 0)
            kw = dict(sel_block=sb, sm_scale=float(scale))
            if ring_mode:
                kw.update(ring=ring, ring_count=rcount)
            with region("attn_sparse"):
                if use_kernel:
                    y = sa.sparse_decode_attention(
                        q[:, 0], data, li, rows, col, batch.start_pos,
                        lens_s, interpret=default_interpret(), **kw)
                else:
                    y = sa.sparse_decode_reference(
                        q[:, 0], data, li, rows, col, batch.start_pos,
                        lens_s, **kw)
            return y.reshape(S, 1, H * D).astype(dtype)
        y_s = jax.lax.cond(jnp.any(live & sparse_row), sparse, zeros)
        with region("attn_core"):
            y_d = jax.lax.cond(
                jnp.any(live & dense_row),
                lambda: dense(jnp.where(dense_row, lens, 0)), zeros)
        return kv, jnp.where(dense_row[:, None, None], y_d, y_s)

    def sparse_chunk():
        tile = next(d for d in range(min(C, 128), 0, -1) if C % d == 0)
        with region("attn_select"):
            score = kernel_scores() if select_kernel else _scores_in_tiles(
                lambda lo: scores(jax.lax.dynamic_slice_in_dim(
                    qg, lo, tile, axis=1)), pos, sp, NB, tile)
            chosen = topk_mask(score, sp.topk) | is_dense[:, :, None, None]
        fn = sa.sparse_prefill_attention if use_kernel \
            else sa.sparse_prefill_reference
        with region("attn_sparse"):
            y, counts = fn(
                q.astype(data.dtype), data, li, tables, batch.start_pos,
                lens, chosen, block_size=bs, sel_block=sb,
                sm_scale=float(scale),
                **(dict(interpret=default_interpret()) if use_kernel
                   else {}))
        return y.reshape(S, C, H * D).astype(dtype), counts

    def dense_chunk():
        # every real query selects every block at or before its own
        n = jnp.sum(jnp.where(valid_q, pos // sb + 1, 0),
                    dtype=jnp.int32) * KV
        with region("attn_core"):
            return dense(lens), jnp.stack([n, n])
    y, counts = jax.lax.cond(jnp.any(valid_q & ~is_dense), sparse_chunk,
                             dense_chunk)
    with region("loop_carry"):
        kv = kv._replace(sel_counts=kv.sel_counts + counts)
    return kv, y


def latent_attention(kv, li, q, row, batch: "RaggedBatch",
                     cfg: RaggedInferenceConfig, pos, valid_q, scale, dtype,
                     latent: int):
    """``paged_attention``'s sibling for a latent-attention layer: append
    this step's ONE row a token to plane ``li`` of the one-plane cache,
    then attend in the absorbed form.

    q [S, C, H, W]: each head's absorbed query ``[q_nope W_UK^T ; q_rope ;
    0]``; row [S, C, W]: ``[c_kv ; k_r ; 0]``, the stored row, key and
    value at once (``latent`` lanes of it are the value). The same three
    shapes of ``kv`` as ``paged_attention``: the pool, or the fused loop's
    ``RingKV`` (the row then goes to ``ring[li, 0, :, t]`` and the pool is
    read-only). On a TPU a pure-decode step runs
    ``ops/kernels/mla_attention.py`` (each live tile fetched once for
    both products) and a prefill chunk the BlockSpec paged kernel with
    the one plane as its K and its V operand (the absorbed form at the
    row's width: no cached row is expanded through ``W_kvb``); elsewhere
    the context is gathered and masked. Returns (kv, o_lat [S, C, H,
    latent] in ``dtype``): ``W_UV`` is the caller's."""
    from ...ops.kernels import default_interpret
    from ...ops.kernels.mla_attention import (mla_attention_reference,
                                              mla_decode_attention)
    S, C, H, W = q.shape
    bs = cfg.block_size
    impl = _attention_impl(cfg)
    ring_mode = isinstance(kv, RingKV)
    if ring_mode:
        ring, t, rcount = kv.ring, kv.t, kv.rcount
        data, _ = pool_parts(kv.pool)
        # the latent ring is SEQUENCE-major, [L, 1, S, R, W]: the decode
        # kernel then takes a [R, W] slab a sequence
        with region("kv_write"):
            ring = ring.at[li, 0, :, t].set(
                row.reshape(S, W).astype(ring.dtype))
        kv = kv._replace(ring=ring)
        lens = jnp.where(batch.n_tokens > 0, batch.start_pos - t, 0)
    else:
        with region("kv_write"):
            kv = _store_step_rows(kv, li, row.reshape(1, S, C, W), batch,
                                  cfg)
        data, _ = pool_parts(kv)
        ring = rcount = None
        lens = jnp.where(batch.n_tokens > 0,
                         batch.start_pos + batch.n_tokens, 0)

    if impl == "paged_flash" and C == 1:
        y = mla_decode_attention(
            q[:, 0].astype(data.dtype), data, ring, batch.block_tables, lens,
            rcount if ring_mode else jnp.zeros((), jnp.int32),
            jnp.asarray([li, li], jnp.int32), block_size=bs, latent=latent,
            sm_scale=float(scale), interpret=default_interpret())[:, None]
        return kv, y.astype(dtype)
    if impl == "paged_flash":
        from ...ops.kernels import flash_paged_attention
        y = flash_paged_attention(
            q.astype(data.dtype), data, li, batch.block_tables,
            batch.start_pos, lens, block_size=bs, num_kv_heads=1,
            sm_scale=scale)
        return kv, y[..., :latent].astype(dtype)

    T = cfg.max_context
    j = jnp.arange(T, dtype=jnp.int32)
    rows = data[li, 0][batch.block_tables[:, j // bs] * bs + j % bs]
    mask = (j[None, None, :] <= pos[:, :, None]) \
        & (j[None, None, :] < lens[:, None, None])
    if ring_mode:
        # columns [T, T + R): the loop's rows, live below rcount
        R = ring.shape[3]
        rows = jnp.concatenate([rows, ring[li, 0]], axis=1)
        live = (jnp.arange(R, dtype=jnp.int32) < rcount)[None, None, :] \
            & (batch.n_tokens > 0)[:, None, None]
        mask = jnp.concatenate(
            [mask, jnp.broadcast_to(live, (S, C, R))], axis=2)
    return kv, mla_attention_reference(q.astype(dtype), rows.astype(dtype),
                                       mask, latent, scale)


def woq_mm(h, w, dtype):
    """``h @ w`` with WOQ-aware dispatch: a dense array multiplies
    directly; an ``Fp6GemmWeight`` goes through the fused Pallas GEMM
    (weights stream at 6 bits/value, decoded tile-wise in VMEM). Runners
    whose matmul sites route through this helper set
    ``supports_fused_woq = True`` so the base class keeps fused leaves
    intact through the in-jit dequant pass."""
    from ...ops.kernels.fp6_gemm import Fp6GemmWeight, fp6_matmul
    if isinstance(w, Fp6GemmWeight):
        return fp6_matmul(h, w)
    return h @ w.astype(dtype)


class RaggedRunnerBase:
    """Shared runner plumbing: jitted step closing over the configs, with
    WOQ int8/int4 leaves dequantized INSIDE the jit (XLA fuses the dequant
    into each layer's matmul while HBM keeps the packed weights). Subclasses
    set ``step_fn``; kv-cache geometry derives from the model config.

    With ``cfg.tp_size > 1`` the engine calls :meth:`init_tp` and every
    jitted program (step / greedy step / fused decode loop / ring flush)
    is rebuilt under ONE ``shard_map`` over the ``model`` mesh axis:
    weights enter as their TP shards, the KV pool and decode ring enter
    head-sharded, and the only collectives are the step functions' two
    per-layer ``tp_all_reduce`` sites plus the ``tp_gather_logits`` before
    token selection (inference/v2/tp.py)."""

    step_fn = None   # staticmethod(params, kv, batch, *, model_cfg, cfg, dtype)
    #: the runner's matmuls dispatch via ``woq_mm`` (fused fp6 capable)
    supports_fused_woq = False
    #: param-path regexes of FUSED [q|k|v] projections; their output dim is
    #: re-laid chip-major at TP init so local jnp.split stays correct
    tp_fused_qkv: tuple = ()

    def __init__(self, model_cfg: Any, cfg: RaggedInferenceConfig,
                 compute_dtype: Any = None):
        self.model_cfg = model_cfg
        self.cfg = cfg
        self.compute_dtype = compute_dtype or model_cfg.dtype
        self.num_layers = model_cfg.num_layers
        self.kv_heads = getattr(model_cfg, "num_kv_heads",
                                model_cfg.num_heads)
        self.head_dim = getattr(
            model_cfg, "head_dim",
            model_cfg.hidden_size // model_cfg.num_heads)
        # the cache follows the layer list: paged planes for each softmax
        # or latent layer, a state row a sequence for each recurrent one
        kinds = getattr(model_cfg, "layer_kinds", None) \
            or ("attn",) * self.num_layers
        self.kv_layers = sum(k in ("attn", "mla", "sparse") for k in kinds)
        other = sorted({k for k in kinds
                        if k in ("swa", "mla", "kda", "gdn", "mamba2",
                                 "mamba1")})
        if other and {"sparse", "lightning"} & set(kinds):
            raise ValueError(
                f"block-selected ('sparse') and Lightning ('lightning') "
                f"layers do not mix with {other} layers in one model: no "
                f"pool, ring or state row has been carried over both")
        #: what the compressed-key plane must hold (None: no
        #: block-selected layer): a row a ``stride`` positions for each
        #: "sparse" layer, whose K rows are the pool's ``pool_layers``
        self.index_spec = None
        if "sparse" in kinds:
            paged = [k for k in kinds if k in ("attn", "sparse")]
            self.index_spec = {
                "layers": kinds.count("sparse"),
                "stride": int(model_cfg.sparse.kernel_stride),
                "pool_layers": tuple(i for i, k in enumerate(paged)
                                     if k == "sparse")}
        #: what the window pool must hold (None: no sliding-window layer):
        #: the layers of kind "swa" keep their rows THERE and not in the
        #: paged pool, which serves the model's full layers alone.
        #: ``ring_of`` says which rows of the fused loop's ring (one a
        #: softmax layer of either kind, in the model's order) flush into
        #: which pool
        self.window_spec = None
        #: rows of the fused loop's ring: one a layer that keeps K/V or a
        #: latent row, in either pool
        self.ring_layers = self.kv_layers + kinds.count("swa")
        if "swa" in kinds:
            other = sorted({k for k in kinds
                            if k not in ("attn", "swa", None)})
            if other:
                raise ValueError(
                    f"sliding-window ('swa') layers do not mix with "
                    f"{other} layers in one model: a sequence's slot "
                    f"names its window-pool row alone")
            softmax = [k for k in kinds if k in ("attn", "swa")]
            self.window_spec = {
                "layers": kinds.count("swa"),
                "window": int(model_cfg.sliding_window),
                "ring_of": {
                    w: tuple(i for i, k in enumerate(softmax)
                             if (k == "swa") == w) for w in (False, True)}}
        #: planes a layer keeps in the paged cache: K and V, or the ONE
        #: plane of a latent-attention model, whose stored row
        #: (``latent_row`` lanes; one "kv head") is key and value at once
        self.kv_planes = 2
        if "mla" in kinds:
            # beside recurrent layers they may stand (those keep their
            # state in the pool's other part, not in a plane)
            if "attn" in kinds:
                raise ValueError(
                    "latent ('mla') layers do not mix with softmax "
                    "('attn') layers in one model: the cache has one "
                    "kind of plane")
            self.kv_planes = 1
            self.kv_heads, self.head_dim = 1, model_cfg.latent_row
        #: what the state pool must hold (None: no recurrent layer): one
        #: state ``[heads, d_v, d_k]`` a recurrent layer (``heads`` 0: the
        #: layers keep no state; ``state_shape``, where a kind gives one,
        #: is how the pool lays a slot's ``heads x d_v x d_k`` numbers
        #: out instead) and the last ``taps - 1`` inputs of its
        #: convolution, ``conv_width`` wide (``taps`` 0: they have none)
        self.state_spec = None
        recurrent = [k for k in kinds
                     if k in ("kda", "gdn", "mamba2", "mamba1", "lightning",
                              "conv")]
        if len(set(recurrent)) > 1:
            raise ValueError(
                f"recurrent layers of more than one kind "
                f"({sorted(set(recurrent))}) in one model: the state pool "
                f"holds one shape of state and one width of convolution")
        if recurrent and recurrent[0] == "conv":
            # the carried inputs of a gated short convolution and NO
            # matrix state (heads 0: the pool then has no state part)
            self.state_spec = {
                "kind": "conv", "layers": len(recurrent), "heads": 0,
                "taps": model_cfg.conv_taps,
                "conv_width": model_cfg.hidden_size}
        elif recurrent and recurrent[0] == "kda":
            d = model_cfg.kda_head_dim
            self.state_spec = {
                "kind": "kda", "layers": len(recurrent),
                "heads": model_cfg.kda_heads, "d_v": d, "d_k": d,
                "taps": model_cfg.kda_conv,
                "conv_width": 3 * model_cfg.kda_heads * d}
        elif recurrent and recurrent[0] == "gdn":
            # ONE decay a head: keys and values of two widths, q | k | v
            # through the convolution, and the layout that tiles whole
            from ...ops.kernels.delta_rule import gdn_state_shape
            from ...ops.kernels.short_conv import whole_width
            from ...utils.dtypes import resolve_dtype
            H, dk, dv = (model_cfg.gdn_heads, model_cfg.gdn_key_dim,
                         model_cfg.gdn_value_dim)
            self.state_spec = {
                "kind": "gdn", "layers": len(recurrent), "heads": H,
                "d_v": dv, "d_k": dk, "taps": model_cfg.gdn_conv,
                "conv_width": whole_width(model_cfg.gdn_conv_width,
                                          resolve_dtype(cfg.dtype)),
                "conv_channels": model_cfg.gdn_conv_width,
                "state_shape": gdn_state_shape(H, dk, dv)}
        elif recurrent and recurrent[0] == "lightning":
            # a state [d_v, d_k] a head and NO short convolution (taps 0:
            # the pool then has no convolution part)
            d = model_cfg.head_dim
            self.state_spec = {
                "kind": "lightning", "layers": len(recurrent),
                "heads": model_cfg.lightning_heads, "d_v": d, "d_k": d,
                "taps": 0, "conv_width": 0}
        elif recurrent and recurrent[0] == "mamba1":
            # a decay a (channel, state) pair: ONE "head" whose state is
            # [state, channels], the channels along the lanes; x ALONE
            # through the convolution
            from ...ops.kernels.selective_scan import mamba1_state_shape
            from ...ops.kernels.short_conv import whole_width
            from ...utils.dtypes import resolve_dtype
            E, N = model_cfg.mamba_inner, model_cfg.mamba_state
            self.state_spec = {
                "kind": "mamba1", "layers": len(recurrent), "heads": 1,
                "d_v": E, "d_k": N, "taps": model_cfg.mamba_conv,
                "conv_width": whole_width(E, resolve_dtype(cfg.dtype)),
                "conv_channels": E,
                "state_shape": mamba1_state_shape(E, N)}
        elif recurrent:
            self.state_spec = {
                "kind": "mamba2", "layers": len(recurrent),
                "heads": model_cfg.mamba_heads,
                "d_v": model_cfg.mamba_head_dim,
                "d_k": model_cfg.mamba_state,
                "taps": model_cfg.mamba_conv,
                "conv_width": model_cfg.mamba_conv_width}
        self.tp = None            # TPContext once init_tp runs
        self.seqctx = None        # SeqContext once init_seq runs
        self.epctx = None         # EPContext once init_ep runs
        self._build_programs()

    # ---------------------------- TP wiring --------------------------- #

    def init_tp(self, tp_ctx) -> None:
        """Adopt a ``tp.TPContext`` and rebuild every device program under
        its ``model``-axis shard_map."""
        self.tp = tp_ctx
        self._build_programs()

    def init_seq(self, seq_ctx) -> None:
        """Adopt a ``seq_parallel.SeqContext`` (mutually exclusive with
        TP) and rebuild every device program under its ``seq``-axis
        shard_map: params replicate, the pool enters as its round-robin
        block shard, and the step wrapper slices each chunk's queries
        chip-major (context-parallel prefill)."""
        if self.tp is not None or self.epctx is not None:
            raise ValueError("init_seq after init_tp/init_ep: the seq "
                             "axis does not compose with model/expert "
                             "sharding")
        self.seqctx = seq_ctx
        self._build_programs()

    def init_ep(self, ep_ctx) -> None:
        """Adopt an ``expert_parallel.EPContext`` and rebuild every
        device program under its shard_map — 1-D ``(expert,)`` or, when
        tp composes, 2-D ``(expert, model)``. In the composed case the
        context carries an inner TPContext built on the SAME mesh, which
        this runner adopts as ``self.tp`` so head localization,
        quant-meta fixes and the TP collectives trace exactly as under
        plain TP; the MoE layers alone ride the ``expert`` axis."""
        if self.seqctx is not None:
            raise ValueError("init_ep after init_seq: the expert axis "
                             "composes with tp, not with seq")
        self.epctx = ep_ctx
        self.tp = ep_ctx.tp          # None for ep-only meshes
        self._build_programs()

    @property
    def local_kv_heads(self) -> int:
        return self.kv_heads // (self.tp.tp_size if self.tp else 1)

    def _wrap(self, fn, in_specs, out_specs):
        """shard_map ``fn`` over the EP, TP or seq mesh (identity when
        no axis is active). EP takes precedence: its mesh already
        contains the composed ``model`` axis when tp rides along."""
        ctx = self.epctx if self.epctx is not None else (
            self.tp if self.tp is not None else self.seqctx)
        if ctx is None:
            return fn
        return shard_map(fn, mesh=ctx.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def _local_params(self, params):
        """In-jit params view: QuantizedTensor static shapes localized to
        this chip's shard, then the WOQ dequant pass."""
        from ..quantization import dequantize_tree
        if self.tp is not None:
            params = self.tp.localize_quant_meta(params)
        return dequantize_tree(params, keep_fused=self.supports_fused_woq)

    # ------------------------- program builders ----------------------- #

    def _build_programs(self) -> None:
        model_cfg, cfg = self.model_cfg, self.cfg
        dtype = self.compute_dtype
        tp = self.tp
        epc = self.epctx
        seqc = self.seqctx if (tp is None and epc is None) else None
        mapped = tp is not None or seqc is not None or epc is not None
        mcfg_l = tp.localize_model_cfg(model_cfg) if tp else model_cfg
        vocab = getattr(model_cfg, "vocab_size", -1)
        moe_experts = getattr(model_cfg, "num_experts", 0) \
            if epc is None else 0
        quantized_pool = cfg.kv_cache_dtype == "int8"
        if epc is not None:
            # expert (or expert×model) mesh: specs merged by the EP
            # planner — expert stacks over 'expert', tp leaves over
            # 'model' when composed, pool/ring via the inner tp view
            pspecs = epc.param_specs
            pool_spec = epc.pool_spec(quantized_pool)
            ring_spec = epc.ring_spec
            batch_spec = RaggedBatch(P(), P(), P(), P())
        elif tp is not None:
            pspecs = tp.param_specs
            pool_spec = tp.pool_spec(quantized_pool)
            ring_spec = tp.ring_spec
            batch_spec = RaggedBatch(P(), P(), P(), P())
        elif seqc is not None:
            pspecs = P()                        # weights replicate
            pool_spec = seqc.pool_spec(quantized_pool)
            ring_spec = seqc.ring_spec          # replicated decode ring
            batch_spec = RaggedBatch(P(), P(), P(), P())

        def _step(params, kv_data, batch):
            shard = None
            if seqc is not None:
                # context-parallel prefill: chip r takes query slice
                # [r*C/sz, (r+1)*C/sz) — start/n shift so the slice's
                # positions/validity come out right in the step_fn
                # (n_tokens goes UNCLIPPED negative/overlong for
                # off-chip slots; valid_q and the clamped last-token
                # take handle both, and the owner psum below discards
                # non-owner logits). Widths the scheduler did not round
                # (C=1 per-step decode slots, replay tails) pad with
                # trash queries first: a pad position sits at
                # pos >= start + n, so valid_q masks it everywhere —
                # its KV write lands in the trash row, its logits are
                # never the owner's
                pad = (-batch.tokens.shape[1]) % seqc.seq_size
                if pad:
                    batch = batch._replace(tokens=jnp.pad(
                        batch.tokens, ((0, 0), (0, pad))))
                r = jax.lax.axis_index(SEQ_AXIS)
                c_loc = batch.tokens.shape[1] // seqc.seq_size
                gbatch = batch
                shard = (seqc.seq_size, r)
            # where the step's rows go in the pool: once, for every layer
            with region("kv_write"):
                batch = batch._replace(write_plan=write_plan(
                    batch.start_pos, batch.n_tokens, batch.block_tables,
                    batch.tokens.shape[1], cfg.block_size,
                    pool_parts(kv_data)[0].shape, shard))
                if self.window_spec is not None:
                    # and in the window pool, through the slots' tables
                    wt = window_tables(batch.state_slots, kv_data, cfg)
                    batch = batch._replace(
                        window_tables=wt, window_plan=write_plan(
                            batch.start_pos, batch.n_tokens, wt,
                            batch.tokens.shape[1], cfg.block_size,
                            kv_data.window.shape))
            if seqc is not None:
                batch = batch._replace(
                    tokens=jax.lax.dynamic_slice_in_dim(
                        batch.tokens, r * c_loc, c_loc, 1),
                    start_pos=batch.start_pos + r * c_loc,
                    n_tokens=batch.n_tokens - r * c_loc)
            else:
                gbatch = batch
            logits, kv_out = type(self).step_fn(
                self._local_params(params), kv_data, batch,
                model_cfg=mcfg_l, cfg=cfg, dtype=dtype)
            # vocab-sharded unembed -> ONE all-gather to full logits
            # (identity for tied/replicated unembeds and at tp_size 1)
            with region("head"):
                logits = tp_gather_logits(logits, vocab)
            if seqc is not None:
                # each slot's true last token lives on ONE chip's query
                # slice; a single masked psum hands its logits to all —
                # the one per-program seq collective
                c_loc = gbatch.tokens.shape[1] // seqc.seq_size
                owner = jnp.clip((gbatch.n_tokens - 1) // c_loc, 0,
                                 seqc.seq_size - 1)
                logits = jax.lax.psum(
                    jnp.where(owner[:, None]
                              == jax.lax.axis_index(SEQ_AXIS),
                              logits, 0.0), SEQ_AXIS)
            return logits, kv_out

        if mapped:
            _step = self._wrap(_step, (pspecs, pool_spec, batch_spec),
                               (P(), pool_spec))
        # every step program consumes the previous KV pool functionally
        # and the engine rebinds its handle to the output, so the pool
        # argument is donated (aliased in place — one pool resident
        # instead of two) on every backend: the CPU test mesh deletes the
        # donated buffer exactly as the chip does, so a reader holding a
        # stale pool handle fails in tier-1 and not first on a TPU.
        donate = (1,)
        self._step = jax.jit(_step, donate_argnums=donate)
        # greedy decode variant: argmax fused into the jit so a decode step
        # returns [S] int32 token ids instead of shipping [S, V] f32 logits
        # to the host (the reference's host-side sampler reads full logits)
        def _step_greedy(params, kv_data, batch):
            logits, kv_out = _step(params, kv_data, batch)
            with region("sample"):
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return tok, kv_out

        self._step_greedy = jax.jit(_step_greedy, donate_argnums=donate)

        # pipelined greedy step with DEVICE token feedback (the overlapped
        # serving pipeline, engine_v2): fed slots take their input token
        # from ``prev_tok`` — the previous in-flight step's [S_prev]
        # last-token output, which never round-trips through the host —
        # gathered through ``feed_idx`` (this sequence's slot in that
        # step); unfed slots keep their host-staged token. The
        # substitution runs on replicated arrays before the (possibly
        # shard_map-wrapped) step, so TP programs are untouched.
        # ``kv_data`` is donated like the other step programs;
        # prev_tok is NOT donated: the commit phase still reads its
        # values after the next step dispatches.
        def _step_greedy_fb(params, kv_data, batch, prev_tok, feed_mask,
                            feed_idx):
            with region("loop_carry"):
                fed = prev_tok[jnp.clip(feed_idx, 0, prev_tok.shape[0] - 1)]
                tok0 = jnp.where(feed_mask > 0, fed, batch.tokens[:, 0])
                batch = batch._replace(
                    tokens=batch.tokens.at[:, 0].set(tok0))
            return _step_greedy(params, kv_data, batch)

        self._step_greedy_fb = jax.jit(_step_greedy_fb,
                                       donate_argnums=donate)

        # sampled sibling of the feedback step (the pipelined SAMPLING
        # path, docs/serving.md "Sampling"): same device-token feed, but
        # token selection is the per-slot temperature/top-k/top-p
        # categorical — keys derived IN-PROGRAM from the staged
        # (seed, position) int32 pairs, so no RNG state crosses the
        # host boundary and zero new host callbacks appear. Greedy
        # slots ride along with temperature 0 (in-program argmax), so
        # one program serves mixed greedy/sampled batches. Returns
        # ((token ids [S], chosen-token logprobs [S]), kv): the token
        # buffer is the same device feedback source step_greedy_fb
        # produces; logprobs ride to the host at commit.
        def _step_sample_fb(params, kv_data, batch, prev_tok, feed_mask,
                            feed_idx, seeds, spos, temps, top_ks, top_ps):
            with region("loop_carry"):
                fed = prev_tok[jnp.clip(feed_idx, 0, prev_tok.shape[0] - 1)]
                tok0 = jnp.where(feed_mask > 0, fed, batch.tokens[:, 0])
                batch = batch._replace(
                    tokens=batch.tokens.at[:, 0].set(tok0))
            logits, kv_out = _step(params, kv_data, batch)
            with region("sample"):
                keys = _sample_keys(seeds, spos)
                cand = min(SAMPLE_CANDIDATES, logits.shape[-1])
                tok = _select_tokens(logits, keys, temps, top_ks, top_ps,
                                     cand=cand)
                lp = _chosen_logprob(logits, tok)
            return (tok, lp), kv_out

        self._step_sample_fb = jax.jit(_step_sample_fb,
                                       donate_argnums=donate)

        # fused multi-step greedy decode: n forward+argmax+KV-append steps
        # in ONE device program (lax.scan), feeding each step's token to
        # the next. Per-token host round-trips — the decode wall when the
        # host talks to the chip over a network hop — collapse to one per n
        # tokens. The pool stays READ-ONLY inside the scan; each step's K/V
        # lands in a small [n, L, 2, S, KV*D] ring carry (n LEADING so the
        # write is a leading-index dynamic-update-slice, in-place in the
        # carry), and the attention ring round attends it. This keeps the
        # per-step pool scatter (TPU scatter slow path) AND the 1-GB pool
        # carry out of the scan entirely — the ring is flushed once per
        # loop (_flush_ring).
        def _decode_loop_impl(params, kv_data, lin, sslots, tok0, start,
                              active, tables, seeds, temps, top_ks, top_ps,
                              drafts, *, n, mode, cand, eos_id, feed):
            params = self._local_params(params)
            S = cfg.max_seqs
            pool_arr, pool_scales = pool_parts(kv_data)
            # over an int8 pool the ring stays in the compute dtype: its
            # rows are the loop's freshest tokens, rewritten every step,
            # and are quantized once at flush time. Under TP the ring —
            # like the pool — is head-sharded: local_kv_heads rows.
            ring_shape = (n, self.ring_layers, 2, S,
                          self.local_kv_heads * self.head_dim)
            # a sliding-window layer's table is loop-invariant too
            wtables = None if self.window_spec is None \
                else window_tables(sslots, kv_data, cfg)
            if self.kv_planes == 1:
                # a latent cache's ring: one plane, sequence-major
                # (latent_attention), over the latent layers alone where
                # recurrent ones stand beside them (their state is ``lin``)
                ring_shape = (self.kv_layers, 1, S, n, self.head_dim)
            ring = jnp.zeros(ring_shape, pool_arr.dtype
                             if pool_scales is None else dtype)
            use_eos = eos_id >= 0
            done0 = jnp.zeros((S,), jnp.bool_)
            # real rows routed to each expert, summed over the sparse
            # layers and the loop's steps (the single-chip expert path
            # adds to it; [0] for a model without experts and under ep),
            # then the experts the grouped kernel found hit and the visits
            # it made to them
            moe0 = jnp.zeros((moe_experts + 2 if moe_experts else 0,),
                             jnp.int32)
            # a model with block-selected layers: the sums of the loop's
            # own keys a compressed-key group (index_plane.py)
            idx0 = None
            if self.index_spec is not None:
                from .index_plane import ring_groups
                idx0 = ring_groups(
                    kv_data, self.index_spec["pool_layers"], start, active,
                    tables, n, cfg.block_size, self.index_spec["stride"])

            def body(carry, t):
                ring, tok, pos, done, moe, lin, idx = carry
                if use_eos:
                    # per-slot EOS freeze: finished slots stop appending KV
                    # (n_tokens 0 -> trash writes) and keep emitting eos_id
                    alive = active * (1 - done.astype(jnp.int32))
                else:
                    # keep the prefetch/index chain loop-invariant: with no
                    # EOS the scheduler state is static per call and XLA
                    # hoists it out of the scan
                    alive = active
                if feed == "given":
                    # speculative VERIFY (docs/serving.md "Speculative
                    # decoding"): step t consumes the CALLER's token —
                    # [last committed, draft_1..draft_K] — instead of
                    # its own previous output, so the scan scores the
                    # model's selection after every draft prefix in ONE
                    # program; the host accepts the longest agreeing
                    # prefix and rolls the rest back
                    tok = drafts[:, t]
                batch = RaggedBatch(tokens=tok[:, None], start_pos=pos,
                                    n_tokens=alive, block_tables=tables,
                                    state_slots=sslots,
                                    window_tables=wtables)
                logits, kv_out = type(self).step_fn(
                    params, RingKV(kv_data, ring, t, t + 1, moe, lin, idx),
                    batch, model_cfg=mcfg_l, cfg=cfg, dtype=dtype)
                ring, moe, lin = kv_out.ring, kv_out.moe_rows, kv_out.lin
                idx = kv_out.idx
                # the one pre-sampling collective: every chip then selects
                # the SAME next token from identical full-width logits
                with region("head"):
                    logits = tp_gather_logits(logits, vocab)
                with region("sample"):
                    if mode == "greedy":
                        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        lp = jnp.zeros((S,), jnp.float32)
                    else:
                        # keys are a pure function of (seed, the position
                        # the selected token will occupy) — deterministic
                        # across fused/per-step paths and restarts
                        # (sampling.py)
                        keys = _sample_keys(seeds, pos + 1)
                        nxt = _select_tokens(logits, keys, temps, top_ks,
                                             top_ps, cand=cand)
                        lp = _chosen_logprob(logits, nxt)
                if use_eos:
                    nxt = jnp.where(done, jnp.int32(eos_id), nxt)
                    new_pos = pos + (1 - done.astype(jnp.int32))
                    done = jnp.logical_or(done, nxt == eos_id)
                else:
                    new_pos = pos + 1
                return (ring, nxt, new_pos, done, moe, lin, idx), (nxt, lp)

            (ring, _, pos_f, _, moe, lin, _), (toks, lps) = jax.lax.scan(
                body, (ring, tok0, start, done0, moe0, lin, idx0),
                jnp.arange(n, dtype=jnp.int32))
            # consumed is shard_map-shape-stable: always an array; the
            # decode_loop wrapper drops it when EOS is disabled
            return jnp.transpose(toks), jnp.transpose(lps), ring, \
                pos_f - start, moe, lin

        def _decode_loop_ring(params, kv_data, lin, sslots, tok0, start,
                              active, tables, seeds, temps, top_ks, top_ps,
                              drafts, *, n, mode, cand, eos_id, feed):
            # n/mode/cand/eos_id/feed are STATIC: they change rarely (per
            # tokenizer / per sampling profile) and shape the program;
            # per-slot sampling params ride as [S] device arrays so one
            # compiled program serves every request mix
            impl = functools.partial(
                _decode_loop_impl, n=n, mode=mode, cand=cand,
                eos_id=eos_id, feed=feed)
            if mapped:
                # lin / sslots are None on every mesh (a model with
                # recurrent layers refuses tp, seq and ep)
                impl = self._wrap(
                    impl,
                    (pspecs, pool_spec, None, None, P(), P(), P(), P(),
                     P(), P(), P(), P(), P()),
                    (P(), P(), ring_spec, P(), P(), None))
            # everything of the loop that no inner region claims: the
            # ring, the feed, positions, counters, the stacked outputs
            with region("loop_carry"):
                return impl(params, kv_data, lin, sslots, tok0, start,
                            active, tables, seeds, temps, top_ks, top_ps,
                            drafts)

        # dslint: allow(DSL002): the pool is strictly READ-ONLY inside
        # the fused loop (fresh K/V rides the small ring carry);
        # _flush_ring consumes — and donates — the pool right after.
        # The recurrent state cannot stay read-only: it enters as its own
        # argument, donated, is the scan's carry and comes back updated
        # in place (None, and no operand at all, without recurrent layers)
        self._decode_loop_ring = jax.jit(
            _decode_loop_ring, donate_argnames=("lin",),
            static_argnames=("n", "mode", "cand", "eos_id", "feed"))

        def _flush_ring(kv_data, ring, tables, start0, active, sslots=None):
            """The loop's ring rows into the pool, through the one writer
            (kv_write.py): a sequence's R rows are R consecutive positions
            from ``start0``, whole windows whatever the layout (one block a
            sequence or many), stored in place a layer at a time; over an
            int8 pool they are quantized here, once (the ring itself runs
            unquantized). Under ``seq`` every chip holds the SAME ring rows
            (the loop is replicated) and stores the windows whose block it
            owns: zero collectives, pool bytes as at seq=1. A model with
            sliding-window layers flushes those layers' rows of the ring
            into the window pool, through the slots' tables (``sslots``),
            and the others' into the paged pool: the same rows, the same
            writer, a second plan."""
            data, scales = pool_parts(kv_data)
            latent = self.kv_planes == 1       # ring [L, 1, S, R, W]
            R = ring.shape[3 if latent else 0]     # else [R, L, 2, S, W]
            kv_heads = 1 if scales is None else scales.shape[2]

            def flush(kv, plan, layers, window=False, ring_of=None):
                def layer(l, kv):
                    rows = jax.lax.dynamic_index_in_dim(
                        ring, l if ring_of is None else ring_of[l],
                        0 if latent else 1, keepdims=False)
                    if not latent:
                        rows = jnp.transpose(rows, (1, 2, 0, 3))  # [2,S,R,W]
                    return store_rows(kv, l, rows, plan, kv_heads, window)
                return jax.lax.fori_loop(0, layers, layer, kv)

            with region("kv_write"):
                count = jnp.where(active > 0, R, 0)
                plan = write_plan(
                    start0, count, tables, R,
                    cfg.block_size, data.shape, None if seqc is None else
                    (seqc.seq_size, jax.lax.axis_index(SEQ_AXIS)))
                if self.index_spec is not None:
                    # the compressed keys of the groups the ring's rows
                    # touch, from the rows just stored
                    from .index_plane import refresh
                    kv_data = flush(kv_data, plan, data.shape[0])
                    spec = self.index_spec
                    for xi, li in enumerate(spec["pool_layers"]):
                        kv_data = refresh(
                            kv_data, li, xi, start0, count, tables, R,
                            cfg.block_size, spec["stride"])
                    return kv_data
                if self.window_spec is None:
                    return flush(kv_data, plan, data.shape[0])
                # two loops over the one ring: XLA re-lays the carry once
                # for them (0.54 GB at the 256-client cell, ~1 ms a round
                # by the v5e compile's own sizes; unrolling the layers or
                # grouping the ring's rows by pool did not remove it)
                ring_of = {w: jnp.asarray(rows, jnp.int32) for w, rows
                           in self.window_spec["ring_of"].items()}
                kv_data = flush(kv_data, plan, data.shape[0],
                                ring_of=ring_of[False])
                wplan = write_plan(
                    start0, count, window_tables(sslots, kv_data, cfg), R,
                    cfg.block_size, kv_data.window.shape)
                return flush(kv_data, wplan, kv_data.window.shape[0],
                             window=True, ring_of=ring_of[True])

        if mapped:
            # all flush work is chip-local (quantize_rows is per-kv-head,
            # the windows live on the slots dim; under seq the ownership
            # mask keeps foreign blocks in the trash block)
            _flush_ring = self._wrap(_flush_ring,
                                     (pool_spec, ring_spec, P(), P(), P()),
                                     pool_spec)
        self._flush_ring = jax.jit(_flush_ring, donate_argnums=(0,))

    def step(self, params, kv_data, batch: "RaggedBatch"):
        """Returns (last_token_logits [S, V] f32, new kv_data)."""
        return self._step(params, kv_data, batch)

    def step_greedy(self, params, kv_data, batch: "RaggedBatch"):
        """Returns (argmax token ids [S] int32, new kv_data)."""
        return self._step_greedy(params, kv_data, batch)

    def step_greedy_fb(self, params, kv_data, batch: "RaggedBatch",
                       prev_tok, feed_mask, feed_idx):
        """Greedy step with device token feedback: slot i's input token is
        ``prev_tok[feed_idx[i]]`` where ``feed_mask[i]`` is set (the
        previous step's device-resident last-token buffer), else
        ``batch.tokens[i, 0]``. Returns (token ids [S] int32, new
        kv_data)."""
        return self._step_greedy_fb(params, kv_data, batch, prev_tok,
                                    feed_mask, feed_idx)

    def step_sample_fb(self, params, kv_data, batch: "RaggedBatch",
                       prev_tok, feed_mask, feed_idx, seeds, spos, temps,
                       top_ks, top_ps):
        """Sampled sibling of :meth:`step_greedy_fb`: per-slot
        temperature/top-k/top-p selection with in-program
        ``fold_in(PRNGKey(seeds[i]), spos[i])`` keys; slots with
        ``temps[i] <= 0`` are exact argmax (the temperature→0 oracle).
        Returns ((token ids [S] int32, chosen logprobs [S] f32), new
        kv_data) — the token buffer doubles as the next step's device
        feedback source."""
        return self._step_sample_fb(params, kv_data, batch, prev_tok,
                                    feed_mask, feed_idx, seeds, spos,
                                    temps, top_ks, top_ps)

    def decode_loop(self, params, kv_data, tok0, start_pos, active,
                    block_tables, n: int, *, seeds=None, temps=None,
                    top_ks=None, top_ps=None, eos_id: int = -1,
                    draft_toks=None, candidates: int = SAMPLE_CANDIDATES,
                    state_slots=None):
        """Decode ``n`` tokens per active slot on-device (greedy when
        ``temps`` is None, else per-slot temperature/top-k/top-p
        categorical — the whole sampler lives inside the scan, keys
        derived from (seed, position)) and flush the loop's KV into the
        pool.

        tok0 [S] int32: each slot's next input token (KV not yet appended);
        start_pos [S]: its absolute position; active [S]: 1 live / 0 idle.
        ``eos_id`` >= 0 freezes a slot once it emits eos (it keeps emitting
        eos and stops consuming KV). ``draft_toks`` [S, n] switches the
        loop to the speculative VERIFY feed: step t consumes
        ``draft_toks[:, t]`` instead of the previous step's own output,
        so one program scores the model's choice after every draft
        prefix. Returns (tokens [S, n] int32, logprobs [S, n] f32 or
        None, new kv_data, consumed [S] int32 or None — KV positions
        each slot appended, None when EOS is off — and moe_rows [E + 2]
        int32 or None: real rows routed to each expert over the loop's
        steps and the sparse layers, then ``llama_runner._moe_mlp``'s two
        counts of the grouped kernel's work; None for a model without
        experts).
        Slots must have KV blocks covering start_pos..start_pos+n-1.
        """
        jnp_ = jax.numpy
        mode = "greedy" if temps is None else "sample"
        feed = "given" if draft_toks is not None else "self"
        if temps is None:
            # unused-but-required operands of the greedy variant: [1]
            # dummies, staged once (shape participates in the jit key,
            # so the greedy program never retraces over them)
            if not hasattr(self, "_dummy_samp"):
                z1 = jnp_.zeros((1,), jnp_.int32)
                self._dummy_samp = (z1, jnp_.zeros((1,), jnp_.float32),
                                    z1, jnp_.ones((1,), jnp_.float32))
            seeds, temps, top_ks, top_ps = self._dummy_samp
        if draft_toks is None:
            if not hasattr(self, "_dummy_draft"):
                self._dummy_draft = jnp_.zeros((1, 1), jnp_.int32)
            draft_toks = self._dummy_draft
        cand = min(candidates, getattr(self.model_cfg, "vocab_size",
                                       1 << 30))
        # the recurrent state leaves the cache value for the loop (its
        # carry, donated) and rejoins it after the flush, which takes the
        # paged planes alone
        lin = None
        if self.state_spec is not None:
            lin = (kv_data.state, kv_data.conv)
            kv_data = kv_data._replace(state=None, conv=None)
        toks, lps, ring, consumed, moe_rows, lin = self._decode_loop_ring(
            params, kv_data, lin, state_slots, tok0, start_pos, active,
            block_tables, seeds, temps, top_ks, top_ps, draft_toks,
            n=n, mode=mode, cand=int(cand), eos_id=int(eos_id), feed=feed)
        kv_data = self._flush_ring(
            kv_data, ring, block_tables, start_pos, active,
            *(() if self.window_spec is None else (state_slots,)))
        if lin is not None:
            kv_data = kv_data._replace(state=lin[0], conv=lin[1])
        return toks, (lps if mode == "sample" else None), kv_data, \
            (consumed if int(eos_id) >= 0 else None), \
            (moe_rows if moe_rows.shape[0] else None)


class GPT2RaggedRunner(RaggedRunnerBase):
    """Paged-KV decode/prefill over the flax ``GPT2`` param tree
    (``deepspeed_tpu/models/gpt2.py`` naming: wte/wpe/h_i/ln_f). The fused
    ``c_attn`` qkv needs its output dim re-laid chip-major under TP so the
    local ``jnp.split`` still yields (q, k, v) — see tp.py."""

    tp_fused_qkv = (r"attn/c_attn",)


def _gpt2_ragged_step(params, kv, batch: RaggedBatch, *, model_cfg: GPT2Config,
                      cfg: RaggedInferenceConfig, dtype):
    S, C = batch.tokens.shape
    H = model_cfg.num_heads
    D = model_cfg.hidden_size // H
    scale = 1.0 / (D ** 0.5)

    # absolute positions of this step's queries
    pos = batch.start_pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    valid_q = jnp.arange(C, dtype=jnp.int32)[None, :] < batch.n_tokens[:, None]
    pos_c = jnp.minimum(pos, model_cfg.max_seq_len - 1)

    wte = params["wte"]["embedding"]
    wpe = params["wpe"]["embedding"]
    x = (wte[batch.tokens] + wpe[pos_c]).astype(dtype)      # [S, C, E]

    for li in range(model_cfg.num_layers):
        p = params[f"h_{li}"]
        h = _layer_norm(x.astype(jnp.float32), p["ln_1"], model_cfg.layer_norm_eps).astype(dtype)
        qkv = h @ p["attn"]["c_attn"]["kernel"].astype(dtype)
        if "bias" in p["attn"]["c_attn"]:
            qkv = qkv + p["attn"]["c_attn"]["bias"].astype(dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(S, C, H, D)
        k = k.reshape(S, C, H, D)
        v = v.reshape(S, C, H, D)

        kv, y = paged_attention(kv, li, q, k, v, batch, cfg, pos, valid_q,
                                scale, dtype)

        y = y @ p["attn"]["c_proj"]["kernel"].astype(dtype)
        y = tp_all_reduce(y, cfg)           # TP collective 1 (row-parallel)
        if "bias" in p["attn"]["c_proj"]:
            y = y + p["attn"]["c_proj"]["bias"].astype(dtype)
        x = x + y

        h = _layer_norm(x.astype(jnp.float32), p["ln_2"], model_cfg.layer_norm_eps).astype(dtype)
        m = h @ p["mlp"]["c_fc"]["kernel"].astype(dtype)
        if "bias" in p["mlp"]["c_fc"]:
            m = m + p["mlp"]["c_fc"]["bias"].astype(dtype)
        m = jax.nn.gelu(m)
        m = m @ p["mlp"]["c_proj"]["kernel"].astype(dtype)
        m = tp_all_reduce(m, cfg)           # TP collective 2 (row-parallel)
        if "bias" in p["mlp"]["c_proj"]:
            m = m + p["mlp"]["c_proj"]["bias"].astype(dtype)
        x = x + m

    x = _layer_norm(x.astype(jnp.float32), params["ln_f"], model_cfg.layer_norm_eps)

    # logits_gather: only each slot's last valid token
    last = jnp.maximum(batch.n_tokens - 1, 0)               # [S]
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    logits = x_last.astype(jnp.float32) @ wte.T.astype(jnp.float32)
    return logits, kv


GPT2RaggedRunner.step_fn = staticmethod(_gpt2_ragged_step)
