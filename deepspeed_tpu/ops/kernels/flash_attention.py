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
delta = rowsum(dO * O) precomputed outside the kernels. The dk/dv kernel
holds its tiles TRANSPOSED (``[key cols, query rows]``: scores as ``k q^T``),
so ``dv = p^T do`` and ``dk = ds^T q`` are plain matmuls with no transpose
of a score-sized tile, and lse / delta reach it as lane-dense ``(1,
block_q)`` rows (4 KB a block where the ``(block_q, 1)`` column the dq
kernel reads is padded to 512 KB in HBM).

Causal work inside a block. The block is the DMA tile (1024 x 1024 in the
benchmark's train cells, a 2 x 2 grid over T = 2048) and NOT the grain of
the causal mask: a grid step knows from its position which of three kinds
its block is (``_block_kinds``) and each kind has its own body in the three
dense kernels.

* interior: the diagonal never touches it. No iota, no compare, no select:
  scores go straight to the online softmax (backward: to ``exp(s - lse)``).
* aligned diagonal: a square block the diagonal enters at its top-left
  corner. It is cut into ``n x n`` sub-tiles (``_sub_tiles``: halves in the
  forward, quarters in the backward, never under 128 rows) and walked one
  STRIP a sub-tile: forward and dq take query sub-tile ``i`` against the key
  columns up to its own, dk/dv takes key sub-tile ``i`` against the query
  rows from its own down (per-row state makes a slice independent). The
  sub-tiles above the diagonal are never computed (no matmul, no exp); the
  strip is masked as one.
* general: everything else (non-causal, a padded last key block, ``tq !=
  tk`` or a ring hop whose diagonal meets no block corner): the whole block
  under its ``[block_q, block_k]`` mask.

A block wholly above the diagonal runs nothing, and its grid step fetches
nothing either: the index maps hold the last block the row (the column, in
dk/dv) does need (``_last_key_block``, ``_first_query_block``).

A sliding window (``window=``: key ``j`` is visible to query ``i`` iff ``0
<= i + causal_offset - j < window``) adds the diagonal's mirror image. A
block wholly LEFT of the window runs nothing and fetches nothing, by the
same hold from the other side; the block the window's left edge enters at
its top-left corner (square blocks, ``window`` a multiple of the block) is
the fourth kind, ``edge``: local key ``c`` is visible to local query ``r``
iff ``c > r``, the diagonal block's complement, and it is walked in the
same strips from the other corner (query sub-tile ``i`` against the key
columns FROM its own; dk/dv: key sub-tile ``i`` against the query rows UP
TO its own). The block's last row sees nothing of it and it is the first
block that row meets, so an edge update guards the running maximum against
``-inf - -inf``. A window that meets no block corner is a general block
under the whole-block mask. ``window=None`` traces the kernels it always
traced; a windowed call's kernels are named ``attn_w<window>``.

A windowed call's grid is as wide as its window. The innermost grid
dimension of forward and dq is not the ``nk`` key blocks but the most key
blocks any query block sees (``_walk_steps``: a static number from the
blocks, the window and the offset, ``window / block + 1`` for aligned
square blocks: 3 of 8 at T = 8192, 1024-blocks and a window of 2048), and
a row's steps are the run of blocks that ENDS at its diagonal block
(``_key_step``), so ``_init`` / ``_finish`` stay on the first / last step.
dk/dv walks ``group x`` the most query blocks any key block is seen from,
counted from the first that sees it (``_query_step``). The one idle kind
of step left is one that falls outside a run shorter than the grid is wide
(the first rows of a sequence, the last key columns, a ``causal_offset``
that starts a row inside the window): it runs no body and holds a block
the row does need. The bodies take the step's block index where they took
the grid's. ``causal_plan`` counts the grid steps the three kernels take
a q head (``steps``: 72 at that shape where the square's walk took 192)
and those that run a body (``steps_run``: 63 either way). Without a window
the walk is the whole square's, the steps above the diagonal idle.

So a large tile no longer pays for the masked half of its diagonal blocks:
at T = 2048 and 1024-blocks the forward computes 0.625 of the square and dq
and dk/dv 0.5625, where a whole-block skip alone computes 0.75 (the mask
needs 0.5). ``causal_plan`` counts the kinds and the score elements of one
call from its static shapes, and ``flash_attention`` notes it for every
causal call it traces (``take_causal_plans``: the train engine's
``flash_score_elems_*``).

Residuals under a caller's recompute. The forward rules keep ``(q, k, v,
o, lse)`` for the backward. ``q``, ``k``, ``v`` are the caller's own; ``o``
and ``lse`` are the kernel's, and the rules put a
``jax.ad_checkpoint.checkpoint_name`` on each: ``RESIDUAL_OUT``
(``"flash_out"``, ``[b, h, tq, d]`` in the inputs' dtype: ``b x h x t x d x
2`` bytes a call in bfloat16) and ``RESIDUAL_LSE`` (``"flash_lse"``, named
in the lane-dense form ``[b, h, tq]`` float32, ``b x h x t x 4`` bytes, and
widened to the kernels' ``[b, h, tq, 1]`` column after the name). A name is
the identity. A caller that wraps its layer in ``jax.checkpoint`` /
``nn.remat`` with ``policy=jax.checkpoint_policies.save_only_these_names(
*RESIDUAL_NAMES)`` keeps the two from its forward to its backward, and its
recompute then runs NO forward kernel (dq and dk/dv read the kept arrays:
the ones the recompute would have made, to the bit); one whose policy lists
neither, or that has no policy, compiles to what it compiled to and runs
the forward kernel again for them (``models/afmoe.py`` lists them;
``models/gpt2.py``'s policies and ``models/llama.py`` do not).

Three bodies a kernel cost three times the tracing and lowering, so the
launchers ``_fwd`` / ``_bwd`` are inner jits: a model's unrolled layers
share one traced and lowered copy of each call, named ``attn`` in a profile.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
_LANES = 128


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# causal work inside a block: the three kinds, the sub-tile rule, the plan
# ---------------------------------------------------------------------------

#: sub-tiles a side of an aligned diagonal block, at most, by kernel. On
#: v5e at [2, 16, 2048, 128] and 1024-blocks (PERF.md, PR 39): every strip
#: of the forward is one online-softmax update whose matmul, softmax and
#: matmul run one after the other, so halves beat quarters (0.392 against
#: 0.415 ms a call; eighths 0.458); the backward kernels only accumulate,
#: and quarters beat halves (dq 0.412 / 0.445, dk/dv 0.508 / 0.553 ms).
_MAX_SUB_TILES = {"fwd": 2, "dq": 4, "dkv": 4}


def _sub_tiles(block: int, kernel: str) -> int:
    """Sub-tiles a side of an aligned diagonal block in ``kernel``: the
    most (up to ``_MAX_SUB_TILES``) that keep a sub-tile a whole number of
    128-lane tiles. Backward: 1024 -> 4 x 256, 512 -> 4 x 128, 256 -> 2 x
    128; a block of 128, or one that does not divide, is its own sub-tile."""
    for n in range(_MAX_SUB_TILES[kernel], 1, -1):
        if block % (n * _LANES) == 0:
            return n
    return 1


def _block_kinds(qi, ki, *, causal, block_q, block_k, kv_len, causal_offset,
                 window=None):
    """(interior, diagonal, general, edge) for the block at grid position
    ``(qi, ki)``: at most one holds, none for a causal block wholly above
    the diagonal or wholly left of the window. Works on traced
    ``program_id``s and, for ``_grid_kinds``, on plain ints."""
    if not causal:
        return False, False, True, False
    row0 = qi * block_q + causal_offset       # diagonal column of row 0
    col0 = ki * block_k
    # blocks strictly above the (bottom-right-aligned) diagonal run nothing
    run = col0 <= row0 + (block_q - 1)
    crossed = col0 + (block_k - 1) > row0     # the diagonal cuts the block
    padded = col0 + block_k > kv_len          # it holds padded key columns
    interior = (col0 + (block_k - 1) <= row0) & (col0 + block_k <= kv_len)
    aligned = block_q == block_k and causal_offset % block_q == 0
    if window is None:
        if not aligned:
            return interior, False, run & (crossed | padded), False
        diagonal = (row0 == col0) & (col0 + block_k <= kv_len)
        return (interior, diagonal,
                run & ((crossed & (row0 != col0)) | padded), False)
    # the window's left edge: row r sees columns > r + causal_offset - window
    run = run & (col0 + (block_k - 1) > row0 - window)
    cut = col0 <= row0 + (block_q - 1) - window    # the edge cuts the block
    interior = interior & (col0 > row0 + (block_q - 1) - window)
    if not aligned or window % block_q:
        return interior, False, run & (crossed | cut | padded), False
    whole = col0 + block_k <= kv_len
    diagonal = (row0 == col0) & whole
    edge = (col0 == row0 - window) & whole
    return (interior, diagonal,
            run & ((crossed & (row0 != col0))
                   | (cut & (col0 != row0 - window)) | padded), edge)


def _grid_kinds(nq: int, nk: int, **geom):
    """How many blocks of an ``nq`` x ``nk`` grid are (interior, diagonal,
    general, edge). Static: a kernel traces no body for a kind its grid
    lacks (the train cells' grid has no general block), and ``causal_plan``
    reports the counts."""
    counts = [0, 0, 0, 0]
    for qi in range(nq):
        for ki in range(nk):
            for i, kind in enumerate(_block_kinds(qi, ki, **geom)):
                counts[i] += bool(kind)
    return tuple(counts)


def _causal_mask(rows: int, cols: int, diag, transposed: bool):
    """``row + diag >= col`` over a ``rows`` x ``cols`` tile (local
    indices; ``diag`` is the tile's first row's diagonal column less its
    first column). ``transposed``: laid out ``[cols, rows]``, as the dk/dv
    kernel holds its scores."""
    shape = (cols, rows) if transposed else (rows, cols)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 1 if transposed else 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 0 if transposed else 1)
    return row + diag >= col


def _general_mask(qi, ki, lse=None, *, transposed=False, causal, block_q,
                  block_k, kv_len, causal_offset, window=None):
    """The whole-block mask of the general kind: key padding, the causal
    diagonal and the window's left edge wherever they run and, in the
    backward, query rows no key is visible to (``lse == -inf``; ``exp(s -
    lse)`` would be inf there)."""
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    col = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0 if transposed else 1)
    mask = col < kv_len
    if causal:
        mask = jnp.logical_and(mask, _causal_mask(
            block_q, block_k,
            qi * block_q + causal_offset - ki * block_k, transposed))
    if window is not None:
        mask = jnp.logical_and(mask, jnp.logical_not(_causal_mask(
            block_q, block_k,
            qi * block_q + causal_offset - window - ki * block_k,
            transposed)))
    if lse is not None:
        mask = jnp.logical_and(mask, jnp.isfinite(lse))
    return mask


def _update_by_kind(qi, ki, update, *, kernel, present, lse=None, **geom):
    """Run ``update(mask, rows, cols)`` over the block at ``(qi, ki)`` as
    its kind asks: once and unmasked (interior), once a sub-tile strip on
    and below the diagonal (aligned diagonal) or right of the window's
    edge (aligned edge), or once under the whole-block mask (general). The
    strips follow the axis whose slices are independent in ``kernel``:
    per-query-row state (``"fwd"``, ``"dq"``) takes query sub-tile ``i``
    against the key columns up to its own (edge: from its own);
    per-key-row state (``"dkv"``) takes key sub-tile ``i`` against the
    query rows from its own down (edge: up to its own), masks laid out
    ``[cols, rows]`` as that kernel holds its tiles. One strip is one
    update: a strip cut again into its unmasked part and the sub-tile on
    the diagonal paid more for the second update than the narrower mask
    saved. ``lse`` is a thunk for the block's logsumexp where the general
    mask needs it. ``present`` (``_grid_kinds``) drops the bodies of kinds
    this grid lacks. Under a window an update may meet a row with nothing
    visible before anything was (the edge block's last row; any row of a
    general block): ``update(..., guard=True)`` tells the forward."""
    interior, diagonal, general, edge = _block_kinds(qi, ki, **geom)
    block_q, block_k = geom["block_q"], geom["block_k"]
    whole_q, whole_k = pl.ds(0, block_q), pl.ds(0, block_k)
    transposed = kernel == "dkv"

    if present[0]:
        pl.when(interior)(lambda: update(None, whole_q, whole_k))

    if present[1]:
        @pl.when(diagonal)
        def _sub_tiled():
            n = _sub_tiles(block_q, kernel)
            sub = block_q // n
            for i in range(n):
                if transposed:
                    rows, cols, diag = ((i * sub, block_q - i * sub),
                                        (i * sub, sub), 0)
                else:
                    rows, cols, diag = ((i * sub, sub), (0, (i + 1) * sub),
                                        i * sub)
                update(_causal_mask(rows[1], cols[1], diag, transposed),
                       pl.ds(*rows), pl.ds(*cols))

    if present[2]:
        @pl.when(general)
        def _whole_block():
            update(_general_mask(qi, ki, None if lse is None else lse(),
                                 transposed=transposed, **geom),
                   whole_q, whole_k, guard=geom.get("window") is not None)

    if present[3]:
        @pl.when(edge)
        def _edge_tiled():
            # local key c is visible to local query r iff c > r
            n = _sub_tiles(block_q, kernel)
            sub = block_q // n
            for i in range(n):
                if transposed:
                    rows, cols, diag = ((0, (i + 1) * sub), (i * sub, sub),
                                        -i * sub)
                else:
                    rows, cols, diag = ((i * sub, sub),
                                        (i * sub, block_k - i * sub), 0)
                update(jnp.logical_not(_causal_mask(rows[1], cols[1], diag,
                                                    transposed)),
                       pl.ds(*rows), pl.ds(*cols), guard=True)


def _clamp(x, lo: int, hi: int):
    """``min(max(x, lo), hi)`` of a plain int (the static reckoning of a
    grid's width) or of a traced block index (an index map, a kernel)."""
    if isinstance(x, int):
        return min(max(x, lo), hi)
    return jnp.clip(x, lo, hi)


def _key_run(qi, nk, *, block_q, block_k, causal_offset, window, **_):
    """(first, last) key block query block ``qi`` sees under a causal
    mask (``window`` None: first is 0), both inside ``0 .. nk - 1``."""
    last = (qi * block_q + (block_q - 1) + causal_offset) // block_k
    if window is None:
        return 0, _clamp(last, 0, nk - 1)
    first = (qi * block_q + causal_offset - window + 1) // block_k
    return _clamp(first, 0, nk - 1), _clamp(last, 0, nk - 1)


def _query_run(ki, nq, *, block_q, block_k, causal_offset, window, **_):
    """The dk/dv walk's counterpart: (first, last) query block that sees
    key block ``ki`` (``window`` None: last is ``nq - 1``)."""
    first = (ki * block_k - causal_offset) // block_q
    if window is None:
        return _clamp(first, 0, nq - 1), nq - 1
    last = (ki * block_k + (block_k - 1) - causal_offset + window - 1) \
        // block_q
    return _clamp(first, 0, nq - 1), _clamp(last, 0, nq - 1)


def _walk_steps(nq: int, nk: int, **geom):
    """Innermost grid steps of (forward and dq, dk/dv a q head). Without a
    window a row walks every key block (a column every query block). Under
    one the grid is as wide as the window: the most key blocks any query
    block sees (the most query blocks any key block is seen from), exactly,
    in plain ints over the ``nq`` rows (``nk`` columns): ``window / block +
    1`` for aligned square blocks, 3 of 8 at the Trinity cell's shape."""
    if geom["window"] is None:      # a window is under a causal mask
        return nk, nq
    runs = [_key_run(qi, nk, **geom) for qi in range(nq)]
    seen = [_query_run(ki, nq, **geom) for ki in range(nk)]
    return (max(last - first + 1 for first, last in runs),
            max(last - first + 1 for first, last in seen))


def _key_step(qi, j, nk, steps, **geom):
    """(key block, whether the step may run a body) of forward / dq grid
    step ``(qi, j)``. Without a window the step IS the block. Under one the
    row's ``steps`` steps are the run of blocks that ENDS at the last block
    it sees, so its last step is always its diagonal block; a step that
    falls before the first block the row sees (the first rows of a
    sequence, a ``causal_offset`` that starts a row inside the window) is
    the one idle kind left."""
    if geom["window"] is None:      # a window is under a causal mask
        return j, None
    first, last = _key_run(qi, nk, **geom)
    ki = last - (steps - 1) + j
    return ki, ki >= first


def _query_step(ki, s, nq, **geom):
    """The dk/dv walk's counterpart: key block ``ki``'s step ``s`` counts
    query blocks from the first that sees it; one past the last idles."""
    if geom["window"] is None:      # a window is under a causal mask
        return s, None
    first, last = _query_run(ki, nq, **geom)
    return first + s, first + s <= last


def _last_key_block(qi, j, nk, steps, **geom):
    """The key block to hold at grid step ``(qi, j)``: the step's own
    while the query block sees it, else the nearest one it does see (the
    last for a step above the diagonal, the first for one before the
    window). Such a step computes nothing, and a block index that repeats
    the step before costs no DMA (nor the re-fetch of block 0 when the
    next query block starts)."""
    if not geom["causal"]:
        return j
    first, last = _key_run(qi, nk, **geom)
    if geom["window"] is None:
        return jnp.minimum(j, last)
    return jnp.maximum(_key_step(qi, j, nk, steps, **geom)[0], first)


def _first_query_block(s, ki, nq, **geom):
    """The dk/dv walk's counterpart: the query block to hold at key block
    ``ki``'s step ``s`` is the step's own while it sees the key block,
    else the first one that does (under a window: the last)."""
    if not geom["causal"]:
        return s
    first, last = _query_run(ki, nq, **geom)
    if geom["window"] is None:
        return jnp.maximum(s, first)
    return jnp.minimum(first + s, last)


def causal_plan(tq: int, tk: int, block_q: int, block_k: int, kv_len: int,
                causal_offset: int, window: Optional[int] = None) -> dict:
    """What the three kernels of ONE causal call over padded lengths ``tq``
    x ``tk`` compute for one (batch, head), from its static shapes alone:
    how many blocks of each kind the grid holds (the kernels share it),
    the side of a sub-tile in each kernel, the score elements the forward,
    dq and dk/dv compute together, and the elements the mask needs of the
    three (``0 <= row + causal_offset - col < window``, ``col < kv_len``:
    a row's ``min(i + 1, window)`` when the lengths are equal).
    ``score_area_share`` is computed / needed: 1.0 would be kernels that
    compute no masked score. ``steps`` are the grid steps the three take
    together for one q head (``_walk_steps``; dk/dv's a q head of its KV
    head's group) and ``steps_run`` those that run a body (a block of a
    kind, in each kernel); ``skipped`` stays the blocks of the SQUARE not
    computed, whether a step idles over them or the grid leaves them out."""
    nq, nk = tq // block_q, tk // block_k
    geom = dict(causal=True, block_q=block_q, block_k=block_k, kv_len=kv_len,
                causal_offset=causal_offset, window=window)
    interior, diagonal, general, edge = _grid_kinds(nq, nk, **geom)
    run = interior + diagonal + general + edge
    k_steps, q_steps = _walk_steps(nq, nk, **geom)
    plan = {"interior": interior, "sub_tiled": diagonal, "general": general,
            "edge": edge, "skipped": nq * nk - run,
            "steps": 2 * nq * k_steps + nk * q_steps,
            "steps_run": len(_MAX_SUB_TILES) * run, "sub": {}}
    computed = 0
    for kernel in _MAX_SUB_TILES:
        n = _sub_tiles(block_q, kernel)
        plan["sub"][kernel] = sub = block_q // n
        computed += ((interior + general) * block_q * block_k
                     + (diagonal + edge) * (n * (n + 1) // 2) * sub * sub)
    diag = np.arange(tq) + causal_offset
    visible = np.clip(diag + 1, 0, kv_len)
    if window is not None:
        visible = np.clip(visible - np.clip(diag - window + 1, 0, kv_len),
                          0, None)
    plan["score_elems_computed"] = computed
    plan["score_elems_needed"] = len(_MAX_SUB_TILES) * int(visible.sum())
    plan["score_area_share"] = computed / max(plan["score_elems_needed"], 1)
    return plan


#: (batch, heads, plan) of every causal ``flash_attention`` call traced
#: since the last ``take_causal_plans``
_CAUSAL_PLANS: list = []


def take_causal_plans() -> list:
    """The plans noted since the last call, as ``(batch, heads, plan)``;
    the record is cleared. A caller that traces a program (the train
    engine around its step function) reads what that program will compute
    a run; calls are noted when TRACED, so a cached program notes none."""
    plans = list(_CAUSAL_PLANS)
    _CAUSAL_PLANS.clear()
    return plans


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _when(cond, body):
    """``body()`` under ``pl.when(cond)``; ``cond`` None: always."""
    body() if cond is None else pl.when(cond)(body)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale, present, nk, **geom):
    qi = pl.program_id(2)
    j = pl.program_id(3)
    steps = pl.num_programs(3)
    ki, seen = _key_step(qi, j, nk, steps, **geom)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[:] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[:] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    def update(mask, rows, cols, guard=False):
        _online_softmax_block(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                              mask, sm_scale, rows, cols, guard)

    _when(seen, lambda: _update_by_kind(qi, ki, update, kernel="fwd",
                                        present=present, **geom))

    @pl.when(j == steps - 1)
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
                          s_mask, sm_scale, rows=None, cols=None,
                          guard=False):
    """One flash block update (shared by the dense and sparse kernels):
    scores for the current (q, k) tile, ``s_mask`` applied (None: every
    score counts), online-softmax accumulators advanced. ``rows`` /
    ``cols`` (``pl.ds``) narrow the update to a sub-tile of the block:
    the accumulators are per query row, so a row slice is independent.
    Matmul operands stay in their storage dtype (bf16 runs the MXU at
    full rate) with fp32 accumulation. ``guard``: a row may have nothing
    visible here and nothing behind it (a window's edge), so its running
    maximum is still ``-inf``: the exponents then take 0 for it, and the
    row's sums stay 0 in place of ``exp(-inf - -inf)``."""
    rows = slice(None) if rows is None else rows
    cols = slice(None) if cols is None else cols
    q = q_ref[0, 0, rows, :]
    k = k_ref[0, 0, cols, :]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    if s_mask is not None:
        s = jnp.where(s_mask, s, _NEG_INF)
    m_prev = m_scr[rows, :]
    l_prev = l_scr[rows, :]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_cur)
    m_ref = jnp.where(m_next == _NEG_INF, 0.0, m_next) if guard else m_next
    alpha = jnp.exp(m_prev - m_ref)
    p = jnp.exp(s - m_ref[:, :1])
    l_scr[rows, :] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    m_scr[rows, :] = m_next
    v = v_ref[0, 0, cols, :]
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[rows, :] = acc_scr[rows, :] * alpha[:, :1] + pv


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


# ``_fwd`` and ``_bwd`` are jitted: a model calls them once a layer with the
# same shapes, and an inner jit traces the kernels' bodies and lowers them to
# Mosaic ONCE a program where 24 unrolled layers did it 24 times (the three
# bodies a kernel make that 8 s of a warm start at 1.3B; PERF.md, PR 39).
# The calls name themselves ``attn``: without a name a Mosaic call takes the
# caller's innermost scope, which under the inner jit would be ``_fwd``.
_KERNEL_NAME = "attn"


def _kernel_name(window) -> str:
    """A windowed call's kernels say so (``attn_w2048``): a profile's
    reader can tell them from a full call's only by name."""
    return _KERNEL_NAME if window is None else f"{_KERNEL_NAME}_w{window}"


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _fwd(q, k, v, causal, sm_scale, block_q, block_k, kv_len, causal_offset,
         interpret, group=1, window=None):
    b, h, tq, d = q.shape
    tk = k.shape[2]
    nq, nk = tq // block_q, tk // block_k
    geom = dict(causal=causal, block_q=block_q, block_k=block_k,
                kv_len=kv_len, causal_offset=causal_offset, window=window)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale,
                               present=_grid_kinds(nq, nk, **geom), nk=nk,
                               **geom)
    steps, _ = _walk_steps(nq, nk, **geom)
    grid = (b, h, nq, steps)
    out_shape = [
        jax.ShapeDtypeStruct((b, h, tq, d), q.dtype),
        jax.ShapeDtypeStruct((b, h, tq, 1), jnp.float32),
    ]
    # GQA: K/V stay (b, h//group, t, d); the index map broadcasts a KV
    # head across its q-head group — no materialized repeat
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, d),
        lambda b, h, i, j: (b, h // group,
                            _last_key_block(i, j, nk, steps, **geom), 0))
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)),
            kv_spec, kv_spec,
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
        name=_kernel_name(window),
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_tile(q, k, v, do, lse, delta, mask, sm_scale, transposed):
    """Probabilities ``p`` and score cotangents ``ds`` (float32) of one
    tile from its operands. ``mask`` None: every score of the tile counts.
    ``transposed``: the tile is held ``[cols, rows]`` (scores as ``k q^T``,
    ``lse`` / ``delta`` as ``(1, rows)`` rows): what the dk/dv kernel
    contracts over query rows then needs no transpose. Matmul operands stay
    in storage dtype (bf16 MXU) with f32 accumulation."""
    nt = (((1,), (1,)), ((), ()))
    a, b = (k, q) if transposed else (q, k)
    s = jax.lax.dot_general(a, b, nt,
                            preferred_element_type=jnp.float32) * sm_scale
    if mask is None:
        # a query row no key is visible to (lse == -inf) lies in no
        # unmasked tile; the guard stays, on the column (or row) alone
        p = jnp.exp(s - jnp.where(jnp.isfinite(lse), lse, jnp.inf))
    else:
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    a, b = (v, do) if transposed else (do, v)
    dp = jax.lax.dot_general(a, b, nt, preferred_element_type=jnp.float32)
    return p, p * (dp - delta) * sm_scale


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, sm_scale, present, nk, **geom):
    qi = pl.program_id(2)
    j = pl.program_id(3)
    steps = pl.num_programs(3)
    ki, seen = _key_step(qi, j, nk, steps, **geom)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, dq_scr.dtype)

    def update(mask, rows, cols, guard=False):
        del guard       # lse is finite wherever a key is visible
        k = k_ref[0, 0, cols, :]
        _, ds = _bwd_tile(q_ref[0, 0, rows, :], k, v_ref[0, 0, cols, :],
                          do_ref[0, 0, rows, :], lse_ref[0, 0, rows, :],
                          delta_ref[0, 0, rows, :], mask, sm_scale, False)
        dq_scr[rows, :] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _when(seen, lambda: _update_by_kind(
        qi, ki, update, kernel="dq", present=present,
        lse=lambda: lse_ref[0, 0], **geom))

    @pl.when(j == steps - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, sm_scale, present, nq, steps, **geom):
    # GQA grouped accumulation: the grid's innermost dim fuses (q-head in
    # group, step of the key block's walk over query blocks) as gq = qh *
    # steps + s, so ONE kv head's dk/dv accumulates over every q head it
    # serves before the block is written — K/V never get materialized per
    # q-head and the cotangent comes out already (b, hk, t, d)
    ki = pl.program_id(2)
    gq = pl.program_id(3)
    ng = pl.num_programs(3)
    qi, seen = _query_step(ki, gq % steps, nq, **geom)

    @pl.when(gq == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, dk_scr.dtype)
        dv_scr[:] = jnp.zeros(dv_scr.shape, dv_scr.dtype)

    # tiles are held [cols, rows]: dv = p^T do and dk = ds^T q contract
    # over query rows, which in this layout is a plain matmul; lse and
    # delta come as lane-dense (1, block_q) rows
    def update(mask, rows, cols, guard=False):
        del guard
        q = q_ref[0, 0, rows, :]
        do = do_ref[0, 0, rows, :]
        p, ds = _bwd_tile(q, k_ref[0, 0, cols, :], v_ref[0, 0, cols, :], do,
                          lse_ref[0, 0, :, rows], delta_ref[0, 0, :, rows],
                          mask, sm_scale, True)
        nn = (((1,), (0,)), ((), ()))
        dv_scr[cols, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, nn, preferred_element_type=jnp.float32)
        dk_scr[cols, :] += jax.lax.dot_general(
            ds.astype(q.dtype), q, nn, preferred_element_type=jnp.float32)

    _when(seen, lambda: _update_by_kind(
        qi, ki, update, kernel="dkv", present=present,
        lse=lambda: lse_ref[0, 0], **geom))

    @pl.when(gq == ng - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6),
                   static_argnames=("group", "window"))
def _bwd(causal, sm_scale, block_q, block_k, kv_len, causal_offset, interpret,
         res, g, dlse=None, group=1, window=None):
    q, k, v, o, lse = res
    do = g[0]
    b, h, tq, d = q.shape
    hk = k.shape[1]
    tk = k.shape[2]
    nq, nk = tq // block_q, tk // block_k
    geom = dict(causal=causal, block_q=block_q, block_k=block_k,
                kv_len=kv_len, causal_offset=causal_offset, window=window)
    present = _grid_kinds(nq, nk, **geom)
    k_steps, q_steps = _walk_steps(nq, nk, **geom)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                    # (b, h, tq, 1)
    if dlse is not None:
        # lse is a differentiable output here (ring attention combines
        # per-round partials by lse). Its cotangent folds into the FA-2
        # backward exactly: ds = p*(dp - delta) gains + p*dlse, i.e. the
        # same kernels run with delta' = delta - dlse.
        delta = delta - dlse.astype(jnp.float32)

    kv_spec = pl.BlockSpec(
        (1, 1, block_k, d),
        lambda b, h, i, j: (b, h // group,
                            _last_key_block(i, j, nk, k_steps, **geom), 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, present=present,
                          nk=nk, **geom),
        grid=(b, h, nq, k_steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)),
            kv_spec, kv_spec,
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
        name=_kernel_name(window),
    )(q, k, v, do, lse, delta)

    # dk/dv: grid walks KV heads (hk = h // group); the innermost dim fuses
    # (q-head in group, step over the q blocks) so each kv head's cotangent
    # sums its whole q-head group in-scratch — the index maps pick the
    # q-side head as hh * group + gq // q_steps and the q block from the
    # step gq % q_steps.
    # lse and delta go in as lane-dense (b, h, 1, tq) rows: the kernel
    # holds its tiles [cols, rows].
    def q_block(i, gq):
        return _first_query_block(gq % q_steps, i, nq, **geom)

    q_spec = pl.BlockSpec(
        (1, 1, block_q, d),
        lambda b, hh, i, gq: (b, hh * group + gq // q_steps, q_block(i, gq),
                              0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda b, hh, i, gq: (b, hh, i, 0))
    row_spec = pl.BlockSpec(
        (1, 1, 1, block_q),
        lambda b, hh, i, gq: (b, hh * group + gq // q_steps, 0,
                              q_block(i, gq)))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, present=present,
                          nq=nq, steps=q_steps, **geom),
        grid=(b, hk, nk, group * q_steps),
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
        name=_kernel_name(window),
    )(q, k, v, do, jnp.swapaxes(lse, 2, 3), jnp.swapaxes(delta, 2, 3))
    return dq, dk, dv


#: ``jax.ad_checkpoint.checkpoint_name`` of the two residuals the forward
#: rules make themselves (module docstring): a caller's remat policy that
#: lists them (``RESIDUAL_NAMES``) keeps the kernel out of its recompute
RESIDUAL_OUT = "flash_out"
RESIDUAL_LSE = "flash_lse"
RESIDUAL_NAMES = (RESIDUAL_OUT, RESIDUAL_LSE)

_STATIC = tuple(range(3, 12))


@functools.partial(jax.custom_vjp, nondiff_argnums=_STATIC)
def _flash(q, k, v, causal, sm_scale, block_q, block_k, kv_len, causal_offset,
           interpret, group, window):
    o, _ = _fwd(q, k, v, causal, sm_scale, block_q, block_k, kv_len,
                causal_offset, interpret, group, window)
    return o


def _named_residuals(o, lse):
    """``o`` and ``lse`` under their checkpoint names (module docstring):
    ``lse`` in the lane-dense form ``[b, h, tq]``, so that a policy that
    keeps it keeps its numbers and not the ``[.., tq, 1]`` column's 128
    lanes a row, widened again for the backward."""
    o = checkpoint_name(o, RESIDUAL_OUT)
    lse = checkpoint_name(lse[..., 0], RESIDUAL_LSE)[..., None]
    return o, lse


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, kv_len,
               causal_offset, interpret, group, window):
    o, lse = _named_residuals(*_fwd(
        q, k, v, causal, sm_scale, block_q, block_k, kv_len, causal_offset,
        interpret, group, window))
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, kv_len, causal_offset,
               interpret, group, window, res, g):
    return _bwd(causal, sm_scale, block_q, block_k, kv_len, causal_offset,
                interpret, res, (g,), group=group, window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=_STATIC)
def _flash_lse(q, k, v, causal, sm_scale, block_q, block_k, kv_len,
               causal_offset, interpret, group, window):
    """(o, lse) with lse a differentiable output (used by ring attention)."""
    return _fwd(q, k, v, causal, sm_scale, block_q, block_k, kv_len,
                causal_offset, interpret, group, window)


def _flash_lse_fwd(q, k, v, causal, sm_scale, block_q, block_k, kv_len,
                   causal_offset, interpret, group, window):
    o, lse = _named_residuals(*_fwd(
        q, k, v, causal, sm_scale, block_q, block_k, kv_len, causal_offset,
        interpret, group, window))
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(causal, sm_scale, block_q, block_k, kv_len, causal_offset,
                   interpret, group, window, res, cts):
    do, dlse = cts
    return _bwd(causal, sm_scale, block_q, block_k, kv_len, causal_offset,
                interpret, res, (do,), dlse=dlse, group=group, window=window)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# ---------------------------------------------------------------------------
# public wrapper
# ---------------------------------------------------------------------------


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    sm_scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    layout: str = "BTHD",
                    interpret: Optional[bool] = None,
                    return_lse: bool = False):
    """Tiled online-softmax attention; differentiable (custom VJP).

    Args:
      q: (B, T, H, D) [layout="BTHD", flax convention] or (B, H, T, D).
      k, v: same layout; KV head count may divide H (GQA — heads broadcast).
      causal: lower-triangular mask.
      window: sliding window under the causal mask: key ``j`` is visible
        to query ``i`` iff ``0 <= i + (Tk - Tq) - j < window`` (the
        query's own key among the ``window``). Blocks the window hides are
        neither computed nor fetched. A window no shorter than the keys is
        no window: the call is the ``window=None`` one.
      sm_scale: softmax scale, default 1/sqrt(D).
      block_q/block_k: tile sizes (clamped to the padded sequence; block_q
        a multiple of 128 outside interpret mode). 512/512
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
    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"window={window!r} needs causal=True and a "
                             f"window of at least one key")
        window = None if window >= tk else int(window)

    block_q = min(block_q, _round_up(tq, _LANES))
    block_k = min(block_k, _round_up(tk, _LANES))
    if block_q % _LANES and not interpret:
        # the dk/dv kernel reads lse and delta as (1, block_q) lane rows
        raise ValueError(
            f"block_q must be a multiple of {_LANES} on the chip, got "
            f"{block_q}")
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
            tk - tq, interpret, group, window)
    if causal:
        _CAUSAL_PLANS.append((b, h, causal_plan(tq_p, tk_p, block_q, block_k,
                                                tk, tk - tq, window)))
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
    from ...utils.jax_compat import manual_axes, shard_map
    from jax.sharding import PartitionSpec as P

    if layout == "BTHD":
        b_dim, h_dim = 0, 2
    elif layout == "BHTD":
        b_dim, h_dim = 0, 1
    else:
        raise ValueError(f"unknown layout {layout!r}")

    # inside an engine's manual seam (a shard_map over ``data``) the batch
    # is this rank's already: the kernel goes under a shard_map over the
    # axes still automatic (a Mosaic call refuses a context with any)
    manual = set(manual_axes())
    sizes = {a: n for a, n in zip(mesh.axis_names, mesh.devices.shape)
             if a not in manual}
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
    if pspec == P(None, None, None, None) and not manual:
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               layout=layout, block_q=block_q,
                               block_k=block_k, interpret=interpret)

    def local(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=causal, sm_scale=sm_scale,
                               layout=layout, block_q=block_q,
                               block_k=block_k, interpret=interpret)

    return shard_map(local, mesh=None if manual else mesh,
                     in_specs=(pspec, pspec, pspec), out_specs=pspec,
                     check_vma=False,
                     axis_names=tuple(sizes) if manual else ())(q, k, v)


def attention_reference(q, k, v, *, causal=True, window=None, sm_scale=None,
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
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq - window)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    o = o.astype(q.dtype)
    if layout == "BTHD":
        o = jnp.swapaxes(o, 1, 2)
    return o
