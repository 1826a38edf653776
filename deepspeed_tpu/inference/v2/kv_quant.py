"""Quantized (int8) KV-cache pool support.

Capability analogue of the reference's KV-cache quantization surface
(``inference/v2/model_implementations/flat_model_helpers.py`` stores KV in
the model's quantization dtype; the FastGen blog lists KV-block memory as
the occupancy limiter). On TPU the decode step is HBM-bandwidth bound and
the KV pool is the dominant term (measured 7.4 GB/step vs 2.2 GB weights at
the llama-1.1B bench shape — PROFILE.md), so int8 KV halves the dominant
traffic term AND doubles the sequences a fixed pool can hold.

Design (TPU-first):
  * pool data stays the flat ``[L, 2, slots, KV*D]`` row layout, in int8;
  * scales are PER TOKEN-ROW PER KV-HEAD, stored TRANSPOSED as
    ``[L, 2, KV, slots]`` f32 — 4 bytes per (row, head) = ~3% of the int8
    row bytes, and the transposed layout means a context window's scales
    DMA as ``KV`` contiguous runs (a ``[slots, KV]`` layout would be
    (8,128)-tile padded to 128 lanes in HBM: 512 bytes/row, destroying
    the win);
  * kernels never materialize dequantized K/V tiles: K-scales multiply the
    SCORE columns after the q@k matmul, V-scales multiply the probability
    columns before the p@v matmul (both exact — the scale is constant
    along the contracted D axis).

The decode-loop ring buffer stays in the compute dtype (bf16): ring rows
are the loop's freshest tokens, rewritten every step; they are quantized
once, at flush time.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax.numpy as jnp


class KVPool(NamedTuple):
    """The cache value every program threads. ``data`` [L, 2, slots, KV*D]
    holds the paged planes of the softmax-attention layers; ``scales`` is
    None for an unquantized pool, else [L, 2, KV, slots] f32 per-row
    scales. A model with recurrent layers (``layer_kinds`` with "kda")
    also carries ``state``, a tuple of one [max_seqs + 1, H, dv, dk] f32
    array a recurrent layer (a transposed delta-rule state a sequence
    slot), and ``conv`` [Ls, max_seqs + 1, K - 1, width], the short
    convolution's last inputs; the last row of both is the idle row that
    padding rows of a batch point at. A model with sliding-window layers
    (``layer_kinds`` with "swa") carries ``window``, a second pool of
    ``data``'s own form [Lw, 2, (max_seqs + 1) * R * block_size, KV*D]
    over those layers alone: a sequence's slot (the one the state rows
    go by) owns R whole blocks of it for its life, logical block ``b``
    of the sequence lives in the slot's block ``b % R``, and the last
    slot is the idle one (kv_cache.py has R). A model with block-selected
    ("sparse") attention layers carries ``index``, the compressed-key
    plane [Ls, slots / stride, KV*D]: one row a ``stride`` consecutive
    positions of a block (the mean of their K rows), a sparse layer and
    all kv heads, addressed by the paged pool's own block table (block
    ``b`` owns rows ``b x block_size / stride ..``), and ``sel_counts``
    [2] int32, the selection blocks its prefill chunks selected and
    visited so far (``index_plane.py``). A recurrent model without a
    short convolution (``"lightning"``) has ``conv`` None beside its
    ``state``."""
    data: Any
    scales: Optional[Any] = None
    state: Optional[Any] = None
    conv: Optional[Any] = None
    window: Optional[Any] = None
    index: Optional[Any] = None
    sel_counts: Optional[Any] = None


class RingKV(NamedTuple):
    """Fused-decode-loop KV state threaded through the runners: the pool is
    READ-ONLY; this step's K/V goes into the [R, L, 2, S, KV*D] ring at
    index ``t`` (see RaggedRunnerBase._decode_loop). ``moe_rows`` [E + 2]
    rides along for models with routed experts: the loop's running count
    of real rows routed to each expert, then of the experts the grouped
    kernel found hit and the visits it made, which the sparse layers add to.
    ``lin`` is the ``(state, conv)`` pair of a model with recurrent
    layers: unlike the pool it cannot stay read-only, so it is a carry of
    the loop and the recurrent layers update it in place. ``idx`` rides
    along for models with block-selected layers: [Ls, S, NG, KV*D] float32,
    the sums of the loop's own K rows over each ``stride``-group the loop
    touches, group 0 the one that holds each sequence's first ring
    position (``index_plane.py``): the selection sees windows that end
    inside the ring, as attention sees the ring's rows."""
    pool: Any           # KVPool or raw pool array
    ring: Any
    t: Any
    rcount: Any
    moe_rows: Any = None
    lin: Any = None
    idx: Any = None


def pool_parts(kv, window: bool = False) -> Tuple[Any, Optional[Any]]:
    """(data, scales) view of a pool that may be a KVPool or a raw array;
    with ``window`` the window pool (never quantized) in the same form."""
    if window:
        return kv.window, None
    if isinstance(kv, KVPool):
        return kv.data, kv.scales
    return kv, None


def repack(kv, data, scales, window: bool = False):
    """Rebuild the caller's pool type from updated parts."""
    if window:
        return kv._replace(window=data)
    if isinstance(kv, KVPool):
        return kv._replace(data=data, scales=scales)
    return data


def quantize_rows(rows: jnp.ndarray, kv_heads: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-(row, kv-head) int8 quantization.

    rows: [N, KV*D] float. Returns (q [N, KV*D] int8,
    scales [KV, N] f32) — scales TRANSPOSED to match the pool's scale
    layout. Zero rows get scale 1 (dequantize to exact zeros).
    """
    n, kvd = rows.shape
    d = kvd // kv_heads
    r = rows.reshape(n, kv_heads, d).astype(jnp.float32)
    amax = jnp.max(jnp.abs(r), axis=2)                    # [N, KV]
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(r / s[:, :, None]), -127, 127)
    return q.astype(jnp.int8).reshape(n, kvd), s.T


def dequantize_rows(q: jnp.ndarray, scales_t: jnp.ndarray,
                    dtype=jnp.bfloat16) -> jnp.ndarray:
    """Inverse of :func:`quantize_rows` (test/debug path only — the
    kernels scale scores/probabilities instead). q [N, KV*D],
    scales_t [KV, N] -> [N, KV*D] in ``dtype``."""
    n, kvd = q.shape
    kv = scales_t.shape[0]
    d = kvd // kv
    r = q.reshape(n, kv, d).astype(jnp.float32) * scales_t.T[:, :, None]
    return r.reshape(n, kvd).astype(dtype)


def lin_parts(kv):
    """(state, conv) of a cache value or of the fused loop's RingKV; a
    pair of None for a model without recurrent layers."""
    if isinstance(kv, RingKV):
        return kv.lin if kv.lin is not None else (None, None)
    if isinstance(kv, KVPool):
        return kv.state, kv.conv
    return None, None


def with_lin(kv, state, conv):
    """``kv`` with its recurrent state replaced."""
    if isinstance(kv, RingKV):
        return kv._replace(lin=(state, conv))
    return kv._replace(state=state, conv=conv)
