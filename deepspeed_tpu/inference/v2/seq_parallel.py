"""Sequence-parallel (long-context) serving for the v2 ragged engine.

Opens the training stack's ``seq`` mesh axis to inference: one sequence's
KV blocks span chips round-robin by CHAIN ORDINAL (block ``o`` of a chain
lives on chip ``o % seq``), so per-chip pool bytes stay FLAT as context
grows past what a single chip's pool holds — the capacity lever the
ROADMAP's 64k–128k prompts need. Three device-side pieces ride the axis:

  * **Context-parallel prefill** — each SplitFuse chunk shards over
    ``seq``: chip ``r`` runs attention for query slice
    ``[r*C/seq, (r+1)*C/seq)`` against the FULL paged history,
    reconstructed from the per-chip pool shards by a ring pass of
    ``seq-1`` :func:`ring_all_gather` ppermute hops (the evoformer ring
    schedule; int8 scale planes ride each hop as a second ppermute,
    exactly the PR 6 quantized-collective shape). Prefill FLOPs for one
    long prompt spread across the axis instead of serializing.
  * **Sequence-sharded decode** — decode q broadcasts over ``seq``; each
    chip computes flash softmax stats (m, l, acc) over its LOCAL blocks
    and one small packed all-gather per layer combines them (exact
    streaming-softmax merge, the FlashDecoding split-K identity).
  * **Replicated weights** — unlike TP, params replicate (``P()``): the
    axis shards the *context*, not the model, so it composes with any
    runner and needs no weight re-lay.

Pool layout (``seq > 1``): slots grow to ``(num_blocks + seq) * bs`` so
every chip's contiguous shard carries its own trash block at the END of
its local rows — inside a shard_map body ``data.shape[2] - 1`` stays the
local trash row, the same invariant the single-chip layout gives the
runner's padded-write scatter. The global row of block ``b`` is
``(b % seq) * shard_rows + (b // seq) * bs`` (``shard_rows =
(num_blocks // seq + 1) * bs``), which reduces to the classic ``b * bs``
at ``seq = 1``.

Host-side state (scheduler, allocator, state manager) stays
single-program, like TP: the allocator just grows per-home free lists so
``reserve`` can place chain ordinal ``o`` on its home chip ``o % seq``.
Mutually exclusive with ``tp_size > 1`` for now — one sharding axis per
engine (config validation enforces both directions).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...utils.jax_compat import manual_axes
from ...utils.logging import log_dist
from .kv_quant import KVPool

#: the inference-side name reuses the TRAINING mesh's sequence axis
#: (parallel/topology.py AXIS_ROLES) — same role, serving-side
SEQ_AXIS = "seq"

#: KV pool sharding: the SLOTS dim chunks contiguously, handing chip r
#: rows [r*shard_rows, (r+1)*shard_rows) — with the round-robin home rule
#: that is exactly "chip r holds blocks with b % seq == r". int8 scale
#: planes are [L, 2, KV, slots]: their slots dim is LAST.
POOL_DATA_SPEC = P(None, None, SEQ_AXIS, None)
POOL_SCALE_SPEC = P(None, None, None, SEQ_AXIS)


def seq_pool_specs(quantized: bool):
    """The KV pool's shard_map spec pytree under the ``seq`` axis —
    shared by every runner program and by ``BlockedKVCache.copy_block``
    (CoW copies a block to the SAME chain ordinal, hence the same home
    chip: the copy stays chip-local, zero collectives, non-owners do a
    trash self-copy)."""
    if quantized:
        return KVPool(POOL_DATA_SPEC, POOL_SCALE_SPEC)
    return POOL_DATA_SPEC


def seq_axis_active() -> bool:
    """True while tracing inside a shard_map body mapped over ``seq`` —
    the gate every in-program helper checks, mirroring tp.py's
    ``MODEL_AXIS in manual_axes()`` discipline."""
    return SEQ_AXIS in manual_axes()


def block_home(block: int, seq: int) -> int:
    """Home chip of chain ordinal / block id ``block`` (host-side)."""
    return block % seq


def local_block(block: int, seq: int) -> int:
    """Index of ``block`` within its home chip's local pool shard."""
    return block // seq


def slot_rows(blocks, block_size: int, num_blocks: int,
              seq: int) -> np.ndarray:
    """Global pool rows of ``blocks`` under the seq-sharded layout — the
    generalized ``_slot_indices`` formula. ``seq = 1`` reproduces the
    classic contiguous ``b * bs`` layout exactly (shard_rows is then the
    whole pool), so single-axis engines keep byte-identical gathers."""
    bs = block_size
    shard_rows = (num_blocks // seq + 1) * bs
    b = np.asarray(list(blocks), np.int32)
    base = (b % seq) * shard_rows + (b // seq) * bs
    return (base[:, None] + np.arange(bs, dtype=np.int32)[None, :]) \
        .reshape(-1)


def ring_all_gather(x, axis_name: str = SEQ_AXIS):
    """Stack every chip's slab by ORIGIN chip — ``[...]`` → ``[sz, ...]``
    with ``out[o]`` = chip ``o``'s ``x`` — via ``sz - 1`` ppermute hops
    around the ring (the evoformer ring schedule: each hop forwards the
    slab received last hop, so slab ``o`` reaches chip ``r`` after
    ``(r - o) % sz`` hops). For an int8 pool the caller rings data and
    scale planes separately — two ppermutes per hop, the PR 6
    quantized-collective shape, each visible to the program auditor under
    its own ``ppermute@dtype`` budget key. Registered DSL001 hot path
    (traced inside the warm prefill program)."""
    sz = jax.lax.axis_size(axis_name)
    if sz == 1:
        return x[None]
    r = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % sz) for i in range(sz)]
    out = jnp.zeros((sz,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, r, 0)
    buf = x
    for h in range(1, sz):
        buf = lax.ppermute(buf, axis_name, perm)
        # after h forwards, buf holds the slab chip (r - h) % sz sent
        out = lax.dynamic_update_index_in_dim(out, buf,
                                              jnp.mod(r - h, sz), 0)
    return out


def combine_decode_stats(acc, l, m, axis_name: str = SEQ_AXIS):
    """Merge per-chip partial flash-softmax stats across the seq axis —
    the FlashDecoding split-K identity, with the split being the seq
    axis's round-robin block shards. ONE packed all-gather per call
    (acc, l, m concatenate into a single [.., D+2] operand so the
    auditor sees exactly one ``all_gather@float32`` per layer per decode
    step):

        m_c = max_i m_i
        num = sum_i acc_i * e^(m_i - m_c),  den = sum_i l_i * e^(m_i - m_c)

    Returns ``(num, den, m_c)`` so the caller can flash-merge further
    partials (the decode loop's ring rows) before dividing; a chip whose
    mask was empty reports ``m = -inf``/``l = 0`` and contributes
    exactly nothing (``e^(-inf) = 0`` — the -inf max is substituted with
    0 before exponentiation, so no NaNs appear even when EVERY chip is
    empty). Shapes: ``acc [..., D]``, ``l``/``m`` ``[...]`` (same
    leading dims). Registered DSL001 hot path."""
    packed = jnp.concatenate(
        [acc, l[..., None], m[..., None]], axis=-1)
    parts = lax.all_gather(packed, axis_name)          # [sz, ..., D+2]
    acc_i = parts[..., :-2]
    l_i = parts[..., -2]
    m_i = parts[..., -1]
    m_c = jnp.max(m_i, axis=0)
    w = jnp.exp(m_i - jnp.where(jnp.isinf(m_c), 0.0, m_c)[None])
    num = jnp.sum(acc_i * w[..., None], axis=0)
    den = jnp.sum(l_i * w, axis=0)
    return num, den, m_c


@dataclasses.dataclass
class SeqContext:
    """Everything the runner's seq shard_map programs need: the 1-D
    ``seq`` mesh and the pool/ring specs. Params carry NO spec tree —
    they replicate wholesale (``P()``)."""

    mesh: Mesh
    seq_size: int

    def pool_spec(self, quantized: bool):
        return seq_pool_specs(quantized)

    @property
    def ring_spec(self):
        # the decode-loop ring buffer REPLICATES over seq: fresh decode
        # kv is computed identically on every chip (batch is replicated),
        # so the in-loop append costs zero collectives — only the
        # per-layer stat combine crosses chips
        return P()

    def device_put_params(self, params):
        """Replicate the params tree over the seq mesh (the axis shards
        context, not weights)."""
        repl = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, repl), params)


def build_seq_context(cfg, runner, params,
                      devices: Optional[Sequence] = None
                      ) -> Tuple[SeqContext, Any]:
    """Build the seq context for ``runner`` and replicate ``params``.

    Returns ``(ctx, params)``. Geometry is validated in the config
    (num_blocks / max_blocks_per_seq / effective_chunk divisibility and
    the dense-attention requirement); this only checks the device count
    and the TP exclusion, mirroring ``build_tp_context``'s contract.
    """
    sz = int(cfg.seq_size)
    if sz <= 1:
        raise ValueError("build_seq_context needs cfg.seq_size > 1")
    if int(getattr(cfg, "tp_size", 1)) > 1:
        raise ValueError(
            "seq_size > 1 with tp_size > 1 is not supported yet — one "
            "sharding axis per engine")
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < sz:
        raise ValueError(
            f"seq_size={sz} but only {len(devices)} devices visible")
    mesh = Mesh(np.asarray(devices[:sz]), (SEQ_AXIS,))
    ctx = SeqContext(mesh=mesh, seq_size=sz)
    params = ctx.device_put_params(params)
    log_dist(
        f"ragged SEQ: pool sharded over '{SEQ_AXIS}' (seq={sz}, "
        f"round-robin block homes, params replicated; prefill ring = "
        f"{sz - 1} ppermute hops/layer, decode stat-combine = 1 "
        f"all-gather/layer)")
    return ctx, params
