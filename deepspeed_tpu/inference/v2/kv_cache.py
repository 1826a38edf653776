"""Blocked (paged) KV cache.

Analogue of the reference's ``BlockedKVCache``
(``inference/v2/ragged/kv_cache.py:40``): a fixed device-resident pool of KV
blocks addressed through per-sequence block tables. Stored flat —
``[layers, 2 (k/v), (num_blocks + 1) * block_size, kv_heads * head_dim]``
(the final block is the trash block for padded writes) — so KV append is
one scatter and context gather is one take per step; block granularity
exists only in the allocator and the block tables. Rows are lane-aligned
``kv_heads * head_dim`` flats: see the allocation comment below.

Sequence-parallel serving (``seq_parallel.py``, ``cfg.seq_size > 1``)
shards the SLOTS dim over the ``seq`` mesh axis: slots grow to
``(num_blocks + seq) * block_size`` so each chip's contiguous shard ends
with its OWN trash block, block ``b`` lives in rows
``(b % seq) * shard_rows + (b // seq) * bs`` (chip ``b % seq``), and the
allocator grows per-home free lists so chain ordinal ``o`` always lands
on chip ``o % seq`` — per-chip pool bytes stay flat however long any one
sequence grows. ``seq = 1`` reproduces the layout above bit-for-bit.
"""

from __future__ import annotations

import math
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp

from .blocked_allocator import BlockedAllocator
from .config import RaggedInferenceConfig
from .prefix_cache import PrefixCache


class _HostBatch:
    """One demotion batch: the rows (and int8 scales) of every block one
    ``reserve`` call demoted, gathered in a SINGLE non-blocking device
    dispatch. The arrays stay in-flight device values until
    :meth:`materialize` (called at a commit boundary, where the step
    readback already proved the gather complete — the ``device_get``
    there is a plain D2H copy, never a pipeline stall); until then a
    promotion can consume the device-resident slice directly, paying no
    host round-trip at all.

    Host-RAM accounting is PER BLOCK, not per batch: materialize copies
    each still-live index into its own contiguous numpy pair and drops
    the batch arrays (and the pow2 padding), and :meth:`drop` (an entry
    promoted or host-cap-evicted) releases that block's copy — so the
    tier's resident bytes track ``prefix_cache_host_blocks``, never the
    historical batch sizes."""

    __slots__ = ("rows", "scales", "block_size", "count", "parts",
                 "dead")

    def __init__(self, rows, scales, block_size: int, count: int):
        self.rows = rows
        self.scales = scales
        self.block_size = block_size
        self.count = count          # victim blocks (before pow2 padding)
        #: index -> (rows, scales) contiguous numpy copies, once
        #: materialized (the batch arrays are then dropped)
        self.parts = None
        self.dead: set = set()

    def drop(self, index: int) -> None:
        self.dead.add(index)
        if self.parts is not None:
            self.parts.pop(index, None)

    def slice(self, index: int):
        if self.parts is not None:
            return self.parts[index]
        lo = index * self.block_size
        hi = lo + self.block_size
        rows = self.rows[:, :, lo:hi]
        scales = None if self.scales is None \
            else self.scales[:, :, :, lo:hi]
        return rows, scales

    def materialize(self) -> None:
        if self.parts is not None:
            return
        import jax
        import numpy as np
        rows = jax.device_get(self.rows)
        scales = None if self.scales is None \
            else jax.device_get(self.scales)
        bs = self.block_size
        self.parts = {}
        for i in range(self.count):
            if i in self.dead:
                continue
            lo, hi = i * bs, (i + 1) * bs
            self.parts[i] = (
                np.ascontiguousarray(rows[:, :, lo:hi]),
                None if scales is None
                else np.ascontiguousarray(scales[:, :, :, lo:hi]))
        self.rows = None
        self.scales = None


class _HostRef:
    """A prefix-cache entry's handle onto its slice of a demotion batch
    (``prefix_cache._Entry.host_ref``). Slicing is lazy: per-block numpy
    copies after materialize, device-array slices before. ``release``
    (called by the cache when the entry leaves the host tier) drops the
    block's bytes so the batch never outlives its survivors."""

    __slots__ = ("batch", "index")

    def __init__(self, batch: _HostBatch, index: int):
        self.batch = batch
        self.index = index

    def get(self):
        """(rows, scales-or-None) for this block."""
        return self.batch.slice(self.index)

    def release(self) -> None:
        self.batch.drop(self.index)


def window_blocks(window: int, cfg: RaggedInferenceConfig) -> int:
    """R: the blocks a sequence's slot owns in the window pool, the ONE
    place it is derived. A window layer keeps logical block ``b`` in the
    slot's block ``b % R``, so row ``j`` is overwritten by row ``j + R x
    block_size``. Every program stores its rows BEFORE it reads (a
    prefill chunk, a decode step) or after its last read (the fused
    loop's flush), so the oldest row a query at position ``i`` may need,
    ``i - window + 1``, must outlive the newest row of its own step: ``R
    x block_size >= window - 1 + n`` for the most rows ``n`` a step
    stores, a prefill chunk's or a flush's. Exact for every start
    position, aligned to a block or not: the kernels fetch whole tiles
    through the table and mask by POSITION, so a fetched row that has
    been overwritten (it lies below every query's window) never reaches
    a score."""
    return -(-(window - 1 + window_step_rows(cfg)) // cfg.block_size)


def window_step_rows(cfg: RaggedInferenceConfig) -> int:
    """The most rows one program stores into a slot of the window pool: a
    prefill chunk's or a fused loop's flush (what ``window_blocks`` sizes
    a slot for, and what ``decode_batch`` holds a caller's ``n`` to)."""
    return max(cfg.effective_chunk, int(cfg.decode_loop_steps), 1)


class BlockedKVCache:
    def __init__(self, cfg: RaggedInferenceConfig, num_layers: int,
                 kv_heads: int, head_dim: int, dtype: Any = None,
                 state_spec: Optional[dict] = None, planes: int = 2,
                 window_spec: Optional[dict] = None,
                 index_spec: Optional[dict] = None):
        """``num_layers`` counts the layers that keep K/V (the softmax
        layers of a hybrid model, every layer otherwise). ``state_spec``
        (``RaggedRunnerBase.state_spec``) asks for the per-sequence state
        pool of a model with recurrent layers beside the paged planes.
        ``planes`` is how many planes a layer keeps: K and V, or the ONE
        plane of a latent-attention layer, whose row (``kv_heads`` 1,
        ``head_dim`` the stored row) is key and value at once.
        ``window_spec`` (``RaggedRunnerBase.window_spec``: ``layers`` and
        ``window``) asks for the window pool of a model with
        sliding-window layers, which ``num_layers`` then leaves out.
        ``index_spec`` (``RaggedRunnerBase.index_spec``: ``layers`` and
        ``stride``) asks for the compressed-key plane of a model with
        block-selected layers (``index_plane.py``): one row a ``stride``
        positions of every block, so the block table addresses it too. A
        ``state_spec`` with ``taps`` 0 has no short convolution: no
        ``conv`` array is made; one with ``heads`` 0 has no matrix state:
        no ``state`` tuple is made (the convolution's carried inputs are
        then all a sequence slot holds)."""
        self.cfg = cfg
        self.planes = planes
        self.num_layers = num_layers
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        self.dtype = dtype or jnp.bfloat16
        # seq-sharded homes: block b belongs to chip b % seq; at the
        # default seq=1 the allocator is exactly the historical one
        self.seq = int(getattr(cfg, "seq_size", 1) or 1)
        self.allocator = BlockedAllocator(cfg.num_blocks,
                                          num_homes=self.seq)
        self.prefix: Optional[PrefixCache] = None   # attach_prefix_cache
        self._mesh = None                           # set by shard()
        self._seq_mesh = None                       # set by shard_seq()
        self._copy_jit = None                       # built on first CoW
        # hierarchical KV (docs/serving.md "Hierarchical KV"): the engine
        # provides the CURRENT functional pool value (its _kv_data) so a
        # demotion gather dispatched mid-plan reads the same thread every
        # step writes — device ordering makes the gathered rows exact
        self._pool_source: Optional[Callable[[], Any]] = None
        #: demotion batches whose gathers are still device-resident,
        #: awaiting materialize at a commit boundary
        self._pending_host: List[_HostBatch] = []
        # +1 trash BLOCK at the end: padded query positions scatter into its
        # last slot, so they can never corrupt a live sequence's KV (see
        # model_runner) — and the pool stays an exact multiple of block_size,
        # so the paged flash kernel's [nb, bs, row] view is a free reshape.
        # Rows are FLAT [KV*D]: a trailing (KV, D) pair would be stored
        # (8, 128)-tile padded in HBM (4x footprint and DMA traffic for the
        # common KV=4, D=64 layouts); lane-aligned flat rows pad nothing.
        # seq>1: one trash block PER CHIP, at the end of each contiguous
        # shard — inside a shard_map body data.shape[2]-1 stays the local
        # trash row, same as the single-chip layout.
        slots = (cfg.num_blocks + self.seq) * cfg.block_size
        self.quantized = cfg.kv_cache_dtype == "int8"
        if self.quantized:
            # int8 rows + per-(token, kv-head) f32 scales TRANSPOSED so a
            # context window's scales DMA as KV contiguous runs (kv_quant)
            self.data = jnp.zeros(
                (num_layers, planes, slots, kv_heads * head_dim), jnp.int8)
            self.scales = jnp.zeros((num_layers, planes, kv_heads, slots),
                                    jnp.float32)
        else:
            self.data = jnp.zeros(
                (num_layers, planes, slots, kv_heads * head_dim),
                self.dtype)
            self.scales = None
        # per-sequence recurrent state: one row a sequence slot (the state
        # manager hands the slots out) + the idle row padding points at
        self.state = self.conv = None
        if state_spec is not None:
            rows = cfg.max_seqs + 1
            # one array a layer: the chip stalled on XLA's gather / scatter
            # of 4 MB rows past 2^30 bytes of ONE array (PERF.md, PR 32).
            # A state is [d_v, d_k], the two sizes apart: square for the
            # delta rule, [head_dim, state] for a state-space layer
            # (``heads`` 0, a gated short convolution's layers, keeps no
            # state: no ``state`` tuple is made, as no ``conv`` at taps 0)
            # (a kind whose [d_v, d_k] would not tile whole gives the
            # layout of a slot's numbers itself: ``state_shape``)
            if state_spec["heads"]:
                shape = state_spec.get("state_shape") or (
                    state_spec["heads"], state_spec["d_v"],
                    state_spec["d_k"])
                self.state = tuple(
                    jnp.zeros((rows,) + tuple(shape), jnp.float32)
                    for _ in range(state_spec["layers"]))
            # a slot's carried convolution inputs [taps - 1, width], laid
            # out in whole tiles as the decode step's kernel takes them
            if state_spec["taps"]:
                from ...ops.kernels.short_conv import pool_shape
                self._conv_width = state_spec["conv_width"]
                self._conv_channels = state_spec.get(
                    "conv_channels", self._conv_width)
                self.conv = jnp.zeros(
                    pool_shape(state_spec["layers"], rows,
                               state_spec["taps"],
                               state_spec["conv_width"]), self.dtype)
        # the window pool: the paged pool's own form over the
        # sliding-window layers, R blocks a sequence slot + the idle
        # slot's (padding rows of a batch point there; its last block is
        # this pool's trash block). A slot's table is a function of the
        # slot (model_runner.window_tables): nothing is allocated, freed
        # or fragmented, and the allocator never sees this pool
        self.window = None
        self.window_blocks = 0
        if window_spec is not None:
            self.window_blocks = window_blocks(window_spec["window"], cfg)
            self.window = jnp.zeros(
                (window_spec["layers"], 2,
                 (cfg.max_seqs + 1) * self.window_blocks * cfg.block_size,
                 kv_heads * head_dim), self.dtype)
        # the compressed-key plane and the prefill selection's two counts
        self.index = self.sel_counts = None
        if index_spec is not None:
            if cfg.block_size % index_spec["stride"]:
                raise ValueError(
                    f"block_size ({cfg.block_size}) must be a multiple of "
                    f"the selection's kernel_stride "
                    f"({index_spec['stride']}): a group of compressed "
                    f"keys lies in one block")
            self.index = jnp.zeros(
                (index_spec["layers"], slots // index_spec["stride"],
                 kv_heads * head_dim), self.dtype)
            self.sel_counts = jnp.zeros((2,), jnp.int32)

    def pin(self, device) -> None:
        """COMMIT the pool to ``device`` (a one-device engine pins itself
        to the device its weights sit on — ``InferenceEngineV2``). Arrays
        merely created under a ``jax.default_device`` scope are
        uncommitted: a step dispatched later from another thread (a
        ``ReplicaPool`` worker) would move them, and the whole
        computation, to that thread's default device."""
        self.data = jax.device_put(self.data, device)
        if self.scales is not None:
            self.scales = jax.device_put(self.scales, device)
        if self.state is not None:
            self.state = jax.device_put(self.state, device)
        if self.conv is not None:
            self.conv = jax.device_put(self.conv, device)
        if self.window is not None:
            self.window = jax.device_put(self.window, device)
        if self.index is not None:
            self.index = jax.device_put(self.index, device)
            self.sel_counts = jax.device_put(self.sel_counts, device)

    @property
    def stateful(self) -> bool:
        """Whether a sequence holds a slot of the state pool: recurrent
        state, carried convolution inputs, or both."""
        return self.state is not None or self.conv is not None

    @property
    def pool(self):
        """The threadable pool pytree: a KVPool when quantized (data +
        scales travel together through the jitted steps) or when the
        model has recurrent layers (the state pool travels with the
        planes), else the raw data array (byte-identical to the pre-int8
        path). The window pool of a model with sliding-window layers
        travels in it too."""
        if self.quantized or self.stateful \
                or self.window is not None or self.index is not None:
            from .kv_quant import KVPool
            return KVPool(self.data, self.scales, self.state, self.conv,
                          self.window, self.index, self.sel_counts)
        return self.data

    def attach_prefix_cache(self, prefix: PrefixCache) -> None:
        """Layer the content-addressed block index over the allocator:
        refcount-0 cached blocks count as reclaimable capacity and are
        LRU-evicted by :meth:`reserve` only under actual pressure. Also
        builds AND compiles the CoW copy program here, off the serve
        loop — the first partial-tail hit must not pay a trace+compile
        inside the pipeline's plan-ahead window (DSL001 discipline)."""
        self.prefix = prefix
        self._warm_copy()

    def _warm_copy(self) -> None:
        """Compile the CoW row copy with a trash-block self-copy (writes
        only the trash block, whose content is never read) and thread the
        result back — the program donates the pool buffers."""
        from .kv_quant import pool_parts
        warmed = self.copy_block(self.pool, self.cfg.num_blocks,
                                 self.cfg.num_blocks)
        self.data, scales = pool_parts(warmed)
        if scales is not None:
            self.scales = scales

    @property
    def free_blocks(self) -> int:
        """Blocks a caller can still reserve: the allocator's free list
        plus refcount-0 prefix-cached blocks (evictable on demand)."""
        n = self.allocator.free_blocks
        if self.prefix is not None:
            n += self.prefix.evictable_blocks
        return n

    def collect_prefix_evictions(self) -> None:
        if self.prefix is not None:
            freed = self.prefix.collect_pending_free()
            if freed:
                self.allocator.free(freed)

    def attach_pool_source(self, fn: Callable[[], Any]) -> None:
        """Give the cache a view of the engine's CURRENT functional pool
        value — what a demotion gather must read. Without it (bare
        kv-cache users, tier-off engines) reserve pressure falls back to
        destroying refcount-0 cached blocks."""
        self._pool_source = fn

    def reserve(self, n: int, homes=None):
        """Allocate ``n`` blocks, reclaiming refcount-0 prefix-cached
        blocks on demand: with the host tier armed they are DEMOTED
        (one batched non-blocking device→host gather per reserve call —
        the cached chain survives, host-resident), otherwise destroyed.
        Registered DSL001 hot path: the gather is dispatch-only; the
        D2H materialize happens at a commit boundary.

        ``homes`` (seq-parallel, one home chip per block) makes the
        pressure loop PER-HOME: eviction victims land back on whatever
        home they came from, so the loop keeps reclaiming until every
        needed home has supply or nothing more is evictable — the
        allocator then fails loudly on a genuine per-home exhaustion."""
        self.collect_prefix_evictions()
        if homes is None:
            short = n - self.allocator.free_blocks
            if short > 0 and self.prefix is not None:
                if self.prefix.host_tier and self._pool_source is not None:
                    short -= self._demote(short)
                if short > 0:
                    self.allocator.free(self.prefix.evict(short))
            return self.allocator.allocate(n)
        while self.prefix is not None:
            short = sum(self.allocator.shortfall(homes))
            if not short:
                break
            recovered = 0
            if self.prefix.host_tier and self._pool_source is not None:
                recovered += self._demote(short)
            if recovered < short:
                freed = self.prefix.evict(short - recovered)
                self.allocator.free(freed)
                recovered += len(freed)
            if not recovered:
                break
        return self.allocator.allocate(n, homes=homes)

    def _demote(self, short: int) -> int:
        """Demote up to ``short`` refcount-0 cached blocks to the host
        tier: ONE gather dispatch for the whole victim set (padded to a
        power-of-two block count so the warm path never compiles a fresh
        gather shape), entries re-tagged ``tier=host``, device blocks
        back to the allocator. Returns the number of blocks recovered."""
        bs = self.cfg.block_size
        recovered = 0
        while recovered < short:
            # rounds, because demoting a leaf makes its parent demotable
            # (leaf-first cascade); each round is still ONE batched
            # gather dispatch, and chains are only as deep as a prompt's
            # block count
            victims = self.prefix.pop_demotable(short - recovered)
            if not victims:
                break
            blocks = [e.block for e in victims]
            rows, scales = self._gather_rows(self._pool_source(), blocks)
            batch = _HostBatch(rows, scales, bs, len(victims))
            self._pending_host.append(batch)
            self.prefix.demote(
                victims,
                [_HostRef(batch, i) for i in range(len(victims))])
            self.allocator.free(blocks)
            recovered += len(blocks)
        return recovered

    def _gather_rows(self, kv_data, blocks):
        """Non-blocking gather of ``blocks``' rows (and int8 scales) off
        the functional pool thread — the device-side half of demotion.
        The index is padded with trash-block slots up to a power-of-two
        victim count, so steady pressure reuses a handful of compiled
        gather shapes instead of one per victim-set size."""
        from .kv_quant import pool_parts
        data, scales = pool_parts(kv_data)
        pad = 1
        while pad < len(blocks):
            pad *= 2
        padded = list(blocks) + [self.cfg.num_blocks] * (pad - len(blocks))
        idx = jnp.asarray(self._slot_indices(padded))
        rows = data[:, :, idx]
        sc = None if scales is None else scales[:, :, :, idx]
        return rows, sc

    def gather_blocks(self, kv_data, blocks):
        """Non-blocking exact-length gather of ``blocks``' rows (and int8
        scales) for the disaggregated-serving KV handoff
        (docs/serving.md "Disaggregated serving"): the same batched
        device-side slice demotion uses (:meth:`_gather_rows`, so steady
        handoff traffic shares demotion's few compiled pow2 gather
        shapes), trimmed back to exactly ``len(blocks) * block_size``
        rows so the result is directly :meth:`restore`-shaped on the
        receiving replica. Dispatch only — the caller materializes (or
        ships) the slice when the transfer must land, letting the D2H
        copy hide under neighboring sequences' compute. Registered
        DSL001 hot path."""
        rows, sc = self._gather_rows(kv_data, blocks)
        n = len(blocks) * self.cfg.block_size
        rows = rows[:, :, :n]
        if sc is not None:
            return rows, sc[:, :, :, :n]
        return rows

    def finalize_demotions(self) -> None:
        """Materialize pending demotion gathers to host numpy — called
        at commit boundaries (the blocking step readback just proved the
        gathers complete, so this is a D2H copy, not a stall) and at
        drain. Until it runs, promotions consume the device-resident
        slices directly."""
        if not self._pending_host:
            return
        for batch in self._pending_host:
            batch.materialize()   # per-live-block copies; padding dropped
        self._pending_host = []

    def buffer_of(self, entry):
        """Resolve a host-tier entry's rows for promotion/CoW — numpy
        (materialized) or an in-flight device slice."""
        return entry.host_ref.get()

    def promote_block(self, kv_data, buf, dst: int):
        """Scatter a demoted block's rows into freshly reserved device
        block ``dst`` — the host→device half of a hierarchical-KV hit.
        A restore-path scatter on the functional pool thread: dispatch
        only (the H2D transfer overlaps whatever compute precedes the
        promoted sequence's own steps), zero collectives under TP (the
        lane/head dim is untouched). Registered DSL001 hot path."""
        rows, scales = buf
        return self.restore(kv_data,
                            (rows, scales) if scales is not None else rows,
                            [dst])

    def promote_blocks(self, kv_data, promotes):
        """Batched promotion: ONE restore scatter for a whole matched
        chain's ((rows, scales), dst) pairs — per-block dispatches put
        k eager-op launches on the plan path where one suffices (the
        measured promote_exposed_frac lever). Buffers concatenate on
        whichever side they live: all-host numpy stays a host concat
        (one H2D inside restore), any in-flight device slice upgrades
        the concat to a device op. Registered DSL001 hot path —
        dispatch only."""
        import numpy as np
        if len(promotes) == 1:
            return self.promote_block(kv_data, *promotes[0])
        bufs = [b for b, _ in promotes]
        blocks = [dst for _, dst in promotes]
        on_host = all(isinstance(b[0], np.ndarray) for b in bufs)
        cat = np.concatenate if on_host else jnp.concatenate
        rows = cat([b[0] for b in bufs], axis=2)
        scales = None
        if bufs[0][1] is not None:
            cats = np.concatenate \
                if all(isinstance(b[1], np.ndarray) for b in bufs) \
                else jnp.concatenate
            scales = cats([b[1] for b in bufs], axis=3)
        return self.restore(kv_data,
                            (rows, scales) if scales is not None else rows,
                            blocks)

    def free(self, blocks) -> None:
        self.allocator.free(blocks)

    # --------------------- prefix-cache CoW copy ---------------------- #

    def copy_block(self, kv_data, src: int, dst: int):
        """Copy one block's rows (and int8 scales) ``src`` -> ``dst`` —
        the copy-on-write step behind a partial-tail prefix match. A
        single compiled row copy on the functional pool thread; under TP
        the pool's lane (head) dim is untouched, so the program is
        head-local with ZERO collectives (audited:
        test_program_audit.py::TestPrefixCacheBudgets)."""
        if self.seq > 1 and src % self.seq != dst % self.seq:
            raise ValueError(
                f"seq CoW copy {src}->{dst} crosses homes "
                f"({src % self.seq} -> {dst % self.seq}): a CoW dst must "
                f"share its src's chain ordinal home")
        if self._copy_jit is None:
            self._copy_jit = self._build_copy()
        return self._copy_jit(kv_data, jnp.int32(src), jnp.int32(dst))

    def _build_copy(self):
        import jax
        from .kv_quant import pool_parts, repack
        bs = self.cfg.block_size
        seq = self.seq
        nb = self.cfg.num_blocks
        seq_local = self._seq_mesh is not None   # body sees a LOCAL shard

        def _copy(kv_data, src, dst):
            data, scales = pool_parts(kv_data)
            rows = jnp.arange(bs, dtype=jnp.int32)
            if seq_local:
                # CoW replaces a block at the SAME chain ordinal, so src
                # and dst share a home chip — the copy is chip-LOCAL:
                # the owner copies its local rows, every other chip does
                # a trash self-copy (write of trash onto itself). Zero
                # collectives, exactly like the TP head-local copy.
                from jax import lax
                from .seq_parallel import SEQ_AXIS
                r = lax.axis_index(SEQ_AXIS)
                own = (src % seq) == r
                trash = (nb // seq) * bs + rows
                si = jnp.where(own, (src // seq) * bs + rows, trash)
                di = jnp.where(own, (dst // seq) * bs + rows, trash)
            elif seq > 1:
                # unsharded pool in the seq layout (CPU harness before
                # shard_seq): global rows via the round-robin formula
                shard_rows = (nb // seq + 1) * bs
                si = (src % seq) * shard_rows + (src // seq) * bs + rows
                di = (dst % seq) * shard_rows + (dst // seq) * bs + rows
            else:
                si = src * bs + rows
                di = dst * bs + rows
            data = data.at[:, :, di].set(data[:, :, si])
            if scales is not None:
                scales = scales.at[:, :, :, di].set(scales[:, :, :, si])
            return repack(kv_data, data, scales)

        if self._seq_mesh is not None:
            from jax.sharding import PartitionSpec as P
            from ...utils.jax_compat import shard_map
            from .seq_parallel import seq_pool_specs
            spec = seq_pool_specs(self.quantized)
            _copy = shard_map(_copy, mesh=self._seq_mesh,
                              in_specs=(spec, P(), P()), out_specs=spec,
                              check_vma=False)
        elif self._mesh is not None:
            from jax.sharding import PartitionSpec as P
            from ...utils.jax_compat import shard_map
            from .tp import pool_specs
            spec = pool_specs(self.quantized)
            _copy = shard_map(_copy, mesh=self._mesh,
                              in_specs=(spec, P(), P()), out_specs=spec,
                              check_vma=False)
        # pool donated like every other pool-threading program
        return jax.jit(_copy, donate_argnums=(0,))

    def shard(self, mesh) -> None:
        """Head-shard the pool at rest over the TP ``model`` mesh axis:
        data rows chunk their flat [KV*D] lane dim (KV/tp heads per chip),
        int8 scale planes chunk their KV dim. The block tables and the
        allocator are untouched — TP is invisible to the host side."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        self._mesh = mesh
        self._copy_jit = None       # rebuild under the mesh
        self.data = jax.device_put(
            self.data, NamedSharding(mesh, P(None, None, None, "model")))
        if self.scales is not None:
            self.scales = jax.device_put(
                self.scales, NamedSharding(mesh, P(None, None, "model",
                                                   None)))
        if self.prefix is not None:
            self._warm_copy()       # recompile eagerly, off the serve loop

    def shard_replicated(self, mesh) -> None:
        """Replicate the pool at rest over a mesh (the ep-only layout:
        the serving batch — and therefore every KV write — is identical
        on all expert ranks, so the pool carries no axis in its specs
        and the programs' pool spec is ``P()``). ``_mesh``/``_seq_mesh``
        stay unset: the prefix-cache block copy needs no shard_map over
        replicated arrays."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        self._copy_jit = None
        repl = NamedSharding(mesh, P())
        self.data = jax.device_put(self.data, repl)
        if self.scales is not None:
            self.scales = jax.device_put(self.scales, repl)
        if self.prefix is not None:
            self._warm_copy()       # recompile eagerly, off the serve loop

    def shard_seq(self, mesh) -> None:
        """Shard the pool at rest over the ``seq`` mesh axis: the slots
        dim chunks contiguously, handing chip r its round-robin block
        homes plus its own trailing trash block (per-chip KV bytes
        ∝ 1/seq of the whole pool and FLAT in any one sequence's
        length). Block tables stay host metadata; the allocator's
        per-home free lists are already seq-aware."""
        import jax
        from jax.sharding import NamedSharding
        from .seq_parallel import POOL_DATA_SPEC, POOL_SCALE_SPEC
        self._seq_mesh = mesh
        self._copy_jit = None       # rebuild under the mesh
        self.data = jax.device_put(
            self.data, NamedSharding(mesh, POOL_DATA_SPEC))
        if self.scales is not None:
            self.scales = jax.device_put(
                self.scales, NamedSharding(mesh, POOL_SCALE_SPEC))
        if self.prefix is not None:
            self._warm_copy()       # recompile eagerly, off the serve loop

    def memory_bytes(self) -> int:
        n = self.data.size * self.data.dtype.itemsize
        if self.scales is not None:
            n += self.scales.size * self.scales.dtype.itemsize
        if self.index is not None:
            n += self.index.size * self.index.dtype.itemsize
        return n + (self.state_bytes_per_slot()
                    + self.window_bytes_per_slot()) * (self.cfg.max_seqs + 1)

    def kv_bytes_per_token(self) -> int:
        """Bytes one token holds in the paged planes as stored, over all
        layers that keep their whole chain there (a latent row's zero
        tail included; scales and the window pool left out)."""
        L, planes, _, row = self.data.shape
        return L * planes * row * self.data.dtype.itemsize

    def state_bytes_per_slot(self, resident: bool = False) -> int:
        """Bytes of recurrent state and convolution inputs one sequence
        slot holds over all recurrent layers (0 without any): the
        model's (a pool wider than the layers' channels,
        ``short_conv.whole_width``, counts the channels), or with
        ``resident`` what the device stores for them, an array's last two
        dimensions in whole tiles (8 sublanes of 4 bytes, 16 of 2, by 128
        lanes: 96 lanes of float32 are stored as 128)."""
        def of(a, rows):
            shape = list(a.shape)
            if resident:
                sub = 32 // a.dtype.itemsize
                shape[-1] = -(-shape[-1] // 128) * 128
                shape[-2] = -(-shape[-2] // sub) * sub
            return math.prod(shape) * a.dtype.itemsize // rows
        conv = 0 if self.conv is None else of(self.conv, self.conv.shape[1])
        if not resident and self.conv is not None:
            conv = conv * self._conv_channels // self._conv_width
        return sum(of(a, a.shape[0]) for a in self.state or ()) + conv

    def window_bytes_per_row(self) -> int:
        """Bytes one position holds in the window pool over all
        sliding-window layers, K and V (0 without any)."""
        if self.window is None:
            return 0
        layers, planes, _, row = self.window.shape
        return layers * planes * row * self.window.dtype.itemsize

    def window_bytes_per_slot(self) -> int:
        """Bytes of the window pool one sequence slot owns over all
        sliding-window layers (0 without any)."""
        return self.window_bytes_per_row() * self.window_blocks \
            * self.cfg.block_size

    def memory_bytes_per_chip(self) -> int:
        """Bytes one chip actually holds, read from the device sharding
        (∝ 1/tp under head-sharded TP; equals :meth:`memory_bytes` on a
        single device)."""
        import numpy as np

        def per_chip(a):
            sh = getattr(a, "sharding", None)
            if sh is None or not hasattr(sh, "shard_shape"):
                return a.size * a.dtype.itemsize
            return int(np.prod(sh.shard_shape(a.shape))) * a.dtype.itemsize

        n = per_chip(self.data)
        if self.scales is not None:
            n += per_chip(self.scales)
        if self.index is not None:
            n += per_chip(self.index)
        # the state and window pools are never sharded (models with
        # recurrent or sliding-window layers refuse meshes)
        return n + (self.state_bytes_per_slot()
                    + self.window_bytes_per_slot()) * (self.cfg.max_seqs + 1)

    # ------------------- host offload / restore ----------------------- #
    # Reference parity: BlockedKVCache.offload/restore
    # (/root/reference/deepspeed/inference/v2/ragged/kv_cache.py:166,176) —
    # a paused sequence's blocks move to host memory so the pool can be
    # oversubscribed; restore scatters them into freshly allocated blocks
    # (the block ids need not match: block tables are per-sequence).

    def _slot_indices(self, blocks):
        # generalized to the seq-sharded layout; seq=1 reduces exactly to
        # the classic contiguous b*bs rows. Rows come out BLOCK-ORDERED
        # regardless of seq, so offload/gather_blocks buffers restore
        # correctly onto a pool of a DIFFERENT seq size (cross-geometry
        # disagg handoff).
        from .seq_parallel import slot_rows
        return slot_rows(blocks, self.cfg.block_size,
                         self.cfg.num_blocks, self.seq)

    def offload(self, kv_data, blocks) -> "Any":
        """Gather ``blocks`` of a (functional) kv buffer to host memory.
        Returns a numpy array [layers, 2, len(blocks)*bs, KV*D] — or, for
        a quantized KVPool, an (int8 rows, f32 scales) pair."""
        import jax
        from .kv_quant import pool_parts
        data, scales = pool_parts(kv_data)
        idx = self._slot_indices(blocks)
        if scales is None:
            return jax.device_get(data[:, :, idx])
        return (jax.device_get(data[:, :, idx]),
                jax.device_get(scales[:, :, :, idx]))

    def restore(self, kv_data, host_buf, blocks):
        """Scatter a host buffer from :meth:`offload` into ``blocks``;
        returns the updated kv buffer (same pytree type as ``kv_data``)."""
        from .kv_quant import pool_parts, repack
        data, scales = pool_parts(kv_data)
        idx = self._slot_indices(blocks)
        host_rows = host_buf[0] if scales is not None else host_buf
        if host_rows.shape[2] != idx.size:
            raise ValueError(
                f"restore: buffer holds {host_rows.shape[2]} slots, "
                f"{idx.size} requested")
        def rows_for(pool_arr, rows):
            # a handoff payload may still be device-resident on the
            # SENDER's devices: a one-device pool takes it to its own
            rows = jnp.asarray(rows, pool_arr.dtype)
            if len(pool_arr.devices()) == 1:
                rows = jax.device_put(rows, next(iter(pool_arr.devices())))
            return rows

        data = data.at[:, :, idx].set(rows_for(data, host_rows))
        if scales is not None:
            scales = scales.at[:, :, :, idx].set(
                rows_for(scales, host_buf[1]))
        return repack(kv_data, data, scales)
