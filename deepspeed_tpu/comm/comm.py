"""Collective facade + multi-host initialization.

Reference parity map (``deepspeed/comm/comm.py``):

- ``init_distributed`` (:619)            → :func:`init_distributed` (env /
  MPI / SLURM discovery → ``jax.distributed.initialize``; SPMD = one process
  per HOST, so "rank" here is the process index, not a per-chip rank).
- ``mpi_discovery`` (:688)               → :func:`mpi_discovery` (OMPI env).
- collectives (:222-521)                 → axis-name collectives for use
  inside ``shard_map`` / ``pjit`` bodies. The reference's eager tensor ops
  become ``jax.lax`` primitives; XLA schedules/overlaps them (the reference
  hand-manages CUDA streams for the same effect).
- ``@timed_op`` comms logging (:101)     → trace-time volume recording into
  :class:`~.comms_logging.CommsLogger`; pair with the jax profiler for
  wall-clock per-op timing.
- ``inference_all_reduce`` (:500)        → same as all_reduce (XLA picks the
  right ICI algorithm; no shm special case needed on TPU).

There is deliberately no Backend ABC / process-group zoo: named mesh axes
(``parallel/topology.py``) are the group registry.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils.logging import log_dist, logger
from .comms_logging import get_comms_logger, note_collective

ReduceOp = {"sum": lax.psum, "max": lax.pmax, "min": lax.pmin}

_INITIALIZED = False


# --------------------------------------------------------------------------- #
# process bring-up (multi-host)
# --------------------------------------------------------------------------- #

def mpi_discovery() -> Optional[dict]:
    """Discover (rank, world_size, coordinator) from OpenMPI/MPICH env vars,
    mirroring reference ``comm/comm.py:688`` (which uses mpi4py; env vars
    avoid the dependency)."""
    for rank_var, size_var in (
            ("OMPI_COMM_WORLD_RANK", "OMPI_COMM_WORLD_SIZE"),
            ("PMI_RANK", "PMI_SIZE"),
            ("SLURM_PROCID", "SLURM_NTASKS")):
        if rank_var in os.environ and size_var in os.environ:
            return {
                "process_id": int(os.environ[rank_var]),
                "num_processes": int(os.environ[size_var]),
                "coordinator_address": os.environ.get("MASTER_ADDR"),
                "coordinator_port": int(os.environ.get("MASTER_PORT", 0)) or None,
            }
    return None


def init_distributed(dist_backend: str = "xla",
                     auto_mpi_discovery: bool = True,
                     coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     timeout=None,
                     init_method=None,
                     rank: int = -1,
                     world_size: int = -1) -> None:
    """Initialize multi-host JAX. Single-host (the common case, and anything
    already initialized) is a no-op. Env protocol matches the launcher
    (``launcher/``): DSTPU_COORDINATOR / DSTPU_NUM_PROCESSES / DSTPU_PROCESS_ID,
    falling back to MPI/SLURM discovery."""
    global _INITIALIZED
    if _INITIALIZED:
        return
    coordinator_address = coordinator_address or os.environ.get("DSTPU_COORDINATOR")
    if num_processes is None and "DSTPU_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["DSTPU_NUM_PROCESSES"])
    if process_id is None and "DSTPU_PROCESS_ID" in os.environ:
        process_id = int(os.environ["DSTPU_PROCESS_ID"])
    if num_processes is None and world_size > 0:
        num_processes = world_size
    if process_id is None and rank >= 0:
        process_id = rank
    if (num_processes is None or process_id is None) and auto_mpi_discovery:
        found = mpi_discovery()
        if found:
            process_id = found["process_id"] if process_id is None else process_id
            num_processes = (found["num_processes"]
                             if num_processes is None else num_processes)
            coordinator_address = coordinator_address or (
                f"{found['coordinator_address']}:{found['coordinator_port']}"
                if found["coordinator_address"] and found["coordinator_port"]
                else None)
    if num_processes and num_processes > 1:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id)
        log_dist(
            f"jax.distributed initialized: process {process_id}/{num_processes} "
            f"coordinator={coordinator_address}", ranks=[0])
    _INITIALIZED = True


def is_initialized() -> bool:
    return _INITIALIZED


def get_world_size(group=None) -> int:
    """Number of participating devices (chips), like the reference's world
    size is the number of GPU ranks."""
    return jax.device_count()


def get_rank(group=None) -> int:
    """Host process index (SPMD: one process per host)."""
    return jax.process_index()


def get_local_rank() -> int:
    return 0  # one process drives all local chips under SPMD


def configure(config=None, enabled=None, prof_all=None, prof_ops=None,
              verbose=None, debug=None) -> None:
    """Wire the comms logger from config (reference ``comm/comm.py:72``)."""
    kw = {}
    if config is not None:
        section = getattr(config, "comms_logger", None) or {}
        if isinstance(section, dict):
            kw = {k: section.get(k) for k in
                  ("enabled", "prof_all", "prof_ops", "verbose", "debug")}
        else:
            kw = {k: getattr(section, k, None) for k in
                  ("enabled", "prof_all", "prof_ops", "verbose", "debug")}
    for k, v in (("enabled", enabled), ("prof_all", prof_all),
                 ("prof_ops", prof_ops), ("verbose", verbose),
                 ("debug", debug)):
        if v is not None:
            kw[k] = v
    get_comms_logger().configure(**{k: v for k, v in kw.items() if v is not None})


# --------------------------------------------------------------------------- #
# collectives (axis-name based; use inside shard_map / with pjit axis ctx)
# --------------------------------------------------------------------------- #

def _axis_size(axis_name) -> int:
    try:
        return jax.lax.axis_size(axis_name)
    except NameError:
        return 1


def _record(op: str, x, axis_name, log_name=None, scale: float = 1.0):
    n = _axis_size(axis_name)
    nbytes = int(np.prod(jnp.shape(x)) * jnp.result_type(x).itemsize * scale)
    # unconditional: the resilience watchdog names this collective when a
    # step stalls (docs/resilience.md); also the 'collective' fault site
    note_collective(op, nbytes, n, log_name=log_name)
    from ..resilience.fault_injection import get_fault_injector
    get_fault_injector().maybe_fire("collective")
    get_comms_logger().append(op, nbytes, n, log_name=log_name)
    # telemetry: traced-site counters keyed by the program auditor's
    # canonical kinds (docs/observability.md) — no-op with telemetry off
    from ..telemetry.registry import comm_counter
    comm_counter(op)


def all_reduce(x, op: str = "sum", axis_name="data", log_name=None):
    """psum/pmax/pmin over a mesh axis. ``op='avg'`` matches the reference's
    ReduceOp.AVG."""
    _record("all_reduce", x, axis_name, log_name)
    if op == "avg":
        return lax.pmean(x, axis_name)
    return ReduceOp[op](x, axis_name)


def inference_all_reduce(x, axis_name="model", log_name=None):
    _record("inference_all_reduce", x, axis_name, log_name)
    return lax.psum(x, axis_name)


def all_gather(x, axis_name="data", axis: int = 0, tiled: bool = True,
               log_name=None):
    """Gather shards along ``axis`` from every rank of the mesh axis
    (reference ``all_gather_into_tensor``, comm.py:296)."""
    _record("all_gather", x, axis_name, log_name)
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, op: str = "sum", axis_name="data", axis: int = 0,
                   log_name=None):
    """Reduce across the axis then keep this rank's shard (reference
    ``reduce_scatter_tensor``, comm.py:257)."""
    _record("reduce_scatter", x, axis_name, log_name)
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def all_to_all_single(x, axis_name="seq", split_axis: int = 0,
                      concat_axis: int = 0, log_name=None):
    """Scatter ``split_axis`` / gather ``concat_axis`` over the mesh axis
    (reference ``all_to_all_single``, comm.py:222 — the Ulysses/MoE primitive)."""
    _record("all_to_all_single", x, axis_name, log_name)
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def broadcast(x, src: int = 0, axis_name="data", log_name=None):
    """Every rank gets rank ``src``'s value (reference comm.py:361). Inside
    SPMD this is a select+psum."""
    _record("broadcast", x, axis_name, log_name)
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


def ppermute(x, perm, axis_name="pipe", log_name=None):
    """Neighbor exchange (the reference's pipeline p2p send/recv pairs,
    ``runtime/pipe/p2p.py`` — one fused collective here)."""
    _record("ppermute", x, axis_name, log_name)
    return lax.ppermute(x, axis_name, perm)


# --------------------------------------------------------------------------- #
# decomposed (overlappable) TP collectives — ISSUE 6
#
# A monolithic psum is one opaque XLA collective: it finishes before any
# consumer starts, so its latency sits exposed on the critical path. The
# builders below decompose the row-parallel TP all-reduce into nearest-
# neighbor ppermute ring steps (the T3/fused-computation-collective regime,
# arXiv:2401.16677 / 2305.06942): chunked reduce-scatter hops followed by
# all-gather hops, each an independent dataflow edge XLA can schedule under
# adjacent GEMMs. With ``quant_bits`` the wire payload rides int8 with
# per-chunk symmetric scales, quantized once per hop on the partial sums
# (EQuARX, arXiv:2506.17615) — compression composes with the overlap
# instead of being a separate monolithic gather.
#
# The hop implementations are module-level jitted functions on purpose:
# their pjit names ("ring_reduce_scatter" / "ring_all_gather") are the
# canonicalization anchor the program auditor uses to classify the hops as
# reduce_scatter / all_gather collectives (analysis/program_audit.py), and
# the jit cache keeps retracing off the program-build path.
# --------------------------------------------------------------------------- #

#: overlap schedule selected by ``resolve_tp_overlap`` / the engine knob
TP_OVERLAP_MODES = ("off", "rs_ag", "rs_ag_chunked")


def resolve_tp_overlap(mode: Optional[str] = None,
                       chunks: Optional[int] = None):
    """(mode, chunks) for the decomposed TP all-reduce, with env overrides:
    ``DSTPU_TP_OVERLAP`` = off | rs_ag | rs_ag_chunked[:k] (the operational
    kill-switch / force-on for any caller that does not thread a config),
    ``DSTPU_TP_OVERLAP_CHUNKS`` = k. ``chunks`` is meaningful only for
    rs_ag_chunked and collapses to 1 otherwise."""
    def _int(s, knob):
        try:
            return int(s)
        except ValueError:
            raise ValueError(
                f"{knob} chunk count must be an integer, got {s!r}") \
                from None

    env = os.environ.get("DSTPU_TP_OVERLAP")
    if env:
        head, _, k = env.partition(":")
        mode = head
        if k:
            chunks = _int(k, "DSTPU_TP_OVERLAP")
    env_c = os.environ.get("DSTPU_TP_OVERLAP_CHUNKS")
    if env_c:
        chunks = _int(env_c, "DSTPU_TP_OVERLAP_CHUNKS")
    mode = mode or "off"
    if mode not in TP_OVERLAP_MODES:
        raise ValueError(
            f"tp overlap mode must be one of {TP_OVERLAP_MODES}, got "
            f"{mode!r} (env DSTPU_TP_OVERLAP)")
    chunks = int(chunks) if chunks else 2
    if mode != "rs_ag_chunked":
        chunks = 1
    return mode, max(1, chunks)


def _quant_hop(x, bits: int):
    """Per-chunk symmetric quantization of one hop payload: the scale is
    per row OF THIS CHUNK (last dim = chunk width), not of the full
    activation row — an outlier poisons one chunk's scale, not the whole
    row (the EQuARX granularity claim)."""
    from ..ops.kernels.quantization import sym_quantize_rowwise
    return sym_quantize_rowwise(x, bits)


def _ring_reduce_scatter_impl(x, *, axis_name, tp, bits):
    """tp-1 ppermute hops reducing ``x``'s last dim into this chip's
    1/tp shard (chip r ends holding fully-summed chunk r). Each hop sends
    the running partial sum to the next ring neighbor; with ``bits`` the
    payload is quantized per hop (values int8 + per-chunk f32 scales)."""
    r = lax.axis_index(axis_name)
    xs = jnp.stack(jnp.split(x, tp, axis=-1))            # [tp, ..., Ec]
    perm = [(i, (i + 1) % tp) for i in range(tp)]

    def take(j):
        return lax.dynamic_index_in_dim(xs, j % tp, axis=0, keepdims=False)

    # the accumulating chunk index walks BACKWARD from (r-1): after hop s
    # chip r holds partials of chunk (r-1-s) mod tp, so after tp-1 hops it
    # holds its own chunk r, fully reduced
    acc = take(r - 1)
    for s in range(1, tp):
        if bits is None:
            acc = lax.ppermute(acc, axis_name, perm)
        else:
            q, scale = _quant_hop(acc, bits)
            q = lax.ppermute(q, axis_name, perm)
            scale = lax.ppermute(scale, axis_name, perm)
            acc = (q.astype(jnp.float32) * scale).astype(x.dtype)
        acc = acc + take(r - 1 - s)
    return acc


def _ring_all_gather_impl(shard, *, axis_name, tp, bits):
    """tp-1 ppermute hops rotating every chip's shard around the ring and
    assembling the full last dim (inverse of the reduce-scatter above).
    With ``bits`` the shard is quantized ONCE (per-chunk scales) and the
    int8 payload + scales ride the ring unmodified — gather adds no
    accumulation, so no per-hop requantization error."""
    r = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % tp) for i in range(tp)]
    if bits is None:
        blk, scale = shard, None
    else:
        blk, scale = _quant_hop(shard, bits)
    out = jnp.zeros((tp,) + blk.shape, blk.dtype)
    out = lax.dynamic_update_index_in_dim(out, blk, r, axis=0)
    if scale is not None:
        out_s = jnp.zeros((tp,) + scale.shape, scale.dtype)
        out_s = lax.dynamic_update_index_in_dim(out_s, scale, r, axis=0)
    for s in range(1, tp):
        blk = lax.ppermute(blk, axis_name, perm)
        out = lax.dynamic_update_index_in_dim(out, blk, (r - s) % tp,
                                              axis=0)
        if scale is not None:
            scale = lax.ppermute(scale, axis_name, perm)
            out_s = lax.dynamic_update_index_in_dim(out_s, scale,
                                                    (r - s) % tp, axis=0)
    if scale is not None:
        out = (out.astype(jnp.float32) * out_s).astype(shard.dtype)
    out = jnp.moveaxis(out, 0, -2)
    return out.reshape(shard.shape[:-1] + (tp * shard.shape[-1],))


_ring_rs_jit = jax.jit(_ring_reduce_scatter_impl,
                       static_argnames=("axis_name", "tp", "bits"))
_ring_ag_jit = jax.jit(_ring_all_gather_impl,
                       static_argnames=("axis_name", "tp", "bits"))


def ring_reduce_scatter(x, axis_name="model", log_name=None,
                        quant_bits: Optional[int] = None):
    """Ring reduce-scatter over a manual mesh axis: returns this chip's
    fully-reduced 1/tp shard of ``x``'s last dim (chip r gets chunk r).
    tp-1 nearest-neighbor hops; each is recorded for the comms logger and
    the resilience watchdog under ``log_name`` (a stalled hop is named
    like any other collective site in fault drills)."""
    tp = _axis_size(axis_name)
    if tp <= 1:
        return x
    # hop payload = one 1/tp chunk (int8: itemsize ratio vs the input);
    # quantized hops additionally carry the f32 per-chunk scale plane
    # (one f32 per row of the chunk) as a second ppermute — record it
    # too, so comms-logger hop counts/bytes and the 'collective' fault
    # site match the audited schedule (2 collectives per quantized hop)
    itemsize = jnp.result_type(x).itemsize
    hop_scale = (1.0 / tp) * (1.0 / itemsize if quant_bits else 1.0)
    scale_plane = 4.0 / (x.shape[-1] * itemsize) if quant_bits else 0.0
    for _ in range(tp - 1):
        _record("reduce_scatter", x, axis_name, log_name, scale=hop_scale)
        if quant_bits:
            _record("reduce_scatter", x, axis_name, log_name,
                    scale=scale_plane)
    return _ring_rs_jit(x, axis_name=axis_name, tp=tp, bits=quant_bits)


def ring_all_gather(shard, axis_name="model", log_name=None,
                    quant_bits: Optional[int] = None):
    """Ring all-gather over a manual mesh axis: inverse of
    :func:`ring_reduce_scatter` — every chip's shard rotates around the
    ring (tp-1 hops) and concatenates to the full last dim, chunk r at
    offset r. Same per-hop recording for watchdog/comms accounting."""
    tp = _axis_size(axis_name)
    if tp <= 1:
        return shard
    # as in ring_reduce_scatter: quantized hops also rotate the f32
    # per-chunk scale plane — record both ppermutes per hop
    itemsize = jnp.result_type(shard).itemsize
    hop_scale = 1.0 / itemsize if quant_bits else 1.0
    scale_plane = 4.0 / (shard.shape[-1] * itemsize) if quant_bits else 0.0
    for _ in range(tp - 1):
        _record("all_gather", shard, axis_name, log_name, scale=hop_scale)
        if quant_bits:
            _record("all_gather", shard, axis_name, log_name,
                    scale=scale_plane)
    return _ring_ag_jit(shard, axis_name=axis_name, tp=tp, bits=quant_bits)


def decomposed_all_reduce(x, axis_name="model", chunks: int = 1,
                          quant_bits: Optional[int] = None, log_name=None):
    """All-reduce decomposed into ``chunks`` independent (ring
    reduce-scatter → ring all-gather) pipelines over ``x``'s last dim.

    Semantically identical to ``psum`` (bitwise at tp=2 — one commutative
    add — and reassociation-equivalent beyond); structurally it replaces
    the one opaque collective with ``2 * chunks * (tp-1)`` nearest-neighbor
    hops whose dataflow edges XLA can interleave with adjacent compute —
    chunk i's gather hops overlap chunk j's reduce hops, and the whole
    tail overlaps the next layer's GEMM wherever the consumer allows.
    ``quant_bits`` rides every hop at int8 with per-chunk scales
    (quantized once per hop on the partial sums — the EQuARX schedule).

    Degrades loudly-but-safely: a last dim not divisible by ``chunks*tp``
    drops to the largest dividing chunk count, and one not divisible by
    ``tp`` at all falls back to the monolithic :func:`all_reduce` (no ring
    seam exists).
    """
    tp = _axis_size(axis_name)
    if tp <= 1:
        return x
    E = x.shape[-1]
    if E % tp:
        # no ring seam exists: callers without a build-time divisibility
        # check (the MoE training paths) would otherwise audit a schedule
        # that silently lost its decomposition
        logger.warning(
            "decomposed_all_reduce(%s): last dim %d not divisible by "
            "tp=%d — falling back to the monolithic all-reduce",
            log_name or axis_name, E, tp)
        return all_reduce(x, "sum", axis_name, log_name)
    c = max(1, int(chunks))
    while c > 1 and E % (c * tp):
        c -= 1
    if c != max(1, int(chunks)):
        logger.warning(
            "decomposed_all_reduce(%s): last dim %d not divisible by "
            "chunks*tp (%d*%d) — degrading to %d chunk(s)",
            log_name or axis_name, E, chunks, tp, c)
    parts = jnp.split(x, c, axis=-1) if c > 1 else [x]
    outs = [ring_all_gather(
        ring_reduce_scatter(p, axis_name, log_name, quant_bits),
        axis_name, log_name, quant_bits) for p in parts]
    return outs[0] if c == 1 else jnp.concatenate(outs, axis=-1)


def overlap_all_reduce(x, axis_name="model", log_name=None,
                       mode: Optional[str] = None,
                       chunks: Optional[int] = None,
                       quant_bits: Optional[int] = None):
    """The one schedule-dispatch for a TP sum-reduction site: resolve the
    overlap schedule (explicit ``mode``/``chunks`` as the defaults, the
    ``DSTPU_TP_OVERLAP*`` env knobs override — :func:`resolve_tp_overlap`)
    and trace either the decomposed ring (:func:`decomposed_all_reduce`)
    or the monolithic :func:`all_reduce`. Callers that already hold a
    fully-resolved schedule (the v2 serve engine, which resolves env at
    engine construction) can keep calling :func:`decomposed_all_reduce`
    directly; env-driven sites (the MoE training reductions) use this so
    the resolution + dispatch live in exactly one place."""
    mode, chunks = resolve_tp_overlap(mode, chunks)
    if mode != "off":
        return decomposed_all_reduce(x, axis_name=axis_name, chunks=chunks,
                                     quant_bits=quant_bits,
                                     log_name=log_name)
    return all_reduce(x, "sum", axis_name, log_name)


def barrier(group=None):
    """Host-level barrier: synchronize all processes (reference comm.py:421).
    Inside a compiled program there is nothing to do — XLA orders collectives;
    at host level we round-trip a tiny psum through all devices."""
    if jax.process_count() == 1:
        return
    # a zero-sized allreduce across all devices forces a sync point
    x = jnp.zeros((jax.device_count(),))
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()), ("all",))
    y = jax.jit(lambda a: a.sum(),
                in_shardings=NamedSharding(mesh, P("all")))(x)
    jax.block_until_ready(y)


def log_summary(show_straggler: bool = False) -> str:
    """Print the comms table (reference ``dist.log_summary``, comm.py:422)."""
    return get_comms_logger().log_summary(show_straggler)
