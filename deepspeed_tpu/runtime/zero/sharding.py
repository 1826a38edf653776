"""ZeRO as declarative sharding, with stage 3's collectives written out.

The reference implements ZeRO imperatively: flattened partitions, gradient
hooks, bucketed reduce-scatter, a parameter coordinator with trace-driven
prefetch (``runtime/zero/stage_1_and_2.py``, ``stage3.py``,
``partitioned_param_coordinator.py`` — ~11k LoC). On TPU the same memory
states are *sharding declarations* over the ``data`` (× ``seq``) mesh axes:

  stage 0 — params/grads/opt-state replicated; grad psum (plain DP)
  stage 1 — optimizer state sharded over data     (opt-state partitioning)
  stage 2 — + gradients constrained to the same shards
  stage 3 — + parameters sharded; every layer's weights are gathered for
            its forward and again for its backward

WHO places the collectives. For stages 1-2 XLA's SPMD partitioner does, from
the declarations alone. For stage 3 over ``data`` the engine's step computes
its micro-gradients inside an explicit ``shard_map`` (``Engine.
_maybe_manual_micro_grads``, ``quantized_collectives.py``): the program
writes each leaf's ``all_gather`` and the gradient leaves the backward
through its transpose, a ``psum_scatter``. What the partitioner made of the
declarations alone on a v5e:2x2 (the compiled step of PR 60, PERF.md section
5): a layer's gradients as the TPU's fused all-reduce-scatter kernels (a
shard comes out, but the TensorCore waits for each), and THREE of a layer's
four backward re-gathers as synchronous all-gathers, ~91 MB a layer with
nothing else running; "the coordinator's prefetch/release becomes compiler
scheduling" held for the forward alone. Written out, the backward's
re-gathers ride asynchronous fusions but for the first one a layer's
recompute needs. What STILL relies on the partitioner for stage 3: the
meshes the seam refuses (hpZ / MiCS: parameters over ``data_inner``; a
``seq``-fused zero axis; ``pipe`` > 1; streamed, pinned-host parameters),
and any model none of whose layers gathers its own weights
(``models/_lm_utils.layer_class``: GPT-Neo, CLIP, the diffusion models, a
caller's own ``loss_fn``), because the seam alone would hold every gathered
weight from its forward use to its backward one; the engine sees that when
it traces the step, says so and keeps this module's step. The declarations
below serve both.

`stage3_param_persistence_threshold` keeps small params replicated, exactly
like the reference's persistent-parameter set (stage3.py persistence logic).
ZeRO++ hpZ (secondary shards within a node) maps to sharding params over an
inner mesh sub-axis only; qwZ/qgZ map to quantized collectives (see
``deepspeed_tpu/ops/quantization.py``).

Offload: ``offload_optimizer.device == "cpu"`` places optimizer-state shards
in host memory (``memory_kind="pinned_host"``); XLA streams them in/out of the
update. NVMe offload is layered on the aio host library (``deepspeed_tpu/io``).
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...config.config import ZeroConfig
from ...parallel.topology import Topology
from ...utils.logging import log_dist, logger


def _axis_product(topo: Topology, axes: Sequence[str]) -> int:
    out = 1
    for a in axes:
        out *= topo.axis_size(a)
    return out


def choose_shard_dim(shape: Tuple[int, ...], n_shards: int,
                     taken_dims: Sequence[int] = ()) -> Optional[int]:
    """Pick the dimension to shard: the largest dim divisible by ``n_shards``
    that isn't already sharded by another axis. None if nothing divides."""
    candidates = [
        (size, dim) for dim, size in enumerate(shape)
        if dim not in taken_dims and size % n_shards == 0 and size >= n_shards
    ]
    if not candidates:
        return None
    return max(candidates)[1]


def _merge_axes_into_spec(spec: Optional[P], shape: Tuple[int, ...],
                          axes: Sequence[str], n_shards: int) -> P:
    """Add ``axes`` (as one sharding group) to an existing PartitionSpec on the
    best free dimension. Returns the original spec when nothing divides."""
    base = tuple(spec) if spec is not None else ()
    base = base + (None,) * (len(shape) - len(base))
    taken = [i for i, s in enumerate(base) if s is not None]
    dim = choose_shard_dim(shape, n_shards, taken_dims=taken)
    if dim is None:
        return P(*base) if any(s is not None for s in base) else P()
    new = list(base)
    new[dim] = axes[0] if len(axes) == 1 else tuple(axes)
    return P(*new)


class ZeroShardingPlan:
    """Computes NamedShardings for params / grads / optimizer state.

    ``tp_specs`` (optional) is a params-shaped pytree of PartitionSpecs from
    the tensor-parallel rule engine; ZeRO composes with it by sharding a
    different dimension.
    """

    def __init__(self, cfg: ZeroConfig, topo: Topology, tp_specs: Any = None):
        self.cfg = cfg
        self.topo = topo
        self.tp_specs = tp_specs
        self.zero_axes = tuple(topo.zero_axes)
        self.stage = cfg.stage

        # hpZ / MiCS: shard within the inner (sub-group) axis only.
        # hpZ (reference _partition_param_sec): params get a SECONDARY
        # partition inside the group so gathers stay on fast links, while
        # grads/opt-state shard over the full zero group. MiCS (mics.py):
        # everything shards within the group; DP reduction across replica
        # groups is the psum XLA inserts over the outer data axis.
        from ...parallel.topology import DATA_INNER_AXIS
        self.param_axes = self.zero_axes
        inner = (DATA_INNER_AXIS,)
        has_inner = topo.axis_size(DATA_INNER_AXIS) > 1
        if cfg.mics_shard_size and cfg.mics_shard_size > 0:
            if has_inner:
                self.param_axes = inner
                self.zero_axes = inner
            else:
                logger.warning(
                    "mics_shard_size set but the mesh has no data_inner axis "
                    "(topology built without inner_shard_size); ignoring MiCS")
        elif cfg.zero_hpz_partition_size > 1 and self.stage >= 3:
            if has_inner:
                self.param_axes = inner
            else:
                logger.warning(
                    "zero_hpz_partition_size set but the mesh has no "
                    "data_inner axis; ignoring hpZ")

        self.n_shards = _axis_product(topo, self.zero_axes)
        self.n_param_shards = _axis_product(topo, self.param_axes)
        if self.n_shards == 1 and self.stage > 0:
            log_dist("ZeRO enabled but data-parallel world size is 1; sharding is a no-op")

        # pipeline residency: with pipe > 1 the compiled pipeline replicates
        # params across the pipe axis DURING the step (shard_map gathers on
        # entry), so their at-rest storage is free to shard over pipe — the
        # memory benefit PP exists for (reference partitions layers per
        # stage, runtime/pipe/module.py:391). Composes multiplicatively with
        # the ZeRO data-axis sharding; gathers ride ICI and autodiff turns
        # them into reduce-scatters for the grads.
        self.pipe_axes: Tuple[str, ...] = ()
        if topo.axis_size("pipe") > 1:
            self.pipe_axes = ("pipe",)
        self.n_pipe = _axis_product(topo, self.pipe_axes) if self.pipe_axes \
            else 1

    def _merge_pipe(self, specs: Any, tree: Any) -> Any:
        if not self.pipe_axes:
            return specs

        def m(spec, leaf):
            # leaves already pipe-sharded by the module itself (e.g.
            # StackedPipelineModule's [L]-stacked blocks / vocab-sharded
            # embedding arrive via tp_specs) keep their placement — merging
            # pipe twice would be an invalid double use of the axis
            for s in tuple(spec):
                names = s if isinstance(s, tuple) else (s,)
                if any(n in self.pipe_axes for n in names if n):
                    return spec
            return _merge_axes_into_spec(
                spec if tuple(spec) else None, tuple(np.shape(leaf)),
                self.pipe_axes, self.n_pipe)

        return jax.tree_util.tree_map(
            m, specs, tree, is_leaf=lambda x: isinstance(x, P))

    # -------------------------------------------------------------- #

    def _tp_spec_for(self, path, leaf) -> Optional[P]:
        if self.tp_specs is None:
            return None
        try:
            sub = self.tp_specs
            for k in path:
                key = getattr(k, "key", getattr(k, "idx", None))
                sub = sub[key]
            return sub if isinstance(sub, P) else None
        except (KeyError, IndexError, TypeError):
            return None

    def _sharded_spec(self, path, leaf, threshold: int = 0,
                      axes: Optional[Sequence[str]] = None) -> P:
        tp = self._tp_spec_for(path, leaf)
        shape = tuple(np.shape(leaf))
        axes = tuple(axes) if axes is not None else self.zero_axes
        n = _axis_product(self.topo, axes)
        if n == 1 or int(np.prod(shape or (1,))) <= threshold:
            return tp if tp is not None else P()
        return _merge_axes_into_spec(tp, shape, axes, n)

    def _replicated_spec(self, path, leaf) -> P:
        tp = self._tp_spec_for(path, leaf)
        return tp if tp is not None else P()

    # ------------------------- public specs ------------------------ #

    def param_specs(self, params: Any) -> Any:
        """PartitionSpec pytree for model parameters."""
        if self.stage >= 3:
            threshold = int(self.cfg.stage3_param_persistence_threshold) \
                if not isinstance(self.cfg.stage3_param_persistence_threshold, str) else 100_000
            specs = jax.tree_util.tree_map_with_path(
                functools.partial(self._sharded_spec, threshold=threshold,
                                  axes=self.param_axes), params)
        else:
            specs = jax.tree_util.tree_map_with_path(self._replicated_spec,
                                                     params)
        return self._merge_pipe(specs, params)

    def grad_specs(self, params: Any) -> Any:
        """PartitionSpec pytree for gradients (stage>=2 → sharded)."""
        if self.stage >= 2:
            specs = jax.tree_util.tree_map_with_path(
                functools.partial(self._sharded_spec, threshold=0), params)
        else:
            specs = jax.tree_util.tree_map_with_path(self._replicated_spec,
                                                     params)
        return self._merge_pipe(specs, params)

    def opt_state_specs(self, opt_state: Any) -> Any:
        """PartitionSpec pytree for optimizer state (stage>=1 → sharded).

        Any leaf with a shardable dim gets sharded over the zero axes; scalars
        (e.g. step counts) stay replicated. This covers optax states (mu/nu
        mirror param shapes) without needing the param tree structure.
        """

        def spec_for(leaf):
            shape = tuple(np.shape(leaf))
            if self.stage < 1 or self.n_shards == 1 or len(shape) == 0:
                return P()
            # MiCS shards opt-state within the group only (zero_axes is
            # already reduced to the inner axis in that case)
            return _merge_axes_into_spec(None, shape, self.zero_axes, self.n_shards)

        specs = jax.tree_util.tree_map(spec_for, opt_state)
        return self._merge_pipe(specs, opt_state)

    # ---------------------- NamedSharding trees -------------------- #

    def _to_sharding(self, specs: Any, memory_kind: Optional[str] = None) -> Any:
        mesh = self.topo.mesh

        def mk(spec):
            if memory_kind is not None:
                try:
                    return NamedSharding(mesh, spec, memory_kind=memory_kind)
                except (ValueError, TypeError):
                    return NamedSharding(mesh, spec)  # backend without memories
            return NamedSharding(mesh, spec)

        return jax.tree_util.tree_map(mk, specs,
                                      is_leaf=lambda x: isinstance(x, P))

    def param_shardings(self, params: Any) -> Any:
        """Device-memory shardings the compiled step runs with."""
        return self._to_sharding(self.param_specs(params))

    def param_host_shardings(self, params: Any) -> Any:
        """Pinned-host variant: the between-steps park for ZeRO-3 param
        offload (engine._evict_params). Scalar-free param trees, so no
        memory-kind fallback subtleties beyond backend support."""
        return self._to_sharding(self.param_specs(params),
                                 memory_kind="pinned_host")

    def grad_shardings(self, params: Any) -> Any:
        return self._to_sharding(self.grad_specs(params))

    def opt_state_shardings(self, opt_state: Any) -> Any:
        """Device-memory shardings used by the compiled step. CPU offload does
        not change these: the engine stashes the state in host memory BETWEEN
        steps (see ``runtime/zero/offload.py``) and restores it to these
        shardings for the update — in-jit memory-kind staging trips the SPMD
        partitioner on scalar leaves (optax step counts)."""
        return self._to_sharding(self.opt_state_specs(opt_state))

    def opt_state_host_shardings(self, opt_state: Any) -> Any:
        """Pinned-host variant for the between-steps stash (CPU offload).
        Scalar leaves keep device placement — they cost nothing resident."""
        specs = self.opt_state_specs(opt_state)
        mesh = self.topo.mesh

        def mk(leaf, spec):
            if np.ndim(leaf) >= 1:
                try:
                    return NamedSharding(mesh, spec, memory_kind="pinned_host")
                except (ValueError, TypeError):
                    return NamedSharding(mesh, spec)
            return NamedSharding(mesh, spec)

        return jax.tree_util.tree_map(mk, opt_state, specs,
                                      is_leaf=lambda x: isinstance(x, P))

    # -------------------------------------------------------------- #

    def constrain_grads(self, grads: Any, params: Any) -> Any:
        """Apply with_sharding_constraint to gradients inside jit (stage>=2:
        the DP reduction's result is a shard; on the v5e the partitioner
        makes it the fused all-reduce-scatter kernel. Behind stage 3's
        explicit seam the large leaves arrive as shards already and this
        slices the replicated small ones)."""
        specs = self.grad_specs(params)
        return jax.tree_util.tree_map(
            lambda g, s: jax.lax.with_sharding_constraint(g, NamedSharding(self.topo.mesh, s)),
            grads, specs, is_leaf=lambda x: isinstance(x, P))

    def memory_summary(self, params: Any) -> str:
        n_params = sum(int(np.prod(np.shape(p))) for p in jax.tree_util.tree_leaves(params))
        shard = 1.0 / self.n_param_shards if self.stage >= 3 else 1.0
        extra = ""
        if self.param_axes != self.zero_axes:
            extra = f" (params over {self.param_axes})"
        return (f"ZeRO stage {self.stage}: {n_params / 1e6:.1f}M params, "
                f"{self.n_shards} shards over axes {self.zero_axes}{extra}, "
                f"param residency {shard * 100:.0f}%")
