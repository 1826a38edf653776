"""Expert-parallel MoE serving for the v2 ragged engine.

Opens the training stack's ``expert`` mesh axis (``moe/layer.py``
EXPERT_AXIS) to inference: the stacked expert weights
(``layer_i/moe/{wi_gate,wi_up,wo}`` from ``checkpoint/hf_loader.py``,
``[E, ...]`` stacks) shard their expert dim so each chip holds ``E/ep``
experts — per-chip expert bytes ∝ 1/ep, the HBM lever that lets a
sparse model bigger than one chip's memory serve at all. The serving
dispatch itself lives in ``moe/sharded_moe.grouped_moe_ffn_ep_serve``
(exactly two ``all_to_all`` hops per MoE layer on a replicated batch);
``llama_runner._moe_mlp`` switches to it whenever the axis is manual.

Composition rules (config.validate enforces them at construction):

  * **ep alone** — 1-D ``(expert,)`` mesh; everything except the expert
    stacks replicates (attention, router gate, shared expert, KV pool,
    decode ring). Activations are replicated, so all non-MoE compute is
    redundant across ep ranks — the axis buys expert HBM capacity and
    expert-GEMM parallelism, not attention FLOPs.
  * **ep × tp** — 2-D ``(expert, model)`` mesh: attention/MLP/lm_head
    shard over ``model`` exactly as ``tp.py`` plans them (the planner
    is reused leaf-for-leaf via :func:`tp.plan_param_layout`), the
    expert stacks shard over ``expert`` (replicated over ``model`` —
    expert GEMMs are redundant across tp columns, the documented
    trade), and the router gate plus the qwen2-moe shared expert
    REPLICATE: the runner adds the shared expert's output without a
    row-parallel all-reduce, so tp-sharding those weights would produce
    wrong partial sums. The KV pool head-shards over ``model`` as under
    plain TP.
  * **ep × seq** is excluded (config.__post_init__).

Quantized expert stacks (WOQ / fp6) are refused here: the 3-D ``[E, K,
N]`` stacks have no clean group-shard seam along the expert dim in the
flat-group layout — serve quantized MoE at ``ep_size=1``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...moe.layer import EXPERT_AXIS
from ...parallel.tp_rules import MODEL_AXIS
from ...utils.jax_compat import manual_axes
from ...utils.logging import log_dist
from .kv_quant import KVPool
from .tp import TPContext, plan_param_layout, pool_specs as tp_pool_specs

#: the inference-side name reuses the TRAINING mesh's expert axis
EP_AXIS = EXPERT_AXIS

#: the 3-D ``[E, ...]`` stacks under a ``moe`` subtree that shard their
#: expert dim; everything else under ``moe`` (the router gate) and every
#: ``shared_*`` leaf replicates
_EP_STACK_NAMES = ("wi", "wi_gate", "wi_up", "wo")


def ep_axis_active() -> bool:
    """True while tracing inside a shard_map body mapped over
    ``expert`` — the gate ``_moe_mlp`` checks, mirroring tp.py's
    ``MODEL_AXIS in manual_axes()`` discipline."""
    return EP_AXIS in manual_axes()


def _moe_override(ep: int, tp: int):
    """``plan_param_layout`` override placing MoE subtrees before the TP
    patterns see them: the stack names ``wi*``/``wo`` would match the
    dense column/row regexes and be mis-sharded over ``model``. On an
    ep-only mesh (``tp == 1``, no ``model`` axis) EVERY non-MoE leaf is
    claimed too — they all replicate."""
    from .tp import _quant_leaf_types
    quant_types = _quant_leaf_types()

    def replicate(x):
        if isinstance(x, quant_types):
            return x, jax.tree_util.tree_map(lambda _: P(), x), "replicate"
        return x, P(), "replicate"

    def override(path: str, x):
        parts = path.split("/")
        if "moe" in parts:
            if isinstance(x, quant_types):
                raise ValueError(
                    f"ep_size={ep} cannot shard quantized expert stack "
                    f"'{path}': the flat-group WOQ/fp6 layouts have no "
                    f"expert-dim seam — serve quantized MoE at ep_size=1")
            if parts[-1] in _EP_STACK_NAMES and np.ndim(x) == 3:
                if x.shape[0] % ep:
                    raise ValueError(
                        f"ep_size={ep} must divide the expert count "
                        f"({x.shape[0]}) of '{path}'")
                return x, P(EP_AXIS, None, None), "ep"
            return replicate(x)                # router gate
        if "shared_" in path:
            # qwen2-moe shared expert: the runner adds its output with NO
            # row-parallel all-reduce, so these must stay whole-width
            return replicate(x)
        if tp == 1:
            return replicate(x)                # ep-only: no 'model' axis
        return None                            # fall through to TP rules

    return override


@dataclasses.dataclass
class EPContext:
    """Everything the runner's expert shard_map programs need: the mesh
    (1-D ``(expert,)`` or 2-D ``(expert, model)``), the merged params
    spec/kind pytrees, and — when tp composes — the inner
    :class:`~.tp.TPContext` view built on the SAME mesh (the runner
    adopts it so head-count localization, quant-meta fixes and the KV
    head shard keep working unchanged)."""

    mesh: Mesh
    ep_size: int
    e_loc: int
    param_specs: Any
    param_kinds: Any
    tp: Optional[TPContext] = None

    def pool_spec(self, quantized: bool):
        if self.tp is not None:
            return tp_pool_specs(quantized)     # head-sharded over model
        # ep alone: the pool replicates (the batch does) — every chip
        # computes identical KV writes, zero pool collectives
        return KVPool(P(), P()) if quantized else P()

    @property
    def ring_spec(self):
        return self.tp.ring_spec if self.tp is not None else P()

    def device_put_params(self, params):
        """Place the params tree sharded-at-rest: expert stacks split
        over ``expert`` (per-chip expert bytes ∝ 1/ep), tp leaves over
        ``model``, the rest replicated."""
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), self.param_specs,
            is_leaf=lambda x: isinstance(x, P))
        return jax.device_put(params, shardings)


def build_ep_context(cfg, runner, params,
                     devices: Optional[Sequence] = None
                     ) -> Tuple[EPContext, Any]:
    """Build the expert-parallel context for ``runner`` and re-lay
    ``params`` for it. Returns ``(ctx, params)``.

    ``cfg.ep_size`` chips along ``expert``; with ``cfg.tp_size > 1`` the
    mesh is 2-D ``(expert, model)`` of ``ep*tp`` chips and the non-MoE
    leaves follow the exact TP plan (head divisibility and overlap
    geometry validated as in ``build_tp_context``).
    """
    ep = int(cfg.ep_size)
    if ep <= 1:
        raise ValueError("build_ep_context needs cfg.ep_size > 1")
    tp = int(getattr(cfg, "tp_size", 1))
    if int(getattr(cfg, "seq_size", 1)) > 1:
        raise ValueError(
            "ep_size > 1 with seq_size > 1 is not supported — the expert "
            "axis composes with tp, not with seq (config validates this)")
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < ep * tp:
        raise ValueError(
            f"ep_size={ep} x tp_size={tp} needs {ep * tp} devices but "
            f"only {len(devices)} are visible")

    mcfg = runner.model_cfg
    E = int(getattr(mcfg, "num_experts", 0))
    if not E:
        raise ValueError(
            "build_ep_context needs a MoE model config (num_experts > 0) "
            "— the expert axis shards expert stacks, nothing else")
    if E % ep:
        raise ValueError(
            f"ep_size={ep} must divide num_experts ({E})")

    num_heads = getattr(mcfg, "num_heads", 0)
    if tp > 1:
        if num_heads % tp or runner.kv_heads % tp:
            raise ValueError(
                f"tp_size={tp} must divide num_heads ({num_heads}) and "
                f"kv_heads ({runner.kv_heads}) — head-sharded KV needs "
                f"whole heads per chip")
        mesh = Mesh(np.asarray(devices[:ep * tp]).reshape(ep, tp),
                    (EP_AXIS, MODEL_AXIS))
    else:
        mesh = Mesh(np.asarray(devices[:ep]), (EP_AXIS,))

    new_params, specs, kinds, n_sharded = plan_param_layout(
        runner, params, tp if tp > 1 else 1, num_heads,
        override=_moe_override(ep, tp))

    tp_ctx = None
    if tp > 1:
        tp_ctx = TPContext(
            mesh=mesh, tp_size=tp, param_specs=specs, param_kinds=kinds,
            quantized_comm=bool(getattr(cfg, "tp_quantized_comm", False)),
            comm_overlap=getattr(cfg, "tp_comm_overlap", "off"),
            comm_chunks=int(getattr(cfg, "tp_comm_chunks", 2)))
    ctx = EPContext(mesh=mesh, ep_size=ep, e_loc=E // ep,
                    param_specs=specs, param_kinds=kinds, tp=tp_ctx)
    new_params = ctx.device_put_params(new_params)
    log_dist(
        f"ragged EP: expert stacks sharded over '{EP_AXIS}' (ep={ep}, "
        f"{E // ep} experts/chip"
        + (f", composed tp={tp} over '{MODEL_AXIS}'" if tp > 1 else "")
        + f", {n_sharded} sharded leaves, overlap="
        f"{getattr(cfg, 'ep_comm_overlap', 'off')})")
    return ctx, new_params


def expert_memory_report(engine) -> dict:
    """Per-chip vs total expert-stack bytes, read from the LIVE device
    shardings (at ep=2 per-chip must be total/2).
    Counts every leaf the EP planner marked ``"ep"``; on an unsharded
    engine every MoE stack counts as fully chip-resident."""
    epc = getattr(engine.runner, "epctx", None)

    total = [0]
    per_chip = [0]

    def visit(path, x):
        parts = path.split("/")
        if "moe" in parts and parts[-1] in _EP_STACK_NAMES:
            item = np.dtype(x.dtype).itemsize
            total[0] += int(np.prod(np.shape(x))) * item
            if hasattr(x, "addressable_shards"):
                sh = x.addressable_shards[0].data
                per_chip[0] += int(np.prod(np.shape(sh))) * item
            else:
                per_chip[0] += int(np.prod(np.shape(x))) * item

    from ...parallel.tp_rules import _path_str
    jax.tree_util.tree_map_with_path(
        lambda p, x: visit(_path_str(p), x), engine.params)
    return {"expert_bytes_total": total[0],
            "expert_bytes_per_chip": per_chip[0],
            "ep_size": epc.ep_size if epc is not None else 1}
