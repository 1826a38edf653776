"""ZeRO-3's explicit collectives, and ZeRO++'s quantized ones (qwZ / qgZ).

Capability parity with the reference's ZeRO++ comm compression
(``runtime/zero/partition_parameters.py`` CUDAQuantizer allgather path for
quantized weights, ``runtime/comm/coalesced_collectives.py:31``
``all_to_all_quant_reduce`` for quantized gradients, kernels in
``csrc/quantization/`` — SURVEY.md §2.3 "ZeRO++ features" row).

Design. Under plain pjit, ZeRO's gather/reduce collectives are placed by XLA:
always at full precision, and on the v5e (the compiled step, PERF.md section
5, PR 60) with most of a layer's backward gathers synchronous. So a stage-3
step over ``data`` runs its micro-gradient computation in **manual mode**: a
``shard_map`` over the ``data`` axis (all other mesh axes stay automatic),
inside which

  - every data-sharded param shard goes through a per-device custom-VJP
    (:func:`_make_param_gather`) whose forward is an ``all_gather`` (int8 /
    int4 under **qwZ**) and whose backward is a ``psum_scatter``, so the
    gradient leaves the backward already a shard (under **qgZ** a quantized
    all-to-all + local dequant-sum, the reference's single-hop
    dequant-reduce-requant schedule);
  - replicated params go through :func:`_make_replicated_prep`, whose
    backward is the DP-grad ``psum`` the automatic partitioner would have
    inserted.

Stage 3's residency (a layer's gathered weights do not outlive the layer)
is the model's to keep, with the seam's help. :func:`prep_params` gathers
the whole tree in front of the model: correct for any loss function, but a
leaf gathered there lives from its forward use to its backward one (it is
an input of the model's remat blocks), which is ZeRO-2's residency. A model
that builds a layer through :func:`gathered_in_layer` has each leaf that
ARRIVES at the layer as the seam's own gathered value (by identity: the
seam's :class:`LayerGathers` knows what it made) gathered again from this
rank's shard INSIDE the layer, inside its remat boundary, so the backward
gathers once more, as the partitioner's program did; the gather in front
then feeds nothing and is dropped as dead code. A leaf the loss function
made something else of first (compression, ``stop_gradient``) is left as it
arrives. The seam counts what the layers took, and the engine keeps the
declarative step for a model whose layers took nothing.

This is also the framework's manual-collective escape hatch (SURVEY.md §7
hard part 1).

Quantization granularity is a per-row (last-dim) symmetric scale; int4 packs
two nibbles per byte when the row length is even.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def shard_map(f, mesh, in_specs, out_specs, axis_names=()):
    """shard_map with partial-manual axes and no replication check."""
    from ...utils.jax_compat import shard_map as _sm
    return _sm(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
               check_vma=False, axis_names=axis_names)


# --------------------------------------------------------------------------- #
# comm-precision helpers
# --------------------------------------------------------------------------- #


# the quantization kernels are imported where qwZ / qgZ use them: every
# model's layer loop imports this module (``gathered_in_layer``), and the
# kernels package costs a second of start-up


def _quant_for_comm(x: jnp.ndarray, bits: int):
    from ...ops.kernels.quantization import pack_int4, sym_quantize_rowwise
    q, scale = sym_quantize_rowwise(x, bits)
    packed = bits == 4 and x.shape[-1] % 2 == 0
    if packed:
        q = pack_int4(q)
    return q, scale, packed


def _dequant_from_comm(q, scale, packed, dtype):
    if packed:
        from ...ops.kernels.quantization import unpack_int4
        q = unpack_int4(q)
    return (q.astype(jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------- #
# per-device collectives (to be used INSIDE shard_map manual regions)
# --------------------------------------------------------------------------- #


def _summed(reduce, ct):
    """``reduce(ct)``, a sum over ranks. XLA:CPU promotes a 16-bit
    all-reduce to float32 itself and aborts on the reducer a partial-manual
    shard_map lowers (its root is a sharding annotation, not the add), so
    there the promotion is written here: the same arithmetic."""
    if jax.default_backend() == "cpu" and ct.dtype.itemsize < 4:
        return reduce(ct.astype(jnp.float32)).astype(ct.dtype)
    return reduce(ct)


@functools.lru_cache(maxsize=None)
def _make_param_gather(dim: int, axes: Tuple[str, ...], world: int,
                       weight_bits: Optional[int], grad_bits: Optional[int]):
    """custom-VJP gather of a param shard along ``dim`` over manual ``axes``.

    fwd: (quantized) all_gather — qwZ when weight_bits set.
    bwd: per-device grad contributions reduce-scattered — quantized
         all-to-all + dequant-sum when grad_bits set (qgZ), else psum_scatter.
    """

    def _gather(local):
        if weight_bits is None:
            return jax.lax.all_gather(local, axes, axis=dim, tiled=True)
        q, scale, packed = _quant_for_comm(local, weight_bits)
        # non-tiled gather keeps a leading world axis so per-row scales stay
        # aligned with their value rows for any rank (incl. 1-D params)
        gq = jax.lax.all_gather(q, axes)               # (W, *q.shape)
        gs = jax.lax.all_gather(scale, axes)           # (W, *scale.shape)
        deq = _dequant_from_comm(gq, gs, packed, local.dtype)  # (W, *local)
        out = jnp.moveaxis(deq, 0, dim)
        return out.reshape(local.shape[:dim] +
                           (world * local.shape[dim],) +
                           local.shape[dim + 1:])

    def _reduce_scatter(ct):
        if grad_bits is None:
            return _summed(lambda c: jax.lax.psum_scatter(
                c, axes, scatter_dimension=dim, tiled=True), ct)
        shape = ct.shape
        chunk = shape[dim] // world
        parts = jnp.moveaxis(
            ct.reshape(shape[:dim] + (world, chunk) + shape[dim + 1:]),
            dim, 0)                                  # (world, ..., chunk, ...)
        q, scale, packed = _quant_for_comm(parts, grad_bits)
        q = jax.lax.all_to_all(q, axes, split_axis=0, concat_axis=0)
        scale = jax.lax.all_to_all(scale, axes, split_axis=0, concat_axis=0)
        deq = _dequant_from_comm(q, scale, packed, jnp.float32)
        return deq.sum(axis=0).astype(ct.dtype)      # (..., chunk, ...)

    @jax.custom_vjp
    def gather(x):
        return _gather(x)

    gather.defvjp(lambda x: (_gather(x), None),
                  lambda _, ct: (_reduce_scatter(ct),))
    return gather


@functools.lru_cache(maxsize=None)
def _make_replicated_prep(axes: Tuple[str, ...]):
    """Identity with bwd = psum over the manual axes: the DP gradient
    reduction for params that ZeRO keeps replicated (persistence threshold)."""

    @jax.custom_vjp
    def prep(x):
        return x

    prep.defvjp(lambda x: (x, None),
                lambda _, ct: (_summed(
                    lambda c: jax.lax.psum(c, axes), ct),))
    return prep


def _manual_entry(spec: Optional[P], manual_axes: Sequence[str]):
    """(dim, axes∩manual) of the first dim sharded over a manual axis."""
    if spec is None:
        return None
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        hit = tuple(a for a in axes if a in manual_axes)
        if hit:
            if len(hit) != len(axes):
                return "mixed"                       # manual+auto on one dim
            return dim, hit
    return None


def strip_to_manual(spec: Optional[P], manual_axes: Sequence[str],
                    ndim: int) -> P:
    """Project a PartitionSpec onto the manual axes (for shard_map in_specs);
    auto axes are left unmentioned and stay compiler-managed."""
    if spec is None:
        return P()
    entries = list(spec) + [None] * (ndim - len(spec))
    out = []
    for entry in entries:
        if entry is None:
            out.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        hit = tuple(a for a in axes if a in manual_axes)
        if len(hit) != len(axes):
            # dim sharded jointly over manual+auto axes: leave it fully
            # automatic (prep_params refuses such leaves anyway)
            out.append(None)
        else:
            out.append(hit[0] if len(hit) == 1 else tuple(hit))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def prep_params(params_local, specs, manual_axes: Tuple[str, ...], world: int,
                weight_bits: Optional[int], grad_bits: Optional[int],
                cast=None, book: Optional["LayerGathers"] = None):
    """Inside the manual region: gather every sharded param (qwZ fwd / qgZ
    bwd) and attach the DP-psum backward to replicated ones. Returns the
    full-parameter tree the model computes with, each leaf through ``cast``
    (the engine's cast to its compute dtype). Full-precision collectives
    come AFTER the cast: the links carry the compute dtype's bytes, as in
    the partitioner's program. qwZ / qgZ come BEFORE it, as they always
    did: qwZ quantizes the parameter's own values and qgZ's reduction runs
    in the parameter's dtype. ``book`` is told of every gathered leaf."""
    cast = cast or (lambda x: x)
    quantized = weight_bits is not None or grad_bits is not None

    def leaf(x, spec):
        entry = _manual_entry(spec if isinstance(spec, P) else None,
                              manual_axes)
        if entry == "mixed":
            raise ValueError(
                f"param dim sharded over manual+auto axes jointly ({spec}); "
                "ZeRO++ manual mode requires zero axes on their own dim")
        if entry is None:
            collect = _make_replicated_prep(manual_axes)
        else:
            dim, axes = entry
            collect = _make_param_gather(dim, axes, world, weight_bits,
                                         grad_bits)

        def full():
            return cast(collect(x)) if quantized else collect(cast(x))

        out = full()
        if book is not None and entry is not None:
            book.note(out, full)
        return out

    return jax.tree_util.tree_map(
        leaf, params_local, specs, is_leaf=lambda s: isinstance(s, P))


# --------------------------------------------------------------------------- #
# a layer's own gathers (stage 3's residency under the seam)
# --------------------------------------------------------------------------- #


class LayerGathers:
    """The seam's book of ONE trace of the loss: which leaf of the tree
    the loss function was given is the result of which gather, so that a
    layer can run that gather again inside itself
    (:func:`gathered_in_layer`), and which leaves a layer did."""

    def __init__(self):
        self._made = {}         # id(leaf) -> (leaf, the gather that made it)
        self.in_layers = set()  # ids of the leaves a layer gathered itself

    def note(self, leaf, gather):
        self._made[id(leaf)] = (leaf, gather)

    def gather_of(self, leaf):
        """The gather that made ``leaf``; None for anything else: a value
        the loss function computed from one (compression's quantized
        weights, a ``stop_gradient``) is not the engine's to gather."""
        made = self._made.get(id(leaf))
        return made[1] if made is not None and made[0] is leaf else None

    def tally(self):
        """(leaves, bytes) gathered inside a layer, and (leaves, bytes)
        held from the forward to the backward: gathered in front of the
        model and not again."""
        size = {k: leaf.size * leaf.dtype.itemsize
                for k, (leaf, _) in self._made.items()}
        inside = sum(size[k] for k in self.in_layers)
        return (len(self.in_layers), inside), \
            (len(size) - len(self.in_layers), sum(size.values()) - inside)


#: the book of the seam that is tracing its loss right now; None outside one
_OPEN: Optional[LayerGathers] = None


@contextlib.contextmanager
def layers_gather_their_own(book: LayerGathers):
    """Held open by the seam around its loss function, for
    :func:`gathered_in_layer` to read: a flax module is constructed inside
    the model, where no argument of the engine's reaches."""
    global _OPEN
    prev, _OPEN = _OPEN, book
    try:
        yield
    finally:
        _OPEN = prev


def gathered_in_layer(module_cls, parent, name: str):
    """For a model: the flax class to instantiate for the child ``name`` of
    ``parent``, ONE layer. Under the seam, ``module_cls`` with each leaf
    that arrives as the seam's own gathered value gathered AGAIN from this
    rank's shard where the layer reads it (``nn.map_variables``: wrap
    ``nn.remat`` AROUND this, so that the gathered leaf is the remat
    block's temporary and not its input; the gather in front of the model
    then feeds nothing and is dropped as dead code). Anywhere else, and
    for every other leaf, ``module_cls`` itself and the values that
    arrive."""
    book = _OPEN
    if book is None:
        return module_cls
    import flax.linen as nn
    from flax.traverse_util import flatten_dict, unflatten_dict

    arriving = parent.variables.get("params", {}).get(name)
    again = {} if arriving is None else {
        key: book.gather_of(leaf)
        for key, leaf in flatten_dict(arriving).items()}
    again = {key: g for key, g in again.items() if g is not None}
    if not again:
        return module_cls
    book.in_layers.update(
        id(leaf) for key, leaf in flatten_dict(arriving).items()
        if key in again)

    def gather(variables):
        return {"params": unflatten_dict({
            key: again[key]() if key in again else leaf
            for key, leaf in flatten_dict(variables["params"]).items()})}

    return nn.map_variables(module_cls, "params", trans_in_fn=gather)
