"""Shared causal-LM plumbing for the model zoo."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..telemetry.trace import region


def constrain_activations(x: jnp.ndarray) -> jnp.ndarray:
    """Pin [B, T, C] activations to the framework's natural layout (batch
    over data, sequence over seq, hidden over model when TP divides it).
    Applied at the embedding output: without it, GSPMD can resolve the
    token gather by fully rematerializing the embedding table per device
    ("involuntary full rematerialization", spmd_partitioner.cc:652) when
    params carry ZeRO/TP shardings, and seq-axis meshes silently
    replicate activations instead of sharding the sequence."""
    from ..parallel import topology as _topo
    if not _topo.has_topology():
        return x
    mesh = _topo.get_topology().mesh
    B, T, C = x.shape
    # batch over ALL data axes (hpZ/MiCS's data_inner included — the
    # engine's batch_sharding uses the same tuple; pinning batch to
    # "data" alone would force replication across the inner group)
    bat = tuple(a for a in ("data", "data_inner")
                if mesh.shape.get(a, 1) > 1)
    bsz = 1
    for a in bat:
        bsz *= mesh.shape[a]
    dims = [bat if bat and B % bsz == 0 else None]
    dims += [a if mesh.shape.get(a, 1) > 1 and d % mesh.shape[a] == 0
             else None
             for a, d in (("seq", T), ("model", C))]
    if not any(dims):
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*dims)))


def make_causal_lm(model, cfg):
    """(model, init_fn, loss_fn) with the engine's ``(params, batch, rng)``
    contract — batch = {"tokens": [B, T+1] int32}, next-token NLL loss."""

    def init_fn(rng, batch_size: int = 2, seq_len: Optional[int] = None):
        T = seq_len or min(cfg.max_seq_len, 64)
        return model.init(rng, jnp.zeros((batch_size, T), jnp.int32))["params"]

    def loss_fn(params, batch, rng):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        logits = model.apply({"params": params}, inputs)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return nll.mean()

    return model, init_fn, loss_fn


def lm_head_xent(hidden: jnp.ndarray, head: jnp.ndarray,
                 targets: jnp.ndarray, cfg, *,
                 head_layout: str = "vc") -> jnp.ndarray:
    """Shared LM-head loss dispatch for the model zoo (gpt2/llama/...):
    reads the ``xent_*`` knobs off ``cfg`` (with defaults, so configs may
    omit them) and routes to the chunked scan, the streaming fused Pallas
    kernel, or its shard_map wrapper — with the manual-seam and
    seq-parallel guards applied once, here, instead of per model.

    ``head_layout``: "vc" for a [V, C] head (tied embedding), "cv" for
    the natural [C, V] Dense kernel — the chunked path contracts either
    orientation directly (no transpose ever materializes); the fused
    Pallas kernel wants [V, C] rows, so "cv" there pays ONE transposed
    copy per step (XLA CSEs it across the fwd/bwd tile passes).
    """
    if head_layout not in ("vc", "cv"):
        raise ValueError(f"head_layout must be 'vc' or 'cv', "
                         f"got {head_layout!r}")

    with region("loss"):
        impl = getattr(cfg, "xent_impl", "chunked")
        if impl not in ("chunked", "fused"):
            raise ValueError(
                f"xent_impl must be 'chunked' or 'fused', got {impl!r}")
        chunks = getattr(cfg, "xent_chunks", 8)
        remat = getattr(cfg, "xent_remat", True)
        ignore = getattr(cfg, "xent_ignore_index", None)

        def _chunked():
            return chunked_lm_xent(hidden, head, targets, num_chunks=chunks,
                                   remat=remat, ignore_index=ignore,
                                   head_layout=head_layout)

        if impl == "fused":
            from ..ops.kernels import fused_lm_xent
            from ..ops.kernels.fused_xent import sharded_fused_lm_xent
            from ..parallel import topology as _topo
            if head_layout == "cv":
                head = head.T
            from ..utils.jax_compat import manual_axes
            manual = manual_axes()
            if manual:
                # already inside an engine manual seam (ZeRO++/1-bit
                # shard_map): hidden is per-rank local and the seam pmeans
                # the loss — run the kernel plainly on the shard
                return fused_lm_xent(hidden, head, targets,
                                     ignore_index=ignore)
            if jax.device_count() > 1:
                if not _topo.has_topology():
                    # plain GSPMD data-parallel jit with no framework mesh:
                    # the Pallas custom call carries no sharding rules, so XLA
                    # would silently all-gather the full [B, T, C] hidden
                    # states around it — the exact traffic the shard_map
                    # wrapper exists to avoid. The chunked einsum shards
                    # naturally under GSPMD instead.
                    import warnings
                    warnings.warn(
                        "xent_impl='fused' with multiple devices but no "
                        "deepspeed_tpu topology registered: falling back to "
                        "the chunked path (the fused kernel would all-gather "
                        "hidden states). Build a mesh via dstpu.initialize / "
                        "parallel.topology to use the fused kernel here.")
                    return _chunked()
                mesh = _topo.get_topology().mesh
                if mesh.shape.get("seq", 1) > 1:
                    # SP meshes: hidden arrives seq-sharded; the row-sharding
                    # wrapper would all-gather T (the chunked einsum shards
                    # naturally under GSPMD instead)
                    return _chunked()
                # Pallas custom calls carry no GSPMD rules — without the
                # shard_map wrapping a multi-device jit would all-gather the
                # [B, T, C] hidden states around the kernel
                return sharded_fused_lm_xent(hidden, head, targets, mesh,
                                             ignore_index=ignore)
            return fused_lm_xent(hidden, head, targets, ignore_index=ignore)
        return _chunked()


def chunked_lm_xent(hidden: jnp.ndarray, embedding: jnp.ndarray,
                    targets: jnp.ndarray, num_chunks: int = 8,
                    remat: bool = True,
                    ignore_index: Optional[int] = None,
                    head_layout: str = "vc") -> jnp.ndarray:
    """Mean next-token NLL without ever materializing the full logits.

    ``hidden`` [B, T, C] (compute dtype, e.g. bf16), ``embedding`` [V, C]
    (the tied LM head), ``targets`` [B, T] int32. The logits for each
    sequence chunk are computed on the MXU in the compute dtype with fp32
    accumulation, reduced to (logsumexp - target logit), and DISCARDED —
    with ``remat=True`` ``jax.checkpoint`` recomputes them in the backward
    pass (peak memory O(B * T/num_chunks * V) instead of O(B * T * V)).
    ``remat=False`` keeps each chunk's fp32 logits for backward: +O(B*T*V)
    bytes resident, but the backward skips the whole unembed recompute —
    measured worth ~2 TFLOPS/chip at the 710M/seq-2k bench shape where the
    memory fits. The reference always pays the full-logits cost (training
    goes through torch xent). ``ignore_index`` (torch cross_entropy
    semantics, e.g. -100) drops those positions from the loss AND the
    mean divisor.
    """
    B, T, C = hidden.shape
    nc = num_chunks
    while T % nc:           # degrade gracefully for odd T
        nc -= 1
    emb = embedding.astype(hidden.dtype)
    # "cv" = the natural [C, V] Dense kernel: contract dim 0 directly —
    # no transpose ever materializes for either orientation
    e_dim = 1 if head_layout == "vc" else 0
    V = emb.shape[0] if head_layout == "vc" else emb.shape[1]

    def chunk_nll(h, t):
        # [B, Tc, C] @ head -> [B, Tc, V] fp32 (bf16 MXU, f32 accum)
        tc = jnp.clip(t, 0, V - 1)                  # ignore ids may be -100
        logits = jax.lax.dot_general(
            h, emb, (((2,), (e_dim,)), ((), ())),
            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        nll = lse - tgt
        # out-of-range ids (t < 0 or t >= V, e.g. a corrupt label) train
        # against NOTHING: zero their nll here and drop them from the
        # divisor below — torch cross_entropy raises for them; silently
        # training against the clamped id V-1 is the one behavior that is
        # never right. (ignore_index ids are a subset of this mask when
        # negative, which is the torch default -100.)
        valid = (t >= 0) & (t < V)
        if ignore_index is not None:
            valid &= t != ignore_index
        nll = jnp.where(valid, nll, 0.0)
        return nll.sum()

    if remat:
        chunk_nll = jax.checkpoint(chunk_nll)

    hs = hidden.reshape(B, nc, T // nc, C).swapaxes(0, 1)    # [nc, B, Tc, C]
    ts = targets.reshape(B, nc, T // nc).swapaxes(0, 1)      # [nc, B, Tc]

    def body(acc, xs):
        h, t = xs
        return acc + chunk_nll(h, t), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ts))
    valid = (targets >= 0) & (targets < V)
    if ignore_index is not None:
        valid &= targets != ignore_index
    return total / jnp.maximum(valid.sum(), 1)


def alibi_slopes(num_heads: int) -> jnp.ndarray:
    """ALiBi per-head slopes (Press et al.): geometric schedule over the
    nearest power of two, with ODD multiples from the 2p schedule filling
    the remainder (so extra slopes interleave, never duplicate)."""
    import math
    p = 2 ** math.floor(math.log2(num_heads))
    base = [2 ** (-8.0 * (i + 1) / p) for i in range(p)]
    if p < num_heads:
        extra = [2 ** (-4.0 * (2 * i + 1) / p)
                 for i in range(num_heads - p)]
        base = base + extra
    return jnp.asarray(base[:num_heads], jnp.float32)


def alibi_bias(num_heads: int, q_len: int, k_len: int) -> jnp.ndarray:
    """[1, H, Tq, Tk] additive attention bias: -slope * distance."""
    slopes = alibi_slopes(num_heads)                       # [H]
    pos_q = jnp.arange(q_len)[:, None]
    pos_k = jnp.arange(k_len)[None, :]
    dist = (pos_q - pos_k).astype(jnp.float32)             # >=0 on causal side
    return (-slopes[None, :, None, None] * dist[None, None]).astype(jnp.float32)


def layer_class(parent, block_cls, name: str, remat: bool, **remat_kw):
    """The flax class ``parent`` instantiates for its child ``name``, ONE
    layer: ``block_cls``, under ``nn.remat`` when the config asks. Under
    the engine's ZeRO-3 seam the layer gathers its own sharded weights
    INSIDE that remat boundary (``quantized_collectives.gathered_in_layer``),
    so they are the block's temporaries in the forward and gathered again
    by its recompute, not held from one to the other; anywhere else this
    is ``nn.remat(block_cls)`` / ``block_cls`` as the models always wrote."""
    import flax.linen as nn
    from ..runtime.zero.quantized_collectives import gathered_in_layer
    cls = gathered_in_layer(block_cls, parent, name)
    return nn.remat(cls, **remat_kw) if remat else cls
