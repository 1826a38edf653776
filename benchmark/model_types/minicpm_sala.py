"""Model type ``minicpm_sala``: a ``config.json`` of the MiniCPM-SALA family
(``mixer_types`` letter for letter: ``minicpm4`` a block-selected attention
layer, ``lightning-attn`` a Lightning linear-attention layer; the muP
scalings; NoPE on the sparse layers, rotary on the linear ones), served by
``inference/v2/llama_runner.py`` from the ``models/minicpm_sala.py`` tree.
The configuration file may hold a cut in depth; ``num_hidden_layers_published``
keeps the residual scale at the model's depth.

The draw. Every matrix normal at deviation 1/sqrt(fan-in). The embedding (a
lookup, fan-in 1) at deviation ``1 / scale_emb``, NOT 1: the muP factor 12
applies on top, and a stream of deviation 12 beside branches of 0.2475 x O(1)
would leave the first norm the token alone: after 16 branches the mixers
would hold under a hundredth of the final state's variance, and nothing of
selection, decay or position would show in the logits. With ``x_0`` at
deviation 1 the branches hold about a half. The SPARSE layers' q and k norm
scales are drawn APART from 1 (``ATTN_DRAW``: 3.0 and 2.0), for
``benchmark/model_types/mellum.py``'s reason and one more: a norm a head
undoes whatever deviation the projections were drawn at, so at a scale of 1
every score is N(0, 1) over 12k-40k keys, the softmax is flat, and a
compressed score (the mean of 32 keys: deviation 1/sqrt(32) of a key's)
would be N(0, 0.03): every block would tie and a wrong selection could not
show. At 3.0 x 2.0 a key's score has deviation 6 (a few keys carry a query's
softmax, as in a trained layer) and a compressed score deviation 1.06: ``P_j``
summed over the 16 heads of a group then spreads by about +-40 % around its
mean (the cell file records the reading), which orders the blocks firmly
though no handful of them carries the mass: with independent random heads
the group sum averages the heads' preferences out, and with independent
random keys a block's mean says little of its best key, so the selection
mostly MISSES the key a dense softmax would find: which blocks were read
then decides the layer's output, and "selection left out" reads far from
the engine. The Lightning layers' q and k norm scales stay 1: the output
norm undoes any common factor of a linear-attention layer's q and k."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..reference import minicpm_sala as reference

#: the learned scale of the SPARSE layers' per-head q and k norms
ATTN_DRAW = {"q_norm": 3.0, "k_norm": 2.0}


def model_config(cfg: Dict[str, Any]):
    from deepspeed_tpu.models.registry import config_from_hf
    _, model_cfg = config_from_hf(cfg)
    return dataclasses.replace(model_cfg, dtype=jnp.bfloat16,
                               param_dtype=jnp.bfloat16)


def init_params(model_cfg, seed: int):
    """Random weights from the seed, made on the device in one jitted
    call, in the dtype they are served in: the tree ``MiniCPMSALA.init``
    gives, drawn as the module docstring says."""
    from deepspeed_tpu.models.minicpm_sala import MiniCPMSALA
    shapes = jax.eval_shape(
        lambda k: MiniCPMSALA(model_cfg).init(
            k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    dtype = model_cfg.param_dtype

    def make(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = jax.tree_util.keystr(path)
            if "scale" in name:
                draw = next((v for n, v in ATTN_DRAW.items()
                             if f"['attn']['{n}']" in name), 1.0)
                out.append(jnp.full(leaf.shape, draw, jnp.float32))
                continue
            dev = 1.0 / model_cfg.scale_emb if "embedding" in name \
                else leaf.shape[-2] ** -0.5
            out.append((jax.random.normal(jax.random.fold_in(key, i),
                                          leaf.shape, jnp.float32)
                        * dev).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def kv_bytes_per_token(model_cfg, itemsize: int = 2) -> int:
    """K and V a SPARSE layer and the compressed keys' row a
    ``kernel_stride`` tokens; a Lightning layer keeps a constant state a
    sequence, nothing a token."""
    row = model_cfg.num_kv_heads * model_cfg.head_dim * itemsize
    n = sum(k == "sparse" for k in model_cfg.layer_kinds)
    return n * (2 * row + row // model_cfg.sparse.kernel_stride)


def reference_dims(model_cfg) -> Dict[str, Any]:
    return dict(
        sparse_layers=tuple(k == "sparse" for k in model_cfg.layer_kinds),
        num_heads=model_cfg.num_heads, kv_heads=model_cfg.num_kv_heads,
        lightning_heads=model_cfg.lightning_heads,
        head_dim=model_cfg.head_dim, rope_theta=model_cfg.rope_theta,
        sparse=dataclasses.asdict(model_cfg.sparse),
        scale_emb=model_cfg.scale_emb,
        residual_scale=model_cfg.residual_scale,
        logit_divisor=model_cfg.logit_divisor, rms_eps=model_cfg.rms_eps,
        sparse_rope=model_cfg.use_rope,
        lightning_rope=model_cfg.lightning_rope)


def reference_logits_with(model_cfg, **wrong):
    """``(params, tokens[B, T], at[B, n]) -> logits[B, n, vocab]`` (numpy):
    the reference's one-sequence forward, jitted, a sequence at a time,
    each result brought to the host before the next starts: four
    sequences of 20k tokens at the published widths do not fit a chip
    side by side. What the caller has dropped (an engine's pools, which
    its own reference cycles keep until a collection) is collected
    first. ``wrong``: the reference's own keywords for ONE thing wrong
    (``tools/chip_parity.py``)."""
    import gc

    import numpy as np
    one = jax.jit(functools.partial(
        reference.logits_one, **{**reference_dims(model_cfg), **wrong}))

    def run(params, tokens, at):
        gc.collect()
        return np.stack([np.asarray(one(params, tokens[b], at[b]))
                         for b in range(tokens.shape[0])])
    return run


def reference_logits(model_cfg):
    return reference_logits_with(model_cfg)
