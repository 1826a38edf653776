"""Model type ``mellum``: a ``config.json`` of the Mellum 2 family (three
sliding-window layers to one full layer by ``layer_types``, a rotary code a
layer kind by ``rope_parameters``, every layer sparse), served by
``inference/v2/llama_runner.py`` from the ``models/mellum.py`` tree. The
configuration file may hold one chip's share of a pipeline stage:
``num_experts`` experts of the ``num_experts_published`` the router scores,
and a slice of the vocabulary.

The draw. Matrices as ``benchmark/model_types/olmoe.py`` draws its own
(normal, deviation 1/sqrt(fan-in), each expert by its own fan-in), the
embedding (a lookup, fan-in 1) at deviation 1 as the later families'. The
q and k norms' scales are drawn APART from 1 (``ATTN_DRAW``): a norm a head
undoes whatever deviation the projections were drawn at, so at a scale of 1
every score is N(0, 1) over ~1,000-6,000 keys, the softmax is all but
flat, a layer's output is a mean of hundreds of random value rows, and a
wrong window or a wrong rotary table would not show in the logits (PR 40
met that for its latent layers and PR 44 for Nemotron's softmax layers,
where a rotary code applied read 84.8 % same top-1 beside an engine at
85.2 %). With these a score has deviation 3 (times 1.63 on a full layer,
YaRN's attention factor squared) and a handful of keys carry a softmax, as
in a trained layer: which keys a query may see, and at what angle, then
decides the output."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..reference import mellum as reference

#: the learned scale of the per-head q and k norms (1 is the
#: initialiser's; the docstring says why not)
ATTN_DRAW = {"q_norm": 2.0, "k_norm": 1.5}


def model_config(cfg: Dict[str, Any]):
    from deepspeed_tpu.models.registry import config_from_hf
    held = cfg["num_experts"]
    routed = cfg.get("num_experts_published", held)
    _, model_cfg = config_from_hf(dict(cfg, num_experts=routed))
    return dataclasses.replace(model_cfg, experts_held=held,
                               dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def init_params(model_cfg, seed: int):
    """Random weights from the seed, made on the device in one jitted
    call, in the dtype they are served in: the tree ``Mellum.init`` gives.
    Every matrix normal with deviation 1/sqrt(fan-in), each expert by its
    own fan-in; the embedding at deviation 1; the layer norms' scales 1;
    the q and k norms' scales ``ATTN_DRAW``."""
    from deepspeed_tpu.models.mellum import Mellum
    shapes = jax.eval_shape(
        lambda k: Mellum(model_cfg).init(
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
                             if f"['{n}']" in name), 1.0)
                out.append(jnp.full(leaf.shape, draw, jnp.float32))
                continue
            fan_in = 1 if "embedding" in name else leaf.shape[-2]
            out.append((jax.random.normal(jax.random.fold_in(key, i),
                                          leaf.shape, jnp.float32)
                        * fan_in ** -0.5).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def kv_bytes_per_token(model_cfg, itemsize: int = 2) -> int:
    """K and V a FULL layer: the paged pool's. A sliding layer keeps a
    bounded row of the window pool a sequence, nothing a token."""
    full = sum(k == "attn" for k in model_cfg.layer_kinds)
    return full * 2 * model_cfg.num_kv_heads * model_cfg.head_dim * itemsize


def reference_dims(model_cfg) -> Dict[str, Any]:
    r = model_cfg.full_rope
    return dict(
        sliding=tuple(k == "swa" for k in model_cfg.layer_kinds),
        num_heads=model_cfg.num_heads, kv_heads=model_cfg.num_kv_heads,
        window=model_cfg.sliding_window, rope_theta=model_cfg.rope_theta,
        yarn=None if r is None else (r.factor, r.original_max, r.beta_fast,
                                     r.beta_slow, r.attention_factor),
        top_k=model_cfg.experts_top_k, rms_eps=model_cfg.rms_eps,
        experts_first=model_cfg.experts_first)


def reference_logits(model_cfg):
    """jitted ``(params, tokens[B, T], at[B, n]) -> logits[B, n, vocab]``."""
    return jax.jit(functools.partial(reference.logits,
                                     **reference_dims(model_cfg)))
