"""Model type ``olmoe``: a ``config.json`` of the OLMoE family (every layer
sparse, QK-norm), served by ``inference/v2/llama_runner.py`` from the
``models/mixtral.py`` tree."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..reference import olmoe as reference


def model_config(cfg: Dict[str, Any]):
    from deepspeed_tpu.models.registry import config_from_hf
    assert cfg["hidden_size"] % cfg["num_attention_heads"] == 0
    _, model_cfg = config_from_hf(cfg)
    return dataclasses.replace(model_cfg, dtype=jnp.bfloat16,
                               param_dtype=jnp.bfloat16)


def init_params(model_cfg, seed: int):
    """Random weights from the seed, made on the device in one jitted
    call, in the dtype they are served in: the tree ``Mixtral.init``
    gives, every matrix normal with deviation 1/sqrt(fan-in). The model's
    own initializer is not used: it builds the stacked experts in float32
    (12.9 GB for 8 layers, which would not fit beside their bfloat16
    copy) and takes the stack's fan-in as experts x hidden, so that the
    sparse block would hardly move the residual stream and a wrong expert
    would hide inside the comparison's tolerance."""
    from deepspeed_tpu.models.mixtral import Mixtral
    shapes = jax.eval_shape(
        lambda k: Mixtral(model_cfg).init(
            {"params": k, "gating": k},
            jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    dtype = model_cfg.param_dtype

    def make(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = jax.tree_util.keystr(path)
            if "scale" in name:
                out.append(jnp.ones(leaf.shape, jnp.float32))
                continue
            # [.., fan-in, fan-out]; the embedding's rows are of hidden size
            fan_in = leaf.shape[-1] if "embedding" in name \
                else leaf.shape[-2]
            out.append((jax.random.normal(jax.random.fold_in(key, i),
                                          leaf.shape, jnp.float32)
                        * fan_in ** -0.5).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def kv_bytes_per_token(model_cfg, itemsize: int = 2) -> int:
    return (2 * model_cfg.num_layers * model_cfg.num_kv_heads
            * model_cfg.head_dim * itemsize)


def reference_logits(model_cfg):
    """jitted ``(params, tokens[B, T], at[B, n]) -> logits[B, n, vocab]``."""
    return jax.jit(functools.partial(
        reference.logits, num_heads=model_cfg.num_heads,
        num_kv_heads=model_cfg.num_kv_heads,
        top_k=model_cfg.experts_top_k, rope_theta=model_cfg.rope_theta,
        rms_eps=model_cfg.rms_eps))
