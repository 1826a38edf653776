"""Model type ``pangu_ultra_moe``: a ``config.json`` of the
openPangu-Ultra-MoE family (latent attention in every layer, leading dense
layers then sparse ones, sandwich norms), served by
``inference/v2/llama_runner.py`` from the ``models/pangu_ultra_moe.py``
tree. The configuration file may hold one chip's share of each layer:
``n_routed_experts`` experts of the ``n_routed_experts_published`` the
router scores, and a slice of the vocabulary."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..reference import pangu_ultra_moe as reference


def model_config(cfg: Dict[str, Any]):
    from deepspeed_tpu.models.registry import config_from_hf
    held = cfg["n_routed_experts"]
    routed = cfg.get("n_routed_experts_published", held)
    _, model_cfg = config_from_hf(dict(cfg, n_routed_experts=routed))
    return dataclasses.replace(model_cfg, experts_held=held,
                               dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def init_params(model_cfg, seed: int):
    """Random weights from the seed, made on the device in one jitted
    call, in the dtype they are served in: the tree ``PanguUltraMoE.init``
    gives. Every matrix is normal with deviation 1/sqrt(fan-in), each
    expert by its own fan-in; norm scales are 1; an embedding row is a
    lookup, fan-in 1, so the table is drawn at deviation 1
    (``benchmark/model_types/solar_open2.py`` has the readings behind both
    choices). No deviation from that is needed here: the sandwich norms
    bring every branch back to deviation 1 before it is added, so the
    stream grows as the root of the branches added, whatever the
    weights' scale."""
    from deepspeed_tpu.models.pangu_ultra_moe import PanguUltraMoE
    shapes = jax.eval_shape(
        lambda k: PanguUltraMoE(model_cfg).init(
            k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    dtype = model_cfg.param_dtype

    def make(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, i)
            if "scale" in name:
                out.append(jnp.ones(leaf.shape, jnp.float32))
            else:
                # [.., fan-in, fan-out]; an embedding row is a lookup
                fan_in = 1 if "embedding" in name else leaf.shape[-2]
                w = jax.random.normal(k, leaf.shape, jnp.float32) \
                    * fan_in ** -0.5
                # the router's matrix stays float32, as the tree has it
                out.append(w.astype(leaf.dtype if leaf.dtype == jnp.float32
                                    else dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def kv_bytes_per_token(model_cfg, itemsize: int = 2) -> int:
    """One latent row a layer: ``kv_lora_rank + qk_rope_head_dim`` lanes,
    key and value at once (the zero tail of the stored row left out)."""
    return len(model_cfg.layer_kinds) * model_cfg.head_dim * itemsize


def reference_dims(model_cfg) -> Dict[str, Any]:
    return dict(
        num_heads=model_cfg.num_heads, nope=model_cfg.qk_nope_head_dim,
        rope=model_cfg.qk_rope_head_dim, v_dim=model_cfg.v_head_dim,
        rank=model_cfg.kv_lora_rank, rope_theta=model_cfg.rope_theta,
        top_k=model_cfg.experts_top_k, rms_eps=model_cfg.rms_eps,
        experts_first=model_cfg.experts_first,
        routed_scaling=model_cfg.routed_scaling,
        sandwich=model_cfg.sandwich_norm)


def reference_logits(model_cfg):
    """jitted ``(params, tokens[B, T], at[B, n]) -> logits[B, n, vocab]``."""
    return jax.jit(functools.partial(reference.logits,
                                     **reference_dims(model_cfg)))
