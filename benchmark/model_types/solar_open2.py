"""Model type ``solar_open2``: a ``config.json`` of the Solar-Open2 family
(three gated delta-rule layers to one NoPE GQA layer, every layer sparse),
served by ``inference/v2/llama_runner.py`` from the ``models/solar_open2.py``
tree. The configuration file may hold one chip's share of each layer:
``n_routed_experts`` experts of the ``n_routed_experts_published`` the
router scores, and a slice of the vocabulary."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..reference import solar_open2 as reference


def model_config(cfg: Dict[str, Any]):
    from deepspeed_tpu.models.registry import config_from_hf
    held = cfg["n_routed_experts"]
    routed = cfg.get("n_routed_experts_published", held)
    _, model_cfg = config_from_hf(dict(cfg, n_routed_experts=routed))
    return dataclasses.replace(model_cfg, experts_held=held,
                               dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def init_params(model_cfg, seed: int):
    """Random weights from the seed, made on the device in one jitted
    call, in the dtype they are served in: the tree ``SolarOpen2.init``
    gives. Every matrix is normal with deviation 1/sqrt(fan-in), each
    expert by its own fan-in (``benchmark/model_types/olmoe.py`` says why
    not the model's initializer); norm scales are 1. An embedding row is
    a lookup, fan-in 1: the table is drawn at deviation 1 (at
    1/sqrt(hidden) the residual stream would be made almost wholly of
    branch outputs, and the bfloat16 rounding of each normed input is then
    amplified about six times through the remaining blocks: the
    configuration file's ``assumed`` has the readings). What the recurrence
    needs apart: ``A_log`` uniform in [log 0.25, log 4] a head and
    ``dt_bias`` uniform in [-3, 3] a channel, so that the decay
    ``a = exp(-exp(A) softplus(f + b))``, with ``f`` of deviation about
    1, spreads over (0, 1) (from 0.02 to 0.99 between the 5th and the
    95th percentile); the router's selection bias normal with deviation
    0.01, about two of the gaps between neighbouring scores around the
    8th largest of 320 (0.006), so that it moves the choice of some
    tokens and not of all: at 0.1 the bias alone picked the experts, the
    busiest held one took five times its share, and the rows that fell on
    the chip's 40 (and with them ``serve_tok_s``, 4,700 to 5,105 over four
    seeds; my chip runs, PR 32) followed the seed's draw of 320 numbers."""
    from deepspeed_tpu.models.solar_open2 import SolarOpen2
    shapes = jax.eval_shape(
        lambda k: SolarOpen2(model_cfg).init(
            k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    dtype = model_cfg.param_dtype

    def make(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, i)
            if "scale" in name or "o_norm" in name:
                out.append(jnp.ones(leaf.shape, jnp.float32))
            elif "A_log" in name:
                out.append(jax.random.uniform(
                    k, leaf.shape, jnp.float32, -1.386, 1.386))
            elif "dt_bias" in name:
                out.append(jax.random.uniform(k, leaf.shape, jnp.float32,
                                              -3.0, 3.0))
            elif "sel_bias" in name:
                out.append(0.01 * jax.random.normal(k, leaf.shape,
                                                    jnp.float32))
            else:
                # [.., fan-in, fan-out]; a convolution's fan-in is its
                # taps; an embedding row is a lookup, fan-in 1
                fan_in = 1 if "embedding" in name else leaf.shape[-2]
                w = jax.random.normal(k, leaf.shape, jnp.float32) \
                    * fan_in ** -0.5
                out.append(w.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def kv_bytes_per_token(model_cfg, itemsize: int = 2) -> int:
    """Only the softmax layers keep K and V."""
    softmax = sum(k == "attn" for k in model_cfg.layer_kinds)
    return 2 * softmax * model_cfg.num_kv_heads * model_cfg.head_dim \
        * itemsize


def reference_logits(model_cfg):
    """jitted ``(params, tokens[B, T], at[B, n]) -> logits[B, n, vocab]``."""
    return jax.jit(functools.partial(
        reference.logits, num_heads=model_cfg.num_heads,
        num_kv_heads=model_cfg.num_kv_heads, kda_heads=model_cfg.kda_heads,
        top_k=model_cfg.experts_top_k, rms_eps=model_cfg.rms_eps,
        experts_first=model_cfg.experts_first,
        routed_scaling=model_cfg.routed_scaling))
