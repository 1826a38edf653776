"""Model type ``olmo_hybrid`` (the harness finds this file by the
configuration's ``model_type``): a ``config.json`` of the Olmo-Hybrid family
(three gated delta-rule layers with ONE decay a head to one full
multi-head attention layer, a dense SwiGLU in every layer, the norms on the
branches' outputs), served by ``inference/v2/llama_runner.py`` from the
``models/olmo_hybrid.py`` tree. The configuration file may hold a cut in
depth; every width, every head and the whole vocabulary are the
published ones.

The draw. Every matrix normal at deviation 1/sqrt(fan-in), a
convolution's fan-in its taps; the embedding (a lookup, fan-in 1; the
head is untied) at deviation 1; ``A_log`` uniform in [log 0.25, log 4] and
``dt_bias`` uniform in [-3, 3] a HEAD, so that the heads' decays spread
over (0, 1) (``benchmark/model_types/kimi_linear.py`` draws the same a
channel); the norms' scales 1, but for the q and k norms of the attention
layers (``ATTN_DRAW``, for ``benchmark/model_types/mellum.py``'s reason:
at 1 a score is N(0, 1) over thousands of keys, the softmax is flat, an
attention layer's output is a mean of thousands of random values, and a
rotary code switched on by mistake would not show). A branch's output is
normed, so every branch adds a vector of unit deviation to the stream:
after the 8 layers of the cell the stream's deviation is about 4, and the
unnormed stream is what the NEXT branch reads (the family has no norm on
a branch's input): the mixers' projections see inputs that grow with
depth, as the published model's do."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..reference import olmo_hybrid as reference

#: the learned scale of the q and k norms over the whole projection (1 is
#: the initialiser's; the docstring says why not)
ATTN_DRAW = {"q_norm": 2.0, "k_norm": 1.5}


def model_config(cfg: Dict[str, Any]):
    from deepspeed_tpu.models.registry import config_from_hf
    _, model_cfg = config_from_hf(cfg)
    return dataclasses.replace(model_cfg, dtype=jnp.bfloat16,
                               param_dtype=jnp.bfloat16)


def init_params(model_cfg, seed: int):
    """Random weights from the seed, made on the device in one jitted
    call, in the dtype they are served in: the tree ``OlmoHybrid.init``
    gives, drawn as the module docstring says."""
    from deepspeed_tpu.models.olmo_hybrid import OlmoHybrid
    shapes = jax.eval_shape(
        lambda k: OlmoHybrid(model_cfg).init(
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
                draw = next((v for n, v in ATTN_DRAW.items()
                             if f"['{n}']" in name), 1.0)
                out.append(jnp.full(leaf.shape, draw, jnp.float32))
            elif "A_log" in name:
                out.append(jax.random.uniform(
                    k, leaf.shape, jnp.float32, -1.386, 1.386))
            elif "dt_bias" in name:
                out.append(jax.random.uniform(k, leaf.shape, jnp.float32,
                                              -3.0, 3.0))
            else:
                fan_in = 1 if "embedding" in name else leaf.shape[-2]
                out.append((jax.random.normal(k, leaf.shape, jnp.float32)
                            * fan_in ** -0.5).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def kv_bytes_per_token(model_cfg, itemsize: int = 2) -> int:
    """K and V a FULL-attention layer: the paged pool's. A delta-rule
    layer keeps a state and ``taps - 1`` inputs a sequence, nothing a
    token."""
    full = sum(k == "attn" for k in model_cfg.layer_kinds)
    return full * 2 * model_cfg.num_kv_heads * model_cfg.head_dim * itemsize


def reference_dims(model_cfg) -> Dict[str, Any]:
    return dict(num_heads=model_cfg.num_heads,
                gdn_heads=model_cfg.gdn_heads, rms_eps=model_cfg.rms_eps)


def reference_logits(model_cfg):
    """jitted ``(params, tokens[B, T], at[B, n]) -> logits[B, n, vocab]``."""
    return jax.jit(functools.partial(reference.logits,
                                     **reference_dims(model_cfg)))
