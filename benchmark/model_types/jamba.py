"""Model type ``jamba`` (the harness finds this file by the configuration's
``model_type``): a ``config.json`` of the Jamba family (Mamba-1 mixers, a
softmax attention layer every ``attn_layer_period`` without a position
code, a dense SwiGLU in every layer, the head tied to the embedding),
served by ``inference/v2/llama_runner.py`` from the ``models/jamba.py``
tree. ``benchmark/configs/jamba2-3b.json`` holds the model WHOLE: no cut.

The draw. Every matrix normal at deviation 1/sqrt(fan-in), a
convolution's fan-in its taps; norm scales 1; the Mamba-1 leaves by
Mamba-1's OWN initialisation, so that the decays spread as a freshly
initialised model's do: ``dt_proj``'s bias the inverse softplus of a step
drawn log-uniform in [``TIME_STEP`` min, max] and floored, ``A_log[n, e] =
log(n + 1)`` (decays 1 .. 16 a channel: a step of 0.001-0.1 under them is
a memory of a few to a thousand positions), ``D`` 1; the convolution's
bias uniform in [-1/2, 1/2] (the depthwise convolution's default at
fan-in 4: at zero a bias left out would not show). The attention layers'
query and key projections drawn apart (``ATTN_DRAW``, for
``benchmark/model_types/nemotron_h.py``'s reason). The embedding is TIED
to the head and drawn as the head, at deviation 1/sqrt(hidden): drawn as
the other families draw an untied lookup, at 1, the input token's own row
would reach the logits through the residual path 12 deviations above the
rest and every stream would repeat its last token; the first layer's norm
takes the scale out on the way in."""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..reference import jamba as reference
from .nemotron_h import ATTN_DRAW

#: Mamba-1's defaults of ``time_step_min``, ``_max``, ``_floor`` (the
#: config has no such keys)
TIME_STEP = (0.001, 0.1, 1e-4)


def model_config(cfg: Dict[str, Any]):
    from deepspeed_tpu.models.registry import config_from_hf
    _, model_cfg = config_from_hf(cfg)
    return dataclasses.replace(model_cfg, dtype=jnp.bfloat16,
                               param_dtype=jnp.bfloat16)


def init_params(model_cfg, seed: int):
    """Random weights from the seed, made on the device in one jitted
    call, in the dtype they are served in: the tree ``Jamba.init`` gives,
    drawn as the module docstring says."""
    from deepspeed_tpu.models.jamba import Jamba
    shapes = jax.eval_shape(
        lambda k: Jamba(model_cfg).init(
            k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    dtype = model_cfg.param_dtype
    lo, hi, floor = TIME_STEP

    def make(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, i)
            f32 = functools.partial(jax.random.uniform, k, leaf.shape,
                                    jnp.float32)
            if "scale" in name or "_norm']" in name or "['D']" in name:
                out.append(jnp.ones(leaf.shape, jnp.float32))
            elif "A_log" in name:
                n = jnp.arange(1, leaf.shape[0] + 1, dtype=jnp.float32)
                out.append(jnp.broadcast_to(jnp.log(n)[:, None], leaf.shape))
            elif "dt_bias" in name:
                dt = jnp.maximum(jnp.exp(f32(minval=math.log(lo),
                                             maxval=math.log(hi))), floor)
                out.append(dt + jnp.log(-jnp.expm1(-dt)))
            elif "conv_b" in name:
                out.append(f32(minval=-0.5, maxval=0.5))
            else:
                # (the tied embedding's fan-in is the head's: its columns)
                fan_in = leaf.shape[-1] if "embedding" in name \
                    else leaf.shape[-2]
                scale = next((v for n, v in ATTN_DRAW.items()
                              if f"['attn']['{n}']" in name), 1.0)
                out.append((jax.random.normal(k, leaf.shape, jnp.float32)
                            * scale * fan_in ** -0.5).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def kv_bytes_per_token(model_cfg, itemsize: int = 2) -> int:
    """K and V an attention layer; the Mamba layers keep nothing a
    token."""
    softmax = sum(k == "attn" for k in model_cfg.layer_kinds)
    return softmax * 2 * model_cfg.num_kv_heads * model_cfg.head_dim \
        * itemsize


def reference_dims(model_cfg) -> Dict[str, Any]:
    return dict(num_heads=model_cfg.num_heads,
                kv_heads=model_cfg.num_kv_heads,
                dt_rank=model_cfg.mamba_dt_rank,
                state=model_cfg.mamba_state, rms_eps=model_cfg.rms_eps)


def reference_logits(model_cfg):
    """jitted ``(params, tokens[B, T], at[B, n]) -> logits[B, n, vocab]``."""
    return jax.jit(functools.partial(reference.logits,
                                     **reference_dims(model_cfg)))
