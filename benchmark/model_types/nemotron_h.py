"""Model type ``nemotron_h``: a ``config.json`` of the Nemotron-H family
(layers that are a Mamba-2 mixer alone, attention alone or a sparse
feed-forward of ungated relu2 experts alone, by
``hybrid_override_pattern``), served by ``inference/v2/llama_runner.py``
from the ``models/nemotron_h.py`` tree. The configuration file may hold one
chip's share of a pipeline stage: ``n_routed_experts`` experts of the
``n_routed_experts_published`` the router scores, and a slice of the
vocabulary.

The draw. Matrices as ``benchmark/model_types/solar_open2.py`` draws its
own (which has the readings behind each choice); the state-space leaves by
the CONFIG'S OWN initialisation (``time_step_min`` / ``time_step_max`` /
``time_step_floor``, Mamba-2's ``A`` in [1, 16], ``D`` 1), so that the
decays spread as a freshly initialised model's do: a step of 0.001-0.1
under ``a`` in [-16, -1] is a memory of a few to a thousand positions. The
convolution's bias uniform in [-1/2, 1/2], the depthwise convolution's
default at fan-in 4: at zero a bias left out would not show."""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..reference import nemotron_h as reference

#: the query and key projections of a softmax layer drawn apart from the
#: rest (times 1/sqrt(fan-in)): at 1 a score is N(0, 1) over ~1,100 keys,
#: the softmax is all but flat, the layer's output a mean of ~400 random
#: values, and a wrong attention layer does not show in the logits (a
#: rotary code applied read 84.8 % same top-1 beside an engine at 85.2 %,
#: and passed the cell's rule; my chip run, PR 44, as PR 40 met for its
#: latent layers), where a trained layer attends to a few keys. With
#: these a score has deviation 3 and a handful of keys carry a softmax
ATTN_DRAW = {"q_proj": 2.0, "k_proj": 1.5}
#: the config's ``time_step_min``, ``time_step_max``, ``time_step_floor``
TIME_STEP = (0.001, 0.1, 1e-4)


def model_config(cfg: Dict[str, Any]):
    from deepspeed_tpu.models.registry import config_from_hf
    held = cfg["n_routed_experts"]
    routed = cfg.get("n_routed_experts_published", held)
    _, model_cfg = config_from_hf(dict(cfg, n_routed_experts=routed))
    return dataclasses.replace(model_cfg, experts_held=held,
                               dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def init_params(model_cfg, seed: int):
    """Random weights from the seed, made on the device in one jitted
    call, in the dtype they are served in: the tree ``NemotronH.init``
    gives. Every matrix normal with deviation 1/sqrt(fan-in)
    (``ATTN_DRAW`` apart), each expert by its own fan-in (and the stored
    tail past the published width zero), a convolution's fan-in its
    taps; norm scales 1; the embedding (a
    lookup, fan-in 1) at deviation 1; the router's selection bias normal
    with deviation 0.01; ``dt_bias`` the inverse softplus of a step drawn
    log-uniform in [``TIME_STEP`` min, max] and floored; ``A_log`` the log
    of a uniform in [1, 16]; ``D`` 1; the convolution's bias uniform in
    [-1/2, 1/2]."""
    from deepspeed_tpu.models.nemotron_h import NemotronH
    shapes = jax.eval_shape(
        lambda k: NemotronH(model_cfg).init(
            k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    dtype = model_cfg.param_dtype
    F = model_cfg.intermediate_size
    lo, hi, floor = TIME_STEP

    def make(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, i)
            f32 = functools.partial(jax.random.uniform, k, leaf.shape,
                                    jnp.float32)
            if "scale" in name or "['norm']" in name or "['D']" in name:
                out.append(jnp.ones(leaf.shape, jnp.float32))
            elif "A_log" in name:
                out.append(jnp.log(f32(minval=1.0, maxval=16.0)))
            elif "dt_bias" in name:
                dt = jnp.maximum(jnp.exp(f32(minval=math.log(lo),
                                             maxval=math.log(hi))), floor)
                out.append(dt + jnp.log(-jnp.expm1(-dt)))
            elif "conv_b" in name:
                out.append(f32(minval=-0.5, maxval=0.5))
            elif "sel_bias" in name:
                out.append(0.01 * jax.random.normal(k, leaf.shape,
                                                    jnp.float32))
            else:
                fan_in = 1 if "embedding" in name else leaf.shape[-2]
                if "['wo']" in name:
                    fan_in = F
                scale = next((v for n, v in ATTN_DRAW.items()
                              if f"['attn']['{n}']" in name), 1.0)
                w = jax.random.normal(k, leaf.shape, jnp.float32) \
                    * scale * fan_in ** -0.5
                if "['wi']" in name:
                    w = w.at[..., F:].set(0)
                elif "['wo']" in name:
                    w = w.at[:, F:].set(0)
                out.append(w.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def kv_bytes_per_token(model_cfg, itemsize: int = 2) -> int:
    """K and V a softmax layer; the state-space layers keep nothing a
    token."""
    softmax = sum(k == "attn" for k in model_cfg.layer_kinds)
    return softmax * 2 * model_cfg.num_kv_heads * model_cfg.head_dim \
        * itemsize


def reference_dims(model_cfg) -> Dict[str, Any]:
    return dict(
        num_heads=model_cfg.num_heads, kv_heads=model_cfg.num_kv_heads,
        mamba_heads=model_cfg.mamba_heads, groups=model_cfg.mamba_groups,
        state=model_cfg.mamba_state, top_k=model_cfg.experts_top_k,
        rms_eps=model_cfg.rms_eps,
        expert_width=model_cfg.intermediate_size,
        experts_first=model_cfg.experts_first,
        routed_scaling=model_cfg.routed_scaling)


def reference_logits(model_cfg):
    """jitted ``(params, tokens[B, T], at[B, n]) -> logits[B, n, vocab]``."""
    return jax.jit(functools.partial(reference.logits,
                                     **reference_dims(model_cfg)))
