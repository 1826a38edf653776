"""Model type ``kimi_linear``: a ``config.json`` of the Kimi-Linear family
(three gated delta-rule layers to one NoPE latent-attention layer, a dense
layer before the sparse ones), served by ``inference/v2/llama_runner.py``
from the ``models/kimi_linear.py`` tree. The configuration file may hold
one chip's share of a pipeline stage: ``num_experts`` experts of the
``num_experts_published`` the router scores, and a slice of the
vocabulary."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..reference import kimi_linear as reference


#: the two matrices of a latent layer drawn apart from the rest (times
#: 1/sqrt(fan-in)): at 1 a score is N(0, 1) over ~1,100 keys, the softmax
#: is all but flat, the layer's output a mean of ~400 random values and
#: 2 % of the stream, and a wrong latent layer does not show in the logits
#: (rotary applied read 84 % same top-1, the latent norm left out 97 %;
#: my chip run, PR 40), where a trained layer attends to a few keys and a
#: trained c_kv is not of unit size (which is why the model norms it).
#: With these a score has deviation ~2.1 (about a dozen keys carry a
#: softmax) and c_kv comes out of its projection at size 2
LATENT_DRAW = {"q_proj": 1.5, "kv_a_proj": 2.0}


def model_config(cfg: Dict[str, Any]):
    from deepspeed_tpu.models.registry import config_from_hf
    held = cfg["num_experts"]
    routed = cfg.get("num_experts_published", held)
    _, model_cfg = config_from_hf(dict(cfg, num_experts=routed))
    return dataclasses.replace(model_cfg, experts_held=held,
                               dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def init_params(model_cfg, seed: int):
    """Random weights from the seed, made on the device in one jitted
    call, in the dtype they are served in: the tree ``KimiLinear.init``
    gives, drawn as ``benchmark/model_types/solar_open2.py`` draws its own
    (which has the readings behind each choice): every matrix normal with
    deviation 1/sqrt(fan-in), each expert by its own fan-in, a
    convolution's fan-in its taps; norm scales 1; the embedding (a lookup,
    fan-in 1) at deviation 1; ``A_log`` uniform in [log 0.25, log 4] a
    head and ``dt_bias`` uniform in [-3, 3] a channel, so that the decay
    spreads over (0, 1); the router's selection bias normal with deviation
    0.01, about two of the gaps between neighbouring scores at the
    selection's edge. Apart from that module's draw: ``LATENT_DRAW``."""
    from deepspeed_tpu.models.kimi_linear import KimiLinear
    shapes = jax.eval_shape(
        lambda k: KimiLinear(model_cfg).init(
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
                fan_in = 1 if "embedding" in name else leaf.shape[-2]
                scale = next((v for n, v in LATENT_DRAW.items()
                              if f"['attn']['{n}']" in name), 1.0)
                w = jax.random.normal(k, leaf.shape, jnp.float32) \
                    * scale * fan_in ** -0.5
                out.append(w.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def kv_bytes_per_token(model_cfg, itemsize: int = 2) -> int:
    """One latent row a LATENT layer (``kv_lora_rank + qk_rope_head_dim``
    lanes, key and value at once; the stored row's zero tail left out);
    the recurrent layers keep nothing a token."""
    latent = sum(k == "mla" for k in model_cfg.layer_kinds)
    return latent * model_cfg.head_dim * itemsize


def reference_dims(model_cfg) -> Dict[str, Any]:
    return dict(
        num_heads=model_cfg.num_heads, nope=model_cfg.qk_nope_head_dim,
        rope=model_cfg.qk_rope_head_dim, v_dim=model_cfg.v_head_dim,
        rank=model_cfg.kv_lora_rank, kda_heads=model_cfg.kda_heads,
        top_k=model_cfg.experts_top_k, rms_eps=model_cfg.rms_eps,
        experts_first=model_cfg.experts_first,
        routed_scaling=model_cfg.routed_scaling)


def reference_logits(model_cfg):
    """jitted ``(params, tokens[B, T], at[B, n]) -> logits[B, n, vocab]``."""
    return jax.jit(functools.partial(reference.logits,
                                     **reference_dims(model_cfg)))
