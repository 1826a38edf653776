"""Model type ``lfm2_moe`` (the harness finds this file by the configuration's
``model_type``; the dense ``lfm2`` sibling's ``config.json`` goes through
the same hooks): a ``config.json`` of the LFM2 family
(gated short-convolution layers and full-attention layers by
``layer_types``, a dense feed-forward on the first ``num_dense_layers``
layers and sigmoid-routed experts after them), served by
``inference/v2/llama_runner.py`` from the ``models/lfm2.py`` tree. The
configuration file may hold a cut in depth and one chip's share of a
stage's experts (``num_experts`` of ``num_experts_published``); the cell's
holds every expert.

The draw. Matrices normal at deviation 1/sqrt(fan-in), each expert by its
own fan-in; the convolution's taps at ``taps ** -0.5``; the selection bias
at 0.01 (as ``solar_open2``'s: of the order of the gaps between
neighbouring scores, so that it changes the selection and never decides it
alone); the layer norms' scales 1; the q and k norms' scales apart from 1
(``ATTN_DRAW``, for ``benchmark/model_types/mellum.py``'s reason: at 1 a
score is N(0, 1) over thousands of keys, the softmax is flat and a wrong
rotary pairing would not show). The EMBEDDING at deviation ``hidden **
-0.5`` (0.022 at 2048: the family's own ``initializer_range`` 0.02 and what
``model_types/qwen2.py``, tied too, takes), NOT 1, because the head is
TIED: ``x_0 = E[token]`` rides the residual stream to the output, and
``RMSNorm(x_L) E^T`` scores the input token's own row at ``sqrt(hidden) x
(x_0's share of the stream's deviation)`` standard deviations of a logit
row: at deviation 1 beside branches that sum to about 3 (below) that is 14
sigma, every sequence repeats its last token whatever the mixers do, and
the check would compare nothing. At 0.022 the own row gets 0.3 sigma and
the mixers and the experts decide the logits; a logit row then has
deviation ``sqrt(hidden) x 0.022`` = 1. The stream and the branches: a
conv mixer's output has deviation about 1 (``B * u`` of two N(0, 1), taps
of unit sum of squares, ``C *`` again, one projection), a dense or sparse
feed-forward's 0.3-0.6, an attention layer's under 1: after the 9 layers
of the cell the stream's deviation is about 3, of which the seven conv
mixers hold three quarters of the variance. The proof that they decide
the logits is the cell's: every wrong-model switch of
``benchmark/reference/lfm2.py`` fails its check (the cell file's ``why``
has the readings)."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..reference import lfm2 as reference

#: the learned scale of the per-head q and k norms (1 is the
#: initialiser's; the docstring says why not)
ATTN_DRAW = {"q_norm": 2.0, "k_norm": 1.5}
#: the deviation of the selection bias
BIAS_DRAW = 0.01


def model_config(cfg: Dict[str, Any]):
    from deepspeed_tpu.models.registry import config_from_hf
    held = cfg.get("num_experts", 0)
    routed = cfg.get("num_experts_published", held)
    _, model_cfg = config_from_hf(dict(cfg, num_experts=routed))
    return dataclasses.replace(
        model_cfg, experts_held=held if held != routed else None,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def init_params(model_cfg, seed: int):
    """Random weights from the seed, made on the device in one jitted
    call, in the dtype they are served in: the tree ``Lfm2.init`` gives,
    drawn as the module docstring says."""
    from deepspeed_tpu.models.lfm2 import Lfm2
    shapes = jax.eval_shape(
        lambda k: Lfm2(model_cfg).init(
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
            k = jax.random.fold_in(key, i)
            if "sel_bias" in name:
                out.append(BIAS_DRAW * jax.random.normal(k, leaf.shape,
                                                         jnp.float32))
                continue
            # [.., fan-in, fan-out]; the convolution's fan-in is its taps;
            # the embedding's deviation is the docstring's
            fan_in = model_cfg.hidden_size if "embedding" in name \
                else leaf.shape[-2]
            out.append((jax.random.normal(k, leaf.shape, jnp.float32)
                        * fan_in ** -0.5).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def kv_bytes_per_token(model_cfg, itemsize: int = 2) -> int:
    """K and V an ATTENTION layer: the paged pool's. A conv layer keeps
    ``taps - 1`` inputs a sequence, nothing a token."""
    full = sum(k == "attn" for k in model_cfg.layer_kinds)
    return full * 2 * model_cfg.num_kv_heads * model_cfg.head_dim * itemsize


def reference_dims(model_cfg) -> Dict[str, Any]:
    return dict(
        kinds=tuple(model_cfg.layer_kinds),
        ffn_kinds=tuple(model_cfg.ffn_kinds),
        num_heads=model_cfg.num_heads, kv_heads=model_cfg.num_kv_heads,
        rope_theta=model_cfg.rope_theta, top_k=model_cfg.experts_top_k,
        rms_eps=model_cfg.rms_eps,
        routed_scaling=model_cfg.routed_scaling,
        experts_first=model_cfg.experts_first)


def reference_logits(model_cfg):
    """jitted ``(params, tokens[B, T], at[B, n]) -> logits[B, n, vocab]``."""
    return jax.jit(functools.partial(reference.logits,
                                     **reference_dims(model_cfg)))
