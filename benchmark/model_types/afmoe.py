"""Model type ``afmoe``: a ``config.json`` of the AFMoE family (Arcee
Trinity-Mini: three sliding-window layers to one full layer, sigmoid-gated
attention, leading dense layers, then 128 experts top-8 by sigmoid + a
selection bias beside a shared expert), TRAINED through ``dstpu.initialize``
/ ``engine.train_batch`` from the ``models/afmoe.py`` tree. The
configuration file may hold one chip's share of a layer: ``num_experts``
experts (from ``experts_held_first`` on) of the ``num_experts_published``
the router scores, and a slice of the vocabulary.

The draw (float32 masters; the file's ``assumed`` says why each): every
matrix normal at ``fan_in^-1/2``, each expert by its own fan-in; the
embedding at ``hidden^-1/2``, so that ``sqrt(hidden) E`` has deviation 1;
the selection biases at 0.01; the stream's norms' scales 1; the q and k
norms' scales :data:`ATTN_DRAW`, apart from 1 as Mellum's: with a flat
softmax no wrong window, rotary table or gate would show."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..reference import afmoe as reference

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
ATTN_DRAW = {"q_norm": 2.0, "k_norm": 1.5}
BIAS_DRAW = 0.01


def model_config(cfg: Dict[str, Any], param_dtype: str):
    """``param_dtype`` comes from the job (the precision recipe is the
    job's, the widths are the configuration's)."""
    from deepspeed_tpu.models.registry import config_from_hf
    held = cfg["num_experts"]
    routed = cfg.get("num_experts_published", held)
    run = cfg["as_run"]
    _, model_cfg = config_from_hf(dict(cfg, num_experts=routed))
    return dataclasses.replace(
        model_cfg, experts_held=held,
        experts_first=cfg.get("experts_held_first", 0),
        max_seq_len=run["seq_len"] + 1, remat=run["remat"],
        flash_block_q=run["flash_block_q"],
        flash_block_k=run["flash_block_k"], xent_impl=run["xent_impl"],
        xent_chunks=run["xent_chunks"], attention_impl=run["attention_impl"],
        dtype=jnp.bfloat16, param_dtype=_DTYPES[param_dtype])


def param_shapes(model_cfg):
    from deepspeed_tpu.models.afmoe import make_model
    _, init_fn, _ = make_model(model_cfg)
    return jax.eval_shape(functools.partial(init_fn, batch_size=1, seq_len=8),
                          jax.random.PRNGKey(0))


def loss_fn(model_cfg):
    """The model's own ``loss_fn(params, batch, rng) -> (loss, aux)``."""
    from deepspeed_tpu.models.afmoe import make_model
    return make_model(model_cfg)[2]


def make(model_cfg, seed: int):
    """(params, loss_fn): params random from the seed, one jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(model_cfg))

    def draw(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = jax.tree_util.keystr(path)
            z = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                  jnp.float32)
            if "scale" in name:
                z = jnp.full(leaf.shape, next(
                    (v for n, v in ATTN_DRAW.items() if f"['{n}']" in name),
                    1.0), jnp.float32)
            elif "select_bias" in name:
                z = z * BIAS_DRAW
            elif "embedding" in name:
                z = z * model_cfg.hidden_size ** -0.5
            else:
                z = z * leaf.shape[-2] ** -0.5
            out.append(z.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return (jax.jit(draw)(jax.random.PRNGKey(seed % (2 ** 31))),
            loss_fn(model_cfg))


def nll(model_cfg, compute_dtype):
    """jitted ``(params, tokens[B, T + 1]) -> each position's next-token
    NLL [B, T]`` of the model itself, over parameters cast as the engine
    casts them, through its full logits."""
    from deepspeed_tpu.models.afmoe import Afmoe
    from deepspeed_tpu.utils.dtypes import cast_floating
    model = Afmoe(model_cfg)

    def per_token(params, tokens):
        logits = model.apply({"params": cast_floating(params, compute_dtype)},
                             tokens[:, :-1])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]

    return jax.jit(per_token)


def group_of(name: str):
    """The leaf group of a parameter by its key string (``select_bias``:
    None: no gradient, it takes part in a selection only)."""
    if "select_bias" in name:
        return None
    return next(g for key, g in (
        ("embed", "embedding"), ("lm_head", "head"),
        ("['attn']['gate_proj']", "attn_gate"),
        ("_norm']", "norms"), ("['attn']", "attn_qkvo"),
        ("['moe']['gate']", "router"), ("['moe']", "held_experts"),
        ("['shared", "shared_expert"), ("['mlp']", "dense_ffn"))
        if key in name)


def param_groups(params) -> Dict[str, Any]:
    """The leaves by group, for the gradient checks: {group: [leaf]}."""
    groups: Dict[str, list] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        group = group_of(jax.tree_util.keystr(path))
        if group is not None:
            groups.setdefault(group, []).append(leaf)
    return groups


def active_params(model_cfg, rows_routed_per_token: float) -> float:
    """Parameters a token's matmuls pass through: everything outside the
    routed experts but the embedding (a lookup), plus one expert's for
    every routed row that fell on an expert held HERE, a token (counted
    rows: ``moe_rows_routed / tokens``, summed over the sparse layers)."""
    from deepspeed_tpu.models.afmoe import param_counts
    _, outside = param_counts(model_cfg)
    outside -= model_cfg.vocab_size * model_cfg.hidden_size
    expert = 3 * model_cfg.hidden_size * model_cfg.moe_intermediate_size
    return outside + expert * rows_routed_per_token


def reference_dims(model_cfg) -> Dict[str, Any]:
    return dict(
        sliding=tuple(k == "swa" for k in model_cfg.layer_kinds),
        num_dense=model_cfg.num_dense_layers, num_heads=model_cfg.num_heads,
        kv_heads=model_cfg.num_kv_heads, window=model_cfg.sliding_window,
        rope_theta=model_cfg.rope_theta, top_k=model_cfg.experts_top_k,
        route_norm=model_cfg.route_norm, route_scale=model_cfg.route_scale,
        rms_eps=model_cfg.rms_eps, held=model_cfg.held,
        mup=model_cfg.mup_enabled,
        q_block=min(512, model_cfg.max_seq_len - 1))


def reference_loss(model_cfg):
    """jitted ``(params, tokens[B, T+1]) -> mean next-token loss``."""
    return jax.jit(functools.partial(reference.loss,
                                     **reference_dims(model_cfg)))
