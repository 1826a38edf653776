"""Model type ``gpt2``: the GPT-2/GPT-3 decoder of ``models/gpt2.py``."""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..reference import gpt as reference

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(cfg: Dict[str, Any], param_dtype: str):
    """``param_dtype`` comes from the job (the precision recipe is the
    job's, the widths are the configuration's)."""
    from deepspeed_tpu.models.gpt2 import GPT2Config
    run = cfg["as_run"]
    return GPT2Config(
        vocab_size=cfg["vocab_size"], max_seq_len=cfg["n_positions"] + 1,
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        hidden_size=cfg["n_embd"], mlp_ratio=cfg["n_inner"] // cfg["n_embd"],
        layer_norm_eps=cfg["layer_norm_epsilon"],
        param_dtype=_DTYPES[param_dtype], remat=run["remat"],
        remat_policy=run["remat_policy"],
        flash_block_q=run["flash_block_q"],
        flash_block_k=run["flash_block_k"])


def make(model_cfg, seed: int):
    """(params, loss_fn): params random from the seed, one jitted call."""
    from deepspeed_tpu.models.gpt2 import make_model
    _, init_fn, loss_fn = make_model(model_cfg)
    params = jax.jit(functools.partial(
        init_fn, batch_size=1, seq_len=model_cfg.max_seq_len - 1))(
            jax.random.PRNGKey(seed % (2 ** 31)))
    return params, loss_fn


def reference_loss(model_cfg):
    """jitted ``(params, tokens[B, T+1]) -> mean next-token loss``."""
    return jax.jit(functools.partial(
        reference.loss, num_heads=model_cfg.num_heads,
        eps=model_cfg.layer_norm_eps))
