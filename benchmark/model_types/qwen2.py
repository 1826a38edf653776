"""Model type ``qwen2``: a ``config.json`` of the Qwen2 family, served by
``inference/v2/llama_runner.py`` from the ``models/llama.py`` tree."""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..reference import qwen2 as reference


def model_config(cfg: Dict[str, Any]):
    from deepspeed_tpu.models.llama import LlamaConfig
    assert cfg["hidden_size"] % cfg["num_attention_heads"] == 0
    return LlamaConfig(
        vocab_size=cfg["vocab_size"],
        max_seq_len=cfg["max_position_embeddings"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        qkv_bias=True, tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def init_params(model_cfg, seed: int):
    """Random weights from the seed, made on the device in one jitted
    call, in the dtype they are served in."""
    from deepspeed_tpu.models.llama import Llama
    model = Llama(model_cfg)
    return jax.jit(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"])(
            jax.random.PRNGKey(seed % (2 ** 31)))


def kv_bytes_per_token(model_cfg, itemsize: int = 2) -> int:
    return (2 * model_cfg.num_layers * model_cfg.num_kv_heads
            * model_cfg.head_dim * itemsize)


def reference_logits(model_cfg):
    """jitted ``(params, tokens[B, T], at[B, n]) -> logits[B, n, vocab]``."""
    return jax.jit(functools.partial(
        reference.logits, num_heads=model_cfg.num_heads,
        num_kv_heads=model_cfg.num_kv_heads,
        rope_theta=model_cfg.rope_theta, rms_eps=model_cfg.rms_eps,
        tie_embeddings=model_cfg.tie_embeddings))
