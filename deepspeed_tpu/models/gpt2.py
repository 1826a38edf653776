"""GPT-2-style causal transformer (flax.linen).

The in-repo flagship model for tests and benchmarks — the analogue of the
reference's toy/test models (``tests/unit/simple_model.py``) and the GPT-2
configurations used for its ZeRO headline numbers (BASELINE.md: GPT-2-1.3B
ZeRO-3 bf16 is the north-star metric).

TPU-first choices: bf16 compute with fp32 params; all matmuls shaped for the
MXU (head_dim multiples of 128 at real sizes); optional ``jax.checkpoint``
remat per block; param names stable so tensor-parallel rules
(``deepspeed_tpu/parallel/tp_rules.py``) can target qkv/mlp projections.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..telemetry.trace import region


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    hidden_size: int = 768
    mlp_ratio: int = 4
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16          # compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = False
    # remat granularity: "full" recomputes the whole block; "dots" saves
    # matmul outputs and recomputes only elementwise ops (usually the best
    # memory/FLOPs trade on TPU — the MXU work is never repeated)
    remat_policy: str = "full"
    use_bias: bool = True
    layer_norm_eps: float = 1e-5   # HF GPT-2 epsilon
    # "auto": Pallas flash attention on TPU, XLA fused attention elsewhere;
    # "flash" / "xla" force one path.
    attention_impl: str = "auto"
    # flash kernel tile geometry (ops/kernels/flash_attention.py): the
    # DMA tile, not the grain of the causal mask. 512/512 suits seq 512;
    # the benchmark's 2048-token configuration sets 1024/1024
    # (benchmark/configs/gpt-1p3b.json), a 2 x 2 grid whose two diagonal
    # blocks the kernels cut into sub-tiles and compute only on and below
    # the diagonal, so a large tile no longer costs its masked half
    flash_block_q: int = 512
    flash_block_k: int = 512
    # fused LM-head xent chunking (models/_lm_utils.chunked_lm_xent):
    # xent_remat=False keeps chunk logits for backward (no unembed
    # recompute) — faster when the fp32 chunks fit HBM.
    # xent_impl "chunked" | "fused": "fused" routes through the streaming
    # Pallas kernel (ops/kernels/fused_xent.py) — logits never touch HBM
    # in either direction, at +1 N*V*C recompute matmul in backward
    xent_chunks: int = 8
    xent_remat: bool = True
    xent_impl: str = "chunked"
    # torch cross_entropy ignore_index semantics (e.g. -100 for padded
    # labels): dropped from the loss, the divisor, and both gradients
    xent_ignore_index: Optional[int] = None

    @staticmethod
    def tiny(**kw):
        return GPT2Config(vocab_size=512, max_seq_len=128, num_layers=2,
                          num_heads=4, hidden_size=64, **kw)

    @staticmethod
    def small(**kw):   # GPT-2 124M
        return GPT2Config(**kw)

    @staticmethod
    def xl_1p3b(**kw):  # GPT-2 1.3B class (the BASELINE.md metric model)
        return GPT2Config(num_layers=24, num_heads=32, hidden_size=2048,
                          max_seq_len=2048, **kw)


class CausalSelfAttention(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.cfg
        B, T, C = x.shape
        H, D = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        qkv = nn.Dense(3 * C, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       use_bias=cfg.use_bias, name="c_attn")(x)
        qkv = checkpoint_name(qkv, "qkv")
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, H, D)
        k = k.reshape(B, T, H, D)
        v = v.reshape(B, T, H, D)
        impl = cfg.attention_impl
        if impl == "auto":
            # Pallas custom calls carry no GSPMD partitioning rules, so a
            # multi-device jit would replicate q/k/v around the kernel. Auto
            # picks: single-device TPU -> plain flash; multi-device TPU with
            # a registered topology -> flash inside shard_map (batch over
            # data, heads over model); anything else -> XLA fused attention.
            from deepspeed_tpu.parallel import topology as _topo
            if jax.default_backend() != "tpu":
                impl = "xla"
            elif jax.device_count() == 1:
                impl = "flash"
            elif _topo.has_topology() and \
                    _topo.get_topology().mesh.shape.get("seq", 1) == 1:
                impl = "flash_sharded"
            else:
                # sequence-parallel meshes must NOT take flash_sharded: its
                # in_specs keep the sequence dim unsharded, so GSPMD would
                # all-gather seq-sharded activations around the kernel,
                # silently defeating SP — those meshes go through
                # ulysses/ring attention (parallel/) or plain XLA here
                impl = "xla"
        # the flash kernels name themselves ``attn`` in a profile
        # (flash_attn_roofline.train), whatever scope calls them
        with region("attn_core"):
            if impl == "flash":
                from deepspeed_tpu.ops.kernels import flash_attention
                y = flash_attention(q, k, v, causal=True, layout="BTHD",
                                    block_q=cfg.flash_block_q,
                                    block_k=cfg.flash_block_k)
            elif impl == "flash_sharded":
                from deepspeed_tpu.ops.kernels import sharded_flash_attention
                from deepspeed_tpu.parallel.topology import get_topology
                y = sharded_flash_attention(q, k, v, get_topology().mesh,
                                            causal=True, layout="BTHD",
                                            block_q=cfg.flash_block_q,
                                            block_k=cfg.flash_block_k)
            elif impl == "xla":
                # jax.nn.dot_product_attention lowers to a fused attention
                # on TPU
                y = jax.nn.dot_product_attention(q, k, v, is_causal=True)
            else:
                raise ValueError(
                    f"attention_impl must be 'auto', 'flash', 'flash_sharded' "
                    f"or 'xla', got {cfg.attention_impl!r}")
        y = y.reshape(B, T, C)
        y = nn.Dense(C, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     use_bias=cfg.use_bias, name="c_proj")(y)
        y = checkpoint_name(y, "attn_out")
        if cfg.dropout > 0:
            y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        return y


class MLP(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.cfg
        h = nn.Dense(cfg.mlp_ratio * cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, use_bias=cfg.use_bias,
                     name="c_fc")(x)
        # tagged for the "no_mlp" remat policy: the two mlp_ratio-wide
        # intermediates dominate per-layer activation memory
        h = checkpoint_name(h, "mlp_pre_act")
        h = nn.gelu(h)
        h = checkpoint_name(h, "mlp_act")
        h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, use_bias=cfg.use_bias,
                     name="c_proj")(h)
        if cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return h


class Block(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.cfg
        # device time is read by region (telemetry/trace.py), under the
        # names the serve step uses; flax puts the modules' names around
        with region("norm"):
            h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                             name="ln_1")(x)
        with region("attn_proj"):       # attn_core opens inside
            h = CausalSelfAttention(cfg, name="attn")(h, deterministic)
        with region("residual"):
            x = x + h
        with region("norm"):
            h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                             name="ln_2")(x)
        with region("ffn_dense"):
            h = MLP(cfg, name="mlp")(h, deterministic)
        with region("residual"):
            x = x + h
        return x


class GPT2(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True,
                 return_hidden: bool = False):
        cfg = self.cfg
        B, T = tokens.shape
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="wte")
        wpe = nn.Embed(cfg.max_seq_len, cfg.hidden_size,
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="wpe")
        from ._lm_utils import constrain_activations, layer_class
        with region("embed"):
            x = wte(tokens) + wpe(jnp.arange(T)[None, :])
            # pin the embedding output to the natural activation layout
            # (shared helper — see _lm_utils.constrain_activations for why)
            x = constrain_activations(x)
        policy = None
        if cfg.remat:
            if cfg.remat_policy == "dots":
                policy = jax.checkpoint_policies.checkpoint_dots
            elif cfg.remat_policy == "no_mlp":
                # save every residual/attention activation, recompute only
                # the two mlp_ratio-wide MLP intermediates in the backward
                # pass — one fc1 matmul recomputed vs "full"'s entire
                # forward (which costs 33% extra step FLOPs)
                policy = jax.checkpoint_policies.save_anything_except_these_names(
                    "mlp_pre_act", "mlp_act")
            elif cfg.remat_policy == "no_gelu":
                # drop only the post-gelu intermediate: recompute is a free
                # elementwise op, memory still sheds one mlp_ratio-wide
                # tensor per layer
                policy = jax.checkpoint_policies.save_anything_except_these_names(
                    "mlp_act")
            elif cfg.remat_policy == "qkv_out":
                # save ONLY the fused qkv and the attention output (4*C per
                # layer): backward recomputes the cheap LNs, the MLP fc1 +
                # gelu, and the flash forward (for its lse), but never the
                # qkv/attn-proj matmuls — a middle point between "full"
                # (+33% step FLOPs) and no remat (OOM at useful batch)
                policy = jax.checkpoint_policies.save_only_these_names(
                    "qkv", "attn_out")
            elif cfg.remat_policy.startswith("save:"):
                # explicit checkpoint_name list, e.g.
                # "save:qkv,attn_out,mlp_pre_act" — saves qkv + attention
                # output + the fc1 pre-activation (8*C per layer), so the
                # backward recomputes only LNs, gelu and the flash forward:
                # near-zero repeated MXU work at ~2x the qkv_out residency
                names = [n for n in cfg.remat_policy[5:].split(",") if n]
                policy = jax.checkpoint_policies.save_only_these_names(*names)
        for i in range(cfg.num_layers):
            x = layer_class(self, Block, f"h_{i}", cfg.remat,
                            static_argnums=(2,), policy=policy)(
                cfg, name=f"h_{i}")(x, deterministic)
        with region("head"):
            x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                             name="ln_f")(x)
            if return_hidden:
                # post-ln_f activations in the compute dtype: the training
                # loss consumes these via the chunked fused cross-entropy
                # (models/_lm_utils.chunked_lm_xent) instead of full logits
                return x
            # tied embedding unembed (GPT-2 ties wte)
            logits = wte.attend(x.astype(jnp.float32))
        return logits


def make_model(cfg: GPT2Config):
    """Returns (init_fn, loss_fn) — loss_fn matches the engine signature
    ``(params, batch, rng) -> loss`` where batch = {"tokens": [B, T+1] int32}
    (next-token LM loss)."""
    model = GPT2(cfg)

    def init_fn(rng, batch_size: int = 2, seq_len: Optional[int] = None):
        T = seq_len or min(cfg.max_seq_len, 64)
        tokens = jnp.zeros((batch_size, T), jnp.int32)
        return model.init(rng, tokens)["params"]

    def loss_fn(params, batch, rng):
        from ._lm_utils import lm_head_xent
        with region("embed"):
            tokens = batch["tokens"]
            inputs, targets = tokens[:, :-1], tokens[:, 1:]
        hidden = model.apply({"params": params}, inputs,
                             deterministic=cfg.dropout == 0,
                             return_hidden=True,
                             rngs={"dropout": rng} if cfg.dropout > 0 else None)
        return lm_head_xent(hidden, params["wte"]["embedding"], targets,
                            cfg)

    return model, init_fn, loss_fn
