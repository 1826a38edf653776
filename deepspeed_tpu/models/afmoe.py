"""AFMoE family (``model_type: afmoe``, Arcee Trinity), in TRAINING form:
the flax module ``dstpu.initialize`` / ``engine.train_batch`` differentiate,
with the flash kernels, remat a layer and the chunked cross-entropy.

* embedding times ``sqrt(hidden_size)`` (``mup_enabled``); one residual
  stream and FOUR norms a layer (sandwich):
  ``h = x + N_post_attn(Attn_l(N_in(x)))``,
  ``x' = h + N_post_mlp(FFN_l(N_pre_mlp(h)))``; a final norm and an UNTIED
  head; RMSNorm everywhere, no bias anywhere.
* attention, every layer: GQA, one RMSNorm over each head's own lanes of q
  and of k; rotate-half RoPE on ``"swa"`` (``sliding_attention``) layers
  ALONE, no position code on ``"attn"`` (``full_attention``) layers (norm
  and code are ONE operation with its own backward,
  ``ops/kernels/qk_norm_rope.py``: float32 inside, one rounding); causal
  softmax at ``head_dim ** -0.5``, on a ``"swa"`` layer over keys ``0 <= i
  - j < sliding_window``; ``y = W_o (attn * sigmoid(W_g z))``. The call is
  the flash kernel with the layer's window
  (``ops/kernels/flash_attention.py``), under ``region("attn_window")`` /
  ``region("attn_core")``. Under ``remat`` a layer keeps that call's
  output and row sums from its forward to its backward (``_REMAT_POLICY``:
  the kernel file's two residual names), so the layer's recompute runs
  everything else again and no flash kernel.
* feed-forward: the first ``num_dense_layers`` layers a SwiGLU of width
  ``intermediate_size``; the rest ``sigmoid`` scores over ``num_experts``
  in float32, the top ``experts_top_k`` of ``score + select_bias``
  (selection only), the chosen scores renormalised and times
  ``route_scale``, SwiGLU experts of width ``moe_intermediate_size``
  through ``moe.sharded_moe.grouped_moe_ffn`` (``ragged_dot``: the
  gradient flows through it), plus ``num_shared_experts`` always-on
  SwiGLUs of the same width, ungated.
* balance: no auxiliary loss. ``loss_fn`` returns ``(loss, aux)``:
  ``aux["add"]`` holds each sparse layer's ``select_bias``'s move,
  ``load_balance_coeff * sign(mean(c) - c)`` over the step's per-expert
  rows ``c`` (the engine adds it to the float32 MASTER and drops the
  optimizer's result for the leaf: no gradient, no decay, and the rule
  does not depend on the compute copy's rounding: the selection reads
  the copy, as every product reads its weights' copy), and
  ``aux["counters"]`` the step's
  ``moe_rows_routed`` / ``moe_rows_elsewhere`` / ``moe_rows_hottest`` as
  ``InferenceEngineV2.pipeline_stats`` counts them in serving, and
  ``moe_rows_visited`` / ``moe_layers_full``: the rows of the sorted order
  the layers' grouped products walked, and the layers whose held rows
  exceeded ``sharded_moe.held_row_bound`` and walked every routed row.

``experts_held`` < ``num_experts`` is one chip's share of a layer divided
over chips by expert (``experts_first`` on): routing runs over all
``num_experts``, a row whose expert lies elsewhere adds nothing here (and
is not visited: ``grouped_moe_ffn`` cuts the sorted order to a bound on the
held rows). On
one chip that is the whole program; the exchange that would bring other
chips' rows here is not stood in for. Under an ``expert`` mesh axis the
share would be computed on every member alike, so the sparse layer
refuses the pair when it is traced.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.kernels import qk_norm_rope as _prep
from ..ops.kernels.flash_attention import RESIDUAL_NAMES, flash_attention
from ..telemetry.trace import region
from .llama import RMSNorm

#: ``layer_types`` -> this module's layer kind
LAYER_TYPES = {"sliding_attention": "swa", "full_attention": "attn"}


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    max_seq_len: int = 8193            # tokens a row of the batch: T + 1
    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 4
    attn_head_dim: int = 128
    #: "swa" or "attn", a layer
    layer_kinds: Tuple[str, ...] = ()
    num_dense_layers: int = 2
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    intermediate_size: int = 6144      # a dense layer's SwiGLU
    moe_intermediate_size: int = 1024  # one expert's, and the shared one's
    num_experts: int = 128
    experts_top_k: int = 8
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    load_balance_coeff: float = 1e-3
    experts_held: Optional[int] = None  # None = all of them
    experts_first: int = 0
    xent_impl: str = "chunked"
    xent_chunks: int = 8
    xent_remat: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    #: "auto" (flash on one TPU, else xla), "flash", "flash_interpret", "xla"
    attention_impl: str = "auto"
    flash_block_q: int = 1024
    flash_block_k: int = 1024

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim

    @property
    def num_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the experts this tree holds."""
        return (self.experts_first, self.num_experts
                if self.experts_held is None else self.experts_held)

    def sparse(self, layer: int) -> bool:
        return layer >= self.num_dense_layers

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 65)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_heads", 4)
        kw.setdefault("num_kv_heads", 1)
        kw.setdefault("attn_head_dim", 16)
        kw.setdefault("sliding_window", 16)
        kw.setdefault("intermediate_size", 96)
        kw.setdefault("moe_intermediate_size", 32)
        kw.setdefault("num_experts", 16)
        kw.setdefault("experts_top_k", 4)
        kw.setdefault("layer_kinds", ("swa", "swa", "swa", "attn",
                                      "swa", "attn"))
        kw.setdefault("dtype", jnp.float32)
        return AfmoeConfig(**kw)


def param_counts(cfg: AfmoeConfig) -> Tuple[int, int]:
    """(parameters of the tree as configured, parameters outside the
    routed experts): embedding, head, attention, norms, dense
    feed-forwards, routers and biases, shared experts. One routed expert
    is ``3 * hidden_size * moe_intermediate_size``."""
    M, D, W = cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size
    attn = M * D * (3 * cfg.num_heads + 2 * cfg.num_kv_heads) + 2 * D
    n_sparse = sum(cfg.sparse(i) for i in range(cfg.num_layers))
    n_dense = cfg.num_layers - n_sparse
    outside = 2 * cfg.vocab_size * M + M \
        + cfg.num_layers * (attn + 4 * M) \
        + n_dense * 3 * M * cfg.intermediate_size \
        + n_sparse * (M * cfg.num_experts + cfg.num_experts
                      + cfg.num_shared_experts * 3 * M * W)
    return outside + n_sparse * cfg.held[1] * 3 * M * W, outside


def _dense(cfg, feats: int, name: str):
    return nn.Dense(feats, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name=name)


class HeadScale(nn.Module):
    """A per-head RMSNorm's one leaf, ``scale`` float32 [head_dim], under
    the name the norm's module had."""

    @nn.compact
    def __call__(self, head_dim: int):
        return self.param("scale", nn.initializers.ones, (head_dim,),
                          jnp.float32)


def _prep_interpreted(cfg: AfmoeConfig) -> bool:
    """``"flash_interpret"`` interprets ``qk_norm_rope``'s kernel too."""
    return cfg.attention_impl == "flash_interpret"


def _attention_impl(cfg: AfmoeConfig) -> str:
    """``attention_impl`` with ``"auto"`` resolved where it is traced."""
    if cfg.attention_impl == "auto":
        return ("flash" if jax.default_backend() == "tpu"
                and jax.device_count() == 1 else "xla")
    return cfg.attention_impl


#: what a layer's ``nn.remat`` keeps from its forward to its backward: the
#: flash call's output and row sums (134 MB + 2 MB a layer at the cell's
#: ``[2, 32, 8192, 128]``), so that the recompute runs the projections,
#: ``qk_norm_rope`` and the feed-forward again and NO flash kernel
_REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    *RESIDUAL_NAMES)


class AfmoeAttention(nn.Module):
    cfg: AfmoeConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, M = x.shape
        H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        window = cfg.sliding_window if self.kind == "swa" else None
        q = _dense(cfg, H * D, "q_proj")(x)
        k = _dense(cfg, KV * D, "k_proj")(x)
        v = _dense(cfg, KV * D, "v_proj")(x).reshape(B, T, KV, D)
        gate = _dense(cfg, H * D, "gate_proj")(x)
        # head-major from here to the flash call's output: [B, H, T, D]
        q, k = _prep.qk_norm_rope(
            q, k, HeadScale(name="q_norm")(D), HeadScale(name="k_norm")(D),
            cfg.rms_eps,
            # a full layer carries no position
            _prep.rotary_table(T, D, cfg.rope_theta)
            if self.kind == "swa" else None,
            interpret=_prep_interpreted(cfg))
        v = jnp.swapaxes(v, 1, 2)

        impl = _attention_impl(cfg)
        with region("attn_window" if window is not None else "attn_core"):
            if impl in ("flash", "flash_interpret"):
                y = jnp.swapaxes(flash_attention(
                    q, k, v, causal=True, window=window, layout="BHTD",
                    block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
                    interpret=True if impl == "flash_interpret" else None),
                    1, 2)
            elif impl == "xla":
                i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
                mask = j <= i
                if window is not None:
                    mask &= j > i - window
                y = jax.nn.dot_product_attention(
                    *(jnp.swapaxes(jnp.repeat(a, H // a.shape[1], axis=1),
                                   1, 2) for a in (q, k, v)),
                    mask=mask[None, None])
            else:
                raise ValueError(
                    f"attention_impl must be 'auto', 'flash', "
                    f"'flash_interpret' or 'xla', got {impl!r}")
        y = y.reshape(B, T, H * D) * jax.nn.sigmoid(gate)
        return _dense(cfg, M, "o_proj")(y)


class AfmoeMLP(nn.Module):
    """One SwiGLU of width ``width``: a dense layer's feed-forward, and a
    sparse layer's shared expert."""
    cfg: AfmoeConfig
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = _dense(cfg, self.width, "gate_proj")(x)
        up = _dense(cfg, self.width, "up_proj")(x)
        return _dense(cfg, cfg.hidden_size, "down_proj")(nn.silu(gate) * up)


class AfmoeSparse(nn.Module):
    """The routed experts of one layer: ``(y, counts)`` with ``counts`` the
    rows each of the ``num_experts`` experts was chosen for ([E] int32)."""
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        B, T, M = h.shape
        E, W = cfg.num_experts, cfg.moe_intermediate_size
        n = cfg.held[1]
        if cfg.experts_held is not None:
            from ..parallel import topology as _topo
            if _topo.has_topology() and \
                    _topo.get_topology().mesh.shape.get("expert", 1) > 1:
                raise NotImplementedError(
                    "afmoe: experts_held is ONE chip's share of a layer; "
                    "under an 'expert' mesh axis every member would compute "
                    "the same share. Divide the experts by the mesh "
                    "(moe.sharded_moe.grouped_moe_ffn_ep) or hold them all")
        init = nn.initializers.lecun_normal()
        gate = self.param("gate", init, (M, E), jnp.float32)
        bias = self.param("select_bias", nn.initializers.zeros, (E,),
                          jnp.float32)
        wi_gate = self.param("wi_gate", init, (n, M, W), cfg.param_dtype)
        wi_up = self.param("wi_up", init, (n, M, W), cfg.param_dtype)
        wo = self.param("wo", init, (n, W, M), cfg.param_dtype)
        from ..moe.sharded_moe import grouped_moe_ffn
        x = h.reshape(B * T, M)
        with region("moe_route"):          # moe_experts opens inside
            logits = jnp.dot(x.astype(jnp.float32), gate.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            y, _, counts = grouped_moe_ffn(
                x, logits, cfg.experts_top_k, (wi_gate, wi_up, wo), nn.silu,
                cfg.dtype, cfg.route_norm, score="sigmoid",
                select_bias=jax.lax.stop_gradient(bias),
                weight_scale=cfg.route_scale, held=cfg.held, impl=None,
                return_counts=True)
        return y.reshape(B, T, M), counts


class AfmoeBlock(nn.Module):
    cfg: AfmoeConfig
    kind: str
    sparse: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_eps, cfg.dtype,  # noqa: E731
                                    name=name)
        with region("norm"):
            h = norm("input_norm")(x)
        with region("attn_proj"):       # the attention call's opens inside
            h = AfmoeAttention(cfg, self.kind, name="attn")(h)
        with region("norm"):
            h = norm("post_attn_norm")(h)
        with region("residual"):
            x = x + h
        with region("norm"):
            h = norm("pre_mlp_norm")(x)
        counts = None
        if self.sparse:
            y, counts = AfmoeSparse(cfg, name="moe")(h)
            with region("moe_shared"):
                for i in range(cfg.num_shared_experts):
                    y = y + AfmoeMLP(
                        cfg, cfg.moe_intermediate_size,
                        name="shared" if i == 0 else f"shared_{i}")(h)
        else:
            with region("ffn_dense"):
                y = AfmoeMLP(cfg, cfg.intermediate_size, name="mlp")(h)
        with region("norm"):
            y = norm("post_mlp_norm")(y)
        with region("residual"):
            x = x + y
        return x, counts


class Afmoe(nn.Module):
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        """Logits [B, T, V], or with ``return_hidden`` ``(hidden [B, T, M]
        after the final norm, [per-expert rows of each sparse layer])``."""
        cfg = self.cfg
        from ._lm_utils import constrain_activations, layer_class
        with region("embed"):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed")(tokens)
            if cfg.mup_enabled:
                x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
            x = constrain_activations(x)
        counts = []
        for i, kind in enumerate(cfg.layer_kinds):
            x, c = layer_class(self, AfmoeBlock, f"layer_{i}", cfg.remat,
                               policy=_REMAT_POLICY)(
                cfg, kind, cfg.sparse(i), name=f"layer_{i}")(x)
            if c is not None:
                counts.append(c)
        with region("head"):
            x = RMSNorm(cfg.rms_eps, cfg.dtype, name="final_norm")(x)
            if return_hidden:
                return x, counts
            return nn.Dense(cfg.vocab_size, use_bias=False,
                            dtype=jnp.float32, param_dtype=cfg.param_dtype,
                            name="lm_head")(x.astype(jnp.float32))


def bias_step(counts: jnp.ndarray, coeff: float) -> jnp.ndarray:
    """The family's balance without a loss, as the selection bias's move
    after a step: an expert chosen for fewer rows than the mean is raised
    by ``coeff``, one chosen for more lowered (DeepSeek-V3's
    auxiliary-loss-free rule)."""
    c = counts.astype(jnp.float32)
    return coeff * jnp.sign(c.mean() - c)


def step_counters(cfg: AfmoeConfig, counts, tokens: int, seq: int) -> dict:
    """``moe_rows_*`` of one step of ``tokens`` tokens from its sparse
    layers' per-expert rows, as ``pipeline_stats`` counts them in serving:
    rows routed to experts held here, rows routed elsewhere, and the
    busiest held expert's rows times the experts held, each summed over
    the layers; and what ``grouped_moe_ffn`` did with them: the rows of
    the sorted order each layer visited (``held_row_bound``, or every
    routed row where the held rows exceeded it), the layers that took
    every row, and the layers whose rows rejoined their tokens through the
    combine kernel (``combine_impl``: 0 wherever ``.at[].add`` ran); and
    the layers whose ``q`` and ``k`` went through ``qk_norm_rope``'s Pallas
    call (0 wherever its twin ran) at rows of ``seq`` tokens; and the
    layers whose recompute kept its flash call's output and row sums
    (``_REMAT_POLICY``: 0 without ``remat``, and where no flash call ran)."""
    from ..moe.sharded_moe import combine_impl, held_row_bound
    first, n = cfg.held
    rows = tokens * cfg.experts_top_k
    bound = held_row_bound(tokens, cfg.experts_top_k, cfg.num_experts,
                           cfg.held)
    kernel = combine_impl(tokens, n, cfg.hidden_size, (bound, rows),
                          cfg.dtype)
    prep = _prep.impl_of(seq, cfg.num_heads, cfg.head_dim, cfg.dtype,
                         _prep_interpreted(cfg))
    here = [c[first:first + n] for c in counts]
    routed = sum(h.sum() for h in here)
    full = sum((h.sum() > bound).astype(jnp.int32) for h in here)
    return {"moe_rows_routed": routed,
            "moe_rows_elsewhere": sum(c.sum() for c in counts) - routed,
            "moe_rows_hottest": sum(h.max() for h in here) * n,
            "moe_rows_visited": bound * len(here) + (rows - bound) * full,
            "moe_layers_full": full,
            "moe_combine_layers": jnp.int32(
                len(here) if kernel is not None else 0),
            "attn_prep_fused_layers": jnp.int32(
                cfg.num_layers if prep is not None else 0),
            "flash_residuals_kept_layers": jnp.int32(
                cfg.num_layers if cfg.remat and _attention_impl(cfg)
                in ("flash", "flash_interpret") else 0)}


def make_model(cfg: AfmoeConfig):
    """(model, init_fn, loss_fn), the registry's contract; ``loss_fn(params,
    batch, rng) -> (loss, aux)`` with the step's ``counters`` and the
    biases' moves to ``add`` (module docstring)."""
    model = Afmoe(cfg)

    def init_fn(rng, batch_size: int = 2, seq_len: Optional[int] = None):
        T = seq_len or min(cfg.max_seq_len - 1, 64)
        return model.init(rng, jnp.zeros((batch_size, T), jnp.int32))["params"]

    def loss_fn(params, batch, rng):
        from ._lm_utils import lm_head_xent
        with region("embed"):
            tokens = batch["tokens"]
            inputs, targets = tokens[:, :-1], tokens[:, 1:]
        hidden, counts = model.apply({"params": params}, inputs,
                                     return_hidden=True)
        loss = lm_head_xent(hidden, params["lm_head"]["kernel"], targets,
                            cfg, head_layout="cv")
        sparse = [i for i in range(cfg.num_layers) if cfg.sparse(i)]
        if not sparse:
            return loss, {}
        with region("optimizer"):       # the balance rule is the step's
            aux = {"add": {f"layer_{i}/moe/select_bias":
                           bias_step(c, cfg.load_balance_coeff)
                           for i, c in zip(sparse, counts)},
                   "counters": step_counters(cfg, counts, inputs.size,
                                            inputs.shape[1])}
        return loss, aux

    return model, init_fn, loss_fn
