"""Ragged paged-KV runner for the Llama family (Mixtral-style MoE, the
hybrid Solar-Open2 family whose layers follow a per-layer list of mixers,
the latent-attention openPangu-Ultra-MoE family, whose feed-forward
kind follows a per-layer list too, Kimi-Linear, whose layer list holds
recurrent AND latent layers, Nemotron-H, whose layers are a mixer ALONE
or a feed-forward ALONE and whose recurrent layers are state-space ones,
Mellum, whose softmax layers are sliding-window or full by the list,
each kind with its own position code and its own pool, and LFM2, whose
recurrent layers are gated short convolutions that carry their last
inputs and no state, and Jamba, whose recurrent layers are Mamba-1
mixers: a decay a channel AND state).

Analogue of the reference's llama_v2 / mistral / mixtral v2 containers
(``inference/v2/model_implementations/{llama_v2,mistral,mixtral}/``): RoPE
applied at each token's absolute position, GQA KV stored at kv-head width,
SwiGLU MLP (or top-k routed MoE for Mixtral), RMSNorm, last-token logits.
Shares the fixed-shape RaggedBatch contract of ``model_runner.py``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ...models.llama import LlamaConfig, apply_rope
from ...models.mixtral import MixtralConfig
from ...telemetry.trace import region
from .config import RaggedInferenceConfig
from .kv_quant import lin_parts, with_lin
from .model_runner import (RaggedBatch, RaggedRunnerBase, latent_attention,
                           paged_attention, sparse_paged_attention,
                           tp_all_reduce, woq_mm)


def _mlp_act(model_cfg):
    """The feed-forward's activation, by the name the model config gives
    it (SiLU where it gives none); a name this runner has no function for
    raises."""
    from ...models.nemotron_h import relu2
    return {"silu": jax.nn.silu,
            "relu2": relu2}[getattr(model_cfg, "mlp_act", "silu")]


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return y * scale


class LlamaRaggedRunner(RaggedRunnerBase):
    """All runner plumbing (jitted step / greedy step / fused decode loop,
    WOQ dequant-in-jit) comes from RaggedRunnerBase; ``step_fn`` is bound at
    the bottom of this module. Matmul sites dispatch through ``woq_mm``,
    so fused fp6 weights (quantized_weights.fused_gemm) stream through
    the Pallas 6-bit GEMM instead of a full dequant."""

    supports_fused_woq = True


def _moe_counts(top_idx, valid, E: int, held, kernel: bool):
    """The fused loop's counters of one sparse layer, [E + 2] int32: the
    routed rows of VALID rows per expert, then the held experts the
    grouped kernel found with a row and the times it streamed an expert's
    matrices (both over every row of the step, padding too, as the kernel
    walks them; 0 without ``kernel``). top_idx [rows, k], valid [rows].
    Counted by ONE compare of the choices against the experts and its
    column sums: a scatter-add of rows x k integers is that many serial
    updates on the TPU (45 us at 256 x 8 over 64 experts where this is 8:
    PERF.md section 6, PR 57)."""
    from ...ops.kernels import grouped_ffn
    chose = top_idx[..., None] == jnp.arange(E, dtype=top_idx.dtype)
    rows = jnp.sum(jnp.where(chose, valid[:, None, None].astype(jnp.int32),
                             0), axis=(0, 1), dtype=jnp.int32)
    hit = reads = jnp.int32(0)
    if kernel:
        first, count = held or (0, E)
        mine = jnp.sum(chose, axis=(0, 1),
                       dtype=jnp.int32)[first:first + count]
        hit = jnp.sum(mine > 0, dtype=jnp.int32)
        routed = top_idx.size
        reads = jnp.sum(grouped_ffn.streams(
            mine, grouped_ffn.row_tile(routed, E),
            grouped_ffn.span_cap(routed, E)), dtype=jnp.int32)
    return jnp.concatenate([rows, jnp.stack([hit, reads])])


def _moe_mlp(p_moe, h, cfg: MixtralConfig, dtype,
             icfg: RaggedInferenceConfig = None, valid=None):
    """Grouped-GEMM MoE for the ragged path: tokens sort by their routed
    expert and each expert multiplies only its rows
    (sharded_moe.grouped_moe_ffn) — E/k x fewer FLOPs than the round-2
    dense-every-expert path. Matches the reference's CUTLASS grouped GEMM
    (inference/v2/kernels/cutlass_ops/moe_gemm/).

    Which grouped matmul, from what this call can see
    (``grouped_ffn.kernel_impl``): on a TPU backend, over plain floating
    expert stacks, every step takes the Pallas grouped kernel, each hit
    expert's matrices streamed once with gate, up and down in one pass,
    at the row tile and the span cap its expected rows an expert ask for
    (``grouped_ffn.row_tile`` / ``span_cap``): every decode step (2-4
    rows an expert) at 16, Solar's [4, 512] refill step (51) at 64, both
    under a 128-row span; OLMoE's and Mellum2's refill step (256 rows an
    expert, the chip's ridge) at 128 under a 512-row span. Quantised
    stacks and every other backend take three ``jax.lax.ragged_dot``
    calls.

    Inside an ``expert``-axis shard_map (``cfg.ep_size > 1`` engines)
    the routed rows instead travel the dispatch→grouped-GEMM→combine
    pipeline of ``grouped_moe_ffn_ep_serve``: router logits computed
    everywhere from the replicated gate, tokens exchanged to their
    experts' home chips and back with exactly TWO ``all_to_all`` hops
    per layer (chunked over ``icfg.ep_comm_chunks`` slices when
    ``ep_comm_overlap='chunked'`` so chunk k's expert GEMMs run under
    chunk k+1's exchange). ``p_moe`` then holds this chip's [E/ep, ...]
    expert stacks while the gate stays full-width.

    Returns (y [S, C, M], rows): with ``valid`` [S, C] given, ``rows``
    [E + 2] int32 counts the routed rows of VALID positions per expert
    (padding positions are computed like the others and left out of the
    count only), then the held experts the kernel found with at least one
    row and the times it streamed an expert's matrices (one a visit: once
    a hit expert unless its rows pass a visit's span cap; both 0 on
    the ``ragged_dot`` path); None without it and on the expert-parallel
    path."""
    from ...moe.sharded_moe import grouped_moe_ffn, route_topk
    from ...ops.kernels import grouped_ffn
    from ...ops.kernels.fp6_gemm import Fp6GemmWeight, fp6_gemm_unpack
    from .expert_parallel import EP_AXIS, ep_axis_active
    S, C, M = h.shape
    gate_w = p_moe["gate"]
    if isinstance(gate_w, Fp6GemmWeight):
        # the router weight [hidden, E] is fused-packable (E % 4 == 0)
        # but tiny — unpack rather than kernel-dispatch the [*, E] GEMV
        gate_w = fp6_gemm_unpack(gate_w)
    with region("moe_route"):
        logits = h.astype(jnp.float32).reshape(S * C, M) @ gate_w
    if "wi_gate" in p_moe:                                    # SwiGLU experts
        weights = (p_moe["wi_gate"], p_moe["wi_up"], p_moe["wo"])
    else:
        weights = (p_moe["wi"], p_moe["wo"])
    norm = getattr(cfg, "norm_topk_prob", True)
    act = _mlp_act(cfg)
    # the router's form and the experts this chip holds, from the model
    # config (softmax over all, no bias, every expert: the defaults)
    router = dict(
        score=getattr(cfg, "router_score", "softmax"),
        select_bias=p_moe["sel_bias"]
        if getattr(cfg, "router_bias", False) else None,
        weight_scale=float(getattr(cfg, "routed_scaling", 1.0)),
        norm_eps=float(getattr(cfg, "router_norm_eps", 1e-20)))
    held = (cfg.experts_first, cfg.held) if hasattr(cfg, "held") else None
    if ep_axis_active():
        from ...moe.sharded_moe import (ep_serve_capacity,
                                        grouped_moe_ffn_ep_serve)
        ep = jax.lax.axis_size(EP_AXIS)
        chunks = int(icfg.ep_comm_chunks) \
            if icfg is not None and icfg.ep_comm_overlap == "chunked" else 1
        factor = float(icfg.ep_capacity_factor) if icfg is not None else 2.0
        cap = ep_serve_capacity(S * C, cfg.experts_top_k, ep, factor,
                                chunks)
        with region("moe_experts"):
            y, _ = grouped_moe_ffn_ep_serve(
                h.reshape(S * C, M), logits, cfg.experts_top_k, weights,
                act, dtype, EP_AXIS, cfg.num_experts, cap,
                normalize_weights=norm, chunks=chunks)
        return y.reshape(S, C, M), None
    impl = grouped_ffn.kernel_impl(weights, dtype)
    # routing, layout and the weighted sum; the grouped matmuls open
    # ``moe_experts`` inside
    with region("moe_route"):
        y, _ = grouped_moe_ffn(
            h.reshape(S * C, M), logits, cfg.experts_top_k, weights,
            act, dtype, normalize_weights=norm, held=held,
            impl=impl, **router)
    rows = None
    if valid is not None:
        # the loop's counters: the same choice the grouped path makes of
        # the same logits
        with region("loop_carry"):
            top_idx = route_topk(logits, cfg.experts_top_k,
                                 score=router["score"],
                                 bias=router["select_bias"])[0]
            rows = _moe_counts(top_idx, valid.reshape(S * C),
                               cfg.num_experts, held, impl is not None)
    return y.reshape(S, C, M), rows


@jax.jit
def kda_chunked_prefill(q, k, v, g, beta, St0, n_tokens):
    """The chunked delta rule (chunks of 64) as one named program of a
    prefill step, on states transposed as the pool holds them: traced
    and lowered once for all the recurrent layers of a step. Positions
    from ``n_tokens`` on are the step's padding (beta 0, g 0)."""
    from ...ops.kernels.delta_rule import kda_prefill
    return kda_prefill(q, k, v, g, beta, St0, n_tokens)


def _state_rows(kv, si: int, batch: RaggedBatch):
    """What the recurrent mixers share of the state pool: layer ``si``'s
    states, and for every row the slot ``batch.state_slots`` names. A row
    whose chunk starts at position 0 is ``fresh``: it starts from zero
    state and zero convolution inputs, whatever the slot's last tenant
    left there; a row with ``n_tokens`` 0 is idle (not ``live``) and
    leaves its pool row as it was. Returns (state, conv, st, slots, fresh,
    live)."""
    state, conv = lin_parts(kv)
    fresh = batch.start_pos == 0
    live = batch.n_tokens > 0
    # a model whose recurrent layers carry convolution inputs alone
    # ('conv') has no state part
    st = None if state is None else state[si]
    return state, conv, st, batch.state_slots, fresh, live


def _short_conv(conv, si: int, batch: RaggedBatch, fresh, live, pre, w,
                bias=None, activation="silu"):
    """The short convolution of recurrent layer ``si`` over its rows'
    carried inputs, and the pool with the inputs the next call carries:
    the last K-1 up to each row's last real position; an idle row keeps
    what it had. pre [S, C, W] float32, w [K, W] (the taps' count is
    the weights'), bias [W] or None, ``activation`` a name of
    ``short_conv.ACTIVATIONS`` or None (a family's own: SiLU for the
    delta-rule and state-space layers, none for LFM2's). A decode step
    on the TPU is one in-place Pallas call (``ops/kernels/short_conv``:
    platform and shape decide, nothing a user sets); a prefill chunk and
    every other backend gather the slots' rows, convolve
    (``models.solar_open2.short_conv``) and scatter. Returns (conv, y
    [S, C, W] float32)."""
    from ...models import solar_open2
    from ...ops.kernels import default_interpret, short_conv
    S, C, W = pre.shape
    slots = batch.state_slots
    wide = conv.shape[2] * conv.shape[3] // (w.shape[0] - 1)
    if wide > W:
        # the pool is wider than the layer's channels
        # (``short_conv.whole_width``): zeros ride in the rest
        room = lambda t: jnp.pad(                        # noqa: E731
            t, [(0, 0)] * (t.ndim - 1) + [(0, wide - W)])
        conv, y = _short_conv(conv, si, batch, fresh, live, room(pre),
                              room(w), None if bias is None else room(bias),
                              activation)
        return conv, y[..., :W]
    if C == 1 and short_conv.decode_uses_kernel(S, W, conv.dtype):
        conv, y = short_conv.short_conv_decode_step(
            conv, si, slots, pre[:, 0], w, bias, fresh, live,
            activation=activation, interpret=default_interpret())
        return conv, y[:, None]
    taps = w.shape[0] - 1
    prev0 = conv[si, slots]                 # [S, (K-1) W / lanes, lanes]
    prev = jnp.where(fresh[:, None, None], 0, prev0).reshape(S, taps, W)
    y, padded = solar_open2.short_conv(pre, w, prev.astype(jnp.float32))
    if bias is not None:
        y = y + bias
    if activation is not None:
        y = short_conv.ACTIVATIONS[activation](y)
    rows = batch.n_tokens[:, None] + jnp.arange(taps, dtype=jnp.int32)
    nxt = jnp.take_along_axis(padded, rows[..., None], axis=1)
    return conv.at[si, slots].set(jnp.where(
        live[:, None, None], nxt.astype(conv.dtype).reshape(prev0.shape),
        prev0)), y


def _with_layer_state(kv, state, conv, si: int, st):
    """``kv`` with layer ``si``'s states replaced by ``st``."""
    return with_lin(kv, state[:si] + (st,) + state[si + 1:], conv)


def _kda_mixer(p, h, kv, si: int, batch: RaggedBatch, model_cfg, valid_q,
               dtype):
    """One gated delta-rule layer over the state pool. ``si`` is the
    layer's index among the recurrent layers (its plane of the pool);
    the rows and their slots are :func:`_state_rows`'. Padded positions
    leave the state as it was (beta 0, g 0). One token a row goes through
    the decode update (in place, one read and one write of each state),
    more through the chunked form. Returns (kv, y [S, C, M])."""
    from ...models.solar_open2 import (kda_conv_inputs, kda_output,
                                       kda_recurrence_inputs)
    from ...ops.kernels.delta_rule import kda_decode_update
    state, conv, st, slots, fresh, live = _state_rows(kv, si, batch)
    S, C, _ = h.shape
    pre, w = kda_conv_inputs(p, h, dtype)
    conv, y = _short_conv(conv, si, batch, fresh, live, pre, w)
    q, k, v, g, beta = kda_recurrence_inputs(p, h, y, model_cfg, dtype)
    g = jnp.where(valid_q[..., None, None], g, 0.0)
    beta = jnp.where(valid_q[..., None], beta, 0.0)
    if C == 1:
        # exp(-inf) = 0 wipes what the slot held: a fresh row's zero state
        g1 = jnp.where((fresh & live)[:, None, None], -jnp.inf, g[:, 0])
        o, st = kda_decode_update(st, slots, q[:, 0], k[:, 0], v[:, 0], g1,
                                  beta[:, 0])
        o = o[:, None]
    else:
        St0 = st[slots]                                   # [S, H, dv, dk]
        o, Sn = kda_chunked_prefill(
            q, k, v, g, beta,
            jnp.where(fresh[:, None, None, None], 0.0, St0),
            batch.n_tokens)
        st = st.at[slots].set(
            jnp.where(live[:, None, None, None], Sn, St0))
    return _with_layer_state(kv, state, conv, si, st), \
        kda_output(p, o, h, model_cfg, dtype)


@jax.jit
def gdn_chunked_prefill(q, k, v, g, beta, St0, n_tokens):
    """:func:`kda_chunked_prefill` for the delta rule with ONE decay a
    head, on states laid out as its pool holds them."""
    from ...ops.kernels.delta_rule import gdn_prefill
    return gdn_prefill(q, k, v, g, beta, St0, n_tokens)


def _gdn_mixer(p, h, kv, si: int, batch: RaggedBatch, model_cfg, valid_q,
               dtype):
    """One gated delta-rule layer with ONE decay a head over the state
    pool, as :func:`_kda_mixer` runs the channel form: the same rows,
    slots and short convolution (q | k | v of two widths), the head's
    decay and the family's own output (``models/olmo_hybrid.py``), a
    state ``[d_k, heads x d_v]`` a row (``delta_rule.gdn_state_shape``).
    Padded positions leave the state as it was (beta 0, g 0). Returns
    (kv, y [S, C, M])."""
    from ...models.olmo_hybrid import gdn_output, gdn_recurrence_inputs
    from ...models.solar_open2 import kda_conv_inputs
    from ...ops.kernels.delta_rule import gdn_decode_update
    state, conv, st, slots, fresh, live = _state_rows(kv, si, batch)
    S, C, _ = h.shape
    pre, w = kda_conv_inputs(p, h, dtype)
    conv, y = _short_conv(conv, si, batch, fresh, live, pre, w)
    q, k, v, g, beta = gdn_recurrence_inputs(p, h, y, model_cfg, dtype)
    g = jnp.where(valid_q[..., None], g, 0.0)
    beta = jnp.where(valid_q[..., None], beta, 0.0)
    if C == 1:
        # exp(-inf) = 0 wipes what the slot held: a fresh row's zero state
        g1 = jnp.where((fresh & live)[:, None], -jnp.inf, g[:, 0])
        o, st = gdn_decode_update(st, slots, q[:, 0], k[:, 0], v[:, 0], g1,
                                  beta[:, 0])
        o = o[:, None]
    else:
        St0 = st[slots]                                   # [S, dk, H dv]
        o, Sn = gdn_chunked_prefill(
            q, k, v, g, beta, jnp.where(fresh[:, None, None], 0.0, St0),
            batch.n_tokens)
        st = st.at[slots].set(jnp.where(live[:, None, None], Sn, St0))
    return _with_layer_state(kv, state, conv, si, st), \
        gdn_output(p, o, h, model_cfg, dtype)


def _mamba2_mixer(p, h, kv, si: int, batch: RaggedBatch, model_cfg, valid_q,
                  dtype):
    """One state-space (Mamba-2) layer over the state pool, as
    :func:`_kda_mixer` runs a delta-rule one: the same rows and slots
    (:func:`_state_rows`), a state ``[H, P, N]`` a row. Padded positions
    take a zero step (``dt`` 0: decay 1, nothing added). One token a row
    goes through the decode update kernel in place (``ops/kernels/ssd``),
    more through the chunked SSD form. Returns (kv, y [S, C, M])."""
    from ...models.nemotron_h import (mamba2_conv_inputs, mamba2_output,
                                      mamba2_recurrence_inputs)
    from ...ops.kernels.ssd import mamba2_decode_update, mamba2_prefill
    state, conv, st, slots, fresh, live = _state_rows(kv, si, batch)
    S, C, _ = h.shape
    f32 = jnp.float32
    z, xbc, dt = mamba2_conv_inputs(p, h, model_cfg, dtype)
    conv, xbc = _short_conv(conv, si, batch, fresh, live, xbc,
                            p["conv_w"].astype(f32), p["conv_b"].astype(f32))
    x, Bm, Cm, dt = mamba2_recurrence_inputs(p, xbc, dt, model_cfg)
    dt = jnp.where(valid_q[..., None], dt, 0.0)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    D = p["D"].astype(jnp.float32)
    if C == 1:
        y, st = mamba2_decode_update(
            st, slots, x[:, 0], dt[:, 0], a, Bm[:, 0], Cm[:, 0], D,
            wipe=fresh & live)
        y = y[:, None]
    else:
        St0 = st[slots]                                   # [S, H, P, N]
        y, Sn = mamba2_prefill(
            x, dt, a, Bm, Cm,
            jnp.where(fresh[:, None, None, None], 0.0, St0),
            chunk=model_cfg.mamba_chunk)
        y = y + D[:, None] * x
        st = st.at[slots].set(
            jnp.where(live[:, None, None, None], Sn, St0))
    return _with_layer_state(kv, state, conv, si, st), \
        mamba2_output(p, y, z, model_cfg, dtype)


def _mamba1_mixer(p, h, kv, si: int, batch: RaggedBatch, model_cfg, valid_q,
                  dtype):
    """One Mamba-1 layer (a decay a channel AND state) over the state
    pool, as :func:`_mamba2_mixer` runs the scalar-decay form: the same
    rows and slots (:func:`_state_rows`), a state ``[N, E]`` a row
    (``selective_scan.mamba1_state_shape``), the short convolution over
    ``x`` alone, B, C and the low-rank step size out of its OUTPUT
    (``models/jamba.py``). Padded positions take a zero step (``dt`` 0:
    decay 1, nothing added). One token a row goes through the decode
    update in place, more through the chunk scan, in place too
    (``ops/kernels/selective_scan``). Returns (kv, y [S, C, M])."""
    from ...models.jamba import (mamba1_conv_inputs, mamba1_output,
                                 mamba1_recurrence_inputs)
    from ...ops.kernels.selective_scan import (mamba1_decode_update,
                                               mamba1_prefill)
    state, conv, st, slots, fresh, live = _state_rows(kv, si, batch)
    S, C, _ = h.shape
    f32 = jnp.float32
    x, z = mamba1_conv_inputs(p, h, model_cfg, dtype)
    conv, x = _short_conv(conv, si, batch, fresh, live, x,
                          p["conv_w"].astype(f32), p["conv_b"].astype(f32))
    dt, Bm, Cm = mamba1_recurrence_inputs(p, x, model_cfg, dtype)
    dt = jnp.where(valid_q[..., None], dt, 0.0)
    A = -jnp.exp(p["A_log"].astype(f32))
    if C == 1:
        y, st = mamba1_decode_update(
            st, slots, x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], p["D"],
            wipe=fresh & live)
        y = y[:, None]
    else:
        y, st = mamba1_prefill(st, slots, x, dt, A, Bm, Cm, p["D"],
                               wipe=fresh, live=live)
    return _with_layer_state(kv, state, conv, si, st), \
        mamba1_output(p, y, z, dtype)


def _gated_conv_mixer(p, h, kv, si: int, batch: RaggedBatch, dtype):
    """One gated short-convolution (LFM2) layer over the convolution part
    of the state pool, which is ALL a sequence carries for it: ``B | C |
    u`` in one projection, ``v = B * u`` in float32, the causal
    convolution of ``v`` with no bias and no activation
    (:func:`_short_conv`: the same rows, slots, ``fresh`` and ``live`` as
    the other recurrent kinds, :func:`_state_rows`), ``W_out (C * conv)``.
    A padded position's ``v`` lies past the row's last real one and is
    never carried. Returns (kv, y [S, C, M])."""
    from ...models.lfm2 import gated_conv_inputs, gated_conv_output
    state, conv, _, _, fresh, live = _state_rows(kv, si, batch)
    v, c, w = gated_conv_inputs(p, h, dtype)
    conv, y = _short_conv(conv, si, batch, fresh, live, v, w,
                          activation=None)
    return with_lin(kv, state, conv), gated_conv_output(p, c, y, dtype)


def _lightning_mixer(p, h, kv, si: int, batch: RaggedBatch, model_cfg, pos,
                     valid_q, dtype):
    """One Lightning linear-attention layer over the state pool: ``S_t =
    lambda_h S_{t-1} + k_t v_t^T``, ``o_t = S_t^T q_t / sqrt(d)``, which
    IS the state-space recurrence of :func:`_mamba2_mixer` at ``dt = 1``,
    ``a = log lambda_h``, ``x = v``, ``B = k``, ``C = q / sqrt(d)``, ``D =
    0`` (``models/minicpm_sala.py``), through the same three forms of
    ``ops/kernels/ssd`` and the same rows and slots (:func:`_state_rows`);
    the pool has no convolution part for it. Padded positions take a zero
    step (``dt`` 0). Returns (kv, y [S, C, M])."""
    from ...models.minicpm_sala import (lightning_inputs,
                                        lightning_log_decay,
                                        lightning_output)
    from ...ops.kernels.ssd import mamba2_decode_update, mamba2_prefill
    state, conv, st, slots, fresh, live = _state_rows(kv, si, batch)
    S, C, _ = h.shape
    x, Bm, Cm = lightning_inputs(p, h, pos, model_cfg, dtype)
    H = model_cfg.lightning_heads
    dt = jnp.broadcast_to(valid_q[..., None].astype(jnp.float32), (S, C, H))
    a = lightning_log_decay(H)
    if C == 1:
        o, st = mamba2_decode_update(
            st, slots, x[:, 0], dt[:, 0], a, Bm[:, 0], Cm[:, 0],
            jnp.zeros((H,), jnp.float32), wipe=fresh & live)
        o = o[:, None]
    else:
        St0 = st[slots]                                   # [S, H, dv, dk]
        o, Sn = mamba2_prefill(
            x, dt, a, Bm, Cm,
            jnp.where(fresh[:, None, None, None], 0.0, St0),
            chunk=model_cfg.lightning_chunk)
        st = st.at[slots].set(
            jnp.where(live[:, None, None, None], Sn, St0))
    return _with_layer_state(kv, state, conv, si, st), \
        lightning_output(p, o, h, model_cfg, dtype)


def _mla_mixer(p, h, kv, plane: int, batch: RaggedBatch, model_cfg, cfg,
               pos, valid_q, dtype):
    """One latent-attention (MLA) layer over plane ``plane`` of the
    one-plane cache (``plane`` counts the latent layers alone in a model
    that also has recurrent ones), in the ABSORBED form: the cache keeps
    ``[c_kv ; k_r]`` a token, ``W_UK`` (the key half of ``kv_b_proj``) is
    multiplied into the query, ``q_lat = q_nope W_UK^T``, and ``W_UV`` (its
    value half) into the output, so no cached row is ever expanded to
    per-head keys and values: scores ``q_lat . c_kv + q_rope . k_r``,
    ``o_lat = sum p c_kv``, ``o = o_lat W_UV``. Decode steps and prefill
    chunks alike. A family's config says whether the query is low rank
    (``q_lora_rank``) and whether the shared lanes carry rotary positions
    (``use_rope``). Returns (kv, y)."""
    S, C, _ = h.shape
    H, r = model_cfg.num_heads, model_cfg.kv_lora_rank
    dn, dr, dv = (model_cfg.qk_nope_head_dim, model_cfg.qk_rope_head_dim,
                  model_cfg.v_head_dim)
    eps, W = model_cfg.rms_eps, model_cfg.latent_row
    if model_cfg.q_lora_rank:
        cq = _rms(woq_mm(h, p["q_a_proj"]["kernel"], dtype),
                  p["q_a_norm"]["scale"], eps).astype(dtype)
        q = woq_mm(cq, p["q_b_proj"]["kernel"], dtype)
    else:                           # a full-rank query, no query norm
        q = woq_mm(h, p["q_proj"]["kernel"], dtype)
    q = q.reshape(S, C, H, dn + dr)
    ckv = woq_mm(h, p["kv_a_proj"]["kernel"], dtype)       # [S, C, r + dr]
    c = _rms(ckv[..., :r], p["kv_a_norm"]["scale"], eps).astype(dtype)
    k_r, q_r = ckv[..., r:], q[..., dn:]
    if getattr(model_cfg, "use_rope", True):
        # else the shared lanes carry no position (kimi_linear: NoPE)
        k_r = apply_rope(k_r[..., None, :], pos,
                         model_cfg.rope_theta)[:, :, 0]
        q_r = apply_rope(q_r, pos, model_cfg.rope_theta)
    w_kvb = p["kv_b_proj"]["kernel"].astype(dtype).reshape(r, H, dn + dv)
    q_lat = jnp.einsum("schd,rhd->schr", q[..., :dn], w_kvb[..., :dn])
    # the stored row and the query against it, whole 128-lane groups
    pad = W - r - dr
    row = jnp.concatenate(
        [c, k_r] + [jnp.zeros((S, C, pad), dtype)] * (pad > 0), axis=-1)
    qa = jnp.concatenate(
        [q_lat, q_r] + [jnp.zeros((S, C, H, pad), dtype)] * (pad > 0),
        axis=-1)
    with region("mla_core"):
        kv, o_lat = latent_attention(kv, plane, qa, row, batch, cfg, pos,
                                     valid_q, (dn + dr) ** -0.5, dtype, r)
    o = jnp.einsum("schr,rhd->schd", o_lat, w_kvb[..., dn:])
    return kv, woq_mm(o.reshape(S, C, H * dv), p["o_proj"]["kernel"], dtype)


def _attn_mixer(pa, h, kv, plane: int, batch: RaggedBatch, model_cfg, cfg,
                pos, valid_q, dtype, kind: str = "attn", ring_layer=None,
                index_layer=None):
    """One softmax-attention layer over plane ``plane`` of the paged
    cache: projections (qkv bias, QK-norm over the whole projection or a
    head by ``qk_norm``'s value), rotary positions unless the family has
    none (``use_rope`` false: no position code at all), paged attention,
    the family's elementwise sigmoid output gate if it has one, the
    output projection. What the layer's ``kind`` says of it: a ``"swa"``
    layer of a model that lists such layers attends inside the model's
    ``sliding_window``, over plane ``plane`` of the WINDOW pool, in its
    own region ``attn_window``; and a model that gives its kinds their
    own rotary code (``rope_of``) has the code as a table here; a
    ``"sparse"`` layer reads the blocks its selection names
    (``sparse_paged_attention``: store, selection, attention over the
    selected blocks), its compressed keys in plane ``index_layer``.
    Returns (kv, y)."""
    S, C, _ = h.shape
    H, KV, D = model_cfg.num_heads, model_cfg.num_kv_heads, model_cfg.head_dim
    q = woq_mm(h, pa["q_proj"]["kernel"], dtype)
    k = woq_mm(h, pa["k_proj"]["kernel"], dtype)
    v = woq_mm(h, pa["v_proj"]["kernel"], dtype)
    if model_cfg.qkv_bias:
        q = q + pa["q_proj"]["bias"].astype(dtype)
        k = k + pa["k_proj"]["bias"].astype(dtype)
        v = v + pa["v_proj"]["bias"].astype(dtype)
    per_head = model_cfg.qk_norm == "head"
    if model_cfg.qk_norm and not per_head:
        q = _rms(q, pa["q_norm"]["scale"], model_cfg.rms_eps).astype(dtype)
        k = _rms(k, pa["k_norm"]["scale"], model_cfg.rms_eps).astype(dtype)
    q = q.reshape(S, C, H, D)
    k = k.reshape(S, C, KV, D)
    v = v.reshape(S, C, KV, D)
    if per_head:
        q = _rms(q, pa["q_norm"]["scale"], model_cfg.rms_eps).astype(dtype)
        k = _rms(k, pa["k_norm"]["scale"], model_cfg.rms_eps).astype(dtype)
    if getattr(model_cfg, "use_rope", True):
        code = model_cfg.rope_of(kind) if hasattr(model_cfg, "rope_of") \
            else ()
        q = apply_rope(q, pos, model_cfg.rope_theta, *code)
        k = apply_rope(k, pos, model_cfg.rope_theta, *code)

    # a model WITHOUT "swa" layers may still have one window for every
    # layer (``LlamaConfig.sliding_window``, Mistral's), kept whole in the
    # paged pool; one WITH them has it on those layers alone
    if kind == "sparse":
        kv, y = sparse_paged_attention(
            kv, plane, index_layer, q, k, v, batch, cfg, pos, valid_q,
            1.0 / (D ** 0.5), dtype, model_cfg.sparse)
        return kv, _gated_out(pa, h, y, model_cfg, cfg, dtype)
    windowed = kind == "swa"
    listed = "swa" in (getattr(model_cfg, "layer_kinds", None) or ())
    window = model_cfg.sliding_window if windowed or not listed else None
    with region("attn_window" if windowed else "attn_core"):
        kv, y = paged_attention(
            kv, plane, q, k, v, batch, cfg, pos, valid_q, 1.0 / (D ** 0.5),
            dtype, sliding_window=window, window_pool=windowed,
            ring_layer=ring_layer)
    return kv, _gated_out(pa, h, y, model_cfg, cfg, dtype)


def _gated_out(pa, h, y, model_cfg, cfg, dtype):
    """The family's elementwise sigmoid output gate if it has one, then
    the output projection."""
    if getattr(model_cfg, "attn_gate", False):
        gate = woq_mm(h, pa["g_proj"]["kernel"], dtype)
        y = (y.astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype)
    y = woq_mm(y, pa["o_proj"]["kernel"], dtype)
    return tp_all_reduce(y, cfg)            # TP collective 1 (row-parallel)


def _llama_ragged_step(params, kv, batch: RaggedBatch, *,
                       model_cfg: LlamaConfig, cfg: RaggedInferenceConfig,
                       dtype):
    S, C = batch.tokens.shape
    is_moe = isinstance(model_cfg, MixtralConfig)

    # device time is read by region (telemetry/trace.py): each ``with``
    # names what is traced under it; a mixer's call site names its
    # projections and the mixer opens the attention call's own inside
    with region("embed"):
        pos = batch.start_pos[:, None] \
            + jnp.arange(C, dtype=jnp.int32)[None, :]
        valid_q = jnp.arange(C, dtype=jnp.int32)[None, :] \
            < batch.n_tokens[:, None]

        # the residual stream's dtype: the compute dtype, unless the family
        # asks for more (solar_open2: float32; its norms then read an
        # unrounded stream, and only matmul operands are rounded to
        # ``dtype``)
        rdtype = getattr(model_cfg, "residual_dtype", None) or dtype
        x = params["embed"]["embedding"][batch.tokens].astype(rdtype)
        # the muP factors of a family that has them: on the embedding, on
        # every branch before the residual add, under the head
        if hasattr(model_cfg, "embed_scale"):
            x = x * model_cfg.embed_scale
    branch = getattr(model_cfg, "residual_scale", None)

    # one step function for every family: the layer lists say which mixer
    # and which feed-forward a layer runs, or that it has none (a layer of
    # one branch has that branch's norm and one residual add); softmax and
    # latent layers take the cache's planes in order, recurrent layers
    # the state pool's and sliding-window layers the window pool's
    kinds = getattr(model_cfg, "layer_kinds", None) \
        or ("attn",) * model_cfg.num_layers
    ffn_kinds = getattr(model_cfg, "ffn_kinds", None) \
        or ("moe" if is_moe else "dense",) * len(kinds)
    # where a block's norms stand (``block_norms``): in front of each
    # branch ("pre", the default), on its OUTPUT too, before the residual
    # add ("sandwich"), or on the output alone ("post")
    norms = getattr(model_cfg, "block_norms", "pre")
    pre_norm, out_norm = norms != "post", norms != "pre"
    act = _mlp_act(model_cfg)
    plane = si = wplane = xi = 0
    for li, (kind, ffn) in enumerate(zip(kinds, ffn_kinds)):
        p = params[f"layer_{li}"]
        if kind is not None:
            with region("norm"):
                h = _rms(x, p["input_norm"]["scale"], model_cfg.rms_eps
                         ).astype(dtype) if pre_norm else x.astype(dtype)
            if kind == "kda":
                with region("linear_attn"):
                    kv, y = _kda_mixer(p["kda"], h, kv, si, batch,
                                       model_cfg, valid_q, dtype)
                si += 1
            elif kind == "gdn":
                with region("linear_attn"):
                    kv, y = _gdn_mixer(p["gdn"], h, kv, si, batch,
                                       model_cfg, valid_q, dtype)
                si += 1
            elif kind == "mamba2":
                with region("ssm"):
                    kv, y = _mamba2_mixer(p["mamba"], h, kv, si, batch,
                                          model_cfg, valid_q, dtype)
                si += 1
            elif kind == "mamba1":
                with region("ssm"):
                    kv, y = _mamba1_mixer(p["mamba"], h, kv, si, batch,
                                          model_cfg, valid_q, dtype)
                si += 1
            elif kind == "conv":
                with region("conv_mixer"):
                    kv, y = _gated_conv_mixer(p["conv"], h, kv, si, batch,
                                              dtype)
                si += 1
            elif kind == "lightning":
                with region("linear_attn"):
                    kv, y = _lightning_mixer(p["lin"], h, kv, si, batch,
                                             model_cfg, pos, valid_q, dtype)
                si += 1
            elif kind == "sparse":
                with region("attn_proj"):
                    kv, y = _attn_mixer(
                        p["attn"], h, kv, plane, batch, model_cfg, cfg, pos,
                        valid_q, dtype, kind, index_layer=xi)
                plane, xi = plane + 1, xi + 1
            elif kind == "mla":
                with region("mla_proj"):
                    kv, y = _mla_mixer(p["attn"], h, kv, plane, batch,
                                       model_cfg, cfg, pos, valid_q, dtype)
                plane += 1
            else:
                # "attn" or "swa": one mixer, the kind says which pool's
                # plane; in a model that lists "swa" layers the fused
                # loop's ring holds both kinds' rows in the model's order
                swa = kind == "swa"
                with region("attn_proj"):
                    kv, y = _attn_mixer(
                        p["attn"], h, kv, wplane if swa else plane, batch,
                        model_cfg, cfg, pos, valid_q, dtype, kind,
                        ring_layer=plane + wplane if "swa" in kinds
                        else None)
                wplane, plane = wplane + swa, plane + (not swa)
            if out_norm:
                with region("norm"):
                    y = _rms(y, p["attn_branch_norm"]["scale"],
                             model_cfg.rms_eps)
            with region("residual"):
                if branch is not None:
                    y = y.astype(rdtype) * branch
                x = x + y.astype(rdtype)
        if ffn is None:
            continue

        with region("norm"):
            h = _rms(x, p["post_attn_norm"]["scale"], model_cfg.rms_eps
                     ).astype(dtype) if pre_norm else x.astype(dtype)
        if ffn == "moe":
            # the fused decode loop's kv carries a count of routed rows
            counted = getattr(kv, "moe_rows", None) is not None
            y, rows = _moe_mlp(p["moe"], h, model_cfg, dtype, cfg,
                               valid=valid_q if counted else None)
            if rows is not None:
                with region("loop_carry"):
                    kv = kv._replace(moe_rows=kv.moe_rows + rows)
            if getattr(model_cfg, "shared_expert_size", 0):
                # always-on shared expert, of the routed experts' form
                # (SwiGLU, or ungated where they are): behind a sigmoid
                # scalar gate (qwen2-moe) or not (the others)
                with region("moe_shared"):
                    if getattr(model_cfg, "gated_experts", True):
                        gate = woq_mm(h, p["shared_gate_proj"]["kernel"],
                                      dtype)
                        up = woq_mm(h, p["shared_up_proj"]["kernel"], dtype)
                        m = act(gate) * up
                    else:
                        m = act(woq_mm(h, p["shared_up_proj"]["kernel"],
                                       dtype))
                    shared = woq_mm(m, p["shared_down_proj"]["kernel"],
                                    dtype)
                    if getattr(model_cfg, "shared_expert_gated", True):
                        sg = jax.nn.sigmoid(
                            (h @ p["shared_expert_gate"]["kernel"].astype(
                                dtype)).astype(jnp.float32))
                        shared = shared * sg.astype(dtype)
                    y = y + shared
        else:
            pm = p["mlp"]
            with region("ffn_dense"):
                gate = woq_mm(h, pm["gate_proj"]["kernel"], dtype)
                up = woq_mm(h, pm["up_proj"]["kernel"], dtype)
                m = jax.nn.silu(gate) * up
                m = woq_mm(m, pm["down_proj"]["kernel"], dtype)
                y = tp_all_reduce(m, cfg)                 # TP collective 2
        if out_norm:
            with region("norm"):
                y = _rms(y, p["mlp_branch_norm"]["scale"], model_cfg.rms_eps)
        with region("residual"):
            if branch is not None:
                y = y.astype(rdtype) * branch
            x = x + y.astype(rdtype)

    from ...ops.kernels.fp6_gemm import Fp6GemmWeight
    with region("head"):
        x = _rms(x, params["final_norm"]["scale"], model_cfg.rms_eps)
        if hasattr(model_cfg, "logit_divisor"):
            x = x / model_cfg.logit_divisor
        last = jnp.maximum(batch.n_tokens - 1, 0)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        if model_cfg.tie_embeddings:
            # embedding tables are never fused-packed (the quantizer's
            # structural exclusion — the token gather needs a dense array)
            w_out = params["embed"]["embedding"].T
        else:
            w_out = params["lm_head"]["kernel"]
            if isinstance(w_out, Fp6GemmWeight):
                return woq_mm(x_last.astype(jnp.float32), w_out,
                              jnp.float32), kv
        logits = x_last.astype(jnp.float32) @ w_out.astype(jnp.float32)
    return logits, kv


LlamaRaggedRunner.step_fn = staticmethod(_llama_ragged_step)
