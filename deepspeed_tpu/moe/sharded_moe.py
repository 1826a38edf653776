"""MoE gating + dispatch math.

Analogue of the reference's ``deepspeed/moe/sharded_moe.py`` (``top1gating:183``,
``top2gating:290``, ``topkgating:374``, ``_capacity:161``, gumbel RTS ``:79``,
einsum-mask dispatch ``MOELayer:533``), re-expressed as pure JAX on static
shapes: capacity-bounded one-hot dispatch/combine tensors computed with
cumsum positions — the GShard formulation, which XLA maps onto the MXU.

All functions return ``(l_aux, combine_weights [S,E,C], dispatch_mask [S,E,C])``
for a flat token group ``[S, M]`` — the layer handles batching and the
expert-parallel all-to-all.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..telemetry.trace import region


def capacity(num_tokens: int, num_experts: int, capacity_factor: float,
             min_capacity: int) -> int:
    """Tokens each expert can accept (reference _capacity:161)."""
    cap = int(math.ceil(num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def _gumbel(rng, shape):
    return -jnp.log(-jnp.log(jax.random.uniform(rng, shape, minval=1e-9, maxval=1.0 - 1e-9)))


def _one_hot(x, n):
    return jax.nn.one_hot(jnp.asarray(x, jnp.int32), n, dtype=jnp.float32)


def _positions_in_expert(mask: jnp.ndarray) -> jnp.ndarray:
    """Queue position of each routed token within its expert.
    mask [S, E] one-hot; returns [S] int positions."""
    positions = jnp.cumsum(mask, axis=0) - 1.0
    return (positions * mask).sum(axis=-1)


def top1gating(logits: jnp.ndarray, capacity_factor: float = 1.0,
               min_capacity: int = 4, rng: Optional[jax.Array] = None,
               noisy_gate_policy: Optional[str] = None,
               used_token_mask: Optional[jnp.ndarray] = None,
               drop_tokens: bool = True,
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Switch-style top-1 gating with capacity drop + RTS
    (reference top1gating:183). logits [S, E]."""
    S, E = logits.shape
    C = capacity(S, E, capacity_factor, min_capacity) if drop_tokens else S

    gates = jax.nn.softmax(logits, axis=-1)
    select_logits = logits
    if noisy_gate_policy == "RSample" and rng is not None:
        select_logits = logits + _gumbel(rng, logits.shape)
    elif noisy_gate_policy == "Jitter" and rng is not None:
        select_logits = logits * jax.random.uniform(
            rng, logits.shape, minval=0.98, maxval=1.02)

    idx = jnp.argmax(select_logits, axis=-1)                   # [S]
    mask1 = _one_hot(idx, E)                                   # [S, E]
    if used_token_mask is not None:
        mask1 = mask1 * used_token_mask[:, None]

    # load-balancing aux loss (before capacity drop, reference semantics)
    me = gates.mean(axis=0)
    ce = mask1.mean(axis=0)
    l_aux = (me * ce).sum() * E

    pos = _positions_in_expert(mask1)                          # [S]
    keep = (pos < C).astype(jnp.float32)
    mask1 = mask1 * keep[:, None]

    gate_val = (gates * mask1).sum(axis=-1)                    # [S]
    combine = (gate_val[:, None, None] * mask1[:, :, None]
               * _one_hot(pos, C)[:, None, :])                 # [S, E, C]
    dispatch = combine > 0
    return l_aux, combine, dispatch


def top2gating(logits: jnp.ndarray, capacity_factor: float = 1.0,
               min_capacity: int = 4, rng: Optional[jax.Array] = None,
               top2_2nd_expert_sampling: bool = True,
               drop_tokens: bool = True,
               normalize_weights: bool = True,
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """GShard top-2 gating (reference top2gating:290). logits [S, E]."""
    S, E = logits.shape
    C = capacity(S, E, 2 * capacity_factor, min_capacity) if drop_tokens else S

    gates = jax.nn.softmax(logits, axis=-1)
    idx1 = jnp.argmax(logits, axis=-1)
    mask1 = _one_hot(idx1, E)

    second_logits = logits
    if top2_2nd_expert_sampling and rng is not None:
        second_logits = logits + _gumbel(rng, logits.shape)
    second_logits = jnp.where(mask1 > 0, -jnp.inf, second_logits)
    idx2 = jnp.argmax(second_logits, axis=-1)
    mask2 = _one_hot(idx2, E)

    me = gates.mean(axis=0)
    ce = mask1.mean(axis=0)
    l_aux = (me * ce).sum() * E

    pos1 = _positions_in_expert(mask1)
    # second-choice tokens queue behind all first choices for that expert
    offset = mask1.sum(axis=0, keepdims=True)                  # [1, E]
    pos2_grid = jnp.cumsum(mask2, axis=0) - 1.0 + offset
    pos2 = (pos2_grid * mask2).sum(axis=-1)

    mask1 = mask1 * (pos1 < C).astype(jnp.float32)[:, None]
    mask2 = mask2 * (pos2 < C).astype(jnp.float32)[:, None]

    g1 = (gates * mask1).sum(axis=-1)
    g2 = (gates * mask2).sum(axis=-1)
    if normalize_weights:   # norm_topk_prob=False keeps full-softmax weights
        denom = jnp.maximum(g1 + g2, 1e-9)
        g1, g2 = g1 / denom, g2 / denom

    combine = (g1[:, None, None] * mask1[:, :, None] * _one_hot(pos1, C)[:, None, :]
               + g2[:, None, None] * mask2[:, :, None] * _one_hot(pos2, C)[:, None, :])
    dispatch = combine > 0
    return l_aux, combine, dispatch


def topkgating(logits: jnp.ndarray, k: int, capacity_factor: float = 1.0,
               min_capacity: int = 4, drop_tokens: bool = True,
               normalize_weights: bool = True,
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Generic top-k gating (reference topkgating:374). logits [S, E]."""
    S, E = logits.shape
    C = capacity(S, E, k * capacity_factor, min_capacity) if drop_tokens else S
    gates = jax.nn.softmax(logits, axis=-1)

    masked = logits
    combine = jnp.zeros((S, E, C), jnp.float32)
    total_mask = jnp.zeros((S, E), jnp.float32)
    offset = jnp.zeros((1, E), jnp.float32)
    gsum = jnp.zeros((S,), jnp.float32)
    picks = []
    for _ in range(k):                                 # k is small + static
        idx = jnp.argmax(masked, axis=-1)
        mask = _one_hot(idx, E)
        pos_grid = jnp.cumsum(mask, axis=0) - 1.0 + offset
        pos = (pos_grid * mask).sum(axis=-1)
        mask_kept = mask * (pos < C).astype(jnp.float32)[:, None]
        g = (gates * mask_kept).sum(axis=-1)
        picks.append((mask_kept, pos, g))
        gsum = gsum + g
        total_mask = total_mask + mask
        offset = offset + mask.sum(axis=0, keepdims=True)
        masked = jnp.where(mask > 0, -jnp.inf, masked)

    me = gates.mean(axis=0)
    ce = (total_mask / k).mean(axis=0)
    l_aux = (me * ce).sum() * E

    denom = jnp.maximum(gsum, 1e-9) if normalize_weights else 1.0
    for mask_kept, pos, g in picks:
        w = g / denom if normalize_weights else g
        combine = combine + (w[:, None, None] * mask_kept[:, :, None]
                             * _one_hot(pos, C)[:, None, :])
    dispatch = combine > 0
    return l_aux, combine, dispatch


def gate(logits: jnp.ndarray, k: int = 1, **kwargs):
    """Dispatch to the right gating fn by k (TopKGate.forward analogue)."""
    if k == 1:
        kwargs.pop("top2_2nd_expert_sampling", None)
        kwargs.pop("normalize_weights", None)   # top-1 weight IS the softmax prob
        return top1gating(logits, **kwargs)
    if k == 2:
        kwargs.pop("noisy_gate_policy", None)
        kwargs.pop("used_token_mask", None)
        return top2gating(logits, **kwargs)
    kwargs.pop("noisy_gate_policy", None)
    kwargs.pop("used_token_mask", None)
    kwargs.pop("rng", None)
    kwargs.pop("top2_2nd_expert_sampling", None)
    return topkgating(logits, k, **kwargs)


def _chosen_by_compare(gates: jnp.ndarray, top_idx: jnp.ndarray):
    """``take_along_axis(gates, top_idx, -1)`` without a gather: a sum that
    picks ONE value and zeros, so exact; its transpose is a masked
    broadcast where the gather's is a scatter-add. XLA runs a scalar gather
    of 131,072 elements at ~8 ns an element on a v5e (1.04 ms). The
    experts stand on the second-minor axis, so the sum over them adds
    whole registers (over the lanes it is 0.3 ms slower a pass)."""
    experts = jnp.arange(gates.shape[-1], dtype=top_idx.dtype)
    hit = top_idx.T[:, None, :] == experts[None, :, None]    # [k, E, S]
    return jnp.sum(jnp.where(hit, gates.T[None], 0), axis=1).T


def _rows_chosen(top_idx: jnp.ndarray, E: int) -> jnp.ndarray:
    """``bincount(top_idx.reshape(-1), length=E)`` as a sum over a one-hot
    compare ([E] int32): the scatter-add of 131,072 ones into 128 bins is
    1.14 ms on a v5e, one serial update each."""
    hit = top_idx[:, :, None] == jnp.arange(E, dtype=top_idx.dtype)
    return jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)


def route_topk(logits: jnp.ndarray, k: int, *, score: str = "softmax",
               bias=None, normalize: bool = True, scale: float = 1.0,
               norm_eps: float = 1e-20, chosen=None):
    """The router's choice for every row: (top_idx [S, k] int32,
    weights [S, k] float32, scores [S, E] float32).

    ``score="softmax"`` (mixtral, qwen2-moe, olmoe): the k largest
    logits; their weights are the softmax over the chosen logits
    (``normalize``) or their share of the softmax over all experts.
    ``score="sigmoid"`` (the DeepSeek-V3 convention solar_open2's keys
    follow): scores ``sigmoid(logits)``; the k largest of ``score +
    bias`` are chosen (the bias takes part in the SELECTION only), and
    their weights are the scores themselves, renormalised over the chosen
    (``normalize``: ``w / (sum w + norm_eps)``; lfm2's ``1e-6`` is its
    family's, the default the others') and multiplied by ``scale``.

    ``chosen(gates, top_idx)``: how the chosen experts' scores are read;
    None is ``take_along_axis``, what every serve step's program holds."""
    if chosen is None:
        chosen = functools.partial(jnp.take_along_axis, axis=-1)
    lf = logits.astype(jnp.float32)
    if score == "softmax":
        gates = jax.nn.softmax(lf, axis=-1)
        top_vals, top_idx = jax.lax.top_k(lf, k)
        if normalize:
            # renormalize over the selected experts (HF norm_topk_prob /
            # the top2gating g/(g1+g2)); at k == 1 this is a constant 1.0
            # — exactly HF's renormalized top-1. Training top-1 wants the
            # raw softmax prob instead (top1gating semantics, and the
            # router's gradient path): the MoE layer passes
            # normalize_weights=False for k == 1.
            w_sel = jax.nn.softmax(top_vals, axis=-1)      # [S, k]
        else:
            w_sel = chosen(gates, top_idx)
        return top_idx, w_sel, gates
    if score != "sigmoid":
        raise ValueError(f"router score must be 'softmax' or 'sigmoid', "
                         f"got {score!r}")
    gates = jax.nn.sigmoid(lf)
    _, top_idx = jax.lax.top_k(
        gates if bias is None else gates + bias.astype(jnp.float32), k)
    w_sel = chosen(gates, top_idx)
    if normalize:
        w_sel = w_sel / (jnp.sum(w_sel, axis=-1, keepdims=True) + norm_eps)
    if scale != 1.0:
        w_sel = w_sel * scale
    return top_idx, w_sel, gates


@jax.custom_vjp
def _keep_cotangent_rows(x: jnp.ndarray, keep: jnp.ndarray) -> jnp.ndarray:
    """``x`` itself, and no operation of the forward program; its
    cotangent passes for the rows ``keep`` marks and is zero elsewhere. A
    grouped matmul leaves the rows past its groups as it found the memory
    (zeros on the CPU, whatever was there on the TPU), in the backward's
    products as in the forward's: the gathered rows take this on their
    way in, so that what the backward leaves in a row no group holds never
    reaches the tokens' gradient."""
    return x


_keep_cotangent_rows.defvjp(
    lambda x, keep: (x, keep),
    lambda keep, ct: (jnp.where(keep[:, None], ct, 0), None))


def held_row_bound(S: int, k: int, E: int, held) -> int:
    """The rows of the sorted order :func:`grouped_moe_ffn`'s ``ragged_dot``
    path visits for a held share: twice the rows an even router sends to
    ``held = (first, count)`` of ``E`` experts, in whole 512-row tiles, and
    never more than the ``S * k`` routed rows (``held`` None or all ``E``:
    every row). A bound from shapes alone, so the program is static; the
    step whose held rows exceed it takes all ``S * k`` (a ``cond`` on the
    device's own count), and ``models/afmoe.py::step_counters`` counts
    those steps."""
    rows = S * k
    if held is None or tuple(held) == (0, E):
        return rows
    expected = -(-rows * held[1] // E)
    return min(rows, -(-2 * expected // 512) * 512)


def combine_impl(S: int, n: int, M: int, rows, dtype) -> Optional[str]:
    """How the ``ragged_dot`` path moves its rows: "pallas" on a TPU
    backend where ``ops/kernels/moe_combine.py`` takes every row count of
    ``rows`` (a call's bound and its ``S * k``), None (XLA's gather and
    ``.at[].add``, the kernel's oracle) otherwise and anywhere else: the
    kernel interpreted would cost the CPU minutes."""
    from ..ops.kernels import moe_combine
    if jax.default_backend() != "tpu":
        return None
    return "pallas" if all(moe_combine.fits(S, n, M, r, dtype)
                           for r in rows) else None


def _in_order(x, index):
    """``x[index]`` along the first axis for an ``index`` out of a sort
    (every entry in bounds): ``take``'s own bounds check is a second pass
    over the gathered rows (0.41 ms over [32,768, 2,048] on a v5e)."""
    return x.at[index].get(mode="promise_in_bounds")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _rows_by_expert(impl, dtype, tokens_dtype, tokens, tok_of, chose, row,
                    sizes):
    """The tokens' rows in the sorted order, ``tokens[tok_of]``; its
    transposition is the combine kernel at unit weights (``chose`` [S, n]:
    token ``t`` chose expert ``e``) where XLA's is a scatter-add of as many
    rows. The kernel reads a row in no group as zeros (what
    :func:`_keep_cotangent_rows` is for elsewhere)."""
    return _in_order(tokens, tok_of).astype(dtype)


def _rows_by_expert_bwd(impl, dtype, tokens_dtype, res, ct):
    from ..ops.kernels.moe_combine import moe_combine
    chose, row, sizes = res
    return (moe_combine(ct, chose.astype(jnp.float32), row, sizes,
                        tokens_dtype, interpret=impl == "interpret"),
            None, None, None, None)


_rows_by_expert.defvjp(
    lambda impl, dtype, tokens_dtype, tokens, tok_of, chose, row, sizes: (
        _rows_by_expert(impl, dtype, tokens_dtype, tokens, tok_of, chose,
                        row, sizes), (chose, row, sizes)),
    _rows_by_expert_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _rows_to_tokens(impl, dtype, ys, weight, w_sel, first, chose, row,
                    sizes):
    """The experts' rows weighted (``weight`` [S, n], token-major) and
    added into their tokens [S, M] through the combine kernel: float32
    sums in expert order, rounded once. Backward: the rows' cotangent is
    XLA's row gather times the rows' own weights (``w_sel`` [S, k] read at
    ``first``: it takes no cotangent here, ``weight`` does), as the
    scatter-add's; a weight's is its row's product with the token's
    cotangent, brought token-major by the kernel again (one lane an
    expert) where a scatter of as many scalars would take them one by
    one. The residuals are the scatter-add's (rows, weights, order)."""
    from ..ops.kernels.moe_combine import moe_combine
    return moe_combine(ys, weight, row, sizes, dtype,
                       interpret=impl == "interpret")


def _rows_to_tokens_bwd(impl, dtype, res, ct):
    from ..ops.kernels.moe_combine import moe_combine
    ys, w_sel, first, chose, row, sizes = res
    n, rows = sizes.shape[0], ys.shape[0]
    at = jnp.arange(rows)
    got = _in_order(ct, first // w_sel.shape[1]).astype(ys.dtype)
    ws = _in_order(w_sel.reshape(-1), first).astype(ys.dtype)
    dys = jnp.where((at < sizes.sum())[:, None], got * ws[:, None], 0)
    dws = jnp.sum(ys.astype(jnp.float32) * got.astype(jnp.float32), axis=-1)
    # a row's expert by compare with the groups' ends; one past the held
    # ones (no lane) for a row in no group
    e_of = jnp.sum(at[:, None] >= jnp.cumsum(sizes)[None, :], axis=1)
    lanes = jnp.arange(-(-n // 128) * 128)
    by_lane = jnp.where((e_of[:, None] == lanes[None, :])
                        & (lanes[None, :] < n), dws[:, None], 0)
    dweight = moe_combine(by_lane, chose.astype(jnp.float32), row, sizes,
                          jnp.float32, interpret=impl == "interpret")[:, :n]
    return dys, dweight, None, None, None, None, None


_rows_to_tokens.defvjp(
    lambda impl, dtype, ys, weight, w_sel, first, chose, row, sizes: (
        _rows_to_tokens(impl, dtype, ys, weight, w_sel, first, chose, row,
                        sizes), (ys, w_sel, first, chose, row, sizes)),
    _rows_to_tokens_bwd)


def grouped_moe_ffn(tokens: jnp.ndarray, logits: jnp.ndarray, k: int,
                    weights, activation, dtype,
                    normalize_weights: bool = True, *,
                    score: str = "softmax", select_bias=None,
                    weight_scale: float = 1.0, held=None,
                    impl: Optional[str] = None, norm_eps: float = 1e-20,
                    return_counts: bool = False):
    """Dropless top-k MoE via grouped expert matmuls (``jax.lax.ragged_dot``).

    TPU-native answer to the reference's CUTLASS grouped GEMM
    (``inference/v2/kernels/cutlass_ops/moe_gemm/``) and the
    megablocks-style dropless dispatch: tokens sort by their routed expert,
    each expert multiplies ONLY its contiguous run of rows, and the outputs
    add back into their tokens weighted by the router. Computes S*k expert
    rows instead of the capacity path's S*E (or the serving dense path's
    every-expert-on-every-token) — E/k x fewer FLOPs — with no capacity
    drop and no [S, E, C] one-hot tensors.

    tokens [S, M]; logits [S, E]; weights = (wi, wo) or gated
    (wi_gate, wi_up, wo) stacked [E, ...]. normalize_weights=True
    renormalizes over the selected experts (mixtral); False keeps
    full-softmax weights (qwen2-moe). Returns (out [S, M], l_aux).

    ``score`` / ``select_bias`` / ``weight_scale`` / ``norm_eps``: the
    router's form (:func:`route_topk`). ``held = (first, count)``: the stacked weights
    hold experts ``first .. first + count`` of the ``E`` the router scores
    (one chip's share of a layer divided over chips). Routing runs over
    all ``E``; rows whose expert is elsewhere sort behind the last held
    group and most are never visited: the sorted order is cut to
    :func:`held_row_bound` rows, twice the share's even part, and the few
    of them past the held rows lie in no group of the grouped matmuls
    (which leave rows past their groups alone) and add nothing to the
    output. A step whose held rows exceed the bound (the count is on the
    device) takes every row through the same body under a ``cond``: no row
    is dropped for any routing. Both bodies add a token's held rows in the
    order they were, so the cut changes no sum.

    How the rows move (``impl=None``). The sort is STABLE over the flat
    slot index ``t * k + j`` and top-k picks distinct experts, so inside
    one expert's group the rows are in token order and a token sends an
    expert at most one row: the rows a tile of consecutive tokens sends
    one expert are ONE contiguous run of the sorted order, and where a
    token's row lies is its expert's group start plus the tokens before it
    that chose the expert, a cumulative sum over a one-hot compare. On
    that rests ``ops/kernels/moe_combine.py``, which a TPU backend takes
    where the shapes fit (:func:`combine_impl`): the rows rejoin their
    tokens through a kernel that walks the runs, in float32, rounded once;
    the same kernel at unit weights is the transposition of the row
    gather, and once more (a lane an expert) it brings the weights'
    cotangent token-major. No scatter of routed-row size is left (XLA's
    walks its rows one by one on a TPU: 2.93 ms for 32,768 rows of 2,048
    where the kernel takes 0.58). Everywhere else the weighted rows are
    added back with ``.at[].add`` (a token's held rows in the order they
    were, in the compute type): the CPU's path, and the kernel's oracle in
    the tests. On every backend the groups' sizes are a sum over a one-hot
    compare and the chosen scores a masked sum (``route_topk(chosen=)``):
    ``bincount`` and ``take_along_axis`` of 131,072 elements are 1.1 ms
    each on a v5e, bit for bit the same values.

    ``impl``: None is the path above, three ``ragged_dot`` calls over the
    rows visited (all S*k of a whole layer): what training takes (the
    gradient flows through it) and
    what serving takes off the TPU or over packed stacks. "pallas" (or
    "interpret", the same kernel interpreted) is the serving path: one
    grouped kernel of our own (``ops/kernels/grouped_ffn.py``) walks the
    held groups that have rows, reads each one's matrices once and does
    gate, up and down in one pass, at the row tile and the span cap the
    expected rows an expert ask for (a decode step's 2-4 at a 16-row
    tile, a refill step's 51 at a 64-row tile, both under a 128-row
    span; a refill step's 256, at the chip's ridge, at a 128-row tile
    under a 512-row span); it has no gradient and returns no aux loss.
    The caller chooses (``inference/v2/llama_runner._moe_mlp``, by
    ``grouped_ffn.kernel_impl``).

    ``return_counts``: also return the rows each of the ``E`` experts the
    router scores was chosen for ([E] int32, before the held cut): what a
    selection bias balanced without a loss moves by, and what a step's
    ``moe_rows_*`` counters are made of. The ``ragged_dot`` path only.
    """
    S, E = logits.shape
    top_idx, w_sel, gates = route_topk(
        logits, k, score=score, bias=select_bias,
        normalize=normalize_weights, scale=weight_scale, norm_eps=norm_eps,
        chosen=_chosen_by_compare if impl is None else None)

    eid = top_idx.reshape(-1)                              # [S*k]
    if return_counts and impl is not None:
        raise ValueError("return_counts is the ragged_dot path's "
                         "(impl=None)")
    here = None
    if held is not None and tuple(held) != (0, E):
        first, count = held
        here = (eid >= first) & (eid < first + count)
        eid = jnp.where(here, eid - first, count)          # elsewhere: last
        E = count
    if impl is not None:
        from ..ops.kernels.grouped_ffn import (layout_and_run, row_tile,
                                               span_cap)
        # row r of ys is token r // k's j-th choice: no sort to undo
        ys = layout_and_run(
            tokens, eid.astype(jnp.int32),
            tuple(w.astype(dtype) for w in weights), activation, dtype,
            tile=row_tile(S * k, logits.shape[1]),
            cap=span_cap(S * k, logits.shape[1]),
            interpret=impl == "interpret")
        out = jnp.sum(ys.reshape(S, k, -1).astype(jnp.float32)
                      * w_sel[..., None], axis=1).astype(dtype)
        return out, jnp.float32(0.0)
    order = jnp.argsort(eid, stable=True)
    # one count of the choices serves the caller and, as a slice, the held
    # groups' sizes
    counts = _rows_chosen(top_idx, logits.shape[1])
    group_sizes = counts if here is None \
        else counts[held[0]:held[0] + held[1]]
    held_rows = group_sizes.sum()
    bound = held_row_bound(S, k, logits.shape[1], held)
    kernel = combine_impl(S, E, tokens.shape[1], (bound, S * k), dtype)
    if kernel is not None:
        from ..ops.kernels import moe_combine
        # [S, k, E]: choice j of token t is held expert e; [S, E]: token t
        # chose it, and the row of the sorted order it sent it
        hit = top_idx[:, :, None] == (0 if here is None else held[0]) \
            + jnp.arange(E, dtype=top_idx.dtype)
        chose = jnp.any(hit, axis=1)
        row = moe_combine.rows_of(chose, group_sizes)

    def visit(rows, tokens, w_sel, weights):
        """The first ``rows`` rows of the sorted order through the experts
        and back into their tokens: all of them, or the bound that holds
        every row of a held group."""
        first = order if rows == S * k else order[:rows]
        tok_of = first // k                                # source token
        if kernel is not None:
            xs = _rows_by_expert(kernel, dtype, tokens.dtype, tokens, tok_of,
                                 chose, row, group_sizes)
        else:
            xs = _in_order(tokens, tok_of).astype(dtype)    # by expert
            if here is not None:
                # held rows sort first: a row past them lies in no group
                keep = jnp.arange(rows) < held_rows
                xs = _keep_cotangent_rows(xs, keep)
        with region("moe_experts"):
            if len(weights) == 3:
                wi_gate, wi_up, wo = weights
                g = jax.lax.ragged_dot(xs, wi_gate.astype(dtype), group_sizes)
                u = jax.lax.ragged_dot(xs, wi_up.astype(dtype), group_sizes)
                h = activation(g) * u
            else:
                wi, wo = weights
                h = activation(
                    jax.lax.ragged_dot(xs, wi.astype(dtype), group_sizes))
            ys = jax.lax.ragged_dot(h, wo.astype(dtype), group_sizes)
        if kernel is not None:
            # a sum that picks one value and zeros, so exact, and its
            # transpose a masked broadcast; rounded as the rows' type
            # holds a weight, like ``ws`` below
            weight = jnp.sum(jnp.where(
                hit, w_sel[:, :, None].astype(dtype), 0), axis=1)
            return _rows_to_tokens(kernel, dtype, ys, weight, w_sel, first,
                                   chose, row, group_sizes)
        ws = _in_order(w_sel.reshape(-1), first).astype(dtype)
        if here is not None:
            # a row past the groups carries whatever the matmul left there
            ys = jnp.where(keep[:, None], ys, 0)
        return jnp.zeros_like(tokens, dtype).at[tok_of].add(ys * ws[:, None])

    operands = (tokens, w_sel, tuple(weights))
    if bound == S * k:
        out = visit(S * k, *operands)
    else:
        # held rows sort first: ``bound`` rows hold them all unless the
        # router sent this share more than twice its even part, and the
        # step that does so takes every row as before. Each branch keeps
        # its operands alone for the backward: left to itself a ``cond``
        # writes the other branch's residuals as zeros, full-size, in
        # every step (Trinity's 8k step then no longer fits a v5e)
        out = jax.lax.cond(
            held_rows <= bound,
            jax.checkpoint(functools.partial(visit, bound)),
            jax.checkpoint(functools.partial(visit, S * k)), *operands)

    # load-balance loss — same statistic the capacity path this call
    # replaces would report: top1gating/top2gating use FIRST-choice counts
    # only (mask1.mean), topkgating averages all k choices. Matching per-k
    # keeps the router regularizer identical when the dropless path
    # auto-replaces the capacity path in MoE.__call__.
    if here is not None:
        # a share: no aux loss
        l_aux = jnp.float32(0.0)
        return (out, l_aux, counts) if return_counts else (out, l_aux)
    me = gates.mean(axis=0)
    if k <= 2:
        ce = _rows_chosen(top_idx[:, :1], E).astype(jnp.float32) / float(S)
    else:
        ce = group_sizes.astype(jnp.float32) / float(S * k)
    l_aux = (me * ce).sum() * E
    return (out, l_aux, counts) if return_counts else (out, l_aux)


def _grouped_aux_loss(gates: jnp.ndarray, top_idx: jnp.ndarray, k: int,
                      E: int) -> jnp.ndarray:
    """The grouped paths' shared l_aux statistic (per-k rule above)."""
    S = gates.shape[0]
    me = gates.mean(axis=0)
    if k <= 2:
        ce = jnp.bincount(top_idx[:, 0], length=E).astype(jnp.float32) / S
    else:
        ce = jnp.bincount(top_idx.reshape(-1),
                          length=E).astype(jnp.float32) / (S * k)
    return (me * ce).sum() * E


def grouped_moe_ffn_ep(tokens: jnp.ndarray, logits: jnp.ndarray, k: int,
                       weights_local, activation, dtype,
                       expert_axis: str, num_experts: int,
                       capacity_rows: int,
                       normalize_weights: bool = True,
                       tp_axis: Optional[str] = None,
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Grouped expert GEMM UNDER expert parallelism (runs inside shard_map
    with ``expert_axis`` manual).

    TPU-native composition of the reference's grouped MoE GEMM
    (``inference/v2/kernels/cutlass_ops/moe_gemm/``) with its expert
    all-to-all (``moe/sharded_moe.py:96`` _AllToAll, ``moe_scatter`` /
    ``moe_gather``): each rank sorts its S*k routed rows by OWNING RANK,
    packs them into fixed ``capacity_rows``-sized per-destination slots
    (static shapes — XLA needs them; rows beyond a slot drop, which at the
    default slack never fires for balanced routing), exchanges slots with
    one ``all_to_all``, runs the LOCAL ``jax.lax.ragged_dot`` grouped GEMM
    over the ~S*k received rows (vs the capacity path's [S, E, C] one-hot
    einsum memory), and returns results through the inverse all-to-all to
    scatter-add into their source tokens.

    ``tokens`` [S, M] local rows; ``logits`` [S, E] full-expert router
    logits; ``weights_local`` this rank's expert stack ([E/ep, ...]); with
    ``tp_axis`` the hidden dim is additionally model-sharded (column wi /
    row wo, one psum before the return a2a). Returns (out [S, M], l_aux
    local — caller pmeans over the mesh).
    """
    S, E = logits.shape
    e_loc = jax.tree_util.tree_leaves(weights_local)[0].shape[0]
    ep = E // e_loc
    Cs = int(capacity_rows)

    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_vals, top_idx = jax.lax.top_k(logits.astype(jnp.float32), k)
    if normalize_weights:
        w_sel = jax.nn.softmax(top_vals, axis=-1)
    else:
        w_sel = jnp.take_along_axis(gates, top_idx, axis=-1)

    eid = top_idx.reshape(-1)                       # [S*k] global expert id
    tok_of = jnp.arange(S * k, dtype=jnp.int32) // k
    # experts are block-assigned to ranks (owner = eid // e_loc), so a sort
    # by expert id is also a sort by destination rank
    order = jnp.argsort(eid, stable=True)
    eid_s = jnp.take(eid, order)
    tok_s = jnp.take(tok_of, order)
    w_s = jnp.take(w_sel.reshape(-1), order)
    dest_s = eid_s // e_loc

    counts = jnp.bincount(dest_s, length=ep)
    start = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                             jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(S * k, dtype=jnp.int32) - start[dest_s].astype(jnp.int32)
    keep = pos < Cs
    # OOB scatter indices DROP in jax — overflow rows vanish here
    slot = jnp.where(keep, dest_s * Cs + pos, ep * Cs)

    x_rows = jnp.take(tokens, tok_s, axis=0).astype(dtype)
    send_x = jnp.zeros((ep * Cs, tokens.shape[1]), dtype).at[slot].set(x_rows)
    # local-expert id at the receiver; e_loc marks an empty slot
    send_leid = jnp.full((ep * Cs,), e_loc, jnp.int32).at[slot].set(
        eid_s % e_loc)
    send_w = jnp.zeros((ep * Cs,), jnp.float32).at[slot].set(w_s)
    send_tok = jnp.full((ep * Cs,), S, jnp.int32).at[slot].set(tok_s)

    def a2a(v):
        return jax.lax.all_to_all(
            v.reshape((ep, Cs) + v.shape[1:]), expert_axis, 0, 0,
            tiled=False).reshape((ep * Cs,) + v.shape[1:])

    recv_x = a2a(send_x)
    recv_leid = a2a(send_leid)
    recv_w = a2a(send_w)

    # local grouped GEMM over received rows, sorted by local expert
    order2 = jnp.argsort(recv_leid, stable=True)     # empties sort last
    xs = jnp.take(recv_x, order2, axis=0)
    gs = jnp.bincount(recv_leid, length=e_loc).astype(jnp.int32)
    if len(weights_local) == 3:
        wi_gate, wi_up, wo = weights_local
        g = jax.lax.ragged_dot(xs, wi_gate.astype(dtype), gs)
        u = jax.lax.ragged_dot(xs, wi_up.astype(dtype), gs)
        h = activation(g) * u
    else:
        wi, wo = weights_local
        h = activation(jax.lax.ragged_dot(xs, wi.astype(dtype), gs))
    ys = jax.lax.ragged_dot(h, wo.astype(dtype), gs)
    if tp_axis is not None:
        # row-parallel wo: partial sums over the hidden shards — routed
        # through the shared comm facade so the DSTPU_TP_OVERLAP
        # decomposed schedule (ring RS+AG instead of one psum) covers the
        # grouped-GEMM training path too, and a stalled hop is
        # watchdog-named like any serve-side collective
        from .. import comm
        ys = comm.overlap_all_reduce(ys, axis_name=tp_axis,
                                     log_name="moe_grouped_wo")
    # rows past sum(gs) are unspecified — zero them before the return trip
    valid = jnp.arange(ep * Cs) < gs.sum()
    ys = jnp.where(valid[:, None], ys, jnp.zeros_like(ys))
    inv2 = jnp.argsort(order2, stable=True)
    ys = jnp.take(ys, inv2, axis=0)
    ys = ys * recv_w[:, None].astype(dtype)

    back = a2a(ys)                                    # my rows' results
    out = jnp.zeros_like(tokens, dtype).at[send_tok].add(back)

    return out, _grouped_aux_loss(gates, top_idx, k, E)


def ep_serve_capacity(n_tokens: int, k: int, ep: int,
                      capacity_factor: float, chunks: int = 1) -> int:
    """Per-destination slot rows for the SERVING expert dispatch.

    ``ceil(rows * factor / ep)`` capped at ``rows`` (a destination can
    never receive more than every routed row) and rounded up to a
    ``chunks`` multiple so the overlapped schedule slices evenly. With
    ``capacity_factor >= ep`` the cap binds — ``Cs == rows`` — and the
    dispatch is PROVABLY dropless under any routing skew, which is what
    keeps the ep=1 ≡ ep=2 parity oracle exact (the default factor 2.0
    makes ep=2 dropless; larger meshes trade slack for wire bytes).
    """
    rows = int(n_tokens) * int(k)
    cs = min(rows, int(math.ceil(rows * float(capacity_factor) / ep)))
    cs = max(cs, 1)
    if chunks > 1:
        cs = -(-cs // chunks) * chunks
    return cs


def grouped_moe_ffn_ep_serve(tokens: jnp.ndarray, logits: jnp.ndarray,
                             k: int, weights_local, activation, dtype,
                             expert_axis: str, num_experts: int,
                             capacity_rows: int,
                             normalize_weights: bool = True,
                             chunks: int = 1,
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel grouped MoE FFN for the SERVING programs: exactly
    TWO ``comm.all_to_all_single`` hops per call (dispatch + combine) on
    a REPLICATED batch.

    The serving programs replicate activations across the ``expert``
    ranks (the batch is one request stream, not data-sharded training
    shards), so ``tokens``/``logits`` are bit-identical on every rank.
    That changes the dispatch shape vs :func:`grouped_moe_ffn_ep`:

      * every rank packs the FULL routed row set ``[x | w | leid]`` into
        one f32 payload of per-destination ``capacity_rows`` slots — one
        operand, so the exchange is ONE all-to-all instead of the
        training path's three (f32 packing is exact: compute-dtype
        activations round-trip bf16→f32→bf16 bit-identically, local
        expert ids are small ints, and the router weights are f32 in the
        oracle path too);
      * after the dispatch all-to-all rank ``d`` holds ``ep`` identical
        copies of its slot block (every sender sent the same buffer); it
        runs the grouped GEMM ONCE on copy 0 and tiles the results into
        all ``ep`` return slots — no duplicated GEMM work, and the
        combine all-to-all hands every rank the same per-slot results;
      * each rank scatter-adds its own copy back through its (identical)
        slot→token map, so the output is replicated and bit-identical
        across ranks — the shard_map out_spec stays ``P()`` and no
        third collective is needed.

    With ``chunks > 1`` the slot dim is sliced into ``chunks`` pieces
    and the loop pipelines them — chunk k's GEMM runs under chunk k+1's
    all-to-all (the PR 6 decomposed-collective shape). Per-row GEMM
    results are independent of the grouping, chunk slices preserve slot
    order, and at ``k <= 2`` each token's two scatter-add contributions
    commute exactly, so ``chunks`` is numerics-invariant (the
    overlap=off parity oracle in tests/unit/test_moe_serving.py).

    ``capacity_rows`` comes from :func:`ep_serve_capacity`; rows past a
    destination's slots drop (OOB scatter indices — impossible when the
    factor makes the cap bind). Returns ``(out [S, M] replicated,
    l_aux)``.
    """
    from .. import comm
    S, E = logits.shape
    M = tokens.shape[1]
    e_loc = jax.tree_util.tree_leaves(weights_local)[0].shape[0]
    ep = E // e_loc
    Cs = int(capacity_rows)
    if Cs % chunks:
        raise ValueError(
            f"capacity_rows ({Cs}) must divide by chunks ({chunks}) — "
            f"ep_serve_capacity rounds this up")

    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_vals, top_idx = jax.lax.top_k(logits.astype(jnp.float32), k)
    if normalize_weights:
        w_sel = jax.nn.softmax(top_vals, axis=-1)
    else:
        w_sel = jnp.take_along_axis(gates, top_idx, axis=-1)

    eid = top_idx.reshape(-1)                      # [S*k] global expert id
    tok_of = jnp.arange(S * k, dtype=jnp.int32) // k
    order = jnp.argsort(eid, stable=True)          # dest-major (block owner)
    eid_s = jnp.take(eid, order)
    tok_s = jnp.take(tok_of, order)
    w_s = jnp.take(w_sel.reshape(-1), order)
    dest_s = eid_s // e_loc

    counts = jnp.bincount(dest_s, length=ep)
    start = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                             jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(S * k, dtype=jnp.int32) \
        - start[dest_s].astype(jnp.int32)
    slot = jnp.where(pos < Cs, dest_s * Cs + pos, ep * Cs)  # OOB drops

    # one packed f32 operand: [x | w | leid]; empty slots carry leid =
    # e_loc (sorts LAST at the receiver) and weight 0
    x_rows = jnp.take(tokens, tok_s, axis=0).astype(jnp.float32)
    payload = jnp.concatenate(
        [x_rows, w_s[:, None].astype(jnp.float32),
         (eid_s % e_loc)[:, None].astype(jnp.float32)], axis=1)
    send = jnp.zeros((ep * Cs + 1, M + 2), jnp.float32)
    send = send.at[:, M + 1].set(float(e_loc)).at[slot].set(payload)
    send = send[:ep * Cs]
    send_tok = jnp.full((ep * Cs,), S, jnp.int32).at[slot].set(tok_s)

    Csc = Cs // chunks
    out = jnp.zeros_like(tokens, dtype)
    send_c = send.reshape(ep, Cs, M + 2)
    tok_c = send_tok.reshape(ep, Cs)
    for c in range(chunks):
        sl = send_c[:, c * Csc:(c + 1) * Csc].reshape(ep * Csc, M + 2)
        recv = comm.all_to_all_single(sl, axis_name=expert_axis,
                                      log_name="ep_dispatch")
        # ep identical copies arrived (replicated senders) — compute on
        # copy 0 only, then tile results into every return slot
        r0 = recv[:Csc]
        leid0 = r0[:, M + 1].astype(jnp.int32)
        w0 = r0[:, M]
        order2 = jnp.argsort(leid0, stable=True)   # empties sort last
        xs = jnp.take(r0[:, :M], order2, axis=0).astype(dtype)
        gs = jnp.bincount(leid0, length=e_loc).astype(jnp.int32)
        if len(weights_local) == 3:
            wi_gate, wi_up, wo = weights_local
            g = jax.lax.ragged_dot(xs, wi_gate.astype(dtype), gs)
            u = jax.lax.ragged_dot(xs, wi_up.astype(dtype), gs)
            h = activation(g) * u
        else:
            wi, wo = weights_local
            h = activation(jax.lax.ragged_dot(xs, wi.astype(dtype), gs))
        ys = jax.lax.ragged_dot(h, wo.astype(dtype), gs)
        valid = jnp.arange(Csc) < gs.sum()         # rows past sum(gs) are
        ys = jnp.where(valid[:, None], ys, jnp.zeros_like(ys))
        ys = jnp.take(ys, jnp.argsort(order2, stable=True), axis=0)
        ys = ys * w0[:, None].astype(dtype)
        back = comm.all_to_all_single(
            jnp.broadcast_to(ys[None], (ep, Csc, M)).reshape(ep * Csc, M),
            axis_name=expert_axis, log_name="ep_combine")
        # back[i*Csc + p] = rank i's result for my slot (i, chunk c, p)
        out = out.at[tok_c[:, c * Csc:(c + 1) * Csc].reshape(-1)].add(back)

    return out, _grouped_aux_loss(gates, top_idx, k, E)
