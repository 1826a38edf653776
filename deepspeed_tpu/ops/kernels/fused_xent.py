"""Streaming fused LM-head cross-entropy (Pallas TPU) — fwd + bwd.

The LM-head matmul + softmax cross-entropy is the single largest non-layer
cost of causal-LM training (measured 23% of the 124M step — PROFILE.md):
``[N, C] @ [V, C]^T`` logits are V-wide (50k+), and every implementation
that materializes them pays O(N*V) HBM traffic in fp32. The reference
always pays full-logits cost (training goes through torch cross_entropy);
the in-tree ``chunked_lm_xent`` (models/_lm_utils.py) bounds the LIVE
footprint by chunking + remat but still streams each fp32 chunk through
HBM and serializes chunks in a scan.

This kernel never writes logits to HBM at all:

  forward  — grid (token tiles × vocab tiles), online logsumexp exactly
    like flash attention's softmax, plus the target logit extracted via an
    in-tile one-hot reduction. Outputs per-token ``lse`` and ``tgt`` only.
  backward — two passes with OPPOSITE grid orders, each recomputing the
    logits tile on the fly (bf16 MXU, f32 accumulation):
      dh   = (P - onehot) @ E   — token-tile outer, dh accumulates in VMEM
             across the inner vocab walk;
      dE   = (P - onehot)^T @ H — vocab-tile outer, dE accumulates in VMEM
             across the inner token walk.
    Both reductions need the full opposite axis in their inner loop, which
    is exactly why ONE pass cannot emit both (the second output would be
    revisited non-consecutively); the extra logits recompute is one more
    N*V*C matmul — MXU FLOPs traded for zero O(N*V) HBM traffic.

Cost accounting vs the chunked path: 5 MXU passes of N*V*C MACs
(fwd, 2x recompute, dh, dE) vs the chunked path's 4 plus ~8*N*V bytes of
fp32 chunk HBM traffic plus scan serialization. Bandwidth-bound shapes
win; the crossover has not been measured on the benchmark's cells, which
run the chunked path (``benchmark/configs/gpt-1p3b.json``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
_LANES = 128


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# --------------------------------------------------------------------- #
# forward: lse + target logit, no logits in HBM
# --------------------------------------------------------------------- #

def _fwd_kernel(h_ref, e_ref, t_ref, lse_ref, tgt_ref, lsum_ref, m_scr,
                l_scr, g_scr, s_scr, *, Tb, Vb, V, Vt, eps):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[:] = jnp.zeros(l_scr.shape, l_scr.dtype)
        g_scr[:] = jnp.zeros(g_scr.shape, g_scr.dtype)
        s_scr[:] = jnp.zeros(s_scr.shape, s_scr.dtype)

    logits = jax.lax.dot_general(
        h_ref[...], e_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # [Tb, Vb]
    col = j * Vb + jax.lax.broadcasted_iota(jnp.int32, (Tb, Vb), 1)
    if eps:
        # label smoothing's uniform term wants sum_j logits_j over the
        # REAL vocab columns — accumulated pre-mask (the -inf form can't
        # be summed). Statically skipped when smoothing is off.
        s_scr[:, :1] = s_scr[:, :1] + jnp.sum(
            jnp.where(col < V, logits, 0.0), axis=1, keepdims=True)

    # target logit: one-hot row reduction inside the tile (a per-row
    # dynamic gather would leave the VPU's vector regime). Accumulated
    # from the PRE-mask logits: a corrupt id in [V, Vt*Vb) then picks up
    # a finite padded-column value (zeros-padded embedding rows) instead
    # of -inf poisoning the whole loss — the row is excluded from loss
    # and gradients by the valid mask either way.
    t_loc = t_ref[...].astype(jnp.int32)                 # [Tb, 1] global id
    hit = col == t_loc
    g_scr[:, :1] = g_scr[:, :1] + jnp.sum(
        jnp.where(hit, logits, 0.0), axis=1, keepdims=True)

    logits = jnp.where(col < V, logits, _NEG_INF)

    m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
    m_cur = jnp.max(logits, axis=1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_next)                     # m_prev=-inf -> 0
    p = jnp.exp(logits - m_next)
    l_next = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    m_scr[:, :1] = m_next
    l_scr[:, :1] = l_next

    @pl.when(j == Vt - 1)
    def _finish():
        lse_ref[...] = m_scr[:, :1] + jnp.log(
            jnp.maximum(l_scr[:, :1], 1e-37))
        tgt_ref[...] = g_scr[:, :1]
        lsum_ref[...] = s_scr[:, :1]


def _fwd(h2, emb, tgt2, *, Tb, Vb, eps, interpret):
    N2, C = h2.shape
    V = emb.shape[0]
    Nt, Vt = N2 // Tb, _round_up(V, Vb) // Vb
    Vpad = Vt * Vb - V
    e = jnp.pad(emb, ((0, Vpad), (0, 0))) if Vpad else emb
    e = e.astype(h2.dtype)
    kernel = functools.partial(_fwd_kernel, Tb=Tb, Vb=Vb, V=V, Vt=Vt,
                               eps=eps)
    lse, tgt, lsum = pl.pallas_call(
        kernel,
        grid=(Nt, Vt),
        in_specs=[
            pl.BlockSpec((Tb, C), lambda i, j: (i, 0)),
            pl.BlockSpec((Vb, C), lambda i, j: (j, 0)),
            pl.BlockSpec((Tb, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((Tb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((Tb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((Tb, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((N2, 1), jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((Tb, _LANES), jnp.float32)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(h2, e, tgt2[:, None])
    return lse[:, 0], tgt[:, 0], lsum[:, 0]


# --------------------------------------------------------------------- #
# backward pass 1: dh = scale * (P - onehot) @ E   (token-tile outer)
# --------------------------------------------------------------------- #

def _grad_p(logits, lse_col, t_loc, col, *, V, z, eps, ignore):
    """d loss_row / d logits for one tile (pure jnp, shared by both
    backward kernels so the ignore/z/eps semantics can never diverge):
    ``(1 + 2z*lse) * P - (1-eps)*onehot - eps/V`` over real vocab
    columns, zeroed at ignored positions."""
    p = jnp.where(col < V, jnp.exp(logits - lse_col), 0.0)
    if z:
        p = p * (1.0 + 2.0 * z * lse_col)
    p = p - jnp.where(col == t_loc, 1.0 - eps, 0.0)
    if eps:
        p = p - jnp.where(col < V, eps / V, 0.0)
    # rows whose target id is out of range — negative (ignore ids like
    # -100) or >= V (corrupt labels) — contribute NO gradient, matching
    # their exclusion from the loss and the divisor
    p = jnp.where((t_loc < 0) | (t_loc >= V), 0.0, p)
    if ignore is not None:
        p = jnp.where(t_loc == ignore, 0.0, p)
    return p



def _dh_kernel(s_ref, h_ref, e_ref, t_ref, lse_ref, dh_ref, acc_scr,
               *, Tb, Vb, V, Vt, ignore, z, eps):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    logits = jax.lax.dot_general(
        h_ref[...], e_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    col = j * Vb + jax.lax.broadcasted_iota(jnp.int32, (Tb, Vb), 1)
    p = _grad_p(logits, lse_ref[...], t_ref[...].astype(jnp.int32), col,
                V=V, z=z, eps=eps, ignore=ignore)
    acc_scr[:] = acc_scr[:] + jax.lax.dot_general(
        p.astype(h_ref.dtype), e_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [Tb, C]

    @pl.when(j == Vt - 1)
    def _finish():
        dh_ref[0] = (acc_scr[:] * s_ref[0]).astype(dh_ref.dtype)


# --------------------------------------------------------------------- #
# backward pass 2: dE = scale * (P - onehot)^T @ H  (vocab-tile outer)
# --------------------------------------------------------------------- #

def _de_kernel(s_ref, h_ref, e_ref, t_ref, lse_ref, de_ref, acc_scr,
               *, Tb, Vb, V, N, Nt, ignore, z, eps):
    i = pl.program_id(1)
    j = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_scr[:] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    logits = jax.lax.dot_general(
        h_ref[...], e_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # [Tb, Vb]
    col = j * Vb + jax.lax.broadcasted_iota(jnp.int32, (Tb, Vb), 1)
    p = _grad_p(logits, lse_ref[...], t_ref[...].astype(jnp.int32), col,
                V=V, z=z, eps=eps, ignore=ignore)
    # padded token rows carry P = uniform garbage (their h rows are zero
    # but lse is finite): mask them out of the vocab-side reduction
    row = i * Tb + jax.lax.broadcasted_iota(jnp.int32, (Tb, Vb), 0)
    p = jnp.where(row < N, p, 0.0)
    acc_scr[:] = acc_scr[:] + jax.lax.dot_general(
        p.astype(h_ref.dtype), h_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [Vb, C]

    @pl.when(i == Nt - 1)
    def _finish():
        de_ref[0] = (acc_scr[:] * s_ref[0]).astype(de_ref.dtype)


# --------------------------------------------------------------------- #
# public op with custom VJP
# --------------------------------------------------------------------- #

def _valid_rows(tgt2, N, ignore, V):
    # in-range check mirrors chunked_lm_xent: out-of-range non-ignored
    # ids (corrupt labels) are dropped from loss + divisor, never
    # trained against
    valid = (jnp.arange(tgt2.shape[0]) < N) & (tgt2 >= 0) & (tgt2 < V)
    if ignore is not None:
        valid = jnp.logical_and(valid, tgt2 != ignore)
    return valid


def _core_total(lse, tgt, lsum, V, tgt2, N, ignore, z, eps):
    valid = _valid_rows(tgt2, N, ignore, V)
    # smoothed NLL: lse - (1-eps)*tgt_logit - (eps/V)*sum_j logits_j
    nll = lse - (1.0 - eps) * tgt
    if eps:
        nll = nll - (eps / V) * lsum
    if z:
        nll = nll + z * lse * lse       # PaLM-style z-loss stabilizer
    return jnp.where(valid, nll, 0.0).sum()


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _xent_core(h2, emb, tgt2, N, Tb, Vb, ignore, z, eps, interpret):
    """Sum of next-token NLL (+ optional z-loss) over the first ``N``
    (valid, non-ignored) rows. The SUM — not the mean — is the
    custom-vjp boundary so the incoming cotangent is a SCALAR (the
    mean's 1/count folds outside); per-row cotangents would need a
    non-separable dE scaling the kernels cannot fold."""
    lse, tgt, lsum = _fwd(h2, emb, tgt2, Tb=Tb, Vb=Vb, eps=eps,
                          interpret=interpret)
    return _core_total(lse, tgt, lsum, emb.shape[0], tgt2, N, ignore, z,
                       eps)


def _xent_fwd_rule(h2, emb, tgt2, N, Tb, Vb, ignore, z, eps, interpret):
    lse, tgt, lsum = _fwd(h2, emb, tgt2, Tb=Tb, Vb=Vb, eps=eps,
                          interpret=interpret)
    total = _core_total(lse, tgt, lsum, emb.shape[0], tgt2, N, ignore, z,
                        eps)
    return total, (h2, emb, tgt2, lse)


def _xent_bwd_rule(N, Tb, Vb, ignore, z, eps, interpret, res, g):
    h2, emb, tgt2, lse = res
    N2, C = h2.shape
    V = emb.shape[0]
    Nt, Vt = N2 // Tb, _round_up(V, Vb) // Vb
    Vpad = Vt * Vb - V
    e = jnp.pad(emb, ((0, Vpad), (0, 0))) if Vpad else emb
    e = e.astype(h2.dtype)
    # d(sum nll)/d(logit) = P - onehot per valid row, all scaled by the
    # scalar cotangent g. Padded rows: dE masks them in-kernel (row < N);
    # dh's padded rows are garbage that jnp.pad's own VJP slices off.
    scale = jnp.reshape(g, (1,)).astype(jnp.float32)

    dh = pl.pallas_call(
        functools.partial(_dh_kernel, Tb=Tb, Vb=Vb, V=V, Vt=Vt,
                          ignore=ignore, z=z, eps=eps),
        grid=(Nt, Vt),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((Tb, C), lambda i, j: (i, 0)),
            pl.BlockSpec((Vb, C), lambda i, j: (j, 0)),
            pl.BlockSpec((Tb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((Tb, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, Tb, C), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Nt, Tb, C), h2.dtype),
        scratch_shapes=[pltpu.VMEM((Tb, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(scale, h2, e, tgt2[:, None], lse[:, None]).reshape(N2, C)

    de = pl.pallas_call(
        functools.partial(_de_kernel, Tb=Tb, Vb=Vb, V=V, N=N, Nt=Nt,
                          ignore=ignore, z=z, eps=eps),
        grid=(Vt, Nt),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((Tb, C), lambda j, i: (i, 0)),
            pl.BlockSpec((Vb, C), lambda j, i: (j, 0)),
            pl.BlockSpec((Tb, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((Tb, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, Vb, C), lambda j, i: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Vt, Vb, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((Vb, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(scale, h2, e, tgt2[:, None], lse[:, None]).reshape(Vt * Vb, C)[:V]

    return dh, de.astype(emb.dtype), None


_xent_core.defvjp(_xent_fwd_rule, _xent_bwd_rule)


def fused_lm_xent(hidden: jnp.ndarray, embedding: jnp.ndarray,
                  targets: jnp.ndarray, *, token_block: Optional[int] = None,
                  vocab_block: Optional[int] = None,
                  ignore_index: Optional[int] = None,
                  z_loss: float = 0.0,
                  label_smoothing: float = 0.0,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Mean next-token NLL with logits never materialized in HBM.

    hidden [B, T, C] (or [N, C]) in the compute dtype, embedding [V, C]
    (the tied LM head), targets [B, T] (or [N]) int32. Differentiable in
    (hidden, embedding); the backward recomputes P tiles on the MXU.
    ``ignore_index`` (torch cross_entropy semantics, e.g. -100) drops
    those positions from the loss, the divisor, and both gradients.
    ``z_loss`` adds the PaLM-style ``z * logsumexp^2`` stabilizer per
    valid position (folded into the same kernels: the backward's P
    factor becomes ``1 + 2z*lse``). ``label_smoothing`` mixes the
    target with the uniform distribution (the backward subtracts the
    smoothed one-hot ``(1-eps)*onehot + eps/V``; the forward's uniform
    term rides a third per-row accumulator).
    """
    if interpret is None:
        from . import default_interpret
        interpret = default_interpret()
    h2 = hidden.reshape(-1, hidden.shape[-1])
    t1 = targets.reshape(-1).astype(jnp.int32)
    N, C = h2.shape
    if token_block is None:
        # grid-step fixed costs dominate when the per-step matmul is small
        # (Tb*Vb*C MACs): widen token tiles at narrow models. The VMEM
        # budget (h tile + f32 dh accumulator + double-buffered emb tiles)
        # caps Tb at 256 for C ~ 2048.
        token_block = 512 if C <= 1024 else 256
    if vocab_block is None:
        # prefer a lane-aligned tile that DIVIDES V: the pad path copies
        # the whole [V, C] embedding (fwd + both bwd passes) just to add
        # the tail rows. 50304 (gpt2 padded vocab) -> 384; 32000 -> 256.
        V = embedding.shape[0]
        vocab_block = next((c for c in (512, 384, 256, 128)
                            if V % c == 0), 512)
    Tb = min(token_block, _round_up(N, 8))
    N2 = _round_up(N, Tb)
    if N2 != N:
        h2 = jnp.pad(h2, ((0, N2 - N), (0, 0)))
        t1 = jnp.pad(t1, (0, N2 - N))
    # NEGATIVE ids (e.g. -100) need no clamping: the kernels never index
    # with targets — the one-hot compare simply never hits, and the
    # validity masks zero those rows' loss and gradients. Positive
    # out-of-range ids (corrupt labels) are likewise excluded from loss,
    # gradients, and the divisor (chunked_lm_xent semantics — torch
    # cross_entropy would raise; silently training against a clamped id
    # is the one behavior that is never right).
    total = _xent_core(h2, embedding, t1, N, Tb, vocab_block,
                       ignore_index, float(z_loss),
                       float(label_smoothing), interpret)
    tflat = targets.reshape(-1)
    valid = (tflat >= 0) & (tflat < embedding.shape[0])
    if ignore_index is not None:
        valid &= tflat != ignore_index
    return total / jnp.maximum(valid.sum(), 1)


def sharded_fused_lm_xent(hidden: jnp.ndarray, embedding: jnp.ndarray,
                          targets: jnp.ndarray, mesh,
                          batch_axes=("data", "data_inner"),
                          **kwargs) -> jnp.ndarray:
    """``fused_lm_xent`` under ``shard_map``: token rows shard over the
    data axes, the embedding stays replicated, and the loss reduces via
    ``psum`` of per-shard (sum, count) pairs — the same wrapping
    ``sharded_flash_attention`` gives the attention kernel (Pallas custom
    calls carry no GSPMD rules, so a multi-device jit would otherwise
    all-gather the hidden states around the kernel). The embedding
    cotangent is psum'd by shard_map's transpose of the replicated input.

    Falls back to the unsharded kernel when no batch axis divides the
    leading dim.
    """
    from ...utils.jax_compat import shard_map
    from jax.sharding import PartitionSpec as P

    ignore = kwargs.get("ignore_index")
    h3 = hidden if hidden.ndim == 3 else hidden[None]
    t2 = targets if targets.ndim == 2 else targets[None]
    bat = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1)
    bsz = 1
    for a in bat:
        bsz *= mesh.shape[a]
    if not bat or h3.shape[0] % bsz:
        return fused_lm_xent(hidden, embedding, targets, **kwargs)

    def local(h_, e_, t_):
        # per-shard sum + RAW valid count; the global mean is the psum
        # ratio with the zero-guard applied AFTER the psum — clamping
        # per shard would inflate the divisor whenever one shard's rows
        # are all ignored (loc * max(raw, 1) recovers the exact
        # per-shard total either way: loc is 0 when raw is 0). The count
        # must mirror fused_lm_xent's own divisor: in-range, non-ignored.
        loc = fused_lm_xent(h_, e_, t_, **kwargs)
        vld = (t_ >= 0) & (t_ < e_.shape[0])
        if ignore is not None:
            vld &= t_ != ignore
        raw = vld.sum().astype(jnp.float32)
        total = jax.lax.psum(loc * jnp.maximum(raw, 1.0), bat)
        count = jax.lax.psum(raw, bat)
        return total / jnp.maximum(count, 1.0)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(bat), P(), P(bat)),
        out_specs=P(),
        check_vma=False,
    )(h3, embedding, t2)
