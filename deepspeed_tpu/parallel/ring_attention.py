"""Ring attention (context parallelism).

The reference has NO ring attention (SURVEY.md §2.4: long context is Ulysses
only). This is the TPU-native extension the survey prescribes: KV blocks
rotate around the ``seq`` mesh axis via ``ppermute`` (nearest-neighbor ICI
traffic) while each device keeps its Q shard and accumulates attention with
an online-softmax, so sequence length scales linearly with the ring size and
full T×T scores never materialize.

Algorithm (blockwise attention / Liu et al. RingAttention):
  each of the sp steps: partial = softmax-accumulate(Q_local, K_rot, V_rot)
  with running (max, denominator, numerator); then ppermute K/V to the next
  ring neighbor. Causal masking uses global block indices.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from ..utils.jax_compat import shard_map

from .. import comm

SEQ_AXIS = "seq"
DATA_AXIS = "data"
NEG_INF = -1e30


def _ring_attention_local(q, k, v, axis_name: str, causal: bool, sm_scale: float):
    """Runs inside shard_map. q/k/v: [B, T_loc, H, D] local shards."""
    sp = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, T_loc, H, D = q.shape

    qf = q.astype(jnp.float32) * sm_scale
    # accumulators for online softmax
    numer = jnp.zeros((B, T_loc, H, D), jnp.float32)
    denom = jnp.zeros((B, T_loc, H), jnp.float32)
    row_max = jnp.full((B, T_loc, H), NEG_INF, jnp.float32)

    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(carry, r):
        numer, denom, row_max, k_blk, v_blk = carry
        # the block we hold at round r originated on device (my_idx - r) mod sp
        src = (my_idx - r) % sp
        # scores [B, T_loc, H, T_loc]
        scores = jnp.einsum("bqhd,bkhd->bqhk", qf, k_blk.astype(jnp.float32))
        if causal:
            q_pos = my_idx * T_loc + jnp.arange(T_loc)[:, None]       # [Tq,1]
            k_pos = src * T_loc + jnp.arange(T_loc)[None, :]          # [1,Tk]
            mask = (k_pos <= q_pos)[None, :, None, :]                 # [1,Tq,1,Tk]
            scores = jnp.where(mask, scores, NEG_INF)
        blk_max = scores.max(axis=-1)                                  # [B,Tq,H]
        new_max = jnp.maximum(row_max, blk_max)
        # guard fully-masked rows (new_max == NEG_INF)
        safe_max = jnp.where(new_max <= NEG_INF / 2, 0.0, new_max)
        correction = jnp.exp(row_max - safe_max)
        correction = jnp.where(row_max <= NEG_INF / 2, 0.0, correction)
        p = jnp.exp(scores - safe_max[..., None])
        p = jnp.where(scores <= NEG_INF / 2, 0.0, p)
        numer = numer * correction[..., None] + jnp.einsum(
            "bqhk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
        denom = denom * correction + p.sum(axis=-1)
        # rotate KV to the next ring neighbor
        k_blk = comm.ppermute(k_blk, perm, axis_name=axis_name)
        v_blk = comm.ppermute(v_blk, perm, axis_name=axis_name)
        return (numer, denom, new_max, k_blk, v_blk), None

    (numer, denom, _, _, _), _ = jax.lax.scan(
        step, (numer, denom, row_max, k, v), jnp.arange(sp))
    out = numer / jnp.maximum(denom, 1e-20)[..., None]
    return out.astype(q.dtype)


def _merge_partials(o1, lse1, o2, lse2):
    """Combine two normalized attention partials by their logsumexps.
    o: [B, T, H, D] fp32; lse: [B, T, H] fp32 (-inf = no contribution)."""
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
    w1 = jnp.where(lse1 <= NEG_INF / 2, 0.0, jnp.exp(lse1 - m_safe))
    w2 = jnp.where(lse2 <= NEG_INF / 2, 0.0, jnp.exp(lse2 - m_safe))
    denom = w1 + w2
    denom_safe = jnp.where(denom == 0.0, 1.0, denom)
    o = (o1 * w1[..., None] + o2 * w2[..., None]) / denom_safe[..., None]
    lse = jnp.where(denom == 0.0, NEG_INF, m_safe + jnp.log(denom_safe))
    return o, lse


def _ring_attention_local_kernel(q, k, v, axis_name: str, causal: bool,
                                 sm_scale: float, interpret):
    """Ring accumulation where each round's local attention IS the Pallas
    flash kernel (forward + backward): round 0 is the diagonal block
    (causal mask inside the kernel); later rounds are all-or-nothing blocks
    (full attend when the KV block comes from earlier in the sequence,
    skipped when later), merged by kernel-emitted logsumexp. The lse output
    is differentiable (ops/kernels/flash_attention._flash_lse), so the whole
    ring trains through jax.grad with kernel fwd+bwd."""
    from ..ops.kernels import flash_attention

    sp = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def attend(kb, vb, causal_flag):
        o, lse = flash_attention(q, kb, vb, causal=causal_flag,
                                 sm_scale=sm_scale, layout="BTHD",
                                 interpret=interpret, return_lse=True)
        return o.astype(jnp.float32), lse.swapaxes(1, 2)   # [B,T,H,D],[B,T,H]

    # round 0 holds the locally-originated KV: the diagonal block
    o_acc, lse_acc = attend(k, v, causal)
    k_blk = comm.ppermute(k, perm, axis_name=axis_name)
    v_blk = comm.ppermute(v, perm, axis_name=axis_name)

    def step(carry, r):
        o_acc, lse_acc, k_blk, v_blk = carry
        # the block held at round r originated on device (my_idx - r) mod sp
        src = (my - r) % sp

        def full_block(_):
            return attend(k_blk, v_blk, False)

        def skip(_):
            return (jnp.zeros_like(o_acc),
                    jnp.full(lse_acc.shape, NEG_INF, jnp.float32))

        if causal:
            o_r, lse_r = jax.lax.cond(src < my, full_block, skip, None)
        else:
            o_r, lse_r = full_block(None)
        o_acc, lse_acc = _merge_partials(o_acc, lse_acc, o_r, lse_r)
        k_nxt = comm.ppermute(k_blk, perm, axis_name=axis_name)
        v_nxt = comm.ppermute(v_blk, perm, axis_name=axis_name)
        return (o_acc, lse_acc, k_nxt, v_nxt), None

    if sp > 1:
        (o_acc, lse_acc, _, _), _ = jax.lax.scan(
            step, (o_acc, lse_acc, k_blk, v_blk), jnp.arange(1, sp))
    return o_acc.astype(q.dtype)


def ring_attention(query: jnp.ndarray, key: jnp.ndarray, value: jnp.ndarray,
                   mesh: Mesh, seq_axis: str = SEQ_AXIS, causal: bool = True,
                   sm_scale: Optional[float] = None,
                   use_kernel: Optional[bool] = None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Context-parallel attention. q/k/v: [B, T, H, D] with T sharded over
    ``seq``; returns [B, T, H, D] with the same sharding.

    ``use_kernel``: run each round's local attention as the Pallas flash
    kernel (default on TPU); False keeps the pure-jnp blockwise path."""
    D = query.shape[-1]
    sm_scale = sm_scale if sm_scale is not None else 1.0 / (D ** 0.5)
    sp = mesh.shape[seq_axis]
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if sp == 1:
        if use_kernel:
            from ..ops.kernels import flash_attention
            return flash_attention(query, key, value, causal=causal,
                                   sm_scale=sm_scale, layout="BTHD",
                                   interpret=interpret)
        return jax.nn.dot_product_attention(query, key, value, is_causal=causal,
                                            scale=sm_scale)

    # batch dim rides the data axis when the mesh has one (avoids replicating
    # a DP-sharded batch across data groups)
    dp = mesh.shape.get(DATA_AXIS, 1)
    batch_axis = DATA_AXIS if dp > 1 and query.shape[0] % dp == 0 else None
    spec = P(batch_axis, seq_axis, None, None)
    if use_kernel:
        fn = functools.partial(_ring_attention_local_kernel,
                               axis_name=seq_axis, causal=causal,
                               sm_scale=sm_scale, interpret=interpret)
    else:
        fn = functools.partial(_ring_attention_local, axis_name=seq_axis,
                               causal=causal, sm_scale=sm_scale)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(query, key, value)
