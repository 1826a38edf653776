"""Operations and bytes latent attention (MLA) needs, from its shapes alone.

The true counts of the ABSORBED form, which
``layer_metrics/mla_attn_roofline.rollout.json`` names as
``mla_cost.mla_decode_attention_cost`` (``kernel_cost.
paged_decode_attention_cost``, its stand-in until PR 37, counts a row's
bytes right at ``kv_heads`` 1, ``head_dim`` 288 and its FLOPs 1.9 x
short). As in ``kernel_cost.py`` these are the algorithm's
needs: a kernel that reads a row for the scores and again for the values,
or multiplies a padded row, does more, and its roofline share shows it.
"""

from __future__ import annotations

from typing import Dict


def mla_decode_attention_cost(context_tokens: float, q_heads: int,
                              latent: int, rope: int,
                              kv_itemsize: int = 2) -> Dict[str, float]:
    """Absorbed decode attention of ONE layer over sequences whose live
    contexts sum to ``context_tokens`` (one query token each): every live
    row ``[c_kv ; k_r]`` is read ONCE (it is key and value at once; the
    query, the output and the new row are 1/context of that), and each
    query head does a dot product over the row's ``latent + rope`` lanes
    and a weighted sum over its ``latent``. The absorption itself
    (``q_nope W_UK^T``, ``o_lat W_UV``) is the caller's and not counted."""
    return {"flops": 2.0 * context_tokens * q_heads * (2 * latent + rope),
            "bytes": float(context_tokens * (latent + rope) * kv_itemsize)}


def mla_prefill_attention_cost(chunk_tokens: int, context_tokens: int,
                               q_heads: int, latent: int, rope: int,
                               nope: int = 0, v_dim: int = 0,
                               kv_itemsize: int = 2) -> Dict[str, float]:
    """One sequence's prefill chunk of ``chunk_tokens`` queries after
    ``context_tokens`` cached tokens (causal inside the chunk: a query
    sees the context and on average half the chunk).

    Absorbed (``nope`` 0): scores over ``latent + rope`` lanes and values
    over ``latent``, straight over the cached rows. Expanded (``nope``,
    ``v_dim`` given): every row the chunk sees goes through ``W_kvb`` first
    (``2 x latent x q_heads x (nope + v_dim)`` FLOPs a row), then scores
    over ``nope + rope`` and values over ``v_dim``. Bytes: the rows read
    once."""
    rows = context_tokens + chunk_tokens
    pairs = chunk_tokens * (context_tokens + (chunk_tokens + 1) / 2.0)
    if nope:
        flops = 2.0 * pairs * q_heads * (nope + rope + v_dim) \
            + 2.0 * rows * latent * q_heads * (nope + v_dim)
    else:
        flops = 2.0 * pairs * q_heads * (2 * latent + rope)
    return {"flops": flops,
            "bytes": float(rows * (latent + rope) * kv_itemsize)}
