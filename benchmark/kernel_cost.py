"""Operations and bytes an attention call needs, from its shapes alone.

These are the algorithm's needs, not what a kernel happens to move: a
kernel that re-reads a tile or streams a padded block does more, and its
roofline share shows it. Kept with the benchmark so that no PR that claims
a gain can change the yardstick.
"""

from __future__ import annotations

import json
import os
from typing import Dict

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks on record for device_kind {device_kind!r}:"
                       f" add it to benchmark/peaks.json with its source")
    return table[device_kind]


def flash_attention_cost(batch: int, heads: int, seq: int, head_dim: int,
                         causal: bool = True, backward: bool = False,
                         itemsize: int = 2) -> Dict[str, float]:
    """One flash-attention call over [batch, seq, heads, head_dim].

    Forward: QK^T and PV, 2 matmuls of 2*T*T*D FLOPs per head, halved by
    the causal mask. Backward: recomputed QK^T, dV, dP, dQ, dK: 5 such
    matmuls. Bytes: forward reads q, k, v and writes o; backward reads q,
    k, v, o, do and writes dq, dk, dv (the f32 row statistics are 1/D of a
    tensor and left out)."""
    tensor = batch * heads * seq * head_dim
    matmul = 2.0 * batch * heads * seq * seq * head_dim
    if causal:
        matmul /= 2
    n_mm, n_tensors = (5, 8) if backward else (2, 4)
    return {"flops": n_mm * matmul, "bytes": float(n_tensors * tensor
                                                   * itemsize)}


def paged_decode_attention_cost(context_tokens: float, q_heads: int,
                                kv_heads: int, head_dim: int,
                                kv_itemsize: int = 2) -> Dict[str, float]:
    """Decode attention of ONE layer over sequences whose live contexts
    sum to ``context_tokens`` (one query token each): every live K and V
    row is read once (the query, the output and the new row are 1/context
    of that), and each query head does a dot product and a weighted sum
    over its context."""
    return {"flops": 4.0 * context_tokens * q_heads * head_dim,
            "bytes": 2.0 * context_tokens * kv_heads * head_dim
            * kv_itemsize}


def roofline_seconds(cost: Dict[str, float], peak: Dict[str, float]) -> Dict:
    """The least time the chip could take, and which limit sets it."""
    t_flops = cost["flops"] / peak["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
