"""Operations and bytes a causal flash-attention call needs under a
SLIDING WINDOW, from its shapes alone (the algorithm's needs, as
``kernel_cost.flash_attention_cost`` counts the full causal square's).

``layer_metrics/flash_window_roofline.train.json`` names
``windowed_flash_attention_cost`` as
``flash_window_cost.windowed_flash_attention_cost``
(``readers.cost_function``). Only the score elements the mask NEEDS are
counted: query ``i`` sees ``min(i + 1, window)`` keys. A kernel that
computes blocks the window hides, or the masked part of a block the
window's edge crosses, does more than is counted here and reads a LOW
share, never one over 100 %.
"""

from __future__ import annotations

from typing import Dict, Optional


def visible_pairs(seq: int, window: Optional[int]) -> int:
    """``sum_i min(i + 1, window)`` over ``seq`` queries: the (query, key)
    pairs a causal mask with a window of ``window`` keys (the query's own
    among them) lets through; ``window`` None or ``>= seq``: the causal
    triangle."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def windowed_flash_attention_cost(batch: int, heads: int, seq: int,
                                  head_dim: int, window: Optional[int],
                                  backward: bool = False,
                                  itemsize: int = 2) -> Dict[str, float]:
    """One flash-attention call over [batch, seq, heads, head_dim] whose
    query ``i`` sees keys ``0 <= i - j < window``. A matmul over the
    visible pairs is ``2 * pairs * head_dim`` FLOPs a head; forward: QK^T
    and PV; backward: the recomputed QK^T, dV, dP, dQ, dK. Bytes as
    ``kernel_cost.flash_attention_cost`` counts them (GQA's fewer K/V
    bytes are not discounted: the count can only be high on bytes, and
    the call is compute-bound)."""
    tensor = batch * heads * seq * head_dim
    matmul = 2.0 * batch * heads * visible_pairs(seq, window) * head_dim
    n_mm, n_tensors = (5, 8) if backward else (2, 4)
    return {"flops": n_mm * matmul,
            "bytes": float(n_tensors * tensor * itemsize)}
