"""Operations and bytes the gated delta rule needs, from its shapes alone
(the algorithm's needs, as ``kernel_cost.py`` counts attention's and
``moe_cost.py`` the grouped matmuls'): what one KDA layer of
``ops/kernels/delta_rule.py`` must compute and move, in its two forms.

``layer_metrics/linear_attn_roofline.rollout.json`` names
``kda_decode_cost`` as ``linear_attn_cost.kda_decode_cost``
(``readers.cost_function``) and ``linear_attn_prefill_roofline.rollout.json``
names ``kda_prefill_cost`` the same way, each with its sizes from the
cell's own file; ``roofline_share`` below is the same share for a builder's
own reduction of a traced run.
"""

from __future__ import annotations

from typing import Dict

from .kernel_cost import roofline_seconds


def kda_decode_cost(sequences: float, heads: int, d_k: int, d_v: int,
                    state_itemsize: int = 4, io_itemsize: int = 4
                    ) -> Dict[str, float]:
    """One decode token of ``sequences`` sequences through one KDA layer.

    Bytes: every state [d_k, d_v] is read once and written once; q, k and
    the decay (d_k each), v and the output (d_v each) and the step size
    once. FLOPs: per state element the decay's multiply, the k^T S
    product (2), the rank-one update (2) and the output's q^T S (2): 7.
    They are elementwise and reductions, not matmuls, so the bf16 matmul
    peak flatters them; the update is memory-bound by a factor of 250."""
    state = float(sequences * heads * d_k * d_v)
    vectors = float(sequences * heads * (3 * d_k + 2 * d_v + 1))
    return {"flops": 7.0 * state,
            "bytes": 2.0 * state * state_itemsize + vectors * io_itemsize}


def kda_prefill_cost(tokens: float, heads: int, d_k: int, d_v: int,
                     chunk: int = 64, sequences: float = 0.0,
                     state_itemsize: int = 4, io_itemsize: int = 4
                     ) -> Dict[str, float]:
    """``tokens`` positions (of ``sequences`` sequences, whose states are
    read and written once) through one KDA layer in chunks of ``chunk``.

    FLOPs per chunk and head, L = chunk: the two [L, L] tables over d_k
    (k k^T and q k^T with their decays, 4 L^2 d_k); the triangular solve
    against [L, d_k + d_v] (L^2 (d_k + d_v)); W S_0 and Q S_0
    (4 L d_k d_v); the table times U (2 L^2 d_v); the state's update
    (2 L d_k d_v). Bytes: q, k, decay, v, output and step size of every
    position once, the state of every sequence twice."""
    L = float(chunk)
    per_chunk = (4 * L * L * d_k + L * L * (d_k + d_v) + 6 * L * d_k * d_v
                 + 2 * L * L * d_v)
    return {"flops": per_chunk * heads * tokens / L,
            "bytes": tokens * heads * (3 * d_k + 2 * d_v + 1) * io_itemsize
            + 2.0 * sequences * heads * d_k * d_v * state_itemsize}


def roofline_share(seconds: float, calls: float, peak: Dict[str, float],
                   cost: Dict[str, float]) -> Dict[str, float]:
    """Share (%) of its roofline that ``calls`` layers of one cost reached
    in ``seconds`` of device time, and which limit bounds it."""
    least = roofline_seconds(cost, peak)
    return {"share": 100.0 * calls * least["seconds"] / seconds,
            "bound": least["bound"]}
