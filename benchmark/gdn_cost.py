"""Operations and bytes the gated delta rule with ONE decay a head needs,
from its shapes alone (the algorithm's needs, as ``linear_attn_cost.py``
counts the channel-decay form's): what one such layer of
``ops/kernels/delta_rule.py`` (``gdn_decode_state_update``,
``gdn_chunk_prefill``) must compute and move. Keys ``d_k`` and values
``d_v`` wide: a state is ``d_k x d_v`` float32, whatever layout holds it.

``layer_metrics/gdn_state_roofline.olmo_hybrid.json`` names
``gdn_cost.gdn_decode_cost`` and ``gdn_prefill_roofline.olmo_hybrid.json``
``gdn_cost.gdn_prefill_cost`` (``readers.cost_function``), each with its
sizes from the cell's own file.
"""

from __future__ import annotations

from typing import Dict


def gdn_decode_cost(sequences: float, heads: int, d_k: int, d_v: int,
                    state_itemsize: int = 4, io_itemsize: int = 4
                    ) -> Dict[str, float]:
    """One decode token of ``sequences`` sequences through one layer.

    Bytes: every state [d_k, d_v] read once and written once, the state's
    TRUE bytes whatever the pool's layout pads; q and k (d_k each), v and
    the output (d_v each), the decay and the step size (ONE number a head
    each) once. FLOPs: per state element the decay's multiply, the k^T S
    product (2), the rank-one update (2) and the output's q^T S (2): 7,
    elementwise and reductions. Memory-bound by two orders."""
    state = float(sequences * heads * d_k * d_v)
    vectors = float(sequences * heads * (2 * d_k + 2 * d_v + 2))
    return {"flops": 7.0 * state,
            "bytes": 2.0 * state * state_itemsize + vectors * io_itemsize}


def gdn_prefill_cost(tokens: float, heads: int, d_k: int, d_v: int,
                     chunk: int = 64, sequences: float = 0.0,
                     state_itemsize: int = 4, io_itemsize: int = 4
                     ) -> Dict[str, float]:
    """``tokens`` positions (of ``sequences`` sequences, whose states are
    read and written once) through one layer in chunks of ``chunk``.

    FLOPs per chunk and head, L = chunk, ``kda_prefill_cost``'s count of
    the same matmuls: the two [L, L] tables over d_k (k k^T and q k^T,
    4 L^2 d_k); the triangular solve against [L, d_k + d_v]
    (L^2 (d_k + d_v)); K S_0 and Q S_0 (4 L d_k d_v); the table times U
    (2 L^2 d_v); the state's update (2 L d_k d_v). Bytes: q, k, v, output
    and ONE decay and one step size a head of every position once, the
    state of every sequence twice."""
    L = float(chunk)
    per_chunk = (4 * L * L * d_k + L * L * (d_k + d_v) + 6 * L * d_k * d_v
                 + 2 * L * L * d_v)
    return {"flops": per_chunk * heads * tokens / L,
            "bytes": tokens * heads * (2 * d_k + 2 * d_v + 2) * io_itemsize
            + 2.0 * sequences * heads * d_k * d_v * state_itemsize}
