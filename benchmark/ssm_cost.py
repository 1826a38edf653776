"""Operations and bytes a state-space (Mamba-2) layer's decode update and
an UNGATED grouped expert feed-forward need, from their shapes alone (the
algorithm's needs, as ``kernel_cost.py`` counts attention's,
``linear_attn_cost.py`` the delta rule's and ``moe_cost.py`` the SwiGLU
experts'): what one layer of ``ops/kernels/ssd.py`` must compute and move a
decode step, and what one sparse layer of two-matrix experts must.

``layer_metrics/ssm_roofline.nemotron.json`` names ``mamba2_decode_cost``
as ``ssm_cost.mamba2_decode_cost`` and
``layer_metrics/grouped_moe_roofline.nemotron.json`` names
``ungated_ffn_cost`` (``readers.cost_function``).
``moe_cost.grouped_moe_ffn_cost`` counts THREE matrices an expert: over
two-matrix experts it would read a share half again too high.
"""

from __future__ import annotations

from typing import Dict


def mamba2_decode_cost(sequences: float, heads: int, head_dim: int,
                       state: int, state_itemsize: int = 4,
                       io_itemsize: int = 4) -> Dict[str, float]:
    """One decode token of ``sequences`` sequences through one Mamba-2
    layer.

    Bytes: every state [head_dim, state] is read once and written once;
    x and the output y (head_dim each), B and C (state each) and the step
    dt (1) a head once. FLOPs: per state element the decay's multiply,
    the rank-one update (2: dt x (x) B, the add), the C contraction (2)
    and, counted with them, the products that make dt x and D x: 7 as the
    delta rule's update. They are elementwise and reductions, not
    matmuls, so the bf16 matmul peak flatters them; the update is
    memory-bound by a factor of 250."""
    elements = float(sequences * heads * head_dim * state)
    vectors = float(sequences * heads * (2 * head_dim + 2 * state + 1))
    return {"flops": 7.0 * elements,
            "bytes": 2.0 * elements * state_itemsize + vectors * io_itemsize}


def ungated_ffn_cost(rows: float, experts_hit: float, hidden: int,
                     width: int, itemsize: int = 2) -> Dict[str, float]:
    """One sparse layer's expert feed-forward over ``rows`` routed rows
    that reach ``experts_hit`` distinct experts of the form
    ``W_down act(W_up h)``: TWO matrices an expert.

    FLOPs: every routed row goes through two [hidden x width] matmuls,
    2 x hidden x width each. Bytes: the two matrices of every expert that
    is hit are read once at the PUBLISHED ``width`` (a program that
    stores them wider streams more than this and reads a lower share),
    and every routed row is read once at the hidden width and written
    once at it."""
    return {"flops": 4.0 * rows * hidden * width,
            "bytes": float(2 * experts_hit * hidden * width * itemsize
                           + 2 * rows * hidden * itemsize)}
