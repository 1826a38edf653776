"""Operations and bytes a state-space (Mamba-2) layer's decode update
needs, from its shapes alone (the algorithm's needs, as ``kernel_cost.py``
counts attention's, ``linear_attn_cost.py`` the delta rule's and
``moe_cost.py`` the experts'): what one layer of ``ops/kernels/ssd.py`` must
compute and move a decode step.

``layer_metrics/ssm_roofline.rollout.json`` names ``mamba2_decode_cost`` as
``ssm_cost.mamba2_decode_cost`` (``readers.cost_function``) with its sizes
from the cell's own file: Nemotron's Mamba-2 layers and MiniCPM-SALA's
Lightning layers run the one kernel.
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
