"""Operations and bytes a recurrent layer's short convolution needs at a
decode step, from its shapes alone (the algorithm's needs, as
``kernel_cost.py`` counts attention's and ``ssm_cost.py`` the state
update's): what one layer of ``ops/kernels/short_conv.py`` must compute
and move for the rows that are live.

``layer_metrics/short_conv_roofline.lfm2.json`` names
``decode_step_cost`` as ``short_conv_cost.decode_step_cost``
(``readers.cost_function``) with its sizes from the cell's own file. The
cost counts the WORK, whatever implements it: the Pallas call in place or
XLA's gather, convolution and scatter.
"""

from __future__ import annotations

from typing import Dict


def decode_step_cost(rows: float, width: int, taps: int,
                     pool_itemsize: int = 2,
                     io_itemsize: int = 4) -> Dict[str, float]:
    """One decode token of ``rows`` live sequences through one layer's
    depthwise causal convolution of ``taps`` taps over ``width`` channels.

    Bytes: a live row's ``taps - 1`` carried inputs are read once and
    written once in the pool's dtype (the shift: the oldest leaves, the
    step's input enters); its ``width`` inputs are read and its ``width``
    outputs written once in float32, as the mixers hand them over. The
    taps themselves (``taps x width`` float32, shared by the rows) are
    left out: 24 KB beside 4 MB at 128 rows. FLOPs: a multiply and an add
    a tap and channel. Elementwise, not a matmul: the step is bound by
    its bytes by a factor of a thousand, and at a cell's 128 rows (3 MB)
    by the launch before either."""
    carried = 2.0 * (taps - 1) * width * pool_itemsize
    io = 2.0 * width * io_itemsize
    return {"flops": 2.0 * taps * width * rows,
            "bytes": rows * (carried + io)}
