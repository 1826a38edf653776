"""Operations and bytes the Mamba-1 selective scan needs, from its shapes
alone (the algorithm's needs, as ``ssm_cost.py`` counts the scalar-decay
form's): what one such layer of ``ops/kernels/selective_scan.py``
(``mamba1_decode_state_update``, ``mamba1_chunk_scan``) must compute and
move. A state is ``channels x state`` float32, a decay a (channel, state)
pair, whatever layout holds it.

``layer_metrics/selective_scan_roofline.jamba2.json`` names
``selective_scan_cost.mamba1_decode_cost`` and
``selective_scan_prefill_roofline.jamba2.json``
``selective_scan_cost.mamba1_prefill_cost`` (``readers.cost_function``),
each with its sizes from the cell's own file.
"""

from __future__ import annotations

from typing import Dict

#: a state element and position: the exponent's multiply (dt A), the
#: exponential (counted as one), the decay's multiply, the input's
#: (dt x) B and its add, the output's multiply by C and its add: 7, and
#: the two a CHANNEL (dt x, D x + .) rounded up over the 16 states: 9
_FLOPS_PER_ELEMENT = 9.0


def mamba1_decode_cost(sequences: float, channels: int, state: int,
                       state_itemsize: int = 4, io_itemsize: int = 4
                       ) -> Dict[str, float]:
    """One decode token of ``sequences`` sequences through one layer.

    Bytes: every state [channels, state] read once and written once; x,
    dt and the output y a channel and B, C a state once. FLOPs: 9 a state
    element, elementwise and one reduction. Memory-bound by an order."""
    elements = float(sequences * channels * state)
    vectors = float(sequences * (3 * channels + 2 * state))
    return {"flops": _FLOPS_PER_ELEMENT * elements,
            "bytes": 2.0 * elements * state_itemsize
            + vectors * io_itemsize}


def mamba1_prefill_cost(tokens: float, sequences: float, channels: int,
                        state: int, state_itemsize: int = 4,
                        io_itemsize: int = 4) -> Dict[str, float]:
    """``tokens`` positions (of ``sequences`` sequences, whose states are
    read and written once) through one layer, walked in order.

    The same a position as :func:`mamba1_decode_cost` but for the state,
    which stays on the chip between a sequence's positions: x, dt, y a
    channel and B, C a state of every position once, the state of every
    prefilled row twice. None of the operations is a matmul: against the
    chip's matmul peak they read as all but free, and the time the vector
    unit takes for them has no peak in ``peaks.json``."""
    return {"flops": _FLOPS_PER_ELEMENT * tokens * channels * state,
            "bytes": tokens * (3 * channels + 2 * state) * io_itemsize
            + 2.0 * sequences * channels * state * state_itemsize}
