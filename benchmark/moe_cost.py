"""Operations and bytes a grouped expert feed-forward needs, from its
shapes alone (the algorithm's needs, as ``kernel_cost.py`` counts
attention's): what the grouped matmuls of one sparse layer
(``moe/sharded_moe.grouped_moe_ffn`` over rows sorted by expert) must
compute and move: gate, up and down projections of SwiGLU experts
(``matrices`` 3), or up and down of ungated ones (``W_down act(W_up h)``:
``matrices`` 2, Nemotron's relu2 experts).

``layer_metrics/grouped_moe_roofline.rollout.json`` names
``grouped_moe_ffn_cost`` as ``moe_cost.grouped_moe_ffn_cost``
(``readers.cost_function``) with ``hidden``, ``width`` and ``matrices`` from
the cell's own file; ``roofline_share`` below is the same share for a
builder's own reduction of a traced run.
"""

from __future__ import annotations

from typing import Dict

from .kernel_cost import roofline_seconds


def grouped_moe_ffn_cost(rows: float, experts_hit: float, hidden: int,
                         width: int, itemsize: int = 2,
                         matrices: int = 3) -> Dict[str, float]:
    """One sparse layer's expert feed-forward over ``rows`` routed rows
    (tokens x experts per token) that reach ``experts_hit`` distinct
    experts of ``matrices`` matrices of width ``width``.

    FLOPs: every routed row goes through ``matrices`` [hidden x width]
    matmuls, 2 x hidden x width each. Bytes: the matrices of every expert
    that is hit are read once at the PUBLISHED ``width`` (a program that
    stores them wider streams more than this and reads a lower share), and
    every routed row is read once at the hidden width and written once at
    it (the [rows, width] intermediates between the matmuls need not leave
    the chip's fast memory and are not counted)."""
    return {"flops": 2.0 * matrices * rows * hidden * width,
            "bytes": float(matrices * experts_hit * hidden * width * itemsize
                           + 2 * rows * hidden * itemsize)}


def expected_experts_hit(rows: float, experts: int) -> float:
    """Distinct experts that ``rows`` uniformly routed rows reach."""
    return experts * (1.0 - (1.0 - 1.0 / experts) ** rows)


def roofline_share(seconds: float, calls: int, peak: Dict[str, float],
                   **shape) -> Dict[str, float]:
    """Share (%) of its roofline that ``calls`` sparse layers of one shape
    reached in ``seconds`` of device time, and which limit bounds it."""
    least = roofline_seconds(grouped_moe_ffn_cost(**shape), peak)
    return {"share": 100.0 * calls * least["seconds"] / seconds,
            "bound": least["bound"]}
