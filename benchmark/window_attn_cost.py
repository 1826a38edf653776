"""Operations and bytes decode attention needs in a model whose layers are
of TWO kinds, full and sliding-window, from its shapes alone (the
algorithm's needs, as ``kernel_cost.py`` counts one kind's): what the
paged decode kernel must read and compute a step over BOTH kinds of layer.

``layer_metrics/paged_attn_roofline.mellum2.json`` names
``mixed_decode_attention_cost`` as
``window_attn_cost.mixed_decode_attention_cost``
(``readers.cost_function``). A window layer's must-read rows are its
WINDOW'S (the engine's ``window_rows_live``), not its chain's: a program
that streamed a window layer's whole chain would do more than is counted
here and read a LOW share, never one over 100 %.
"""

from __future__ import annotations

from typing import Dict


def mixed_decode_attention_cost(full_rows: float, window_rows: float,
                                full_layers: int, window_layers: int,
                                q_heads: int, kv_heads: int, head_dim: int,
                                kv_itemsize: int = 2) -> Dict[str, float]:
    """Decode attention over ``full_layers`` layers that each read
    ``full_rows`` settled rows (the live contexts, a layer's worth:
    ``decode_kv_rows_live``) and ``window_layers`` layers that each read
    ``window_rows`` (the settled rows inside the live sequences' windows,
    a layer's worth: ``window_rows_live``), one query token a sequence.

    Bytes: every row a layer MUST read, its K and its V, once (the query,
    the output and the new row are 1/context of that). FLOPs: each query
    head a dot product and a weighted sum over the same rows."""
    rows = float(full_layers * full_rows + window_layers * window_rows)
    return {"flops": 4.0 * rows * q_heads * head_dim,
            "bytes": 2.0 * rows * kv_heads * head_dim * kv_itemsize}
