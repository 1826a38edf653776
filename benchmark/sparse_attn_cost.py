"""Operations and bytes block-selected attention needs, from its shapes
alone (the algorithm's needs, as ``kernel_cost.py`` counts dense
attention's): what one sparse layer of ``ops/kernels/sparse_attention.py``
must read a decode step and what a prefill chunk's selected blocks must
compute.

``layer_metrics/sparse_attn_roofline.sala.json`` names
``sparse_decode_attention_cost`` and
``layer_metrics/sparse_prefill_roofline.sala.json``
``sparse_prefill_attention_cost`` (``readers.cost_function``); a Lightning
layer's decode update is ``ssm_cost.mamba2_decode_cost`` at 96 sequences,
32 heads, 128 x 128 (``ssm_roofline.rollout``): the same recurrence at
``dt = 1``.
"""

from __future__ import annotations

from typing import Dict


def sparse_decode_attention_cost(rows_selected: float, layers: int,
                                 group: int, head_dim: int,
                                 itemsize: int = 2) -> Dict[str, float]:
    """Decode steps of block-selected attention that must read
    ``rows_selected`` key rows a sparse layer (one row = one position of
    ONE kv head: the engine's ``sparse_rows_selected``), over ``layers``
    such layers.

    Bytes: every selected row's key and value, ``head_dim`` wide, once.
    FLOPs: each of the kv head's ``group`` query heads scores the row and
    weighs its value, ``2 x head_dim`` each. The loop's ring rows, the
    queries and the outputs are left out (under a hundredth of the
    selected rows at 4,096 rows a list), so the share reads a little
    low."""
    rows = float(rows_selected) * layers
    return {"flops": 4.0 * rows * group * head_dim,
            "bytes": 2.0 * rows * head_dim * itemsize}


def sparse_prefill_attention_cost(blocks_selected: float, sel_block: int,
                                  group: int, head_dim: int,
                                  itemsize: int = 2) -> Dict[str, float]:
    """Prefill chunks whose real queries selected ``blocks_selected``
    blocks of ``sel_block`` keys (a block counted once a query and kv
    head, over all sparse layers: the engine's
    ``sparse_prefill_blocks_selected``).

    FLOPs: each of the kv head's ``group`` query heads scores every key of
    a selected block and weighs its value. Bytes: a selected block's keys
    and values at least once a QUERY TILE would be the kernel's; the
    algorithm's floor is once a chunk and is left at the blocks' bytes
    over the ``group`` heads that share them: compute-bound by two orders
    either way."""
    keys = float(blocks_selected) * sel_block
    return {"flops": 4.0 * keys * group * head_dim,
            "bytes": 2.0 * keys * head_dim * itemsize / group}
