"""Pallas TPU kernels — the analogue of the reference's ``csrc/`` native op
families (SURVEY.md §2.6): fused attention (``csrc/transformer/``), fused
optimizers (``csrc/adam``, ``csrc/lamb``, ``csrc/lion``), group quantization
(``csrc/quantization/``), and fused norms (``csrc/transformer/inference``
layer_norm/rms_norm kernels).

Every kernel ships with a pure-jnp reference path. Dispatch: compiled Pallas on
TPU, interpreter/jnp elsewhere (so the CPU test mesh exercises identical code).
"""

import jax


def default_interpret() -> bool:
    """Pallas kernels compile only on TPU; interpret elsewhere (tests)."""
    return jax.default_backend() != "tpu"


from .flash_attention import (  # noqa: E402,F401
    flash_attention,
    flash_attention_sparse,
    sharded_flash_attention,
)
from .paged_attention import (  # noqa: E402,F401
    decode_rows_fetched, decode_rows_scored, decode_tile_rows,
    flash_paged_attention)
from .normalization import fused_layer_norm, fused_rms_norm  # noqa: E402,F401
from .quantization import (  # noqa: E402,F401
    dequantize_blockwise,
    quant_dequant,
    quantize_blockwise,
)
from .fused_optimizer import fused_adamw_update  # noqa: E402,F401
from .fused_xent import fused_lm_xent  # noqa: E402,F401
from .evoformer import evoformer_flash  # noqa: E402,F401
from .fp6_gemm import (  # noqa: E402,F401
    Fp6GemmWeight,
    fp6_gemm_pack,
    fp6_gemm_unpack,
    fp6_matmul,
)
