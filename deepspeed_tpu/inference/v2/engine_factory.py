"""Engine factory — ``build_hf_engine`` parity.

The reference's flagship serving entry (``inference/v2/engine_factory.py:69``
``build_hf_engine``): point it at an HF checkpoint directory and get a
running ragged engine. Here: config.json → arch + model config (registry),
shards → param pytree (checkpoint/hf_loader), arch → ragged runner
(engine_v2 dispatch). Optional weight-only quantization applies the
reference's quantization-mode knob.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from ...checkpoint.hf_loader import load_hf_model
from ...utils.dtypes import resolve_dtype
from ...utils.logging import log_dist
from .config import RaggedInferenceConfig
from .engine_v2 import InferenceEngineV2

#: arches whose HF weights map exactly AND that have a ragged runner
_RAGGED_ARCHES = {"llama", "mistral", "qwen", "qwen2", "phi3", "phi", "gpt2",
                  "opt", "mixtral", "qwen2_moe", "olmoe", "bloom", "gpt_neox",
                  "gptj", "jamba"}


def build_hf_engine(model_dir: str,
                    engine_config: Optional[RaggedInferenceConfig] = None,
                    dtype: Optional[str] = None,
                    quantization_mode: Optional[str] = None,
                    strict: bool = True,
                    tp_size: Optional[int] = None,
                    draft_model_dir: Optional[str] = None
                    ) -> InferenceEngineV2:
    """Build a ragged inference engine from a HuggingFace checkpoint dir.

    ``quantization_mode``: None | "wf8" (int8 WOQ) | "wf4" (int4 WOQ) —
    mirrors the reference's quantization-mode string.
    ``tp_size``: tensor-parallel degree over the ``model`` mesh axis
    (overrides ``engine_config.tp_size`` — the reference's AutoTP-style
    one-knob entry; see docs/serving.md).
    ``draft_model_dir``: a config-paired small DRAFT checkpoint for
    speculative decoding (e.g. gpt2 drafting for llama — any of the
    served families; must share the target's tokenizer/vocab). The
    draft is attached via ``engine.attach_draft`` and used when
    ``spec_decode='draft'`` (docs/serving.md "Speculative decoding").
    """
    import json
    import os
    with open(os.path.join(model_dir, "config.json")) as f:
        arch_name = json.load(f).get("model_type", "").lower()
    if arch_name not in _RAGGED_ARCHES:
        # fail BEFORE reading the (possibly multi-GB) weight shards
        raise ValueError(
            f"architecture '{arch_name}' is not servable via build_hf_engine "
            f"(have {sorted(_RAGGED_ARCHES)}); load params yourself and use "
            "InferenceEngineV2 / the v1 engine / hybrid generate")
    arch, model_cfg, params = load_hf_model(model_dir, strict=strict)
    if dtype is not None:
        model_cfg = dataclasses.replace(model_cfg,
                                        dtype=resolve_dtype(dtype))
    if quantization_mode:
        bits = {"wf8": 8, "wf4": 4}.get(quantization_mode)
        if bits is None:
            raise ValueError(
                f"quantization_mode must be 'wf8' or 'wf4', "
                f"got {quantization_mode!r}")
        from ..quantization import quantize_model_params
        params = quantize_model_params(params, {"quantized_weights": {
            "enabled": True, "num_bits": bits,
            "modules": ["proj", "fc", "attn", "mlp"],
            "excluded_modules": ["embed", "wte", "wpe", "norm", "ln"]}})
    cfg = engine_config or RaggedInferenceConfig()
    if tp_size is not None:
        cfg = dataclasses.replace(cfg, tp_size=int(tp_size))
    engine = InferenceEngineV2(model_cfg, params, cfg)
    if draft_model_dir is not None:
        d_arch, d_cfg, d_params = load_hf_model(draft_model_dir,
                                                strict=strict)
        if dtype is not None:
            d_cfg = dataclasses.replace(d_cfg,
                                        dtype=resolve_dtype(dtype))
        engine.attach_draft(d_cfg, d_params)
        log_dist(f"build_hf_engine: draft pair {d_arch} from "
                 f"{draft_model_dir} (spec_decode={cfg.spec_decode})")
    log_dist(f"build_hf_engine: {arch} from {model_dir} "
             f"(quant={quantization_mode or 'off'}, tp={cfg.tp_size})")
    return engine
