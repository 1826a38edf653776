"""HuggingFace checkpoint loading — torch/safetensors → param pytrees.

Parity with the reference's checkpoint-ingestion surface: the v2 engine
factory streams HF shards (``inference/v2/checkpoint/huggingface_engine.py``,
``build_hf_engine``), v1 loads sharded ``.bin``/``.safetensors`` files
(``module_inject/load_checkpoint.py``, ``state_dict_factory.py``), and
SURVEY.md §7 hard-part 6 calls out torch-format interop explicitly.

Pieces:
  - a dependency-free **safetensors reader** (the format is a JSON header +
    raw little-endian tensor bytes — no torch needed);
  - a ``.bin`` path via ``torch.load`` (torch-cpu is available; weights are
    converted to numpy immediately);
  - per-architecture **name maps** from HF module paths to this framework's
    flax param paths, with the torch→flax transpose on linear kernels.

Entry points:
    state = load_hf_state_dict(model_dir)            # {hf_name: np.ndarray}
    params = convert_hf_state(arch, state)           # framework pytree
    arch, cfg, params = load_hf_model(model_dir)     # all of the above
"""

from __future__ import annotations

import json
import os
import re
import struct
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..utils.logging import log_dist, logger

_SAFETENSORS_DTYPES = {
    "F64": np.float64, "F32": np.float32, "F16": np.float16,
    "I64": np.int64, "I32": np.int32, "I16": np.int16, "I8": np.int8,
    "U8": np.uint8, "BOOL": np.bool_,
    # BF16 has no numpy dtype pre-ml_dtypes; widened to f32 on read
    "BF16": None,
}


def read_safetensors(path: str) -> Dict[str, np.ndarray]:
    """Minimal pure-python safetensors reader."""
    out = {}
    with open(path, "rb") as f:
        (hlen,) = struct.unpack("<Q", f.read(8))
        header = json.loads(f.read(hlen))
        base = 8 + hlen
        for name, meta in header.items():
            if name == "__metadata__":
                continue
            dt = meta["dtype"]
            if dt not in _SAFETENSORS_DTYPES:
                raise ValueError(f"unsupported safetensors dtype {dt}")
            start, end = meta["data_offsets"]
            f.seek(base + start)
            raw = f.read(end - start)
            if dt == "BF16":
                u16 = np.frombuffer(raw, dtype=np.uint16)
                arr = (u16.astype(np.uint32) << 16).view(np.float32)
            else:
                arr = np.frombuffer(raw, dtype=_SAFETENSORS_DTYPES[dt])
            out[name] = arr.reshape(meta["shape"]).copy()
    return out


def _read_torch_bin(path: str) -> Dict[str, np.ndarray]:
    import torch
    sd = torch.load(path, map_location="cpu", weights_only=True)
    return {k: v.to(torch.float32).numpy() if v.dtype == torch.bfloat16
            else v.numpy() for k, v in sd.items()}


def load_hf_state_dict(model_dir: str) -> Dict[str, np.ndarray]:
    """Read all weight shards of an HF checkpoint directory."""
    files = sorted(os.listdir(model_dir))
    shards = [f for f in files if f.endswith(".safetensors")]
    if shards:
        out = {}
        for s in shards:
            out.update(read_safetensors(os.path.join(model_dir, s)))
        return out
    bins = [f for f in files
            if f.endswith(".bin") and f.startswith("pytorch_model")]
    if bins:
        out = {}
        for b in bins:
            out.update(_read_torch_bin(os.path.join(model_dir, b)))
        return out
    raise FileNotFoundError(
        f"no .safetensors or pytorch_model*.bin shards in {model_dir}")


# --------------------------------------------------------------------------- #
# name mapping
# --------------------------------------------------------------------------- #

# HF-path regex -> (framework path template, kind)
# kind: "linear" (transpose [out,in]->[in,out]), "embed", "vector"
_LLAMA_MAP = [
    (r"model\.embed_tokens\.weight", "embed/embedding", "embed"),
    (r"model\.norm\.weight", "final_norm/scale", "vector"),
    (r"lm_head\.weight", "lm_head/kernel", "linear"),
    (r"model\.layers\.(\d+)\.input_layernorm\.weight",
     "layer_{0}/input_norm/scale", "vector"),
    (r"model\.layers\.(\d+)\.post_attention_layernorm\.weight",
     "layer_{0}/post_attn_norm/scale", "vector"),
    (r"model\.layers\.(\d+)\.self_attn\.(q|k|v|o)_proj\.weight",
     "layer_{0}/attn/{1}_proj/kernel", "linear"),
    (r"model\.layers\.(\d+)\.self_attn\.(q|k|v)_proj\.bias",
     "layer_{0}/attn/{1}_proj/bias", "vector"),
    (r"model\.layers\.(\d+)\.mlp\.(gate|up|down)_proj\.weight",
     "layer_{0}/mlp/{1}_proj/kernel", "linear"),
]

_OPT_MAP = [
    # .bin checkpoints carry lm_head.weight even when tied; load_hf_model
    # drops the mapped head for tie_embeddings configs
    (r"lm_head\.weight", "lm_head/kernel", "linear"),
    (r"(?:model\.)?decoder\.embed_tokens\.weight", "embed_tokens/embedding",
     "embed"),
    (r"(?:model\.)?decoder\.embed_positions\.weight",
     "embed_positions/embedding", "embed"),
    (r"(?:model\.)?decoder\.final_layer_norm\.(weight|bias)",
     "final_layer_norm/{w:scale,b:bias}", "vector"),
    (r"(?:model\.)?decoder\.project_in\.weight", "project_in/kernel",
     "linear"),
    (r"(?:model\.)?decoder\.project_out\.weight", "project_out/kernel",
     "linear"),
    (r"(?:model\.)?decoder\.layers\.(\d+)\.self_attn\.(q|k|v|out)_proj\.weight",
     "layer_{0}/self_attn/{1}_proj/kernel", "linear"),
    (r"(?:model\.)?decoder\.layers\.(\d+)\.self_attn\.(q|k|v|out)_proj\.bias",
     "layer_{0}/self_attn/{1}_proj/bias", "vector"),
    (r"(?:model\.)?decoder\.layers\.(\d+)\.self_attn_layer_norm\.(weight|bias)",
     "layer_{0}/self_attn_layer_norm/{w:scale,b:bias}", "vector"),
    (r"(?:model\.)?decoder\.layers\.(\d+)\.final_layer_norm\.(weight|bias)",
     "layer_{0}/final_layer_norm/{w:scale,b:bias}", "vector"),
    (r"(?:model\.)?decoder\.layers\.(\d+)\.fc(1|2)\.weight",
     "layer_{0}/fc{1}/kernel", "linear"),
    (r"(?:model\.)?decoder\.layers\.(\d+)\.fc(1|2)\.bias",
     "layer_{0}/fc{1}/bias", "vector"),
]

_GPT2_MAP = [
    (r"(?:transformer\.)?wte\.weight", "wte/embedding", "embed"),
    (r"(?:transformer\.)?wpe\.weight", "wpe/embedding", "embed"),
    (r"(?:transformer\.)?ln_f\.(weight|bias)",
     "ln_f/{w:scale,b:bias}", "vector"),
    # HF GPT-2 Conv1D weights are ALREADY [in, out] — no transpose
    (r"(?:transformer\.)?h\.(\d+)\.ln_(1|2)\.(weight|bias)",
     "h_{0}/ln_{1}/{w:scale,b:bias}", "vector"),
    (r"(?:transformer\.)?h\.(\d+)\.attn\.c_attn\.(weight|bias)",
     "h_{0}/attn/c_attn/{w:kernel,b:bias}", "conv1d"),
    (r"(?:transformer\.)?h\.(\d+)\.attn\.c_proj\.(weight|bias)",
     "h_{0}/attn/c_proj/{w:kernel,b:bias}", "conv1d"),
    (r"(?:transformer\.)?h\.(\d+)\.mlp\.c_fc\.(weight|bias)",
     "h_{0}/mlp/c_fc/{w:kernel,b:bias}", "conv1d"),
    (r"(?:transformer\.)?h\.(\d+)\.mlp\.c_proj\.(weight|bias)",
     "h_{0}/mlp/c_proj/{w:kernel,b:bias}", "conv1d"),
]

_GPT_NEO_MAP = [
    # GPT-Neo (reference module_inject/containers/gptneo.py): unfused
    # torch Linears (transposed on load), bias-free q/k/v, tied head
    (r"(?:transformer\.)?wte\.weight", "wte/embedding", "embed"),
    (r"(?:transformer\.)?wpe\.weight", "wpe/embedding", "embed"),
    (r"(?:transformer\.)?ln_f\.(weight|bias)",
     "ln_f/{w:scale,b:bias}", "vector"),
    (r"lm_head\.weight", "lm_head/kernel", "linear"),  # dropped when tied
    (r"(?:transformer\.)?h\.(\d+)\.ln_(1|2)\.(weight|bias)",
     "h_{0}/ln_{1}/{w:scale,b:bias}", "vector"),
    (r"(?:transformer\.)?h\.(\d+)\.attn\.attention\.(q|k|v|out)_proj\.weight",
     "h_{0}/{1}_proj/kernel", "linear"),
    (r"(?:transformer\.)?h\.(\d+)\.attn\.attention\.out_proj\.bias",
     "h_{0}/out_proj/bias", "vector"),
    (r"(?:transformer\.)?h\.(\d+)\.mlp\.c_fc\.(weight|bias)",
     "h_{0}/c_fc/{w:kernel,b:bias}", "linear"),
    (r"(?:transformer\.)?h\.(\d+)\.mlp\.c_proj\.(weight|bias)",
     "h_{0}/c_proj/{w:kernel,b:bias}", "linear"),
]


_DISTILBERT_MAP = [
    # DistilBERT (reference module_inject/containers/distil_bert.py):
    # BERT encoder without token types, pooler-free, tied MLM head
    (r"distilbert\.embeddings\.word_embeddings\.weight",
     "word_embeddings/embedding", "embed"),
    (r"distilbert\.embeddings\.position_embeddings\.weight",
     "position_embeddings/embedding", "embed"),
    (r"distilbert\.embeddings\.LayerNorm\.(weight|bias)",
     "embed_norm/{w:scale,b:bias}", "vector"),
    (r"distilbert\.transformer\.layer\.(\d+)\.attention\.q_lin\.(weight|bias)",
     "layer_{0}/query/{w:kernel,b:bias}", "linear"),
    (r"distilbert\.transformer\.layer\.(\d+)\.attention\.k_lin\.(weight|bias)",
     "layer_{0}/key/{w:kernel,b:bias}", "linear"),
    (r"distilbert\.transformer\.layer\.(\d+)\.attention\.v_lin\.(weight|bias)",
     "layer_{0}/value/{w:kernel,b:bias}", "linear"),
    (r"distilbert\.transformer\.layer\.(\d+)\.attention\.out_lin\.(weight|bias)",
     "layer_{0}/attn_out/{w:kernel,b:bias}", "linear"),
    (r"distilbert\.transformer\.layer\.(\d+)\.sa_layer_norm\.(weight|bias)",
     "layer_{0}/attn_norm/{w:scale,b:bias}", "vector"),
    (r"distilbert\.transformer\.layer\.(\d+)\.ffn\.lin1\.(weight|bias)",
     "layer_{0}/intermediate/{w:kernel,b:bias}", "linear"),
    (r"distilbert\.transformer\.layer\.(\d+)\.ffn\.lin2\.(weight|bias)",
     "layer_{0}/output/{w:kernel,b:bias}", "linear"),
    (r"distilbert\.transformer\.layer\.(\d+)\.output_layer_norm\.(weight|bias)",
     "layer_{0}/out_norm/{w:scale,b:bias}", "vector"),
    (r"vocab_transform\.(weight|bias)",
     "mlm_transform/{w:kernel,b:bias}", "linear"),
    (r"vocab_layer_norm\.(weight|bias)", "mlm_norm/{w:scale,b:bias}",
     "vector"),
    (r"vocab_projector\.bias", "mlm_bias", "vector"),
    # vocab_projector.weight is the tied word embedding: skipped below
]


_PHI_MAP = [
    (r"model\.embed_tokens\.weight", "embed_tokens/embedding", "embed"),
    (r"model\.final_layernorm\.(weight|bias)",
     "final_layernorm/{w:scale,b:bias}", "vector"),
    (r"lm_head\.weight", "lm_head/kernel", "linear"),
    (r"lm_head\.bias", "lm_head/bias", "vector"),
    (r"model\.layers\.(\d+)\.input_layernorm\.(weight|bias)",
     "layer_{0}/input_layernorm/{w:scale,b:bias}", "vector"),
    (r"model\.layers\.(\d+)\.self_attn\.(q|k|v)_proj\.weight",
     "layer_{0}/self_attn/{1}_proj/kernel", "linear"),
    (r"model\.layers\.(\d+)\.self_attn\.(q|k|v)_proj\.bias",
     "layer_{0}/self_attn/{1}_proj/bias", "vector"),
    (r"model\.layers\.(\d+)\.self_attn\.dense\.weight",
     "layer_{0}/self_attn/dense/kernel", "linear"),
    (r"model\.layers\.(\d+)\.self_attn\.dense\.bias",
     "layer_{0}/self_attn/dense/bias", "vector"),
    (r"model\.layers\.(\d+)\.mlp\.fc(1|2)\.weight",
     "layer_{0}/fc{1}/kernel", "linear"),
    (r"model\.layers\.(\d+)\.mlp\.fc(1|2)\.bias",
     "layer_{0}/fc{1}/bias", "vector"),
]

_BLOOM_MAP = [
    (r"lm_head\.weight", "lm_head/kernel", "linear"),   # untied variants
    (r"(?:transformer\.)?word_embeddings\.weight",
     "word_embeddings/embedding", "embed"),
    (r"(?:transformer\.)?word_embeddings_layernorm\.(weight|bias)",
     "word_embeddings_layernorm/{w:scale,b:bias}", "vector"),
    (r"(?:transformer\.)?ln_f\.(weight|bias)", "ln_f/{w:scale,b:bias}",
     "vector"),
    (r"(?:transformer\.)?h\.(\d+)\.(input|post_attention)_layernorm\.(weight|bias)",
     "layer_{0}/{1}_layernorm/{w:scale,b:bias}", "vector"),
    (r"(?:transformer\.)?h\.(\d+)\.self_attention\.(q|k|v)_proj\.weight",
     "layer_{0}/self_attention/{1}_proj/kernel", "linear"),
    (r"(?:transformer\.)?h\.(\d+)\.self_attention\.(q|k|v)_proj\.bias",
     "layer_{0}/self_attention/{1}_proj/bias", "vector"),
    (r"(?:transformer\.)?h\.(\d+)\.self_attention\.dense\.weight",
     "layer_{0}/self_attention/dense/kernel", "linear"),
    (r"(?:transformer\.)?h\.(\d+)\.self_attention\.dense\.bias",
     "layer_{0}/self_attention/dense/bias", "vector"),
    (r"(?:transformer\.)?h\.(\d+)\.mlp\.dense_(h_to_4h|4h_to_h)\.weight",
     "layer_{0}/dense_{1}/kernel", "linear"),
    (r"(?:transformer\.)?h\.(\d+)\.mlp\.dense_(h_to_4h|4h_to_h)\.bias",
     "layer_{0}/dense_{1}/bias", "vector"),
]

_NEOX_MAP = [
    (r"gpt_neox\.embed_in\.weight", "embed_in/embedding", "embed"),
    (r"gpt_neox\.final_layer_norm\.(weight|bias)",
     "final_layer_norm/{w:scale,b:bias}", "vector"),
    (r"embed_out\.weight", "embed_out/kernel", "linear"),
    (r"gpt_neox\.layers\.(\d+)\.(input|post_attention)_layernorm\.(weight|bias)",
     "layer_{0}/{1}_layernorm/{w:scale,b:bias}", "vector"),
    (r"gpt_neox\.layers\.(\d+)\.attention\.(q|k|v)_proj\.weight",
     "layer_{0}/{1}_proj/kernel", "linear"),
    (r"gpt_neox\.layers\.(\d+)\.attention\.(q|k|v)_proj\.bias",
     "layer_{0}/{1}_proj/bias", "vector"),
    (r"gpt_neox\.layers\.(\d+)\.attention\.dense\.weight",
     "layer_{0}/dense/kernel", "linear"),
    (r"gpt_neox\.layers\.(\d+)\.attention\.dense\.bias",
     "layer_{0}/dense/bias", "vector"),
    (r"gpt_neox\.layers\.(\d+)\.mlp\.dense_(h_to_4h|4h_to_h)\.weight",
     "layer_{0}/dense_{1}/kernel", "linear"),
    (r"gpt_neox\.layers\.(\d+)\.mlp\.dense_(h_to_4h|4h_to_h)\.bias",
     "layer_{0}/dense_{1}/bias", "vector"),
]

_GPTJ_MAP = [
    (r"transformer\.wte\.weight", "wte/embedding", "embed"),
    (r"transformer\.ln_f\.(weight|bias)", "ln_f/{w:scale,b:bias}", "vector"),
    (r"lm_head\.weight", "lm_head/kernel", "linear"),
    (r"lm_head\.bias", "lm_head/bias", "vector"),
    (r"transformer\.h\.(\d+)\.ln_1\.(weight|bias)",
     "layer_{0}/ln_1/{w:scale,b:bias}", "vector"),
    (r"transformer\.h\.(\d+)\.attn\.(q|k|v|out)_proj\.weight",
     "layer_{0}/{1}_proj/kernel", "linear"),
    (r"transformer\.h\.(\d+)\.mlp\.fc_(in|out)\.weight",
     "layer_{0}/fc_{1}/kernel", "linear"),
    (r"transformer\.h\.(\d+)\.mlp\.fc_(in|out)\.bias",
     "layer_{0}/fc_{1}/bias", "vector"),
]

ARCH_MAPS = {
    "llama": _LLAMA_MAP,
    "mistral": _LLAMA_MAP,
    "qwen": _LLAMA_MAP,    # v1: fused names pre-split by _split_qwen_fused
    "qwen2": _LLAMA_MAP,
    "bloom": _BLOOM_MAP,   # fused qkv pre-split by _split_headwise_qkv
    "gpt_neox": _NEOX_MAP,
    "gptj": _GPTJ_MAP,
    "phi3": _LLAMA_MAP,
    "phi": _PHI_MAP,
    "opt": _OPT_MAP,
    "gpt2": _GPT2_MAP,
    "gpt_neo": _GPT_NEO_MAP,
    "distilbert": _DISTILBERT_MAP,
}


def _split_phi3_fused(state: Dict[str, np.ndarray],
                      hf_cfg: Dict) -> Dict[str, np.ndarray]:
    """Phi-3 stores fused qkv_proj / gate_up_proj; split them to the
    llama-style unfused names so _LLAMA_MAP applies (same math)."""
    heads = int(hf_cfg["num_attention_heads"])
    kv = int(hf_cfg.get("num_key_value_heads", heads))
    hidden = int(hf_cfg["hidden_size"])
    d = hidden // heads
    out = {}
    for name, arr in state.items():
        m = re.match(r"(model\.layers\.\d+\.self_attn)\.qkv_proj\.weight$",
                     name)
        if m:
            q, k, v = np.split(arr, [heads * d, heads * d + kv * d], axis=0)
            out[f"{m.group(1)}.q_proj.weight"] = q
            out[f"{m.group(1)}.k_proj.weight"] = k
            out[f"{m.group(1)}.v_proj.weight"] = v
            continue
        m = re.match(r"(model\.layers\.\d+\.mlp)\.gate_up_proj\.weight$",
                     name)
        if m:
            gate, up = np.split(arr, 2, axis=0)
            out[f"{m.group(1)}.gate_proj.weight"] = gate
            out[f"{m.group(1)}.up_proj.weight"] = up
            continue
        out[name] = arr
    return out


def _stack_moe_experts(state: Dict[str, np.ndarray], hf_cfg: Dict,
                       expert_re: str, gate_name: str, up_name: str,
                       down_name: str, prefix_out: str
                       ) -> Dict[str, np.ndarray]:
    """Assemble per-expert SwiGLU triples into the framework's stacked
    [E, M, H] / [E, H, M] tensors (pre-transposed: mapped with kind
    'stacked', no further transpose)."""
    out = {}
    experts: Dict[Tuple[int, str], Dict[int, np.ndarray]] = {}
    rx = re.compile(expert_re)
    for name, arr in state.items():
        m = rx.match(name)
        if not m:
            out[name] = arr
            continue
        layer, eidx, which = int(m.group(1)), int(m.group(2)), m.group(3)
        experts.setdefault((layer, which), {})[eidx] = arr
    for (layer, which), tensors in experts.items():
        stacked = np.stack([tensors[i] for i in range(len(tensors))])
        # HF per-expert weights are [out, in]; stacked layout wants
        # wi*: [E, M, H] (in, out) and wo: [E, H, M] (in, out)
        stacked = stacked.transpose(0, 2, 1)
        kind = {gate_name: "wi_gate", up_name: "wi_up",
                down_name: "wo"}[which]
        out[f"{prefix_out}.{layer}.moe_stacked.{kind}"] = stacked
    return out


def _mixtral_experts(state, hf_cfg):
    return _stack_moe_experts(
        state, hf_cfg,
        r"model\.layers\.(\d+)\.block_sparse_moe\.experts\.(\d+)\.(w1|w2|w3)\.weight$",
        gate_name="w1", up_name="w3", down_name="w2",
        prefix_out="model.layers")


def _qwen2_moe_experts(state, hf_cfg):
    return _stack_moe_experts(
        state, hf_cfg,
        r"model\.layers\.(\d+)\.mlp\.experts\.(\d+)\."
        r"(gate_proj|up_proj|down_proj)\.weight$",
        gate_name="gate_proj", up_name="up_proj", down_name="down_proj",
        prefix_out="model.layers")


#: pre-conversion transforms keyed by arch (fused-tensor splitting,
#: per-expert stacking)
def _split_qwen_fused(state: Dict[str, np.ndarray],
                      hf_cfg: Dict) -> Dict[str, np.ndarray]:
    """Qwen v1 (model_type "qwen", the original Qwen-7B layout — reference
    inference/v2/model_implementations/qwen/): fused ``c_attn`` qkv and
    ``w1``/``w2``/``c_proj`` SwiGLU rename to llama-style unfused names so
    _LLAMA_MAP applies. Qwen's MLP is ``c_proj(w1(x) * silu(w2(x)))`` —
    w2 is the gate (silu branch), w1 the up projection."""
    out: Dict[str, np.ndarray] = {}
    H = int(hf_cfg["hidden_size"])
    for name, arr in state.items():
        n = name.replace("transformer.h.", "model.layers.")
        if n.endswith(".attn.c_attn.weight") or \
                n.endswith(".attn.c_attn.bias"):
            base = n[:n.index(".attn.c_attn.")]
            leaf = name.split(".")[-1]
            q, k, v = arr[:H], arr[H:2 * H], arr[2 * H:]
            out[f"{base}.self_attn.q_proj.{leaf}"] = q
            out[f"{base}.self_attn.k_proj.{leaf}"] = k
            out[f"{base}.self_attn.v_proj.{leaf}"] = v
        elif ".attn.c_proj." in n:
            # weight + bias (bias only exists when no_bias=False; the
            # shipped Qwen-7B uses no_bias=True so usually weight-only)
            out[n.replace(".attn.c_proj.", ".self_attn.o_proj.")] = arr
        elif ".mlp.w2." in n:                       # silu branch = gate
            out[n.replace(".mlp.w2.", ".mlp.gate_proj.")] = arr
        elif ".mlp.w1." in n:                       # multiplicative branch
            out[n.replace(".mlp.w1.", ".mlp.up_proj.")] = arr
        elif ".mlp.c_proj." in n:
            out[n.replace(".mlp.c_proj.", ".mlp.down_proj.")] = arr
        elif ".ln_1." in n:
            out[n.replace(".ln_1.", ".input_layernorm.")] = arr
        elif ".ln_2." in n:
            out[n.replace(".ln_2.", ".post_attention_layernorm.")] = arr
        elif name.endswith("transformer.wte.weight"):
            out["model.embed_tokens.weight"] = arr
        elif name.endswith("transformer.ln_f.weight"):
            out["model.norm.weight"] = arr
        else:
            out[n] = arr                            # lm_head etc.
    return out


def _split_headwise_qkv(state: Dict[str, np.ndarray], hf_cfg: Dict,
                        fused_suffix: str) -> Dict[str, np.ndarray]:
    """BLOOM / GPT-NeoX fused ``query_key_value`` is PER-HEAD interleaved:
    rows ordered (head, [q k v], head_dim). Split into q/k/v projections
    (reference containers do the same de-interleave when injecting —
    module_inject/containers/bloom.py, gptneox.py)."""
    heads = int(hf_cfg.get("n_head", hf_cfg.get("num_attention_heads")))
    out: Dict[str, np.ndarray] = {}
    for name, arr in state.items():
        if f"{fused_suffix}.weight" in name or f"{fused_suffix}.bias" in name:
            base = name[:name.index(fused_suffix)]
            leaf = name.split(".")[-1]
            hd3 = arr.shape[0]
            D = hd3 // (3 * heads)
            a = arr.reshape((heads, 3, D) + arr.shape[1:])
            for j, which in enumerate("qkv"):
                out[f"{base}{which}_proj.{leaf}"] = np.ascontiguousarray(
                    a[:, j].reshape((heads * D,) + arr.shape[1:]))
        else:
            out[name] = arr
    return out


def _split_bloom_fused(state, hf_cfg):
    return _split_headwise_qkv(state, hf_cfg, "query_key_value")


def _split_neox_fused(state, hf_cfg):
    return _split_headwise_qkv(state, hf_cfg, "query_key_value")


def _pangu_ultra_moe_names(state, hf_cfg):
    """pangu_ultra_moe: per-expert stacking as qwen2-moe names them, and
    the multi-token-prediction modules, which the checkpoint stores as
    layers ``num_hidden_layers ..`` (the DeepSeek-V3 convention), renamed
    ``model.mtp.<i>`` so that ``_PANGU_ULTRA_MOE_MAP`` can tell them from
    the decoder's layers."""
    n = int(hf_cfg.get("num_hidden_layers", 61))
    out = {}
    for name, arr in _qwen2_moe_experts(state, hf_cfg).items():
        m = re.match(r"model\.layers\.(\d+)\.(.*)", name)
        if m and int(m.group(1)) >= n:
            name = f"model.mtp.{int(m.group(1)) - n}.{m.group(2)}"
        out[name] = arr
    return out


def _kimi_linear_names(state, hf_cfg):
    """kimi_linear: per-expert stacking as mixtral names them (``w1`` the
    gate, ``w3`` the up, ``w2`` the down projection), and the recurrent
    layers' mixer, which the checkpoint keeps under ``self_attn`` like the
    latent layers', renamed ``model.layers.<i>.kda`` for the layers
    ``linear_attn_config.kda_layers`` names (1-based), with its tensors
    brought to this tree's shapes: a convolution ``[C, 1, K]`` to
    ``[K, C]``, ``A_log`` ``[1, 1, H, 1]`` to ``[H]``."""
    kda = {int(i) - 1 for i in
           (hf_cfg.get("linear_attn_config") or {}).get("kda_layers", ())}
    out = {}
    for name, arr in _mixtral_experts(state, hf_cfg).items():
        m = re.match(r"model\.layers\.(\d+)\.self_attn\.(.*)", name)
        if m and int(m.group(1)) in kda:
            name = f"model.layers.{m.group(1)}.kda.{m.group(2)}"
            if "_conv1d." in name:
                arr = arr.reshape(arr.shape[0], arr.shape[-1]).T
            elif name.endswith(".A_log"):
                arr = arr.reshape(-1)
        out[name] = arr
    return out


def _nemotron_h_names(state, hf_cfg):
    """nemotron_h: every block is ``backbone.layers.<i>.{norm, mixer}``
    whatever its kind; the pattern's letter says which branch the norm
    stands before (``input_layernorm`` before a mixer,
    ``post_attention_layernorm`` before a feed-forward) and what ``mixer``
    is: attention (``self_attn``), Mamba-2 (``mamba``, its convolution
    ``[C, 1, K]`` brought to ``[K, C]``) or the sparse feed-forward, whose
    two matrices an expert are stacked and stored at the width rounded up
    to whole 128-lane groups (zeros; ``models/nemotron_h.py`` says why)."""
    from ..models.nemotron_h import pad_experts
    pattern = hf_cfg["hybrid_override_pattern"]
    renamed = {}
    for name, arr in state.items():
        name = re.sub(r"^backbone\.embeddings\.", "model.embed_tokens.", name)
        name = re.sub(r"^backbone\.norm_f\.", "model.norm.", name)
        m = re.match(r"backbone\.layers\.(\d+)\.(norm|mixer)\.(.*)", name)
        if m:
            i, part, rest = int(m.group(1)), m.group(2), m.group(3)
            letter = pattern[i]
            if part == "norm":
                rest = ("post_attention_layernorm." if letter == "E"
                        else "input_layernorm.") + rest
            elif letter == "*":
                rest = "self_attn." + rest
            elif letter == "M":
                if rest == "conv1d.weight":
                    arr = arr.reshape(arr.shape[0], arr.shape[-1]).T
                rest = "mamba." + rest
            else:
                rest = "moe." + rest
            name = f"model.layers.{i}.{rest}"
        renamed[name] = arr
    out = _stack_moe_experts(
        renamed, hf_cfg,
        r"model\.layers\.(\d+)\.moe\.experts\.(\d+)\."
        r"(up_proj|down_proj)\.weight$",
        gate_name="", up_name="up_proj", down_name="down_proj",
        prefix_out="model.layers")
    for name in [n for n in out if n.endswith("moe_stacked.wi_up")]:
        base = name[:-len("wi_up")]
        out[base + "wi"], out[base + "wo"] = pad_experts(
            out.pop(name), out.pop(base + "wo"))
    return out


def _jamba_names(state, hf_cfg):
    """jamba: a Mamba layer's convolution ``[C, 1, K]`` brought to
    ``[K, C]`` and its ``A_log`` ``[channels, state]`` to ``[state,
    channels]``, as the state pool lays a state out
    (``models/jamba.py``)."""
    out = {}
    for name, arr in state.items():
        if name.endswith(".mamba.conv1d.weight"):
            arr = arr.reshape(arr.shape[0], arr.shape[-1]).T
        elif name.endswith(".mamba.A_log"):
            arr = arr.T
        out[name] = arr
    return out


def _minicpm_sala_names(state, hf_cfg):
    """minicpm_sala: both kinds of mixer sit under ``self_attn`` in the
    checkpoint; ``mixer_types`` says which a layer's is, and a Lightning
    layer's tensors move to ``lightning.`` so that the map can tell them
    apart (its tree is ``layer_i/lin``)."""
    mixers = hf_cfg["mixer_types"]
    out = {}
    for name, arr in state.items():
        m = re.match(r"model\.layers\.(\d+)\.self_attn\.(.*)", name)
        if m and mixers[int(m.group(1))] == "lightning-attn":
            name = f"model.layers.{m.group(1)}.lightning.{m.group(2)}"
        out[name] = arr
    return out


SPECIAL_HANDLERS = {
    "pangu_ultra_moe": _pangu_ultra_moe_names,
    "nemotron_h": _nemotron_h_names,
    "jamba": _jamba_names,
    "kimi_linear": _kimi_linear_names,
    "phi3": _split_phi3_fused,
    "qwen": _split_qwen_fused,
    "bloom": _split_bloom_fused,
    "gpt_neox": _split_neox_fused,
    "mixtral": _mixtral_experts,
    "qwen2_moe": _qwen2_moe_experts,
    "olmoe": _qwen2_moe_experts,     # the same per-expert names
    "mellum": _qwen2_moe_experts,    # assumed: Qwen3MoE's (as its config keys)
    "minicpm_sala": _minicpm_sala_names,
}

_MOE_STACKED_RULES = [
    (r"model\.layers\.(\d+)\.moe_stacked\.(wi_gate|wi_up|wo)",
     "layer_{0}/moe/{1}", "stacked"),
]

_MIXTRAL_MAP = _LLAMA_MAP + _MOE_STACKED_RULES + [
    (r"model\.layers\.(\d+)\.block_sparse_moe\.gate\.weight",
     "layer_{0}/moe/gate", "linear"),
]

_QWEN2_MOE_MAP = _LLAMA_MAP + _MOE_STACKED_RULES + [
    (r"model\.layers\.(\d+)\.mlp\.gate\.weight",
     "layer_{0}/moe/gate", "linear"),
    (r"model\.layers\.(\d+)\.mlp\.shared_expert\.(gate|up|down)_proj\.weight",
     "layer_{0}/shared_{1}_proj/kernel", "linear"),
    (r"model\.layers\.(\d+)\.mlp\.shared_expert_gate\.weight",
     "layer_{0}/shared_expert_gate/kernel", "linear"),
]

_OLMOE_MAP = _LLAMA_MAP + _MOE_STACKED_RULES + [
    (r"model\.layers\.(\d+)\.mlp\.gate\.weight",
     "layer_{0}/moe/gate", "linear"),
    (r"model\.layers\.(\d+)\.self_attn\.(q|k)_norm\.weight",
     "layer_{0}/attn/{1}_norm/scale", "vector"),
]

def _pangu_layer_rules(hf: str, fw: str):
    """One decoder block's names under the checkpoint prefix ``hf`` and
    the tree prefix ``fw`` (a layer, or an MTP module's block). The
    sandwich norms: ``post_attention_layernorm`` norms the attention
    branch's OUTPUT and ``pre_mlp_layernorm`` is the norm before the
    feed-forward, which this tree calls ``post_attn_norm`` as every
    Llama-family tree does."""
    return [
        (hf + r"\.input_layernorm\.weight", fw + "/input_norm/scale",
         "vector"),
        (hf + r"\.post_attention_layernorm\.weight",
         fw + "/attn_branch_norm/scale", "vector"),
        (hf + r"\.pre_mlp_layernorm\.weight", fw + "/post_attn_norm/scale",
         "vector"),
        (hf + r"\.post_mlp_layernorm\.weight",
         fw + "/mlp_branch_norm/scale", "vector"),
        (hf + r"\.self_attn\.(q_a|q_b|kv_b|o)_proj\.weight",
         fw + "/attn/{1}_proj/kernel", "linear"),
        (hf + r"\.self_attn\.kv_a_proj_with_mqa\.weight",
         fw + "/attn/kv_a_proj/kernel", "linear"),
        (hf + r"\.self_attn\.(q_a|kv_a)_layernorm\.weight",
         fw + "/attn/{1}_norm/scale", "vector"),
        (hf + r"\.mlp\.(gate|up|down)_proj\.weight",
         fw + "/mlp/{1}_proj/kernel", "linear"),
        (hf + r"\.mlp\.gate\.weight", fw + "/moe/gate", "linear"),
        (hf + r"\.mlp\.shared_experts\.(gate|up|down)_proj\.weight",
         fw + "/shared_{1}_proj/kernel", "linear"),
        (hf + r"\.moe_stacked\.(wi_gate|wi_up|wo)", fw + "/moe/{1}",
         "stacked"),
    ]


_PANGU_ULTRA_MOE_MAP = _LLAMA_MAP[:3] \
    + _pangu_layer_rules(r"model\.layers\.(\d+)", "layer_{0}") \
    + _pangu_layer_rules(r"model\.mtp\.(\d+)", "mtp_{0}/block") + [
        (r"model\.mtp\.(\d+)\.(enorm|hnorm)\.weight", "mtp_{0}/{1}/scale",
         "vector"),
        (r"model\.mtp\.(\d+)\.eh_proj\.weight", "mtp_{0}/eh_proj/kernel",
         "linear"),
        (r"model\.mtp\.(\d+)\.shared_head\.norm\.weight",
         "mtp_{0}/final_norm/scale", "vector"),
    ]

#: the recurrent layers' tensors are bare arrays in the tree (a ``kda``
#: dict of ``models/solar_open2.py::KDAMixer``'s names); the latent layers'
#: are flax Dense kernels under ``attn``
_KIMI_LINEAR_MAP = _LLAMA_MAP[:5] + _MOE_STACKED_RULES + [
    (r"model\.layers\.(\d+)\.kda\.(q|k|v|o|b)_proj\.weight",
     "layer_{0}/kda/{1}_proj", "linear"),
    (r"model\.layers\.(\d+)\.kda\.(f|g)_(a|b)_proj\.weight",
     "layer_{0}/kda/{1}_{2}", "linear"),
    (r"model\.layers\.(\d+)\.kda\.(q|k|v)_conv1d\.weight",
     "layer_{0}/kda/{1}_conv", "vector"),
    (r"model\.layers\.(\d+)\.kda\.(A_log|dt_bias)",
     "layer_{0}/kda/{1}", "vector"),
    (r"model\.layers\.(\d+)\.kda\.o_norm\.weight",
     "layer_{0}/kda/o_norm", "vector"),
    (r"model\.layers\.(\d+)\.self_attn\.(q|kv_b|o)_proj\.weight",
     "layer_{0}/attn/{1}_proj/kernel", "linear"),
    (r"model\.layers\.(\d+)\.self_attn\.kv_a_proj_with_mqa\.weight",
     "layer_{0}/attn/kv_a_proj/kernel", "linear"),
    (r"model\.layers\.(\d+)\.self_attn\.kv_a_layernorm\.weight",
     "layer_{0}/attn/kv_a_norm/scale", "vector"),
    (r"model\.layers\.(\d+)\.mlp\.(gate|up|down)_proj\.weight",
     "layer_{0}/mlp/{1}_proj/kernel", "linear"),
    (r"model\.layers\.(\d+)\.block_sparse_moe\.gate\.weight",
     "layer_{0}/moe/gate", "linear"),
    (r"model\.layers\.(\d+)\.block_sparse_moe\.gate"
     r"\.e_score_correction_bias", "layer_{0}/moe/sel_bias", "vector"),
    (r"model\.layers\.(\d+)\.block_sparse_moe\.shared_experts"
     r"\.(gate|up|down)_proj\.weight",
     "layer_{0}/shared_{1}_proj/kernel", "linear"),
]

#: a state-space layer's tensors are bare arrays in the tree (a ``mamba``
#: dict of ``models/nemotron_h.py::Mamba2Mixer``'s names); the names are
#: :func:`_nemotron_h_names`'
_NEMOTRON_H_MAP = _LLAMA_MAP[:6] + [
    (r"model\.layers\.(\d+)\.mamba\.(in|out)_proj\.weight",
     "layer_{0}/mamba/{1}_proj", "linear"),
    (r"model\.layers\.(\d+)\.mamba\.conv1d\.weight",
     "layer_{0}/mamba/conv_w", "vector"),
    (r"model\.layers\.(\d+)\.mamba\.conv1d\.bias",
     "layer_{0}/mamba/conv_b", "vector"),
    (r"model\.layers\.(\d+)\.mamba\.(A_log|D|dt_bias)",
     "layer_{0}/mamba/{1}", "vector"),
    (r"model\.layers\.(\d+)\.mamba\.norm\.weight",
     "layer_{0}/mamba/norm", "vector"),
    (r"model\.layers\.(\d+)\.moe\.gate\.weight",
     "layer_{0}/moe/gate", "linear"),
    (r"model\.layers\.(\d+)\.moe\.gate\.e_score_correction_bias",
     "layer_{0}/moe/sel_bias", "vector"),
    (r"model\.layers\.(\d+)\.moe_stacked\.(wi|wo)",
     "layer_{0}/moe/{1}", "stacked"),
    (r"model\.layers\.(\d+)\.moe\.shared_experts\.(up|down)_proj\.weight",
     "layer_{0}/shared_{1}_proj/kernel", "linear"),
]

#: assumed names (the family's modelling file, from memory: a layer's
#: feed-forward under ``feed_forward``, its second norm ``pre_ff_layernorm``,
#: the last norm ``final_layernorm``, the three inner norms
#: ``{dt,b,c}_layernorm``); a Mamba layer's tensors are bare arrays in the
#: tree (``models/jamba.py::Mamba1Mixer``'s names), brought to its shapes by
#: :func:`_jamba_names`
_JAMBA_MAP = [
    (r"model\.embed_tokens\.weight", "embed/embedding", "embed"),
    (r"model\.final_layernorm\.weight", "final_norm/scale", "vector"),
    (r"lm_head\.weight", "lm_head/kernel", "linear"),
    (r"model\.layers\.(\d+)\.input_layernorm\.weight",
     "layer_{0}/input_norm/scale", "vector"),
    (r"model\.layers\.(\d+)\.pre_ff_layernorm\.weight",
     "layer_{0}/post_attn_norm/scale", "vector"),
    (r"model\.layers\.(\d+)\.self_attn\.(q|k|v|o)_proj\.weight",
     "layer_{0}/attn/{1}_proj/kernel", "linear"),
    (r"model\.layers\.(\d+)\.feed_forward\.(gate|up|down)_proj\.weight",
     "layer_{0}/mlp/{1}_proj/kernel", "linear"),
    (r"model\.layers\.(\d+)\.mamba\.(in|x|dt|out)_proj\.weight",
     "layer_{0}/mamba/{1}_proj", "linear"),
    (r"model\.layers\.(\d+)\.mamba\.dt_proj\.bias",
     "layer_{0}/mamba/dt_bias", "vector"),
    (r"model\.layers\.(\d+)\.mamba\.conv1d\.weight",
     "layer_{0}/mamba/conv_w", "vector"),
    (r"model\.layers\.(\d+)\.mamba\.conv1d\.bias",
     "layer_{0}/mamba/conv_b", "vector"),
    (r"model\.layers\.(\d+)\.mamba\.(A_log|D)",
     "layer_{0}/mamba/{1}", "vector"),
    (r"model\.layers\.(\d+)\.mamba\.(dt|b|c)_layernorm\.weight",
     "layer_{0}/mamba/{1}_norm", "vector"),
]

#: assumed names (the family's MiniCPM4 code for the sparse layers, with
#: ``o_gate`` for its output gate; ``z_proj`` and ``o_norm`` for a Lightning
#: layer's gate and output norm): the catalog row has the config's keys, not
#: the checkpoint's
_MINICPM_SALA_MAP = _LLAMA_MAP + [
    (r"model\.layers\.(\d+)\.self_attn\.(q|k)_norm\.weight",
     "layer_{0}/attn/{1}_norm/scale", "vector"),
    (r"model\.layers\.(\d+)\.self_attn\.o_gate\.weight",
     "layer_{0}/attn/g_proj/kernel", "linear"),
    (r"model\.layers\.(\d+)\.lightning\.(q|k|v|o)_proj\.weight",
     "layer_{0}/lin/{1}_proj/kernel", "linear"),
    (r"model\.layers\.(\d+)\.lightning\.z_proj\.weight",
     "layer_{0}/lin/g_proj/kernel", "linear"),
    (r"model\.layers\.(\d+)\.lightning\.(q|k|o)_norm\.weight",
     "layer_{0}/lin/{1}_norm/scale", "vector"),
]

ARCH_MAPS["minicpm_sala"] = _MINICPM_SALA_MAP
ARCH_MAPS["pangu_ultra_moe"] = _PANGU_ULTRA_MOE_MAP
ARCH_MAPS["nemotron_h"] = _NEMOTRON_H_MAP
ARCH_MAPS["jamba"] = _JAMBA_MAP
ARCH_MAPS["kimi_linear"] = _KIMI_LINEAR_MAP
ARCH_MAPS["mixtral"] = _MIXTRAL_MAP
ARCH_MAPS["qwen2_moe"] = _QWEN2_MOE_MAP
ARCH_MAPS["olmoe"] = _OLMOE_MAP
# assumed names (Qwen3MoE's, whose config keys the family shares letter
# for letter): OLMoE's map; the q / k norm scales are then a head wide
ARCH_MAPS["mellum"] = _OLMOE_MAP


def _fw_path(template: str, groups: Tuple[str, ...]) -> str:
    """Expand a map template: {N} positional groups and the
    {w:scale,b:bias} weight/bias selector."""
    out = template
    for i, g in enumerate(groups):
        out = out.replace("{" + str(i) + "}", g)
    m = re.search(r"\{w:([^,]+),b:([^}]+)\}", out)
    if m:
        which = groups[-1]
        out = out[:m.start()] + (m.group(1) if which.startswith("w")
                                 else m.group(2)) + out[m.end():]
    return out


#: non-parameter tensors present in real Hub checkpoints — skipped silently
_IGNORED_TENSORS = re.compile(
    r".*\.((attn|attention)\.(bias|masked_bias)|rotary_emb\.inv_freq|embeddings\.position_ids)$")


def convert_hf_state(arch: str, state: Dict[str, np.ndarray],
                     strict: bool = True,
                     tied: bool = False) -> Dict[str, Any]:
    """Map an HF state dict onto this framework's nested param dict.

    ``tied=True`` (tie_word_embeddings archs, e.g. gpt_neo) drops the
    serialized ``lm_head.weight`` duplicate at convert time — torch .bin
    checkpoints carry the tied tensor even though the flax model unembeds
    through the embedding, and keeping it would waste a full-vocab kernel.
    """
    if arch not in ARCH_MAPS:
        raise ValueError(f"no HF name map for architecture '{arch}' "
                         f"(have {sorted(ARCH_MAPS)})")
    rules = [(re.compile(pat + r"$"), tmpl, kind)
             for pat, tmpl, kind in ARCH_MAPS[arch]]
    params: Dict[str, Any] = {}
    unmapped = []
    for name, arr in state.items():
        if _IGNORED_TENSORS.match(name):
            continue
        if arch == "gpt2" and name.endswith("lm_head.weight"):
            continue                      # tied duplicate of wte
        if arch == "distilbert" and name.endswith("vocab_projector.weight"):
            continue                      # tied duplicate of word embeddings
        if tied and name.endswith("lm_head.weight"):
            continue                      # tied duplicate of the embedding
        hit = None
        for rx, tmpl, kind in rules:
            m = rx.match(name)
            if m:
                hit = (_fw_path(tmpl, m.groups() + (name.split(".")[-1],)),
                       kind)
                break
        if hit is None:
            unmapped.append(name)
            continue
        path, kind = hit
        if kind == "linear" and arr.ndim == 2:
            arr = arr.T                      # torch [out,in] -> flax [in,out]
        node = params
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = np.ascontiguousarray(arr)
    if unmapped:
        msg = (f"{len(unmapped)} HF tensors had no mapping for '{arch}': "
               f"{unmapped[:5]}{'...' if len(unmapped) > 5 else ''}")
        if strict:
            raise ValueError(msg)
        logger.warning(msg)
    return params


def load_hf_model(model_dir: str, strict: bool = True):
    """(arch, model_config, params) from an HF checkpoint directory."""
    from ..models.registry import config_from_hf
    with open(os.path.join(model_dir, "config.json")) as f:
        hf_cfg = json.load(f)
    arch, cfg = config_from_hf(hf_cfg)
    if arch not in ARCH_MAPS:
        # fail BEFORE reading multi-GB shards
        raise ValueError(f"no HF name map for architecture '{arch}' "
                         f"(have {sorted(ARCH_MAPS)})")
    state = load_hf_state_dict(model_dir)
    if arch in SPECIAL_HANDLERS:
        state = SPECIAL_HANDLERS[arch](state, hf_cfg)
    params = convert_hf_state(arch, state, strict=strict,
                              tied=getattr(cfg, "tie_embeddings", False))
    if getattr(cfg, "tie_embeddings", False) and isinstance(params, dict):
        # belt-and-braces for maps whose head key isn't lm_head.weight
        params.pop("lm_head", None)
    n = sum(int(np.prod(a.shape)) for a in state.values())
    log_dist(f"loaded HF checkpoint {model_dir}: arch={arch}, "
             f"{n / 1e6:.1f}M params")
    return arch, cfg, params
