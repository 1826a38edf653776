"""Model-architecture registry + HF config mapping.

Analogue of the reference's arch→policy map in ``build_hf_engine``
(``inference/v2/engine_factory.py:69``) and the container registry
(``module_inject/replace_policy.py``): maps an architecture name (or a raw
HuggingFace config dict's ``model_type``) to this framework's model config /
module classes, so checkpoints and serving configs can be resolved by name.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple

from .bert import Bert, BertConfig
from .bert import make_model as make_bert
from .diffusion import UNet2DCondition, UNetConfig, VAE, VAEConfig
from .bloom import Bloom, BloomConfig
from .bloom import make_model as make_bloom
from .gpt_neo import GPTNeo, GPTNeoConfig
from .gpt_neo import make_model as make_gpt_neo
from .gpt_neox import (GPTJ, GPTJConfig, GPTNeoX, GPTNeoXConfig,
                       make_model_gptj, make_model_neox)
from .falcon import Falcon, FalconConfig
from .falcon import make_model as make_falcon
from .gpt2 import GPT2, GPT2Config
from .gpt2 import make_model as make_gpt2
from .llama import Llama, LlamaConfig
from .llama import make_model as make_llama
from .mixtral import Mixtral, MixtralConfig
from .mixtral import make_model as make_mixtral
from .opt import OPT, OPTConfig
from .opt import make_model as make_opt
from .phi import Phi, PhiConfig
from .phi import make_model as make_phi
from .solar_open2 import SolarOpen2, SolarOpen2Config
from .solar_open2 import make_model as make_solar_open2
from .pangu_ultra_moe import PanguUltraMoE, PanguUltraMoEConfig
from .pangu_ultra_moe import make_model as make_pangu_ultra_moe
from .kimi_linear import KimiLinear, KimiLinearConfig
from .kimi_linear import make_model as make_kimi_linear
from .afmoe import LAYER_TYPES as AFMOE_LAYER_TYPES
from .afmoe import Afmoe, AfmoeConfig
from .afmoe import make_model as make_afmoe
from .lfm2 import LAYER_TYPES as LFM2_LAYER_TYPES
from .lfm2 import Lfm2, Lfm2Config
from .lfm2 import make_model as make_lfm2
from .mellum import LAYER_TYPES, Mellum, MellumConfig, YarnRope
from .mellum import make_model as make_mellum
from .olmo_hybrid import LAYER_TYPES as OLMO_HYBRID_LAYER_TYPES
from .olmo_hybrid import OlmoHybrid, OlmoHybridConfig
from .olmo_hybrid import make_model as make_olmo_hybrid
from .minicpm_sala import (MIXER_TYPES, MiniCPMSALA, MiniCPMSALAConfig,
                           SparseConfig)
from .minicpm_sala import make_model as make_minicpm_sala
from .jamba import Jamba, JambaConfig, kinds_from_periods
from .jamba import make_model as make_jamba
from .nemotron_h import NemotronH, NemotronHConfig, kinds_from_pattern
from .nemotron_h import make_model as make_nemotron_h


class ArchEntry(NamedTuple):
    config_cls: type
    model_cls: type
    make_model: Callable
    from_hf: Callable[[Dict[str, Any]], Any]


def _hf_llama(d: Dict[str, Any], **extra) -> LlamaConfig:
    base = dict(
        vocab_size=d.get("vocab_size", 32000),
        max_seq_len=d.get("max_position_embeddings", 4096),
        num_layers=d.get("num_hidden_layers", 32),
        num_heads=d.get("num_attention_heads", 32),
        num_kv_heads=d.get("num_key_value_heads",
                           d.get("num_attention_heads", 32)),
        hidden_size=d.get("hidden_size", 4096),
        intermediate_size=d.get("intermediate_size", 11008),
        rope_theta=d.get("rope_theta", 10000.0),
        rms_eps=d.get("rms_norm_eps", 1e-5),
        tie_embeddings=d.get("tie_word_embeddings", False),
    )
    base.update(extra)
    return base


def _entry_llama(d):
    return LlamaConfig(**_hf_llama(d))


def _entry_mistral(d):
    return LlamaConfig(**_hf_llama(d, sliding_window=d.get("sliding_window")))


def _entry_qwen2(d):
    return LlamaConfig(**_hf_llama(d, qkv_bias=True))


def _entry_qwen(d):
    """Qwen v1 (original Qwen-7B; reference
    inference/v2/model_implementations/qwen/): llama-shaped with biased
    fused qkv, RMSNorm, SwiGLU whose config ``intermediate_size`` counts
    BOTH branches (per-branch width is half), and its own config key names
    (seq_length / rotary_emb_base / layer_norm_epsilon)."""
    return LlamaConfig(
        vocab_size=d.get("vocab_size", 151936),
        max_seq_len=d.get("seq_length", 8192),
        num_layers=d.get("num_hidden_layers", 32),
        num_heads=d.get("num_attention_heads", 32),
        num_kv_heads=d.get("num_attention_heads", 32),
        hidden_size=d.get("hidden_size", 4096),
        intermediate_size=d.get("intermediate_size", 22016) // 2,
        rope_theta=d.get("rotary_emb_base", 10000.0),
        rms_eps=d.get("layer_norm_epsilon", 1e-6),
        tie_embeddings=d.get("tie_word_embeddings", False),
        qkv_bias=True)


def _entry_mixtral(d):
    return MixtralConfig(**_hf_llama(
        d,
        num_experts=d.get("num_local_experts", 8),
        experts_top_k=d.get("num_experts_per_tok", 2),
        router_aux_loss_coef=d.get("router_aux_loss_coef", 0.02)))


def _entry_gpt2(d):
    return GPT2Config(
        vocab_size=d.get("vocab_size", 50257),
        max_seq_len=d.get("n_positions", 1024),
        num_layers=d.get("n_layer", 12),
        num_heads=d.get("n_head", 12),
        hidden_size=d.get("n_embd", 768),
        layer_norm_eps=d.get("layer_norm_epsilon", 1e-5))


def _entry_bert(d):
    return BertConfig(
        vocab_size=d.get("vocab_size", 30522),
        max_seq_len=d.get("max_position_embeddings", 512),
        type_vocab_size=d.get("type_vocab_size", 2),
        num_layers=d.get("num_hidden_layers", 12),
        num_heads=d.get("num_attention_heads", 12),
        hidden_size=d.get("hidden_size", 768),
        intermediate_size=d.get("intermediate_size", 3072),
        layer_norm_eps=d.get("layer_norm_eps", 1e-12))


def _entry_distilbert(d):
    # DistilBERT = BERT encoder, no token-type embeddings, gelu, sinusoidal
    # optional (sinusoidal_pos_embds default False -> learned, as here)
    if d.get("sinusoidal_pos_embds", False):
        raise ValueError("distilbert sinusoidal_pos_embds=True is not "
                         "supported (learned positions only)")
    act = d.get("activation", "gelu")
    if act != "gelu":
        raise ValueError(f"distilbert activation={act!r} is not supported "
                         f"(exact gelu only)")
    return BertConfig(
        vocab_size=d.get("vocab_size", 30522),
        max_seq_len=d.get("max_position_embeddings", 512),
        type_vocab_size=0,
        num_layers=d.get("n_layers", 6),
        num_heads=d.get("n_heads", 12),
        hidden_size=d.get("dim", 768),
        intermediate_size=d.get("hidden_dim", 3072),
        layer_norm_eps=1e-12)


def _entry_opt(d):
    proj = d.get("word_embed_proj_dim")
    return OPTConfig(
        vocab_size=d.get("vocab_size", 50272),
        max_seq_len=d.get("max_position_embeddings", 2048),
        num_layers=d.get("num_hidden_layers", 12),
        num_heads=d.get("num_attention_heads", 12),
        hidden_size=d.get("hidden_size", 768),
        ffn_dim=d.get("ffn_dim", 3072),
        do_layer_norm_before=d.get("do_layer_norm_before", True),
        word_embed_proj_dim=(proj if proj and
                             proj != d.get("hidden_size", 768) else None),
        tie_embeddings=d.get("tie_word_embeddings", True))


def _entry_bloom(d):
    return BloomConfig(
        vocab_size=d.get("vocab_size", 250880),
        num_layers=d.get("n_layer", d.get("num_hidden_layers", 30)),
        num_heads=d.get("n_head", d.get("num_attention_heads", 32)),
        hidden_size=d.get("hidden_size", d.get("n_embed", 4096)),
        layer_norm_eps=d.get("layer_norm_epsilon", 1e-5),
        tie_embeddings=d.get("tie_word_embeddings", True))


def _entry_gpt_neo(d):
    # attention_types: [[["global","local"], N], ...] expands to per-layer
    kinds = None
    at = d.get("attention_types")
    if at:
        kinds = []
        for pattern, n in at:
            kinds.extend(list(pattern) * int(n))   # pattern repeated n times
        if len(kinds) != d.get("num_layers", 24):
            raise ValueError(
                f"attention_types expands to {len(kinds)} layers but "
                f"num_layers={d.get('num_layers', 24)}")
        kinds = tuple(kinds)
    act = d.get("activation_function", "gelu_new")
    if act != "gelu_new":
        raise ValueError(
            f"gpt_neo activation_function={act!r} is not supported (only "
            f"gelu_new, the shipped GPT-Neo default)")
    return GPTNeoConfig(
        vocab_size=d.get("vocab_size", 50257),
        max_seq_len=d.get("max_position_embeddings", 2048),
        num_layers=d.get("num_layers", 24),
        num_heads=d.get("num_heads", 16),
        hidden_size=d.get("hidden_size", 2048),
        intermediate_size=d.get("intermediate_size"),
        window_size=d.get("window_size", 256),
        attention_layers=kinds,
        tie_embeddings=d.get("tie_word_embeddings", True),
        layer_norm_eps=d.get("layer_norm_epsilon", 1e-5))


def _entry_gpt_neox(d):
    return GPTNeoXConfig(
        vocab_size=d.get("vocab_size", 50432),
        max_seq_len=d.get("max_position_embeddings", 2048),
        num_layers=d.get("num_hidden_layers", 44),
        num_heads=d.get("num_attention_heads", 64),
        hidden_size=d.get("hidden_size", 6144),
        intermediate_size=d.get("intermediate_size", 24576),
        rotary_pct=d.get("rotary_pct", 0.25),
        rope_theta=d.get("rope_theta", d.get("rotary_emb_base", 10000.0)),
        layer_norm_eps=d.get("layer_norm_eps", 1e-5),
        use_parallel_residual=d.get("use_parallel_residual", True),
        tie_embeddings=d.get("tie_word_embeddings", False))


def _entry_gptj(d):
    return GPTJConfig(
        vocab_size=d.get("vocab_size", 50400),
        max_seq_len=d.get("n_positions", 2048),
        num_layers=d.get("n_layer", 28),
        num_heads=d.get("n_head", 16),
        hidden_size=d.get("n_embd", 4096),
        intermediate_size=d.get("n_inner") or 4 * d.get("n_embd", 4096),
        rotary_dim=d.get("rotary_dim", 64),
        layer_norm_eps=d.get("layer_norm_epsilon", 1e-5),
        tie_embeddings=d.get("tie_word_embeddings", False))


def _entry_falcon(d):
    new_arch = d.get("new_decoder_architecture", False)
    return FalconConfig(
        vocab_size=d.get("vocab_size", 65024),
        max_seq_len=d.get("max_position_embeddings", 2048),
        num_layers=d.get("num_hidden_layers", 32),
        num_heads=d.get("num_attention_heads", 71),
        num_kv_heads=(d.get("num_kv_heads", 8) if new_arch
                      else (d.get("num_attention_heads", 71)
                            if not d.get("multi_query", True) else 1)),
        hidden_size=d.get("hidden_size", 4544),
        alibi=d.get("alibi", False),
        parallel_attn=d.get("parallel_attn", True),
        new_decoder_architecture=new_arch,
        tie_embeddings=d.get("tie_word_embeddings", True))


def _entry_phi(d):
    return PhiConfig(
        vocab_size=d.get("vocab_size", 51200),
        max_seq_len=d.get("max_position_embeddings", 2048),
        num_layers=d.get("num_hidden_layers", 24),
        num_heads=d.get("num_attention_heads", 32),
        hidden_size=d.get("hidden_size", 2048),
        intermediate_size=d.get("intermediate_size", 8192),
        rotary_fraction=d.get("partial_rotary_factor", 0.5),
        rope_theta=d.get("rope_theta", 10000.0))


def _entry_phi3(d):
    # phi-3 is llama-architecture (fused qkv/gate_up in the HF checkpoint,
    # unfused here — same math)
    return LlamaConfig(**_hf_llama(d))


def _entry_internlm(d):
    """InternLM v1/v2 are llama-architecture (reference
    module_inject/containers/internlm.py). v1's optional attention bias
    covers q/k/v here; configs with bias=True also put a bias on o_proj,
    which this model family does not carry — flagged loudly."""
    if d.get("bias", False):
        raise ValueError(
            "internlm configs with bias=True (o_proj bias) are not "
            "supported; bias=False checkpoints load as llama")
    return LlamaConfig(**_hf_llama(d))


def _entry_unet(d):
    from .diffusion import UNetConfig
    ahd = d.get("attention_head_dim", 8)
    if isinstance(ahd, (list, tuple)):
        if len(set(ahd)) != 1:
            raise ValueError(
                f"per-block attention_head_dim {ahd} (SD 2.x style) is not "
                f"supported — this UNet uses one head dim for all blocks")
        ahd = ahd[0]
    return UNetConfig(
        in_channels=d.get("in_channels", 4),
        out_channels=d.get("out_channels", 4),
        block_channels=tuple(d.get("block_out_channels",
                                   (320, 640, 1280, 1280))),
        layers_per_block=d.get("layers_per_block", 2),
        cross_attn_dim=d.get("cross_attention_dim", 768),
        attn_head_dim=ahd,
        norm_groups=d.get("norm_num_groups", 32))


def _entry_vae(d):
    from .diffusion import VAEConfig
    return VAEConfig(
        in_channels=d.get("in_channels", 3),
        latent_channels=d.get("latent_channels", 4),
        block_channels=tuple(d.get("block_out_channels",
                                   (128, 256, 512, 512))),
        norm_groups=d.get("norm_num_groups", 32),
        scaling_factor=d.get("scaling_factor", 0.18215))


def _entry_qwen2_moe(d):
    # qwen2-moe = mixtral block + an always-on sigmoid-gated shared expert
    if int(d.get("decoder_sparse_step", 1)) != 1 or d.get("mlp_only_layers"):
        raise ValueError(
            "qwen2_moe configs with dense layers interleaved "
            "(decoder_sparse_step != 1 or mlp_only_layers) are not "
            "supported — every layer is treated as sparse MoE here")
    return MixtralConfig(**_hf_llama(
        d,
        qkv_bias=True,                  # qwen2 family uses biased q/k/v
        intermediate_size=d.get("moe_intermediate_size",
                                d.get("intermediate_size", 11008)),
        num_experts=d.get("num_experts", 8),
        experts_top_k=d.get("num_experts_per_tok", 2),
        shared_expert_size=d.get("shared_expert_intermediate_size", 0),
        norm_topk_prob=d.get("norm_topk_prob", False),
        router_aux_loss_coef=d.get("router_aux_loss_coef", 0.001)))


def _entry_olmoe(d):
    """OLMoE (allenai/OLMoE-1B-7B): every layer sparse, SwiGLU experts of
    width ``intermediate_size``, softmax over all experts with the top-k
    kept as they are, RMSNorm over the whole q and k projections.
    ``clip_qkv`` is not implemented: a config that sets it is refused."""
    if d.get("clip_qkv") is not None:
        raise ValueError("olmoe configs with clip_qkv set are not "
                         "supported (the published ones leave it null)")
    return MixtralConfig(**_hf_llama(
        d,
        qk_norm=True,
        num_experts=d.get("num_experts", 64),
        experts_top_k=d.get("num_experts_per_tok", 8),
        norm_topk_prob=d.get("norm_topk_prob", False),
        router_aux_loss_coef=d.get("router_aux_loss_coef", 0.01)))


def _entry_solar_open2(d):
    """Solar-Open2 (upstage/Solar-Open2-250B): ``gqa_layers`` are softmax
    GQA layers without rotary positions and with an output gate, the
    others gated delta-rule (KDA) layers; every layer sparse, sigmoid
    router, one ungated shared expert. What the published config leaves
    open is refused rather than guessed at: leading dense layers
    (``first_k_dense_replace``), the full-rank decay projection
    (``kda_use_full_proj``) and grouped key/value heads in the linear
    layers (``linear_attn_config.num_kv_heads``)."""
    la = d.get("linear_attn_config") or {}
    if int(d.get("first_k_dense_replace", 0)) != 0:
        raise ValueError("solar_open2 configs with leading dense layers "
                         "(first_k_dense_replace != 0) are not supported")
    if d.get("kda_use_full_proj", False):
        raise ValueError("solar_open2 configs with kda_use_full_proj set "
                         "are not supported (the published one is low-rank)")
    if la.get("num_kv_heads") not in (None, la.get("num_heads")):
        raise ValueError("solar_open2 linear-attention layers with grouped "
                         "key/value heads are not supported")
    n = d.get("num_hidden_layers", 48)
    gqa = d.get("gqa_layers")
    if gqa is None:
        gqa = range(0, n, int(d.get("gqa_interval", 3)) + 1)
    gqa = set(gqa)
    width = d.get("moe_intermediate_size", 1280)
    base = _hf_llama(d, intermediate_size=width)
    return SolarOpen2Config(
        **base,
        attn_head_dim=d.get("head_dim",
                            base["hidden_size"] // base["num_heads"]),
        layer_kinds=tuple("attn" if i in gqa else "kda" for i in range(n)),
        use_rope=bool(d.get("use_rope", False)),
        attn_gate=bool(d.get("use_gqa_gate", True)),
        kda_heads=la.get("num_heads", 64),
        kda_head_dim=la.get("head_dim", 128),
        kda_conv=la.get("short_conv_kernel_size", 4),
        kda_rank=la.get("head_dim", 128),
        kda_neg_eigval=bool(d.get("kda_allow_neg_eigval", True)),
        num_experts=d.get("n_routed_experts", 320),
        experts_top_k=d.get("num_experts_per_tok", 8),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        routed_scaling=float(d.get("routed_scaling_factor", 1.0)),
        shared_expert_size=int(d.get("n_shared_experts", 1)) * width,
        router_aux_loss_coef=0.0)


def _entry_pangu_ultra_moe(d):
    """openPangu-Ultra-MoE (FreedomIntelligence/openPangu-Ultra-MoE-718B):
    latent attention in every layer, the first ``first_k_dense_replace``
    layers dense and the others sparse (sigmoid router, one ungated shared
    expert), sandwich norms, ``num_nextn_predict_layers`` MTP modules. The
    cache keeps one latent row a token (``num_kv_heads`` 1, whatever
    ``num_key_value_heads`` says of the expanded heads). Attention bias
    and rope scaling are refused rather than guessed at."""
    if d.get("attention_bias", False):
        raise ValueError("pangu_ultra_moe configs with attention_bias set "
                         "are not supported (the published one has none)")
    if d.get("rope_scaling") is not None:
        raise ValueError("pangu_ultra_moe configs with rope_scaling set are "
                         "not supported (the published one has none)")
    n = d.get("num_hidden_layers", 61)
    k_dense = int(d.get("first_k_dense_replace", 3))
    width = d.get("moe_intermediate_size", 2048)
    base = _hf_llama(d, intermediate_size=width, num_kv_heads=1)
    return PanguUltraMoEConfig(
        **base,
        q_lora_rank=d.get("q_lora_rank", 1536),
        kv_lora_rank=d.get("kv_lora_rank", 512),
        qk_nope_head_dim=d.get("qk_nope_head_dim", 128),
        qk_rope_head_dim=d.get("qk_rope_head_dim", 64),
        v_head_dim=d.get("v_head_dim", 128),
        layer_kinds=("mla",) * n,
        ffn_kinds=tuple("dense" if i < k_dense else "moe"
                        for i in range(n)),
        dense_intermediate_size=d.get("intermediate_size", 18432),
        sandwich_norm=bool(d.get("sandwich_norm", True)),
        num_experts=d.get("n_routed_experts", 256),
        experts_top_k=d.get("num_experts_per_tok", 8),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        routed_scaling=float(d.get("routed_scaling_factor", 2.5)),
        shared_expert_size=int(d.get("n_shared_experts", 1)) * width,
        nextn_layers=int(d.get("num_nextn_predict_layers", 0)),
        router_aux_loss_coef=0.0)


def _entry_kimi_linear(d):
    """Kimi-Linear (moonshotai/Kimi-Linear-48B-A3B-Instruct):
    ``linear_attn_config.kda_layers`` are gated delta-rule (KDA) layers and
    ``full_attn_layers`` latent-attention (MLA) layers, both lists 1-BASED;
    the first ``first_k_dense_replace`` layers dense and the others sparse
    (sigmoid router with a selection bias, one group, one ungated shared
    expert). The latent layers have a full-rank query and no position
    code. The cache keeps one latent row a token and latent layer
    (``num_kv_heads`` 1). What the published config does not set is
    refused by the key's name rather than guessed at."""
    la = d.get("linear_attn_config") or {}
    n = d.get("num_hidden_layers", 27)
    for key, want in (("q_lora_rank", None), ("rope_scaling", None),
                      ("mla_use_nope", True), ("num_expert_group", 1),
                      ("topk_group", 1), ("moe_layer_freq", 1),
                      ("num_nextn_predict_layers", 0),
                      ("hidden_act", "silu"),
                      ("moe_router_activation_func", "sigmoid")):
        if d.get(key, want) != want:
            raise ValueError(
                f"kimi_linear configs with {key}={d[key]!r} are not "
                f"supported (the published one has {want!r})")
    kda = {int(i) for i in la.get("kda_layers", ())}
    full = {int(i) for i in la.get("full_attn_layers", ())}
    if kda & full or kda | full != set(range(1, n + 1)):
        raise ValueError(
            "kimi_linear linear_attn_config: kda_layers and "
            "full_attn_layers must name each of the layers 1 .. "
            f"num_hidden_layers ({n}) once")
    k_dense = int(d.get("first_k_dense_replace", 1))
    width = d.get("moe_intermediate_size", 1024)
    base = _hf_llama(d, intermediate_size=width, num_kv_heads=1,
                     max_seq_len=d.get("model_max_length", 1048576))
    return KimiLinearConfig(
        **base,
        kv_lora_rank=d.get("kv_lora_rank", 512),
        qk_nope_head_dim=d.get("qk_nope_head_dim", 128),
        qk_rope_head_dim=d.get("qk_rope_head_dim", 64),
        v_head_dim=d.get("v_head_dim", 128),
        layer_kinds=tuple("mla" if i + 1 in full else "kda"
                          for i in range(n)),
        ffn_kinds=tuple("dense" if i < k_dense else "moe"
                        for i in range(n)),
        dense_intermediate_size=d.get("intermediate_size", 9216),
        kda_heads=la.get("num_heads", 32),
        kda_head_dim=la.get("head_dim", 128),
        kda_conv=la.get("short_conv_kernel_size", 4),
        kda_rank=la.get("head_dim", 128),
        num_experts=d.get("num_experts", 256),
        experts_top_k=d.get("num_experts_per_token", 8),
        norm_topk_prob=bool(d.get("moe_renormalize", True)),
        routed_scaling=float(d.get("routed_scaling_factor", 2.446)),
        shared_expert_size=int(d.get("num_shared_experts", 1)) * width,
        router_aux_loss_coef=0.0)


def _entry_mellum(d):
    """Mellum 2 (JetBrains/Mellum2-12B-A2.5B): ``layer_types`` says which
    layers attend inside ``sliding_window`` and which in full,
    ``rope_parameters`` gives each kind its own rotary code (plain for
    the sliding layers, YaRN for the full ones); every layer sparse
    (``mlp_layer_types``), softmax router renormalised over its top-k, no
    shared expert; q and k normed a head. What the served path has no
    form for is refused by name: a dense feed-forward layer, attention
    bias, a ``rope_parameters`` section this entry does not know."""
    n = d.get("num_hidden_layers", 28)
    types = d.get("layer_types") or ["full_attention"] * n
    bad = sorted(set(types) - set(LAYER_TYPES))
    if bad or len(types) != n:
        raise ValueError(
            f"mellum layer_types must name num_hidden_layers ({n}) layers "
            f"of {sorted(LAYER_TYPES)}; got {len(types)} with {bad}")
    if set(d.get("mlp_layer_types") or ["sparse"]) != {"sparse"}:
        raise ValueError("mellum configs with a dense feed-forward layer "
                         "(mlp_layer_types other than 'sparse') are not "
                         "supported")
    if d.get("attention_bias", False):
        raise ValueError("mellum configs with attention_bias set are not "
                         "supported (the published one has none)")
    rope = d.get("rope_parameters") or {}
    unknown = sorted(set(rope) - set(LAYER_TYPES))
    if unknown:
        raise ValueError(
            f"mellum rope_parameters sections {unknown} are not known "
            f"(a section a layer type: {sorted(LAYER_TYPES)})")
    full = rope.get("full_attention") or {}
    slide = rope.get("sliding_attention") or {}
    theta = float(slide.get("rope_theta", full.get(
        "rope_theta", d.get("rope_theta", 10000.0))))
    if slide.get("rope_type", "default") != "default" \
            or float(full.get("rope_theta", theta)) != theta:
        raise ValueError(
            "mellum rope_parameters: the sliding layers' code must be "
            "'default' and both sections share one rope_theta; got "
            f"{rope!r}")
    kind = full.get("rope_type", "default")
    if kind not in ("default", "yarn"):
        raise ValueError(f"mellum rope_parameters full_attention "
                         f"rope_type {kind!r} is not supported "
                         f"('default' or 'yarn')")
    yarn = None
    if kind == "yarn":
        import math
        factor = float(full["factor"])
        yarn = YarnRope(
            factor=factor,
            original_max=int(full["original_max_position_embeddings"]),
            beta_fast=float(full.get("beta_fast", 32.0)),
            beta_slow=float(full.get("beta_slow", 1.0)),
            attention_factor=float(full.get(
                "attention_factor", 0.1 * math.log(factor) + 1.0)))
    base = _hf_llama(d, intermediate_size=d.get("moe_intermediate_size",
                                                896), rope_theta=theta)
    return MellumConfig(
        **base,
        attn_head_dim=d.get("head_dim",
                            base["hidden_size"] // base["num_heads"]),
        layer_kinds=tuple(LAYER_TYPES[t] for t in types),
        sliding_window=int(d.get("sliding_window", 1024)),
        full_rope=yarn,
        num_experts=d.get("num_experts", 64),
        experts_top_k=d.get("num_experts_per_tok", 8),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        router_aux_loss_coef=0.0)


def _entry_afmoe(d):
    """AFMoE (arcee-ai/Trinity-Mini, -Nano): ``layer_types`` says which
    layers attend inside ``sliding_window`` (rotary) and which in full (no
    position code); the first ``num_dense_layers`` layers have a dense
    feed-forward, the others ``num_experts`` experts top-k by a sigmoid
    score plus a selection bias, renormalised (``route_norm``) and scaled
    (``route_scale``), beside ``num_shared_experts``; the embedding times
    ``sqrt(hidden_size)`` (``mup_enabled``); the head untied. What the
    module has no form for is refused by name: expert groups, a router
    score other than the sigmoid, a scaled rotary code, a tied head."""
    n = d.get("num_hidden_layers", 32)
    types = d.get("layer_types") or ["full_attention"] * n
    bad = sorted(set(types) - set(AFMOE_LAYER_TYPES))
    if bad or len(types) != n:
        raise ValueError(
            f"afmoe layer_types must name num_hidden_layers ({n}) layers "
            f"of {sorted(AFMOE_LAYER_TYPES)}; got {len(types)} with {bad}")
    groups = {k: d.get(k, 1) for k in ("n_group", "topk_group",
                                        "num_expert_groups",
                                        "num_limited_groups")}
    if set(groups.values()) != {1}:
        raise ValueError(f"afmoe configs with expert groups are not "
                         f"supported (one group of all experts): {groups}")
    if d.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError(f"afmoe score_func {d['score_func']!r} is not "
                         f"supported ('sigmoid')")
    if d.get("rope_scaling"):
        raise ValueError("afmoe configs with rope_scaling set are not "
                         "supported (the published one has none)")
    if d.get("tie_word_embeddings", False):
        raise ValueError("afmoe configs with a tied head are not supported "
                         "(the published one is untied)")
    hidden = d.get("hidden_size", 2048)
    heads = d.get("num_attention_heads", 32)
    return AfmoeConfig(
        vocab_size=d.get("vocab_size", 200192),
        max_seq_len=d.get("max_position_embeddings", 131072) + 1,
        hidden_size=hidden, num_heads=heads,
        num_kv_heads=d.get("num_key_value_heads", heads),
        attn_head_dim=d.get("head_dim", hidden // heads),
        layer_kinds=tuple(AFMOE_LAYER_TYPES[t] for t in types),
        num_dense_layers=d.get("num_dense_layers", 2),
        sliding_window=int(d.get("sliding_window", 2048)),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        rms_eps=d.get("rms_norm_eps", 1e-5),
        intermediate_size=d.get("intermediate_size", 6144),
        moe_intermediate_size=d.get("moe_intermediate_size", 1024),
        num_experts=d.get("num_experts", 128),
        experts_top_k=d.get("num_experts_per_tok", 8),
        num_shared_experts=d.get("num_shared_experts", 1),
        route_norm=bool(d.get("route_norm", True)),
        route_scale=float(d.get("route_scale", 1.0)),
        mup_enabled=bool(d.get("mup_enabled", False)),
        load_balance_coeff=float(d.get("load_balance_coeff", 1e-3)))


def _entry_lfm2(d):
    """LFM2 (LiquidAI/LFM2-24B-A2B, ``lfm2_moe``; the dense ``lfm2`` is
    the same entry with no sparse layer): ``layer_types`` says which
    layers are gated short convolutions and which attend in full; the
    first ``num_dense_layers`` layers have a dense feed-forward of
    ``intermediate_size`` AS GIVEN and the others are sparse (sigmoid
    router with a selection bias, renormalised over its top-k with the
    family's ``1e-6``, no shared expert); q and k normed a head; the head
    tied. What the served path has no form for is refused by name: a
    convolution bias, the dense sibling's ``block_auto_adjust_ff_dim``
    (it rewrites the width the config states), a rotary code other than
    the plain one."""
    moe = d.get("model_type") == "lfm2_moe"
    n = d.get("num_hidden_layers", 40 if moe else 32)
    types = d.get("layer_types")
    if types is None:
        full = d.get("full_attn_idxs")
        full = set(range(n)) if full is None else set(full)
        types = ["full_attention" if i in full else "conv"
                 for i in range(n)]
    bad = sorted(set(types) - set(LFM2_LAYER_TYPES))
    if bad or len(types) != n:
        raise ValueError(
            f"lfm2 layer_types must name num_hidden_layers ({n}) layers "
            f"of {sorted(LFM2_LAYER_TYPES)}; got {len(types)} with {bad}")
    # the dense sibling's own default for block_auto_adjust_ff_dim is
    # True: an ``lfm2`` file that leaves the key out is refused too
    for key, have in (("conv_bias", d.get("conv_bias", False)),
                      ("block_auto_adjust_ff_dim",
                       d.get("block_auto_adjust_ff_dim", not moe))):
        if have:
            raise ValueError(
                f"lfm2 configs with {key}={have!r} are not supported "
                f"(the served path has False)")
    rope = d.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default" \
            or d.get("rope_scaling") is not None:
        raise ValueError("lfm2 configs with a scaled rotary code "
                         f"({rope or d.get('rope_scaling')!r}) are not "
                         "supported ('default' alone)")
    k_dense = int(d.get("num_dense_layers", 0)) if moe else n
    base = _hf_llama(
        d, num_layers=n,
        intermediate_size=d.get("moe_intermediate_size", 1536),
        rope_theta=float(rope.get("rope_theta",
                                  d.get("rope_theta", 1000000.0))),
        rms_eps=d.get("norm_eps", 1e-5),
        max_seq_len=d.get("max_position_embeddings", 128000),
        tie_embeddings=bool(d.get("tie_word_embeddings",
                                  d.get("tie_embedding", True))))
    return Lfm2Config(
        **base,
        attn_head_dim=d.get("head_dim",
                            base["hidden_size"] // base["num_heads"]),
        layer_kinds=tuple(LFM2_LAYER_TYPES[t] for t in types),
        ffn_kinds=tuple("dense" if i < k_dense else "moe"
                        for i in range(n)),
        conv_taps=int(d.get("conv_L_cache", 3)),
        dense_intermediate_size=d.get("intermediate_size", 11776),
        num_experts=d.get("num_experts", 64),
        experts_top_k=d.get("num_experts_per_tok", 4),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        router_bias=bool(d.get("use_expert_bias", True)),
        routed_scaling=float(d.get("routed_scaling_factor", 1.0)),
        router_aux_loss_coef=0.0)


def _entry_olmo_hybrid(d):
    """Olmo-Hybrid (allenai/Olmo-Hybrid-7B): ``layer_types`` says which
    layers are gated delta-rule layers with ONE decay a head
    (``linear_attention``: the ``linear_*`` keys give their heads, the two
    widths and the taps) and which attend in full (multi-head, RMSNorm
    over the whole q and k projections); a dense SwiGLU in every layer,
    the norms on the branches' outputs, the head untied. A null
    ``rope_parameters.rope_theta`` is NO position code; a number is the
    plain rotary code. What the served path has no form for is refused by
    name: grouped key heads in the linear layers, a bias, another
    activation, a window, a scaled rotary code."""
    n = d.get("num_hidden_layers", 32)
    types = d.get("layer_types") or []
    bad = sorted(set(types) - set(OLMO_HYBRID_LAYER_TYPES))
    if bad or len(types) != n:
        raise ValueError(
            f"olmo_hybrid layer_types must name num_hidden_layers ({n}) "
            f"layers of {sorted(OLMO_HYBRID_LAYER_TYPES)}; got "
            f"{len(types)} with {bad}")
    heads = d.get("linear_num_value_heads", 30)
    for key, have, served in (
            ("linear_num_key_heads", d.get("linear_num_key_heads", heads),
             heads),
            ("attention_bias", bool(d.get("attention_bias", False)), False),
            ("hidden_act", d.get("hidden_act", "silu"), "silu"),
            ("sliding_window", d.get("sliding_window"), None)):
        if have != served:
            raise ValueError(
                f"olmo_hybrid configs with {key}={have!r} are not "
                f"supported (the served path has {served!r})")
    rope = d.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default" \
            or d.get("rope_scaling") is not None:
        raise ValueError("olmo_hybrid configs with a scaled rotary code "
                         f"({rope or d.get('rope_scaling')!r}) are not "
                         "supported (none, or 'default')")
    theta = rope.get("rope_theta", d.get("rope_theta"))
    base = _hf_llama(d, num_layers=n, rms_eps=d.get("rms_norm_eps", 1e-6),
                     rope_theta=float(theta or 10000.0))
    head_dim = d.get("head_dim") or base["hidden_size"] // base["num_heads"]
    if head_dim * base["num_heads"] != base["hidden_size"]:
        raise ValueError(
            f"olmo_hybrid configs with head_dim={head_dim!r} x "
            f"{base['num_heads']} heads != hidden_size are not supported")
    return OlmoHybridConfig(
        **base,
        layer_kinds=tuple(OLMO_HYBRID_LAYER_TYPES[t] for t in types),
        use_rope=theta is not None,
        gdn_heads=heads,
        gdn_key_dim=d.get("linear_key_head_dim", 96),
        gdn_value_dim=d.get("linear_value_head_dim", 192),
        gdn_conv=d.get("linear_conv_kernel_dim", 4),
        gdn_neg_eigval=bool(d.get("linear_allow_neg_eigval", True)))


def _entry_jamba(d):
    """Jamba (ai21labs/AI21-Jamba2-3B): layer ``i`` attends where ``i %
    attn_layer_period == attn_layer_offset`` and is a Mamba-1 mixer
    otherwise (the ``mamba_*`` keys give its expansion, state width, taps
    and step-size rank); a dense SwiGLU in every layer, no position code,
    the head tied by ``tie_word_embeddings``. What the served path has no
    form for is refused by name: sparse feed-forwards (``num_experts`` >
    1, on the layers ``expert_layer_period / _offset`` name: inert at 1),
    a bias on the Mamba projections, no bias on its convolution, another
    activation, a window."""
    for key, have, served in (
            ("num_experts", d.get("num_experts", 1), 1),
            ("num_experts_per_tok", d.get("num_experts_per_tok", 1), 1),
            ("mamba_proj_bias", bool(d.get("mamba_proj_bias", False)), False),
            ("mamba_conv_bias", bool(d.get("mamba_conv_bias", True)), True),
            ("hidden_act", d.get("hidden_act", "silu"), "silu"),
            ("sliding_window", d.get("sliding_window"), None)):
        if have != served:
            raise ValueError(
                f"jamba configs with {key}={have!r} are not supported "
                f"(the served path has {served!r})")
    base = _hf_llama(d, rms_eps=d.get("rms_norm_eps", 1e-6),
                     tie_embeddings=bool(d.get("tie_word_embeddings", False)))
    period = int(d.get("attn_layer_period", 8))
    offset = int(d.get("attn_layer_offset", 4))
    if not 0 <= offset < period:
        raise ValueError(
            f"jamba configs with attn_layer_offset={offset!r} outside "
            f"attn_layer_period={period!r} are not supported")
    rank = d.get("mamba_dt_rank", "auto")
    if rank == "auto":
        rank = -(-base["hidden_size"] // 16)
    return JambaConfig(
        **base,
        layer_kinds=kinds_from_periods(base["num_layers"], period, offset),
        mamba_expand=int(d.get("mamba_expand", 2)),
        mamba_state=int(d.get("mamba_d_state", 16)),
        mamba_conv=int(d.get("mamba_d_conv", 4)),
        mamba_dt_rank=int(rank))


def _entry_nemotron_h(d):
    """Nemotron-H (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16):
    ``hybrid_override_pattern`` read letter by letter, a layer a mixer
    alone (``M`` Mamba-2, ``*`` attention without a position code) or a
    feed-forward alone (``E``: sigmoid router with a selection bias, one
    group, ungated relu2 experts, one shared expert of the same form).
    ``expand`` is not read: the state-space layers' inner width is
    ``mamba_num_heads x mamba_head_dim``. What the published config does
    not set is refused by the key's name rather than guessed at."""
    pattern = d["hybrid_override_pattern"]
    for key, want in (("n_group", 1), ("topk_group", 1),
                      ("mlp_hidden_act", "relu2"),
                      ("mamba_hidden_act", "silu"),
                      ("attention_bias", False), ("mlp_bias", False),
                      ("mamba_proj_bias", False), ("use_bias", False),
                      ("use_conv_bias", True), ("n_shared_experts", 1),
                      ("sliding_window", None),
                      ("num_hidden_layers", len(pattern))):
        if d.get(key, want) != want:
            raise ValueError(
                f"nemotron_h configs with {key}={d[key]!r} are not "
                f"supported (the published one has {want!r})")
    kinds, ffn_kinds = kinds_from_pattern(pattern)
    base = _hf_llama(d, num_layers=len(pattern),
                     intermediate_size=d.get("moe_intermediate_size", 1856),
                     max_seq_len=d.get("max_position_embeddings", 262144),
                     rms_eps=d.get("norm_eps",
                                   d.get("layer_norm_epsilon", 1e-5)))
    return NemotronHConfig(
        **base,
        attn_head_dim=d.get("head_dim", 128),
        layer_kinds=kinds, ffn_kinds=ffn_kinds,
        mamba_heads=d.get("mamba_num_heads", 64),
        mamba_head_dim=d.get("mamba_head_dim", 64),
        mamba_groups=d.get("n_groups", 8),
        mamba_state=d.get("ssm_state_size", 128),
        mamba_conv=d.get("conv_kernel", 4),
        mamba_chunk=d.get("chunk_size", 128),
        num_experts=d.get("n_routed_experts", 128),
        experts_top_k=d.get("num_experts_per_tok", 6),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        routed_scaling=float(d.get("routed_scaling_factor", 2.5)),
        shared_expert_size=d.get("moe_shared_expert_intermediate_size",
                                 3712),
        router_aux_loss_coef=0.0)


def _entry_minicpm_sala(d):
    """MiniCPM-SALA (openbmb/MiniCPM-SALA): ``mixer_types`` read letter for
    letter (``minicpm4`` a block-selected attention layer,
    ``lightning-attn`` a Lightning linear-attention layer; the published
    list has no period), the family's muP scalings, output gates on both
    kinds, NoPE on the sparse layers and rotary on the linear ones. The
    selection's sizes come from ``sparse_config`` where the config has
    one, else MiniCPM4.1's published values (the published config gives
    none). ``num_hidden_layers_published`` (a cut configuration's own
    key) keeps the residual scale at the model's depth. What the
    published config does not set is refused by the key's name."""
    mixers = d["mixer_types"]
    n = d.get("num_hidden_layers", len(mixers))
    unknown = sorted(set(mixers) - set(MIXER_TYPES))
    if unknown or len(mixers) != n:
        raise ValueError(
            f"minicpm_sala mixer_types must name num_hidden_layers ({n}) "
            f"layers of {sorted(MIXER_TYPES)}, got {len(mixers)} with "
            f"unknown {unknown}")
    for key, want in (("attention_bias", False), ("qk_norm", True),
                      ("hidden_act", "silu"),
                      ("lightning_scale", "1/sqrt(d)"),
                      ("use_output_gate", True), ("use_output_norm", True),
                      ("attn_use_output_gate", True),
                      ("lightning_head_dim", d.get("head_dim", 128)),
                      ("lightning_nkv", d.get("lightning_nh", 32)),
                      ("tie_word_embeddings", False)):
        if d.get(key, want) != want:
            raise ValueError(
                f"minicpm_sala configs with {key}={d[key]!r} are not "
                f"supported (the published one has {want!r})")
    names = {f.name for f in dataclasses.fields(SparseConfig)}
    sparse = SparseConfig(**{k: v for k, v in
                             (d.get("sparse_config") or {}).items()
                             if k in names})
    return MiniCPMSALAConfig(
        **_hf_llama(d, num_layers=n,
                    rms_eps=d.get("rms_norm_eps", 1e-6),
                    max_seq_len=d.get("max_position_embeddings", 524288)),
        attn_head_dim=d.get("head_dim", 128),
        layer_kinds=tuple(MIXER_TYPES[m] for m in mixers),
        use_rope=bool(d.get("attn_use_rope", False)),
        lightning_heads=d.get("lightning_nh", 32),
        lightning_rope=bool(d.get("lightning_use_rope", True)),
        sparse=sparse,
        scale_emb=float(d.get("scale_emb", 12)),
        scale_depth=float(d.get("scale_depth", 1.4)),
        dim_model_base=int(d.get("dim_model_base", 256)),
        depth_published=int(d.get("num_hidden_layers_published", n)))


ARCHITECTURES: Dict[str, ArchEntry] = {
    "gpt2": ArchEntry(GPT2Config, GPT2, make_gpt2, _entry_gpt2),
    "llama": ArchEntry(LlamaConfig, Llama, make_llama, _entry_llama),
    "mistral": ArchEntry(LlamaConfig, Llama, make_llama, _entry_mistral),
    "qwen": ArchEntry(LlamaConfig, Llama, make_llama, _entry_qwen),
    "qwen2": ArchEntry(LlamaConfig, Llama, make_llama, _entry_qwen2),
    "mixtral": ArchEntry(MixtralConfig, Mixtral, make_mixtral, _entry_mixtral),
    "bert": ArchEntry(BertConfig, Bert, make_bert, _entry_bert),
    "distilbert": ArchEntry(BertConfig, Bert, make_bert,
                            _entry_distilbert),
    "opt": ArchEntry(OPTConfig, OPT, make_opt, _entry_opt),
    "falcon": ArchEntry(FalconConfig, Falcon, make_falcon, _entry_falcon),
    "bloom": ArchEntry(BloomConfig, Bloom, make_bloom, _entry_bloom),
    "gpt_neox": ArchEntry(GPTNeoXConfig, GPTNeoX, make_model_neox,
                          _entry_gpt_neox),
    "gptj": ArchEntry(GPTJConfig, GPTJ, make_model_gptj, _entry_gptj),
    "phi": ArchEntry(PhiConfig, Phi, make_phi, _entry_phi),
    "phi3": ArchEntry(LlamaConfig, Llama, make_llama, _entry_phi3),
    "qwen2_moe": ArchEntry(MixtralConfig, Mixtral, make_mixtral,
                           _entry_qwen2_moe),
    "olmoe": ArchEntry(MixtralConfig, Mixtral, make_mixtral, _entry_olmoe),
    "solar_open2": ArchEntry(SolarOpen2Config, SolarOpen2,
                             make_solar_open2, _entry_solar_open2),
    "pangu_ultra_moe": ArchEntry(PanguUltraMoEConfig, PanguUltraMoE,
                                 make_pangu_ultra_moe,
                                 _entry_pangu_ultra_moe),
    "kimi_linear": ArchEntry(KimiLinearConfig, KimiLinear,
                             make_kimi_linear, _entry_kimi_linear),
    "nemotron_h": ArchEntry(NemotronHConfig, NemotronH, make_nemotron_h,
                            _entry_nemotron_h),
    "jamba": ArchEntry(JambaConfig, Jamba, make_jamba, _entry_jamba),
    "mellum": ArchEntry(MellumConfig, Mellum, make_mellum, _entry_mellum),
    "afmoe": ArchEntry(AfmoeConfig, Afmoe, make_afmoe, _entry_afmoe),
    "lfm2": ArchEntry(Lfm2Config, Lfm2, make_lfm2, _entry_lfm2),
    "lfm2_moe": ArchEntry(Lfm2Config, Lfm2, make_lfm2, _entry_lfm2),
    "olmo_hybrid": ArchEntry(OlmoHybridConfig, OlmoHybrid,
                             make_olmo_hybrid, _entry_olmo_hybrid),
    "minicpm_sala": ArchEntry(MiniCPMSALAConfig, MiniCPMSALA,
                              make_minicpm_sala, _entry_minicpm_sala),
    "gpt_neo": ArchEntry(GPTNeoConfig, GPTNeo, make_gpt_neo,
                         _entry_gpt_neo),
    "internlm": ArchEntry(LlamaConfig, Llama, make_llama, _entry_internlm),
    "internlm2": ArchEntry(LlamaConfig, Llama, make_llama, _entry_llama),
}


# diffusers model_index components (reference
# module_inject/containers/unet.py, vae.py +
# model_implementations/diffusers/)
ARCHITECTURES.update({
    "unet2dconditionmodel": ArchEntry(UNetConfig, UNet2DCondition,
                                      None, _entry_unet),
    "autoencoderkl": ArchEntry(VAEConfig, VAE, None, _entry_vae),
})


def get_arch(name: str) -> ArchEntry:
    try:
        return ARCHITECTURES[name.lower()]
    except KeyError:
        raise ValueError(f"unknown architecture {name!r}; known: "
                         f"{sorted(ARCHITECTURES)}")


def config_from_hf(hf_config: Dict[str, Any]):
    """Build this framework's model config from a HuggingFace config dict
    (e.g. json.load of config.json). Returns (arch_name, config)."""
    mt = hf_config.get("model_type")
    if mt is None:
        raise ValueError("hf config missing 'model_type'")
    entry = get_arch(mt)
    return mt.lower(), entry.from_hf(hf_config)
