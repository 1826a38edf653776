"""The plain reference of LFM2 (``benchmark/reference/lfm2.py``) against the
family's own published code: ``transformers.Lfm2ForCausalLM`` (4.57.6, the
DENSE sibling ``lfm2``; ``lfm2_moe`` itself is not in that version), at
hidden 64 on the CPU, with this repo's tree copied in. In a file of its own
because importing ``torch`` and ``transformers`` is a third of
``test_lfm2.py``'s seconds, and a test file is one worker's."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.model_types import lfm2_moe as mt
from deepspeed_tpu.models.lfm2 import Lfm2
from deepspeed_tpu.models.registry import config_from_hf


def ref_logits(cfg, params, tokens, at):
    out = mt.reference_logits(cfg)(params, jnp.asarray([tokens]),
                                   jnp.asarray([at]))
    return np.asarray(out)[0]


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


def test_the_reference_is_the_familys_own_published_code():
    """``transformers.Lfm2ForCausalLM`` (4.57.6, the DENSE sibling) with
    this tree's weights copied in, against the plain reference: both layer
    kinds, tied head, ``block_auto_adjust_ff_dim`` off. Pins the
    convolution's tap order and padding, the B | C | u order, the gates,
    the per-head norms, the rotary pairing, the two layer norms and the
    final norm to the family's own code."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "Lfm2ForCausalLM"):
        pytest.skip("this transformers has no Lfm2ForCausalLM")
    hf = dict(model_type="lfm2", vocab_size=512, hidden_size=64,
              intermediate_size=96, num_hidden_layers=4,
              num_attention_heads=4, num_key_value_heads=2,
              max_position_embeddings=256, norm_eps=1e-5,
              rope_theta=1000000.0, conv_bias=False, conv_L_cache=3,
              block_auto_adjust_ff_dim=False, tie_word_embeddings=True,
              layer_types=["conv", "full_attention", "conv", "conv"])
    _, cfg = config_from_hf(hf)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32,
                              param_dtype=jnp.float32)
    assert cfg.layer_kinds == ("conv", "attn", "conv", "conv")
    assert set(cfg.ffn_kinds) == {"dense"} and cfg.tie_embeddings
    params = mt.init_params(cfg, 5)
    t = lambda a: torch.tensor(np.asarray(a, np.float32))   # noqa: E731
    sd = {"model.embed_tokens.weight": t(params["embed"]["embedding"]),
          "model.embedding_norm.weight": t(params["final_norm"]["scale"])}
    for i, kind in enumerate(cfg.layer_kinds):
        p, pre = params[f"layer_{i}"], f"model.layers.{i}."
        sd[pre + "operator_norm.weight"] = t(p["input_norm"]["scale"])
        sd[pre + "ffn_norm.weight"] = t(p["post_attn_norm"]["scale"])
        for ours, theirs in (("gate_proj", "w1"), ("up_proj", "w3"),
                             ("down_proj", "w2")):
            sd[pre + f"feed_forward.{theirs}.weight"] = \
                t(p["mlp"][ours]["kernel"].T)
        if kind == "conv":
            c = p["conv"]
            sd[pre + "conv.in_proj.weight"] = t(c["in_proj"].T)
            sd[pre + "conv.out_proj.weight"] = t(c["out_proj"].T)
            # torch's depthwise weight [channels, 1, taps]
            sd[pre + "conv.conv.weight"] = t(c["conv_w"].T[:, None, :])
        else:
            a = p["attn"]
            for ours, theirs in (("q_proj", "q_proj"), ("k_proj", "k_proj"),
                                 ("v_proj", "v_proj"),
                                 ("o_proj", "out_proj")):
                sd[pre + f"self_attn.{theirs}.weight"] = \
                    t(a[ours]["kernel"].T)
            sd[pre + "self_attn.q_layernorm.weight"] = \
                t(a["q_norm"]["scale"])
            sd[pre + "self_attn.k_layernorm.weight"] = \
                t(a["k_norm"]["scale"])
    theirs = transformers.Lfm2ForCausalLM(transformers.Lfm2Config(
        **{k: v for k, v in hf.items() if k != "model_type"},
        attn_implementation="eager"))
    missing, unexpected = theirs.load_state_dict(sd, strict=False)
    assert not unexpected and set(missing) <= {"lm_head.weight"}
    theirs.tie_weights()
    tokens = prompt_of(29, seed=6)
    with torch.no_grad():
        want = theirs(torch.tensor([tokens]), use_cache=False).logits[0]
    got = ref_logits(cfg, params, tokens, list(range(len(tokens))))
    assert float(np.abs(got - want.numpy()).max()) < 1e-4
    # and the flax tree's own forward is the same function
    with jax.default_matmul_precision("highest"):
        mine = Lfm2(cfg).apply({"params": params}, jnp.asarray([tokens]))[0]
    assert float(np.abs(np.asarray(mine) - got).max()) < 1e-4
