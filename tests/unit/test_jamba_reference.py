"""The plain reference of Jamba (``benchmark/reference/jamba.py``) against
the family's published modelling code, which this machine has:
``transformers``' ``models/jamba`` (``JambaForCausalLM`` on its naive path,
``use_mamba_kernels`` false), a tiny model of random weights whose
checkpoint goes through ``checkpoint/hf_loader``'s names into the tree the
runner serves. One comparison holds the layer order, the Mamba-1 mixer
(the convolution over x alone, the three inner RMSNorms, ``dt_proj``'s
bias, ``A_log`` transposed), attention without a position code, the
tied head and every assumed checkpoint name. And the reference against a
hand-rolled loop, and ``param_counts`` at the catalog's row. In a file of
its own because importing ``torch`` and ``transformers`` costs seconds,
and a test file is one worker's."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_harness as H
from benchmark.model_types import jamba as mt
from benchmark.reference import jamba as reference
from deepspeed_tpu.models.jamba import JambaConfig, param_counts
from deepspeed_tpu.models.registry import config_from_hf

HF = dict(model_type="jamba", vocab_size=96, hidden_size=32,
          intermediate_size=48, num_hidden_layers=4, num_attention_heads=4,
          num_key_value_heads=1, attn_layer_period=4, attn_layer_offset=2,
          expert_layer_period=2, expert_layer_offset=1, num_experts=1,
          num_experts_per_tok=1, mamba_d_state=8, mamba_d_conv=4,
          mamba_expand=2, mamba_dt_rank=6, mamba_conv_bias=True,
          mamba_proj_bias=False, rms_norm_eps=1e-6, hidden_act="silu",
          tie_word_embeddings=True, max_position_embeddings=64,
          sliding_window=None)


def test_the_reference_is_the_published_model_through_the_loaders_names():
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    try:
        from transformers import JambaConfig as HFConfig
        from transformers import JambaForCausalLM
    except ImportError:
        pytest.skip("this transformers has no jamba")
    from deepspeed_tpu.checkpoint.hf_loader import (SPECIAL_HANDLERS,
                                                    convert_hf_state)
    torch.manual_seed(0)
    hf_cfg = HFConfig(**{k: v for k, v in HF.items() if k != "model_type"},
                      use_mamba_kernels=False, pad_token_id=0)
    model = JambaForCausalLM(hf_cfg).eval()
    with torch.no_grad():
        # the initialiser's ones, zeros and log(1..N) would hide a scale,
        # a bias or a transposed A_log left out or misplaced
        for name, p in model.named_parameters():
            if p.ndim == 1 or name.endswith("A_log"):
                p.add_(0.3 * torch.randn_like(p))
    assert model.config.layers_block_type == ["mamba", "mamba", "attention",
                                              "mamba"]
    state = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    _, cfg = config_from_hf(HF)
    assert cfg.layer_kinds == ("mamba1", "mamba1", "attn", "mamba1")
    params = convert_hf_state("jamba", SPECIAL_HANDLERS["jamba"](state, HF),
                              tied=True)
    assert "lm_head" not in params
    assert params["layer_0"]["mamba"]["A_log"].shape == (8, 64)
    tokens = np.random.default_rng(0).integers(1, 96, (2, 19))
    with torch.no_grad():
        want = model(torch.tensor(tokens)).logits.numpy()
    at = jnp.tile(jnp.arange(19)[None], (2, 1))
    got = np.asarray(mt.reference_logits(cfg)(
        jax.tree_util.tree_map(jnp.asarray, params), jnp.asarray(tokens),
        at))
    assert float(np.abs(want).max()) > 0.5
    # float32 on both sides, sums in another order
    assert float(np.abs(got - want).max()) < 2e-5


def test_the_mamba_mixer_against_a_hand_rolled_loop():
    """``_mamba1`` on random weights against numpy loops over positions,
    channels' states written out: the convolution's taps' order and zeros
    before position 0, the split of ``x_proj``, the norms, the softplus
    with its bias, a decay for every (state, channel), ``D x``, the
    gate."""
    B, T, M, E, N, R, K = 2, 9, 12, 24, 4, 3, 4
    ks = iter(jax.random.split(jax.random.PRNGKey(1), 16))
    rnd = lambda *s: np.asarray(jax.random.normal(next(ks), s))  # noqa
    p = {"in_proj": rnd(M, 2 * E) * M ** -0.5,
         "x_proj": rnd(E, R + 2 * N) * E ** -0.5,
         "dt_proj": rnd(R, E) * R ** -0.5,
         "out_proj": rnd(E, M) * E ** -0.5, "conv_w": rnd(K, E) * 0.5,
         "conv_b": rnd(E) * 0.3, "dt_bias": rnd(E), "A_log": rnd(N, E),
         "D": rnd(E), "dt_norm": 1 + 0.2 * rnd(R), "b_norm": 1 + 0.2 * rnd(N),
         "c_norm": 1 + 0.2 * rnd(N)}
    h = rnd(B, T, M)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference._mamba1(
            jax.tree_util.tree_map(jnp.asarray, p), jnp.asarray(h),
            dt_rank=R, state=N, rms_eps=1e-6))
    silu = lambda v: v / (1 + np.exp(-v))                   # noqa: E731
    rms = lambda v, w: v / np.sqrt((v * v).mean(-1, keepdims=True)  # noqa
                                   + 1e-6) * w
    want = np.zeros((B, T, M))
    for b in range(B):
        xz = h[b] @ p["in_proj"]
        xt, z = xz[:, :E], xz[:, E:]
        x = np.zeros((T, E))
        for t in range(T):
            for j in range(K):
                if t - (K - 1 - j) >= 0:
                    x[t] += xt[t - (K - 1 - j)] * p["conv_w"][j]
        x = silu(x + p["conv_b"])
        dbc = x @ p["x_proj"]
        dt = rms(dbc[:, :R], p["dt_norm"]) @ p["dt_proj"] + p["dt_bias"]
        dt = np.log1p(np.exp(dt))
        Bm = rms(dbc[:, R:R + N], p["b_norm"])
        Cm = rms(dbc[:, R + N:], p["c_norm"])
        A = -np.exp(p["A_log"])
        S = np.zeros((N, E))
        y = np.zeros((T, E))
        for t in range(T):
            for n in range(N):
                S[n] = np.exp(dt[t] * A[n]) * S[n] + dt[t] * x[t] * Bm[t, n]
            y[t] = (S * Cm[t][:, None]).sum(0) + p["D"] * x[t]
        want[b] = (y * silu(z)) @ p["out_proj"]
    assert float(np.abs(want).max()) > 0.1
    assert float(np.abs(got - want).max()) < 1e-5


def test_param_counts_at_the_catalogs_row():
    if not os.path.exists(H.CATALOG):
        pytest.skip("no catalog on this machine")
    _, cfg = config_from_hf(H.catalog_row("AI21-Jamba2-3B")["config"])
    assert param_counts(cfg) == (3029337472, 3029337472)


def test_the_reference_reads_every_leaf_of_the_served_tree():
    """Every leaf of the tree ``models/jamba.py`` defines changes the
    reference's logits: none is read by the engine alone."""
    cfg = JambaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    params = mt.init_params(cfg, 1)
    tokens = jnp.asarray([np.random.default_rng(0).integers(0, 512, 16)])
    at = jnp.asarray([[15]])
    ref = mt.reference_logits(cfg)
    want = ref(params, tokens, at)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    for i, (path, leaf) in enumerate(leaves):
        bent = [l for _, l in leaves]
        bent[i] = leaf * 1.5 + 0.25
        got = ref(jax.tree_util.tree_unflatten(treedef, bent), tokens, at)
        assert float(jnp.abs(got - want).max()) > 1e-6, \
            jax.tree_util.keystr(path)


def test_a_published_checkpoint_directory_serves_through_build_hf_engine(
        tmp_path):
    """``save_pretrained`` of a tiny ``JambaForCausalLM`` (config.json and
    safetensors, as the hub holds the family), ``build_hf_engine`` on the
    directory, a prompt prefilled in two chunks and four tokens through
    the fused loop: the published model's own logits and tokens."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    try:
        from transformers import JambaConfig as HFConfig
        from transformers import JambaForCausalLM
    except ImportError:
        pytest.skip("this transformers has no jamba")
    from deepspeed_tpu.inference.v2 import RaggedInferenceConfig
    from deepspeed_tpu.inference.v2.engine_factory import build_hf_engine
    torch.manual_seed(1)
    model = JambaForCausalLM(HFConfig(
        **{k: v for k, v in HF.items() if k != "model_type"},
        use_mamba_kernels=False, pad_token_id=0)).eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if p.ndim == 1 or name.endswith("A_log"):
                p.add_(0.3 * torch.randn_like(p))
    model.save_pretrained(str(tmp_path), safe_serialization=True)
    eng = build_hf_engine(str(tmp_path), RaggedInferenceConfig(
        chunk_size=16, max_seqs=2, block_size=16, num_blocks=8,
        max_blocks_per_seq=4, decode_loop_steps=4, dtype="float32"),
        dtype="float32")
    prompt = np.random.default_rng(0).integers(1, 96, 21).tolist()
    got = np.asarray(eng.put([1], [prompt])[1])
    with torch.no_grad():
        want = model(torch.tensor([prompt])).logits[0, -1].numpy()
    assert float(np.abs(got - want).max()) < 2e-5
    tok = int(np.argmax(got))
    toks = [int(t) for t in eng.decode_batch([1], [tok], 4)[1]]
    with torch.no_grad():
        logits = model(torch.tensor([prompt + [tok] + toks])).logits[0]
    assert toks == logits[len(prompt):-1].argmax(-1).tolist()
