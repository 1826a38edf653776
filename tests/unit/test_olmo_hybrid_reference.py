"""The plain reference of Olmo-Hybrid (``benchmark/reference/olmo_hybrid.py``)
against published code for the one mechanism whose module this machine has:
the gated delta rule with a scalar decay as ``transformers`` 4.57.6 ships it
(``models/qwen3_next``: ``torch_recurrent_gated_delta_rule``, its L2
normalisation and its gated RMSNorm, the reference module the ``linear_*``
keys name). The family's own modelling code is not on this machine: the
norm arrangement and the QK-norm are ``assumed`` in the configuration file.
In a file of its own because importing ``torch`` and ``transformers`` costs
seconds, and a test file is one worker's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmo_hybrid as reference


def test_the_references_mixer_is_the_published_gated_delta_rule():
    """One linear_attention mixer on random weights: the convolution (taps'
    order, zeros before position 0, SiLU after), the L2 normalisation and
    the query's scale, the head's decay from ``A_log`` / ``dt_bias``, beta
    with the 2 of ``allow_neg_eigval``, the recurrence on a [d_k, d_v]
    state with d_k != d_v, the RMSNorm a head BEFORE the SiLU gate."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    try:
        from transformers.models.qwen3_next import modeling_qwen3_next as hf
    except ImportError:
        pytest.skip("this transformers has no qwen3_next")
    F = torch.nn.functional
    B, T, M, H, dk, dv, K = 2, 23, 48, 6, 12, 24, 4
    ks = iter(jax.random.split(jax.random.PRNGKey(4), 16))
    rnd = lambda *s: jax.random.normal(next(ks), s, jnp.float32)  # noqa
    p = {"q_proj": rnd(M, H * dk) * M ** -0.5,
         "k_proj": rnd(M, H * dk) * M ** -0.5,
         "v_proj": rnd(M, H * dv) * M ** -0.5,
         "g_proj": rnd(M, H * dv) * M ** -0.5,
         "o_proj": rnd(H * dv, M) * (H * dv) ** -0.5,
         "a_proj": rnd(M, H) * M ** -0.5, "b_proj": rnd(M, H) * M ** -0.5,
         "q_conv": rnd(K, H * dk) * 0.5, "k_conv": rnd(K, H * dk) * 0.5,
         "v_conv": rnd(K, H * dv) * 0.5, "A_log": rnd(H),
         "dt_bias": rnd(H), "o_norm": 1.0 + 0.1 * rnd(dv)}
    x = rnd(B, T, M)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference._gated_delta(p, x, heads=H, rms_eps=1e-6))

    t = lambda a: torch.tensor(np.asarray(a, np.float32))   # noqa: E731
    xt = t(x)
    # torch's depthwise weight [channels, 1, taps], one conv over q | k | v
    w = t(jnp.concatenate([p["q_conv"], p["k_conv"], p["v_conv"]], -1))
    mixed = torch.cat([xt @ t(p[n]) for n in ("q_proj", "k_proj",
                                              "v_proj")], -1)
    conv = F.conv1d(mixed.transpose(1, 2), w.T[:, None, :], padding=K - 1,
                    groups=w.shape[1])[:, :, :T]
    q, k, v = torch.split(F.silu(conv).transpose(1, 2),
                          [H * dk, H * dk, H * dv], dim=-1)
    beta = 2.0 * (xt @ t(p["b_proj"])).sigmoid()
    g = -t(p["A_log"]).exp() * F.softplus(xt @ t(p["a_proj"])
                                          + t(p["dt_bias"]))
    o, _ = hf.torch_recurrent_gated_delta_rule(
        q.reshape(B, T, H, dk), k.reshape(B, T, H, dk),
        v.reshape(B, T, H, dv), g=g, beta=beta, initial_state=None,
        output_final_state=False, use_qk_l2norm_in_kernel=True)
    norm = hf.Qwen3NextRMSNormGated(dv, eps=1e-6)
    with torch.no_grad():
        norm.weight.copy_(t(p["o_norm"]))
        gate = (xt @ t(p["g_proj"])).reshape(-1, dv)
        want = norm(o.reshape(-1, dv), gate).reshape(B, T, H * dv) \
            @ t(p["o_proj"])
    assert float(np.abs(want.numpy()).max()) > 0.1
    assert float(np.abs(got - want.numpy()).max()) < 2e-5


def test_the_reference_reads_every_leaf_of_the_served_tree():
    """Every leaf of the tree ``models/olmo_hybrid.py`` defines changes the
    reference's logits: none is read by the engine alone."""
    from benchmark.model_types import olmo_hybrid as mt
    from deepspeed_tpu.models.olmo_hybrid import OlmoHybridConfig
    cfg = OlmoHybridConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
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
