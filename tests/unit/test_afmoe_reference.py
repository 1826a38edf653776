"""The plain reference of AFMoE (``benchmark/reference/afmoe.py``) held to
account: its router against the local ``transformers``' ``deepseek_v3``
one, and each of its WRONG models against itself, beyond the tolerances
``test_afmoe.py`` holds the model to (on its three-layer model: a dense
sliding layer, a sparse sliding layer, a sparse full layer)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import afmoe as reference

from test_afmoe import SMALL as CFG
from test_afmoe import _dims, _draw, _rel, _tokens, reference_side


@pytest.fixture(scope="module")
def right():
    params, tokens = _draw(CFG), _tokens(CFG)
    return (params, tokens) + reference_side(params, tokens, **_dims(CFG))


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_every_wrong_model_of_the_reference_is_caught(right, wrong):
    """A planted fault: the reference with one equation wrong moves the
    loss, or some leaf's gradient, beyond the tolerances of the test
    above."""
    params, tokens, ref_loss, _, ref_grads = right
    loss, _, grads = reference_side(params, tokens,
                                    **_dims(CFG, wrong=(wrong,)))
    moved = max(_rel(g, r) for g, r in zip(
        jax.tree_util.tree_leaves(grads),
        jax.tree_util.tree_leaves(ref_grads)))
    assert abs(loss - ref_loss) > 1e-4 or moved > 1e-3, (loss, moved)


def test_the_router_is_deepseek_v3s_at_one_group():
    """``reference.route`` against the local ``transformers``'
    ``DeepseekV3TopkRouter`` at ``n_group = topk_group = 1``,
    ``norm_topk_prob``, ``routed_scaling_factor`` 2.826 and a bias that
    CHANGES the selection."""
    torch = pytest.importorskip("torch")
    from transformers.models.deepseek_v3.configuration_deepseek_v3 import \
        DeepseekV3Config
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import \
        DeepseekV3TopkRouter
    E, M, k = 32, 48, 8
    router = DeepseekV3TopkRouter(DeepseekV3Config(
        hidden_size=M, n_routed_experts=E, num_experts_per_tok=k, n_group=1,
        topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.826))
    rng = np.random.default_rng(0)
    w = rng.standard_normal((E, M)).astype(np.float32) * M ** -0.5
    b = rng.standard_normal(E).astype(np.float32) * 0.3
    x = rng.standard_normal((64, M)).astype(np.float32)
    with torch.no_grad():
        router.weight.copy_(torch.from_numpy(w))
        router.e_score_correction_bias.copy_(torch.from_numpy(b))
        idx, wts = router(torch.from_numpy(x))
    sel, ours = reference.route(jnp.asarray(x), jnp.asarray(w.T),
                                jnp.asarray(b), top_k=k, route_norm=True,
                                route_scale=2.826)
    unbiased, _ = reference.route(jnp.asarray(x), jnp.asarray(w.T),
                                  jnp.asarray(b), top_k=k, biased=False)
    assert np.any(np.sort(sel, -1) != np.sort(unbiased, -1))
    order_t = np.argsort(idx.numpy(), -1)
    order_o = np.argsort(np.asarray(sel), -1)
    np.testing.assert_array_equal(
        np.take_along_axis(idx.numpy(), order_t, -1),
        np.take_along_axis(np.asarray(sel), order_o, -1))
    np.testing.assert_allclose(
        np.take_along_axis(wts.numpy(), order_t, -1),
        np.take_along_axis(np.asarray(ours), order_o, -1), atol=1e-5)
