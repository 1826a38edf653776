"""The flash kernels under a sliding window (interpret mode on the CPU)
against dense masked attention: forward, dq, dk and dv; the blocks the
window hides counted out of the plan; and ``window=None`` tracing exactly
the kernels it traced before the window existed."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.kernels.flash_attention import (attention_reference,
                                                       causal_plan,
                                                       flash_attention,
                                                       take_causal_plans)

# (tq, tk, q heads, kv heads, block, window) -> blocks of each kind a
# (batch, head): interior / sub-tiled diagonal / general / edge
CASES = {
    "window_is_the_block": ((512, 512, 2, 2, 128, 128), (0, 4, 0, 3)),
    "four_windows_gqa_4_to_1": ((512, 512, 4, 1, 128, 128), (0, 4, 0, 3)),
    "edge_interior_diagonal": ((768, 768, 2, 2, 256, 512), (2, 3, 0, 1)),
    "t_not_a_multiple_of_the_block": ((700, 700, 2, 2, 256, 256),
                                      (0, 2, 1, 2)),
    "window_under_the_block": ((512, 512, 2, 2, 256, 64), (0, 0, 3, 0)),
    "window_meets_no_corner": ((640, 640, 2, 1, 256, 300), (0, 0, 6, 0)),
    "decode_style_tq_lt_tk": ((256, 512, 2, 2, 256, 256), (0, 1, 0, 1)),
}


def _qkv(tq, tk, h, hk, seed):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k1, (1, tq, h, 16)),
            jax.random.normal(k2, (1, tk, hk, 16)),
            jax.random.normal(k3, (1, tk, hk, 16)))


@pytest.mark.parametrize("case", sorted(CASES))
def test_window_parity_forward_and_gradients(case):
    (tq, tk, h, hk, block, window), kinds = CASES[case]
    q, k, v = _qkv(tq, tk, h, hk, tq + window)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               interpret=True, block_q=block, block_k=block)

    def dense(q, k, v):
        return attention_reference(q, k, v, causal=True, window=window)

    take_causal_plans()
    out = flash(q, k, v)
    (_, h_, plan), = take_causal_plans()
    assert h_ == h
    assert (plan["interior"], plan["sub_tiled"], plan["general"],
            plan["edge"]) == kinds
    np.testing.assert_allclose(out, dense(q, k, v), atol=2e-5, rtol=2e-5)
    g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(jnp.sin(dense(*a))),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)
    # the plan's needed elements against a brute-force count of the mask
    # over the padded query rows the kernels walk
    tq_p = -(-tq // block) * block
    i = np.arange(tq_p)[:, None] + tk - tq
    j = np.arange(tk)[None, :]
    assert plan["score_elems_needed"] == 3 * int(
        ((j <= i) & (j > i - window)).sum())
    assert plan["score_elems_computed"] >= plan["score_elems_needed"]


def test_a_window_no_shorter_than_the_keys_is_no_window():
    """Bit for bit, forward and gradients: the call IS the ``window=None``
    one (same kernels, same name)."""
    q, k, v = _qkv(256, 256, 2, 1, 5)

    def run(window):
        f = lambda *a: flash_attention(                    # noqa: E731
            *a, causal=True, window=window, interpret=True, block_q=128,
            block_k=128)
        return (f(q, k, v),) + jax.grad(
            lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(run(None), run(256)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(run(None), run(4096)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="causal=True"):
        flash_attention(q, k, v, causal=False, window=64, interpret=True)


@pytest.mark.parametrize("geom,want", [
    # the Trinity cell's window call: T 8192, window 2048, 1024-blocks: a
    # query block meets an edge block, a whole block and a diagonal block
    ((8192, 8192, 1024, 1024, 8192, 0, 2048),
     dict(interior=7, sub_tiled=8, general=0, edge=6, skipped=43)),
    # the same at 512-blocks
    ((8192, 8192, 512, 512, 8192, 0, 2048),
     dict(interior=1 + 2 + 3 + 12 * 3, sub_tiled=16, general=0, edge=12,
          skipped=256 - 16 - 12 - 42)),
    # no window: the plan the GPT cells always had
    ((2048, 2048, 1024, 1024, 2048, 0, None),
     dict(interior=1, sub_tiled=2, general=0, edge=0, skipped=1)),
])
def test_causal_plan_under_a_window(geom, want):
    plan = causal_plan(*geom)
    assert {k: plan[k] for k in want} == want
    tq, window = geom[0], geom[6]
    assert plan["score_elems_needed"] == 3 * sum(
        min(i + 1, window or tq) for i in range(tq))
    assert 1.0 <= plan["score_area_share"] < 1.2


#: sha256 of the jaxpr of the GPT train cells' flash call ([2, 2048, 16,
#: 128] bf16 at 1024-blocks, value and gradient) as the parent of ISSUE 61
#: traced it, addresses cut; under jax ``_PINNED_JAX``
_PINNED = "afe4160abeb541e58a0c8d412a02f211d4ba1cfbff695f6575759e9e5dd56f9a"
_PINNED_JAX = "0.9.0"


def test_window_none_traces_the_kernels_it_always_traced():
    """``flash_attention(window=None)`` at the GPT cells' shape traces to
    the SAME jaxpr as before the window existed (kernel bodies, index maps,
    names: the text holds them all), so the two GPT train cells run the
    kernels they ran. A later change to the kernels refreshes the pin: the
    hash of ``str(jax.make_jaxpr(jax.value_and_grad(f, (0, 1, 2)))(x, x,
    x))`` with ``0x...`` addresses cut."""
    if jax.__version__ != _PINNED_JAX:
        pytest.skip(f"pinned under jax {_PINNED_JAX}")

    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False,
                               block_q=1024, block_k=1024).astype(
                                   jnp.float32).sum()

    x = jax.ShapeDtypeStruct((2, 2048, 16, 128), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.value_and_grad(f, argnums=(0, 1, 2)))(
        x, x, x))
    assert "name=attn" in text and "attn_w" not in text
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest() == _PINNED


def test_llama_sliding_window_takes_the_flash_path():
    """``LlamaConfig(sliding_window=8)`` under ``attention_impl="flash"``
    equals ``"xla"``, forward and gradient (the raise that stood here is
    gone: the kernel knows the window)."""
    import dataclasses

    from deepspeed_tpu.models.llama import LlamaConfig, make_model
    cfg = LlamaConfig.tiny(sliding_window=8, attention_impl="xla",
                           dtype=jnp.float32, num_layers=1)
    model, init_fn, loss_xla = make_model(cfg)
    _, _, loss_flash = make_model(dataclasses.replace(
        cfg, attention_impl="flash"))
    params = init_fn(jax.random.PRNGKey(0), 2, 32)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                          cfg.vocab_size)}
    (la, ga), (lb, gb) = (jax.jit(jax.value_and_grad(fn))(params, batch, None)
                          for fn in (loss_xla, loss_flash))
    assert abs(float(la) - float(lb)) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-4)
