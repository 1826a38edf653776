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
    # the Trinity cell's geometry in miniature: an 8 x 8 grid under a window
    # of two blocks, so a row's grid steps are 3 of 8 (its first rows idle
    # the steps in front of block 0), GQA 4 : 1 as the dk/dv walk fuses it
    "eight_blocks_window_two_gqa_4_to_1": ((1024, 1024, 4, 1, 128, 256),
                                           (7, 8, 0, 6)),
    # the same window under tq < tk: every row's run starts inside the keys
    "eight_key_blocks_tq_lt_tk": ((512, 1024, 2, 2, 128, 256), (4, 4, 0, 4)),
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
    # query block meets an edge block, a whole block and a diagonal block,
    # and each of the three kernels walks 8 x 3 steps a q head, not 8 x 8
    ((8192, 8192, 1024, 1024, 8192, 0, 2048),
     dict(interior=7, sub_tiled=8, general=0, edge=6, skipped=43,
          steps=3 * 24, steps_run=3 * 21)),
    # the same at 512-blocks: five blocks a row
    ((8192, 8192, 512, 512, 8192, 0, 2048),
     dict(interior=1 + 2 + 3 + 12 * 3, sub_tiled=16, general=0, edge=12,
          skipped=256 - 16 - 12 - 42, steps=3 * 16 * 5, steps_run=3 * 70)),
    # a window that meets no corner: a row of 256 under 300 keys touches
    # three key blocks, and so does a column
    ((768, 768, 256, 256, 640, 0, 300),
     dict(interior=0, sub_tiled=0, general=6, edge=0, skipped=3,
          steps=3 * 9, steps_run=3 * 6)),
    # tq < tk: the query blocks' runs start inside the keys, a row's at
    # most three blocks, and key blocks 0, 1 are seen from no row at all
    ((512, 1024, 128, 128, 1024, 512, 256),
     dict(interior=4, sub_tiled=4, general=0, edge=4, skipped=20,
          steps=2 * 4 * 3 + 8 * 3, steps_run=3 * 12)),
    # no window: the plan the GPT cells always had, every step of the square
    ((2048, 2048, 1024, 1024, 2048, 0, None),
     dict(interior=1, sub_tiled=2, general=0, edge=0, skipped=1,
          steps=3 * 4, steps_run=3 * 3)),
])
def test_causal_plan_under_a_window(geom, want):
    plan = causal_plan(*geom)
    assert {k: plan[k] for k in want} == want
    tq, tk, window = geom[0], geom[1], geom[6]
    if tq == tk == geom[4]:
        assert plan["score_elems_needed"] == 3 * sum(
            min(i + 1, window or tq) for i in range(tq))
        assert 1.0 <= plan["score_area_share"] < 1.2
    assert plan["steps_run"] <= plan["steps"] \
        <= 3 * (tq // geom[2]) * (tk // geom[3])


@pytest.mark.parametrize("tq,tk,block_q,block_k,kv_len,offset,window", [
    (8192, 8192, 1024, 1024, 8192, 0, 2048),    # the Trinity cell
    (1024, 1024, 128, 128, 1024, 0, 256),
    (512, 1024, 128, 128, 1024, 512, 256),      # tq < tk
    (768, 768, 256, 256, 640, 0, 300),          # no corner, padded keys
    (512, 1024, 256, 128, 1000, 500, 200),      # block_q > block_k
    (1024, 768, 128, 256, 700, -300, 130),      # tq > tk: rows above the keys
    (512, 512, 256, 256, 512, 0, 64),           # a window under the block
    (640, 640, 128, 128, 640, 0, 1),            # a query sees its own key
])
def test_the_windowed_walk_meets_every_block_of_a_kind(
        tq, tk, block_q, block_k, kv_len, offset, window):
    """In plain ints, no kernel: the steps a row (a column, in dk/dv) takes
    under a window meet every block of the square that has a kind, each
    once, in ascending order, a row's last step its last block; a step
    outside the run may run no body; and every step holds a block inside
    the operand."""
    import importlib
    fa = importlib.import_module(
        "deepspeed_tpu.ops.kernels.flash_attention")
    nq, nk = tq // block_q, tk // block_k
    geom = dict(causal=True, block_q=block_q, block_k=block_k, kv_len=kv_len,
                causal_offset=offset, window=window)
    kinds = {(qi, ki) for qi in range(nq) for ki in range(nk)
             if any(fa._block_kinds(qi, ki, **geom))}
    k_steps, q_steps = fa._walk_steps(nq, nk, **geom)
    assert k_steps <= nk and q_steps <= nq
    met = []
    for qi in range(nq):
        row = [fa._key_step(qi, j, nk, k_steps, **geom)
               for j in range(k_steps)]
        assert [ki for ki, _ in row] == list(
            range(row[-1][0] - k_steps + 1, row[-1][0] + 1))
        assert row[-1][1]                   # ``_finish`` meets a live step
        met += [(qi, ki) for ki, seen in row if seen]
        for j in range(k_steps):
            assert 0 <= int(fa._last_key_block(qi, j, nk, k_steps,
                                               **geom)) < nk
    assert len(met) == len(set(met)) and kinds <= set(met)
    assert all(0 <= ki < nk for _, ki in met)
    met = []
    for ki in range(nk):
        col = [fa._query_step(ki, s, nq, **geom) for s in range(q_steps)]
        assert col[0][1]                    # the walk starts on a live step
        met += [(qi, ki) for qi, seen in col if seen]
        for s in range(q_steps):
            assert 0 <= int(fa._first_query_block(s, ki, nq, **geom)) < nq
    assert len(met) == len(set(met)) and kinds <= set(met)
    assert all(0 <= qi < nq for qi, _ in met)
    plan = causal_plan(tq, tk, block_q, block_k, kv_len, offset, window)
    assert plan["steps"] == 2 * nq * k_steps + nk * q_steps
    assert plan["steps_run"] == 3 * len(kinds)


def _pallas_grids(jaxpr):
    """The ``grid`` of every ``pallas_call`` under ``jaxpr``, in order."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grids += _pallas_grids(sub)
    return grids


def test_the_window_calls_grids_are_as_wide_as_the_window():
    """The Trinity cell's sliding layer, traced (not run) as the chip takes
    it: ``[2, 8192, 32 / 4, 128]`` at 1,024-blocks under a window of
    2,048. Forward and dq walk 3 key blocks a query block and dk/dv 3 query
    blocks a q head of the group of 8, where each walked all 8; the full
    layer's call beside it keeps the whole square."""
    q = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 8192, 4, 128), jnp.bfloat16)

    def grids(window):
        def f(q, k, v):
            return flash_attention(q, k, v, causal=True, window=window,
                                   interpret=False, block_q=1024,
                                   block_k=1024).astype(jnp.float32).sum()
        take_causal_plans()
        found = _pallas_grids(jax.make_jaxpr(
            jax.value_and_grad(f, argnums=(0, 1, 2)))(q, kv, kv).jaxpr)
        (_, _, plan), = take_causal_plans()
        return found, plan

    found, plan = grids(2048)
    assert found == [(2, 32, 8, 3), (2, 32, 8, 3), (2, 4, 8, 8 * 3)]
    assert (plan["steps"], plan["steps_run"], plan["skipped"]) == (72, 63, 43)
    found, plan = grids(None)
    assert found == [(2, 32, 8, 8), (2, 32, 8, 8), (2, 4, 8, 8 * 8)]
    assert (plan["steps"], plan["steps_run"], plan["skipped"]) \
        == (192, 3 * 36, 28)


#: sha256 of the jaxpr of the GPT train cells' flash call ([2, 2048, 16,
#: 128] bf16 at 1024-blocks, value and gradient) as the parent of ISSUE 61
#: traced it, addresses cut; under jax ``_PINNED_JAX``. Re-pinned by ISSUE
#: 69 (from ``afe4160a..``): the text sees the forward rule's two ``name``
#: equations and ``lse``'s slice / squeeze / ``broadcast_in_dim`` between
#: them (15 lines after the forward call and the renumbered variables
#: below; kernel bodies, index maps and names are the parent's:
#: ``test_kernels_flash.py::TestResidualNames`` holds the jaxpr to the
#: unnamed one's, ``name`` equations apart)
_PINNED = "d0d5d05a32f54b72b38d06951e2fc8ecab07c74197c0a8af31d1e622e4dea355"
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
