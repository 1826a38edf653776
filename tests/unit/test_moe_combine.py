"""ISSUE 64: a sparse layer's ``ragged_dot`` path moves its rows without a
serial scatter. The combine kernel (``ops/kernels/moe_combine.py``,
interpreted here) against ``.at[].add``, which stays the CPU's path and is
the oracle; the counts and chosen scores by compare against ``bincount``
and ``take_along_axis``; and what the traced programs hold."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.sharded_moe import (
    _chosen_by_compare, _rows_chosen, grouped_moe_ffn, held_row_bound,
    route_topk)
from deepspeed_tpu.ops.kernels import moe_combine as mc


def _routing(S, E, k, shape, seed=0):
    """[S, k] distinct experts a token, as ``top_k`` gives them."""
    logits = jax.random.normal(jax.random.PRNGKey(seed), (S, E))
    if shape == "all_held":       # every choice of every token is 0 .. k
        logits = logits.at[:, :k].add(20.0)
    elif shape == "empty_expert":     # nobody chooses expert 1
        logits = logits.at[:, 1].add(-20.0)
    elif shape == "empty_tile":       # tokens 8 .. 16 choose no held expert
        logits = logits.at[8:16, :4].add(-20.0)
    elif shape == "one_expert":       # a run of a tile's every token
        logits = logits.at[:, 2].add(20.0)
    return jax.lax.top_k(logits, k)[1]


#: name: (S, E, k, held, rows visited (None: all), routing, M). The token
#: tile is 8 where S is 16 or less, so "empty_tile" leaves the second tile
#: without a row; "one_expert" gives expert 2 a run of 40 rows a tile of
#: 40 tokens (three 16-row chunks, none aligned with the run)
_CASES = {
    "share": (96, 16, 2, (0, 4), None, "drawn", 128),
    "share_cut": (512, 16, 2, (4, 2), 512, "drawn", 128),
    "whole": (64, 8, 2, None, None, "drawn", 128),
    "many_groups": (40, 40, 4, None, None, "drawn", 128),
    "share_of_many": (48, 64, 6, (3, 20), None, "drawn", 256),
    "overflowing": (64, 16, 2, (0, 2), None, "all_held", 128),
    "all_choices_held": (32, 8, 4, (0, 4), None, "all_held", 128),
    "empty_expert": (64, 8, 2, (0, 4), None, "empty_expert", 128),
    "empty_tile": (16, 8, 2, (0, 4), None, "empty_tile", 128),
    "run_over_chunks": (40, 8, 2, (0, 4), None, "one_expert", 128),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_the_combine_kernel_is_the_scatter_add(case, dtype):
    """``moe_combine`` over the sorted order against ``.at[tok_of].add`` of
    the weighted rows in float32, for every shape of routing the issue
    names; the rows past the groups hold NaN (what ``ragged_dot`` may leave
    there on the TPU) and none reaches a token."""
    S, E, k, held, rows, routing, M = _CASES[case]
    dtype = jnp.dtype(dtype)
    top_idx = _routing(S, E, k, routing)
    first, n = held or (0, E)
    eid = top_idx.reshape(-1)
    local = jnp.where((eid >= first) & (eid < first + n), eid - first, n)
    order = jnp.argsort(local, stable=True)
    sizes = _rows_chosen(top_idx, E)[first:first + n]
    total = int(sizes.sum())
    rows = rows or S * k
    assert total <= rows and rows % mc.CHUNK == 0
    if routing == "empty_expert":
        assert int(sizes[1]) == 0 and total > 0
    if routing == "one_expert":
        assert int(sizes[2]) == S
    kw, ky = jax.random.split(jax.random.PRNGKey(1))
    w_sel = jax.random.uniform(kw, (S, k), minval=0.1)
    ys = jax.random.normal(ky, (rows, M)).astype(dtype)
    ys = jnp.where((jnp.arange(rows) < total)[:, None], ys, jnp.nan)
    hit = top_idx[:, :, None] == first + jnp.arange(n)      # [S, k, n]
    weight = jnp.sum(jnp.where(hit, w_sel[:, :, None].astype(dtype), 0),
                     axis=1).astype(jnp.float32)
    row = mc.rows_of(jnp.any(hit, axis=1), sizes)
    # the planes say where the sort put every chosen row
    slot_row = jnp.argsort(order)                   # [S * k]: slot -> row
    for t, j in ((0, 0), (S // 2, k - 1), (S - 1, 0)):
        e = int(top_idx[t, j]) - first
        if 0 <= e < n:
            assert int(row[t, e]) == int(slot_row[t * k + j])
    got = mc.moe_combine(ys, weight, row, sizes, jnp.float32,
                         interpret=True)
    first_rows = order[:rows]
    ws = jnp.take(w_sel.reshape(-1), first_rows).astype(dtype)
    kept = jnp.where((jnp.arange(rows) < total)[:, None], ys, 0)
    want = jnp.zeros((S, M), jnp.float32).at[first_rows // k].add(
        kept.astype(jnp.float32) * ws.astype(jnp.float32)[:, None])
    assert not np.isnan(np.asarray(got)).any()
    assert np.any(np.asarray(want)) == (total > 0)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def _layer(S, M, W, E, k, held, routing="drawn", seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    n = E if held is None else held[1]
    tokens = jax.random.normal(ks[0], (S, M))
    logits = jax.random.normal(ks[1], (S, E))
    if routing == "overflowing":
        logits = logits.at[:, held[0]:held[0] + held[1]].add(10.0)
    weights = tuple(jax.random.normal(kk, shape) * 0.3 for kk, shape in zip(
        ks[2:], ((n, M, W), (n, M, W), (n, W, M))))
    return tokens, logits, weights


def _value_and_grads(S, M, W, E, k, held, routing, score="sigmoid"):
    tokens, logits, weights = _layer(S, M, W, E, k, held, routing)

    def loss(tokens, logits, weights):
        out, _, counts = grouped_moe_ffn(
            tokens, logits, k, weights, jax.nn.silu, jnp.float32,
            score=score, held=held, return_counts=True)
        return jnp.sum(jnp.sin(out)), (out, counts)

    return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(
        tokens, logits, weights)


@pytest.mark.parametrize("case", ["share_cut", "overflowing", "whole",
                                  "softmax_whole"])
def test_the_gradient_through_the_kernel_is_the_scatter_adds(case,
                                                             monkeypatch):
    """``jax.grad`` of a loss through ``grouped_moe_ffn(impl=None)`` with
    the kernel forced against the ``.at[].add`` form: the output, the
    per-expert rows, and the gradients of the tokens, the router's logits
    and the three stacks. ``share_cut`` takes the ``cond``'s bound branch,
    ``overflowing`` its full one (every choice held), ``whole`` no
    ``cond``."""
    S, M, W, E, k, held, routing, score = {
        "share_cut": (512, 128, 8, 16, 2, (4, 2), "drawn", "sigmoid"),
        "overflowing": (512, 128, 8, 16, 2, (4, 2), "overflowing",
                        "sigmoid"),
        "whole": (96, 128, 8, 8, 2, None, "drawn", "sigmoid"),
        "softmax_whole": (96, 128, 8, 8, 3, None, "drawn", "softmax"),
    }[case]
    (_, (ref, ref_counts)), g_ref = _value_and_grads(
        S, M, W, E, k, held, routing, score)
    monkeypatch.setattr(sharded_moe, "combine_impl",
                        lambda S, n, M, rows, dtype: "interpret")
    (_, (out, counts)), g = _value_and_grads(
        S, M, W, E, k, held, routing, score)
    if held is not None:
        n_here = int(counts[held[0]:held[0] + held[1]].sum())
        assert (n_here > held_row_bound(S, k, E, held)) == (
            case == "overflowing")
    np.testing.assert_array_equal(counts, ref_counts)
    assert np.any(np.asarray(ref))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        assert np.any(np.asarray(b))
        assert np.linalg.norm(a - b) < 1e-5 * np.linalg.norm(b)


@pytest.mark.parametrize("score,normalize", [
    ("sigmoid", True), ("sigmoid", False), ("softmax", False)])
def test_the_compares_are_bincount_and_take_along_axis_bit_for_bit(
        score, normalize):
    """The per-expert rows as a sum over a one-hot compare against
    ``bincount``, and the chosen scores as a masked sum against the gather,
    through ``route_topk``'s whole tail (the renormalisation and the
    scale): every bit. A softmax router that renormalises reads no score."""
    S, E, k = 384, 24, 4
    logits = jax.random.normal(jax.random.PRNGKey(5), (S, E)) * 3
    bias = jax.random.normal(jax.random.PRNGKey(6), (E,)) \
        if score == "sigmoid" else None
    kw = dict(score=score, bias=bias, normalize=normalize, scale=2.5)
    top_idx, w_gather, gates = route_topk(logits, k, **kw)
    _, w_compare, _ = route_topk(logits, k, chosen=_chosen_by_compare, **kw)
    np.testing.assert_array_equal(w_compare, w_gather)
    np.testing.assert_array_equal(
        _chosen_by_compare(gates, top_idx),
        jnp.take_along_axis(gates, top_idx, axis=-1))
    np.testing.assert_array_equal(
        _rows_chosen(top_idx, E),
        jnp.bincount(top_idx.reshape(-1), length=E))
    np.testing.assert_array_equal(
        _rows_chosen(top_idx[:, :1], E),
        jnp.bincount(top_idx[:, 0], length=E))
    # and its transpose: the gather's scatter-add, as a masked broadcast
    ct = jax.random.normal(jax.random.PRNGKey(7), (S, k))
    np.testing.assert_array_equal(
        jax.vjp(lambda g: _chosen_by_compare(g, top_idx), gates)[1](ct)[0],
        jax.vjp(lambda g: jnp.take_along_axis(g, top_idx, axis=-1),
                gates)[1](ct)[0])


def _scatters(jaxpr, found):
    """(primitive, update elements) of every scatter in ``jaxpr`` and what
    it calls, a Pallas kernel's own body apart."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            found.append((eqn.primitive.name,
                          int(np.prod(eqn.invars[2].aval.shape))))
        if eqn.primitive.name == "pallas_call":
            found.append(("pallas_call", eqn.params["name"]))
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scatters(sub, found)
    return found


@pytest.mark.parametrize("held", [(4, 2), None])
def test_the_tpu_paths_train_step_holds_no_scatter_of_routed_size(
        held, monkeypatch):
    """What a TPU backend traces for a sparse layer's forward and backward
    (``combine_impl`` says "pallas"; tracing runs no kernel): no
    ``scatter`` whose updates number more than ``E``, and the rows rejoin
    their tokens through ``moe_combine``: once forward, and in the backward
    for the tokens' and the weights' cotangents and NOT in its recompute,
    in each branch of a cut share's ``cond``. Off the TPU the same trace
    holds the scatter-adds of every row (the oracle's)."""
    S, M, W, E, k = 512, 128, 8, 16, 2
    tokens, logits, weights = _layer(S, M, W, E, k, held)

    def loss(tokens, logits, weights):
        out, _, counts = grouped_moe_ffn(
            tokens, logits, k, weights, jax.nn.silu, jnp.float32,
            score="sigmoid", held=held, return_counts=True)
        return jnp.sum(jnp.sin(out)), counts

    def trace():
        return _scatters(jax.make_jaxpr(jax.value_and_grad(
            loss, (0, 1, 2), has_aux=True))(tokens, logits, weights).jaxpr,
            [])

    rows = held_row_bound(S, k, E, held)
    plain = trace()
    assert max(n for name, n in plain if name != "pallas_call") >= rows * M
    assert not any(name == "pallas_call" for name, _ in plain)
    monkeypatch.setattr(sharded_moe, "combine_impl",
                        lambda S, n, M, rows, dtype: "pallas")
    found = trace()
    assert [n for name, n in found if name != "pallas_call" and n > E] == []
    calls = [n for name, n in found if name == "pallas_call"]
    assert set(calls) == {"moe_combine"}
    branches = 1 if rows == S * k else 2
    # forward and the two cotangents; the recompute a checkpointed branch
    # runs inside the backward keeps no combine (nobody reads its output)
    assert len(calls) == branches * 3


@pytest.mark.parametrize("S,n,M,rows,dtype,fits,tile,group", [
    (16384, 16, 2048, 32768, "bfloat16", True, 256, 16),   # the train cell
    (16384, 16, 2048, 131072, "bfloat16", True, 256, 16),  # its full branch
    (16384, 128, 2048, 131072, "bfloat16", True, 256, 16),  # a whole layer
    (16384, 16, 128, 32768, "float32", True, 256, 16),  # weights' cotangent
    (4096, 8, 4096, 8192, "float32", True, 128, 8),     # mixtral's widths
    (100, 4, 128, 300, "float32", False, 104, 4),       # rows: no chunks
    (512, 4, 96, 1024, "float32", False, 256, 4),       # lanes
    (512, 4, 128, 1024, "int8", False, 256, 4),
])
def test_the_kernels_tiles_follow_the_calls_shapes(S, n, M, rows, dtype,
                                                   fits, tile, group):
    """``fits`` (which ``combine_impl`` asks on a TPU backend), the tokens
    and the experts of a grid step: the float32 tile and a slot of a
    round's rows stay at 2 MB each whatever the width; off the TPU
    ``combine_impl`` is None for every call."""
    assert mc.fits(S, n, M, rows, dtype) == fits
    assert mc.token_tile(S, M) == tile
    assert mc.group(n, M, dtype) == group
    assert tile * M * 4 <= 1 << 21
    assert group * mc.CHUNK * M * jnp.dtype(dtype).itemsize <= 1 << 21
    assert sharded_moe.combine_impl(S, n, M, (rows,), dtype) is None


#: sha256 of the jaxpr of a serve step's sparse layer
#: (``llama_runner._moe_mlp`` as a TPU backend traces it: ``impl="pallas"``,
#: the loop's counters on; 64 rows, top-8 of 64 experts) as the parent of
#: ISSUE 64 (``c376070``) traced it, addresses cut; under jax ``_PINNED_JAX``.
#: ``sigmoid``: a selection bias, weights renormalised and scaled (Solar,
#: Pangu, Kimi, Nemotron, Mellum2, LFM2); ``softmax``: over the chosen
#: (OLMoE); ``softmax_all``: their share of the softmax over all
_SERVE_LAYER_PINNED = {
    "sigmoid": "1815e55ba19476a4c65c0c491da374ca"
               "71c19ae1eef274f2e635a4f13217c10f",
    "softmax": "e4d11e1e95d910fc798e01b2ee5cddfe"
               "ac92f7dba9c970aa127b0aa97a00eab5",
    "softmax_all": "0fffe12a1c1bae3da5cee1596c73eca4"
                   "da0528bd9c7c8e748f268eaa3a1921bf",
}
_PINNED_JAX = "0.9.0"


@pytest.mark.parametrize("router", sorted(_SERVE_LAYER_PINNED))
def test_a_serve_steps_sparse_layer_traces_what_it_always_traced(
        router, monkeypatch):
    """What may NOT move with ISSUE 64: the seven MoE serve cells call
    ``route_topk`` through ``grouped_moe_ffn(impl="pallas")`` and once more
    for the loop's counters, and Nemotron's ``correct`` stands at 1.94
    sigma of a limit of 2.0 (an integer-only change re-fused a float
    expression there once). The picks by compare live behind
    ``route_topk(chosen=...)``, which the ``impl=None`` body alone passes:
    the serve layer's jaxpr (router matmul, ``route_topk``, the grouped
    kernel's layout and body, the weighted sum, the counters) is the
    parent's, character for character."""
    import hashlib
    import re
    import types
    from deepspeed_tpu.inference.v2.llama_runner import _moe_mlp
    if jax.__version__ != _PINNED_JAX:
        pytest.skip(f"pinned under jax {_PINNED_JAX}")
    E, k, M, W, S, C = 64, 8, 256, 128, 4, 16
    cfg = types.SimpleNamespace(
        num_experts=E, experts_top_k=k,
        norm_topk_prob=router != "softmax_all",
        router_score="sigmoid" if router == "sigmoid" else "softmax",
        router_bias=router == "sigmoid",
        routed_scaling=2.5 if router == "sigmoid" else 1.0)
    sd = jax.ShapeDtypeStruct
    p = {"gate": sd((M, E), jnp.float32),
         "wi_gate": sd((E, M, W), jnp.bfloat16),
         "wi_up": sd((E, M, W), jnp.bfloat16),
         "wo": sd((E, W, M), jnp.bfloat16),
         "sel_bias": sd((E,), jnp.float32)}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = str(jax.make_jaxpr(
        lambda p, h, valid: _moe_mlp(p, h, cfg, jnp.bfloat16, valid=valid))(
            p, sd((S, C, M), jnp.bfloat16), sd((S, C), jnp.bool_)))
    assert "pallas_call" in text and "moe_combine" not in text
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == _SERVE_LAYER_PINNED[router]
