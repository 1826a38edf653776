"""AFMoE (Trinity) in training form against its plain reference
(``benchmark/reference/afmoe.py``): loss, per-token NLL and EVERY leaf's
gradient on seeded weights at a small size, the chip's share adding up to
the uncut layer, the router against ``transformers``' ``deepseek_v3`` one,
the selection bias moved by the engine's ``aux["add"]`` and by nothing
else, the step's counters, and every wrong model of the reference caught.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import afmoe as reference
from deepspeed_tpu.models.afmoe import AfmoeConfig, make_model

#: two dense + four sparse layers at the published pattern (3 sliding : 1
#: full), T = 4 windows
CFG = AfmoeConfig.tiny(num_dense_layers=2, remat=False,
                       attention_impl="xla")
T = 64
#: the engine's tests: one dense layer, two sparse
SMALL = AfmoeConfig.tiny(num_dense_layers=1, remat=False,
                         attention_impl="xla",
                         layer_kinds=("swa", "swa", "attn"))


def _dims(cfg, **kw):
    return dict(sliding=tuple(k == "swa" for k in cfg.layer_kinds),
                num_dense=cfg.num_dense_layers, num_heads=cfg.num_heads,
                kv_heads=cfg.num_kv_heads, window=cfg.sliding_window,
                rope_theta=cfg.rope_theta, top_k=cfg.experts_top_k,
                route_norm=cfg.route_norm, route_scale=cfg.route_scale,
                rms_eps=cfg.rms_eps, held=cfg.held, mup=cfg.mup_enabled,
                q_block=32, **kw)


def _draw(cfg, seed=0):
    """The tree ``init_fn`` gives with every leaf drawn: norms' scales
    apart from 1, the biases at 0.05 (so that they DECIDE selections), the
    embedding so that ``sqrt(hidden) E`` has deviation 1."""
    _, init_fn, _ = make_model(cfg)
    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        z = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), i),
                              leaf.shape, jnp.float32)
        if "scale" in name:
            out.append(1.0 + 0.3 * z)
        elif "select_bias" in name:
            out.append(0.05 * z)
        elif "embedding" in name:
            out.append(z * cfg.hidden_size ** -0.5)
        else:
            out.append(z * leaf.shape[-2] ** -0.5)
    return jax.tree_util.tree_unflatten(treedef, out)


def _tokens(cfg, batch=2, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, T + 1), 0,
                              cfg.vocab_size, jnp.int32)


def reference_side(params, tokens, **dims):
    """(loss, per-token NLL, every leaf's gradient) in one program."""
    def f(p):
        nll = reference.per_token_nll(p, tokens, **dims)
        return nll.mean(), nll
    (loss, nll), grads = jax.jit(jax.value_and_grad(f, has_aux=True))(params)
    return float(loss), np.asarray(nll), grads


@functools.lru_cache(maxsize=None)
def _reference_side(head_dim=CFG.head_dim):
    cfg = dataclasses.replace(CFG, attn_head_dim=head_dim)
    params, tokens = _draw(cfg), _tokens(cfg)
    return (params, tokens) + reference_side(params, tokens, **_dims(cfg))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("impl,head_dim", [
    ("xla", 16), ("flash_interpret", 16), ("flash_interpret", 128)])
def test_loss_nll_and_every_leafs_gradient_match_the_reference(impl,
                                                               head_dim):
    """At 128-lane heads ``"flash_interpret"`` routes ``q`` and ``k``
    through ``qk_norm_rope``'s kernel, interpreted (ISSUE 67); at the toy
    16 lanes, and under ``"xla"``, through its ``jax.numpy`` twin. The
    norms' scales stay leaves of the tree under the names they had."""
    from deepspeed_tpu.ops.kernels import qk_norm_rope
    params, tokens, ref_loss, ref_nll, ref_grads = _reference_side(head_dim)
    cfg = dataclasses.replace(CFG, attention_impl=impl, flash_block_q=32,
                              flash_block_k=32, xent_chunks=2,
                              attn_head_dim=head_dim)
    assert qk_norm_rope.impl_of(
        T, cfg.num_heads, head_dim, cfg.dtype, impl == "flash_interpret") \
        == ("interpret" if head_dim == 128 else None)
    for name in ("q_norm", "k_norm"):
        scale, = params["layer_0"]["attn"][name].values()
        assert list(params["layer_0"]["attn"][name]) == ["scale"]
        assert (scale.shape, scale.dtype) == ((head_dim,), jnp.float32)
    model, _, loss_fn = make_model(cfg)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, {"tokens": tokens}, None), has_aux=True))(params)
    assert abs(float(loss) - ref_loss) < 1e-5
    logits = jax.jit(lambda p: model.apply({"params": p}, tokens[:, :-1]))(
        params)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               tokens[:, 1:, None], -1)[..., 0]
    assert np.abs(np.asarray(nll) - ref_nll).max() < 1e-4
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert len(flat) == len(ref) >= 60
    worst = max((_rel(g, ref[path]), jax.tree_util.keystr(path))
                for path, g in flat if "select_bias" not in
                jax.tree_util.keystr(path))
    assert worst[0] < 1e-4, worst
    # the bias takes part in a selection only
    assert all(not np.any(np.asarray(g)) for path, g in flat
               if "select_bias" in jax.tree_util.keystr(path))
    counts = reference.expert_counts(params, tokens, **_dims(CFG))
    assert int(aux["counters"]["moe_rows_routed"]) == sum(
        int(c.sum()) for c in counts) == 4 * 2 * T * CFG.experts_top_k
    assert int(aux["counters"]["moe_rows_elsewhere"]) == 0
    for i, c in zip(range(2, 6), counts):
        bias = params[f"layer_{i}"]["moe"]["select_bias"]
        np.testing.assert_array_equal(
            bias + aux["add"][f"layer_{i}/moe/select_bias"],
            reference.bias_update(bias, c, CFG.load_balance_coeff))


@pytest.mark.parametrize("head_dim", [16, 128])
def test_a_layers_recompute_keeps_its_flash_calls_output_and_row_sums(
        monkeypatch, head_dim):
    """ISSUE 69: a layer's ``nn.remat`` keeps the flash call's ``o`` and
    ``lse`` (``afmoe._REMAT_POLICY``), so the gradient's program holds one
    forward kernel a layer where the policy-less remat holds two; the kept
    arrays are the ones the recompute made, so loss and EVERY leaf's
    gradient are the same to the bit, and ``remat`` off is both again."""
    from deepspeed_tpu.models import afmoe
    cfg = dataclasses.replace(
        CFG, attention_impl="flash_interpret", flash_block_q=32,
        flash_block_k=32, xent_chunks=2, attn_head_dim=head_dim, remat=True)
    params, tokens = _draw(cfg), _tokens(cfg)

    def forward_kernels(jaxpr):     # the flash call that reads q, k, v
        return sum((eqn.primitive.name == "pallas_call"
                    and eqn.params["name"].startswith("attn")
                    and len(eqn.invars) == 3)
                   + sum(map(forward_kernels,
                             jax.core.jaxprs_in_params(eqn.params)))
                   for eqn in jaxpr.eqns)

    def side(c):
        fn = jax.value_and_grad(
            lambda p: make_model(c)[2](p, {"tokens": tokens}, None),
            has_aux=True)
        (loss, aux), grads = jax.jit(fn)(params)
        return (forward_kernels(jax.make_jaxpr(fn)(params).jaxpr), loss,
                grads, int(aux["counters"]["flash_residuals_kept_layers"]))

    kept = side(cfg)
    monkeypatch.setattr(afmoe, "_REMAT_POLICY", None)
    plain = side(cfg)
    off = side(dataclasses.replace(cfg, remat=False))
    L = cfg.num_layers
    assert (kept[0], plain[0], off[0]) == (L, 2 * L, L)
    assert (kept[3], off[3]) == (L, 0)
    for other in (plain, off):
        assert float(kept[1]) == float(other[1])
        for a, b in zip(jax.tree_util.tree_leaves(kept[2]),
                        jax.tree_util.tree_leaves(other[2])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's section 4 test, forward AND gradient: with ``held`` each
    eighth of the experts in turn, the routed parts add up to the uncut
    layer's (the shared expert is outside them, counted once), and a held
    expert's weight gradient is the uncut layer's for that expert."""
    from deepspeed_tpu.models.afmoe import AfmoeSparse
    cfg = dataclasses.replace(CFG, num_experts=16, experts_top_k=4)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, T, cfg.hidden_size))
    whole = AfmoeSparse(cfg)
    params = whole.init(jax.random.PRNGKey(4), h)["params"]
    params = dict(params, select_bias=0.05 * jax.random.normal(
        jax.random.PRNGKey(5), (16,)))

    def out_and_grads(cfg, p):
        def f(p):
            y, counts = AfmoeSparse(cfg).apply({"params": p}, h)
            return jnp.sum(jnp.sin(y)), (y, counts)
        (_, (y, counts)), g = jax.value_and_grad(f, has_aux=True)(p)
        return y, counts, g

    y_all, counts_all, _ = out_and_grads(cfg, params)
    # the uncut layer's gradient wrt each expert's weights, given the
    # cotangent the WHOLE output defines
    ct = jnp.cos(y_all)
    _, vjp = jax.vjp(lambda p: AfmoeSparse(cfg).apply({"params": p}, h)[0],
                     params)
    g_all, = vjp(ct)
    total = jnp.zeros_like(y_all)
    for first in range(0, 16, 2):
        share = dataclasses.replace(cfg, experts_held=2, experts_first=first)
        p = dict(params, **{k: params[k][first:first + 2]
                            for k in ("wi_gate", "wi_up", "wo")})
        y, counts, _ = out_and_grads(share, p)
        np.testing.assert_array_equal(counts, counts_all)
        total = total + y
        _, vjp = jax.vjp(
            lambda p: AfmoeSparse(share).apply({"params": p}, h)[0], p)
        g, = vjp(ct)
        for k in ("wi_gate", "wi_up", "wo"):
            np.testing.assert_allclose(g[k], g_all[k][first:first + 2],
                                       atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(total, y_all, atol=1e-5, rtol=1e-5)


def _engine(loss_fn, params, **extra):
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.config.config import MeshConfig
    topology = dstpu.build_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    engine, _, _, _ = dstpu.initialize(
        loss_fn=loss_fn, params=params, topology=topology, config=dict({
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1, "gradient_clipping": 1.0,
            "steps_per_print": 10 ** 6,
            "optimizer": {"type": "AdamW", "params": {
                "lr": 1e-3, "weight_decay": 0.1}},
            "zero_optimization": {"stage": 0}, "mesh": {"data": 1}}, **extra))
    return engine


def test_the_engine_moves_the_bias_and_sums_the_counters():
    """``aux["add"]``: after one ``train_batch`` each bias equals
    ``reference.bias_update`` of the step's counts exactly (no decay, no
    moment), and no other leaf differs from a run whose loss drops the
    key. ``aux["counters"]``: summed over 3 steps they equal the host's
    recount, and ``step_stats`` is where they are read."""
    cfg = dataclasses.replace(SMALL, experts_held=4, experts_first=4)
    _, _, loss_fn = make_model(cfg)
    params = _draw(cfg)
    dims = _dims(cfg)
    batches = [{"tokens": _tokens(cfg, seed=s)} for s in (1, 2, 3)]

    def without(p, batch, rng):
        loss, aux = loss_fn(p, batch, rng)
        return loss, {"counters": aux["counters"]}

    plain = _engine(without, params)
    plain.train_batch(batches[0])
    engine = _engine(loss_fn, params)
    recount = {"moe_rows_routed": 0, "moe_rows_elsewhere": 0,
               "moe_rows_hottest": 0}
    for n, batch in enumerate(batches):
        before = jax.device_get(engine.state.params)
        counts = reference.expert_counts(before, batch["tokens"], **dims)
        engine.train_batch(batch)
        after = jax.device_get(engine.state.params)
        for i, c in zip(range(1, 3), counts):
            np.testing.assert_array_equal(
                after[f"layer_{i}"]["moe"]["select_bias"],
                reference.bias_update(
                    before[f"layer_{i}"]["moe"]["select_bias"], c,
                    cfg.load_balance_coeff))
            here = np.asarray(c[4:8])
            recount["moe_rows_routed"] += int(here.sum())
            recount["moe_rows_elsewhere"] += int(c.sum() - here.sum())
            recount["moe_rows_hottest"] += int(here.max()) * 4
        if n == 0:
            other = jax.device_get(plain.state.params)
            for (path, a), b in zip(
                    jax.tree_util.tree_leaves_with_path(after),
                    jax.tree_util.tree_leaves(other)):
                if "select_bias" not in jax.tree_util.keystr(path):
                    np.testing.assert_array_equal(a, b)
    stats = engine.step_stats
    assert {k: stats[k] for k in recount} == recount
    assert stats["steps"] == 3 and plain.step_stats["moe_rows_routed"] > 0


def test_the_bias_master_moves_by_the_rule_under_a_bf16_compute_copy():
    """A bias near 1 has a bf16 copy whose spacing (0.0078) is more than
    the rule's step (0.001): written back from the copy it could never
    move. The step adds the move to the float32 MASTER: every entry moves
    by exactly ``-c``, ``0`` or ``+c`` each step, and an entry the first
    step moved keeps the precision the copy would have dropped."""
    cfg = dataclasses.replace(SMALL, experts_held=4, dtype=jnp.bfloat16)
    _, _, loss_fn = make_model(cfg)
    params = _draw(cfg)
    for i in (1, 2):
        moe = params[f"layer_{i}"]["moe"]
        moe["select_bias"] = 1.0 + moe["select_bias"]
    engine = _engine(loss_fn, params, bf16={"enabled": True})
    c = np.float32(cfg.load_balance_coeff)
    moved = 0
    for seed in (1, 2):
        before = jax.device_get(engine.state.params)
        engine.train_batch({"tokens": _tokens(cfg, seed=seed)})
        after = jax.device_get(engine.state.params)
        for i in (1, 2):
            b0, b1 = (t[f"layer_{i}"]["moe"]["select_bias"]
                      for t in (before, after))
            assert b1.dtype == np.float32
            assert set(np.unique(b1 - b0)) <= {
                x for s in (-1, 0, 1) for x in np.unique(
                    (b0 + np.float32(s) * c) - b0)}
            moved += int(np.sum(b1 != b0))
            # a master the copy's rounding would have swallowed
            assert np.any(b1 != np.asarray(
                jnp.asarray(b1).astype(jnp.bfloat16).astype(jnp.float32)))
    assert moved > 0


def test_accumulation_sums_the_counters_and_a_share_refuses_an_expert_mesh():
    cfg = dataclasses.replace(SMALL, experts_held=4)
    _, _, loss_fn = make_model(cfg)
    params, batch = _draw(cfg), {"tokens": _tokens(cfg, batch=4)}
    one = _engine(loss_fn, params, train_micro_batch_size_per_gpu=4)
    two = _engine(loss_fn, params, gradient_accumulation_steps=2)
    one.train_batch(batch)
    two.train_batch(batch)
    for key in ("moe_rows_routed", "moe_rows_elsewhere"):
        assert one.step_stats[key] == two.step_stats[key] > 0
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.parallel.topology import set_topology
    set_topology(dstpu.build_mesh(MeshConfig(data=1, expert=2),
                                  devices=jax.devices()[:2]))
    with pytest.raises(NotImplementedError, match="experts_held is ONE"):
        loss_fn(params, batch, None)


def test_config_from_hf_reads_the_catalog_rows_keys():
    import json
    import os
    from deepspeed_tpu.models.registry import config_from_hf
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "trinity-mini-26b-a3b.json")) as f:
        d = json.load(f)
    name, cfg = config_from_hf(dict(d, num_experts=d["num_experts_published"]))
    assert name == "afmoe"
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.sliding_window, cfg.num_experts, cfg.experts_top_k,
            cfg.route_scale, cfg.moe_intermediate_size) == (
                2048, 32, 4, 128, 2048, 128, 8, 2.826, 1024)
    assert cfg.layer_kinds == ("swa", "swa", "swa", "swa", "attn")
    with pytest.raises(ValueError, match="expert groups"):
        config_from_hf(dict(d, n_group=2))


def _every_row_ffn(tokens, logits, k, weights, held, **router):
    """``grouped_moe_ffn``'s ``ragged_dot`` path as the parents of ISSUE 62
    and ISSUE 64 had it, kept here to compare with: the chosen scores by
    ``take_along_axis`` (``route_topk`` as every serve step calls it), the
    groups' sizes by ``bincount``, every routed row sorted, gathered,
    multiplied, masked and added back. ``held`` None: a whole layer."""
    from deepspeed_tpu.moe.sharded_moe import _keep_cotangent_rows, route_topk
    top_idx, w_sel, _ = route_topk(logits, k, score="sigmoid", **router)
    eid = top_idx.reshape(-1)
    first, count = held or (0, logits.shape[1])
    here = (eid >= first) & (eid < first + count)
    eid = jnp.where(here, eid - first, count)
    order = jnp.argsort(eid, stable=True)
    tok_of = order // k
    xs = _keep_cotangent_rows(jnp.take(tokens, tok_of, axis=0),
                              jnp.take(here, order))
    sizes = jnp.bincount(eid, length=count).astype(jnp.int32)
    wi_gate, wi_up, wo = weights
    h = jax.nn.silu(jax.lax.ragged_dot(xs, wi_gate, sizes)) \
        * jax.lax.ragged_dot(xs, wi_up, sizes)
    ys = jax.lax.ragged_dot(h, wo, sizes)
    ys = jnp.where(jnp.take(here, order)[:, None], ys, 0)
    ws = jnp.take(w_sel.reshape(-1), order)
    return jnp.zeros_like(tokens).at[tok_of].add(ys * ws[:, None])


@pytest.mark.parametrize("routing", ["even", "overflowing"])
def test_a_share_cut_to_its_bound_is_the_share_over_every_row(routing):
    """ISSUE 62: a held share visits ``held_row_bound`` rows of the sorted
    order, and every routed row in the step whose held rows exceed that.
    Either way nothing is dropped: the output is the parent's body's bit
    for bit (a token's held rows are added in the order they were) and the
    gradients of the tokens, the three stacks and the logits agree to
    1e-6; the step's counters say which body ran."""
    from deepspeed_tpu.models.afmoe import step_counters
    from deepspeed_tpu.moe.sharded_moe import grouped_moe_ffn, held_row_bound
    S, M, W, E, k, held = 512, 16, 8, 16, 2, (4, 2)
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    tokens = jax.random.normal(ks[0], (S, M))
    logits = jax.random.normal(ks[1], (S, E))
    weights = tuple(jax.random.normal(kk, shape) * 0.3 for kk, shape in zip(
        ks[2:], ((2, M, W), (2, M, W), (2, W, M))))
    if routing == "overflowing":    # every token's choices are held
        logits = logits.at[:, 4:6].add(10.0)
    bound = held_row_bound(S, k, E, held)
    assert bound == 512 < S * k

    def cut(tokens, weights, logits):
        out, _, counts = grouped_moe_ffn(
            tokens, logits, k, weights, jax.nn.silu, jnp.float32,
            score="sigmoid", held=held, return_counts=True)
        return jnp.sum(jnp.sin(out)), (out, counts)

    def every(tokens, weights, logits):
        out = _every_row_ffn(tokens, logits, k, weights, held)
        return jnp.sum(jnp.sin(out)), out

    (_, (out, counts)), g = jax.jit(jax.value_and_grad(
        cut, (0, 1, 2), has_aux=True))(tokens, weights, logits)
    (_, ref), g_ref = jax.jit(jax.value_and_grad(
        every, (0, 1, 2), has_aux=True))(tokens, weights, logits)
    n_here = int(counts[4:6].sum())
    assert (n_here > bound) == (routing == "overflowing")
    assert np.any(np.asarray(ref))
    np.testing.assert_array_equal(out, ref)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        assert np.any(np.asarray(b))
        assert _rel(a, b) < 1e-6
    cfg = AfmoeConfig.tiny(num_experts=E, experts_top_k=k, experts_held=2,
                           experts_first=4)
    c = step_counters(cfg, [counts], S, S)
    assert int(c["moe_rows_routed"]) == n_here
    if routing == "overflowing":
        assert n_here == S * k
        assert (int(c["moe_layers_full"]), int(c["moe_rows_visited"])) == (
            1, S * k)
    else:
        assert (int(c["moe_layers_full"]), int(c["moe_rows_visited"])) == (
            0, bound)


@pytest.mark.parametrize("S,k,E,held,bound", [
    (16384, 8, 128, (0, 16), 32768),    # train-trinity-mini-8k-1chip
    (16384, 8, 128, (0, 128), 131072),
    (16384, 8, 128, None, 131072),
    (16384, 8, 128, (64, 64), 131072),  # twice a half: every row
    (512, 2, 16, (4, 2), 512),
    (100, 3, 16, (0, 1), 300),          # a tile is more than the rows
    (4096, 6, 64, (8, 3), 2560),        # 1,152 expected: 2,304 -> 5 tiles
])
def test_the_bound_on_a_shares_rows_is_twice_its_even_part(S, k, E, held,
                                                           bound):
    from deepspeed_tpu.moe.sharded_moe import held_row_bound
    got = held_row_bound(S, k, E, held)
    assert got == bound
    assert got == S * k or (got % 512 == 0 and got < S * k)
    if held is not None:
        assert got >= min(S * k, 2 * S * k * held[1] / E)


def test_the_steps_counters_say_which_layers_visited_every_row():
    """``step_counters`` from the layers' per-expert rows alone: a layer
    whose held rows exceed the bound counts every routed row as visited
    and itself as full, a layer at or under it the bound; a tree that holds
    every expert visits every row and no layer is "full"."""
    from deepspeed_tpu.models.afmoe import step_counters
    from deepspeed_tpu.moe.sharded_moe import held_row_bound
    tokens, E, k = 1024, 16, 4
    cfg = AfmoeConfig.tiny(num_experts=E, experts_top_k=k, experts_held=2,
                           experts_first=6)
    bound = held_row_bound(tokens, k, E, cfg.held)
    assert bound == 1024

    def layer(here):        # `here` rows on experts 6 and 7, the rest on 0
        c = np.zeros(E, np.int32)
        c[6], c[7] = here - here // 3, here // 3
        c[0] = tokens * k - here
        return jnp.asarray(c)

    got = jax.jit(lambda cs: step_counters(cfg, cs, tokens, 64))(
        [layer(300), layer(bound), layer(bound + 1), layer(4096)])
    assert {key: int(v) for key, v in got.items()} == {
        "moe_rows_routed": 300 + 1024 + 1025 + 4096,
        "moe_rows_elsewhere": 4 * 4096 - (300 + 1024 + 1025 + 4096),
        "moe_rows_hottest": 2 * (200 + 683 + 684 + 2731),
        "moe_rows_visited": 2 * 1024 + 2 * 4096, "moe_layers_full": 2,
        # off the TPU ``.at[].add`` moves the rows (ISSUE 64),
        # ``qk_norm_rope`` runs its twin (ISSUE 67) and no flash call
        # leaves residuals to keep (ISSUE 69)
        "moe_combine_layers": 0, "attn_prep_fused_layers": 0,
        "flash_residuals_kept_layers": 0}
    whole = AfmoeConfig.tiny(num_experts=E, experts_top_k=k)
    got = step_counters(whole, [layer(300), layer(4096)], tokens, 64)
    assert (int(got["moe_rows_visited"]), int(got["moe_layers_full"])) == (
        2 * 4096, 0)


@pytest.mark.parametrize("prep,remat", [
    ("twin", False), ("kernel", False), ("kernel", True), ("twin", True)])
def test_the_new_counters_reach_step_stats_and_add_up_over_steps(prep,
                                                                 remat):
    """ISSUE 62's two counters through the engine: a tree whose first
    sparse layer's selection bias sends every choice to the held experts
    (its held rows exceed the bound: every row visited, in every step) and
    whose second routes as drawn (the bound's rows visited). Over three
    steps ``step_stats`` holds the host's recount, as for
    ``moe_rows_routed``. And ISSUE 67's: every layer of a step whose ``q``
    and ``k`` went through ``qk_norm_rope``'s Pallas call (128-lane heads,
    interpreted here), none where its twin ran. And ISSUE 69's: every
    layer of a step whose ``nn.remat`` kept its flash call's output and
    row sums, none without ``remat`` and none where no flash call ran."""
    from deepspeed_tpu.moe.sharded_moe import held_row_bound
    cfg = dataclasses.replace(SMALL, experts_held=4, experts_first=4,
                              remat=remat)
    if prep == "kernel":
        cfg = dataclasses.replace(cfg, attn_head_dim=128, flash_block_q=32,
                                  flash_block_k=32,
                                  attention_impl="flash_interpret")
    _, _, loss_fn = make_model(cfg)
    params = _draw(cfg)
    moe = params["layer_1"]["moe"]
    moe["select_bias"] = moe["select_bias"].at[4:8].add(10.0)
    dims = _dims(cfg)
    rows = 4 * T * cfg.experts_top_k
    bound = held_row_bound(4 * T, cfg.experts_top_k, cfg.num_experts,
                           cfg.held)
    assert bound == 512 < rows
    engine = _engine(loss_fn, params, train_micro_batch_size_per_gpu=4)
    recount = {"moe_rows_routed": 0, "moe_rows_visited": 0,
               "moe_layers_full": 0}
    for seed in (1, 2, 3):
        batch = {"tokens": _tokens(cfg, batch=4, seed=seed)}
        counts = reference.expert_counts(
            jax.device_get(engine.state.params), batch["tokens"], **dims)
        engine.train_batch(batch)
        for c in counts:
            here = int(np.asarray(c[4:8]).sum())
            recount["moe_rows_routed"] += here
            recount["moe_rows_visited"] += rows if here > bound else bound
            recount["moe_layers_full"] += here > bound
    stats = engine.step_stats
    assert {key: stats[key] for key in recount} == recount
    assert stats["moe_combine_layers"] == 0      # the CPU: ``.at[].add``
    assert stats["attn_prep_fused_layers"] == (
        3 * cfg.num_layers if prep == "kernel" else 0)
    assert stats["flash_residuals_kept_layers"] == (
        3 * cfg.num_layers if prep == "kernel" and remat else 0)
    assert recount["moe_layers_full"] == 3
    assert recount["moe_rows_visited"] == 3 * (rows + bound)


@pytest.mark.parametrize("routing", ["even", "overflowing"])
def test_what_a_grouped_matmul_leaves_past_its_groups_reaches_no_gradient(
        monkeypatch, routing):
    """On the TPU ``ragged_dot`` leaves the rows past its groups as it
    found the memory, in the backward's products too (the first chip run of
    ISSUE 61 read token gradients 20,000 times the reference's). Planted
    here: a ``ragged_dot`` that writes NaN there, forward and backward. The
    share's output, its token gradient and its weight gradients stay what
    the clean one gives: over the rows of the bound (``even``: the rows
    past the held ones and under ``held_row_bound``) and over every routed
    row (``overflowing``: nine tokens in ten choose held experts alone, so
    the held rows exceed the bound and a few rows still lie past them)."""
    from deepspeed_tpu.moe.sharded_moe import grouped_moe_ffn, held_row_bound
    real = jax.lax.ragged_dot

    def dirt(x, sizes):
        rows = jnp.arange(x.shape[0]) < sizes.sum()
        return jnp.where(rows[:, None], x, jnp.nan), rows

    @jax.custom_vjp
    def dirty(lhs, rhs, sizes):
        return dirt(real(lhs, rhs, sizes), sizes)[0]

    def fwd(lhs, rhs, sizes):
        return dirty(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, ct):
        lhs, rhs, sizes = res
        rows = dirt(ct, sizes)[1]       # the kernels read group rows only
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes),
                         jnp.where(rows[:, None], lhs, 0), rhs)
        dl, dr = vjp(jnp.where(rows[:, None], ct, 0))
        return dirt(dl, sizes)[0], dr, None

    dirty.defvjp(fwd, bwd)
    S, M, W, E, k = 640, 16, 8, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    tokens = jax.random.normal(ks[0], (S, M))
    logits = jax.random.normal(ks[1], (S, E))
    weights = tuple(jax.random.normal(kk, shape) * 0.3 for kk, shape in zip(
        ks[2:], ((2, M, W), (2, M, W), (2, W, M))))
    if routing == "overflowing":    # nine tokens in ten choose held ones
        logits = logits.at[:576, 2:4].add(10.0)
    counts = grouped_moe_ffn(tokens, logits, k, weights, jax.nn.silu,
                             jnp.float32, score="sigmoid", held=(2, 2),
                             return_counts=True)[2]
    bound = held_row_bound(S, k, E, (2, 2))
    assert bound == 1024 < S * k
    assert (bound < int(counts[2:4].sum()) < S * k) == (
        routing == "overflowing")

    def f(tokens, weights):
        out, _ = grouped_moe_ffn(tokens, logits, k, weights, jax.nn.silu,
                                 jnp.float32, score="sigmoid", held=(2, 2))
        return jnp.sum(jnp.sin(out)), out

    (_, clean), g_clean = jax.value_and_grad(f, (0, 1), has_aux=True)(
        tokens, weights)
    monkeypatch.setattr(jax.lax, "ragged_dot", dirty)
    (_, out), g = jax.value_and_grad(f, (0, 1), has_aux=True)(tokens, weights)
    np.testing.assert_array_equal(out, clean)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_clean)):
        np.testing.assert_allclose(a, b, atol=1e-6)


#: sha256 of the sorted instructions of a FORWARD program of
#: ``grouped_moe_ffn(..., impl=None)`` as a serving step off the TPU calls
#: it, compiled for the CPU (metadata, instruction and region numbers cut),
#: under jax ``_SERVE_PINNED_JAX``. ``share``: ``held=(4, 4)`` over 48
#: routed rows, where ``held_row_bound`` is all of them; ``whole``:
#: ``held=None``, what ``moe/layer.py`` and a whole-layer serve take;
#: ``share_cut``: the same share over 2,048 routed rows, where the bound is
#: 1,024 (a ``conditional`` of the body over 1,024 rows and over 2,048).
#: ALL THREE WERE RE-PINNED BY ISSUE 64, BY DESIGN: the body counts its
#: groups by a one-hot compare (it was ``bincount``), reads the chosen
#: scores by a masked sum (it was ``take_along_axis``), marks the held
#: rows by their place in the sorted order (it was a gather of ``here``)
#: and gathers by the sort's order without ``take``'s bounds check, on
#: every backend. What the serve CELLS run (``impl="pallas"``) did not
#: move: ``test_moe_combine.py`` pins it to ISSUE 64's parent
_SERVE_PINNED = {
    "share": (24, (4, 4), "9456e94045811dc40db1beab7261f420"
                          "6b8a18b508e2e176e3284f26a8fdd76b"),
    "whole": (24, None, "653a2fa22d198420025db17e394dac7e"
                        "c7c891292a87d6fc34bd4f428160e9e9"),
    "share_cut": (1024, (4, 4), "64d5e134cb353b24a52e903ab8f8e0b4"
                                "a2636e087e2389f8eeae9256c8b9aeaa"),
}
_SERVE_PINNED_JAX = "0.9.0"


@pytest.mark.parametrize("case", sorted(_SERVE_PINNED))
def test_a_serving_share_compiles_to_the_instructions_it_always_did(case):
    """``_keep_cotangent_rows`` and ``return_counts`` (off) add nothing to
    a forward program, and neither does ``held_row_bound`` where it cuts
    nothing: a held share over few rows, a whole layer and a share whose
    bound cuts the sorted order, served through the ``ragged_dot`` path,
    compile to the instructions pinned here. ISSUE 64 changed how that
    body counts and picks (the comment above) and re-pinned all three on
    its own tree; a later change to it has to say so here. So that the pin
    still guards something, the program's OUTPUT is held to the parents'
    arithmetic written out (``_every_row_ffn``: ``bincount``,
    ``take_along_axis``, every row): bit for bit."""
    import hashlib
    import re
    from deepspeed_tpu.moe.sharded_moe import grouped_moe_ffn
    S, held, pinned = _SERVE_PINNED[case]

    def step(x, logits, wg, wu, wo, bias):
        return grouped_moe_ffn(
            x, logits, 2, (wg, wu, wo), jax.nn.silu, jnp.float32, True,
            score="sigmoid", select_bias=bias, weight_scale=2.5,
            held=held, impl=None)[0]

    M, W, E = 16, 8, 16
    n = E if held is None else held[1]
    shapes = ((S, M), (S, E), (n, M, W), (n, M, W), (n, W, M), (E,))
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    exe = jax.jit(step).lower(*args).compile()
    x, logits, wg, wu, wo, bias = (
        jax.random.normal(kk, s) for kk, s in zip(
            jax.random.split(jax.random.PRNGKey(3), 6), shapes))
    bias = 0.1 * bias               # the choice stays the scores' mostly
    want = _every_row_ffn(x, logits, 2, (wg, wu, wo), held, bias=bias,
                          scale=2.5)
    assert np.any(np.asarray(want))
    np.testing.assert_array_equal(exe(x, logits, wg, wu, wo, bias), want)
    text = exe.as_text()
    assert ("conditional(" in text) == (case == "share_cut")
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"\.\d+", "", text)
    text = "\n".join(
        line for line in text.splitlines()
        if " = " in line or line.startswith(("ENTRY", "}", "HloModule"))
        or line.rstrip().endswith("{"))
    text = re.sub(r"region_\d+", "region", text)
    text = "\n".join(sorted(line.strip() for line in text.splitlines()))
    if jax.__version__ == _SERVE_PINNED_JAX:
        assert hashlib.sha256(text.encode()).hexdigest() == pinned
