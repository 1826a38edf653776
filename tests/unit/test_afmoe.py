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
def _reference_side():
    params, tokens = _draw(CFG), _tokens(CFG)
    return (params, tokens) + reference_side(params, tokens, **_dims(CFG))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("impl", ["xla", "flash_interpret"])
def test_loss_nll_and_every_leafs_gradient_match_the_reference(impl):
    params, tokens, ref_loss, ref_nll, ref_grads = _reference_side()
    cfg = dataclasses.replace(CFG, attention_impl=impl, flash_block_q=32,
                              flash_block_k=32, xent_chunks=2)
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


def test_what_a_grouped_matmul_leaves_past_its_groups_reaches_no_gradient(
        monkeypatch):
    """On the TPU ``ragged_dot`` leaves the rows past its groups as it
    found the memory, in the backward's products too (the first chip run of
    ISSUE 61 read token gradients 20,000 times the reference's). Planted
    here: a ``ragged_dot`` that writes NaN there, forward and backward. The
    share's output, its token gradient and its weight gradients stay what
    the clean one gives."""
    from deepspeed_tpu.moe.sharded_moe import grouped_moe_ffn
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
    S, M, W, E, k = 48, 16, 8, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    tokens = jax.random.normal(ks[0], (S, M))
    logits = jax.random.normal(ks[1], (S, E))
    weights = tuple(jax.random.normal(kk, shape) * 0.3 for kk, shape in zip(
        ks[2:], ((2, M, W), (2, M, W), (2, W, M))))

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


#: sha256 of the sorted instructions of a held share's FORWARD program
#: (``grouped_moe_ffn(..., held=(4, 4), impl=None)`` as a serving step
#: calls it, compiled for the CPU; metadata, instruction and region
#: numbers cut) as the parent of ISSUE 61 compiled it, under jax
#: ``_SERVE_PINNED_JAX``
_SERVE_PINNED = \
    "66d1526fd93b7b8bcb1887de3f38e8cda7fe1793b0b96721570d39cc8ad3c6a8"
_SERVE_PINNED_JAX = "0.9.0"


def test_a_serving_share_compiles_to_the_instructions_it_always_did():
    """``_keep_cotangent_rows`` and ``return_counts`` (off) add nothing to
    a forward program: a held share served through the ``ragged_dot`` path
    compiles to the parent's instructions, one for one."""
    import hashlib
    import re
    from deepspeed_tpu.moe.sharded_moe import grouped_moe_ffn

    def step(x, logits, wg, wu, wo, bias):
        return grouped_moe_ffn(
            x, logits, 2, (wg, wu, wo), jax.nn.silu, jnp.float32, True,
            score="sigmoid", select_bias=bias, weight_scale=2.5,
            held=(4, 4), impl=None)[0]

    S, M, W, E, n = 24, 16, 8, 16, 4
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (S, M), (S, E), (n, M, W), (n, M, W), (n, W, M), (E,))]
    text = jax.jit(step).lower(*args).compile().as_text()
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"\.\d+", "", text)
    text = "\n".join(
        line for line in text.splitlines()
        if " = " in line or line.startswith(("ENTRY", "}", "HloModule"))
        or line.rstrip().endswith("{"))
    text = re.sub(r"region_\d+", "region", text)
    text = "\n".join(sorted(line.strip() for line in text.splitlines()))
    if jax.__version__ == _SERVE_PINNED_JAX:
        assert hashlib.sha256(text.encode()).hexdigest() == _SERVE_PINNED
