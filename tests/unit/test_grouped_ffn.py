"""The grouped expert feed-forward kernel (``ops/kernels/grouped_ffn.py``)
interpreted on the CPU: against ``jax.lax.ragged_dot`` (the path it
replaces at decode shapes) and against every expert on every token masked
by the router's choice; the layout's bounds; who takes which path; and
the two counters the fused loop carries for it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.sharded_moe import grouped_moe_ffn, route_topk
from deepspeed_tpu.ops.kernels import grouped_ffn as gf

S, M, F, E = 40, 256, 128, 8


def _weights(rng, groups, gated=True, dtype=jnp.float32):
    shapes = ((groups, M, F), (groups, M, F), (groups, F, M)) if gated \
        else ((groups, M, F), (groups, F, M))
    return tuple(jnp.asarray(rng.normal(size=s) * 0.1, dtype)
                 for s in shapes)


def _dense(tokens, logits, k, weights, held, router, normalize):
    """Every held expert on every token, masked by the top-k of all."""
    top, w_sel, _ = route_topk(logits, k, score=router.get("score",
                                                           "softmax"),
                               bias=router.get("select_bias"),
                               normalize=normalize,
                               scale=router.get("weight_scale", 1.0))
    first, count = held or (0, logits.shape[1])
    x = tokens.astype(jnp.float32)
    out = jnp.zeros_like(x)
    for g in range(count):
        w = [m[g].astype(jnp.float32) for m in weights]
        h = jax.nn.silu(x @ w[0]) * (x @ w[1]) if len(w) == 3 \
            else jax.nn.silu(x @ w[0])
        gatew = jnp.sum(jnp.where(top == first + g, w_sel, 0.0), -1)
        out = out + gatew[:, None] * (h @ w[-1])
    return out


#: name -> (k, held, router form, gated, logits' shape)
CASES = {
    # OLMoE's form: softmax over all experts, unrenormalised, all held
    "softmax-all-held": dict(k=2, held=None, router={}, normalize=False),
    # Solar's form: sigmoid scores, selection bias, a share of the experts
    "sigmoid-bias-first-half": dict(k=2, held=(0, 4), normalize=True,
                                    router=dict(score="sigmoid", bias=True,
                                                weight_scale=2.5)),
    "sigmoid-bias-second-half": dict(k=2, held=(4, 4), normalize=True,
                                     router=dict(score="sigmoid", bias=True,
                                                 weight_scale=2.5)),
    "two-matrix-experts": dict(k=2, held=None, router={}, normalize=True,
                               gated=False),
    # 40 rows on one expert: a group over three row tiles
    "every-row-on-one-expert": dict(k=1, held=None, router={},
                                    normalize=False, hot=(3,)),
    # two experts take everything: six held groups have no row
    "held-groups-with-no-row": dict(k=2, held=None, router={},
                                    normalize=True, hot=(1, 6)),
    # 320 rows over 8 experts: past two 16-row tiles an expert, so the
    # layout takes the 64-row tile (a refill step's shape)
    "forty-rows-an-expert": dict(k=2, held=(2, 4), router={}, normalize=True,
                                 S=160),
    # the share holds experts 4..8 and every row goes to 0..4
    "no-held-row-at-all": dict(k=2, held=(4, 4), router={}, normalize=True,
                               hot=(0, 2)),
}


def _case(name, dtype):
    c = CASES[name]
    rng = np.random.default_rng(sorted(CASES).index(name))
    rows = c.get("S", S)
    tokens = jnp.asarray(rng.normal(size=(rows, M)), dtype)
    logits = rng.normal(size=(rows, E))
    for e in c.get("hot", ()):
        logits[:, e] += 50.0
    router = dict(c["router"])
    if router.pop("bias", False):
        router["select_bias"] = jnp.asarray(rng.normal(size=E) * 0.3,
                                            jnp.float32)
    groups = c["held"][1] if c["held"] else E
    weights = _weights(rng, groups, c.get("gated", True), dtype)
    return c, tokens, jnp.asarray(logits, jnp.float32), router, weights


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_is_ragged_dot_and_the_dense_reference(name, dtype):
    c, tokens, logits, router, weights = _case(name, dtype)
    assert gf.row_tile(tokens.shape[0] * c["k"], E) \
        == (64 if name == "forty-rows-an-expert" else 16)
    call = dict(normalize_weights=c["normalize"], held=c["held"], **router)
    want, _ = grouped_moe_ffn(tokens, logits, c["k"], weights, jax.nn.silu,
                              dtype, **call)
    got, aux = grouped_moe_ffn(tokens, logits, c["k"], weights, jax.nn.silu,
                               dtype, impl="interpret", **call)
    dense = _dense(tokens, logits, c["k"], weights, c["held"], router,
                   c["normalize"])
    assert got.dtype == want.dtype == jnp.dtype(dtype) and float(aux) == 0.0
    scale = float(jnp.abs(dense).max())
    if name == "no-held-row-at-all":
        assert scale == 0.0 and not np.asarray(got, np.float32).any()
        return
    assert scale > 1e-2
    # float32: the order of the sums; bfloat16: the kernel rounds g, u
    # and the weighted sum later than ragged_dot's outputs do, never earlier
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for other in (want, dense):
        err = float(jnp.abs(got.astype(jnp.float32)
                            - other.astype(jnp.float32)).max())
        assert err < tol * max(scale, 1.0), (name, err, scale)
    if dtype == jnp.bfloat16:
        # and is no further from the float32 answer than ragged_dot is
        exact = _dense(tokens.astype(jnp.float32), logits, c["k"],
                       [w.astype(jnp.float32) for w in weights], c["held"],
                       router, c["normalize"])
        mine = float(jnp.abs(got.astype(jnp.float32) - exact).max())
        theirs = float(jnp.abs(want.astype(jnp.float32) - exact).max())
        assert mine <= theirs * 1.5 + 1e-3 * scale


def test_the_shares_halves_sum_to_the_whole():
    """Solar's form through the kernel: each share's routed part, added
    up, is the uncut layer's (as ``test_solar_open2`` holds for the
    ``ragged_dot`` path)."""
    rng = np.random.default_rng(11)
    tokens = jnp.asarray(rng.normal(size=(S, M)), jnp.float32)
    logits = jnp.asarray(rng.normal(size=(S, E)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=E) * 0.3, jnp.float32)
    whole = _weights(rng, E)
    call = dict(score="sigmoid", select_bias=bias, weight_scale=2.5,
                impl="interpret")

    def part(first, count):
        w = tuple(m[first:first + count] for m in whole)
        return grouped_moe_ffn(tokens, logits, 2, w, jax.nn.silu,
                               jnp.float32, held=(first, count), **call)[0]

    uncut = part(0, E)
    halves = part(0, 4), part(4, 4)
    assert all(float(jnp.abs(h).max()) > 1e-3 for h in halves)
    assert float(jnp.abs(halves[0] + halves[1] - uncut).max()) < 1e-5


@pytest.mark.parametrize("sizes", [(0, 0, 0, 0), (1, 0, 17, 0), (0, 64, 0, 0),
                                   (16, 16, 16, 16), (3, 5, 2, 7)])
def test_layout_puts_every_group_at_a_tile_and_within_its_bound(sizes):
    G, T = len(sizes), gf.ROW_TILE
    elsewhere = 9
    eid = np.concatenate([np.full(n, g) for g, n in enumerate(sizes)]
                         + [np.full(elsewhere, G)]).astype(np.int32)
    np.random.default_rng(0).shuffle(eid)
    dest, gid, nvis, got_sizes = jax.device_get(
        gf.group_layout(jnp.asarray(eid), G))
    V = gf.visits_bound(len(eid), G)
    tiles = [-(-n // T) for n in sizes]
    assert gid.shape == (V,) and int(nvis[0]) == sum(tiles) <= V
    assert tuple(got_sizes) == sizes
    want_gid = [g for g, t in enumerate(tiles) for _ in range(t)]
    assert list(gid[:len(want_gid)]) == want_gid
    # behind the last visit its group repeats (with no visit at all
    # nothing reads the list)
    if want_gid:
        assert set(gid[len(want_gid):]) <= {want_gid[-1]}
    held = eid < G
    assert (dest[~held] == V * T).all()
    assert len(set(dest[held])) == held.sum()            # no two rows share
    start = np.cumsum([0] + tiles[:-1]) * T
    for g, n in enumerate(sizes):
        mine = np.sort(dest[eid == g])
        assert list(mine) == list(range(start[g], start[g] + n))


def test_who_takes_which_path(monkeypatch):
    """The rule is shapes, operand types and the backend: on a TPU, steps
    whose routed rows are a weight stream take the kernel at the row tile
    that holds an expert's expected rows (every decode step 16, Solar's
    refill step 64); a step at the chip's ridge (OLMoE's refill, 256 rows
    an expert), quantised stacks and every other backend keep
    ``ragged_dot``."""
    bf = jnp.bfloat16
    solar = tuple(jax.ShapeDtypeStruct(s, bf) for s in
                  ((40, 4096, 1280), (40, 4096, 1280), (40, 1280, 4096)))
    olmoe = tuple(jax.ShapeDtypeStruct(s, bf) for s in
                  ((64, 2048, 1024), (64, 2048, 1024), (64, 1024, 2048)))
    assert gf.fits(128 * 8, 320, solar, bf)          # 3.2 rows an expert
    assert gf.fits(32 * 8, 64, olmoe, bf)            # 4
    assert gf.fits(16 * 8, 64, olmoe, bf)            # a [16, 1] bucket: 2
    assert gf.fits(4 * 512 * 8, 320, solar, bf)      # the refill: 51
    assert not gf.fits(4 * 512 * 8, 64, olmoe, bf)   # 256, at the ridge
    assert [gf.row_tile(r, e) for r, e in
            ((1024, 320), (256, 64), (128, 64), (16384, 320), (4096, 64),
             (16384, 64))] == [16, 16, 16, 64, 64, 128]
    assert not gf.fits(32 * 8, 64, olmoe, jnp.float32)   # stacks to cast
    int8 = tuple(jax.ShapeDtypeStruct(w.shape, jnp.int8) for w in olmoe)
    assert not gf.fits(32 * 8, 64, int8, jnp.int8)
    narrow = tuple(jax.ShapeDtypeStruct(s, bf) for s in
                   ((8, 64, 96), (8, 64, 96), (8, 96, 64)))
    assert not gf.fits(16, 8, narrow, bf)            # lanes do not tile
    assert not gf.fits(32 * 8, 64, (object(),) * 3, bf)  # a packed weight
    # the CPU default stays XLA, whatever the shapes
    assert gf.kernel_impl(32 * 8, 64, olmoe, bf) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gf.kernel_impl(32 * 8, 64, olmoe, bf) == "pallas"
    assert gf.kernel_impl(4 * 512 * 8, 64, olmoe, bf) is None


def test_training_layer_keeps_ragged_dot_and_its_gradient():
    """``moe/layer.py`` is not the kernel's caller: its program still holds
    ``ragged_dot`` (no Pallas call) and a gradient flows through it."""
    from deepspeed_tpu.moe.layer import MoE
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 6, 16), jnp.float32)
    layer = MoE(d_model=16, num_experts=4, k=2, hidden=32, drop_tokens=False,
                gated=True, use_grouped_gemm=True,
                top2_2nd_expert_sampling=False, activation=jax.nn.silu)
    variables = layer.init(jax.random.PRNGKey(0), x)

    def loss(v):
        out, l_aux = layer.apply(v, x)
        return (out ** 2).mean() + 0.01 * l_aux

    text = str(jax.make_jaxpr(loss)(variables))
    assert "ragged_dot" in text and "pallas_call" not in text
    grads = jax.tree_util.tree_leaves(jax.grad(loss)(variables))
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)
    assert all(float(np.abs(np.asarray(g)).sum()) > 0 for g in grads)


# ------------------------- the fused loop's counters ---------------------- #


def _closed_form(per_step_sizes):
    """(hit, reads) over a list of per-(step, layer) held group sizes."""
    hit = sum(int((s > 0).sum()) for s in per_step_sizes)
    reads = sum(int((-(-s // gf.ROW_TILE)).sum()) for s in per_step_sizes)
    return hit, reads


def _spy_on_layouts(monkeypatch):
    """Record the held group sizes of every sparse layer a traced program
    runs, through ``jax.debug.callback`` (the routing the program itself
    computes is what the closed form is over)."""
    seen = []
    real = gf.group_layout

    def spying(eid, groups, tile=gf.ROW_TILE):
        out = real(eid, groups, tile)
        jax.debug.callback(lambda s: seen.append(np.asarray(s)), out[3])
        return out
    monkeypatch.setattr(gf, "group_layout", spying)
    return seen


def _olmoe_engine():
    from tests.unit.test_olmoe import make_engine, tiny_cfg, tiny_params
    cfg = tiny_cfg(2)
    return make_engine(cfg, tiny_params(cfg)), 64


def _solar_engine():
    from benchmark.model_types import solar_open2 as mt
    from tests.unit.test_solar_open2 import engine, tiny
    cfg = tiny()
    return engine(cfg, mt.init_params(cfg, 3)), 512


def _dense_engine():
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)
    from deepspeed_tpu.models.llama import Llama, LlamaConfig
    cfg = LlamaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    params = Llama(cfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    return InferenceEngineV2(cfg, params, RaggedInferenceConfig(
        max_seqs=4, chunk_size=16, block_size=8, num_blocks=32,
        max_blocks_per_seq=8, decode_loop_steps=4, dtype="float32")), 64


@pytest.mark.parametrize("family", ["olmoe", "solar_open2", "dense"])
def test_fused_loop_counts_experts_hit_and_reads(family, monkeypatch):
    """After a ``decode_batch`` through the (interpreted) kernel,
    ``moe_experts_hit`` and ``moe_expert_reads`` are the closed form over
    the routing the program computed: held groups with a row, and row
    tiles visited, summed over sparse layers and steps. A model with no
    routed expert leaves both at 0, and so does the ``ragged_dot`` path."""
    build = {"olmoe": _olmoe_engine, "solar_open2": _solar_engine,
             "dense": _dense_engine}[family]
    eng, vocab = build()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, vocab, 5 + i).tolist() for i in range(3)]
    first = eng.put([0, 1, 2], prompts, _greedy=True)
    # the CPU default is ragged_dot: a loop on it counts nothing
    eng.decode_batch([0, 1, 2], [first[u] for u in (0, 1, 2)], 2)
    stats = eng.pipeline_stats
    assert stats["moe_experts_hit"] == stats["moe_expert_reads"] == 0
    if family == "dense":
        assert stats["moe_rows_routed"] == 0
        return
    routed_before = stats["moe_rows_routed"]
    assert routed_before > 0
    # steer the choice from the test, as test_tpu_compile steers the
    # backend: the program has no option for it
    monkeypatch.setattr(gf, "kernel_impl", lambda *a: "interpret")
    seen = _spy_on_layouts(monkeypatch)
    jax.clear_caches()
    eng2, _ = build()
    first = eng2.put([0, 1, 2], prompts, _greedy=True)
    seen.clear()                       # the prefill steps are not counted
    toks = eng2.decode_batch([0, 1, 2], [first[u] for u in (0, 1, 2)], 4)
    jax.effects_barrier()
    stats = eng2.pipeline_stats
    layers = eng2.runner.model_cfg.num_layers
    assert len(seen) == 4 * layers
    assert (stats["moe_experts_hit"], stats["moe_expert_reads"]) \
        == _closed_form(seen)
    assert 0 < stats["moe_experts_hit"] <= stats["moe_expert_reads"]
    # the same tokens as the ragged_dot loop decodes
    eng3, _ = build()
    monkeypatch.undo()
    jax.clear_caches()
    f3 = eng3.put([0, 1, 2], prompts, _greedy=True)
    want = eng3.decode_batch([0, 1, 2], [f3[u] for u in (0, 1, 2)], 4)
    assert {u: list(map(int, t)) for u, t in toks.items()} \
        == {u: list(map(int, t)) for u, t in want.items()}
