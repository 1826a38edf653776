"""The grouped expert feed-forward kernel (``ops/kernels/grouped_ffn.py``)
interpreted on the CPU: against ``jax.lax.ragged_dot`` (the path it
replaces at decode shapes) and against every expert on every token masked
by the router's choice (the bfloat16 half of those cases in
``test_grouped_ffn_bf16.py``), and what a visit writes back. The layout's
bounds and who takes which path are in ``test_grouped_ffn_layout.py``, the
two counters the fused loop carries for it in
``test_grouped_ffn_engine.py`` (a file is what tier-1 schedules, and the
four together were its heaviest)."""

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
    # groups of 17, 32, 33, 64, 129 and 0 rows (and 1, 0 to fill the eight)
    # in ONE call at the 16-row tile: visits of two, two, three (a 64-row
    # operand over a neighbour's tile), four and eight tiles, and a group
    # split at the 128-row cap whose second visit is one tile
    "spans-of-many-tiles": dict(k=1, held=None, router={}, normalize=False,
                                rows=(17, 32, 33, 64, 129, 0, 1, 0), tile=16),
    "spans-of-many-tiles-ungated": dict(
        k=1, held=None, router={}, normalize=False, gated=False,
        rows=(0, 129, 64, 33, 32, 17, 0, 1), tile=16),
    # the same under a share's offset: the held half takes 17 + 33 + 129
    # rows, the other half's 97 rows are never laid out
    "spans-in-a-share": dict(k=1, held=(4, 4), router={}, normalize=False,
                             rows=(64, 0, 32, 1, 17, 0, 33, 129), tile=16),
    # a refill step's 64-row tile: groups of 65, 128, 129 and 200 rows are
    # visits of two tiles (once 128 rows) or split at the cap
    "spans-at-the-64-row-tile": dict(
        k=1, held=None, router={}, normalize=False,
        rows=(65, 128, 129, 200, 0, 64, 3, 0), tile=64),
    # a refill step at the chip's ridge: more than 128 rows an expert, so
    # the layout takes the 128-row tile under the 512-row span and a hit
    # expert is ONE visit (``streams``: the times its matrices stream).
    # Every expert held (OLMoE's form), 256 rows an expert; one has none
    "ridge-every-expert-held": dict(
        k=1, held=None, router={}, normalize=False,
        rows=(256, 241, 272, 0, 255, 257, 129, 384),
        streams=(1, 1, 1, 0, 1, 1, 1, 1)),
    # half the rows in no held group (Mellum2's form): never laid out
    "ridge-half-in-no-held-group": dict(
        k=1, held=(0, 4), router={}, normalize=False,
        rows=(256, 260, 0, 250, 300, 280, 200, 254), streams=(1, 1, 0, 1)),
    "ridge-second-half-held": dict(
        k=1, held=(4, 4), router={}, normalize=True,
        rows=(256, 260, 0, 250, 300, 280, 200, 254), streams=(1, 1, 1, 1)),
    # ~400 rows an expert: operands of 384 and 512 rows
    "ridge-400-rows-an-expert": dict(
        k=1, held=None, router={}, normalize=False,
        rows=(400, 385, 415, 512, 390, 0, 401, 397),
        streams=(1, 1, 1, 1, 1, 0, 1, 1)),
    # groups past the tall cap: 513 and 700 rows are two visits, 1,025 three
    "ridge-groups-past-the-cap": dict(
        k=1, held=None, router={}, normalize=False,
        rows=(513, 256, 700, 0, 130, 1025, 384, 8),
        streams=(2, 1, 2, 0, 1, 3, 1, 1)),
    "ridge-ungated": dict(
        k=1, held=None, router={}, normalize=False, gated=False,
        rows=(256, 0, 400, 513, 255, 257, 128, 300),
        streams=(1, 0, 1, 2, 1, 1, 1, 1)),
    "ridge-ungated-in-a-share": dict(
        k=1, held=(2, 4), router={}, normalize=False, gated=False,
        rows=(256, 300, 400, 0, 513, 257, 128, 300), streams=(1, 0, 2, 1)),
    # top-2 of real logits, no row steered: 1,200 tokens' 2,400 rows over
    # 8 experts, ~300 each, the held half's laid out
    "ridge-top-2-unsteered": dict(k=2, held=(0, 4), router={},
                                  normalize=True, S=1200),
}


def _case(name, dtype):
    c = CASES[name]
    rng = np.random.default_rng(sorted(CASES).index(name))
    rows = sum(c["rows"]) if "rows" in c else c.get("S", S)
    tokens = jnp.asarray(rng.normal(size=(rows, M)), dtype)
    logits = rng.normal(size=(rows, E))
    for e in c.get("hot", ()):
        logits[:, e] += 50.0
    if "rows" in c:
        # token t's one expert, by count, shuffled over the tokens
        mine = rng.permutation(np.repeat(np.arange(E), c["rows"]))
        logits[np.arange(rows), mine] += 50.0
    router = dict(c["router"])
    if router.pop("bias", False):
        router["select_bias"] = jnp.asarray(rng.normal(size=E) * 0.3,
                                            jnp.float32)
    groups = c["held"][1] if c["held"] else E
    weights = _weights(rng, groups, c.get("gated", True), dtype)
    return c, tokens, jnp.asarray(logits, jnp.float32), router, weights


def kernel_is_ragged_dot_and_the_dense_reference(name, dtype, monkeypatch):
    """The kernel (interpreted) against ``ragged_dot`` and against every
    expert on every token, one case of ``CASES`` at ``dtype``."""
    c, tokens, logits, router, weights = _case(name, dtype)
    if "tile" in c:
        # the tile is the caller's: a decode step's 16 under the spread of
        # a cell that expects 12 rows an expert, whatever this draw expects
        monkeypatch.setattr(gf, "row_tile", lambda rows, experts: c["tile"])
        sizes = np.asarray(c["rows"])
        first, count = c["held"] or (0, E)
        streams = np.asarray(gf.streams(sizes[first:first + count],
                                        c["tile"]))
        assert streams.max() == 2 and (streams[sizes[first:first + count]
                                               <= 128] <= 1).all()
    elif name.startswith("ridge"):
        R = tokens.shape[0] * c["k"]
        assert R > 128 * E
        assert (gf.row_tile(R, E), gf.span_cap(R, E)) == (128, 512)
        if "streams" in c:
            first, count = c["held"] or (0, E)
            assert tuple(np.asarray(gf.streams(
                np.asarray(c["rows"])[first:first + count], 128, 512))) \
                == c["streams"]
    else:
        assert gf.row_tile(tokens.shape[0] * c["k"], E) \
            == (64 if name == "forty-rows-an-expert" else 16)
        assert gf.span_cap(tokens.shape[0] * c["k"], E) == 128
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


@pytest.mark.parametrize("dtype", [jnp.float32], ids=["float32"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_is_ragged_dot_and_the_dense_reference(name, dtype,
                                                      monkeypatch):
    """In float32; ``test_grouped_ffn_bf16.py`` holds the bfloat16 half."""
    kernel_is_ragged_dot_and_the_dense_reference(name, dtype, monkeypatch)


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


def test_a_visit_writes_back_its_own_tiles_alone():
    """A group of 33 rows is one visit of three tiles under a 64-row
    operand; the fourth tile of that operand is the next group's. Stopped
    after the first visit, the call has written three tiles and left the
    neighbour's as allocated (the interpreter allocates NaN); run to the
    end, both groups are their experts' feed-forward."""
    rng = np.random.default_rng(5)
    sizes = (33, 64)
    eid = jnp.asarray(np.repeat(np.arange(2), sizes), jnp.int32)
    dest, visits, nvis, _ = gf.group_layout(eid, 2)
    assert [int(v[0]) for v in visits] == [0, 0, 3] and int(nvis[0]) == 2
    weights = _weights(rng, 2, gated=False)
    P = visits[0].shape[0] * gf.ROW_TILE
    xs = jnp.asarray(rng.normal(size=(P, M)), jnp.float32)
    run = lambda n: np.asarray(gf.grouped_ffn_decode(
        xs, visits, jnp.full((1,), n, jnp.int32), weights,
        activation=jax.nn.silu, interpret=True))
    none, one, both = run(0), run(1), run(2)
    assert np.isnan(none).all()
    assert not np.isnan(one[:48]).any() and np.isnan(one[48:]).all()
    want = [jax.nn.silu(xs[a:b] @ weights[0][g]) @ weights[1][g]
            for g, (a, b) in enumerate(((0, 48), (48, 112)))]
    np.testing.assert_allclose(one[:48], want[0], atol=1e-5)
    np.testing.assert_allclose(both[:112], np.concatenate(want), atol=1e-5)
    assert np.isnan(both[112:]).all()


