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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_is_ragged_dot_and_the_dense_reference(name, dtype,
                                                      monkeypatch):
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


@pytest.mark.parametrize("tile,cap", [(16, 128), (64, 128), (128, 512),
                                      (64, 512)])
@pytest.mark.parametrize("sizes", [(0, 0, 0, 0), (1, 0, 17, 0), (0, 64, 0, 0),
                                   (16, 16, 16, 16), (3, 5, 2, 7),
                                   (17, 32, 33, 64, 129, 0), (300, 0, 5, 0),
                                   (128, 129, 0, 257), (512, 513, 256, 1025)])
def test_layout_puts_every_group_at_a_tile_and_within_its_bound(sizes, tile,
                                                                cap):
    G, T = len(sizes), tile
    elsewhere = 9
    eid = np.concatenate([np.full(n, g) for g, n in enumerate(sizes)]
                         + [np.full(elsewhere, G)]).astype(np.int32)
    np.random.default_rng(0).shuffle(eid)
    dest, (gid, first, ntile), nvis, got_sizes = jax.device_get(
        gf.group_layout(jnp.asarray(eid), G, T, cap))
    V = gf.visits_bound(len(eid), G, T)
    tiles = [-(-n // T) for n in sizes]
    assert tuple(got_sizes) == sizes
    # the visit table: ONE visit a group with a row while its tiles are
    # within the span cap (128 rows, or a ridge call's 512), one more for
    # every cap of rows beyond
    per = cap // T
    want = [(g, t0 + k, min(per, t - k))
            for g, (t, t0) in enumerate(zip(tiles, np.cumsum([0] + tiles)))
            for k in range(0, t, per)]
    assert gid.shape == first.shape == ntile.shape == (V,)
    assert int(nvis[0]) == len(want) <= sum(tiles) <= V
    assert list(zip(gid, first, ntile))[:len(want)] == want
    streams = np.asarray(gf.streams(jnp.asarray(sizes, jnp.int32), T, cap))
    assert list(streams) == [sum(g == w[0] for w in want) for g in range(G)]
    assert all(streams[g] == (n > 0) for g, n in enumerate(sizes)
               if -(-n // T) * T <= cap)
    # behind the last visit it repeats (with no visit at all nothing
    # reads the lists)
    if want:
        assert set(list(zip(gid, first, ntile))[len(want):]) <= {want[-1]}
    # the rows' places and the padded size: what they were when a visit
    # was a row tile
    held = eid < G
    assert (dest[~held] == V * T).all()
    assert len(set(dest[held])) == held.sum()            # no two rows share
    start = np.cumsum([0] + tiles[:-1]) * T
    for g, n in enumerate(sizes):
        mine = np.sort(dest[eid == g])
        assert list(mine) == list(range(start[g], start[g] + n))


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


def test_who_takes_which_path(monkeypatch):
    """The rule is operand types, widths and the backend: on a TPU, over
    plain floating stacks, every step takes the kernel at the row tile
    that holds an expert's expected rows (every decode step 16 or 32,
    Solar's refill step 64) under a 128-row span, and a step at the
    chip's ridge (OLMoE's and Mellum2's [4, 512] refill, 256 rows an
    expert) at the 128-row tile under a 512-row span; quantised stacks
    and every other backend keep ``ragged_dot``."""
    bf = jnp.bfloat16
    solar = tuple(jax.ShapeDtypeStruct(s, bf) for s in
                  ((40, 4096, 1280), (40, 4096, 1280), (40, 1280, 4096)))
    olmoe = tuple(jax.ShapeDtypeStruct(s, bf) for s in
                  ((64, 2048, 1024), (64, 2048, 1024), (64, 1024, 2048)))
    mellum = tuple(jax.ShapeDtypeStruct(s, bf) for s in
                   ((32, 2304, 896), (32, 2304, 896), (32, 896, 2304)))
    assert gf.fits(solar, bf) and gf.fits(olmoe, bf) and gf.fits(mellum, bf)
    # (routed rows, router outputs): decode steps at 3.2, 4 and 2 rows an
    # expert, Solar's refill at 51, 64 and 32 exactly, then past 128
    shapes = ((1024, 320), (256, 64), (128, 64), (16384, 320), (4096, 64),
              (2048, 64), (16384, 64), (8193, 64), (8192, 64), (65536, 64))
    assert [gf.row_tile(r, e) for r, e in shapes] \
        == [16, 16, 16, 64, 64, 32, 128, 128, 128, 128]
    assert [gf.span_cap(r, e) for r, e in shapes] \
        == [128, 128, 128, 128, 128, 128, 512, 512, 128, 512]
    assert not gf.fits(olmoe, jnp.float32)           # stacks to cast
    int8 = tuple(jax.ShapeDtypeStruct(w.shape, jnp.int8) for w in olmoe)
    assert not gf.fits(int8, jnp.int8)
    narrow = tuple(jax.ShapeDtypeStruct(s, bf) for s in
                   ((8, 64, 96), (8, 64, 96), (8, 96, 64)))
    assert not gf.fits(narrow, bf)                   # lanes do not tile
    assert not gf.fits((object(),) * 3, bf)          # a packed weight
    # the CPU default stays XLA, whatever the shapes
    assert gf.kernel_impl(olmoe, bf) is None
    assert gf.kernel_impl(mellum, bf) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gf.kernel_impl(olmoe, bf) == gf.kernel_impl(mellum, bf) \
        == gf.kernel_impl(solar, bf) == "pallas"
    assert gf.kernel_impl(int8, jnp.int8) is None
    assert gf.kernel_impl(olmoe, jnp.float32) is None


#: (rows, router outputs, held experts, hidden, expert width, gated) of
#: every call the ten cells made through the kernel before a ridge call
#: took it too, and what the module said of each then (commit 451879e):
#: (row tile, layout rows, heights, ``vmem_need``)
PARENT_CALLS = {
    "olmoe-decode": ((256, 64, 64, 2048, 1024, True),
                     (16, 1216, (16, 32, 64, 128), 14680064)),
    "solar2-decode": ((1024, 320, 40, 4096, 1280, True),
                      (16, 1616, (16, 32, 64, 128), 16515072)),
    "pangu-decode": ((1024, 256, 8, 7680, 2048, True),
                     (16, 1136, (16, 32, 64, 128), 26214400)),
    "kimi-decode": ((1024, 256, 64, 2304, 1024, True),
                    (16, 1984, (16, 32, 64, 128), 13107200)),
    "nemotron-decode": ((1536, 128, 64, 2688, 1920, False),
                        (16, 2496, (16, 32, 64, 128), 12648448)),
    "mellum2-decode": ((2048, 64, 32, 2304, 896, True),
                       (32, 3040, (32, 64, 128), 12582912)),
    "solar2-refill": ((16384, 320, 40, 4096, 1280, True),
                      (64, 18880, (64, 128), 16515072)),
    "pangu-refill": ((16384, 256, 8, 7680, 2048, True),
                     (64, 16832, (64, 128), 26214400)),
    "kimi-refill": ((16384, 256, 64, 2304, 1024, True),
                    (64, 20416, (64, 128), 13107200)),
    "nemotron-refill": ((12288, 128, 64, 2688, 1920, False),
                        (128, 20352, (128,), 12648448)),
}


@pytest.mark.parametrize("call", sorted(PARENT_CALLS))
def test_calls_under_the_ridge_keep_their_tile_heights_and_vmem(call):
    """An edit to the ridge calls' cap or heights must not move a call
    that expects at most 128 rows an expert: its row tile, its layout,
    its heights (so its Mosaic body) and the VMEM it asks for are what
    they were."""
    (rows, experts, held, M, F, gated), want = PARENT_CALLS[call]
    tile, cap = gf.row_tile(rows, experts), gf.span_cap(rows, experts)
    V = gf.visits_bound(rows, held, tile)
    assert cap == 128
    assert (tile, V * tile, gf._heights(tile, V, cap),
            gf.vmem_need(tile, V, M, F, 2, gated, cap)) == want
    # and the defaults are that cap
    assert gf._heights(tile, V) == want[2]
    assert gf.vmem_need(tile, V, M, F, 2, gated) == want[3]


@pytest.mark.parametrize("name,held,M,F,rows_of,need", [
    ("mellum2", 32, 2304, 896, 20352, 25952256),
    ("olmoe", 64, 2048, 1024, 24448, 27262976)])
def test_a_ridge_call_asks_vmem_for_its_512_row_span(name, held, M, F,
                                                     rows_of, need):
    """The [4, 512] refill step of the two cells at 256 rows an expert:
    a 128-row tile, heights rising by a tile to the 512-row cap, and a
    ``vmem_limit_bytes`` that counts that span's rows and sums from the
    shapes (22-25 MB where a 128-row span asks 12-15)."""
    rows, experts = 4 * 512 * 8, 64
    tile, cap = gf.row_tile(rows, experts), gf.span_cap(rows, experts)
    V = gf.visits_bound(rows, held, tile)
    assert (tile, cap, V * tile) == (128, 512, rows_of)
    assert gf._heights(tile, V, cap) == (128, 256, 384, 512)
    asked = gf.vmem_need(tile, V, M, F, 2, True, cap)
    assert asked == need
    assert asked - gf.vmem_need(tile, V, M, F, 2, True) \
        == (512 - 128) * (2 * M * 2 + (2 * F + 2 * M) * 4)


def _sorted_layout(eid, G, T, cap):
    """:func:`group_layout` as a stable sort by group gives it (NumPy):
    a row's place is its group's first row (groups at multiples of T, in
    order) plus its rank among the group's rows; the visit lists as
    :func:`streams` counts them, the last repeated behind them."""
    R = len(eid)
    V = gf.visits_bound(R, G, T)
    order = np.argsort(eid, kind="stable")
    sizes = np.bincount(eid, minlength=G + 1)[:G]
    tiles = -(-sizes // T)
    first_tile = np.cumsum(tiles) - tiles
    first_row = np.cumsum(sizes) - sizes
    dest = np.full(R, V * T, np.int64)
    held = eid[order] < G
    g = eid[order][held]
    dest[order[held]] = first_tile[g] * T + np.arange(R)[held] - first_row[g]
    per = cap // T
    visits = [(g, first_tile[g] + k, min(per, tiles[g] - k))
              for g in range(G) for k in range(0, tiles[g], per)]
    last = visits[-1] if visits else (G - 1, tiles.sum(), 0)
    gid, first, ntile = np.array(visits + [last] * (V - len(visits))).T
    return dest, (gid, first, ntile), np.array([len(visits)]), sizes


def _layout_cases():
    """name -> (eid, groups, tile, cap): every call of the six MoE cells,
    loop and refill, with rows drawn as the cell draws them (a uniform
    choice of the router's outputs, the held first), and the edges."""
    cells = dict(PARENT_CALLS)
    cells["olmoe-refill"] = ((16384, 64, 64), None)
    cells["mellum2-refill"] = ((16384, 64, 32), None)
    cases = {}
    for i, (name, (call, _)) in enumerate(sorted(cells.items())):
        rows, experts, held = call[:3]
        eid = np.minimum(np.random.default_rng(i).integers(
            0, experts, rows), held)
        cases[name] = (eid, held, gf.row_tile(rows, experts),
                       gf.span_cap(rows, experts))
    rng = np.random.default_rng(57)
    cases.update({
        "every-row-in-no-group": (np.full(200, 4), 4, 16, 128),
        "one-group-holds-all": (np.full(700, 2), 4, 16, 128),
        "one-group-past-the-ridge-cap": (np.full(1300, 0), 2, 128, 512),
        "empty-groups-between": (rng.choice([1, 5, 8], 300), 8, 16, 128),
        "one-row": (np.array([3]), 8, 16, 128),
        "one-row-in-no-group": (np.array([8]), 8, 16, 128),
        "one-group": (rng.integers(0, 2, 130), 1, 16, 128),
        "a-block-and-a-row": (rng.integers(0, 9, 129), 8, 32, 128),
        "a-row-short-of-a-block": (rng.integers(0, 9, 127), 8, 64, 128),
        "sorted-already": (np.sort(rng.integers(0, 17, 1000)), 16, 16, 128),
        "sorted-backwards": (np.sort(rng.integers(0, 17, 1000))[::-1],
                             16, 16, 128),
    })
    return cases


LAYOUT_CASES = _layout_cases()


@pytest.mark.parametrize("name", sorted(LAYOUT_CASES))
def test_layout_is_the_stable_sorts_element_for_element(name):
    """Counted, not sorted: a compare against the groups and its running
    sum place every row where ``argsort(stable)`` + ``bincount`` + the
    scatter back placed it, with the same sizes and visit lists."""
    eid, G, T, cap = LAYOUT_CASES[name]
    eid = eid.astype(np.int32)
    got = jax.device_get(jax.jit(gf.group_layout, static_argnums=(1, 2, 3))(
        jnp.asarray(eid), G, T, cap))
    want = _sorted_layout(eid, G, T, cap)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == np.int32
        np.testing.assert_array_equal(g, np.asarray(w))


def _primitives(jaxpr):
    """Every primitive's name in ``jaxpr`` and the jaxprs inside it."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


@pytest.mark.parametrize("what", ["group_layout", "loop_counters",
                                  "loop_counters_off_the_kernel"])
def test_index_arithmetic_holds_no_sort_and_no_scatter(what):
    """A scatter of N integers is N serial updates on the TPU: the layout
    and the fused loop's counters place nothing by scatter and sort
    nothing (the ``src`` scatter of ``layout_and_run`` moves ROWS into the
    kernel's operand and stays)."""
    from deepspeed_tpu.inference.v2.llama_runner import _moe_counts
    if what == "group_layout":
        jaxpr = jax.make_jaxpr(lambda e: gf.group_layout(e, 32, 32, 128))(
            jnp.zeros((2048,), jnp.int32))
    else:
        jaxpr = jax.make_jaxpr(lambda t, v: _moe_counts(
            t, v, 64, (0, 32), what == "loop_counters"))(
            jnp.zeros((256, 8), jnp.int32), jnp.ones((256,), bool))
    names = _primitives(jaxpr.jaxpr)
    assert "dot_general" in names or what != "group_layout"
    bad = {n for n in names if "sort" in n or "scatter" in n
           or n in ("gather", "while")}
    assert not bad, bad


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
    reads = sum(int(gf.streams(jnp.asarray(s), gf.ROW_TILE).sum())
                for s in per_step_sizes)
    return hit, reads


def _spy_on_layouts(monkeypatch):
    """Record the held group sizes of every sparse layer a traced program
    runs, through ``jax.debug.callback`` (the routing the program itself
    computes is what the closed form is over)."""
    seen = []
    real = gf.group_layout

    def spying(eid, groups, *tiling):
        out = real(eid, groups, *tiling)
        jax.debug.callback(lambda s: seen.append(np.asarray(s)), out[3])
        return out
    monkeypatch.setattr(gf, "group_layout", spying)
    return seen


def _olmoe_engine():
    from tests.unit.test_olmoe import make_engine, tiny_cfg, tiny_params
    cfg = tiny_cfg(2)
    return make_engine(cfg, tiny_params(cfg)), 64


def _olmoe_hot_engine():
    """Twenty sequences whose router is silent (a zero gate: every row
    ties, and the top-k of a tie is the first k experts), so each step
    routes its 20+ rows to the same two experts: groups of two row tiles."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)
    from tests.unit.test_olmoe import tiny_cfg, tiny_params
    cfg = tiny_cfg(2)
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.zeros_like(leaf)
        if "'gate'" in jax.tree_util.keystr(path) else leaf,
        tiny_params(cfg))
    return InferenceEngineV2(cfg, params, RaggedInferenceConfig(
        max_seqs=24, chunk_size=8, block_size=8, num_blocks=96,
        max_blocks_per_seq=4, decode_loop_steps=4, dtype="float32")), 64


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


@pytest.mark.parametrize("family", ["olmoe", "solar_open2", "dense",
                                    "olmoe-one-hot-pair"])
def test_fused_loop_counts_experts_hit_and_reads(family, monkeypatch):
    """After a ``decode_batch`` through the (interpreted) kernel,
    ``moe_experts_hit`` and ``moe_expert_reads`` are the closed form over
    the routing the program computed: held groups with a row, and the
    kernel's visits to them (streams of an expert's matrices: one a group
    within 128 rows, however many row tiles), summed over sparse layers
    and steps. A model with no routed expert leaves both at 0, and so
    does the ``ragged_dot`` path."""
    build = {"olmoe": _olmoe_engine, "solar_open2": _solar_engine,
             "dense": _dense_engine,
             "olmoe-one-hot-pair": _olmoe_hot_engine}[family]
    eng, vocab = build()
    rng = np.random.default_rng(2)
    uids = list(range(20 if family == "olmoe-one-hot-pair" else 3))
    prompts = [rng.integers(1, vocab, 5 + i % 3).tolist() for i in uids]
    first = eng.put(uids, prompts, _greedy=True)
    # the CPU default is ragged_dot: a loop on it counts nothing
    eng.decode_batch(uids, [first[u] for u in uids], 2)
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
    first = eng2.put(uids, prompts, _greedy=True)
    seen.clear()                       # the prefill steps are not counted
    toks = eng2.decode_batch(uids, [first[u] for u in uids], 4)
    jax.effects_barrier()
    stats = eng2.pipeline_stats
    layers = eng2.runner.model_cfg.num_layers
    assert len(seen) == 4 * layers
    assert (stats["moe_experts_hit"], stats["moe_expert_reads"]) \
        == _closed_form(seen)
    assert 0 < stats["moe_experts_hit"] <= stats["moe_expert_reads"]
    if family == "olmoe-one-hot-pair":
        # 20+ rows on each of two experts, two row tiles a group: one
        # stream each all the same
        assert all(sorted(s)[-2:] == [max(s)] * 2 and max(s) > gf.ROW_TILE
                   and sum(s) == 2 * max(s) for s in map(list, seen))
        assert stats["moe_expert_reads"] == stats["moe_experts_hit"] \
            == 2 * len(seen)
    # the same tokens as the ragged_dot loop decodes
    eng3, _ = build()
    monkeypatch.undo()
    jax.clear_caches()
    f3 = eng3.put(uids, prompts, _greedy=True)
    want = eng3.decode_batch(uids, [f3[u] for u in uids], 4)
    assert {u: list(map(int, t)) for u, t in toks.items()} \
        == {u: list(map(int, t)) for u, t in want.items()}


@pytest.mark.parametrize("family", ["olmoe", "solar_open2", "dense"])
def test_prefill_steps_count_tokens_through_the_kernel(family, monkeypatch):
    """``moe_prefill_tokens`` counts the real positions of every prefill
    step of a model with routed experts and ``moe_prefill_kernel_tokens``
    those of them in steps whose shape took the grouped kernel, by the
    choice the runner itself makes: none on the CPU, where the steps run
    ``ragged_dot``; all of them once the choice says so, and the steps
    then do run the kernel and serve the same first tokens. A dense
    model counts neither."""
    build = {"olmoe": _olmoe_engine, "solar_open2": _solar_engine,
             "dense": _dense_engine}[family]
    eng, vocab = build()
    rng = np.random.default_rng(3)
    uids = [0, 1, 2]
    prompts = [rng.integers(1, vocab, 5 + i).tolist() for i in uids]
    want = eng.put(uids, prompts, _greedy=True)
    stats = eng.pipeline_stats
    real = sum(map(len, prompts))
    assert stats["prefill_tokens_real"] == real
    assert stats["moe_prefill_kernel_tokens"] == 0
    assert stats["moe_prefill_tokens"] == (0 if family == "dense" else real)
    # a one-token step is a decode step: not a prefill token
    eng.put(uids, [[want[u]] for u in uids], _greedy=True)
    assert eng.pipeline_stats["moe_prefill_tokens"] \
        == stats["moe_prefill_tokens"]
    if family == "dense":
        return
    monkeypatch.setattr(gf, "kernel_impl", lambda *a: "interpret")
    seen = _spy_on_layouts(monkeypatch)
    jax.clear_caches()
    eng2, _ = build()
    got = eng2.put(uids, prompts, _greedy=True)
    jax.effects_barrier()
    stats = eng2.pipeline_stats
    assert stats["moe_prefill_tokens"] \
        == stats["moe_prefill_kernel_tokens"] == real
    # the steps the counter spoke for went through the kernel's layout
    assert len(seen) == stats["prefill_steps"] \
        * eng2.runner.model_cfg.num_layers > 0
    assert got == want
    monkeypatch.undo()
    jax.clear_caches()
