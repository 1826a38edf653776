"""The grouped expert kernel's layout (``ops/kernels/grouped_ffn.py``): every
group at a tile and within its bound, the index arithmetic against the
stable sort, who takes which path, the tile heights and VMEM of the calls
under and at the ridge, and the training layer's path; the kernel's
arithmetic is in ``test_grouped_ffn.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.kernels import grouped_ffn as gf


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
