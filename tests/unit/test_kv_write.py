"""The one writer of rows into the paged pool (inference/v2/kv_write.py)
against the row scatter it replaced, bytes compared: every slot outside
the trash block must come out as the scatter leaves it, so a neighbour's
block and a sequence's own rows outside ``[start, start + count)`` keep
the sentinel the pool was filled with."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.inference.v2.kv_quant import KVPool, quantize_rows
from deepspeed_tpu.inference.v2.kv_write import (runs_issued, store_rows,
                                                 tile_rows, write_plan)
from deepspeed_tpu.utils.jax_compat import shard_map

L, W, KV, MAXB = 2, 16, 2, 3
LAYER = 1


def _row_scatter(kv, rows, start, count, tables, bs):
    """The parent's store: one scatter a plane over every position of the
    step, padding to the pool's last row."""
    data, scales = kv.data, kv.scales
    planes, S, n, _ = rows.shape
    pos = start[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    valid = jnp.arange(n, dtype=jnp.int32)[None, :] < count[:, None]
    blk = jnp.take_along_axis(tables, jnp.minimum(pos // bs, MAXB - 1), axis=1)
    widx = jnp.where(valid, blk * bs + pos % bs,
                     data.shape[2] - 1).reshape(-1)
    for p in range(planes):
        flat = rows[p].reshape(S * n, W)
        if scales is None:
            data = data.at[LAYER, p, widx].set(flat.astype(data.dtype))
        else:
            q, sc = quantize_rows(flat, KV)
            data = data.at[LAYER, p, widx].set(q)
            scales = scales.at[LAYER, p, :, widx].set(sc.T)
    return KVPool(data, scales)


def _pool(rng, int8, planes, slots):
    """A pool of sentinels: no row of it is a row the step could write."""
    if int8:
        return KVPool(
            jnp.asarray(rng.integers(-120, 120, (L, planes, slots, W)),
                        jnp.int8),
            jnp.asarray(rng.random((L, planes, KV, slots)) + 7.0,
                        jnp.float32))
    return KVPool(jnp.asarray(
        rng.standard_normal((L, planes, slots, W)) + 100.0, jnp.bfloat16))


def _starts(case, n, bs):
    """First positions of five sequences: full, idle, ending mid-tile,
    empty (a position but no row), one row."""
    if case == "aligned":
        s = [0, 0, n if 2 * n <= MAXB * bs else 0, 0, bs]
    elif case == "unaligned":
        s = [3, 1, 7, 5, 9]
    else:                      # the rows cross a block boundary
        s = [bs - 1, bs - 3, max(bs - n // 2, 1), 2 * bs - 2,
             2 * bs - n // 2 - 1 if n > 1 else 2 * bs - 1]
    return np.minimum(s, MAXB * bs - n).astype(np.int32)


def _counts(n):
    return np.array([n, 0, max(n - n // 3, 1), 0, 1], np.int32)


def _bits(x):
    return np.asarray(x).view(np.uint8)


@pytest.mark.parametrize("case", ["aligned", "unaligned", "crossing"])
@pytest.mark.parametrize("n,bs", [(512, 640), (256, 256), (64, 640),
                                  (1, 256)])
@pytest.mark.parametrize("planes", [2, 1])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_writer_leaves_the_pool_as_the_row_scatter_does(int8, planes, n, bs,
                                                        case):
    rng = np.random.default_rng(n + bs + planes)
    S = 5
    slots = (S * MAXB + 1) * bs
    tables = jnp.asarray(
        rng.permutation(S * MAXB).reshape(S, MAXB), jnp.int32)
    start, count = jnp.asarray(_starts(case, n, bs)), jnp.asarray(_counts(n))
    rows = jnp.asarray(rng.standard_normal((planes, S, n, W)), jnp.bfloat16)
    kv = _pool(rng, int8, planes, slots)

    @jax.jit
    def both(kv, rows, start, count, tables):
        plan = write_plan(start, count, tables, n, bs, kv.data.shape)
        return (store_rows(kv, LAYER, rows, plan, KV),
                _row_scatter(kv, rows, start, count, tables, bs))

    got, want = both(kv, rows, start, count, tables)
    keep = slots - bs                   # everything but the trash block
    assert np.array_equal(_bits(got.data[:, :, :keep]),
                          _bits(want.data[:, :, :keep]))
    if int8:
        assert np.array_equal(_bits(got.scales[..., :keep]),
                              _bits(want.scales[..., :keep]))
    # the step changed exactly its real rows, in layer LAYER alone
    changed = (_bits(got.data[:, :, :keep])
               != _bits(kv.data[:, :, :keep])).any(-1).reshape(
                   L, planes, keep, -1).any(-1)
    assert not changed[1 - LAYER].any()
    assert int(changed[LAYER].sum()) <= planes * int(count.sum())
    # and the counter's closed form is the plan's own count of windows
    plan = write_plan(start, count, tables, n, bs, kv.data.shape)
    live = int((np.asarray(plan.index[0]) != keep // tile_rows(n, bs)).sum())
    assert live == sum(runs_issued(int(s), int(c), n, bs)
                       for s, c in zip(start, count))


def _mesh(axis):
    return Mesh(np.array(jax.devices()[:2]), (axis,))


@pytest.mark.parametrize("n,bs", [(64, 128), (32, 32), (1, 32)])
def test_seq_shards_store_only_the_blocks_they_own(n, bs):
    """seq=2: block b lives on chip b % 2 as its block b // 2, each chip
    with a trash block of its own; every chip is handed every row and
    keeps its own."""
    rng = np.random.default_rng(n)
    S, sz, nblocks = 4, 2, 12
    tables = jnp.asarray(rng.permutation(nblocks).reshape(S, MAXB),
                         jnp.int32)
    start = jnp.asarray(np.minimum([5, 0, bs - 3, bs], MAXB * bs - n),
                        jnp.int32)
    count = jnp.asarray([n, 0, max(n // 2, 1), n], jnp.int32)
    rows = jnp.asarray(rng.standard_normal((2, S, n, W)), jnp.bfloat16)
    one = _pool(rng, False, 2, (nblocks + 1) * bs)
    # the sharded pool at rest: chip r's slots are its blocks then trash
    loc = (nblocks // sz + 1) * bs
    by_chip = [jnp.concatenate(
        [one.data[:, :, b * bs:(b + 1) * bs] for b in range(r, nblocks, sz)]
        + [one.data[:, :, -bs:]], axis=2) for r in range(sz)]

    def local(data, rows, start, count, tables):
        plan = write_plan(start, count, tables, n, bs, data.shape,
                          (sz, jax.lax.axis_index("seq")))
        return store_rows(data, LAYER, rows, plan)

    got = jax.jit(shard_map(
        local, mesh=_mesh("seq"), in_specs=(P(None, None, "seq"),) + (P(),) * 4,
        out_specs=P(None, None, "seq"), check_vma=False))(
            jnp.concatenate(by_chip, axis=2), rows, start, count, tables)
    want = jax.jit(functools.partial(_row_scatter, bs=bs))(
        one, rows, start, count, tables).data
    for r in range(sz):
        for j, b in enumerate(range(r, nblocks, sz)):
            assert np.array_equal(
                _bits(got[:, :, r * loc + j * bs:r * loc + (j + 1) * bs]),
                _bits(want[:, :, b * bs:(b + 1) * bs])), (r, b)


def test_tp_shards_store_their_own_lanes():
    """tp=2: the pool and the rows enter lane-sharded (a kv head a chip),
    int8 scales head-sharded; the writer is chip-local."""
    n, bs, S = 64, 128, 3
    rng = np.random.default_rng(7)
    tables = jnp.asarray(rng.permutation(S * MAXB).reshape(S, MAXB),
                         jnp.int32)
    start = jnp.asarray([bs - 9, 0, 40], jnp.int32)
    count = jnp.asarray([n, 0, 17], jnp.int32)
    rows = jnp.asarray(rng.standard_normal((2, S, n, W)), jnp.bfloat16)
    kv = _pool(rng, True, 2, (S * MAXB + 1) * bs)

    def local(kv, rows, start, count, tables):
        plan = write_plan(start, count, tables, n, bs, kv.data.shape)
        return store_rows(kv, LAYER, rows, plan, KV // 2)

    spec = KVPool(P(None, None, None, "model"), P(None, None, "model"))
    got = jax.jit(shard_map(
        local, mesh=_mesh("model"),
        in_specs=(spec, P(None, None, None, "model"), P(), P(), P()),
        out_specs=spec, check_vma=False))(kv, rows, start, count, tables)
    want = jax.jit(functools.partial(_row_scatter, bs=bs))(
        kv, rows, start, count, tables)
    keep = S * MAXB * bs
    assert np.array_equal(_bits(got.data[:, :, :keep]),
                          _bits(want.data[:, :, :keep]))
    assert np.array_equal(_bits(got.scales[..., :keep]),
                          _bits(want.scales[..., :keep]))


@pytest.mark.parametrize("start,count,n,bs,runs", [
    (0, 512, 512, 640, 4), (0, 300, 512, 640, 3), (640, 512, 512, 640, 4),
    (3, 512, 512, 640, 5), (0, 0, 512, 640, 0), (700, 64, 64, 640, 2),
    (704, 64, 64, 640, 1), (639, 64, 64, 640, 2), (77, 1, 1, 256, 1),
    (0, 256, 256, 256, 1)])
def test_runs_issued_closed_form(start, count, n, bs, runs):
    assert runs_issued(start, count, n, bs) == runs


def test_engine_counts_rows_and_runs_per_step_and_per_flush():
    """``pipeline_stats``' ``kv_write_rows`` / ``kv_write_runs`` against a
    hand count: prefill chunks of 16 into blocks of 128 move 16 rows a
    run, one-token steps are the row path (1.0), a flush's ring rows are
    a run unless they straddle a multiple of their count."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
    mcfg = GPT2Config(vocab_size=96, max_seq_len=512, num_layers=1,
                      num_heads=2, hidden_size=32, dtype=jnp.float32)
    params = GPT2(mcfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngineV2(mcfg, params, RaggedInferenceConfig(
        max_seqs=4, chunk_size=16, block_size=128, num_blocks=12,
        max_blocks_per_seq=2, dtype="float32", decode_loop_steps=4))
    stats = eng.pipeline_stats
    prompts = {1: 32, 2: 48}
    first = eng.put(list(prompts), [list(range(1, n + 1))
                                    for n in prompts.values()], _greedy=True)
    # whole chunks at multiples of 16: every run carries a whole tile
    assert (stats["kv_write_rows"], stats["kv_write_runs"]) == (80, 5)
    eng.decode_pipelined(list(prompts), [first[u] for u in prompts], 3)
    assert (stats["kv_write_rows"], stats["kv_write_runs"]) \
        == (80 + 6, 5 + 6)
    # the flush of 4 ring rows from positions 35 and 51: 35..38 straddles
    # 36, 51..54 straddles 52
    eng.decode_batch(list(prompts), [7, 8], 4)
    assert (stats["kv_write_rows"], stats["kv_write_runs"]) \
        == (86 + 8, 11 + 4)


@pytest.mark.parametrize("n,bs,row,t", [
    (512, 256, 3840, 64),      # 30 K/V heads of 128: 256 rows would be 1.9 MiB
    (256, 256, 3840, 64), (512, 640, 2048, 128), (512, 256, 1024, 256),
    (256, 256, 640, 256), (512, 256, 0, 256), (1, 256, 3840, 1),
    (96, 96, 1 << 20, 3)])     # halved while even, never below an odd tile
def test_a_window_is_halved_until_the_gather_takes_it_whole(n, bs, row, t):
    """512 KiB of bfloat16 a window: the cells' windows until PR 65 (at
    most 128 rows of 2,048 lanes) keep their size, 3,840-lane rows get 64."""
    assert tile_rows(n, bs, row) == t
    assert runs_issued(0, n, n, bs, row) == n // t


@pytest.mark.parametrize("case", ["aligned", "unaligned", "crossing"])
def test_a_halved_window_leaves_the_pool_as_the_row_scatter_does(
        case, monkeypatch):
    """The writer under a window limit that bites (16 rows of the toy
    pool's 16 lanes where the step and the block give 256)."""
    from deepspeed_tpu.inference.v2 import kv_write
    monkeypatch.setattr(kv_write, "_WINDOW_ELEMENTS", 16 * W)
    n = bs = 256
    assert tile_rows(n, bs, W) == 16
    rng = np.random.default_rng(11)
    S = 5
    slots = (S * MAXB + 1) * bs
    tables = jnp.asarray(
        rng.permutation(S * MAXB).reshape(S, MAXB), jnp.int32)
    start, count = jnp.asarray(_starts(case, n, bs)), jnp.asarray(_counts(n))
    rows = jnp.asarray(rng.standard_normal((2, S, n, W)), jnp.bfloat16)
    kv = _pool(rng, False, 2, slots)
    plan = write_plan(start, count, tables, n, bs, kv.data.shape)
    assert plan.real.shape[1:] == (n // 16 + 1, 16)
    got = store_rows(kv, LAYER, rows, plan, KV)
    want = _row_scatter(kv, rows, start, count, tables, bs)
    keep = slots - bs
    assert np.array_equal(_bits(got.data[:, :, :keep]),
                          _bits(want.data[:, :, :keep]))
    live = int((np.asarray(plan.index[0]) != keep // 16).sum())
    assert live == sum(runs_issued(int(s), int(c), n, bs, W)
                       for s, c in zip(start, count))
