"""The paged-attention DECODE kernel (``C == 1`` calls at 128-lane rows)
against a dense reference, interpreted on the CPU: one case a row of ISSUE
31's list, and the plan's arithmetic. The prefill kernel, the int8 pool and
the engine's counters are in ``test_paged_attention.py``."""

import jax.numpy as jnp
import numpy as np
import pytest

from test_paged_attention import _as_pool, _dense_reference


def _decode_case(*, geom="gqa", maxb=2, S=8, bs=256, kv_dtype="bf16",
                 rcount=None, R=4, window=None, alibi=False, poison=False,
                 lens=None, wrap=None, seed=0):
    """One pure-decode call of the paged kernel (interpret mode) against
    a dense float32 reference over the same rows. Block tables are a
    random permutation: a sequence's blocks are never adjacent by
    construction. ``wrap``: the window pool's table instead, logical block
    ``b`` of slot ``s`` in block ``s * wrap + b % wrap``, so a position
    below the window reads the NEWER row that took its place and only a
    mask by position keeps it out. Returns (out, ref), both [S, H, D]
    float32."""
    from deepspeed_tpu.inference.v2.kv_quant import (dequantize_rows,
                                                     quantize_rows)
    from deepspeed_tpu.ops.kernels import (decode_tile_rows,
                                           flash_paged_attention)
    rng = np.random.default_rng(seed)
    H, KV, D = {"gqa": (12, 2, 128), "mha": (16, 16, 128),
                "gqa32": (32, 4, 128)}[geom]
    KVD = KV * D
    L, li = 2, 1
    nb = S * (wrap or maxb) + 3
    slots = (nb + 1) * bs
    ts = decode_tile_rows(bs, KVD, 1 if kv_dtype == "int8" else 2)
    assert ts == 128
    if lens is None:
        # idle, one row, around a tile edge, around a block edge, full
        want = [0, 1, ts - 1, ts, ts + 1, bs, bs + 1, maxb * bs]
        lens = [min(want[s % len(want)], maxb * bs) for s in range(S)]
    lens = np.asarray(lens, np.int64)
    if wrap:
        tables = np.arange(S)[:, None] * wrap + np.arange(maxb)[None] % wrap
    else:
        tables = rng.permutation(nb)[:S * maxb].reshape(S, maxb)
    live = np.zeros((slots,), bool)
    for s in range(S):
        j = np.arange(lens[s])
        live[tables[s, j // bs] * bs + j % bs] = True
    dt = {"bf16": jnp.bfloat16, "f32": jnp.float32, "int8": jnp.bfloat16}[
        kv_dtype]
    kf = jnp.asarray(rng.normal(size=(slots, KVD)), dt)
    vf = jnp.asarray(rng.normal(size=(slots, KVD)), dt)
    kw = {}
    if kv_dtype == "int8":
        pk, sk = quantize_rows(kf.astype(jnp.float32), KV)
        pv, sv = quantize_rows(vf.astype(jnp.float32), KV)
        k_ref = dequantize_rows(pk, sk, jnp.float32)
        v_ref = dequantize_rows(pv, sv, jnp.float32)
        if poison:       # a dead row's scale is whatever was left there
            dead = jnp.asarray(~live)[None, :]
            sk = jnp.where(dead, jnp.nan, sk)
            sv = jnp.where(dead, jnp.nan, sv)
        kw.update(scales=_as_pool(sk, sv, li, L))
    else:
        k_ref, v_ref = kf, vf
        if poison:       # every row above a live length holds NaN
            dead = jnp.asarray(~live)[:, None]
            pk = jnp.where(dead, jnp.nan, kf)
            pv = jnp.where(dead, jnp.nan, vf)
        else:
            pk, pv = kf, vf
    qdt = jnp.float32 if kv_dtype == "f32" else jnp.bfloat16
    q = jnp.asarray(rng.normal(size=(S, 1, H, D)), qdt)
    slopes = np.asarray([2.0 ** -(h + 1) for h in range(H)], np.float32)
    if alibi:
        kw.update(alibi_slopes=jnp.asarray(slopes))
    lens_j = jnp.asarray(lens, jnp.int32)
    ref_kw = {}
    if rcount is None:
        start = jnp.maximum(lens_j - 1, 0)
    else:
        ring = jnp.asarray(rng.normal(size=(R, L, 2, S, KVD)), qdt)
        start = lens_j + rcount - 1
        kw.update(ring=ring, ring_count=jnp.asarray(rcount, jnp.int32))
        ref_kw.update(ring=ring[:, li], rcount=rcount)
    out = flash_paged_attention(
        q, _as_pool(pk, pv, li, L), li, jnp.asarray(tables, jnp.int32),
        start, lens_j, block_size=bs, num_kv_heads=KV,
        sliding_window=window, interpret=True, **kw)
    ref = _dense_reference(q, k_ref, v_ref, tables, start, lens, bs, KV,
                           window=window, slopes=slopes if alibi else None,
                           **ref_kw)
    return np.asarray(out, np.float32)[:, 0], ref[:, 0], lens


# idle, under the window, one row over it, a window that starts on a
# tile's last row (nine tiles), windows around a chunk's edge, the mean
# context of the rollout cell, a full context
_LONG = dict(geom="gqa32", maxb=24, window=1024,
             lens=[0, 700, 1025, 1407, 1664, 1665, 3243, 6144])


class TestPagedDecodeKernel:
    """The decode kernel of ``C == 1`` calls at 128-lane rows (several
    sequences a grid step, live tiles only, through the block table) vs a
    dense reference: one case a row of ISSUE 31's list."""

    @pytest.mark.parametrize("case", [
        # blocks a sequence 1 / 2 / 6, tables permuted, every length class
        dict(maxb=1), dict(maxb=2), dict(maxb=6),
        # the fused loop's ring: empty, one token, full
        dict(rcount=0), dict(rcount=1), dict(rcount=4, maxb=1),
        # MHA 16 / 16 at 2048-lane rows (chunked: a context does not fit)
        dict(geom="mha", S=4, lens=[0, 129, 257, 512]),
        dict(geom="mha", S=4, lens=[1, 128, 256, 511], rcount=2),
        # slot counts the group size does not divide, or under one group
        dict(S=20), dict(S=3, rcount=2), dict(S=16, maxb=1),
        # int8 pool: two and six blocks a sequence, ring over int8
        dict(kv_dtype="int8"), dict(kv_dtype="int8", maxb=6),
        dict(kv_dtype="int8", rcount=3, maxb=1),
        # float32 pool
        dict(kv_dtype="f32", S=4, lens=[0, 130, 256, 300]),
        # sliding window (tiles wholly below it are not copied), ALiBi
        dict(window=100), dict(window=200, rcount=4, maxb=6),
        dict(window=100, kv_dtype="int8"), dict(alibi=True),
        dict(alibi=True, rcount=2, window=300),
        # poison: NaN in every pool row (or scale) above a live length
        dict(poison=True, S=16), dict(poison=True, rcount=2, maxb=6),
        dict(poison=True, geom="mha", S=4, lens=[0, 129, 257, 500]),
        dict(poison=True, kv_dtype="int8"),
        # a window of 1,024 over a context of 24 blocks (Mellum2's shape):
        # the chunks count from each sequence's own first live tile, and
        # the eight sequences of the one group stand all over the context
        *[dict(_LONG, **table, **form)
          for table in (dict(), dict(wrap=6))
          for form in (dict(), dict(rcount=4), dict(kv_dtype="int8"),
                       dict(poison=True))],
    ], ids=lambda c: "-".join(f"{k}={v}" for k, v in c.items()
                              if k != "lens"))
    def test_matches_dense_reference(self, case):
        out, ref, lens = _decode_case(**case)
        assert np.isfinite(out).all()
        assert not out[lens == 0].any()              # idle slots emit zeros
        tol = {"f32": 2e-5, "int8": 0.03}.get(case.get("kv_dtype"), 0.02)
        np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)

    def test_plan_follows_the_shapes(self):
        # G, chunk rows and chunks from slot count, context and row bytes:
        # Qwen's 512-byte rows hold a group's context in one or two
        # chunks, OLMoE's 4,096-byte rows stream it in tiles
        from deepspeed_tpu.ops.kernels.paged_attention import (
            _DECODE_KV_VMEM, _decode_plan, decode_rows_fetched,
            decode_rows_scored, decode_tile_rows)
        assert decode_tile_rows(640, 256, 2) == 128
        assert decode_tile_rows(256, 2048, 1) == 128
        assert decode_tile_rows(64, 256, 2) == 64          # block < tile
        assert decode_tile_rows(640, 64, 2) == 640         # narrow rows
        for S, ctx, row in ((128, 1280, 512), (16, 1536, 512),
                            (32, 1280, 4096), (20, 512, 512), (3, 256, 512)):
            G, cr, nch = _decode_plan(S, ctx, 128, row)
            assert S % G == 0 and cr % 128 == 0 and cr * nch >= ctx
            assert 4 * G * cr * row <= _DECODE_KV_VMEM
        assert _decode_plan(32, 1280, 128, 4096)[2] > 1
        assert decode_rows_fetched(0, 128) == 0
        assert decode_rows_fetched(129, 128) == 256
        assert decode_rows_fetched(673, 128) == 768
        assert decode_rows_fetched(673, 128, window=200) == 384
        # the serve cells' calls (slots, context rows, bytes a row): chat,
        # rollout, OLMoE, Solar, Nemotron, Mellum2's full layers
        for shape, plan in (((64, 1536, 512), (8, 768, 2)),
                            ((128, 1280, 512), (8, 640, 2)),
                            ((32, 1280, 4096), (8, 128, 10)),
                            ((128, 1280, 2048), (8, 384, 4)),
                            ((256, 6144, 512), (8, 1024, 6)),
                            ((256, 6144, 1024), (8, 768, 8))):
            S, ctx, row = shape
            assert _decode_plan(S, ctx, 128, row) == plan
            # a window the context fits in changes nothing
            assert _decode_plan(S, ctx, 128, row, window=ctx) == plan
        # under a window the chunks cover the tiles its rows can touch (a
        # window that starts on a tile's last row: 1 + 7 x 128 + 127 rows
        # in nine tiles), not the context: Mellum2's sliding layers
        assert _decode_plan(256, 6144, 128, 1024, window=1024) == (8, 640, 2)
        assert _decode_plan(8, 1536, 128, 512, window=130) == (8, 384, 1)
        assert decode_rows_scored(256, 6144, 128, 1024) == 8 * 768
        assert decode_rows_scored(256, 6144, 128, 1024, window=1024) \
            == 2 * 640
