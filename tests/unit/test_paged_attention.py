"""The paged-attention kernels (``ops/kernels/paged_attention.py``) called
directly, in interpret mode, against a dense numpy reference over the same
rows, and the engine-level checks that belong to the kernels: parity with
the dense fallback, what a decode step fetches, what the decode kernel's
set-up costs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceConfig)
from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config


def _as_pool(k, v, layer=1, layers=3):
    """One layer's K and V planes ``[slots, KVD]`` (or its K and V scales
    ``[KV, slots]``) as layer ``layer`` of the ``[layers, 2, ...]`` array
    the kernels take. Every other plane holds other rows, so a call that
    reads a wrong layer, or K for V, cannot pass."""
    rng = np.random.default_rng(layers * 10 + layer)
    shape = (layers, 2) + k.shape
    other = (rng.integers(-127, 128, shape) if k.dtype == jnp.int8
             else rng.standard_normal(shape, dtype=np.float32))
    return jnp.asarray(other, k.dtype).at[layer, 0].set(k).at[layer, 1].set(v)


def _dense_reference(q, k_rows, v_rows, tables, start, lens, bs, KV, *,
                     window=None, slopes=None, ring=None, rcount=0):
    """Attention of ``q [S, C, H, D]`` over each sequence's ``lens[s]``
    settled rows of one layer's float K and V planes ``[slots, KV * D]``,
    gathered through the block tables, in float32 numpy. Query ``c`` of
    sequence ``s`` sits at position ``start[s] + c``. ``ring [R, 2, S,
    KVD]`` (one layer of the fused loop's carry) adds its first ``rcount``
    rows as the positions that end at ``start[s]``. An idle slot
    (``lens[s] == 0``) emits zeros. Returns ``[S, C, H, D]``."""
    q = np.asarray(q, np.float32)
    k_rows, v_rows = (np.asarray(x, np.float32) for x in (k_rows, v_rows))
    tables, start, lens = (np.asarray(x) for x in (tables, start, lens))
    S, C, H, D = q.shape
    ref = np.zeros((S, C, H, D), np.float32)
    for s in range(S):
        n = int(lens[s])
        if n == 0:
            continue
        j = np.arange(n)
        idx = tables[s, j // bs] * bs + j % bs
        kc, vc, pos = k_rows[idx], v_rows[idx], j
        if ring is not None:
            rg = np.asarray(ring[:rcount, :, s], np.float32)
            kc = np.concatenate([kc, rg[:, 0]])
            vc = np.concatenate([vc, rg[:, 1]])
            pos = np.concatenate(
                [pos, int(start[s]) - (rcount - 1) + np.arange(rcount)])
        kc = np.repeat(kc.reshape(-1, KV, D), H // KV, 1)
        vc = np.repeat(vc.reshape(-1, KV, D), H // KV, 1)
        sc = np.einsum("chd,khd->chk", q[s], kc) / np.sqrt(D)
        dist = (int(start[s]) + np.arange(C))[:, None] - pos[None, :]
        mask = dist >= 0
        if window is not None:
            mask &= dist < window
        if slopes is not None:
            sc = sc - np.asarray(slopes)[None, :, None] * dist[:, None, :]
        sc = np.where(mask[:, None, :], sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        ref[s] = np.einsum("chk,khd->chd", p / p.sum(-1, keepdims=True), vc)
    return ref


class TestPagedFlashKernel:
    """The Pallas paged-decode kernel vs the dense-gather fallback — and the
    long-context capability the dense path's max_context wall precluded."""

    def test_engine_tokens_identical_dense_vs_kernel(self):
        rng = np.random.default_rng(3)
        prompt = list(rng.integers(1, 96, 13))
        mcfg = GPT2Config(vocab_size=96, max_seq_len=128, num_layers=2,
                          num_heads=2, hidden_size=32, dtype=jnp.float32)
        params = GPT2(mcfg).init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
        gens = []
        for impl in ("dense", "paged_flash"):
            cfg = RaggedInferenceConfig(
                max_seqs=4, chunk_size=8, block_size=4, num_blocks=64,
                max_blocks_per_seq=16, dtype="float32", attention_impl=impl)
            eng = InferenceEngineV2(mcfg, params, cfg)
            gens.append(eng.generate([prompt], max_new_tokens=8)[0])
        assert gens[0] == gens[1]

    def test_long_context_8k(self):
        """Flash through block tables at 8k+ context: per-step work scales
        with LIVE blocks; here the pool itself is smaller than max_context
        would require for the dense path ((128+1)*64 slots vs S*8192)."""
        from deepspeed_tpu.ops.kernels import flash_paged_attention
        bs, nb = 64, 129                     # 8256 poolable tokens
        KV = H = 2
        D = 16
        S, C = 1, 1
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        pool_k = jax.random.normal(ks[0], ((nb + 1) * bs, KV * D), jnp.float32)
        pool_v = jax.random.normal(ks[1], ((nb + 1) * bs, KV * D), jnp.float32)
        maxb = 129
        tables = jnp.asarray(
            np.random.default_rng(0).permutation(nb)[None, :maxb], jnp.int32)
        seq_len = 8192 + 17                  # > 8k live tokens
        start = jnp.asarray([seq_len - 1], jnp.int32)
        lens = jnp.asarray([seq_len], jnp.int32)
        q = jax.random.normal(ks[2], (S, C, H, D), jnp.float32)

        out = flash_paged_attention(q, _as_pool(pool_k, pool_v), 1, tables,
                                    start, lens, block_size=bs,
                                    num_kv_heads=KV, interpret=True)

        ref = _dense_reference(q, pool_k, pool_v, tables, start, lens, bs, KV)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=1e-4)

    def test_gqa_and_chunk_parity(self):
        """Chunked prefill (C>1) + GQA kv heads vs dense reference."""
        from deepspeed_tpu.ops.kernels import flash_paged_attention
        bs, nb, KV, H, D, S, C = 8, 16, 2, 4, 8, 3, 4
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        pool_k = jax.random.normal(ks[0], ((nb + 1) * bs, KV * D), jnp.float32)
        pool_v = jax.random.normal(ks[1], ((nb + 1) * bs, KV * D), jnp.float32)
        perm = np.random.default_rng(1).permutation(nb)
        tables = np.zeros((S, 8), np.int32)   # <=5 live blocks per seq
        for s in range(S):
            tables[s, :5] = perm[s * 5:s * 5 + 5]
        tables = jnp.asarray(tables)
        start = jnp.asarray([0, 5, 29], jnp.int32)
        lens = start + C
        q = jax.random.normal(ks[2], (S, C, H, D), jnp.float32)
        out = flash_paged_attention(q, _as_pool(pool_k, pool_v, layer=2), 2,
                                    tables, start, lens, block_size=bs,
                                    num_kv_heads=KV, interpret=True)
        ref = _dense_reference(q, pool_k, pool_v, tables, start, lens, bs, KV)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("mode,kv_dtype,window,ring", [
        (mode, kv_dtype, window, ring)
        for mode in ("decode", "prefill")
        for kv_dtype in ("bf16", "int8")
        for window in (None, 12)
        for ring in ((False, True) if mode == "decode" else (False,))])
    def test_blockspec_kernel_reads_its_layer_of_the_pool(
            self, mode, kv_dtype, window, ring):
        """BlockSpec path (rows of 16 lanes): the whole [L, 2, slots, KVD]
        pool, its scales and the ring with a layer index against the
        reference over that layer's rows. Every plane holds different rows
        and the layer is the last of three, so a wrong layer or K/V index
        in the index map (or in the scales' or the ring's slice) cannot
        pass."""
        from deepspeed_tpu.inference.v2.kv_quant import dequantize_rows
        from deepspeed_tpu.ops.kernels import flash_paged_attention
        rng = np.random.default_rng(26)
        L, li = 3, 2
        bs, nb, maxb, KV, H, D, S = 8, 16, 4, 2, 4, 8, 4
        KVD, slots = KV * D, (nb + 1) * bs
        C = 1 if mode == "decode" else 4
        kw = dict(block_size=bs, num_kv_heads=KV, sliding_window=window,
                  interpret=True)
        if kv_dtype == "int8":
            pool = jnp.asarray(
                rng.integers(-127, 128, (L, 2, slots, KVD)), jnp.int8)
            scales = jnp.asarray(
                rng.uniform(0.005, 0.02, (L, 2, KV, slots)), jnp.float32)
            kw.update(scales=scales)
            k_rows, v_rows = (dequantize_rows(pool[li, x], scales[li, x],
                                              jnp.float32) for x in (0, 1))
        else:
            pool = jnp.asarray(
                rng.normal(size=(L, 2, slots, KVD)), jnp.bfloat16)
            k_rows, v_rows = pool[li]
        tables = jnp.asarray(
            rng.permutation(nb)[:S * maxb].reshape(S, maxb), jnp.int32)
        lens = jnp.asarray([29, 9, 17, 0], jnp.int32)   # slot 3 idle
        q = jnp.asarray(rng.normal(size=(S, C, H, D)), jnp.bfloat16)
        ref_kw = {}
        if ring:
            # the fused loop's form: the pool holds the settled rows, the
            # loop's own tokens sit in the (never quantized) ring
            rcount = 3
            carry = jnp.asarray(
                rng.normal(size=(4, L, 2, S, KVD)), jnp.bfloat16)
            kw.update(ring=carry, ring_count=jnp.asarray(rcount, jnp.int32))
            ref_kw.update(ring=carry[:, li], rcount=rcount)
            start, settled = lens + rcount - 1, lens
        else:
            start, settled = jnp.maximum(lens - C, 0), lens
        out = np.asarray(flash_paged_attention(
            q, pool, li, tables, start, settled, **kw), np.float32)
        assert out[:3].any()
        assert not out[3].any()                         # idle slot
        ref = _dense_reference(q, k_rows, v_rows, tables, start, settled,
                               bs, KV, window=window, **ref_kw)
        tol = 0.03 if kv_dtype == "int8" else 0.02
        np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)

    def test_whole_pool_operand_is_checked(self):
        # a pool of another geometry than the call's, a layer outside it,
        # scales that do not go with the pool's dtype: each is refused
        # before any kernel is built
        from deepspeed_tpu.ops.kernels import flash_paged_attention
        bs, slots, KV, D = 8, 24, 2, 8
        pool = jnp.zeros((3, 2, slots, KV * D), jnp.bfloat16)
        scales = jnp.ones((3, 2, KV, slots), jnp.float32)

        def call(pool, layer, D=D, **kw):
            return flash_paged_attention(
                jnp.zeros((2, 1, 4, D), jnp.bfloat16), pool, layer,
                jnp.zeros((2, 2), jnp.int32), jnp.zeros((2,), jnp.int32),
                jnp.ones((2,), jnp.int32), block_size=bs, num_kv_heads=KV,
                interpret=True, **kw)
        for bad, layer, match in (
                (pool[0], 0, r"pool must be \[L, 1 or 2"),
                (pool[:, :, :20], 0, "multiple of block_size"),
                (pool[..., :8], 0, "pool rows"),
                (pool, 3, "layer 3 out of range")):
            with pytest.raises(ValueError, match=match):
                call(bad, layer)
        with pytest.raises(ValueError, match="int8 pool needs its scales"):
            call(pool.astype(jnp.int8), 0)
        with pytest.raises(ValueError, match=r"scales must be \[3, 2, 2, 24\]"):
            call(pool.astype(jnp.int8), 0, scales=scales[:2])
        with pytest.raises(ValueError, match="the pool is not int8"):
            call(pool, 0, scales=scales)
        # the decode kernel reads plane 1 for V: a latent pool's one plane
        # (whose decode is mla_decode_attention's) is not handed to it
        with pytest.raises(ValueError, match="one-plane"):
            call(jnp.zeros((3, 1, slots, 2 * 128), jnp.bfloat16), 0, D=128)


class TestKVInt8Kernel:
    """Direct calls over an int8 pool (kv_quant.py): the kernels scale
    scores and probabilities and never dequantize a tile."""

    def test_kernel_direct_int8_parity(self):
        # direct kernel call: quantized pool + scales vs the fp pool,
        # prefill (multi-block BlockSpec path) and grouped decode (linear
        # layout) both
        from deepspeed_tpu.inference.v2.kv_quant import (dequantize_rows,
                                                         quantize_rows)
        from deepspeed_tpu.ops.kernels import flash_paged_attention
        rng = np.random.default_rng(7)
        S, H, KV, D = 4, 8, 2, 16
        KVD = KV * D

        # prefill: blocked layout
        bs, nb, maxb = 16, 12, 3
        slots = (nb + 1) * bs
        kf = jnp.asarray(rng.normal(size=(slots, KVD)), jnp.float32)
        vf = jnp.asarray(rng.normal(size=(slots, KVD)), jnp.float32)
        qk, sk = quantize_rows(kf, KV)
        qv, sv = quantize_rows(vf, KV)
        tables = jnp.asarray(
            rng.permutation(nb)[:S * maxb].reshape(S, maxb), jnp.int32)
        lens = jnp.asarray([40, 33, 17, 0], jnp.int32)
        C = 8
        q = jnp.asarray(rng.normal(size=(S, C, H, D)), jnp.float32)
        start = jnp.maximum(lens - C, 0)
        kw = dict(block_size=bs, num_kv_heads=KV, interpret=True)
        o_fp = flash_paged_attention(q, _as_pool(kf, vf), 1, tables, start,
                                     lens, **kw)
        o_i8 = flash_paged_attention(q, _as_pool(qk, qv), 1, tables, start,
                                     lens, scales=_as_pool(sk, sv), **kw)
        rel = float(jnp.max(jnp.abs(o_fp - o_i8))) / float(
            jnp.max(jnp.abs(o_fp)))
        assert rel < 0.05

        # grouped decode: linear layout, int8 pool + scales + ring
        bs2 = 64
        slots2 = (S + 1) * bs2
        kf2 = jnp.asarray(rng.normal(size=(slots2, KVD)), jnp.float32)
        vf2 = jnp.asarray(rng.normal(size=(slots2, KVD)), jnp.float32)
        qk2, sk2 = quantize_rows(kf2, KV)
        qv2, sv2 = quantize_rows(vf2, KV)
        L, li = 3, 1
        tables2 = jnp.arange(S, dtype=jnp.int32)[:, None]
        lens2 = jnp.asarray([40, 20, 64, 0], jnp.int32)
        q2 = jnp.asarray(rng.normal(size=(S, 1, H, D)), jnp.float32)
        R, rcount = 4, 2
        ring = jnp.asarray(rng.normal(size=(R, L, 2, S, KVD)), jnp.float32)
        start2 = lens2 + rcount
        o_full = flash_paged_attention(
            q2, _as_pool(qk2, qv2, li, L), li, tables2, start2, lens2,
            block_size=bs2, num_kv_heads=KV,
            scales=_as_pool(sk2, sv2, li, L), ring=ring,
            ring_count=jnp.asarray(rcount, jnp.int32), interpret=True)
        # dense reference over the dequantized pool + ring tokens (every
        # ring row visible: the query sits one past the last of them)
        want = _dense_reference(
            q2, dequantize_rows(qk2, sk2, jnp.float32),
            dequantize_rows(qv2, sv2, jnp.float32), tables2, start2, lens2,
            bs2, KV, ring=ring[:, li], rcount=rcount)
        live = np.asarray(lens2) > 0
        np.testing.assert_allclose(np.asarray(o_full)[live], want[live],
                                   atol=5e-5, rtol=5e-5)

    def test_kernel_int8_sliding_window(self):
        # mistral-class sliding window over an int8 pool: the window mask
        # must compose with score/prob scaling (scale applied pre-mask)
        from deepspeed_tpu.inference.v2.kv_quant import quantize_rows
        from deepspeed_tpu.ops.kernels import flash_paged_attention
        rng = np.random.default_rng(8)
        S, H, KV, D = 2, 4, 2, 16
        KVD = KV * D
        bs = 64
        slots = (S + 1) * bs
        kf = jnp.asarray(rng.normal(size=(slots, KVD)), jnp.float32)
        vf = jnp.asarray(rng.normal(size=(slots, KVD)), jnp.float32)
        qk, sk = quantize_rows(kf, KV)
        qv, sv = quantize_rows(vf, KV)
        tables = jnp.arange(S, dtype=jnp.int32)[:, None]
        lens = jnp.asarray([60, 33], jnp.int32)
        # kernel contract: start_pos is the query's own position and its
        # K/V row is already in the pool — the engine always calls with
        # start = seq_len - 1 at decode
        start = lens - 1
        q = jnp.asarray(rng.normal(size=(S, 1, H, D)), jnp.float32)
        kw = dict(block_size=bs, num_kv_heads=KV, sliding_window=16,
                  interpret=True)
        o_fp = flash_paged_attention(q, _as_pool(kf, vf), 1, tables, start,
                                     lens, **kw)
        o_i8 = flash_paged_attention(q, _as_pool(qk, qv), 1, tables, start,
                                     lens, scales=_as_pool(sk, sv), **kw)
        rel = float(jnp.max(jnp.abs(o_fp - o_i8))) / float(
            jnp.max(jnp.abs(o_fp)))
        assert rel < 0.05


class TestSeqLenBoundedGroupedReads:
    """Satellite: the grouped decode kernel's per-sequence context copy is
    tiled and stops at each sequence's settled length instead of streaming
    the whole (linear-layout) block; dead tiles are zero-filled."""

    def test_partial_lengths_match_reference(self):
        from deepspeed_tpu.ops.kernels import flash_paged_attention
        rng = np.random.default_rng(41)
        S, H, KV, D = 4, 4, 2, 16
        KVD = KV * D
        bs = 512                          # ts=256 -> 2 copy tiles per seq
        slots = (S + 1) * bs
        kf = jnp.asarray(rng.normal(size=(slots, KVD)), jnp.float32)
        vf = jnp.asarray(rng.normal(size=(slots, KVD)), jnp.float32)
        tables = jnp.arange(S, dtype=jnp.int32)[:, None]
        lens = jnp.asarray([130, 512, 1, 0], jnp.int32)  # partial/full/idle
        start = jnp.maximum(lens - 1, 0)
        q = jnp.asarray(rng.normal(size=(S, 1, H, D)), jnp.float32)
        out = flash_paged_attention(q, _as_pool(kf, vf), 1, tables, start,
                                    lens, block_size=bs, num_kv_heads=KV,
                                    interpret=True)
        assert not np.asarray(out[3]).any()              # idle slot
        ref = _dense_reference(q, kf, vf, tables, start, lens, bs, KV)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=1e-4)


def _wide_gpt2(layers=4):
    """A GPT-2 whose KV row is 256 lanes (2 heads of 128): the decode
    kernel's shape class, small enough for interpret mode."""
    mcfg = GPT2Config(vocab_size=96, max_seq_len=512, num_layers=layers,
                      num_heads=2, hidden_size=256, dtype=jnp.float32)
    params = GPT2(mcfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    cfg = RaggedInferenceConfig(
        max_seqs=4, chunk_size=16, block_size=128, num_blocks=12,
        max_blocks_per_seq=2, dtype="float32", decode_loop_steps=4,
        attention_impl="paged_flash")
    return mcfg, params, cfg


class TestDecodeKernelSetupCost:
    """``setup_s`` is programs times their tracing: the decode kernel's
    body is traced once a program family, not once a layer, and its
    copies are loops, not G x tiles unrolled regions."""

    def test_body_built_once_for_all_layers(self, monkeypatch):
        from deepspeed_tpu.ops.kernels import paged_attention as pa
        built = []
        body = pa._decode_kernel

        def counted(*a, **k):
            built.append(k["R"])
            return body(*a, **k)
        monkeypatch.setattr(pa, "_decode_kernel", counted)
        pa._decode_call.clear_cache()
        mcfg, params, cfg = _wide_gpt2(layers=4)
        eng = InferenceEngineV2(mcfg, params, cfg)
        first = eng.put([1, 2], [[5, 6, 7], [9, 8, 7, 6, 5]], _greedy=True)
        assert built == []                       # prefill: BlockSpec kernel
        # the unfed and the fed step program, 4 layers each: one body
        eng.decode_pipelined([1, 2], [first[1], first[2]], 3)
        assert built == [None]
        # the fused loop, 4 layers x 4 steps: one more (it has the ring)
        eng.decode_batch([1, 2], [3, 4], 4)
        assert built == [None, 4]

    def test_copies_are_loops(self):
        from deepspeed_tpu.ops.kernels import flash_paged_attention
        S, H, KV, D, bs, maxb = 16, 2, 2, 128, 256, 6
        pool = jnp.zeros((2, 2, (S * maxb + 1) * bs, KV * D), jnp.bfloat16)

        def call(q, pool, tables, lens):
            return flash_paged_attention(
                q, pool, 1, tables, lens - 1, lens, block_size=bs,
                num_kv_heads=KV, interpret=True)
        text = str(jax.make_jaxpr(call)(
            jnp.zeros((S, 1, H, D), jnp.bfloat16), pool,
            jnp.zeros((S, maxb), jnp.int32), jnp.ones((S,), jnp.int32)))
        # G = 8 sequences x 12 tiles x (K, V) would be 192 starts a step
        # if unrolled: the start appears once each for K and V in the
        # first-step and next-step loops, and so does the wait
        assert text.count("pallas_call") == 1
        assert 0 < text.count("dma_start") <= 4
        assert 0 < text.count("dma_wait") <= 2
        assert "while" in text


def test_decode_kv_rows_counted_per_step_and_per_fused_loop():
    """pipeline_stats' live / fetched K/V rows against a hand count on a
    three-sequence engine, over decode_pipelined and decode_batch."""
    from deepspeed_tpu.ops.kernels import decode_rows_fetched
    mcfg, params, cfg = _wide_gpt2(layers=1)
    eng = InferenceEngineV2(mcfg, params, cfg)
    prompts = {1: 3, 2: 127, 3: 130}
    first = eng.put(list(prompts), [list(range(1, n + 1))
                                    for n in prompts.values()], _greedy=True)
    stats = eng.pipeline_stats
    assert stats["decode_kv_rows_live"] == stats["decode_kv_rows_fetched"] == 0
    n = 3
    eng.decode_pipelined(list(prompts), [first[u] for u in prompts], n)
    # step t attends its own token too: lengths p+1 .. p+n, tiles of 128
    live = sum(p + t for p in prompts.values() for t in range(1, n + 1))
    fetched = sum(decode_rows_fetched(p + t, 128)
                  for p in prompts.values() for t in range(1, n + 1))
    assert fetched == 3 * 128 + (128 + 256 * 2) + 3 * 256
    assert (stats["decode_kv_rows_live"], stats["decode_kv_rows_fetched"]) \
        == (live, fetched)
    # the fused loop reads, every step, the rows settled at its entry
    # (its own tokens ride the ring): 4 steps at lengths p + n
    eng.decode_batch(list(prompts), [7, 8, 9], 4)
    live += 4 * sum(p + n for p in prompts.values())
    fetched += 4 * sum(decode_rows_fetched(p + n, 128)
                       for p in prompts.values())
    assert (stats["decode_kv_rows_live"], stats["decode_kv_rows_fetched"]) \
        == (live, fetched)
    assert 0 < live / fetched < 1
