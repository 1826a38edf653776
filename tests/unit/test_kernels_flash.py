"""The flash-attention kernels (dense, block-sparse, sharded) against their
references, interpreted on the CPU; one class a file of ``test_kernels*.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.kernels import flash_attention
from deepspeed_tpu.ops.kernels.flash_attention import attention_reference


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("t", [128, 80])  # 80 exercises padding+mask
    def test_forward_parity(self, causal, t):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        q = _rand(k1, (2, t, 2, 32))
        k = _rand(k2, (2, t, 2, 32))
        v = _rand(k3, (2, t, 2, 32))
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gqa_forward(self):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
        q = _rand(k1, (1, 128, 4, 16))
        k = _rand(k2, (1, 128, 2, 16))
        v = _rand(k3, (1, 128, 2, 16))
        out = flash_attention(q, k, v, causal=True, interpret=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("t", [128, 80])  # 80 exercises padding+mask
    def test_gqa_gradient_parity(self, causal, t):
        """GQA backward: the grouped dk/dv accumulation grid must sum a KV
        head's cotangent over its whole q-head group (4 q heads over 2 KV
        heads here), matching autodiff through the repeated reference —
        with multiple q/k blocks so the fused (q-head, q-block) inner grid
        dim is exercised across block boundaries."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
        q = _rand(k1, (1, t, 4, 16))
        k = _rand(k2, (1, t, 2, 16))
        v = _rand(k3, (1, t, 2, 16))

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal, interpret=True,
                                block_q=64, block_k=128)
            return jnp.sum(jnp.sin(o))

        def loss_ref(q, k, v):
            return jnp.sum(jnp.sin(attention_reference(q, k, v, causal=causal)))

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("causal", [True, False])
    def test_gradient_parity(self, causal):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
        q = _rand(k1, (1, 128, 2, 16))
        k = _rand(k2, (1, 128, 2, 16))
        v = _rand(k3, (1, 128, 2, 16))

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal, interpret=True)
            return jnp.sum(jnp.sin(o))

        def loss_ref(q, k, v):
            return jnp.sum(jnp.sin(attention_reference(q, k, v, causal=causal)))

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)

    def test_gradient_parity_padded(self):
        """Padded (non-multiple-of-block) sequence: grads must match too."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
        q = _rand(k1, (1, 72, 2, 16))
        k = _rand(k2, (1, 72, 2, 16))
        v = _rand(k3, (1, 72, 2, 16))

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           interpret=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("tq,tk", [(1, 128), (64, 256), (96, 160)])
    def test_causal_decode_alignment(self, tq, tk):
        """q_len != kv_len: causal diagonal is bottom-right aligned (decode
        over a prefix attends the whole prefix)."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
        q = _rand(k1, (1, tq, 2, 16))
        k = _rand(k2, (1, tk, 2, 16))
        v = _rand(k3, (1, tk, 2, 16))
        out = flash_attention(q, k, v, causal=True, interpret=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_attention_impl_validation(self):
        from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
        cfg = GPT2Config.tiny(attention_impl="typo", dtype=jnp.float32)
        model = GPT2(cfg)
        toks = jnp.zeros((1, 8), jnp.int32)
        with pytest.raises(ValueError, match="attention_impl"):
            model.init(jax.random.PRNGKey(0), toks)

    # (tq, tk, q heads, kv heads, block, kv padding) -> blocks of each kind a
    # (batch, head): interior / sub-tiled / general
    BLOCK_KIND_CASES = {
        "three_kinds": ((700, 700, 2, 2, 256), (3, 2, 1)),
        "sub64_of_256": ((512, 512, 2, 2, 256), (1, 2, 0)),
        "cell_blocks_1024": ((2048, 2048, 1, 1, 1024), (1, 2, 0)),
        "gqa_dkv_walk": ((768, 768, 4, 2, 256), (3, 3, 0)),
        "one_block_512": ((512, 512, 2, 1, 512), (0, 1, 0)),
        "block_128_is_its_own_sub_tile": ((256, 256, 2, 2, 128), (1, 2, 0)),
        "tq_lt_tk_aligned": ((256, 512, 2, 2, 256), (1, 1, 0)),
        "tq_lt_tk_unaligned": ((200, 456, 2, 2, 256), (1, 0, 1)),
        "padded_kv_len": ((200, 200, 2, 2, 256), (0, 0, 1)),
    }

    @pytest.mark.parametrize("case", sorted(BLOCK_KIND_CASES))
    def test_block_kinds_parity(self, case):
        """Forward and gradients against the reference for grids that hold
        interior, sub-tiled (aligned diagonal) and general blocks: an
        aligned diagonal block computes only the strips on and below the
        diagonal, an interior block builds no mask, and everything else
        (padding, an offset that meets no block corner) keeps the
        whole-block mask and its old answer."""
        from deepspeed_tpu.ops.kernels.flash_attention import \
            take_causal_plans
        (tq, tk, h, hk, block), kinds = self.BLOCK_KIND_CASES[case]
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(tq + tk + h), 3)
        q = _rand(k1, (1, tq, h, 16))
        k = _rand(k2, (1, tk, hk, 16))
        v = _rand(k3, (1, tk, hk, 16))

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True, interpret=True,
                                   block_q=block, block_k=block)

        take_causal_plans()
        out = flash(q, k, v)
        (b_, h_, plan), = take_causal_plans()
        assert (b_, h_) == (1, h)
        assert (plan["interior"], plan["sub_tiled"], plan["general"]) == kinds
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

        g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))),
                      argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda *a: jnp.sum(jnp.sin(
            attention_reference(*a, causal=True))), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("geom,want", [
        # the train cells: T 2048 at 1024-blocks, offset 0
        ((2048, 2048, 1024, 1024, 2048, 0),
         dict(interior=1, sub_tiled=2, general=0, skipped=1,
              sub={"fwd": 512, "dq": 256, "dkv": 256},
              computed=1024 * 1024 * 3 + 2 * (3 * 512 ** 2 + 2 * 10 * 256 ** 2),
              needed=3 * (2048 * 2049 // 2))),
        # the defaults over the same T: 4 x 4 blocks of 512
        ((2048, 2048, 512, 512, 2048, 0),
         dict(interior=6, sub_tiled=4, general=0, skipped=6,
              sub={"fwd": 256, "dq": 128, "dkv": 128},
              computed=6 * 512 ** 2 * 3 + 4 * (3 * 256 ** 2 + 2 * 10 * 128 ** 2),
              needed=3 * (2048 * 2049 // 2))),
        # ring attention's diagonal hop: a shard's T / sp against itself
        ((512, 512, 512, 512, 512, 0),
         dict(interior=0, sub_tiled=1, general=0, skipped=0,
              sub={"fwd": 256, "dq": 128, "dkv": 128},
              computed=3 * 256 ** 2 + 2 * 10 * 128 ** 2,
              needed=3 * (512 * 513 // 2))),
        # a query shard one whole block below its keys: offset = a block
        ((512, 1024, 512, 512, 1024, 512),
         dict(interior=1, sub_tiled=1, general=0, skipped=0,
              sub={"fwd": 256, "dq": 128, "dkv": 128},
              computed=3 * 512 ** 2 + 3 * 256 ** 2 + 2 * 10 * 128 ** 2,
              needed=3 * (512 * 512 + 512 * 513 // 2))),
        # an offset that meets no block corner: every crossed block general
        ((512, 1024, 512, 512, 900, 388),
         dict(interior=0, sub_tiled=0, general=2, skipped=0,
              sub={"fwd": 256, "dq": 128, "dkv": 128},
              computed=3 * 2 * 512 ** 2,
              needed=3 * sum(min(r + 389, 900) for r in range(512)))),
    ])
    def test_causal_plan(self, geom, want):
        from deepspeed_tpu.ops.kernels.flash_attention import causal_plan
        plan = causal_plan(*geom)
        for key in ("interior", "sub_tiled", "general", "skipped", "sub"):
            assert plan[key] == want[key], key
        assert plan["score_elems_computed"] == want["computed"]
        assert plan["score_elems_needed"] == want["needed"]
        assert plan["score_area_share"] == pytest.approx(
            want["computed"] / want["needed"])
        assert plan["score_area_share"] >= 1.0

    def test_block_q_must_fill_lanes_on_the_chip(self):
        """The dk/dv kernel reads lse and delta as (1, block_q) lane rows."""
        x = jnp.zeros((1, 128, 1, 16))
        with pytest.raises(ValueError, match="multiple of 128"):
            flash_attention(x, x, x, block_q=64, interpret=False)

    def test_multi_block(self):
        """Sequence spanning several KV blocks (online-softmax accumulation)."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
        q = _rand(k1, (1, 256, 1, 16))
        k = _rand(k2, (1, 256, 1, 16))
        v = _rand(k3, (1, 256, 1, 16))
        out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                              interpret=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestFlashAttentionSparse:
    """Block-sparse flash path (splash-style grid skipping)."""

    def _ref(self, q, k, v, bm, block=128):
        mask = np.kron(np.asarray(bm, bool),
                       np.ones((block, block), dtype=bool))[:, :q.shape[2],
                                                            :k.shape[2]]
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / np.sqrt(q.shape[-1])
        s = jnp.where(jnp.asarray(mask)[None], s,
                      float(np.finfo(np.float32).min))
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(jnp.asarray(mask)[None].any(-1, keepdims=True), p, 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))

    def test_matches_masked_reference(self):
        from deepspeed_tpu.ops.kernels import flash_attention_sparse
        rng = jax.random.PRNGKey(0)
        b, h, t, d = 2, 2, 384, 64            # 3x3 blocks of 128
        q = jax.random.normal(rng, (b, h, t, d), jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(1), (b, h, t, d))
        v = jax.random.normal(jax.random.PRNGKey(2), (b, h, t, d))
        bm = np.array([[[1, 0, 1], [0, 1, 0], [1, 1, 1]],
                       [[1, 1, 0], [1, 0, 1], [0, 0, 1]]], np.int32)
        out = flash_attention_sparse(q, k, v, bm, layout="BHTD",
                                     interpret=True)
        ref = self._ref(q, k, v, bm)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_fully_masked_row_is_zero(self):
        from deepspeed_tpu.ops.kernels import flash_attention_sparse
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 256, 64))
        bm = np.array([[[1, 1], [0, 0]]], np.int32)   # row block 1: nothing
        out = flash_attention_sparse(q, q, q, bm, layout="BHTD",
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(out[:, :, 128:]), 0.0)
        assert float(jnp.abs(out[:, :, :128]).max()) > 0

    def test_sparse_attention_flash_impl(self):
        from deepspeed_tpu.ops.sparse_attention import (
            BigBirdSparsityConfig, sparse_attention)
        # 128-block layout re-tiles exactly — the flash path applies it
        cfg = BigBirdSparsityConfig(num_heads=2, block=128,
                                    num_sliding_window_blocks=1,
                                    num_global_blocks=1)
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 384, 32))
        layout = cfg.make_layout(384)
        out = sparse_attention(q, q, q, cfg, layout=layout, impl="flash")
        ref = sparse_attention(q, q, q, cfg, layout=layout)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_flash_impl_rejects_inexact_and_token_masks(self):
        from deepspeed_tpu.ops.sparse_attention import (
            FixedSparsityConfig, sparse_attention)
        # fine causal layout: coarsening would add (future) attention
        cfg = FixedSparsityConfig(num_heads=1, block=16,
                                  attention="unidirectional")
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 256, 32))
        with pytest.raises(ValueError, match="128-block"):
            sparse_attention(q, q, q, cfg, impl="flash")
        with pytest.raises(ValueError, match="layout_mask"):
            sparse_attention(q, q, q, cfg, impl="flash",
                             layout_mask=jnp.ones((1, 256, 256), bool))

    def test_coarsen_layout(self):
        from deepspeed_tpu.ops.sparse_attention import (
            coarsen_layout, coarsening_is_exact)
        fine = np.zeros((1, 16, 16), bool)
        fine[0, 3, 9] = True                  # one 16-block hit
        coarse = coarsen_layout(fine, 16, 128)
        assert coarse.shape == (1, 2, 2)
        assert coarse[0, 0, 1] and coarse.sum() == 1
        assert not coarsening_is_exact(fine, 16)   # partial block -> inexact
        # fully-dense coarse blocks are exact
        fine2 = np.zeros((1, 16, 16), bool)
        fine2[0, :8, 8:] = True
        assert coarsening_is_exact(fine2, 16)
        # expansion (block > 128) is exact by repetition
        big = np.asarray([[[1, 0], [0, 1]]], bool)
        exp = coarsen_layout(big, 256, 128)
        assert exp.shape == (1, 4, 4)
        assert exp[0, 0, 0] and exp[0, 1, 1] and not exp[0, 0, 2]


class TestShardedFlash:
    """sharded_flash_attention: the DP/ZeRO/TP shard_map wrapping."""

    def test_batch_and_head_sharded(self, devices8):
        from deepspeed_tpu.config import MeshConfig
        from deepspeed_tpu.ops.kernels import sharded_flash_attention
        from deepspeed_tpu.parallel import build_mesh
        topo = build_mesh(MeshConfig(data=4, model=2))
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(x, (8, 32, 4, 16), jnp.float32)
                   for x in ks)
        ref = attention_reference(q, k, v, causal=True)
        out = sharded_flash_attention(q, k, v, topo.mesh, causal=True,
                                      interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)

    def test_indivisible_falls_back(self, devices8):
        from deepspeed_tpu.config import MeshConfig
        from deepspeed_tpu.ops.kernels import sharded_flash_attention
        from deepspeed_tpu.parallel import build_mesh
        topo = build_mesh(MeshConfig(data=8))
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        # batch 3 not divisible by data=8 -> unsharded kernel fallback
        q, k, v = (jax.random.normal(x, (3, 16, 2, 8), jnp.float32)
                   for x in ks)
        ref = attention_reference(q, k, v, causal=True)
        out = sharded_flash_attention(q, k, v, topo.mesh, causal=True,
                                      interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)

    def test_grad_matches_reference(self, devices8):
        from deepspeed_tpu.config import MeshConfig
        from deepspeed_tpu.ops.kernels import sharded_flash_attention
        from deepspeed_tpu.parallel import build_mesh
        topo = build_mesh(MeshConfig(data=2, model=2, seq=2))
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q, k, v = (jax.random.normal(x, (4, 32, 4, 8), jnp.float32)
                   for x in ks)

        def loss_kernel(q, k, v):
            o = sharded_flash_attention(q, k, v, topo.mesh, causal=True,
                                        interpret=True)
            return jnp.sum(o ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

        gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=1e-3)

    def test_lse_output_grad(self):
        """return_lse: the lse cotangent folds into the backward
        (delta - dlse) — check against autodiff of a jnp logsumexp."""
        from deepspeed_tpu.ops.kernels import flash_attention
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q, k, v = (jax.random.normal(x, (1, 16, 2, 8), jnp.float32)
                   for x in ks)
        sm = 1.0 / np.sqrt(8)

        def loss_kernel(q, k, v):
            o, lse = flash_attention(q, k, v, causal=True, interpret=True,
                                     return_lse=True)
            return jnp.sum(o) + jnp.sum(jnp.sin(lse))

        def loss_ref(q, k, v):
            qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
            s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * sm
            mask = jnp.tril(jnp.ones((16, 16), bool))
            s = jnp.where(mask, s, -jnp.inf)
            o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), vt)
            lse = jax.nn.logsumexp(s, axis=-1)
            return jnp.sum(jnp.swapaxes(o, 1, 2)) + jnp.sum(jnp.sin(lse))

        np.testing.assert_allclose(float(loss_kernel(q, k, v)),
                                   float(loss_ref(q, k, v)), rtol=1e-5)
        gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=1e-3)


def _eqns(jaxpr):
    """Every equation under ``jaxpr``, in order, nested ones after their
    owner."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _forward_kernels(jaxpr) -> int:
    """The forward ``pallas_call``s under ``jaxpr``: the one kernel of the
    three that reads ``q``, ``k``, ``v`` and nothing else."""
    return sum(e.primitive.name == "pallas_call" and len(e.invars) == 3
               for e in _eqns(jaxpr))


def _shapes(jaxpr, skip=()):
    """``(primitive, output avals)`` of every equation under ``jaxpr``,
    the primitives of ``skip`` left out."""
    return [(e.primitive.name, tuple(str(v.aval) for v in e.outvars))
            for e in _eqns(jaxpr) if e.primitive.name not in skip]


@pytest.mark.parametrize("window", [None, 32])
@pytest.mark.parametrize("return_lse", [False, True])
class TestResidualNames:
    """ISSUE 69: the forward rules name ``o`` and ``lse``
    (``RESIDUAL_NAMES``), so a caller's ``jax.checkpoint`` decides whether
    its recompute runs the forward kernel again."""

    @staticmethod
    def _loss(window, return_lse, policy=None, checkpoint=True):
        def f(q, k, v):
            # something to recompute on either side of the call
            out = flash_attention(jnp.tanh(q), k, v, causal=True,
                                  window=window, interpret=True, block_q=32,
                                  block_k=32, return_lse=return_lse)
            if return_lse:
                return jnp.sum(jnp.sin(out[0])) + jnp.sum(jnp.cos(out[1]))
            return jnp.sum(jnp.sin(out))
        if checkpoint:
            f = jax.checkpoint(f, policy=policy)
        return jax.value_and_grad(f, argnums=(0, 1, 2))

    @staticmethod
    def _qkv():
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        return (_rand(ks[0], (1, 96, 4, 16)), _rand(ks[1], (1, 96, 2, 16)),
                _rand(ks[2], (1, 96, 2, 16)))

    def test_a_policy_that_lists_them_keeps_the_forward_out_of_the_recompute(
            self, window, return_lse):
        from deepspeed_tpu.ops.kernels.flash_attention import RESIDUAL_NAMES
        qkv = self._qkv()
        keep = jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES)
        plain = self._loss(window, return_lse)
        kept = self._loss(window, return_lse, keep)
        # the backward's two kernels are there either way
        for fn, forwards in ((plain, 2), (kept, 1)):
            jaxpr = jax.make_jaxpr(fn)(*qkv).jaxpr
            assert _forward_kernels(jaxpr) == forwards
            assert sum(e.primitive.name == "pallas_call"
                       for e in _eqns(jaxpr)) == forwards + 2
        (la, ga), (lb, gb) = jax.jit(plain)(*qkv), jax.jit(kept)(*qkv)
        assert float(la) == float(lb)
        for a, b in zip(ga, gb):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_a_policy_that_lists_another_name_recomputes_as_no_name_did(
            self, window, return_lse, monkeypatch):
        """A name is the identity: under a policy that keeps some OTHER
        name (the GPT cells' ``qkv_out``) the gradient's jaxpr is the one
        the unnamed rules trace, ``name`` equations apart, and so is the
        un-checkpointed gradient's."""
        import sys
        # the package's ``flash_attention`` attribute is the function
        fa = sys.modules["deepspeed_tpu.ops.kernels.flash_attention"]
        qkv = self._qkv()
        other = jax.checkpoint_policies.save_only_these_names("attn_out")
        named = [jax.make_jaxpr(self._loss(window, return_lse, other))(*qkv),
                 jax.make_jaxpr(self._loss(window, return_lse,
                                           checkpoint=False))(*qkv)]
        monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
        bare = [jax.make_jaxpr(self._loss(window, return_lse, other))(*qkv),
                jax.make_jaxpr(self._loss(window, return_lse,
                                          checkpoint=False))(*qkv)]
        for a, b in zip(named, bare):
            # the names ARE in the one and in the other nothing else is
            assert len(_shapes(a.jaxpr)) > len(_shapes(b.jaxpr))
            assert _shapes(a.jaxpr, skip=("name",)) == _shapes(b.jaxpr)
            assert _forward_kernels(a.jaxpr) == _forward_kernels(b.jaxpr)
        assert _forward_kernels(named[0].jaxpr) == 2
