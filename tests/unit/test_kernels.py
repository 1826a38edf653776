"""Pallas kernel parity tests (interpret mode on the CPU mesh) — the analogue
of the reference's per-op numerical tests under ``tests/unit/ops/``. The
flash-attention classes are in ``test_kernels_flash.py``, the FP6 ones in
``test_kernels_fp6.py`` (a file is what tier-1 schedules)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.kernels import (
    dequantize_blockwise,
    fused_adamw_update,
    fused_layer_norm,
    fused_rms_norm,
    quant_dequant,
    quantize_blockwise,
)
from deepspeed_tpu.ops.kernels.fused_optimizer import adamw_reference


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype)


class TestNorms:
    def test_rms_norm(self):
        x = _rand(jax.random.PRNGKey(0), (64, 256))
        w = 1.0 + 0.1 * _rand(jax.random.PRNGKey(1), (256,))
        out = fused_rms_norm(x, w, interpret=True)
        ref = (x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)) * w
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_rms_norm_grad(self):
        x = _rand(jax.random.PRNGKey(2), (32, 128))
        w = 1.0 + 0.1 * _rand(jax.random.PRNGKey(3), (128,))

        def f_fused(x, w):
            return jnp.sum(fused_rms_norm(x, w, interpret=True) ** 2)

        def f_ref(x, w):
            y = (x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)) * w
            return jnp.sum(y ** 2)

        gx1, gw1 = jax.grad(f_fused, (0, 1))(x, w)
        gx2, gw2 = jax.grad(f_ref, (0, 1))(x, w)
        np.testing.assert_allclose(gx1, gx2, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(gw1, gw2, atol=1e-4, rtol=1e-4)

    def test_layer_norm(self):
        x = _rand(jax.random.PRNGKey(4), (48, 192))
        w = 1.0 + 0.1 * _rand(jax.random.PRNGKey(5), (192,))
        b = 0.1 * _rand(jax.random.PRNGKey(6), (192,))
        out = fused_layer_norm(x, w, b, interpret=True)
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        ref = (x - mu) / jnp.sqrt(var + 1e-5) * w + b
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_layer_norm_grad(self):
        x = _rand(jax.random.PRNGKey(7), (16, 128))
        w = 1.0 + 0.1 * _rand(jax.random.PRNGKey(8), (128,))
        b = 0.1 * _rand(jax.random.PRNGKey(9), (128,))

        def f_fused(x, w, b):
            return jnp.sum(jnp.cos(fused_layer_norm(x, w, b, interpret=True)))

        def f_ref(x, w, b):
            mu = jnp.mean(x, -1, keepdims=True)
            y = (x - mu) / jnp.sqrt(jnp.var(x, -1, keepdims=True) + 1e-5)
            return jnp.sum(jnp.cos(y * w + b))

        g1 = jax.grad(f_fused, (0, 1, 2))(x, w, b)
        g2 = jax.grad(f_ref, (0, 1, 2))(x, w, b)
        for a, c in zip(g1, g2):
            np.testing.assert_allclose(a, c, atol=1e-4, rtol=1e-4)

    def test_bf16_io_f32_stats(self):
        x = _rand(jax.random.PRNGKey(10), (32, 128)).astype(jnp.bfloat16)
        w = jnp.ones((128,), jnp.bfloat16)
        out = fused_rms_norm(x, w, interpret=True)
        assert out.dtype == jnp.bfloat16


class TestQuantization:
    @pytest.mark.parametrize("bits,tol", [(8, 0.02), (4, 0.35)])
    @pytest.mark.parametrize("symmetric", [True, False])
    def test_round_trip(self, bits, tol, symmetric):
        x = _rand(jax.random.PRNGKey(0), (1024,)) * 3.0
        qt = quantize_blockwise(x, bits=bits, group_size=128,
                                symmetric=symmetric, interpret=True)
        out = dequantize_blockwise(qt)
        err = float(jnp.max(jnp.abs(out - x)))
        scale_mag = float(jnp.max(jnp.abs(x)))
        assert err < tol * scale_mag, err

    def test_non_divisible_length(self):
        x = _rand(jax.random.PRNGKey(1), (1000,))
        out = quant_dequant(x, bits=8, group_size=128, interpret=True)
        assert out.shape == x.shape
        assert float(jnp.max(jnp.abs(out - x))) < 0.1

    def test_shape_preserved(self):
        x = _rand(jax.random.PRNGKey(2), (8, 32, 16))
        out = quant_dequant(x, bits=8, group_size=64, interpret=True)
        assert out.shape == x.shape

    def test_int4_packing_halves_bytes(self):
        x = _rand(jax.random.PRNGKey(3), (512,))
        q8 = quantize_blockwise(x, bits=8, group_size=128, interpret=True)
        q4 = quantize_blockwise(x, bits=4, group_size=128, interpret=True)
        assert q4.values.size == q8.values.size // 2


class TestFusedAdamW:
    @pytest.mark.parametrize("n", [1024, 1000])  # 1000 exercises padding
    def test_parity_with_reference(self, n):
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        p = _rand(keys[0], (n,))
        g = _rand(keys[1], (n,))
        m = 0.1 * _rand(keys[2], (n,))
        v = jnp.abs(0.1 * _rand(keys[3], (n,)))
        kw = dict(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
        p1, m1, v1 = fused_adamw_update(p, g, m, v, 3, interpret=True, **kw)
        p2, m2, v2 = adamw_reference(p, g, m, v, 3, **kw)
        np.testing.assert_allclose(p1, p2, atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(m1, m2, atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(v1, v2, atol=1e-6, rtol=1e-6)

    def test_multi_step_matches_optax_adamw(self):
        import optax
        n = 512
        p = _rand(jax.random.PRNGKey(1), (n,))
        tx = optax.adamw(1e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
        state = tx.init(p)
        p_opx = p
        p_fused = p
        m = jnp.zeros((n,))
        v = jnp.zeros((n,))
        for t in range(1, 4):
            g = _rand(jax.random.PRNGKey(10 + t), (n,))
            upd, state = tx.update(g, state, p_opx)
            p_opx = optax.apply_updates(p_opx, upd)
            p_fused, m, v = fused_adamw_update(
                p_fused, g, m, v, t, lr=1e-3, weight_decay=0.01,
                interpret=True)
        np.testing.assert_allclose(p_fused, p_opx, atol=1e-5, rtol=1e-5)


class TestFusedXent:
    """Streaming LM-head cross-entropy (ops/kernels/fused_xent.py): loss
    and both gradients must match the chunked reference exactly — the
    kernel recomputes identical logits tiles, so the only difference is
    f32 summation order."""

    def _data(self, B=2, T=24, C=64, V=300):
        rng = np.random.RandomState(0)
        h = jnp.asarray(rng.randn(B, T, C) * 0.5, jnp.float32)
        emb = jnp.asarray(rng.randn(V, C) * 0.2, jnp.float32)
        tgt = jnp.asarray(rng.randint(0, V, size=(B, T)), jnp.int32)
        return h, emb, tgt

    def test_loss_and_grads_match_chunked(self):
        from deepspeed_tpu.models._lm_utils import chunked_lm_xent
        from deepspeed_tpu.ops.kernels import fused_lm_xent
        h, emb, tgt = self._data()
        ref = chunked_lm_xent(h, emb, tgt, num_chunks=4)
        got = fused_lm_xent(h, emb, tgt, token_block=16, vocab_block=128,
                            interpret=True)
        assert abs(float(ref) - float(got)) < 1e-4
        gr = jax.grad(lambda a, b: chunked_lm_xent(a, b, tgt, 4), (0, 1))(
            h, emb)
        gg = jax.grad(lambda a, b: fused_lm_xent(
            a, b, tgt, token_block=16, vocab_block=128, interpret=True),
            (0, 1))(h, emb)
        for a, b in zip(gr, gg):
            d = float(jnp.max(jnp.abs(a - b))) / float(jnp.max(jnp.abs(a)))
            assert d < 1e-3

    def test_token_padding_excluded(self):
        # N not a multiple of token_block: padded rows must not leak into
        # the loss or the embedding gradient
        from deepspeed_tpu.models._lm_utils import chunked_lm_xent
        from deepspeed_tpu.ops.kernels import fused_lm_xent
        h, emb, tgt = self._data(T=19)
        ref = chunked_lm_xent(h, emb, tgt, num_chunks=1)
        got = fused_lm_xent(h, emb, tgt, token_block=16, vocab_block=128,
                            interpret=True)
        assert abs(float(ref) - float(got)) < 1e-4
        gr = jax.grad(lambda a, b: chunked_lm_xent(a, b, tgt, 1), (0, 1))(
            h, emb)
        gg = jax.grad(lambda a, b: fused_lm_xent(
            a, b, tgt, token_block=16, vocab_block=128, interpret=True),
            (0, 1))(h, emb)
        for a, b in zip(gr, gg):       # dh exercises the padded-row slice
            d = float(jnp.max(jnp.abs(a - b))) / float(jnp.max(jnp.abs(a)))
            assert d < 1e-3
            assert np.isfinite(np.asarray(b)).all()

    def test_bf16_inputs(self):
        from deepspeed_tpu.models._lm_utils import chunked_lm_xent
        from deepspeed_tpu.ops.kernels import fused_lm_xent
        h, emb, tgt = self._data()
        ref = chunked_lm_xent(h, emb, tgt, num_chunks=4)
        got = fused_lm_xent(h.astype(jnp.bfloat16), emb.astype(jnp.bfloat16),
                            tgt, token_block=16, vocab_block=128,
                            interpret=True)
        assert abs(float(ref) - float(got)) < 0.05

    def test_model_config_routes_fused(self):
        # GPT2Config(xent_impl="fused") trains through the kernel path
        from deepspeed_tpu.models.gpt2 import GPT2Config, make_model
        cfg = GPT2Config(vocab_size=96, max_seq_len=17, num_layers=1,
                         num_heads=2, hidden_size=32, dtype=jnp.float32,
                         xent_impl="fused")
        model, init_fn, loss_fn = make_model(cfg)
        params = init_fn(jax.random.PRNGKey(0), batch_size=2, seq_len=16)
        batch = {"tokens": jnp.asarray(
            np.random.RandomState(0).randint(0, 96, size=(2, 17)),
            jnp.int32)}
        loss, grads = jax.value_and_grad(loss_fn)(params, batch,
                                                  jax.random.PRNGKey(1))
        assert np.isfinite(float(loss))
        gnorm = sum(float(jnp.sum(g * g))
                    for g in jax.tree_util.tree_leaves(grads))
        assert gnorm > 0

    def test_ignore_index(self):
        # torch cross_entropy ignore_index semantics: dropped from loss,
        # divisor, and BOTH gradients, in both implementations
        from deepspeed_tpu.models._lm_utils import chunked_lm_xent
        from deepspeed_tpu.ops.kernels import fused_lm_xent
        h, emb, tgt = self._data(T=20)
        mask = np.zeros((2, 20), bool)
        mask[0, 3:7] = True
        mask[1, -5:] = True
        tgt_ig = jnp.where(jnp.asarray(mask), -100, tgt)

        # reference: mean over kept positions only
        logits = (h.astype(jnp.float32)
                  @ emb.astype(jnp.float32).T)
        lse = jax.nn.logsumexp(logits, axis=-1)
        t_c = jnp.clip(tgt_ig, 0, emb.shape[0] - 1)
        nll = lse - jnp.take_along_axis(logits, t_c[..., None], -1)[..., 0]
        want = float(jnp.where(tgt_ig == -100, 0, nll).sum()
                     / (~mask).sum())

        got_c = chunked_lm_xent(h, emb, tgt_ig, num_chunks=4,
                                ignore_index=-100)
        got_f = fused_lm_xent(h, emb, tgt_ig, token_block=16,
                              vocab_block=128, ignore_index=-100,
                              interpret=True)
        assert abs(float(got_c) - want) < 1e-4
        assert abs(float(got_f) - want) < 1e-4

        # gradients: zero flow through ignored positions
        gh_c, ge_c = jax.grad(lambda a, b: chunked_lm_xent(
            a, b, tgt_ig, 4, ignore_index=-100), (0, 1))(h, emb)
        gh_f, ge_f = jax.grad(lambda a, b: fused_lm_xent(
            a, b, tgt_ig, token_block=16, vocab_block=128,
            ignore_index=-100, interpret=True), (0, 1))(h, emb)
        m3 = jnp.asarray(mask)[..., None]
        assert float(jnp.abs(jnp.where(m3, gh_f, 0)).max()) == 0.0
        for a, b in ((gh_c, gh_f), (ge_c, ge_f)):
            d = float(jnp.max(jnp.abs(a - b))) / float(jnp.max(jnp.abs(a)))
            assert d < 1e-3

    def test_out_of_range_ids_excluded(self):
        # corrupt labels (>= V, or >= the padded vocab grid) must not
        # poison the loss (ADVICE r4: the -inf masked column), must carry
        # zero gradient, and both impls must agree — torch raises here;
        # we exclude from loss + divisor (documented divergence)
        from deepspeed_tpu.models._lm_utils import chunked_lm_xent
        from deepspeed_tpu.ops.kernels import fused_lm_xent
        h, emb, tgt = self._data(T=20, V=300)
        bad = np.zeros((2, 20), bool)
        bad[0, 2] = bad[0, 11] = bad[1, 0] = True
        # 305 lands inside the padded vocab tile ([V, Vt*Vb)); 7000 is
        # beyond the whole padded grid — both failure modes from ADVICE
        tgt_bad = jnp.asarray(
            np.where(bad, np.array([[305] * 20, [7000] * 20]), tgt),
            jnp.int32)

        logits = h.astype(jnp.float32) @ emb.astype(jnp.float32).T
        lse = jax.nn.logsumexp(logits, axis=-1)
        t_c = jnp.clip(tgt_bad, 0, emb.shape[0] - 1)
        nll = lse - jnp.take_along_axis(logits, t_c[..., None], -1)[..., 0]
        want = float(jnp.where(jnp.asarray(bad), 0, nll).sum()
                     / (~bad).sum())

        got_c = chunked_lm_xent(h, emb, tgt_bad, num_chunks=4)
        got_f = fused_lm_xent(h, emb, tgt_bad, token_block=16,
                              vocab_block=128, interpret=True)
        assert np.isfinite(float(got_c)) and np.isfinite(float(got_f))
        assert abs(float(got_c) - want) < 1e-4
        assert abs(float(got_f) - want) < 1e-4

        gh_c, ge_c = jax.grad(lambda a, b: chunked_lm_xent(
            a, b, tgt_bad, 4), (0, 1))(h, emb)
        gh_f, ge_f = jax.grad(lambda a, b: fused_lm_xent(
            a, b, tgt_bad, token_block=16, vocab_block=128,
            interpret=True), (0, 1))(h, emb)
        m3 = jnp.asarray(bad)[..., None]
        assert float(jnp.abs(jnp.where(m3, gh_f, 0)).max()) == 0.0
        assert np.isfinite(np.asarray(gh_f)).all()
        assert np.isfinite(np.asarray(ge_f)).all()
        for a, b in ((gh_c, gh_f), (ge_c, ge_f)):
            d = float(jnp.max(jnp.abs(a - b))) / float(jnp.max(jnp.abs(a)))
            assert d < 1e-3

    def test_z_loss(self):
        # PaLM-style z-loss: loss + z*lse^2 per position, gradients via
        # the in-kernel (1 + 2z*lse)*P - onehot factor — checked against
        # autodiff of the explicit formula
        from deepspeed_tpu.ops.kernels import fused_lm_xent
        h, emb, tgt = self._data()
        z = 1e-2

        def ref_loss(a, b):
            logits = (a.astype(jnp.float32).reshape(-1, a.shape[-1])
                      @ b.astype(jnp.float32).T)
            lse = jax.nn.logsumexp(logits, axis=-1)
            t = tgt.reshape(-1)
            nll = lse - jnp.take_along_axis(
                logits, t[:, None], axis=-1)[:, 0]
            return (nll + z * lse * lse).mean()

        want = ref_loss(h, emb)
        got = fused_lm_xent(h, emb, tgt, token_block=16, vocab_block=128,
                            z_loss=z, interpret=True)
        assert abs(float(want) - float(got)) < 1e-4
        gr = jax.grad(ref_loss, (0, 1))(h, emb)
        gg = jax.grad(lambda a, b: fused_lm_xent(
            a, b, tgt, token_block=16, vocab_block=128, z_loss=z,
            interpret=True), (0, 1))(h, emb)
        for a, b in zip(gr, gg):
            d = float(jnp.max(jnp.abs(a - b))) / float(jnp.max(jnp.abs(a)))
            assert d < 1e-3

    def test_label_smoothing(self):
        # smoothed target distribution (1-eps)*onehot + eps/V — loss and
        # both gradients vs autodiff of the explicit formula
        from deepspeed_tpu.ops.kernels import fused_lm_xent
        h, emb, tgt = self._data()
        eps = 0.1

        def ref_loss(a, b):
            logits = (a.astype(jnp.float32).reshape(-1, a.shape[-1])
                      @ b.astype(jnp.float32).T)
            logp = jax.nn.log_softmax(logits, axis=-1)
            t = tgt.reshape(-1)
            V = b.shape[0]
            q = (1 - eps) * jax.nn.one_hot(t, V) + eps / V
            return -(q * logp).sum(-1).mean()

        want = ref_loss(h, emb)
        got = fused_lm_xent(h, emb, tgt, token_block=16, vocab_block=128,
                            label_smoothing=eps, interpret=True)
        assert abs(float(want) - float(got)) < 1e-4
        gr = jax.grad(ref_loss, (0, 1))(h, emb)
        gg = jax.grad(lambda a, b: fused_lm_xent(
            a, b, tgt, token_block=16, vocab_block=128,
            label_smoothing=eps, interpret=True), (0, 1))(h, emb)
        for a, b in zip(gr, gg):
            d = float(jnp.max(jnp.abs(a - b))) / float(jnp.max(jnp.abs(a)))
            assert d < 1e-3

    def test_sharded_wrapper_matches_chunked(self, devices8):
        # shard_map wrapping (rows over data, emb replicated, psum'd
        # loss): values AND both grads — incl. the psum'd embedding
        # cotangent and per-shard ignore_index counts — must match
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from deepspeed_tpu.models._lm_utils import chunked_lm_xent
        from deepspeed_tpu.ops.kernels.fused_xent import (
            sharded_fused_lm_xent)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        rng = np.random.RandomState(0)
        B, T, C, V = 16, 24, 64, 300
        h = jnp.asarray(rng.randn(B, T, C) * 0.5, jnp.float32)
        emb = jnp.asarray(rng.randn(V, C) * 0.2, jnp.float32)
        tgt = jnp.asarray(rng.randint(0, V, size=(B, T)), jnp.int32)
        # shard 0's rows are ENTIRELY ignored: the divisor must be the
        # global valid count (a per-shard clamp would inflate it by 1)
        tgt = tgt.at[0].set(-100)
        tgt = tgt.at[1].set(-100)
        h = jax.device_put(h, NamedSharding(mesh, P("data")))
        tgt = jax.device_put(tgt, NamedSharding(mesh, P("data")))
        emb = jax.device_put(emb, NamedSharding(mesh, P()))

        def loss_sh(h_, e_):
            return sharded_fused_lm_xent(
                h_, e_, tgt, mesh, token_block=16, vocab_block=128,
                ignore_index=-100, interpret=True)

        def loss_ref(h_, e_):
            return chunked_lm_xent(h_, e_, tgt, num_chunks=4,
                                   ignore_index=-100)

        assert abs(float(jax.jit(loss_sh)(h, emb))
                   - float(jax.jit(loss_ref)(h, emb))) < 1e-4
        g1 = jax.jit(jax.grad(loss_sh, argnums=(0, 1)))(h, emb)
        g2 = jax.jit(jax.grad(loss_ref, argnums=(0, 1)))(h, emb)
        for a, b in zip(g1, g2):
            d = float(jnp.max(jnp.abs(a - b))) / float(jnp.max(jnp.abs(b)))
            assert d < 1e-3


