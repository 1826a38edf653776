"""``ops/kernels/qk_norm_rope.py`` (ISSUE 67): the kernel interpreted, its
``jax.numpy`` twin and ``jax.grad`` of the composition it replaces
(``models.llama.RMSNorm``'s arithmetic, then ``apply_rope``, in float32)
agree on the result, on both operands' cotangents and on both scales'."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.llama import apply_rope
from deepspeed_tpu.ops.kernels import qk_norm_rope as qn

EPS, THETA, D = 1e-5, 10000.0, 128


def _composition(q, k, q_scale, k_scale, rotate):
    """The two modules the call replaced, on float32 operands (no rounding
    inside): rows ``[B, T, heads * D]`` in, head-major out."""
    def one(x, scale):
        B, T, W = x.shape
        x = x.astype(jnp.float32).reshape(B, T, W // D, D)
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
        y = y * scale
        if rotate:
            y = apply_rope(y, jnp.arange(T)[None, :], THETA)
        return jnp.swapaxes(y, 1, 2)
    return one(q, q_scale), one(k, k_scale)


def _draw(T, H, KV, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = (2.0 * jax.random.normal(keys[0], (2, T, H * D))).astype(dtype)
    k = (0.5 * jax.random.normal(keys[1], (2, T, KV * D))).astype(dtype)
    scales = [1.0 + 0.2 * jax.random.normal(key, (D,)) for key in keys[2:4]]
    weights = [jax.random.normal(keys[4], (2, H, T, D)),
               jax.random.normal(keys[5], (2, KV, T, D))]
    return q, k, scales, weights


def _value_and_grads(fn, q, k, scales, weights):
    """The outputs, and the gradient of a weighted sum of them in the four
    differentiable operands: the weights are the outputs' cotangents."""
    def loss(q, k, q_scale, k_scale):
        yq, yk = fn(q, k, q_scale, k_scale)
        return sum(jnp.sum(y.astype(jnp.float32) * w)
                   for y, w in zip((yq, yk), weights)), (yq, yk)
    grads, ys = jax.grad(loss, argnums=(0, 1, 2, 3), has_aux=True)(
        q, k, *scales)
    return ys, grads


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("T", [64, 72], ids=["whole", "ragged"])
@pytest.mark.parametrize("H,KV", [(32, 4), (4, 1)])
@pytest.mark.parametrize("rotate", [True, False], ids=["rope", "nope"])
def test_kernel_twin_and_composition_agree(rotate, H, KV, T, dtype):
    """y, dx and both dscale of the interpreted kernel (a ``T`` of whole
    row blocks) or of the twin (a ``T`` that is none: ``impl_of`` says
    which) against autodiff of the float32 composition: to float32's
    rounding on float32 operands, and on bfloat16 operands within ONE
    rounding of the result (the composition rounds twice)."""
    q, k, scales, weights = _draw(T, H, KV, dtype)
    table = qn.rotary_table(T, D, THETA) if rotate else None
    impl = qn.impl_of(T, H, D, dtype, interpret=True)
    assert impl == ("interpret" if T % 16 == 0 else None)
    assert qn.impl_of(T, H, D, dtype) is None       # the CPU: the twin

    ys, grads = _value_and_grads(
        lambda *a: qn.qk_norm_rope(*a, EPS, table, interpret=True),
        q, k, scales, weights)
    ref_ys, ref_grads = _value_and_grads(
        lambda *a: _composition(*a, rotate), q, k, scales, weights)
    assert [y.dtype for y in ys] == [dtype, dtype]
    assert [y.shape for y in ys] == [(2, H, T, D), (2, KV, T, D)]
    assert [g.dtype for g in grads] == [dtype, dtype, jnp.float32,
                                        jnp.float32]
    # half a unit in the last place of the largest value, and float32's
    # own sums
    ulp = 2.0 ** -8 if dtype == jnp.bfloat16 else 2.0 ** -21
    for got, ref in zip(ys, ref_ys):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref),
            atol=ulp * float(jnp.abs(ref).max()), rtol=ulp)
    # a scale's cotangent sums 2 x T x heads products: the sums' order
    # shows at float32's 1e-5, not at its last bit
    for got, ref, tol in zip(grads, ref_grads, (2 * ulp, 2 * ulp,
                                                max(2 * ulp, 1e-5),
                                                max(2 * ulp, 1e-5))):
        scale = float(jnp.abs(ref.astype(jnp.float32)).max())
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=tol * scale, rtol=tol)

    # and the twin beside the kernel on the same operands: one
    # arithmetic, so float32's rounding apart whatever the dtype
    twin_ys, twin_grads = _value_and_grads(
        lambda *a: qn.qk_norm_rope(*a, EPS, table), q, k, scales, weights)
    for got, ref in zip(ys + grads, twin_ys + twin_grads):
        ref = np.asarray(ref, np.float32)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), ref, rtol=max(ulp, 1e-5),
            atol=max(ulp, 1e-5) * np.abs(ref).max())


def test_the_result_rounds_once_where_the_composition_rounds_twice():
    """On bfloat16 operands the call's result IS the float32 arithmetic
    rounded to bfloat16, element for element but for float32's last bit;
    ``RMSNorm(dtype=bf16)`` then ``apply_rope`` is further from it."""
    T, H, KV = 64, 4, 1
    q, k, scales, _ = _draw(T, H, KV, jnp.bfloat16, seed=1)
    table = qn.rotary_table(T, D, THETA)
    exact = _composition(q, k, *scales, True)
    for interpret in (True, False):
        got = qn.qk_norm_rope(q, k, *scales, EPS, table, interpret=interpret)
        for y, ref in zip(got, exact):
            once = ref.astype(jnp.bfloat16)
            assert float(jnp.mean(y == once)) > 0.999
            assert float(jnp.abs(y.astype(jnp.float32)
                                 - once.astype(jnp.float32)).max()) \
                <= 2.0 ** -7 * float(jnp.abs(ref).max())

    def twice(x, scale):
        xf = x.astype(jnp.float32).reshape(2, T, H, D)
        n = (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + EPS)
             * scale).astype(jnp.bfloat16)
        return jnp.swapaxes(apply_rope(n, jnp.arange(T)[None, :], THETA),
                            1, 2)

    def gap(y, ref):
        return float(jnp.abs(y.astype(jnp.float32) - ref).mean())
    assert gap(got[0], exact[0]) < 0.8 * gap(twice(q, scales[0]), exact[0])


def test_row_blocks_follow_bytes_and_whole_tiles():
    # the cell's call: 4,096 bfloat16 lanes a row, 2 MB a block
    assert qn.row_block(8192, 4096, jnp.bfloat16) == 256
    assert qn.row_block(8192, 4096, jnp.float32) == 128
    assert qn.row_block(8192, 512, jnp.bfloat16) == 2048
    assert qn.row_block(64, 4096, jnp.bfloat16) == 64
    assert qn.row_block(16 * 509, 4096, jnp.bfloat16) == 16  # a prime's
    assert qn.row_block(72, 4096, jnp.bfloat16) == 0
    assert qn.fits(8192, 32, 128, jnp.bfloat16)
    assert not qn.fits(8192, 32, 16, jnp.bfloat16)      # rehearse's heads
    assert not qn.fits(8191, 32, 128, jnp.bfloat16)
    assert not qn.fits(8192, 32, 128, jnp.int8)
    assert not qn.uses_kernel(8192, 32, 128, jnp.bfloat16)   # no TPU here


def test_uses_kernel_takes_one_tpu_device(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    assert qn.uses_kernel(8192, 32, 128, jnp.bfloat16)
    assert qn.impl_of(8192, 32, 128, jnp.bfloat16) == "pallas"
    assert qn.impl_of(8192, 32, 16, jnp.bfloat16) is None
    monkeypatch.setattr(jax, "device_count", lambda *a: 4)
    assert not qn.uses_kernel(8192, 32, 128, jnp.bfloat16)


def test_the_table_is_apply_ropes_angles():
    cos, sin = qn.rotary_table(96, D, THETA)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 96, 3, D))
    got = x * cos[:, None] + jnp.roll(x, D // 2, -1) * sin[:, None]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(apply_rope(x, jnp.arange(96)[None],
                                               THETA)), rtol=0, atol=1e-6)


def test_a_traced_step_holds_one_call_a_pass():
    """The kernel's path in a jaxpr: ONE ``pallas_call`` forward and one
    backward, and nothing of float32 at the operand's size outside them
    (the residuals are the operands)."""
    T, H, KV = 64, 4, 1
    q, k, scales, weights = _draw(T, H, KV, jnp.bfloat16)
    table = qn.rotary_table(T, D, THETA)

    def loss(q, k, q_scale, k_scale):
        yq, yk = qn.qk_norm_rope(q, k, q_scale, k_scale, EPS, table,
                                 interpret=True)
        return jnp.sum(yq.astype(jnp.float32) * weights[0]) \
            + jnp.sum(yk.astype(jnp.float32) * weights[1])
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(
        q, k, *scales))
    assert text.count("name=qk_norm_rope") == 2
    # nothing converts the operands to float32 outside the two calls (the
    # head-major float32 in the text is the loss's own product)
    assert "f32[2,64,512]" not in text and "f32[2,64,128]" not in text
