"""The decode step's short convolution as one in-place Pallas call
(``ops/kernels/short_conv``, interpreted here) against what every other
path runs: ``llama_runner._short_conv``'s gather, ``conv_silu`` and
scatter. Bit for bit: the arithmetic is elementwise float32 in one order
and the pool is rounded to once."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.llama_runner import _short_conv
from deepspeed_tpu.inference.v2.model_runner import RaggedBatch
from deepspeed_tpu.ops.kernels import short_conv as sc

TAPS = 4


@pytest.mark.parametrize("S, W, dtype, bias", [
    # the three families' reduced shapes (tests/unit/test_<family>.py):
    # 4 heads of 16 three times over; 32 + 2 groups of 16 twice, biased
    (8, 192, jnp.float32, False),
    (4, 192, jnp.float32, False),
    (4, 96, jnp.float32, True),
    (4, 96, jnp.float32, False),
    # whole tiles, as the chip runs it: 16 rows a grid step, and 8 where
    # the rows are no multiple of 16
    (32, 2048, jnp.bfloat16, False),
    (32, 4096, jnp.bfloat16, True),
    (24, 2048, jnp.bfloat16, True),
], ids=["solar2", "kimi", "nemotron", "nemotron-no-bias", "tiles-16-rows",
        "tiles-biased", "tiles-8-rows"])
def test_kernel_equals_gather_conv_scatter_bit_for_bit(S, W, dtype, bias):
    """Slots a shuffled subset of the pool's rows; ordinary, fresh and
    idle rows in one call (and one that is both): a fresh row takes zeros
    for what its slot held, an idle row's slot keeps the last tenant's
    garbage, every other row of the pool is untouched."""
    rng = np.random.default_rng(S + W)
    layers, rows, si = 3, S + 5, 1
    pool = jnp.asarray(rng.normal(size=sc.pool_shape(layers, rows, TAPS, W)),
                       dtype)
    slots = rng.permutation(rows)[:S].astype(np.int32)
    x = jnp.asarray(rng.normal(size=(S, 1, W)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(TAPS, W)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(W,)), jnp.float32) if bias else None
    kind = rng.integers(0, 4, S)      # 0 ordinary, 1 fresh, 2 idle, 3 both
    kind[:4] = [0, 1, 2, 3]
    fresh = np.isin(kind, (1, 3))
    live = ~np.isin(kind, (2, 3))
    batch = RaggedBatch(
        tokens=jnp.zeros((S, 1), jnp.int32),
        # a fresh row is one whose chunk starts at position 0
        start_pos=jnp.asarray(np.where(fresh, 0, 7), jnp.int32),
        n_tokens=jnp.asarray(live, jnp.int32),
        block_tables=jnp.zeros((S, 1), jnp.int32),
        state_slots=jnp.asarray(slots))
    # jitted, as the step programs run it (XLA's CPU backend contracts a
    # multiply and an add inside one program, and only there)
    want_pool, want_y = jax.jit(_short_conv, static_argnums=1)(
        pool, si, batch, jnp.asarray(fresh), jnp.asarray(live), x, w, b)
    got_pool, got_y = sc.short_conv_decode_step(
        pool, si, jnp.asarray(slots), x[:, 0], w, b, jnp.asarray(fresh),
        jnp.asarray(live), interpret=True)
    assert got_y.dtype == jnp.float32 and got_pool.dtype == pool.dtype
    assert np.array_equal(np.asarray(got_y), np.asarray(want_y[:, 0]))
    f32 = lambda a: np.asarray(a.astype(jnp.float32))      # noqa: E731
    assert np.array_equal(f32(got_pool), f32(want_pool))
    # and what had to stay did: the other layers, the rows nobody named,
    # the idle rows' slots
    kept = np.ones((layers, rows), bool)
    kept[si, slots[live]] = False
    assert kept[si, slots[~live]].all()
    assert np.array_equal(f32(got_pool)[kept], f32(pool)[kept])
    # a live row carries its slot's last two inputs and the new one
    r = int(np.flatnonzero(kind == 0)[0])
    old = f32(pool)[si, slots[r]].reshape(TAPS - 1, W)
    new = f32(got_pool)[si, slots[r]].reshape(TAPS - 1, W)
    assert np.array_equal(new[:-1], old[1:])
    assert np.array_equal(new[-1], f32(x[r, 0].astype(dtype)))


def test_the_pool_is_whole_tiles_and_the_dispatch_reads_platform_and_shape():
    # the three cells' pools: a slot's [3, W] as rows of 128 lanes
    assert sc.pool_shape(3, 129, 4, 24576) == (3, 129, 576, 128)
    assert sc.pool_shape(6, 129, 4, 12288) == (6, 129, 288, 128)
    assert sc.pool_shape(6, 257, 4, 6144) == (6, 257, 144, 128)
    # a toy width keeps [taps - 1, W]
    assert sc.pool_shape(3, 5, 4, 96) == (3, 5, 3, 96)
    bf16 = jnp.bfloat16
    for S, W in ((128, 24576), (128, 12288), (256, 6144), (16, 2048)):
        assert sc.decode_uses_kernel(S, W, bf16, backend="tpu")
        assert not sc.decode_uses_kernel(S, W, bf16, backend="cpu")
    assert not sc.decode_uses_kernel(128, 6144, bf16)      # here: the CPU
    # rows in whole sublane tiles, a tap in whole tiles of the pool's dtype
    assert not sc.decode_uses_kernel(4, 6144, bf16, backend="tpu")
    assert not sc.decode_uses_kernel(128, 1024, bf16, backend="tpu")
    assert sc.decode_uses_kernel(128, 1024, jnp.float32, backend="tpu")
    assert not sc.decode_uses_kernel(128, 192, jnp.float32, backend="tpu")
