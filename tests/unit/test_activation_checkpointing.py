"""Activation checkpointing (remat) tests.

Mirrors the reference's ``tests/unit/runtime/activation_checkpointing/``:
checkpointed forward+backward must match the uncheckpointed one bit-for-bit
(same RNG), for plain fns, dropout fns, and layer stacks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.config.config import ActivationCheckpointingConfig
from deepspeed_tpu.runtime import activation_checkpointing as ac


@pytest.fixture(autouse=True)
def _reset_ac():
    yield
    ac.reset()


def _mlp(params, x):
    h = jnp.tanh(x @ params["w1"])
    return jnp.sum((h @ params["w2"]) ** 2)


def _params(key, d=16):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (d, d)) * 0.1,
            "w2": jax.random.normal(k2, (d, d)) * 0.1}


class TestCheckpoint:
    def test_grad_matches_uncheckpointed(self):
        params = _params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))

        g_ref = jax.grad(_mlp)(params, x)
        g_ckpt = jax.grad(lambda p, x_: ac.checkpoint(_mlp, p, x_))(params, x)
        for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                        jax.tree_util.tree_leaves(g_ckpt)):
            # remat reorders fusion; tolerance covers XLA-version jitter
            np.testing.assert_allclose(a, b, rtol=5e-6)

    def test_policies_resolve(self):
        for name in ("nothing_saveable", "dots_saveable", "checkpoint_dots"):
            cfg = ActivationCheckpointingConfig(policy=name)
            assert ac.resolve_policy(cfg) is not None
        with pytest.raises(ValueError):
            ac.resolve_policy(ActivationCheckpointingConfig(policy="bogus"))

    def test_cpu_checkpointing_policy(self):
        cfg = ActivationCheckpointingConfig(cpu_checkpointing=True)
        pol = ac.resolve_policy(cfg)
        assert callable(pol)
        # host-offload grad parity
        params = _params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
        g_ref = jax.grad(_mlp)(params, x)
        ac.configure(cpu_checkpointing=True)
        # host-offload policies move saved residuals with device_put-to-
        # memory-kind, an in-jit-only feature — jit like the engine does
        g = jax.jit(jax.grad(
            lambda p, x_: ac.checkpoint(_mlp, p, x_)))(params, x)
        # the offloaded gradient is jitted and the reference is eager, so
        # XLA reassociates the reductions: an element that is a small
        # difference of O(0.1) terms moves by one ulp OF THOSE TERMS
        # (2^-26 = 1.5e-8 on this XLA), which is 5.7e-6 of a 2.6e-3
        # element. atol admits one float32 ulp of the O(1) summands; the
        # relative bound stays where it was.
        for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                        jax.tree_util.tree_leaves(g)):
            np.testing.assert_allclose(a, b, rtol=5e-6,
                                       atol=float(np.finfo(np.float32).eps))

    def test_configure_kwargs(self):
        cfg = ac.configure(policy="dots_saveable")
        assert cfg.policy == "dots_saveable"
        assert ac.get_config().policy == "dots_saveable"
        with pytest.raises(ValueError):
            ac.configure(not_a_knob=True)

    def test_rng_determinism_with_dropout(self):
        def dropped(params, x, key):
            h = jnp.tanh(x @ params["w1"])
            mask = jax.random.bernoulli(key, 0.5, h.shape)
            return jnp.sum(((h * mask) @ params["w2"]) ** 2)

        params = _params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
        key = jax.random.PRNGKey(7)
        g_ref = jax.grad(dropped)(params, x, key)
        g_ckpt = jax.grad(lambda p, x_, k: ac.checkpoint(dropped, p, x_, k))(
            params, x, key)
        for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                        jax.tree_util.tree_leaves(g_ckpt)):
            np.testing.assert_allclose(a, b, rtol=1e-6)


class TestCheckpointSequential:
    def _stack(self, n_layers=4, d=8):
        keys = jax.random.split(jax.random.PRNGKey(0), n_layers)
        w = jnp.stack([jax.random.normal(k, (d, d)) * 0.1 for k in keys])
        return {"w": w}

    @staticmethod
    def _block(p, h):
        return h + jnp.tanh(h @ p["w"])

    def _ref_apply(self, stacked, x):
        h = x
        for i in range(stacked["w"].shape[0]):
            h = self._block(jax.tree_util.tree_map(lambda p: p[i], stacked), h)
        return h

    @pytest.mark.parametrize("interval", [1, 2, 4])
    def test_matches_loop(self, interval):
        stacked = self._stack()
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))

        out = ac.checkpoint_sequential(self._block, stacked, x, interval=interval)
        ref = self._ref_apply(stacked, x)
        np.testing.assert_allclose(out, ref, rtol=1e-5)

        # gradients too
        g = jax.grad(lambda s, x_: jnp.sum(
            ac.checkpoint_sequential(self._block, s, x_, interval=interval)))(
                stacked, x)
        g_ref = jax.grad(lambda s, x_: jnp.sum(self._ref_apply(s, x_)))(stacked, x)
        np.testing.assert_allclose(g["w"], g_ref["w"], rtol=1e-5)

    def test_bad_interval(self):
        stacked = self._stack(n_layers=4)
        x = jnp.ones((2, 8))
        with pytest.raises(ValueError):
            ac.checkpoint_sequential(self._block, stacked, x, interval=3)


class TestRNGTracker:
    def test_fork_deterministic(self):
        t1 = ac.CheckpointableRNG(seed=0)
        t2 = ac.CheckpointableRNG(seed=0)
        k1, k2 = t1.fork(), t2.fork()
        np.testing.assert_array_equal(k1, k2)
        # second fork differs from first
        assert not np.array_equal(np.asarray(t1.fork()), np.asarray(k1))

    def test_states_roundtrip(self):
        t = ac.CheckpointableRNG()
        t.add("extra", 3)
        states = t.get_states()
        t.fork("extra")
        t.set_states(states)
        k_after = t.fork("extra")
        t.set_states(states)
        np.testing.assert_array_equal(k_after, t.fork("extra"))
