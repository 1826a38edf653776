"""The train step and a loss's aux: ``aux["counters"]`` and
``aux["add"]`` are no-ops for a loss that returns none (the GPT step
lowers to the text it lowered to before they existed), an ``add`` moves the
master by its delta and by nothing else, one that names no leaf is refused
when the step is traced, and the seams that cannot carry them say so."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.config.config import MeshConfig
from deepspeed_tpu.models.gpt2 import GPT2Config, make_model


def _engine(loss_fn, params, devices=1, **extra):
    mesh = {"data": devices}
    engine, _, _, _ = dstpu.initialize(
        loss_fn=loss_fn, params=params,
        topology=dstpu.build_mesh(MeshConfig(**mesh),
                                  devices=jax.devices()[:devices]),
        config=dict({"train_micro_batch_size_per_gpu": 2,
                     "gradient_accumulation_steps": 1,
                     "gradient_clipping": 1.0, "steps_per_print": 10 ** 6,
                     "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                     "zero_optimization": {"stage": 0}, "mesh": mesh},
                    **extra))
    return engine


def _tiny():
    cfg = GPT2Config(vocab_size=256, max_seq_len=33, num_layers=2,
                     num_heads=2, hidden_size=32, attention_impl="xla")
    _, init_fn, loss_fn = make_model(cfg)
    return loss_fn, init_fn(jax.random.PRNGKey(0), 1, 8)


def _lowered(engine, rows=2):
    text = engine._train_step.lower(
        engine.state, {"tokens": jnp.zeros((rows, 33), jnp.int32)}).as_text()
    return re.sub(r"loc\(.*?\)", "", text)


#: sha256 of the tiny GPT step's lowered text (locations cut) as the parent
#: of ISSUE 61 lowered it, under jax ``_PINNED_JAX``
_PINNED = "edb684b5f9fc802f36177f558233fd0990aac3569ae42d4d4252f9ea4ccf1205"
_PINNED_JAX = "0.9.0"


def test_a_loss_without_aux_lowers_to_the_program_it_always_lowered_to():
    loss_fn, params = _tiny()
    plain = _lowered(_engine(loss_fn, params))
    # an aux with nothing for the step in it adds nothing to the program
    for aux in ({}, {"counters": {}, "add": {}}, {"other": 1.0}):
        wrapped = _engine(lambda p, b, r, aux=aux: (loss_fn(p, b, r), aux),
                          params)
        assert _lowered(wrapped) == plain
    if jax.__version__ == _PINNED_JAX:
        assert hashlib.sha256(plain.encode()).hexdigest() == _PINNED


def test_add_moves_a_leaf_by_its_delta_and_refuses_a_name_that_is_none():
    loss_fn, params = _tiny()
    batch = {"tokens": jnp.zeros((2, 33), jnp.int32)}
    delta = jnp.full((32,), 0.25)

    def with_add(name):
        return lambda p, b, r: (loss_fn(p, b, r), {
            "add": {name: delta}, "counters": {"rows": jnp.int32(5)}})

    engine = _engine(with_add("ln_f/scale"), params,
                     optimizer={"type": "AdamW", "params": {
                         "lr": 1e-3, "weight_decay": 0.1}})
    engine.train_batch(batch)
    engine.train_batch(batch)
    # the old value + delta, twice: no gradient step and no decay on it
    np.testing.assert_array_equal(engine.state.params["ln_f"]["scale"],
                                  params["ln_f"]["scale"] + 0.5)
    assert engine.step_stats["rows"] == 10
    with pytest.raises(KeyError, match="names no leaf"):
        _engine(with_add("ln_f/nothing"), params).train_batch(batch)


def test_the_seams_that_cannot_carry_the_aux_refuse_it(devices8):
    loss_fn, params = _tiny()

    def counted(p, b, r):
        return loss_fn(p, b, r), {"counters": {"rows": jnp.int32(1)}}

    engine = _engine(
        counted, params, devices=2,
        optimizer={"type": "OneBitAdam", "params": {"lr": 1e-3,
                                                    "freeze_step": 2}})
    with pytest.raises(NotImplementedError, match="1-bit"):
        engine.train_batch({"tokens": jnp.zeros((4, 33), jnp.int32)})
