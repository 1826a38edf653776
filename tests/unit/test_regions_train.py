"""The TRAIN step carries its regions and nothing else changes: the other
half of ``test_regions.py`` (whose helpers it reads), apart because a file
is what tier-1 schedules and the two together were its heaviest."""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.telemetry import trace
from test_regions import (_OP_NAME, _named, _strip, _unscoped,
                          metadata_keyed)


def _train_program(gas):
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, make_model
    mcfg = GPT2Config.tiny(dtype=jnp.float32, remat=True,
                           remat_policy="qkv_out")
    _model, init_fn, loss_fn = make_model(mcfg)
    params = init_fn(jax.random.PRNGKey(0), batch_size=2, seq_len=17)
    engine, _, _, _ = dstpu.initialize(
        loss_fn=loss_fn, params=params, config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 0}, "gradient_clipping": 1.0,
            "steps_per_print": 1000})
    batch = {"tokens": jnp.zeros((engine.config.train_batch_size, 18),
                                 jnp.int32)}
    return engine._train_step.lower(engine.state, batch).compile().as_text()


@pytest.mark.parametrize("gas", [1, 2], ids=["one-micro", "accumulated"])
def test_the_train_step_carries_its_regions_and_nothing_else_changes(
        gas, monkeypatch, metadata_keyed):
    scoped = _train_program(gas)
    with _unscoped(monkeypatch):
        bare = _train_program(gas)
    total, under, seen = _named(scoped)
    assert {"embed", "norm", "attn_proj", "attn_core", "ffn_dense",
            "residual", "head", "loss", "grad_clip", "optimizer"} <= seen
    assert seen <= set(trace.REGIONS)
    assert under >= 0.9 * total, (under, total)
    # the three passes are read from the path, not from a region
    paths = _OP_NAME.findall(scoped)
    assert any("transpose(jvp" in p and "rg.ffn_dense" in p for p in paths)
    assert any("rematted_computation" in p and "rg.norm" in p for p in paths)
    assert _named(bare)[2] == set()
    assert _strip(scoped) == _strip(bare)
