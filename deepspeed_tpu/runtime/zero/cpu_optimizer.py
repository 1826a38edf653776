"""ZeRO-Offload: the optimizer STEP runs on the host CPU.

Reference semantics (``runtime/zero/stage_1_and_2.py`` CPU-offload path +
``csrc/adam/cpu_adam*.cpp``): fp32 master parameters and Adam moments never
touch accelerator memory — the device computes gradients against low-precision
parameters, gradients stream to host, the host applies the optimizer update,
and refreshed low-precision parameters stream back. This is what makes
"13B params on one 32GB GPU" possible (docs/_pages/training.md:302): device
memory holds only compute-dtype params + grads + rematerialized activations.

TPU form: two jitted programs instead of hook-driven streams —
  grad_step   (device): GAS scan of value_and_grad, fp16 loss scaling
  cpu_update  (host CPU backend): unscale, global-norm clip, optax update,
              overflow gate, loss-scale/step advance, bf16 param re-cast
with the host orchestrating the d2h/h2d transfers between them (the XLA
analogue of the reference's pinned-buffer copy streams).

Activated by ``zero_optimization.offload_optimizer.device == "cpu"``.
Composes with DP/TP/SP meshes (grads arrive GSPMD-replicated); the manual
1-bit / ZeRO++ collective seams are mutually exclusive with it.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import loss_scaler as ls
from ...utils.dtypes import cast_floating
from ...utils.logging import log_dist


def cpu_device():
    return jax.local_devices(backend="cpu")[0]


def build_cpu_optimizer_step(engine):
    """Returns ``step_fn(state, batch) -> (new_state, metrics)`` with the
    TrainState's params (fp32 master) / opt_state living on the host CPU and
    ``engine._device_params`` (compute dtype) living on the device mesh."""
    cfg = engine.config
    gas = engine.gradient_accumulation_steps
    if engine._stream_params and gas > 1:
        raise ValueError(
            "offload_param.stream composed with the CPU optimizer needs "
            "gradient_accumulation_steps == 1: the in-jit grad accumulator "
            "would mix device and pinned-host memory spaces")
    fp16 = cfg.fp16.enabled
    clip = float(cfg.gradient_clipping or 0.0)
    compute_dtype = engine.compute_dtype
    batch_sharding = engine._batch_sharding()
    cpu = cpu_device()

    # ---------------- device program: gradients only ------------------- #

    def grad_step(dparams, batch, rngs, scale_state, step):
        def to_micro(x):
            x = jnp.asarray(x)
            mb = x.shape[0] // gas
            x = x.reshape((gas, mb) + x.shape[1:])
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(batch_sharding.mesh,
                                 P(None, *batch_sharding.spec)))
        micro = jax.tree_util.tree_map(to_micro, batch)

        def micro_grads(mb, r):
            def scaled_loss(cp):
                loss, aux = engine._loss_and_aux(cp, mb, r, step)
                from ..engine import _refuse_step_aux
                _refuse_step_aux(aux, "the CPU-offloaded optimizer's step")
                return (ls.scale_loss(loss, scale_state) if fp16 else loss,
                        loss)
            (_s, loss), grads = jax.value_and_grad(
                scaled_loss, has_aux=True)(dparams)
            return loss, jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads)

        if gas == 1:
            mb = jax.tree_util.tree_map(lambda x: x[0], micro)
            loss_sum, grads = micro_grads(mb, rngs[0])
        else:
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), dparams)

            def body(carry, xs):
                gacc, lacc = carry
                mb, r = xs
                loss, g = micro_grads(mb, r)
                return (jax.tree_util.tree_map(jnp.add, gacc, g),
                        lacc + loss), None

            (grads, loss_sum), _ = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)), (micro, rngs))
        return (loss_sum / gas).astype(jnp.float32), grads

    grad_step = jax.jit(grad_step) if cfg.compile else grad_step

    # ---------------- host program: the optimizer update --------------- #

    def cpu_update(master, opt_state, grads, scale_state, step):
        grads = jax.tree_util.tree_map(lambda g: g / gas, grads)
        if fp16:
            grads = ls.unscale_grads(grads, scale_state)
        finite = ls.grads_finite(grads) if fp16 else jnp.asarray(True)
        leaves = jax.tree_util.tree_leaves(grads)
        grad_norm = jnp.sqrt(sum(jnp.vdot(g, g).real
                                 for g in leaves)).astype(jnp.float32)
        if clip > 0.0:
            factor = jnp.minimum(1.0, clip / (grad_norm + 1e-6))
            grads = jax.tree_util.tree_map(lambda g: g * factor, grads)
        updates, new_opt = engine.optimizer.update(grads, opt_state, master)
        new_master = jax.tree_util.tree_map(
            lambda p, u: p + u.astype(p.dtype), master, updates)

        def sel(new, old):
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(finite, n, o), new, old)
        new_master = sel(new_master, master)
        new_opt = sel(new_opt, opt_state)
        new_scale = ls.update_state(scale_state, finite, cfg.fp16)
        new_step = step + jnp.where(finite, 1, 0).astype(jnp.int32)
        # compute-dtype copy cast on HOST: halves the h2d bytes
        new_dparams = cast_floating(new_master, compute_dtype)
        return (new_master, new_opt, new_scale, new_step, grad_norm, finite,
                new_dparams)

    cpu_update = jax.jit(cpu_update) if cfg.compile else cpu_update

    param_shardings = engine.zero_plan.param_shardings(engine.state.params)
    if engine._stream_params:
        # streamed leaves stay in the accelerator host's pinned memory
        # across steps — re-uploading them to plain device shardings here
        # would migrate the full model into HBM from step 2 on
        from .param_stream import host_sharding
        thr = engine._stream_threshold
        param_shardings = jax.tree_util.tree_map(
            lambda p, s: host_sharding(s) if p.size > thr else s,
            engine.state.params, param_shardings)

    from ..engine import StepMetrics, TrainState    # deferred: avoids cycle

    def step_fn(state: TrainState, batch: Any) -> Tuple[TrainState, StepMetrics]:
        rng = jax.device_put(state.rng, cpu)
        rngs = jax.random.split(rng, gas + 1)
        new_rng, micro_rngs = rngs[0], rngs[1:]

        loss, grads = grad_step(
            engine._device_params, batch,
            jax.device_put(micro_rngs, engine.topology.replicated()),
            jax.device_put(state.scale_state, engine.topology.replicated()),
            jax.device_put(state.step, engine.topology.replicated()))

        grads_host = jax.device_put(grads, cpu)          # d2h stream
        (new_master, new_opt, new_scale, new_step, grad_norm, finite,
         new_dparams) = cpu_update(state.params, state.opt_state, grads_host,
                                   state.scale_state, state.step)
        engine._device_params = jax.tree_util.tree_map(  # h2d stream
            lambda x, s: jax.device_put(x, s), new_dparams, param_shardings)

        lr = jnp.asarray(engine.lr_schedule(state.step), jnp.float32)
        metrics = StepMetrics(loss=loss, grad_norm=grad_norm, lr=lr,
                              loss_scale=new_scale.scale,
                              skipped=jnp.logical_not(finite),
                              nonfinite=jnp.logical_not(
                                  jnp.isfinite(loss)
                                  & jnp.isfinite(grad_norm)))
        new_state = TrainState(step=new_step, params=new_master,
                               opt_state=new_opt, scale_state=new_scale,
                               rng=jax.device_put(new_rng, cpu),
                               comm_state=state.comm_state)
        return new_state, metrics

    log_dist("ZeRO-Offload: optimizer step on host CPU — device holds "
             f"{compute_dtype.__name__ if hasattr(compute_dtype, '__name__') else compute_dtype} "
             "params + grads only; fp32 master + moments in host memory")
    return step_fn
