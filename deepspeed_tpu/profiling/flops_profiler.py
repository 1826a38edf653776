"""FLOPs profiler.

Analogue of the reference's ``FlopsProfiler``
(``profiling/flops_profiler/profiler.py:29``). The reference installs module
hooks and monkeypatches ``torch.nn.functional`` to count MACs at Python speed;
on TPU the compiler already knows: XLA's ``cost_analysis`` on the compiled
train step gives exact FLOPs/bytes for the whole program. At ``profile_step``
we time one step, pull the cost analysis, and report FLOPs, TFLOPS,
parameters, and achieved utilization.
"""

from __future__ import annotations

import time
from typing import Any, Optional

import jax
import numpy as np

from ..config.config import FlopsProfilerConfig
from ..utils.logging import log_dist, logger

# peak bf16 FLOP/s per chip, keyed by ``device_kind`` prefix (longest
# prefix first). Source: Google Cloud TPU documentation, system
# architecture pages of each generation.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5": 459e12,        # v5p
    "TPU v6 lite": 918e12,   # v6e
}


def device_peak_flops() -> float:
    """Peak bf16 FLOP/s of device 0. A device that is not in the table is
    an error, not a default: a ratio against a made-up peak is not a
    utilization."""
    kind = jax.devices()[0].device_kind
    for name, flops in PEAK_FLOPS.items():
        if kind.lower().startswith(name.lower()):
            return flops
    raise KeyError(
        f"no peak FLOP/s on record for device_kind {kind!r}; add it to "
        f"profiling.flops_profiler.PEAK_FLOPS with its source")


def utilization(flops_per_s: float) -> Optional[float]:
    """``flops_per_s`` over the device's peak, or None on a device with no
    peak on record (the CPU test mesh)."""
    try:
        return flops_per_s / device_peak_flops()
    except KeyError:
        return None


class FlopsProfiler:
    """Engine-integrated profiler: arms at ``profile_step``, reports at the
    end of that step. Also usable standalone via ``profile_fn``."""

    def __init__(self, engine, cfg: FlopsProfilerConfig):
        self.engine = engine
        self.cfg = cfg
        self._t0: Optional[float] = None
        self._armed_batch = None
        self.results: Optional[dict] = None

    # engine calls these around its train step ------------------------- #

    def maybe_start(self, step: int, batch: Any = None) -> None:
        if step + 1 == self.cfg.profile_step:
            self._t0 = time.perf_counter()
            self._armed_batch = batch

    def maybe_stop(self, step: int, metrics: Any = None) -> None:
        if self._t0 is None or step != self.cfg.profile_step:
            return
        jax.block_until_ready(metrics.loss if metrics is not None else None)
        latency = time.perf_counter() - self._t0
        self._t0 = None
        cost = self._cost_analysis()
        n_params = sum(int(np.prod(np.shape(p)))
                       for p in jax.tree_util.tree_leaves(self.engine.state.params))
        flops = cost.get("flops", 0.0) if cost else 0.0
        result = {
            "step": step,
            "latency_s": latency,
            "flops_per_step": flops,
            "tflops": flops / latency / 1e12 if latency > 0 else 0.0,
            "params": n_params,
            "utilization": utilization(flops / latency) if latency > 0 else None,
            "bytes_accessed": cost.get("bytes accessed", 0.0) if cost else 0.0,
        }
        self.results = result
        # publish the phase-labelled roofline gauges (telemetry/
        # registry.py): bench rows and monitor bridges read achieved
        # TFLOPS from the process-default registry — that contract
        # stands. With the training observatory attached the gauges
        # ADDITIONALLY land in its per-host registry, so ONE export
        # file carries tflops + attribution + goodput + anomaly
        # counters (dstpu_top --train renders it).
        from ..telemetry import record_phase_tflops
        record_phase_tflops("train", flops_per_step=flops,
                            latency_s=latency,
                            utilization=result["utilization"])
        obs = getattr(self.engine, "_train_obs", None)
        if obs is not None:
            record_phase_tflops("train", flops_per_step=flops,
                                latency_s=latency,
                                utilization=result["utilization"],
                                registry=obs.registry)
        self._print(result)
        if self.cfg.output_file:
            import json
            with open(self.cfg.output_file, "w") as f:
                json.dump(result, f, indent=2)

    # ------------------------------------------------------------------ #

    def _cost_analysis(self) -> Optional[dict]:
        try:
            step_fn = self.engine._train_step
            if self._armed_batch is None or not hasattr(step_fn, "lower"):
                return None
            lowered = step_fn.lower(self.engine.state, self._armed_batch)
            return dict(lowered.compile().cost_analysis() or {})
        except Exception as e:
            logger.warning(f"flops cost analysis unavailable: {e}")
            return None

    def _print(self, r: dict) -> None:
        log_dist(
            "-------------------------- Flops Profiler --------------------------\n"
            f"params:               {r['params'] / 1e6:.2f} M\n"
            f"fwd+bwd+step latency: {r['latency_s'] * 1000:.2f} ms\n"
            f"FLOPs per step:       {r['flops_per_step'] / 1e9:.2f} G\n"
            f"achieved:             {r['tflops']:.2f} TFLOPS ("
            + ("no peak on record for this device" if r["utilization"] is None
               else f"{r['utilization'] * 100:.1f}% of peak") + ")\n"
            f"bytes accessed:       {r['bytes_accessed'] / 1e9:.2f} GB\n"
            "---------------------------------------------------------------------")


def profile_fn(fn, *args) -> dict:
    """Standalone: jit, run once, return {flops, bytes, latency_s}."""
    jfn = jax.jit(fn)
    lowered = jfn.lower(*args)
    compiled = lowered.compile()
    t0 = time.perf_counter()
    out = jfn(*args)
    jax.block_until_ready(out)
    latency = time.perf_counter() - t0
    cost = dict(compiled.cost_analysis() or {})
    return {"flops": cost.get("flops", 0.0),
            "bytes_accessed": cost.get("bytes accessed", 0.0),
            "latency_s": latency}
