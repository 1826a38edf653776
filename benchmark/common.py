"""What every job kind shares: the run's context, the phase clock, spans on
the profiler's clock, the compile counters and the traced window."""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, Iterator, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_manifest() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(manifest: Dict[str, Any], workload: str):
    """(manifest entry, cell file, configuration file, traffic file) of
    one workload, each found by the name ``BENCHMARK.json`` gives it."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        fail(f"no workload {workload!r} in BENCHMARK.json "
             f"(have {sorted(cells)})", 2)
    entry = cells[workload]
    cell = load_json("cells", entry["name"] + ".json")
    cell["chips"] = entry["chips"]
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == entry["config"])
    with open(os.path.join(ROOT, config_entry["file"])) as f:
        config = json.load(f)
    return entry, cell, config, load_json("traffic",
                                          entry["traffic"] + ".json")


def say(tag: str, obj: Any) -> None:
    """An earlier line of the output (the last line is the result)."""
    print(f"[benchmark] {tag}: {json.dumps(obj, default=str)}", flush=True)


class CompileClock:
    """Counts and times what JAX traces, lowers and compiles, from JAX's
    own monitoring events. ``programs`` counts backend compiles (a hit in
    the persistent cache still passes through here); ``trace_lower_s`` is
    the host work no cache removes."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
              "/jax/core/compile/backend_compile_duration": "compile_s"}

    def __init__(self):
        import jax.monitoring
        self.s = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0}
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        key = self.EVENTS.get(event)
        if key is not None:
            self.s[key] += duration
            if key == "compile_s":
                self.programs += 1

    def snapshot(self) -> Dict[str, float]:
        return dict(self.s, programs=self.programs,
                    trace_lower_s=self.s["trace_s"] + self.s["lower_s"])


class Ctx:
    """One run of one cell."""

    def __init__(self, *, cell_name: str, cell: Dict[str, Any],
                 config: Dict[str, Any], traffic: Dict[str, Any], seed: int,
                 seconds: float, trace: bool, rehearse: bool,
                 t_process: float):
        self.cell_name, self.cell = cell_name, cell
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = seed, seconds
        self.trace, self.rehearse = trace, rehearse
        self.t_process = t_process
        self.phase_s: Dict[str, float] = {}
        self._t_phase = t_process
        self.setup_s: Optional[float] = None
        self.compiles: Optional[CompileClock] = None
        self.setup_compiles: Dict[str, float] = {}
        self.trace_dir = os.path.join(ROOT, ".bench_trace", cell_name)
        self._tracing = False
        self.memory_peak_bytes = 0

    # ---- parameters: the cell file, overridden by its rehearse block ---- #

    def param(self, key: str, default: Any = None) -> Any:
        if self.rehearse and key in self.cell.get("rehearse", {}):
            return self.cell["rehearse"][key]
        return self.cell.get(key, default)

    def model_dims(self) -> Dict[str, Any]:
        cfg = dict(self.config)
        if self.rehearse:
            cfg.update(cfg.get("rehearse", {}))
        return cfg

    @property
    def devices(self) -> List[Any]:
        import jax
        return jax.devices()[:int(self.cell["chips"])]

    # ------------------------------ clocks ------------------------------ #

    def mark(self, phase: str) -> None:
        now = time.perf_counter()
        self.phase_s[phase] = self.phase_s.get(phase, 0.0) \
            + now - self._t_phase
        self._t_phase = now

    def window_opens(self) -> None:
        """The first measured step or request starts now: set-up ends
        (and the compile clock's reading here is set-up's own: what a
        serve cell's reference compiles after the window is not in it)."""
        self.mark("setup_tail")
        self.setup_s = time.perf_counter() - self.t_process
        if self.compiles is not None:
            self.setup_compiles = self.compiles.snapshot()

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        """A benchmark span around a call into a layer, on the trace's
        clock while a trace is being taken and free otherwise."""
        if not self._tracing:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation("bench:" + name):
            yield

    @contextlib.contextmanager
    def traced_window(self) -> Iterator[None]:
        """Profile what runs inside, under the span ``window`` (a
        rehearsal walks it with no profiler: the CPU has no device
        plane to read)."""
        if self.rehearse:
            yield
            return
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self._tracing = True
        try:
            with jax.profiler.TraceAnnotation("bench:window"):
                yield
        finally:
            self._tracing = False
            jax.profiler.stop_trace()

    def read_memory_peak(self) -> None:
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = max(self.memory_peak_bytes, peak)


def counters_delta(now: Dict[str, Any], then: Dict[str, Any]
                   ) -> Dict[str, float]:
    """What an engine's own totals (``pipeline_stats``, ``step_stats``)
    grew by between two copies: EVERY number of the dict, so a counter a
    later PR adds reaches a new reader file with no edit here."""
    return {k: now[k] - then.get(k, 0) for k in now
            if isinstance(now[k], (int, float))}


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of a non-empty sample."""
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


def fail(msg: str, code: int = 3) -> "NoReturn":  # noqa: F821
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)
