"""Telemetry — metrics registry, phase flight recorder, trace hooks.

The observability substrate (docs/observability.md): counters, gauges
and log-bucketed streaming histograms with Prometheus/JSON export
(:mod:`.registry`), a bounded ring of plan/dispatch/commit/drain/replay
spans dumped as Chrome-trace JSON on watchdog fire / fault-drill crash /
drain (:mod:`.flight_recorder`), per-request SLO instrumentation for the
v2 serve engine (:mod:`.serve`), a MonitorMaster bridge
(:mod:`.monitor_bridge`), the engines' brackets on the profiler's clock
(:mod:`.trace`) and the ``bin/dstpu_top`` renderer (:mod:`.top`).

Kill switch: ``DSTPU_TELEMETRY=0`` — every registry call becomes a
shared no-op and the serve engine skips instrumentation entirely.
"""

from .attribution import (ATTRIBUTION_COMPONENTS,
                          TRAIN_ATTRIBUTION_COMPONENTS,
                          attribution_report, comm_share,
                          component_totals, train_attribution_report)
from .flight_recorder import (FlightRecorder, auto_dump, flight_dir,
                              merge_chrome_traces, register_recorder,
                              request_tracks)
from .goodput import (goodput_from_ledgers, goodput_report,
                      load_ledger_events)
from .loadgen import (LoadResult, PoissonArrivals, Request,
                      TraceArrivals, UniformArrivals, WorkloadMix,
                      build_requests, run_open_loop, sweep_capacity)
from .monitor_bridge import MonitorBridge, attach_monitor
from .registry import (COMM_CANONICAL_KINDS, REGISTERED_METRICS, Counter,
                       Gauge, Histogram, MetricsRegistry, NullRegistry,
                       comm_counter, get_registry, merge_snapshots,
                       new_registry, record_phase_tflops, set_registry,
                       telemetry_enabled)
from .serve import ServeObserver, serve_observer
from .trace import SPANS, SpanSet
from .train import (TrainObserver, train_comm_share, train_observer,
                    train_skew_report)

__all__ = [
    "ATTRIBUTION_COMPONENTS", "COMM_CANONICAL_KINDS", "Counter",
    "FlightRecorder", "Gauge", "Histogram", "LoadResult",
    "MetricsRegistry", "MonitorBridge", "NullRegistry",
    "PoissonArrivals", "REGISTERED_METRICS", "Request", "SPANS",
    "ServeObserver", "SpanSet", "TRAIN_ATTRIBUTION_COMPONENTS",
    "TraceArrivals", "TrainObserver", "UniformArrivals", "WorkloadMix",
    "attach_monitor", "attribution_report", "auto_dump",
    "build_requests", "comm_counter", "comm_share", "component_totals",
    "flight_dir", "get_registry", "goodput_from_ledgers",
    "goodput_report", "load_ledger_events", "merge_chrome_traces",
    "merge_snapshots", "new_registry",
    "record_phase_tflops", "register_recorder", "request_tracks",
    "run_open_loop", "serve_observer", "set_registry", "sweep_capacity",
    "telemetry_enabled", "train_attribution_report",
    "train_comm_share", "train_observer", "train_skew_report",
]
