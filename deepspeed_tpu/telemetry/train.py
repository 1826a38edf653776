"""Train-side telemetry observer — step-time attribution, goodput,
straggler and anomaly instrumentation for the training engine.

The serve engine got the full observability stack in PRs 8/9/14; this
module gives ``runtime/engine.py`` the same discipline (ISSUE 15,
docs/observability.md "Training observatory"). One object the engine
owns (``engine._train_obs``; None when ``DSTPU_TRAIN_OBS=0`` or
``DSTPU_TELEMETRY=0`` — the kill switch restores the exact pre-observer
``train_batch`` path), recording ONLY at the train loop's existing
host-side boundaries:

  * **step-time attribution** — every committed ``train_batch``
    decomposes into ``data_wait`` (the between-step span: the caller's
    data fetch) / ``stage`` (validation, watchdog/profiler arming,
    offload swap-in) / ``dispatch`` (the compiled-step call) /
    ``device_execute`` (the exposed device wait: the engine waits for
    the PREVIOUS step's metrics with this step already queued behind
    it) / ``commit_apply`` (loss-scale + monitor + checkpoint
    bookkeeping) / ``host_gap`` (the CLOSURE of the sum: wall between
    step-exit boundaries minus every bracket), so the six components ≡
    measured wall by construction — the same closure discipline
    serve attribution keeps, gated by ``tests/unit/test_train_obs.py``. The
    four bracketed components arrive from the engine's own brackets
    (``telemetry/trace.py``, ``on_span``);
  * **goodput** — checkpoint saves, resumes and step progress land as
    stamped events in a :class:`~..resilience.ledger.RestartLedger`
    (``DSTPU_TRAIN_LEDGER``); at export boundaries the observer
    integrates them (merged with the elastic agent's supervisor ledger,
    ``DSTPU_RESTART_LEDGER``) through :mod:`.goodput` into the
    ``train_goodput_frac`` gauge;
  * **straggler evidence** — the per-host registry is named
    ``train@<host>`` (``DSTPU_TRAIN_OBS_HOST``, default the jax process
    index), so N hosts' exports roll up through the existing
    ``MetricsRegistry.merge`` source scheme and
    :func:`train_skew_report` names the laggard;
  * **anomaly sentinel** — the compiled step reduces a non-finite
    loss/grad-norm flag into ``StepMetrics.nonfinite`` IN-PROGRAM (no
    new callbacks — audited), the observer reads it ONE STEP LATE, when
    the value is ready without stalling the step just dispatched, plus
    keeps a windowed z-score on the loss series; either tripping
    increments a counter, records a ``train_anomaly`` flight event and
    auto-dumps the ring — a NaN'd or spiking run leaves forensics
    behind (the last step's entry is flushed at checkpoint saves).

Everything on the record path is pre-bound counter/histogram arithmetic
over host floats (dslint DSL001-registered). The observer never blocks
on the step just dispatched: the engine's ``train/device_wait`` bracket
waits for the step before it, so the device always has one step queued,
and ``tests/unit/test_train_obs.py`` holds the record path to 0 fresh
warm-path compiles and bit-identical state on or off.
"""

from __future__ import annotations

import math
import os
import time
from collections import deque
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

from .attribution import (TRAIN_ATTRIBUTION_COMPONENTS,
                          share_from_report, train_attribution_report)
from .flight_recorder import FlightRecorder, auto_dump, register_recorder
from .registry import MetricsRegistry, new_registry, telemetry_enabled


def train_obs_enabled() -> bool:
    """DSTPU_TRAIN_OBS (default on) gates the whole observer; 0 is the
    exact pre-observer train_batch path."""
    return os.environ.get("DSTPU_TRAIN_OBS", "1") \
        not in ("0", "false", "off")


def train_observer(engine) -> Optional["TrainObserver"]:
    """The engine's attach point: a TrainObserver, or None when either
    kill switch (DSTPU_TELEMETRY / DSTPU_TRAIN_OBS) is off — the engine
    then never calls into this module again."""
    if not telemetry_enabled() or not train_obs_enabled():
        return None
    return TrainObserver(engine)


def _host_id() -> str:
    hid = os.environ.get("DSTPU_TRAIN_OBS_HOST")
    if hid:
        return hid
    try:
        import jax
        return str(jax.process_index())
    except Exception:
        return "0"


class TrainObserver:
    def __init__(self, engine):
        self.engine = engine
        self.host = _host_id()
        self.registry: MetricsRegistry = new_registry(f"train@{self.host}")
        self.flight = FlightRecorder()
        register_recorder(self.flight)
        # env knobs read with LITERAL names (dslint DSL004/5 scan)
        self.export_path = os.environ.get("DSTPU_TELEMETRY_EXPORT") or None
        self.export_every = int(
            os.environ.get("DSTPU_TELEMETRY_EXPORT_EVERY", "50") or "50")
        self.window = int(
            os.environ.get("DSTPU_TRAIN_OBS_WINDOW", "32") or "32")
        self.zmax = float(
            os.environ.get("DSTPU_TRAIN_OBS_ZMAX", "6.0") or "6.0")
        self.stall_factor = float(
            os.environ.get("DSTPU_TRAIN_OBS_STALL_FACTOR", "10.0")
            or "10.0")
        self.progress_every = int(
            os.environ.get("DSTPU_TRAIN_OBS_PROGRESS_EVERY", "25")
            or "25")
        #: the newest step's (step, metrics), examined one step late
        self._pending_sentinel: Optional[Tuple[int, Any]] = None
        self._last_progress: Optional[Dict[str, Any]] = None
        # the observer's own event ledger (goodput source); in-memory
        # when DSTPU_TRAIN_LEDGER is unset. The agent's supervisor
        # ledger is a DIFFERENT file (two processes must not rewrite
        # one JSON document); goodput merges both at report time.
        from ..resilience.ledger import RestartLedger
        self.ledger_path = os.environ.get("DSTPU_TRAIN_LEDGER") or None
        self.agent_ledger_path = \
            os.environ.get("DSTPU_RESTART_LEDGER") or None
        self.ledger = RestartLedger(self.ledger_path)
        #: the prior incarnation's step high-water mark, read from the
        #: ledger BEFORE this run appends anything — the caught-up
        #: marker (goodput's replay_catchup boundary) compares against
        #: the highest step any earlier incarnation ATTEMPTED
        #: (train_progress) or durably saved (checkpoint_save)
        self.prior_max_step = max(
            (int(e.get("step") or 0) for e in self.ledger.events
             if e.get("event") in ("checkpoint_save", "train_progress",
                                   "train_caught_up", "train_resume")),
            default=0)
        self._caught_up = self.prior_max_step == 0
        self.ledger.record("train_start", t_start=time.time(),
                           host=self.host)

        # attribution state (pure perf_counter arithmetic)
        self._t_enter = 0.0
        self._acc: Dict[str, float] = {}
        self._last_exit: Optional[float] = None
        self._between_apply = 0.0    # checkpoint/eval work between steps
        self._between_this = 0.0     # its share of the CURRENT step
        self._wall_anchor: Optional[float] = None
        self._attrib_prev: Dict[str, float] = {}
        self._last_export_step = 0
        self._loss_window: deque = deque(maxlen=max(4, self.window))
        self._wall_window: deque = deque(maxlen=max(4, self.window))

        r = self.registry
        # hot handles bound once — the record paths below are pre-bound
        # attribute ops, no registry lookups per step
        self.c_steps = r.counter("train_steps")
        self.c_samples = r.counter("train_samples")
        self.c_skipped = r.counter("train_steps_skipped")
        self.c_nonfinite = r.counter("train_nonfinite_steps")
        self.c_anomalies = r.counter("train_anomalies")
        self.h_data = r.histogram("train_data_wait_s")
        self.h_stage = r.histogram("train_stage_s")
        self.h_dispatch = r.histogram("train_dispatch_s")
        self.h_device = r.histogram("train_device_execute_s")
        self.h_apply = r.histogram("train_commit_apply_s")
        self.h_gap = r.histogram("train_host_gap_s")
        self.h_wall = r.histogram("train_step_wall_s")
        self.g_loss = r.gauge("train_loss")
        self.g_gnorm = r.gauge("train_grad_norm")
        self.g_goodput = r.gauge("train_goodput_frac")

    # ------------------- step brackets (hot paths) -------------------- #
    # Registered DSL001 hot paths: pure perf_counter reads, attribute
    # stores and pre-bound histogram observes.

    def on_step_enter(self):
        """train_batch entry: close the between-step span. The gap since
        the previous step's exit minus any bracketed between-step work
        (checkpoint saves ride commit_apply) is ``data_wait`` — for a
        train loop, the data fetch."""
        now = time.perf_counter()
        self._t_enter = now
        if self._wall_anchor is None:
            # first observed step: the wall ledger opens here, so the
            # closure covers [first enter -> last exit] exactly
            self._wall_anchor = now
        acc = {"data_wait": 0.0, "stage": 0.0, "dispatch": 0.0,
               "device_execute": 0.0, "commit_apply": 0.0}
        if self._last_exit is not None:
            # the between-step bracket work (checkpoint save, resume
            # load) is INSIDE the measured gap — re-file it under
            # commit_apply. With no exit anchor the work happened
            # before this step's measured wall: dropping it keeps the
            # components ≤ wall (a resumed run's 2 s checkpoint load
            # must not blow the first step's closure)
            gap = now - self._last_exit
            between = min(self._between_apply, gap)
            acc["data_wait"] = max(0.0, gap - between)
            acc["commit_apply"] = between
            # remembered so the stall detector can exclude EXPECTED
            # bracketed work (a checkpoint save / validation sweep is
            # not a stall) from its wall comparison
            self._between_this = between
        else:
            self._between_this = 0.0
        self._between_apply = 0.0
        self._acc = acc

    def on_span(self, span, t0, t1):
        """One of ``train_batch``'s brackets closed (``telemetry/
        trace.py``): stage (validation, watchdog/profiler arming,
        offload swap-in), dispatch (the compiled-step call: an enqueue
        on TPU; the CPU harness executes synchronously, the caveat
        serve_attrib documents), device_execute (the wait for the
        previous step) or commit_apply. The duration goes to the flight
        ring and to this step's component, which ``on_step_exit``
        observes."""
        phase = span.spec.phase
        self.flight.record(phase, t0, t1, span.args.get("step"))
        if self._acc:
            self._acc[phase] += t1 - t0

    def previous_loss(self):
        """The loss of the step before the one just dispatched (None
        before the first): what ``train/device_wait`` blocks on, so the
        sentinel below reads a ready value."""
        prev = self._pending_sentinel
        return None if prev is None else prev[1].loss

    def on_step_abort(self):
        """A dead step must not leak its anchors into the next window:
        drop the open accumulators; the next enter re-anchors (the
        serve observer's self-healing rule). A deferred sentinel entry
        is dropped too — after a runtime error even prior steps'
        buffers may be poisoned, and the sentinel must never block on
        a dead computation."""
        self._acc = {}
        self._last_exit = None
        self._wall_anchor = None
        self._between_apply = 0.0
        self._pending_sentinel = None

    def flush(self):
        """Process the deferred sentinel entry — the final step of a run
        would otherwise end the process with its metrics stashed and
        never examined, leaving no forensics for a last-step NaN. Called
        at every checkpoint save (the normal and urgent-preemption
        end-of-run paths) and public for
        explicit teardown; blocks on the metrics if still in flight
        (teardown semantics, not the hot path)."""
        prev = self._pending_sentinel
        self._pending_sentinel = None
        if prev is not None:
            self._sentinel(*prev)

    def on_between(self, dt: float):
        """Bracketed between-step engine work (checkpoint save, eval):
        accounted into the NEXT step's commit_apply instead of reading
        as data_wait."""
        self._between_apply += dt

    # --------------------- step close (hot-ish) ----------------------- #

    def on_step_exit(self, step: int, metrics: Any, samples: int = 0):
        """Close the books on one committed step: the closure residual
        is host_gap, per-component histograms observe, and the sentinel
        examines the PREVIOUS step's metrics (ready: the device bracket
        blocked on them with this step queued behind) for the in-program
        non-finite flag and the windowed loss z-score, stashing this
        step's; then periodic sampling/export. The scalar readbacks are
        transfers of READY values, not device syncs.
        """
        now = time.perf_counter()
        acc = self._acc
        if not acc or self._wall_anchor is None:
            return
        wall = now - (self._last_exit if self._last_exit is not None
                      else self._t_enter)
        gap = wall - sum(acc.values())
        self._last_exit = now
        self._acc = {}

        self.c_steps.inc()
        if samples:
            self.c_samples.inc(samples)
        self.h_data.observe(acc["data_wait"])
        self.h_stage.observe(acc["stage"])
        self.h_dispatch.observe(acc["dispatch"])
        self.h_device.observe(acc["device_execute"])
        self.h_apply.observe(acc["commit_apply"])
        self.h_gap.observe(gap if gap > 0.0 else 0.0)
        self.h_wall.observe(wall)

        prev = self._pending_sentinel
        self._pending_sentinel = (step, metrics)
        if prev is not None:
            self._sentinel(*prev)
        self._finish_step(step, wall)

    def _sentinel(self, step: int, metrics: Any):
        """The anomaly sentinel's readbacks for ONE step's metrics —
        ready values when called (the device bracket waited for them
        one step later). Registered DSL001 hot path — scalar transfers
        + pre-bound counter arithmetic."""
        # dslint: allow(DSL001): scalar transfers of READY values — the
        # device_execute bracket proved them
        loss = float(metrics.loss)
        # dslint: allow(DSL001): ready-value transfer (see above)
        gnorm = float(metrics.grad_norm)
        if bool(metrics.skipped):
            # fp16 overflow skip: routine self-healing (the loss-scale
            # search), already counted and state-protected by the
            # overflow gate — NOT an anomaly, and its garbage inf/NaN
            # must reach neither the loss/grad-norm gauges (an exported
            # snapshot carrying Infinity breaks strict-JSON readers)
            # nor the z-score window
            self.c_skipped.inc()
            return
        # gauges only ever carry finite values (a NaN'd step is visible
        # through train_nonfinite_steps + the anomaly dump instead)
        if math.isfinite(loss):
            self.g_loss.set(loss)
        if math.isfinite(gnorm):
            self.g_gnorm.set(gnorm)
        nonfinite = metrics.nonfinite
        bad = bool(nonfinite) if nonfinite is not None else \
            not (math.isfinite(loss) and math.isfinite(gnorm))
        if bad:
            self.c_nonfinite.inc()
            self._trip("nonfinite", step, loss=loss, grad_norm=gnorm)
        else:
            win = self._loss_window
            if len(win) >= max(4, self.window // 4):
                mean = sum(win) / len(win)
                var = sum((v - mean) ** 2 for v in win) / len(win)
                std = math.sqrt(var)
                if std > 0.0 and abs(loss - mean) / std > self.zmax:
                    self._trip("loss_zscore", step, loss=loss,
                               mean=round(mean, 6),
                               z=round((loss - mean) / std, 2))
            win.append(loss)

    def _finish_step(self, step: int, wall: float):
        """The step close's tail — stall detection, progress/caught-up
        ledger markers, sampling + periodic export — shared by normal
        and overflow-skipped steps. Registered DSL001 hot path."""
        # ---- stall detection -> ledger interval (goodput's bucket).
        # Engine-bracketed between-step work (checkpoint save, eval
        # sweep) is EXPECTED time — excluded from both the comparison
        # and the rolling median so it can never read as a stall.
        stall_wall = max(0.0, wall - self._between_this)
        ww = self._wall_window
        if len(ww) >= max(4, self.window // 4) and self.stall_factor > 0:
            med = sorted(ww)[len(ww) // 2]
            if med > 0 and stall_wall > self.stall_factor * med:
                self.ledger.record(
                    "train_stall",
                    t_start=time.time() - stall_wall,
                    t_end=time.time(), step=step,
                    wall_s=round(stall_wall, 4),
                    median_s=round(med, 4))
        ww.append(stall_wall)

        # ---- progress + caught-up markers (goodput's catchup boundary)
        # >=: reaching the prior high-water mark means every previously
        # attempted step has been redone — the NEXT step is new work
        if not self._caught_up and step >= self.prior_max_step:
            self._caught_up = True
            self.ledger.record("train_caught_up", t_start=time.time(),
                               step=step)
        if self.progress_every > 0 and step % self.progress_every == 0:
            # this incarnation's progress events collapse to ONE (the
            # high-water mark only needs the latest) — replaced by
            # IDENTITY so interleaved checkpoint/stall events cannot
            # defeat the collapse and grow the ledger per N steps
            self._last_progress = self.ledger.replace(
                self._last_progress, "train_progress",
                t_start=time.time(), t_end=time.time(), step=step)

        self.registry.maybe_sample()
        if step - self._last_export_step >= self.export_every:
            self._last_export_step = step
            self.sync_gauges()
            if self.export_path:
                self.registry.export(self.export_path,
                                     extra={"engine": "train",
                                            "host": self.host})
            self.registry.tick(step)

    def _trip(self, kind: str, step: int, **args):
        """One anomaly: counter + trace-worthy flight event + ring
        auto-dump (no-op without DSTPU_FLIGHT_DIR) — the forensics a
        NaN'd run leaves behind. Non-finite floats are stringified
        first: json.dump would emit a literal ``NaN`` token that
        strict-JSON readers (Perfetto — the dump's target tool) refuse
        to load."""
        args = {k: (repr(v) if isinstance(v, float)
                    and not math.isfinite(v) else v)
                for k, v in args.items()}
        self.c_anomalies.inc()
        self.flight.event("train_anomaly", step=step, kind=kind, **args)
        auto_dump("train_anomaly")

    # --------------------- checkpoint / resume ------------------------ #

    def on_checkpoint(self, t0: float, t1: float, step: int,
                      save_dir: str):
        """One checkpoint save published: a stamped ledger interval (the
        goodput ledger's checkpoint_save bucket) + between-step
        accounting so the save rides commit_apply, not data_wait. Also
        flushes the deferred sentinel entry — a run that ends (or is
        preempted) right after its final save leaves complete
        forensics."""
        self.flush()
        self.ledger.record("checkpoint_save", t_start=t0, t_end=t1,
                           step=step, dir=save_dir)
        self.on_between(t1 - t0)
        self.flight.record("checkpoint_save",
                           time.perf_counter() - (t1 - t0),
                           time.perf_counter(), step=step)

    def on_resume(self, t0: float, t1: float, step: int, load_dir: str):
        """A checkpoint load: the goodput ledger's resume marker — with
        step > 0 it opens the replay_catchup span that
        ``train_caught_up`` closes."""
        self.ledger.record("train_resume", t_start=t0, t_end=t1,
                           step=step, dir=load_dir)
        self.on_between(t1 - t0)
        # resumed below the prior high-water mark: catch-up runs until
        # the counter gets back there; at (or past) it, nothing is owed
        self._caught_up = step >= self.prior_max_step
        if self._caught_up and step > 0:
            # a CLEAN resume (urgent checkpoint landed at the exact
            # high-water mark — the cooperative-preemption path) owes
            # no redo: record the marker NOW, or goodput_report would
            # see a step>0 resume with no caught marker and misfile
            # the whole healthy incarnation as replay_catchup
            self.ledger.record("train_caught_up", t_start=time.time(),
                               step=step)

    def reset_anchor(self):
        """Drop the between-step anchor (bench windows toggling the
        observer call this on re-attach so the off-window gap never
        reads as one giant data_wait)."""
        self._last_exit = None
        self._wall_anchor = None
        self._between_apply = 0.0

    # --------------------- reports / exports -------------------------- #

    def sync_gauges(self):
        """Export-boundary work (never the hot path): mirror component
        histogram sums into the labelled
        ``train_attrib_seconds_total{component=...}`` counter
        (delta-sync keeps it monotone) and refresh the goodput gauge
        from the merged ledgers."""
        r = self.registry
        for comp, hist in (("data_wait", self.h_data),
                           ("stage", self.h_stage),
                           ("dispatch", self.h_dispatch),
                           ("device_execute", self.h_device),
                           ("commit_apply", self.h_apply),
                           ("host_gap", self.h_gap)):
            cur = hist.sum
            prev = self._attrib_prev.get(comp, 0.0)
            if cur > prev:
                r.counter("train_attrib_seconds_total",
                          component=comp).inc(cur - prev)
                self._attrib_prev[comp] = cur
        rep = self.goodput_report()
        if rep["train_goodput_frac"] is not None:
            self.g_goodput.set(rep["train_goodput_frac"])

    def goodput_report(self) -> Dict[str, Any]:
        """The wall-clock partition over this run's merged event
        timeline: the observer's own ledger (in memory + file) plus the
        elastic agent's supervisor ledger when present."""
        from .goodput import goodput_report, load_ledger_events
        events = list(self.ledger.events)
        if self.agent_ledger_path:
            events = load_ledger_events([self.agent_ledger_path]) + events
        return goodput_report(events, t_end=time.time())

    def attribution_report(self,
                           prev: Optional[Mapping[str, Any]] = None
                           ) -> Dict[str, Any]:
        return train_attribution_report(self.registry.snapshot(), prev)


# ---------------------------------------------------------------------- #
# report-time helpers (never the hot path)
# ---------------------------------------------------------------------- #


def train_comm_share(engine, batch: Any, program: str = "train_step",
                     rng: Any = None) -> Optional[Dict[str, Any]]:
    """The audited-collective share of the compiled train (or eval)
    step, straight from the program auditor's trip-weighted jaxpr
    counts — collective hops (the grad-accum ``lax.scan`` body
    trip-weighted, ring decompositions included) vs trip-weighted
    ``dot_general``s, with 0 host callbacks and 0 device timers. The
    op-level comm-vs-compute split of ``device_execute`` the autotuning
    item needs on the training side. Report-time only (lowers the
    program)."""
    from ..analysis.program_audit import audit_fn
    try:
        if program == "train_step":
            rep = audit_fn(engine._train_step, engine.state, batch,
                           name=program)
        elif program == "eval_step":
            if engine._eval_step is None:
                return None
            import jax
            rng = rng if rng is not None else jax.random.PRNGKey(0)
            rep = audit_fn(engine._eval_step, engine.state.params, batch,
                           rng, engine.state.step, name=program)
        else:
            raise ValueError(f"unknown program {program!r} "
                             f"(train_step | eval_step)")
    except (AttributeError, NotImplementedError, TypeError):
        return None
    return share_from_report(rep, program)


def train_skew_report(per_source: Sequence[Tuple[str, Mapping[str, Any]]]
                      ) -> Dict[str, Any]:
    """The straggler view over per-host train snapshots ([(source,
    snapshot), ...] — the shape ``dstpu_top`` loads): per-host step-time
    and data-wait medians, the max/median step-time skew, and the
    laggard host. Sources are the stable ``train@<host>`` registry
    names the merge scheme keys on."""
    hosts: Dict[str, Dict[str, Any]] = {}
    for src, snap in per_source:
        h = snap.get("histograms", {})
        wall = h.get("train_step_wall_s", {})
        data = h.get("train_data_wait_s", {})
        hosts[src] = {
            "steps": int(wall.get("count", 0)),
            "step_wall_p50_s": wall.get("p50"),
            "step_wall_max_s": wall.get("max"),
            "data_wait_p50_s": data.get("p50"),
            "data_wait_frac": (data.get("sum", 0.0) / wall["sum"])
            if wall.get("sum") else None,
        }
    p50s = [(src, row["step_wall_p50_s"]) for src, row in hosts.items()
            if row["step_wall_p50_s"] is not None]
    out: Dict[str, Any] = {"hosts": hosts, "laggard": None,
                           "step_time_skew": None,
                           "max_step_p50_s": None,
                           "median_step_p50_s": None}
    if p50s:
        vals = sorted(v for _, v in p50s)
        # LOWER median: with an even host count the upper median IS
        # (or neighbors) the laggard, which would read a 3x-slower
        # host on a 2-host fleet as skew 1.0
        med = vals[(len(vals) - 1) // 2]
        laggard, worst = max(p50s, key=lambda kv: kv[1])
        out.update(laggard=laggard,
                   max_step_p50_s=worst, median_step_p50_s=med,
                   step_time_skew=(worst / med) if med > 0 else None)
    return out
