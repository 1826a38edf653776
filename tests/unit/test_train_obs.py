"""Training observatory (ISSUE 15) — step-time attribution closure,
observer on/off bit-identical state, goodput-ledger arithmetic (synthetic
+ a real agent-supervised kill), straggler merge, anomaly sentinel."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.models.gpt2 import GPT2Config, make_model
from deepspeed_tpu.telemetry.attribution import (
    TRAIN_ATTRIBUTION_COMPONENTS, TRAIN_STEP_WALL_COMPONENTS,
    component_totals, train_attribution_report)
from deepspeed_tpu.telemetry.goodput import (goodput_report,
                                             load_ledger_events)
from deepspeed_tpu.telemetry.train import train_comm_share, train_skew_report


def _engine(extra=None, obs=True, monkeypatch=None):
    if monkeypatch is not None:
        monkeypatch.setenv("DSTPU_TRAIN_OBS", "1" if obs else "0")
    cfg_model = GPT2Config.tiny(dtype=jnp.float32)
    model, init_fn, loss_fn = make_model(cfg_model)
    params = init_fn(jax.random.PRNGKey(0), batch_size=2, seq_len=17)
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "steps_per_print": 100000,
    }
    if extra:
        config.update(extra)
    engine, _, _, _ = dstpu.initialize(loss_fn=loss_fn, params=params,
                                       config=config)
    return engine


def _batches(n, eng, seed=0):
    rng = np.random.RandomState(seed)
    B = eng.config.train_batch_size
    return [{"tokens": jnp.asarray(rng.randint(0, 512, size=(B, 18)),
                                   jnp.int32)} for _ in range(n)]


class TestAttributionClosure:
    def test_closure_vs_external_wall(self):
        """Six components must sum to an EXTERNALLY measured loop wall
        (not just the observer's own wall histogram)."""
        eng = _engine()
        obs = eng._train_obs
        assert obs is not None
        bs = _batches(10, eng)
        for b in bs[:3]:
            eng.train_batch(b)           # warm
        obs.reset_anchor()
        snap0 = obs.registry.snapshot()
        t0 = time.perf_counter()
        for i, b in enumerate(bs[3:]):
            if i:
                time.sleep(0.005)        # a little "data fetch"
            loss = eng.train_batch(b)
        # the books close at the last step's exit; that step may still
        # be running (train_batch waits for the step BEFORE the one it
        # dispatched), so the external clock stops here too
        wall = time.perf_counter() - t0
        jax.block_until_ready(loss)
        comps = component_totals(obs.registry.snapshot(), snap0,
                                 components=TRAIN_ATTRIBUTION_COMPONENTS)
        csum = sum(comps[c] for c in TRAIN_STEP_WALL_COMPONENTS)
        assert abs(wall - csum) / wall < 0.10, (wall, comps)
        # internal closure (host_gap measured as the residual) is exact
        rep = train_attribution_report(obs.registry.snapshot(), snap0)
        assert rep["closure_err_frac"] is not None
        assert rep["closure_err_frac"] < 0.01

    def test_data_stall_localized(self):
        """A synthetic data-loader stall between train_batch calls must
        land in data_wait — the largest delta share."""
        eng = _engine()
        obs = eng._train_obs
        bs = _batches(14, eng, seed=1)
        for b in bs[:3]:
            eng.train_batch(b)
        obs.reset_anchor()
        snap0 = obs.registry.snapshot()
        for b in bs[3:8]:
            eng.train_batch(b)
        snap1 = obs.registry.snapshot()
        for b in bs[8:13]:
            time.sleep(0.02)
            eng.train_batch(b)
        snap2 = obs.registry.snapshot()
        base = component_totals(snap1, snap0,
                                components=TRAIN_ATTRIBUTION_COMPONENTS)
        inj = component_totals(snap2, snap1,
                               components=TRAIN_ATTRIBUTION_COMPONENTS)
        deltas = {c: inj[c] - base[c] for c in TRAIN_STEP_WALL_COMPONENTS}
        # the stall lets the step in flight finish, and the CPU backend
        # then runs the next one inline in its dispatch: a step's compute
        # moves from device_execute (the wait for it, one call later) to
        # dispatch, and their sum stays
        deltas["dispatch"] += deltas.pop("device_execute")
        assert max(deltas, key=deltas.get) == "data_wait", deltas
        # 4 of the 5 sleeps are between observed steps (the first lands
        # before the window's first enter re-anchor)
        assert deltas["data_wait"] >= 0.5 * 4 * 0.02, deltas

    def test_warm_no_fresh_compiles_with_observer(self):
        from deepspeed_tpu.analysis import RecompileTripwire
        eng = _engine()
        bs = _batches(6, eng, seed=2)
        for b in bs[:3]:
            eng.train_batch(b)
        tw = RecompileTripwire()
        with tw:
            for b in bs[3:]:
                eng.train_batch(b)
        assert tw.fresh_compiles == 0


class TestObserverParity:
    def test_on_off_bit_identical_state(self, monkeypatch):
        """Observer on vs off: the loss stream AND the final train state
        must be bit-identical over >= 3 steps (the observer records, it
        never computes)."""
        e_on = _engine(monkeypatch=monkeypatch, obs=True)
        e_off = _engine(monkeypatch=monkeypatch, obs=False)
        assert e_on._train_obs is not None
        assert e_off._train_obs is None
        bs = _batches(4, e_on, seed=3)
        l_on = [float(e_on.train_batch(b)) for b in bs]
        l_off = [float(e_off.train_batch(b)) for b in bs]
        assert l_on == l_off
        for a, b in zip(jax.tree_util.tree_leaves(e_on.state.params),
                        jax.tree_util.tree_leaves(e_off.state.params)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_kill_switch_exact_path(self, monkeypatch):
        monkeypatch.setenv("DSTPU_TRAIN_OBS", "0")
        eng = _engine()
        assert eng._train_obs is None
        monkeypatch.setenv("DSTPU_TRAIN_OBS", "1")
        monkeypatch.setenv("DSTPU_TELEMETRY", "0")
        eng2 = _engine()
        assert eng2._train_obs is None

    def test_audited_train_step(self):
        """The compiled step with the observatory armed: 0 host
        callbacks, the in-program nonfinite flag present, and the
        trip-weighted comm-op share derivable."""
        from deepspeed_tpu.analysis.program_audit import audit_fn
        eng = _engine()
        b = _batches(1, eng, seed=4)[0]
        rep = audit_fn(eng._train_step, eng.state, b, name="train_step")
        assert rep.host_callbacks == 0
        loss = eng.train_batch(b)
        m = eng._last_metrics
        assert m.nonfinite is not None
        assert not bool(m.nonfinite)
        share = train_comm_share(eng, b)
        assert share is not None
        assert share["host_callbacks"] == 0
        assert share["dot_generals_per_step"] > 0
        assert share["comm_op_share"] == 0.0    # dp=1: no collectives
        jax.block_until_ready(loss)


class TestGoodputLedger:
    def test_synthetic_buckets_sum_exactly(self):
        evs = [
            {"event": "launch", "time": 0.0, "t_start": 0.0},
            {"event": "checkpoint_save", "time": 11.0, "t_start": 10.0,
             "t_end": 11.0, "step": 5},
            {"event": "train_progress", "time": 14.0, "t_start": 14.0,
             "t_end": 14.0, "step": 7},
            {"event": "restart", "time": 15.0, "t_start": 0.0,
             "t_end": 15.0, "membership_change": False},
            {"event": "launch", "time": 17.0, "t_start": 17.0},
            {"event": "train_resume", "time": 17.5, "t_start": 17.0,
             "t_end": 17.5, "step": 5},
            {"event": "train_stall", "time": 19.5, "t_start": 19.0,
             "t_end": 19.5, "step": 6},
            {"event": "train_caught_up", "time": 21.0, "t_start": 21.0,
             "step": 7},
            {"event": "success", "time": 30.0, "t_start": 17.0,
             "t_end": 30.0},
        ]
        rep = goodput_report(evs)
        b = rep["buckets"]
        assert abs(sum(b.values()) - rep["total_wall_s"]) < 1e-9
        assert rep["total_wall_s"] == 30.0
        # downtime 15->17 (2) + discarded tail 11->15 (4)
        assert abs(b["restart_lost"] - 6.0) < 1e-9
        assert abs(b["checkpoint_save"] - 1.0) < 1e-9
        assert abs(b["stall"] - 0.5) < 1e-9
        # 17 -> 21 catch-up, minus the 0.5 s stall inside it
        assert abs(b["replay_catchup"] - 3.5) < 1e-9
        assert abs(b["productive"] - 19.0) < 1e-9
        assert abs(rep["train_goodput_frac"] - 19.0 / 30.0) < 1e-9

    def test_zero_timestamp_markers_not_dropped(self):
        """Regression (review catch): a legitimate t_start of exactly
        0.0 (relative-timestamp ledgers) must not read as missing — a
        caught-up marker at t=0 otherwise misfiles the whole
        incarnation as replay_catchup."""
        evs = [
            {"event": "launch", "time": 0.0, "t_start": 0.0},
            {"event": "train_resume", "time": 0.0, "t_start": 0.0,
             "t_end": 0.0, "step": 5},
            {"event": "train_caught_up", "time": 0.0, "t_start": 0.0,
             "step": 5},
            {"event": "success", "time": 10.0, "t_start": 0.0,
             "t_end": 10.0},
        ]
        rep = goodput_report(evs)
        assert rep["buckets"]["replay_catchup"] == 0.0
        assert rep["buckets"]["productive"] == 10.0

    def test_legacy_ledger_readable(self):
        """Pre-stamp events (time + runtime_s only) must reconstruct."""
        evs = [{"event": "launch", "time": 0.0},
               {"event": "success", "time": 20.0, "runtime_s": 20.0}]
        rep = goodput_report(evs)
        assert rep["total_wall_s"] == 20.0
        assert rep["buckets"]["productive"] == 20.0

    def test_observer_ledger_events(self, tmp_path, monkeypatch):
        """Engine checkpoint/resume land as stamped ledger events; a
        second incarnation reads the high-water mark and records the
        caught-up marker after redoing the lost steps."""
        ledger = tmp_path / "train_ledger.json"
        monkeypatch.setenv("DSTPU_TRAIN_LEDGER", str(ledger))
        monkeypatch.setenv("DSTPU_TRAIN_OBS_PROGRESS_EVERY", "1")
        save = str(tmp_path / "ckpt")
        eng = _engine()
        bs = _batches(4, eng, seed=5)
        eng.train_batch(bs[0])
        eng.save_checkpoint(save)
        eng.train_batch(bs[1])
        eng.train_batch(bs[2])       # attempted past the checkpoint
        events = json.load(open(ledger))["events"]
        kinds = [e["event"] for e in events]
        assert "train_start" in kinds and "checkpoint_save" in kinds
        ck = next(e for e in events if e["event"] == "checkpoint_save")
        assert ck["t_end"] >= ck["t_start"] and ck["step"] == 1
        assert any(e["event"] == "train_progress" and e["step"] == 3
                   for e in events)
        # "incarnation 2": fresh engine, resume from step 1, redo 2..3
        eng2 = _engine()
        assert eng2._train_obs.prior_max_step == 3
        eng2.load_checkpoint(save)
        assert eng2._train_obs._caught_up is False
        eng2.train_batch(bs[1])
        eng2.train_batch(bs[2])
        eng2.train_batch(bs[3])
        events = json.load(open(ledger))["events"]
        resumed = [e for e in events if e["event"] == "train_resume"]
        caught = [e for e in events if e["event"] == "train_caught_up"]
        assert resumed and resumed[-1]["step"] == 1
        assert caught and caught[-1]["step"] == 3
        rep = goodput_report(load_ledger_events([str(ledger)]),
                             t_end=time.time())
        assert abs(sum(rep["buckets"].values())
                   - rep["total_wall_s"]) < 1e-6
        assert rep["buckets"]["replay_catchup"] > 0
        assert rep["buckets"]["checkpoint_save"] > 0

    def test_clean_resume_is_productive_not_catchup(self, tmp_path,
                                                    monkeypatch):
        """Regression (review catch): a resume AT the high-water mark
        (the cooperative-preemption path — urgent checkpoint landed)
        owes no redo; the caught-up marker must be recorded at resume
        or the whole healthy incarnation misfiles as replay_catchup."""
        ledger = tmp_path / "ledger.json"
        monkeypatch.setenv("DSTPU_TRAIN_LEDGER", str(ledger))
        monkeypatch.setenv("DSTPU_TRAIN_OBS_PROGRESS_EVERY", "1")
        save = str(tmp_path / "ckpt")
        eng = _engine()
        bs = _batches(5, eng, seed=21)
        eng.train_batch(bs[0])
        eng.train_batch(bs[1])
        eng.save_checkpoint(save)        # durable AT the high-water mark
        # clean restart: resume exactly where the last run stopped
        eng2 = _engine()
        eng2.load_checkpoint(save)
        assert eng2._train_obs._caught_up is True
        for b in bs[2:]:
            eng2.train_batch(b)
        events = json.load(open(ledger))["events"]
        caught = [e for e in events if e["event"] == "train_caught_up"]
        assert caught and caught[-1]["step"] == 2
        rep = goodput_report(load_ledger_events([str(ledger)]),
                             t_end=time.time())
        b = rep["buckets"]
        assert b["productive"] > b["replay_catchup"], b

    def test_real_injected_kill_matches_drill_arithmetic(self, tmp_path):
        """A REAL kill (os._exit inside a checkpoint save) under the
        REAL elastic agent: the ledger-integrated goodput must match
        the drill's independent wall-stamp arithmetic within 5%, with
        buckets summing to wall exactly."""
        from deepspeed_tpu.resilience.faultdrill import drill_train_goodput
        res = drill_train_goodput(str(tmp_path), verbose=False)
        assert res["fault_fired"], res
        assert res["buckets_sum_exact"], res
        assert res["frac_matches_drill"], res
        assert res["goodput"]["buckets"]["restart_lost"] > 0
        assert res["goodput"]["buckets"]["replay_catchup"] > 0
        assert res["recovered"], res


class TestStragglerSkew:
    def _host_snap(self, name, step_ms):
        from deepspeed_tpu.telemetry.registry import MetricsRegistry
        r = MetricsRegistry(name)
        for _ in range(20):
            r.histogram("train_step_wall_s").observe(step_ms / 1e3)
            r.histogram("train_data_wait_s").observe(0.2 * step_ms / 1e3)
        r.counter("train_steps").inc(20)
        return r

    def test_skew_report_names_laggard(self):
        regs = [self._host_snap("train@0", 10.0),
                self._host_snap("train@1", 10.5),
                self._host_snap("train@2", 31.0)]
        per_source = [(r.name, r.snapshot()) for r in regs]
        rep = train_skew_report(per_source)
        assert rep["laggard"] == "train@2"
        assert rep["step_time_skew"] == pytest.approx(31.0 / 10.5,
                                                      rel=0.12)
        assert set(rep["hosts"]) == {"train@0", "train@1", "train@2"}
        # review catch: even host counts use the LOWER median — a
        # 3x-slower host on a 2-host fleet must not read as skew 1.0
        two = [self._host_snap("train@0", 10.0),
               self._host_snap("train@1", 30.0)]
        rep2 = train_skew_report([(r.name, r.snapshot()) for r in two])
        assert rep2["laggard"] == "train@1"
        assert rep2["step_time_skew"] == pytest.approx(3.0, rel=0.12)

    def test_merge_keeps_stable_source_labels(self):
        """Per-host counters roll up through the documented merge
        scheme; gauges keep train@<host> identity."""
        from deepspeed_tpu.telemetry.registry import (MetricsRegistry,
                                                      merge_snapshots)
        regs = [self._host_snap("train@0", 10.0),
                self._host_snap("train@1", 20.0)]
        for r in regs:
            r.gauge("train_loss").set(4.2)
        merged = MetricsRegistry.merge(regs,
                                       sources=[r.name for r in regs])
        snap = merged.snapshot()
        assert snap["counters"]["train_steps"] == 40
        assert 'train_loss{source="train@0"}' in snap["gauges"]
        assert 'train_loss{source="train@1"}' in snap["gauges"]
        # snapshot-level merge agrees (the cross-process file path)
        snap2 = merge_snapshots([r.snapshot() for r in regs],
                                sources=[r.name for r in regs])
        assert snap2["counters"]["train_steps"] == 40


class TestAnomalySentinel:
    def _poison_engine(self, monkeypatch, tmp_path, window="16"):
        monkeypatch.setenv("DSTPU_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("DSTPU_TRAIN_OBS_WINDOW", window)

        def loss_fn(params, batch, rng):
            base = jnp.sum(params["w"] ** 2)
            return base + jnp.mean(batch["x"])

        engine, _, _, _ = dstpu.initialize(
            loss_fn=loss_fn, params={"w": jnp.ones((4,), jnp.float32)},
            config={"train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "AdamW",
                                  "params": {"lr": 1e-3}},
                    "steps_per_print": 100000})
        return engine

    @staticmethod
    def _x(eng, val):
        B = eng.config.train_batch_size
        return {"x": jnp.full((B, 4), val, jnp.float32)}

    def test_nan_batch_trips_and_dumps_flight_trace(self, monkeypatch,
                                                    tmp_path):
        eng = self._poison_engine(monkeypatch, tmp_path)
        obs = eng._train_obs
        eng.train_batch(self._x(eng, 0.1))
        assert obs.c_nonfinite.value == 0
        eng.train_batch(self._x(eng, float("nan")))     # the planted batch
        # the sentinel reads a step's metrics one step late (it never
        # blocks on the step just dispatched): the next step trips it
        assert obs.c_nonfinite.value == 0
        eng.train_batch(self._x(eng, 0.1))
        assert obs.c_nonfinite.value == 1
        assert obs.c_anomalies.value >= 1
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight_train_anomaly")]
        assert dumps, os.listdir(tmp_path)
        # auto_dump writes one file per LIVE recorder (earlier engines
        # in this process included) — the anomaly event must be in the
        # poison engine's, and every dump must be loadable Chrome JSON
        anomaly_events = []
        for f in dumps:
            raw = open(tmp_path / f).read()
            # review catch: strict JSON — the raw NaN/Inf loss must be
            # stringified or Perfetto refuses the forensic artifact
            assert "NaN" not in raw and "Infinity" not in raw
            trace = json.loads(raw)
            assert isinstance(trace["traceEvents"], list)
            anomaly_events += [e for e in trace["traceEvents"]
                               if e["name"] == "train_anomaly"]
        assert anomaly_events
        assert anomaly_events[0]["args"]["kind"] == "nonfinite"

    def test_loss_spike_trips_zscore(self, monkeypatch, tmp_path):
        eng = self._poison_engine(monkeypatch, tmp_path)
        obs = eng._train_obs
        rng = np.random.RandomState(0)
        for _ in range(8):
            eng.train_batch(self._x(eng, float(rng.normal(0.0, 0.01))))
        assert obs.c_anomalies.value == 0
        eng.train_batch(self._x(eng, 1000.0))           # the spike
        assert obs.c_anomalies.value == 0               # one step late
        obs.flush()                  # what a checkpoint save does
        assert obs.c_anomalies.value == 1
        assert obs.c_nonfinite.value == 0


class TestExportAndTop:
    def test_single_export_file_carries_everything(self, monkeypatch,
                                                   tmp_path, capsys):
        """ONE export file: attribution components + tflops{phase=train}
        + goodput gauge + anomaly counters; dstpu_top --train renders
        it, and two host files render the straggler table."""
        export = tmp_path / "train_export.json"
        monkeypatch.setenv("DSTPU_TELEMETRY_EXPORT", str(export))
        monkeypatch.setenv("DSTPU_TELEMETRY_EXPORT_EVERY", "2")
        # a fresh process-default registry (an earlier test file may
        # have left a NullRegistry installed)
        from deepspeed_tpu.telemetry import set_registry
        set_registry(None)
        eng = _engine(extra={"flops_profiler": {"enabled": True,
                                                "profile_step": 2}})
        for b in _batches(5, eng, seed=7):
            eng.train_batch(b)
        assert export.exists()
        snap = json.load(open(export))
        assert snap["engine"] == "train"
        assert "train_step_wall_s" in snap["histograms"]
        assert 'achieved_tflops{phase="train"}' in snap["gauges"]
        # review catch #3: the process-default registry KEEPS the
        # roofline gauges — pre-existing consumers must not strand
        from deepspeed_tpu.telemetry import get_registry
        dflt = get_registry().snapshot()["gauges"]
        assert 'achieved_tflops{phase="train"}' in dflt
        assert "train_goodput_frac" in snap["gauges"]
        assert "train_anomalies" in snap["counters"]
        assert any(k.startswith("train_attrib_seconds_total")
                   for k in snap["counters"])
        from deepspeed_tpu.telemetry import top
        assert top.main(["--train", str(export)]) == 0
        out = capsys.readouterr().out
        assert "step time" in out and "goodput" in out
        # straggler table over two per-host exports
        snap2 = json.loads(json.dumps(snap))
        snap2["registry"] = "train@other"
        p2 = tmp_path / "h2.json"
        json.dump(snap2, open(p2, "w"))
        assert top.main(["--train", str(export), str(p2)]) == 0
        out = capsys.readouterr().out
        assert "straggler" in out and "train@other" in out
        # review catch: the fleet-merged view must still resolve the
        # source-labelled gauges (loss/goodput came up 0/- before)
        assert "no ledger events" not in out
        assert "loss         0.0000" not in out


class TestReviewHardening:
    def test_pre_window_between_work_never_breaks_closure(self):
        """Regression (review catch): a resume load BEFORE the first
        observed step must not inflate that step's components past its
        wall — un-anchored between-step work is dropped, not filed."""
        eng = _engine()
        obs = eng._train_obs
        obs.on_between(2.0)          # a "2 s checkpoint load" pre-step
        snap0 = obs.registry.snapshot()
        t0 = time.perf_counter()
        loss = eng.train_batch(_batches(1, eng, seed=11)[0])
        jax.block_until_ready(loss)
        wall = time.perf_counter() - t0
        comps = component_totals(obs.registry.snapshot(), snap0,
                                 components=TRAIN_ATTRIBUTION_COMPONENTS)
        csum = sum(comps[c] for c in TRAIN_STEP_WALL_COMPONENTS)
        assert csum <= wall * 1.2, (wall, comps)

    def test_fp16_overflow_skip_is_not_an_anomaly(self):
        """Regression (review catch): routine fp16 loss-scale-search
        skips count train_steps_skipped but never trip the sentinel."""
        eng = _engine(extra={"fp16": {"enabled": True,
                                      "initial_scale_power": 32,
                                      "loss_scale_window": 1000}})
        obs = eng._train_obs
        for b in _batches(3, eng, seed=12):
            eng.train_batch(b)
        assert obs.c_skipped.value >= 1          # scale 2^32 overflows
        assert obs.c_anomalies.value == 0
        assert obs.c_nonfinite.value == 0
        # review catch #2: the skipped steps' inf/NaN must never reach
        # the exported gauges (strict-JSON readers would choke)
        import math
        assert math.isfinite(obs.g_loss.value)
        assert math.isfinite(obs.g_gnorm.value)

    def test_commit_apply_error_aborts_observed_step(self, monkeypatch):
        """Regression (review catch): a failure AFTER the device
        bracket (deferred XLA error at the blocking timer/log reads,
        monitor IO) must also drop the anchors."""
        eng = _engine()
        obs = eng._train_obs
        bs = _batches(2, eng, seed=14)
        eng.train_batch(bs[0])
        assert obs._last_exit is not None

        def boom(metrics):
            raise RuntimeError("monitor IO failed")

        monkeypatch.setattr(eng, "_maybe_log", boom)
        with pytest.raises(RuntimeError, match="monitor IO"):
            eng.train_batch(bs[1])
        assert obs._last_exit is None            # anchors dropped

    def test_eval_batch_files_under_commit_apply(self):
        """Regression (review catch): engine-driven eval between steps
        is bracketed work — it must ride commit_apply, not read as
        data_wait (nor ever count toward a stall)."""
        eng = _engine()
        obs = eng._train_obs
        bs = _batches(3, eng, seed=15)
        eng.train_batch(bs[0])
        eng.eval_batch(bs[1])
        assert obs._between_apply > 0.0
        snap0 = obs.registry.snapshot()
        eng.train_batch(bs[2])
        comps = component_totals(obs.registry.snapshot(), snap0,
                                 components=TRAIN_ATTRIBUTION_COMPONENTS)
        assert comps["commit_apply"] >= comps["data_wait"], comps

    def test_sync0_final_step_sentinel_flushed_at_checkpoint(
            self, monkeypatch, tmp_path):
        """Regression (review catch): the LAST step's stashed metrics
        flush at the end-of-run checkpoint save, so a final-step NaN
        still leaves forensics."""

        def loss_fn(params, batch, rng):
            return jnp.sum(params["w"] ** 2) + jnp.mean(batch["x"])

        eng, _, _, _ = dstpu.initialize(
            loss_fn=loss_fn, params={"w": jnp.ones((4,), jnp.float32)},
            config={"train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "AdamW",
                                  "params": {"lr": 1e-3}},
                    "steps_per_print": 100000})
        obs = eng._train_obs
        B = eng.config.train_batch_size
        eng.train_batch({"x": jnp.full((B, 4), 0.1, jnp.float32)})
        eng.train_batch({"x": jnp.full((B, 4), float("nan"),
                                       jnp.float32)})   # final step
        assert obs.c_nonfinite.value == 0        # still stashed
        eng.save_checkpoint(str(tmp_path / "ckpt"))
        assert obs.c_nonfinite.value == 1        # flushed at the save

    def test_overlap_mode_defers_sentinel_one_step(self, monkeypatch,
                                                   tmp_path):
        """Regression (review catch): no per-step block on the step just
        dispatched (TPU dispatch-ahead overlap survives); the sentinel
        lags exactly one step but still trips."""
        monkeypatch.setenv("DSTPU_FLIGHT_DIR", str(tmp_path))

        def loss_fn(params, batch, rng):
            return jnp.sum(params["w"] ** 2) + jnp.mean(batch["x"])

        eng, _, _, _ = dstpu.initialize(
            loss_fn=loss_fn, params={"w": jnp.ones((4,), jnp.float32)},
            config={"train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "AdamW",
                                  "params": {"lr": 1e-3}},
                    "steps_per_print": 100000})
        obs = eng._train_obs
        B = eng.config.train_batch_size
        eng.train_batch({"x": jnp.full((B, 4), 0.1, jnp.float32)})
        eng.train_batch({"x": jnp.full((B, 4), float("nan"),
                                       jnp.float32)})
        assert obs.c_nonfinite.value == 0        # one step behind
        eng.train_batch({"x": jnp.full((B, 4), 0.1, jnp.float32)})
        assert obs.c_nonfinite.value == 1        # the lagged trip
        # attribution still closes (wall is wall)
        assert obs.h_wall.count == 3

    def test_train_batch_never_blocks_on_the_step_it_dispatched(
            self, monkeypatch):
        """The device bracket waits for the step BEFORE the one just
        dispatched, so the device always has a step queued: no blocking
        call inside train_batch may receive that call's own loss."""
        import deepspeed_tpu.runtime.engine as engine_mod
        eng = _engine()
        waited = []
        real = jax.block_until_ready

        def spy(x):
            waited.append(x)
            return real(x)

        monkeypatch.setattr(engine_mod.jax, "block_until_ready", spy)
        losses = []
        for b in _batches(4, eng, seed=16):
            before = len(waited)
            loss = eng.train_batch(b)
            assert all(w is not loss for w in waited[before:])
            losses.append(loss)
        # it did wait, each time for the step before: steps 2..4
        assert len(waited) == 3
        assert all(w is prev for w, prev in zip(waited, losses))
        assert eng.step_stats["steps"] == 4

    def test_pre_dispatch_error_aborts_observed_step(self):
        """Regression (review catch): a validation error between
        on_step_enter and dispatch must drop the anchors — the caller's
        recovery time must not read as the next step's data_wait."""
        eng = _engine()
        obs = eng._train_obs
        bs = _batches(3, eng, seed=13)
        eng.train_batch(bs[0])
        with pytest.raises(Exception, match="train_batch expects"):
            eng.train_batch({"tokens": jnp.zeros((1, 18), jnp.int32)})
        assert obs._last_exit is None            # anchors dropped
        time.sleep(0.05)                         # "recovery" time
        obs_snap0 = obs.registry.snapshot()
        eng.train_batch(bs[1])
        comps = component_totals(obs.registry.snapshot(), obs_snap0,
                                 components=TRAIN_ATTRIBUTION_COMPONENTS)
        assert comps["data_wait"] < 0.04, comps
