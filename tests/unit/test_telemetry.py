"""Telemetry tests (ISSUE 9): metrics registry, SLO instrumentation,
flight recorder, monitor bridge, and the no-op kill switch.

The layer's contract: percentiles within the sketch's alpha bound,
per-request SLO invariants (TTFT >= queue wait, monotone token stamps)
on a REAL pipelined depth-2 serve run, audited serve programs unchanged
(0 host callbacks, 0 warm fresh compiles) with telemetry on, and a
crash leaving a loadable Chrome-trace flight dump. Subprocess drill
variants ride the slow tier; everything here reuses one tiny GPT-2."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry.flight_recorder import FlightRecorder, auto_dump
from deepspeed_tpu.telemetry.registry import (Histogram, MetricsRegistry,
                                              NullRegistry,
                                              REGISTERED_METRICS)

# ------------------------------------------------------------------ #
# histogram quantile accuracy (satellite: vs numpy on random +
# adversarial distributions)
# ------------------------------------------------------------------ #


class TestHistogram:
    ALPHA = 0.05

    def _check(self, data, qs=(50, 90, 99), tol=None):
        tol = tol if tol is not None else self.ALPHA + 0.01
        h = Histogram(alpha=self.ALPHA)
        for v in data:
            h.observe(float(v))
        for q in qs:
            est = h.quantile(q / 100.0)
            # the sketch is nearest-rank: compare against the exact
            # order statistic, not numpy's interpolated default
            ref = float(np.percentile(data, q, method="lower"))
            assert est is not None
            assert abs(est - ref) <= tol * max(abs(ref), 1e-12), \
                f"p{q}: est {est} vs ref {ref}"

    def test_uniform_vs_numpy(self):
        self._check(np.random.RandomState(0).uniform(1e-3, 10.0, 20000))

    def test_lognormal_vs_numpy(self):
        self._check(np.random.RandomState(1).lognormal(0.0, 2.0, 20000))

    def test_adversarial_bimodal(self):
        # 60/40 split: every checked quantile sits deep inside a mode
        # (a 50/50 split's p50 is genuinely ambiguous between modes)
        low = np.abs(np.random.RandomState(2).normal(1e-3, 1e-4, 12000))
        high = np.random.RandomState(3).normal(100.0, 1.0, 8000)
        self._check(np.concatenate([low, high]))

    def test_single_bucket_constant(self):
        h = Histogram(alpha=self.ALPHA)
        for _ in range(500):
            h.observe(3.7)
        for q in (0.0, 0.5, 0.99, 1.0):
            assert h.quantile(q) == pytest.approx(3.7)
        s = h.summary()
        assert s["count"] == 500 and s["min"] == s["max"] == 3.7

    def test_small_count_upper_quantile_hits_top(self):
        # nearest-rank: p99 of {2 small, 1 huge} must be the huge one
        h = Histogram()
        h.observe(0.002)
        h.observe(0.002)
        h.observe(0.628)
        assert h.quantile(0.99) == pytest.approx(0.628, rel=0.06)

    def test_zero_and_negative_values(self):
        h = Histogram()
        for v in (-1.0, 0.0, 0.0, 5.0):
            h.observe(v)
        assert h.count == 4
        assert h.quantile(0.25) <= 0.0
        assert h.quantile(1.0) == pytest.approx(5.0, rel=0.06)

    def test_weighted_observe(self):
        h = Histogram()
        h.observe(1.0, n=99)
        h.observe(100.0, n=1)
        assert h.count == 100
        assert h.quantile(0.5) == pytest.approx(1.0, rel=0.06)
        assert h.quantile(1.0) == pytest.approx(100.0, rel=0.06)

    def test_empty(self):
        h = Histogram()
        assert h.quantile(0.5) is None
        # alpha rides even the empty summary: an idle replica's sketch
        # must rebuild on its configured lattice (merge_snapshots)
        assert h.summary() == {"count": 0, "sum": 0.0, "alpha": 0.05}


# ------------------------------------------------------------------ #
# registry
# ------------------------------------------------------------------ #


class TestRegistry:
    def test_counters_gauges_snapshot(self):
        r = MetricsRegistry("t")
        r.counter("serve_steps").inc()
        r.counter("serve_steps").inc(2)
        r.gauge("kv_pool_blocks_free").set(7)
        r.histogram("serve_ttft_s").observe(0.5)
        snap = r.snapshot()
        assert snap["counters"]["serve_steps"] == 3.0
        assert snap["gauges"]["kv_pool_blocks_free"] == 7
        assert snap["histograms"]["serve_ttft_s"]["count"] == 1

    def test_handles_are_cached(self):
        r = MetricsRegistry("t")
        assert r.counter("a") is r.counter("a")
        with pytest.raises(ValueError):
            r.gauge("a")            # kind conflict

    def test_labels(self):
        r = MetricsRegistry("t")
        r.gauge("achieved_tflops", phase="train").set(50.0)
        r.gauge("achieved_tflops", phase="serve_decode").set(2.0)
        snap = r.snapshot()["gauges"]
        assert snap['achieved_tflops{phase="train"}'] == 50.0
        assert snap['achieved_tflops{phase="serve_decode"}'] == 2.0

    def test_prometheus_text(self):
        r = MetricsRegistry("t")
        r.counter("serve_steps").inc(4)
        h = r.histogram("serve_tpot_s")
        for v in (0.01, 0.02, 0.03):
            h.observe(v)
        text = r.to_prometheus()
        assert "# TYPE serve_steps counter" in text
        assert "serve_steps 4" in text
        assert "# TYPE serve_tpot_s summary" in text
        assert 'serve_tpot_s{quantile="0.5"}' in text
        assert "serve_tpot_s_count 3" in text

    def test_export_atomic_json(self, tmp_path):
        r = MetricsRegistry("t")
        r.counter("serve_tokens_committed").inc(9)
        path = str(tmp_path / "snap.json")
        r.export(path, extra={"engine": "serve"})
        blob = json.loads(open(path).read())
        assert blob["engine"] == "serve"
        assert blob["counters"]["serve_tokens_committed"] == 9.0
        assert not [f for f in os.listdir(tmp_path) if ".tmp" in f]

    def test_null_registry_noop(self, monkeypatch):
        monkeypatch.setenv("DSTPU_TELEMETRY", "0")
        r = telemetry.new_registry("t")
        assert isinstance(r, NullRegistry) and not r.enabled
        r.counter("x").inc()
        r.gauge("y").set(1)
        r.histogram("z").observe(2.0)
        assert r.snapshot() == {"counters": {}, "gauges": {},
                                "histograms": {}}

    def test_record_phase_tflops(self):
        r = MetricsRegistry("t")
        tf = telemetry.record_phase_tflops("train", flops_per_step=2e12,
                                           latency_s=0.5,
                                           utilization=0.4, registry=r)
        assert tf == pytest.approx(4.0)
        g = r.snapshot()["gauges"]
        assert g['achieved_tflops{phase="train"}'] == pytest.approx(4.0)
        assert g['mxu_utilization{phase="train"}'] == pytest.approx(0.4)

    def test_comm_counter_canonical_kinds(self, monkeypatch):
        monkeypatch.delenv("DSTPU_TELEMETRY", raising=False)
        r = MetricsRegistry("default")
        telemetry.set_registry(r)
        try:
            telemetry.comm_counter("inference_all_reduce")
            telemetry.comm_counter("ppermute")
            telemetry.comm_counter("ppermute")
            snap = r.snapshot()["counters"]
            assert snap["comm_traced_all_reduce"] == 1.0
            assert snap["comm_traced_ppermute"] == 2.0
        finally:
            telemetry.set_registry(None)

    def test_registered_metrics_table_is_str_dict(self):
        assert REGISTERED_METRICS
        for k, v in REGISTERED_METRICS.items():
            assert isinstance(k, str) and isinstance(v, str)


# ------------------------------------------------------------------ #
# flight recorder
# ------------------------------------------------------------------ #


class TestFlightRecorder:
    def test_ring_wraparound(self):
        rec = FlightRecorder(capacity=8)
        for i in range(20):
            rec.record(f"span{i}", float(i), float(i) + 0.5, step=i)
        assert len(rec) == 8
        names = [s[0] for s in rec.spans]
        assert names == [f"span{i}" for i in range(12, 20)]

    def test_phase_transitions_close_spans(self):
        rec = FlightRecorder(capacity=16)
        rec.phase("plan", step=1)
        rec.phase("dispatch", step=1)
        rec.phase("commit", step=1)
        rec.phase("idle")
        names = [s[0] for s in rec.spans]
        assert names == ["plan", "dispatch", "commit"]
        for _, t0, t1, _, _ in rec.spans:
            assert t1 >= t0

    def test_chrome_trace_format(self, tmp_path):
        rec = FlightRecorder(capacity=4)
        with rec.span("drain", step=7, sequences=3):
            pass
        path = str(tmp_path / "trace.json")
        rec.dump(path, reason="unit")
        trace = json.loads(open(path).read())
        (ev,) = trace["traceEvents"]
        assert ev["ph"] == "X" and ev["name"] == "drain"
        assert ev["dur"] >= 0 and ev["ts"] >= 0
        assert ev["args"]["sequences"] == 3 and ev["args"]["step"] == 7
        assert trace["otherData"]["reason"] == "unit"

    def test_auto_dump_gated_on_flight_dir(self, tmp_path, monkeypatch):
        rec = FlightRecorder(capacity=4)
        telemetry.register_recorder(rec)
        rec.record("plan", 0.0, 1.0)
        monkeypatch.delenv("DSTPU_FLIGHT_DIR", raising=False)
        assert auto_dump("nowhere") == []
        monkeypatch.setenv("DSTPU_FLIGHT_DIR", str(tmp_path))
        paths = auto_dump("unit_reason")
        mine = [p for p in paths if "unit_reason" in p]
        assert mine and all(os.path.exists(p) for p in mine)


# ------------------------------------------------------------------ #
# serve-engine integration (tiny GPT-2, pipelined depth 2)
# ------------------------------------------------------------------ #

N_TOK = 8


def _gpt2():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
    mcfg = GPT2Config(vocab_size=96, max_seq_len=128, num_layers=2,
                      num_heads=2, hidden_size=32, dtype=jnp.float32)
    params = GPT2(mcfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    return mcfg, params


def _engine(**kw):
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)
    mcfg, params = _gpt2()
    base = dict(max_seqs=4, chunk_size=8, block_size=4, num_blocks=64,
                max_blocks_per_seq=16, dtype="float32",
                attention_impl="dense", decode_loop_steps=0,
                serve_pipeline_depth=2, prefix_cache=True)
    base.update(kw)
    return InferenceEngineV2(mcfg, params, RaggedInferenceConfig(**base))


def _workload():
    rng = np.random.default_rng(55)
    shared = rng.integers(1, 96, 10).tolist()
    return [shared + rng.integers(1, 96, 5).tolist() for _ in range(3)]


def _serve(eng, prompts, n=N_TOK):
    toks = {}
    for u, p in enumerate(prompts):
        r = eng.put([u], [list(p)], _greedy=True)
        if u in r:
            toks[u] = [int(r[u])]
    while True:
        live = [u for u in toks if len(toks[u]) < n
                and u in eng.state.sequences]
        if not live:
            return toks
        # exact budgets: the engine must commit exactly what the test
        # accounts for (registry counters are compared against toks)
        k = min(2, n - min(len(toks[u]) for u in live))
        outs = eng.decode_pipelined(live, [toks[u][-1] for u in live], k)
        for u in live:
            toks[u].extend(outs[u][:n - len(toks[u])])


class TestServeTelemetry:
    @pytest.fixture(scope="class")
    def served(self):
        """One pipelined depth-2 run, sequences still live (per-seq
        stamps inspectable), then flushed."""
        eng = _engine()
        prompts = _workload()
        toks = _serve(eng, prompts)
        seqs = {u: eng.state.sequences[u] for u in toks}
        report = eng.slo_report()
        for u in list(toks):
            eng.flush(u)
        return eng, toks, seqs, report

    def test_per_request_slo_invariants(self, served):
        _, toks, seqs, _ = served
        for u, seq in seqs.items():
            # admission -> first schedule -> first token, in order
            assert seq.admitted_at is not None
            assert seq.first_sched_at is not None
            assert seq.first_token_at is not None
            assert seq.admitted_at <= seq.first_sched_at
            assert seq.first_sched_at <= seq.first_token_at
            ttft = seq.first_token_at - seq.admitted_at
            queue_wait = seq.first_sched_at - seq.admitted_at
            assert ttft >= queue_wait >= 0.0
            # monotone committed-token stamps
            assert seq.last_token_at >= seq.first_token_at

    def test_registry_counts_match_run(self, served):
        eng, toks, _, report = served
        n_req = len(toks)
        total = sum(len(t) for t in toks.values())
        c = eng.metrics.snapshot()["counters"]
        assert c["serve_requests_admitted"] == n_req
        assert c["serve_tokens_committed"] == total
        h = eng.metrics.snapshot()["histograms"]
        assert h["serve_ttft_s"]["count"] == n_req
        assert h["serve_queue_wait_s"]["count"] == n_req
        # every token after a request's first is a TPOT observation
        assert h["serve_tpot_s"]["count"] == total - n_req
        assert report["ttft_s"]["p50"] > 0
        assert report["goodput_frac"] is None  # nothing terminal yet

    def test_completion_counters_and_goodput(self, served):
        eng, toks, _, _ = served
        rep = eng.slo_report()
        assert rep["requests"]["completed"] == len(toks)
        assert rep["goodput_frac"] == 1.0

    def test_flight_recorder_saw_all_phases(self, served):
        eng, _, _, _ = served
        names = {s[0] for s in eng.flight.spans}
        assert {"plan", "dispatch", "commit"} <= names

    def test_prefix_and_pool_metrics(self, served):
        eng, _, _, _ = served
        snap = eng.metrics.snapshot()
        assert snap["counters"]["prefix_matched_tokens"] > 0
        assert snap["counters"]["prefix_prefill_tokens"] > 0
        assert snap["gauges"]["kv_pool_blocks_total"] == 64
        assert snap["gauges"]["kv_pool_bytes_total"] > 0

    def test_engine_metric_names_are_registered(self, served):
        eng, _, _, _ = served
        for name in eng.metrics.metric_names():
            assert name in REGISTERED_METRICS, \
                f"engine emitted unregistered metric {name}"

    def test_noop_when_disabled(self, monkeypatch):
        monkeypatch.setenv("DSTPU_TELEMETRY", "0")
        eng = _engine()
        prompts = _workload()
        toks = _serve(eng, prompts)
        assert eng._obs is None
        assert eng.metrics is None and eng.flight is None
        assert eng.slo_report() == {}
        seq = eng.state.sequences[0]
        # the observer's own stamps stay unset; the first-token stamp
        # is the engine's since ISSUE 55 (tests/unit/test_spans.py)
        assert seq.admitted_at is None and seq.last_token_at is None
        assert seq.first_token_at is not None
        assert all(len(t) == N_TOK for t in toks.values())

    def test_disabled_stream_identical_to_enabled(self, served,
                                                  monkeypatch):
        _, toks_on, _, _ = served
        monkeypatch.setenv("DSTPU_TELEMETRY", "0")
        eng = _engine()
        toks_off = _serve(eng, _workload())
        assert toks_off == toks_on

    def test_abort_and_rejection_counters(self):
        eng = _engine()
        prompts = _workload()
        r = eng.put([0], [prompts[0]], _greedy=True)
        assert 0 in r
        eng.abort(0)
        eng.flush(0)
        c = eng.metrics.snapshot()["counters"]
        assert c["serve_requests_aborted"] == 1
        assert c["serve_requests_completed"] == 0

    def test_double_abort_counts_once(self):
        """A retried cancel on a not-yet-flushed FINISHED sequence is
        idempotent: one abort outcome per request (the goodput
        denominator must not inflate)."""
        from deepspeed_tpu.inference.v2 import SequenceStatus
        eng = _engine()
        r = eng.put([0], [_workload()[0]], _greedy=True)
        assert 0 in r
        # the deferred-flush window: abort() has marked the sequence
        # FINISHED but its flush still waits on an in-flight commit —
        # a serving layer's retried cancel must be a counted-once no-op
        eng.state.sequences[0].status = SequenceStatus.FINISHED
        assert eng.abort(0) is True
        assert eng.abort(0) is True
        c = eng.metrics.snapshot()["counters"]
        assert c["serve_requests_aborted"] == 0
        eng.flush(0)

    def test_drain_attaches_telemetry_and_counts_drained(self):
        eng = _engine()
        prompts = _workload()
        _serve(eng, prompts, n=2)
        manifest = eng.drain()
        assert manifest["telemetry"]["requests"]["drained"] == \
            len(manifest["sequences"])
        assert manifest["telemetry"]["tokens_committed"] > 0

    def test_export_published_at_boundary(self, tmp_path, monkeypatch):
        path = str(tmp_path / "export.json")
        monkeypatch.setenv("DSTPU_TELEMETRY_EXPORT", path)
        monkeypatch.setenv("DSTPU_TELEMETRY_EXPORT_EVERY", "2")
        eng = _engine()
        _serve(eng, _workload())
        blob = json.loads(open(path).read())
        assert blob["engine"] == "serve"
        assert blob["counters"]["serve_tokens_committed"] > 0
        # the dstpu_top renderer accepts the snapshot as-is
        from deepspeed_tpu.telemetry.top import render
        out = render(blob)
        assert "goodput" in out and "ttft" in out

    def test_crash_leaves_flight_dump(self, tmp_path, monkeypatch):
        """Satellite: crash-dump presence on a serve fault (in-process
        variant of the drill's hard-exit path — the injector dumps for
        every mode before firing)."""
        from deepspeed_tpu.resilience.fault_injection import (
            FaultInjector, InjectedFault, set_fault_injector)
        monkeypatch.setenv("DSTPU_FLIGHT_DIR", str(tmp_path))
        eng = _engine()
        set_fault_injector(FaultInjector(site="mid_commit", mode="raise"))
        try:
            with pytest.raises(InjectedFault):
                _serve(eng, _workload())
        finally:
            set_fault_injector(None)
        # EVERY live recorder of the process dumps (auto_dump), one file
        # a recorder: an engine an earlier test of this worker left alive
        # leaves a file too, and ``os.listdir`` names them in no order.
        # The faulted engine's own file is the one named after its recorder
        mine = f"_{id(eng.flight) & 0xffff:04x}.json"
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight_fault_mid_commit")
                 and f.endswith(mine)]
        assert len(dumps) == 1
        trace = json.loads(open(tmp_path / dumps[0]).read())
        assert any(ev["name"] in ("plan", "dispatch", "commit")
                   for ev in trace["traceEvents"])


class TestAuditedPrograms:
    def test_telemetry_on_keeps_programs_callback_free(self):
        """Acceptance: the audited serve programs' collective/callback
        budgets are unchanged with telemetry on — instrumentation never
        reaches traced code — and the warm pipelined path stays
        compile-free."""
        from deepspeed_tpu.analysis import (RecompileTripwire,
                                            audit_serve_programs)
        eng = _engine(prefix_cache=False)
        rep = audit_serve_programs(eng, programs=("step_greedy",))[
            "step_greedy"]
        assert rep.host_callbacks == 0
        assert rep.collectives == {}       # tp1: zero collectives
        prompts = _workload()
        toks = _serve(eng, prompts)        # warm every program
        tw = RecompileTripwire()
        with tw:
            outs = eng.decode_pipelined(
                list(toks), [toks[u][-1] for u in toks], 2)
        assert all(len(v) == 2 for v in outs.values())
        assert tw.fresh_compiles == 0


# ------------------------------------------------------------------ #
# monitor bridge + CSV handle fix
# ------------------------------------------------------------------ #


class TestMonitorBridge:
    class FakeMaster:
        def __init__(self):
            self.calls = []

        def write_events(self, events):
            self.calls.append(list(events))

    def test_interval_and_event_shape(self):
        r = MetricsRegistry("t")
        r.counter("serve_steps").inc(5)
        r.histogram("serve_ttft_s").observe(0.2)
        master = self.FakeMaster()
        telemetry.attach_monitor(master, interval_steps=10, registry=r)
        r.tick(1)                  # first tick always emits
        r.tick(5)                  # < interval: no emit
        r.tick(11)                 # >= interval: emits
        assert len(master.calls) == 2
        tags = {t for t, _, _ in master.calls[0]}
        assert "telemetry/serve_steps" in tags
        assert "telemetry/serve_ttft_s/p50" in tags
        assert "telemetry/serve_ttft_s/count" in tags
        for _, value, step in master.calls[0]:
            assert isinstance(value, float) and step == 1

    def test_serve_observer_ticks_bridges(self, monkeypatch):
        monkeypatch.setenv("DSTPU_TELEMETRY_EXPORT_EVERY", "2")
        eng = _engine()
        master = self.FakeMaster()
        telemetry.attach_monitor(master, interval_steps=1,
                                 registry=eng.metrics)
        _serve(eng, _workload())
        assert master.calls       # commit boundaries drove the bridge

    def test_csv_monitor_keeps_handles(self, tmp_path):
        from deepspeed_tpu.monitor.monitor import CSVMonitor
        cfg = SimpleNamespace(output_path=str(tmp_path), job_name="job")
        mon = CSVMonitor(cfg)
        mon.write_events([("a/b", 1.0, 1), ("c", 2.0, 1)])
        f_first = mon._files["a/b"]
        mon.write_events([("a/b", 3.0, 2)])
        assert mon._files["a/b"] is f_first       # handle reused
        mon.close()
        rows = open(tmp_path / "job" / "a_b.csv").read().splitlines()
        assert rows == ["step,a/b", "1,1.0", "2,3.0"]
        assert mon._files == {}


# ------------------------------------------------------------------ #
# dslint DSL006 (metric-catalog drift) — synthetic trees; the repo-
# clean direction is enforced by tests/unit/test_dslint.py
# ------------------------------------------------------------------ #

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class TestDSL006:
    def _root(self, tmp_path, metrics, doc_rows):
        import textwrap
        reg = tmp_path / "deepspeed_tpu" / "telemetry" / "registry.py"
        reg.parent.mkdir(parents=True)
        body = "".join(f'    "{m}": "doc",\n' for m in metrics)
        reg.write_text("REGISTERED_METRICS = {\n" + body + "}\n")
        docs = tmp_path / "docs"
        docs.mkdir()
        (docs / "CONFIG.md").write_text(
            "# cfg\n\n## Environment knobs (`DSTPU_*`)\n\n"
            "| knob | default | read at |\n|---|---|---|\n")
        (docs / "observability.md").write_text(textwrap.dedent("""\
            # obs

            ## Metric catalog

            | metric | type | meaning |
            |---|---|---|
            """) + "".join(f"| `{m}` | counter | x |\n" for m in doc_rows))
        return str(tmp_path)

    def _dslint(self):
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import dslint
        return dslint

    def test_two_way_drift_flagged(self, tmp_path):
        dslint = self._dslint()
        root = self._root(tmp_path, ["m_registered", "m_both"],
                          ["m_both", "m_doc_only"])
        findings = dslint.lint([], repo_root=root)
        found = {(f.rule, "m_registered" in f.message or
                  "m_doc_only" in f.message) for f in findings}
        assert ("DSL006", True) in found
        msgs = "\n".join(f.message for f in findings
                         if f.rule == "DSL006")
        assert "m_registered" in msgs and "m_doc_only" in msgs
        assert "m_both" not in msgs

    def test_clean_when_synced(self, tmp_path):
        dslint = self._dslint()
        root = self._root(tmp_path, ["m_a", "m_b"], ["m_a", "m_b"])
        assert [f for f in dslint.lint([], repo_root=root)
                if f.rule == "DSL006"] == []

    def test_missing_doc_flagged(self, tmp_path):
        dslint = self._dslint()
        root = self._root(tmp_path, ["m_a"], ["m_a"])
        os.remove(os.path.join(root, "docs", "observability.md"))
        findings = dslint.lint([], repo_root=root)
        assert any(f.rule == "DSL006" and "missing" in f.message
                   for f in findings)

    def test_repo_catalog_in_sync(self):
        """Both directions on the REAL repo — the tier-1 enforcement
        point for the metric catalog (mirrors the knob-table test)."""
        dslint = self._dslint()
        table = {n for n, _ in dslint.registered_metrics(
            os.path.join(REPO, dslint.METRICS_TABLE_FILE))}
        with open(os.path.join(REPO, dslint.OBSERVABILITY_DOC)) as f:
            doc = {n for n, _ in dslint.documented_metrics(f.read())}
        assert table == doc, (
            f"metric catalog drifted (undocumented: "
            f"{sorted(table - doc)}, stale: {sorted(doc - table)})")
        assert table == set(REGISTERED_METRICS)


# ------------------------------------------------------------------ #
# subprocess drill (slow tier): hard-crash flight dump + recovery
# ------------------------------------------------------------------ #


@pytest.mark.slow
class TestServeDrillFlightDump:
    def test_drill_asserts_flight_dump(self, tmp_path):
        from deepspeed_tpu.resilience.faultdrill import drill_serve_site
        res = drill_serve_site("mid_commit", str(tmp_path),
                               verbose=False)
        assert res["fault_fired"]
        assert res["flight_dump"] is True
        assert res["recovered"], res


# ------------------------------------------------------------------ #
# fleet rollup (ISSUE 10): bucket-wise EXACT histogram merge,
# registry merge, snapshot merge
# ------------------------------------------------------------------ #


class TestHistogramMerge:
    QS = (0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99, 1.0)

    def _split_check(self, data):
        """merge(h1, h2) must equal the single-stream sketch EXACTLY —
        same buckets, same count/min/max, identical quantiles — which
        is the property the multi-replica rollup stands on."""
        h1, h2, hall = Histogram(), Histogram(), Histogram()
        for i, v in enumerate(data):
            (h1 if i % 3 else h2).observe(float(v))
            hall.observe(float(v))
        merged = h1.merge(h2)
        assert merged.buckets == hall.buckets
        assert merged.zero == hall.zero
        assert merged.count == hall.count
        assert merged.min == hall.min and merged.max == hall.max
        for q in self.QS:
            assert merged.quantile(q) == hall.quantile(q), q

    def test_uniform_split_exact(self):
        self._split_check(
            np.random.RandomState(0).uniform(1e-3, 10.0, 8000))

    def test_lognormal_split_exact(self):
        self._split_check(
            np.random.RandomState(1).lognormal(0.0, 2.0, 8000))

    def test_bimodal_split_exact(self):
        low = np.abs(np.random.RandomState(2).normal(1e-3, 1e-4, 5000))
        high = np.random.RandomState(3).normal(100.0, 1.0, 3000)
        self._split_check(np.concatenate([low, high]))

    def test_zero_and_empty_merge(self):
        h1, h2 = Histogram(), Histogram()
        h1.observe(0.0)
        h1.observe(-2.0)
        h1.merge(h2)                      # empty right side: no-op
        assert h1.count == 2 and h1.zero == 2
        h2.merge(h1)                      # empty left side absorbs
        assert h2.count == 2 and h2.quantile(1.0) <= 0.0

    def test_gamma_mismatch_refused(self):
        h1, h2 = Histogram(alpha=0.05), Histogram(alpha=0.01)
        h1.observe(1.0)
        h2.observe(2.0)
        with pytest.raises(ValueError):
            h1.merge(h2)
        # but a side with NO positive observations carries no lattice:
        # merging it is exact under any alpha (idle replica case)
        empty = Histogram(alpha=0.01)
        h1.merge(empty)
        empty2 = Histogram(alpha=0.01)
        empty2.merge(h1)
        assert empty2.count == 1
        assert empty2.quantile(1.0) == h1.quantile(1.0)

    def test_state_roundtrip_preserves_quantiles(self):
        h = Histogram()
        for v in np.random.RandomState(4).lognormal(0, 1, 3000):
            h.observe(float(v))
        for blob in (h.state(), h.summary()):
            h2 = Histogram.from_state(
                json.loads(json.dumps(blob)))   # through JSON
            for q in self.QS:
                assert h2.quantile(q) == h.quantile(q)


def _replica(name, steps, ttfts):
    """Shared rollup-test fixture: one synthetic replica registry."""
    r = MetricsRegistry(name)
    r.counter("serve_steps").inc(steps)
    r.gauge("kv_pool_blocks_free").set(steps * 2)
    for v in ttfts:
        r.histogram("serve_ttft_s").observe(v)
    return r


class TestFleetRollup:
    def test_merge_counters_gauges_histograms(self):
        a = _replica("a", 3, [0.1, 0.2])
        b = _replica("b", 4, [0.3])
        m = MetricsRegistry.merge([a, b], name="fleet")
        snap = m.snapshot()
        assert snap["counters"]["serve_steps"] == 7.0
        assert snap["gauges"]['kv_pool_blocks_free{source="a"}'] == 6
        assert snap["gauges"]['kv_pool_blocks_free{source="b"}'] == 8
        h = snap["histograms"]["serve_ttft_s"]
        assert h["count"] == 3 and h["min"] == 0.1 and h["max"] == 0.3

    def test_merge_quantiles_equal_single_stream(self):
        vals = np.random.RandomState(5).lognormal(-3, 1, 4000)
        regs = [MetricsRegistry(f"r{i}") for i in range(4)]
        hall = Histogram()
        for i, v in enumerate(vals):
            regs[i % 4].histogram("serve_tpot_s").observe(float(v))
            hall.observe(float(v))
        m = MetricsRegistry.merge(regs)
        merged = m._metrics["serve_tpot_s"]
        for q in (0.5, 0.9, 0.99):
            assert merged.quantile(q) == hall.quantile(q)

    def test_merge_snapshots_cross_process(self):
        """The file-based path: exported snapshot JSONs merge with the
        same exactness (histogram summaries carry the sketch state)."""
        a = _replica("a", 2, [0.1, 0.4, 0.4])
        b = _replica("a", 5, [0.2])       # name COLLISION
        snaps = [json.loads(a.to_json()), json.loads(b.to_json())]
        merged = telemetry.merge_snapshots(snaps)
        assert merged["counters"]["serve_steps"] == 7.0
        gk = set(merged["gauges"])
        assert 'kv_pool_blocks_free{source="a"}' in gk
        assert 'kv_pool_blocks_free{source="a#1"}' in gk
        h = merged["histograms"]["serve_ttft_s"]
        assert h["count"] == 4
        ref = Histogram()
        for v in (0.1, 0.4, 0.4, 0.2):
            ref.observe(v)
        assert h["p99"] == ref.quantile(0.99)
        # gauge labels merge with existing labels intact
        c = MetricsRegistry("c")
        c.gauge("achieved_tflops", phase="serve").set(1.5)
        out = telemetry.merge_snapshots([c.snapshot()], sources=["x"])
        assert out["gauges"][
            'achieved_tflops{phase="serve",source="x"}'] == 1.5


# ------------------------------------------------------------------ #
# time series (ISSUE 10): bounded sampling, windowed rates, export
# ------------------------------------------------------------------ #


class TestTimeSeries:
    def test_sample_rate_and_bounded_ring(self, monkeypatch):
        monkeypatch.setenv("DSTPU_SERIES_CAPACITY", "8")
        r = MetricsRegistry("t")
        c = r.counter("serve_tokens_committed")
        for i in range(20):
            c.inc(10)
            r.sample(now=100.0 + i)
        series = r.series()["serve_tokens_committed"]
        assert len(series) == 8                  # ring bounded
        assert r.rate("serve_tokens_committed") == pytest.approx(10.0)
        assert r.rate("serve_tokens_committed",
                      window_s=3.0) == pytest.approx(10.0)
        assert r.rate("nope") is None

    def test_maybe_sample_throttles(self, monkeypatch):
        monkeypatch.setenv("DSTPU_SERIES_EVERY_S", "5.0")
        r = MetricsRegistry("t")
        r.counter("serve_steps").inc()
        r.maybe_sample(now=100.0)
        r.maybe_sample(now=102.0)               # < interval: skipped
        r.maybe_sample(now=106.0)
        assert len(r.series()["serve_steps"]) == 2

    def test_series_rides_export_and_top_render(self, tmp_path):
        from deepspeed_tpu.telemetry.top import render
        r = MetricsRegistry("serve")
        c = r.counter("serve_tokens_committed")
        for i in range(6):
            c.inc(30 + 5 * i)
            r.sample(now=200.0 + i)
        path = str(tmp_path / "snap.json")
        r.export(path)
        blob = json.loads(open(path).read())
        assert "serve_tokens_committed" in blob["series"]
        out = render(blob)
        assert "rates (sampled series)" in out
        assert "tokens/s" in out

    def test_null_registry_series_noop(self, monkeypatch):
        monkeypatch.setenv("DSTPU_TELEMETRY", "0")
        r = telemetry.new_registry("t")
        r.sample()
        r.maybe_sample()
        assert r.series() == {} and r.rate("x") is None


# ------------------------------------------------------------------ #
# flight recorder: drop accounting + uid-tagged request spans
# ------------------------------------------------------------------ #


class TestFlightDropsAndRequestSpans:
    def test_ring_wrap_counts_drops(self, tmp_path):
        rec = FlightRecorder(capacity=4)
        for i in range(10):
            rec.record(f"s{i}", float(i), float(i) + 0.5)
        assert rec.dropped == 6
        rec.phase("plan")
        rec.phase("idle")                      # closes -> 7th drop
        assert rec.dropped == 7
        path = str(tmp_path / "t.json")
        rec.dump(path)
        trace = json.loads(open(path).read())
        assert trace["otherData"]["spans_dropped"] == 7

    def test_uid_events_get_per_request_tracks(self):
        rec = FlightRecorder(capacity=16)
        rec.event("req_admit", uid=3)
        rec.phase("plan")
        rec.phase("idle")
        trace = rec.to_chrome_trace()
        by_name = {ev["name"]: ev for ev in trace["traceEvents"]}
        assert by_name["req_admit"]["tid"] == 4        # uid + 1
        assert by_name["req_admit"]["args"]["uid"] == 3
        assert by_name["plan"]["tid"] == 0             # engine lane

    def test_serve_run_emits_request_lifecycle_spans(self):
        """One request's admit -> queue -> prefill chunks -> first
        token -> decode -> finish life must be reconstructable from the
        engine's flight ring (uid-tagged spans, ISSUE 10)."""
        eng = _engine()
        toks = _serve(eng, _workload())
        for u in list(toks):
            eng.flush(u)
        spans = eng.flight.spans
        for uid in toks:
            names = [s[0] for s in spans
                     if s[4] and s[4].get("uid") == uid]
            for expected in ("req_admit", "req_queue_wait",
                             "req_prefill_chunk", "req_ttft",
                             "req_decode", "req_finish"):
                assert expected in names, (uid, expected, names)
            fin = [s for s in spans if s[4]
                   and s[4].get("uid") == uid
                   and s[0] == "req_finish"]
            assert fin[-1][4]["outcome"] == "completed"

    def test_request_spans_disabled_by_knob(self, monkeypatch):
        monkeypatch.setenv("DSTPU_FLIGHT_REQUESTS", "0")
        eng = _engine()
        toks = _serve(eng, _workload(), n=2)
        for u in list(toks):
            eng.flush(u)
        assert not [s for s in eng.flight.spans
                    if s[0].startswith("req_")]

    def test_flight_drops_surface_as_registry_counter(self,
                                                     monkeypatch):
        monkeypatch.setenv("DSTPU_FLIGHT_CAPACITY", "6")
        eng = _engine()
        toks = _serve(eng, _workload())
        for u in list(toks):
            eng.flush(u)
        eng._obs.sync_gauges()
        assert eng.flight.dropped > 0
        c = eng.metrics.snapshot()["counters"]
        assert c["flight_spans_dropped"] == eng.flight.dropped


class TestRollupHardening:
    """Review-driven edge cases on the fleet rollup."""

    def test_remerging_rollups_keeps_replica_sources(self):
        """Hierarchical rollup (review-driven): merging two rollups —
        or re-merging a rollup's snapshot — must preserve the ORIGINAL
        per-replica gauge sources, not crash or collapse them."""
        a = _replica("a", 1, [0.1])
        b = _replica("b", 1, [0.2])
        c = _replica("c", 1, [0.3])
        fleet_ab = MetricsRegistry.merge([a, b], name="pool0")
        fleet = MetricsRegistry.merge([fleet_ab, c], name="global")
        g = fleet.snapshot()["gauges"]
        for src in ("a", "b", "c"):
            assert f'kv_pool_blocks_free{{source="{src}"}}' in g, g
        assert fleet.snapshot()["counters"]["serve_steps"] == 3.0
        # and the snapshot path, same property
        snap = telemetry.merge_snapshots(
            [fleet_ab.snapshot(), c.snapshot()], sources=["p0", "c"])
        for src in ("a", "b", "c"):
            assert f'kv_pool_blocks_free{{source="{src}"}}' \
                in snap["gauges"]

    def test_idle_replica_with_custom_alpha_merges(self):
        """Review-driven: an idle replica's empty sketch (no buckets)
        carries no lattice information — merging it with a populated
        non-default-alpha sketch must stay exact, not raise
        mixed-gamma, in both the object and snapshot paths."""
        idle, busy = MetricsRegistry("i"), MetricsRegistry("b")
        idle.histogram("serve_ttft_s", alpha=0.01)
        hb = busy.histogram("serve_ttft_s", alpha=0.01)
        for v in (0.1, 0.2, 0.4):
            hb.observe(v)
        m = MetricsRegistry.merge([idle, busy])
        merged = m._metrics["serve_ttft_s"]
        assert merged.count == 3
        assert merged.quantile(0.99) == hb.quantile(0.99)
        snap = telemetry.merge_snapshots(
            [idle.snapshot(), busy.snapshot()])
        assert snap["histograms"]["serve_ttft_s"]["count"] == 3
        assert snap["histograms"]["serve_ttft_s"]["p99"] \
            == hb.quantile(0.99)

    def test_colliding_sources_suffix_not_overwrite(self):
        """Two pools each holding a replica named 'a': the second 'a'
        gauge is suffixed, never silently overwritten (both paths)."""
        p0 = MetricsRegistry.merge([_replica("a", 1, [])], name="p0")
        p1 = MetricsRegistry.merge([_replica("a", 4, [])], name="p1")
        g = MetricsRegistry.merge([p0, p1]).snapshot()["gauges"]
        assert g['kv_pool_blocks_free{source="a"}'] == 2
        assert g['kv_pool_blocks_free{source="a#1"}'] == 8
        snap = telemetry.merge_snapshots([p0.snapshot(), p1.snapshot()])
        assert snap["gauges"]['kv_pool_blocks_free{source="a"}'] == 2
        assert snap["gauges"]['kv_pool_blocks_free{source="a#1"}'] == 8

    def test_short_sources_list_rejected(self):
        with pytest.raises(ValueError):
            telemetry.merge_snapshots(
                [_replica("a", 1, []).snapshot(),
                 _replica("b", 1, []).snapshot()], sources=["only-one"])
