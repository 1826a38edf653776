"""The engines' brackets (ISSUE 25, ``telemetry/trace.py``): one duration
per boundary reaches the engine's totals, the flight ring and the
registry histogram; the request's four stamps split its first-token
time; the counters of ``pipeline_stats`` are filled where the work
happens; the train engine's ``step_stats`` likewise. Since ISSUE 55 the
books close: a call into an engine holds a total, every stretch beneath
it a bracket with one, and three of the stamps are the engine's own.
One tiny GPT-2."""

import glob
import time

import numpy as np
import pytest

from deepspeed_tpu.telemetry.trace import SPANS, SpanSet


def _engine(**kw):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
    mcfg = GPT2Config(vocab_size=96, max_seq_len=128, num_layers=2,
                      num_heads=2, hidden_size=32, dtype=jnp.float32)
    params = GPT2(mcfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    base = dict(max_seqs=4, chunk_size=8, block_size=4, num_blocks=64,
                max_blocks_per_seq=16, dtype="float32",
                attention_impl="dense", decode_loop_steps=0,
                serve_pipeline_depth=2)
    base.update(kw)
    return InferenceEngineV2(mcfg, params, RaggedInferenceConfig(**base))


def _train_engine(**model_kw):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, make_model
    _, init_fn, loss_fn = make_model(GPT2Config.tiny(dtype=jnp.float32,
                                                     **model_kw))
    params = init_fn(jax.random.PRNGKey(0), batch_size=2, seq_len=17)
    engine, _, _, _ = dstpu.initialize(
        loss_fn=loss_fn, params=params,
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
                "steps_per_print": 100000})
    rng = np.random.RandomState(25)
    B = engine.config.train_batch_size
    batches = [{"tokens": jnp.asarray(rng.randint(0, 512, size=(B, 18)),
                                      jnp.int32)} for _ in range(3)]
    return engine, batches


class _Sink:
    """What an observer is to a SpanSet."""

    def __init__(self):
        self.seen = []

    def on_span(self, span, t0, t1):
        self.seen.append((span.name, t1 - t0, dict(span.args)))


class _Dog:
    def __init__(self):
        self.phases = []

    def phase(self, name):
        self.phases.append(name)


def _prompts(n, lens=(13, 5, 9, 7)):
    rng = np.random.default_rng(25)
    return [rng.integers(1, 96, lens[i % len(lens)]).tolist()
            for i in range(n)]


class TestBracket:
    def test_one_duration_reaches_totals_observer_and_watchdog(self):
        totals = {"plan_s": 1.0}
        sink = _Sink()
        spans = SpanSet(totals, lambda: sink)
        spans.watchdog = _Dog()
        with spans.span("serve/plan", step=7) as span:
            time.sleep(0.002)
            span.set(S=16, T=8)
            span.count(prefill_tokens_real=5)
        (name, dt, args), = sink.seen
        assert name == "serve/plan" and dt >= 0.002
        # the SAME duration, not a second clock read
        assert totals["plan_s"] == 1.0 + dt
        assert totals["prefill_tokens_real"] == 5
        assert args == {"step": 7, "S": 16, "T": 8}
        assert spans.watchdog.phases == ["plan"]

    def test_nested_spans_each_record_and_an_entry_point_holds_a_total(self):
        totals = {}
        sink = _Sink()
        spans = SpanSet(totals, lambda: sink)
        spans.watchdog = _Dog()
        with spans.span("serve/put", requests=2):
            with spans.span("serve/plan"):
                pass
            with spans.span("serve/dispatch", fed=0):
                pass
        # an entry point holds a total (the sum its brackets are held
        # against) and still reaches neither ring nor histogram nor dog
        assert [n for n, _, _ in sink.seen] \
            == ["serve/plan", "serve/dispatch"]
        assert spans.watchdog.phases == ["plan", "dispatch"]
        assert set(totals) == {"put_s", "plan_s", "dispatch_s"}
        assert totals["put_s"] >= totals["plan_s"] + totals["dispatch_s"]

    def test_a_voided_bracket_reaches_nobody(self):
        """A plan that scheduled nothing is not a step: no seconds in
        the totals, no ring entry, no histogram sample."""
        totals = {"plan_s": 0.0}
        sink = _Sink()
        spans = SpanSet(totals, lambda: sink)
        with spans.span("serve/plan") as span:
            span.void()
        assert totals == {"plan_s": 0.0} and sink.seen == []

    def test_the_observer_is_read_when_the_bracket_closes(self):
        """Benches switch the observer of a live engine on and off."""
        holder = {"obs": None}
        spans = SpanSet({}, lambda: holder["obs"])
        with spans.span("serve/plan"):
            pass
        holder["obs"] = sink = _Sink()
        with spans.span("serve/plan"):
            pass
        assert len(sink.seen) == 1

    def test_a_raising_body_still_closes_the_bracket(self):
        totals = {}
        spans = SpanSet(totals)
        with pytest.raises(RuntimeError):
            with spans.span("train/dispatch", step=1):
                raise RuntimeError("dead dispatch")
        assert totals["dispatch_s"] >= 0.0

    def test_the_table_is_the_whole_vocabulary(self):
        with pytest.raises(KeyError):
            SpanSet({}).span("serve/whatever")
        # the readers split keys on dots
        assert not any("." in name for name in SPANS)
        hists = {s.hist for s in SPANS.values() if s.hist}
        from deepspeed_tpu.telemetry.registry import REGISTERED_METRICS
        assert hists <= set(REGISTERED_METRICS)

    def test_every_span_that_brackets_host_seconds_has_a_total(self):
        """A call's seconds add up from its brackets only if each has a
        total; two spans may share a histogram, never a total."""
        totals = [s.total for s in SPANS.values()]
        assert all(t and t.endswith("_s") for t in totals)
        by_engine = {}
        for name, spec in SPANS.items():
            by_engine.setdefault(name.split("/")[0], []).append(spec.total)
        for keys in by_engine.values():
            assert len(set(keys)) == len(keys)
        # the calls, the stretches between their steps and the counters'
        # own arithmetic are totals only: nothing new in the flight ring,
        # the registry or the watchdog's phases
        for name in ("serve/put", "serve/decode_pipelined",
                     "serve/decode_batch", "serve/admit", "serve/plan_count",
                     "serve/fused_stage", "serve/fused_count", "train/batch",
                     "train/step_exit"):
            assert SPANS[name].phase is None and SPANS[name].hist is None
        # two of them are a total alone: no annotation, so the profile's
        # gaps keep the names the benchmark's readers sum
        assert {n for n, s in SPANS.items() if not s.annotated} == \
            {"serve/plan_count", "train/batch"}

    def test_every_total_starts_at_nought_on_a_fresh_engine(self):
        """``counters_delta`` exports what both ends of a window hold: a
        key first written by its bracket would miss the first window."""
        eng = _engine()
        for name, spec in SPANS.items():
            if name.startswith("serve/"):
                assert eng.pipeline_stats[spec.total] == 0.0, name
        assert eng.pipeline_stats["steps"] == 0


class TestServeBrackets:
    @pytest.fixture(scope="class")
    def served(self):
        eng = _engine(decode_loop_steps=4)
        due = time.monotonic() - 0.05
        first = eng.put([0, 1], _prompts(2), _greedy=True,
                        arrivals={0: due, 1: due})
        after_put = dict(eng.pipeline_stats)
        outs = eng.decode_pipelined([0, 1], [int(first[0]), int(first[1])],
                                    3)
        after_decode = dict(eng.pipeline_stats)
        eng.decode_batch([0, 1], [outs[0][-1], outs[1][-1]], 4)
        return eng, due, after_put, after_decode, dict(eng.pipeline_stats)

    def test_totals_ring_and_histogram_hold_the_same_seconds(self, served):
        eng = served[0]
        ring = {}
        for name, t0, t1, _step, _args in eng.flight.spans:
            ring[name] = ring.get(name, 0.0) + (t1 - t0)
        st = eng.pipeline_stats
        hist = eng.metrics.snapshot()["histograms"]
        assert ring["plan"] == pytest.approx(st["plan_s"], rel=1e-9)
        assert hist["serve_plan_s"]["sum"] \
            == pytest.approx(st["plan_s"], rel=1e-9)
        # the fused loop shares phase names and histograms with the step
        assert ring["dispatch"] == pytest.approx(
            st["dispatch_s"] + st["fused_dispatch_s"], rel=1e-9)
        assert hist["serve_commit_apply_s"]["sum"] == pytest.approx(
            st["commit_apply_s"] + st["fused_apply_s"], rel=1e-9)
        # the fused readback is a wait with a total of its own: ring
        # and histogram hold both waits under one name
        assert ring["commit"] == pytest.approx(
            st["commit_block_s"] + st["fused_readback_s"], rel=1e-9)
        assert hist["serve_commit_block_s"]["sum"] == pytest.approx(
            st["commit_block_s"] + st["fused_readback_s"], rel=1e-9)
        assert st["fused_readback_s"] > 0.0

    def test_a_calls_leaf_totals_never_exceed_the_calls(self, served):
        """put, decode_pipelined and decode_batch each hold a total, and
        what lies beneath them is bracketed leaf by leaf: the leaves sum
        to at most the calls, and what is left is a number."""
        _, _, after_put, after_decode, final = served
        steps = ("plan_s", "dispatch_s", "commit_block_s", "commit_apply_s")
        put = sum(after_put[k] for k in ("admit_s",) + steps)
        assert 0.0 < put <= after_put["put_s"]
        assert after_put["decode_pipelined_s"] == 0.0
        piped = sum(after_decode[k] - after_put[k]
                    for k in ("admit_s",) + steps)
        assert 0.0 < piped <= after_decode["decode_pipelined_s"]
        assert after_decode["put_s"] == after_put["put_s"]
        fused = sum(final[k] for k in (
            "fused_stage_s", "fused_dispatch_s", "fused_readback_s",
            "fused_count_s", "fused_apply_s"))
        assert 0.0 < fused <= final["decode_batch_s"]
        # no step of the fused loop opens a pipelined bracket
        assert all(final[k] == after_decode[k] for k in steps)

    @pytest.mark.parametrize("key,where", [
        ("admit_s", "put"), ("plan_count_s", "put"),
        ("admit_s", "decode"), ("plan_count_s", "decode"),
        ("fused_stage_s", "batch"),
        ("fused_count_s", "batch")])
    def test_the_stretches_between_the_steps_are_bracketed(self, served,
                                                           key, where):
        _, _, after_put, after_decode, final = served
        then, now = {"put": ({key: 0.0}, after_put),
                     "decode": (after_put, after_decode),
                     "batch": (after_decode, final)}[where]
        assert now[key] > then[key]
        # the counters' arithmetic is nested in the plan that holds it
        assert final["plan_count_s"] < final["plan_s"]

    def test_the_rings_phase_names_are_unchanged(self, served):
        names = {s[0] for s in served[0].flight.spans
                 if not s[0].startswith("req_")}
        assert {"plan", "dispatch", "commit"} <= names
        assert names <= {"plan", "dispatch", "commit", "commit_apply"}

    def test_steps_and_fed_steps_are_counted_by_the_dispatch_bracket(
            self, served):
        eng, _, after_put, after_decode, _ = served
        assert after_put["steps"] == 2 and after_put["fed_steps"] == 0
        assert after_decode["steps"] == 5 and after_decode["fed_steps"] == 2
        c = eng.metrics.snapshot()["counters"]
        assert c["serve_steps"] == 5 and c["serve_steps_device_fed"] == 2

    def test_stamps_are_ordered_and_the_parts_sum(self, served):
        eng, due = served[0], served[1]
        for uid in (0, 1):
            seq = eng.state.sequences[uid]
            assert seq.admitted_at == due
            assert due <= seq.put_at <= seq.first_sched_at \
                <= seq.first_token_at
            door = seq.put_at - seq.admitted_at
            sched = seq.first_sched_at - seq.put_at
            prefill = seq.first_token_at - seq.first_sched_at
            assert door >= 0.05
            assert door + sched + prefill == pytest.approx(
                seq.first_token_at - seq.admitted_at, abs=1e-9)
        rep = eng.slo_report()
        for key in ("door_wait_s", "sched_wait_s", "prefill_s"):
            assert rep[key]["count"] == 2
        assert rep["door_wait_s"]["sum"] + rep["sched_wait_s"]["sum"] \
            == pytest.approx(rep["queue_wait_s"]["sum"], rel=1e-6)

    def test_prefill_and_decode_counters_on_a_two_prompt_put(self, served):
        _, _, after_put, after_decode, _ = served
        # 13 + 5 prompt tokens in chunks of 8: two steps of the
        # [prefill_rows, 8] = [2, 8] program, two rows then one
        assert after_put["prefill_tokens_real"] == 18
        assert after_put["prefill_tokens_real"] \
            <= after_put["prefill_tokens_planned"]
        assert after_put["prefill_tokens_planned"] == 2 * 2 * 8
        assert after_put["prefill_steps"] == 2
        assert after_put["prefill_rows"] == 3
        assert after_put["decode_slots_live"] == 0
        # three pure-decode steps of two live sequences in four slots
        assert after_decode["decode_slots_live"] == 6
        assert after_decode["decode_slots_planned"] == 12
        assert after_decode["prefill_tokens_planned"] == 2 * 2 * 8
        assert after_decode["prefill_steps"] == 2

    def test_a_plan_that_schedules_nothing_is_not_a_step(self, served):
        eng = served[0]
        before = dict(eng.pipeline_stats)
        ring = len(eng.flight.spans)
        plans = eng.metrics.snapshot()["histograms"]["serve_plan_s"]["count"]
        assert eng._plan_step(greedy=True) is None
        assert eng.pipeline_stats == before
        assert len(eng.flight.spans) == ring
        assert eng.metrics.snapshot()["histograms"]["serve_plan_s"][
            "count"] == plans

    def test_decode_spec_rounds_go_through_the_fused_brackets(self):
        """The speculative verify is a fused dispatch, a readback and an
        apply: the same three brackets as decode_batch, no clock pairs
        of its own."""
        eng = _engine(spec_decode="ngram", spec_k=3, num_blocks=96,
                      max_blocks_per_seq=24)
        pat = np.random.default_rng(3).integers(1, 96, 6).tolist()
        first = eng.put([0], [(pat * 4)[:15]], _greedy=True)
        st0 = dict(eng.pipeline_stats)
        eng.decode_spec([0], [int(first[0])], 8)
        st = eng.pipeline_stats
        rounds = int(eng.slo_report()["spec"]["rounds"])
        assert rounds > 0
        assert st["fused_dispatch_s"] > st0["fused_dispatch_s"]
        assert st["fused_apply_s"] > st0["fused_apply_s"]
        phases = [s[0] for s in eng.flight.spans
                  if s[0] in ("dispatch", "commit", "commit_apply")]
        assert phases[-3 * rounds:] \
            == ["dispatch", "commit", "commit_apply"] * rounds

    def test_the_fused_path_fills_the_same_dict(self, served):
        _, _, _, after_decode, final = served
        for key in ("fused_dispatch_s", "fused_apply_s"):
            assert after_decode[key] == 0.0 and final[key] > 0.0
        # and leaves the pipelined counters alone
        for key in ("steps", "plan_s", "decode_slots_live"):
            assert final[key] == after_decode[key]

    def test_totals_accumulate_without_telemetry(self, monkeypatch):
        monkeypatch.setenv("DSTPU_TELEMETRY", "0")
        eng = _engine()
        assert eng._obs is None
        first = eng.put([0], _prompts(1), _greedy=True)
        eng.decode_pipelined([0], [int(first[0])], 2)
        st = eng.pipeline_stats
        assert st["steps"] == 4
        for key in ("plan_s", "dispatch_s", "commit_block_s",
                    "commit_apply_s"):
            assert st[key] > 0.0
        assert eng.state.sequences[0].put_at is not None
        for key in ("put_s", "admit_s", "plan_count_s",
                    "decode_pipelined_s"):
            assert st[key] > 0.0

    @pytest.mark.parametrize("decode", ["pipelined", "fused"])
    def test_the_stamps_are_the_engines_own(self, monkeypatch, decode):
        """``put_at``, ``first_sched_at`` and ``first_token_at`` are set
        with the observer off: the benchmark's per-request readers keep
        reading when its cost is measured."""
        monkeypatch.setenv("DSTPU_TELEMETRY", "0")
        eng = _engine(decode_loop_steps=4)
        assert eng._obs is None
        before = time.monotonic()
        first = eng.put([0, 1], _prompts(2), _greedy=True)
        stamps = {}
        for uid in (0, 1):
            seq = eng.state.sequences[uid]
            assert before <= seq.put_at <= seq.first_sched_at \
                <= seq.first_token_at
            # the observer's own two stay unset
            assert seq.admitted_at is None and seq.last_token_at is None
            stamps[uid] = (seq.put_at, seq.first_sched_at,
                           seq.first_token_at)
        if decode == "pipelined":
            eng.decode_pipelined([0, 1], [int(first[0]), int(first[1])], 2)
        else:
            eng.decode_batch([0, 1], [int(first[0]), int(first[1])], 4)
        # later schedules and commits leave the first stamps alone
        for uid in (0, 1):
            seq = eng.state.sequences[uid]
            assert stamps[uid] == (seq.put_at, seq.first_sched_at,
                                   seq.first_token_at)

    def test_a_first_token_from_the_fused_loop_is_stamped(self,
                                                          monkeypatch):
        """A sequence whose first committed output comes from
        ``decode_batch`` (its prompt fed without a greedy put) is stamped
        by ``_apply_fused``."""
        monkeypatch.setenv("DSTPU_TELEMETRY", "0")
        eng = _engine(decode_loop_steps=4)
        prompt = _prompts(1)[0]
        eng.put([0], [prompt[:-1]])
        seq = eng.state.sequences[0]
        at_put = seq.first_token_at
        seq.first_token_at = None
        eng.decode_batch([0], [prompt[-1]], 4)
        assert at_put is not None and seq.first_token_at > at_put

    def test_an_attached_watchdog_is_told_each_phase(self):
        eng = _engine()
        dog = _Dog()
        dog.step_start = dog.step_end = lambda step: None
        dog.step_abort = lambda: None
        eng.attach_watchdog(dog)
        eng.put([0], _prompts(1), _greedy=True)
        assert dog.phases[:2] == ["plan", "dispatch"]
        assert {"commit", "commit_apply"} <= set(dog.phases)

    def test_the_spans_are_on_the_profilers_clock(self, tmp_path):
        """With a profiler session live the brackets land in the trace
        under ``dstpu:``, arguments and all; nothing gates them."""
        import jax
        eng = _engine()
        eng.put([9], _prompts(1), _greedy=True)       # compile outside
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.put([0, 1], _prompts(2), _greedy=True)
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
        found = {}
        for plane in data.planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("dstpu:"):
                        found.setdefault(e.name, dict(e.stats))
        assert {"dstpu:serve/put", "dstpu:serve/admit", "dstpu:serve/plan",
                "dstpu:serve/dispatch", "dstpu:serve/commit_block",
                "dstpu:serve/commit_apply"} <= set(found)
        # a total alone: the counters' arithmetic stays under serve/plan
        assert "dstpu:serve/plan_count" not in found
        plan = found["dstpu:serve/plan"]
        assert plan["S"] == 2 and plan["T"] == 8 and plan["seqs"] == 2
        assert found["dstpu:serve/dispatch"]["program"] == "step_greedy"
        assert found["dstpu:serve/put"]["requests"] == 2


class TestTrainBrackets:
    def test_step_stats_and_ring_without_the_registry(self):
        eng, batches = _train_engine()
        for b in batches:
            eng.train_batch(b)
        st = eng.step_stats
        assert st["steps"] == 3
        flash = {"flash_score_elems_computed", "flash_score_elems_needed",
                 "flash_grid_steps", "flash_grid_steps_run"}
        # sharded leaves by who writes their collectives: none at stage 0
        zero = {"zero_manual_leaves", "zero_held_leaves", "zero_auto_leaves"}
        assert set(st) == {"steps", "train_batch_s", "stage_s",
                           "dispatch_s", "device_wait_s", "commit_apply_s",
                           "step_exit_s"} | flash | zero
        assert all(st[k] > 0.0 for k in set(st) - flash - zero)
        assert all(st[k] == 0 for k in zero)
        names = [s[0] for s in eng._train_obs.flight.spans]
        assert names == ["stage", "dispatch", "device_execute",
                         "commit_apply"] * 3
        # the observer files each phase's seconds under train_<phase>_s
        hist = eng._train_obs.registry.snapshot()["histograms"]
        assert hist["train_dispatch_s"]["sum"] \
            == pytest.approx(st["dispatch_s"], rel=1e-9)
        assert hist["train_device_execute_s"]["count"] == 3

    @pytest.mark.parametrize("impl", ["xla", "flash"])
    def test_flash_score_elems_grow_with_the_steps(self, impl):
        """What the causal flash kernels compute and what the mask needs,
        from the plans noted while the step function was traced: the same
        two numbers every step, their ratio the plan's share; 0 and 0 for
        a program without the kernel."""
        from deepspeed_tpu.ops.kernels.flash_attention import causal_plan
        eng, batches = _train_engine(attention_impl=impl)
        seen = []
        for b in batches:
            eng.train_batch(b)
            seen.append((eng.step_stats["flash_score_elems_computed"],
                         eng.step_stats["flash_score_elems_needed"]))
        if impl == "xla":
            assert seen == [(0, 0)] * 3
            return
        # T = 17 under one 128-block: a general block a (batch, head)
        plan = causal_plan(128, 128, 128, 128, 17, 0)
        assert plan["general"] == 1
        computed, needed = seen[0]
        assert computed > 0 and computed % plan["score_elems_computed"] == 0
        assert computed * plan["score_elems_needed"] \
            == needed * plan["score_elems_computed"]
        assert seen == [(computed * n, needed * n) for n in (1, 2, 3)]
        # one block a (batch, head): each of the three kernels takes one
        # grid step for it and runs it, in each of the three train steps
        assert (plan["steps"], plan["steps_run"]) == (3, 3)
        calls = computed // plan["score_elems_computed"]
        st = eng.step_stats
        assert st["flash_grid_steps"] == st["flash_grid_steps_run"] \
            == 3 * calls * 3

    def test_step_stats_fill_with_the_observer_off(self, monkeypatch):
        monkeypatch.setenv("DSTPU_TRAIN_OBS", "0")
        eng, batches = _train_engine()
        assert eng._train_obs is None
        for b in batches[:2]:
            eng.train_batch(b)
        assert eng.step_stats["steps"] == 2
        assert eng.step_stats["dispatch_s"] > 0.0
        # no observer, no books to close: the bracket never opens
        assert eng.step_stats["step_exit_s"] == 0.0
        assert eng.step_stats["train_batch_s"] > 0.0

    def test_a_two_step_train_batchs_leaves_never_exceed_the_call(self):
        eng, batches = _train_engine()
        fresh = dict(eng.step_stats)
        assert {k: v for k, v in fresh.items() if k.endswith("_s")} \
            == {SPANS[n].total: 0.0 for n in SPANS if n.startswith("train/")}
        for b in batches[:2]:
            eng.train_batch(b)
        st = eng.step_stats
        leaves = sum(st[k] for k in ("stage_s", "dispatch_s",
                                     "device_wait_s", "commit_apply_s",
                                     "step_exit_s"))
        assert 0.0 < leaves <= st["train_batch_s"]
        # the observer closing its books, and the wait for step N-1 (none
        # before the second step), each under its own total
        assert st["step_exit_s"] > 0.0 and st["device_wait_s"] > 0.0
        # the enclosing call and the exit reach neither ring nor registry
        names = [s[0] for s in eng._train_obs.flight.spans]
        assert names == ["stage", "dispatch", "device_execute",
                         "commit_apply"] * 2
