"""Overload-robust serving tests (ISSUE 16): the admission
controller's AIMD knee-seeking loop and brownout ladder on synthetic
evidence (fake engine, explicit clock), the typed-rejection /
retry-budget contract through the open-loop loadgen, rejection-record
back-compat, and the in-process spike gate — controller ON must hold
goodput at or above the uncontrolled run on the SAME seeded spike
schedule, with bit-identical token streams when disarmed and 0 fresh
compiles when armed."""

import pytest

from deepspeed_tpu.serving.admission import (BROWNOUT_LEVELS,
                                             AdmissionController,
                                             admission_enabled,
                                             build_admission)
from deepspeed_tpu.telemetry.registry import MetricsRegistry

# ------------------------------------------------------------------ #
# synthetic harness: a fake engine + an explicit control clock
# ------------------------------------------------------------------ #


class _FakeState:
    def __init__(self):
        self.promote_defer_ticks = 1


class _FakeConfig:
    def __init__(self):
        self.max_seqs = 8
        self.chunk_size = 16
        self.prefill_chunk_cap = 16


class _FakeEngine:
    """The attribute surface the controller reads/actuates — nothing
    else. Evidence is fed straight into the registry histogram."""

    def __init__(self):
        self.config = _FakeConfig()
        self.state = _FakeState()
        self.spec_mode = "topk"
        self.spec_k = 4
        self.metrics = MetricsRegistry("adm-test")
        self.rejections = {}

    def _reject(self, uid, reason, **fields):
        self.rejections[uid] = {
            "uid": uid, "reason": reason, "time": 0.0,
            "retry_after_s": fields.pop("retry_after_s", None),
            **fields}


def _ctrl(eng, **kw):
    kw.setdefault("window_s", 1.0)
    kw.setdefault("qw_slo_s", 0.1)
    kw.setdefault("tick_s", 0.1)
    kw.setdefault("hysteresis_s", 2.0)
    return AdmissionController(eng, **kw)


def _feed(eng, value, n=4):
    h = eng.metrics.histogram("serve_queue_wait_s")
    for _ in range(n):
        h.observe(value)


class TestControlLaw:
    def test_knee_hold_under_healthy_evidence(self):
        """Healthy windowed p99 -> the window HOLDS at cap: the
        controller located the knee and stays there, no flapping."""
        eng = _FakeEngine()
        c = _ctrl(eng)
        t = 0.0
        for _ in range(50):
            _feed(eng, 0.02)              # p99 well under the 0.1 SLO
            c.tick(t)
            t += 0.1
        assert c.window == c.cap == 8
        assert c.level == 0 and c.transitions == 0

    def test_one_cut_per_evidence_window(self):
        """A bad windowed p99 stays visible until the snapshot rotates;
        the multiplicative cut must fire once per evidence window, not
        once per tick (else one burst collapses the window to the
        floor)."""
        eng = _FakeEngine()
        c = _ctrl(eng)
        _feed(eng, 0.5)                   # one overloaded burst
        c.tick(0.0)
        assert c.window == int(8 * c.md)  # exactly one cut
        w = c.window
        for i in range(1, 9):             # same un-rotated evidence
            c.tick(i * 0.1)
        assert c.window == w              # no further cuts this window

    def test_hysteresis_no_flap_and_recovery(self):
        """After overload ends the window holds through the dwell, then
        recovers additively to cap; the ladder never re-enters on
        healthy evidence (no flap)."""
        eng = _FakeEngine()
        c = _ctrl(eng, hysteresis_s=1.0)
        t = 0.0
        for _ in range(45):               # sustained overload: one cut
            _feed(eng, 0.5)               # per evidence window, down
            c.tick(t)                     # to the floor
            t += 0.1
        assert c.window == c.min_live
        lvl = c.level
        assert lvl >= 1
        # healthy again: no new observations -> windowed p99 None
        t_bad = t - 0.1                   # the last bad tick
        while t - t_bad < 1.0:            # inside the dwell: hold
            c.tick(t)
            assert c.window == c.min_live
            assert c.level <= lvl         # exits allowed, entries not
            t += 0.1
        for _ in range(70):               # one rung exit per dwell
            _feed(eng, 0.01)
            c.tick(t)
            t += 0.1
        assert c.window == c.cap
        assert c.level == 0

    def test_ladder_enter_exit_ordering_and_actuation(self):
        """Rungs rise one per evidence window in order, actuate the
        documented knobs, and exits restore the EXACT baseline."""
        eng = _FakeEngine()
        c = _ctrl(eng, hysteresis_s=0.5)
        seen = []
        t = 0.0
        for _ in range(60):               # ratio 10: wants max level
            _feed(eng, 1.0)
            c.tick(t)
            if not seen or seen[-1] != c.level:
                seen.append(c.level)
            t += 0.1
        assert seen == [1, 2, 3, 4]       # one rung at a time, in order
        assert eng.state.promote_defer_ticks == 4          # L1
        assert eng.spec_mode == "off" and eng.spec_k <= 2  # L2
        assert eng.config.prefill_chunk_cap == 8           # L3: halved
        assert c.decode_burst_cap == 2                     # L3
        assert not c.door(0, klass=1)                      # L4 sheds
        assert c.door(0, klass=0)                          # ...only low
        down = []
        for _ in range(200):              # healthy: exit rung by rung
            c.tick(t)
            if not down or down[-1] != c.level:
                down.append(c.level)
            t += 0.1
        assert down[-1] == 0 and down == sorted(down, reverse=True)
        assert eng.state.promote_defer_ticks == 1          # restored
        assert eng.spec_mode == "topk" and eng.spec_k == 4
        assert eng.config.prefill_chunk_cap == 16
        assert c.decode_burst_cap > 1000
        # every move was recorded: enters + exits, catalogued counter
        snap = eng.metrics.snapshot()["counters"]
        trans = sum(v for k, v in snap.items()
                    if k.startswith("brownout_transitions"))
        assert trans == c.transitions == len(seen) + len(down) - 1

    def test_prime_resets_past_history(self):
        """prime() rotates the evidence snapshot past ALL prior
        history and resets control state — a controller attached after
        a collapse must not steer on the collapse's histogram."""
        eng = _FakeEngine()
        c = _ctrl(eng)
        _feed(eng, 2.0, n=50)             # a prior pass's wreckage
        c.tick(0.0)
        assert c.window < 8
        c.prime(now=10.0)
        assert c.window == c.cap and c.level == 0
        assert c.transitions == 0
        _feed(eng, 0.01)
        c.tick(10.1)
        assert c.window == c.cap          # old wreckage invisible

    def test_reject_record_shape_and_retry_hint(self):
        eng = _FakeEngine()
        c = _ctrl(eng)
        rec = c.reject(7, klass=1)
        assert rec["reason"] == "admission_overload"
        assert rec["retry_after_s"] == pytest.approx(c.tick_s)
        assert rec["level"] == 0 and rec["window"] == 8
        assert rec["klass"] == 1
        assert eng.rejections[7] is rec
        c.level = 3
        c.last_ratio = 2.0
        assert c.retry_after_s() == pytest.approx(
            min(c.retry_cap_s, c.tick_s * 8 * 2.0))

    def test_build_admission_kill_switch(self, monkeypatch):
        eng = _FakeEngine()
        monkeypatch.setenv("DSTPU_ADMISSION", "0")
        assert not admission_enabled()
        assert build_admission(eng) is None
        monkeypatch.setenv("DSTPU_ADMISSION", "1")
        monkeypatch.setenv("DSTPU_TELEMETRY", "0")
        assert build_admission(eng) is None  # blind controller: refuse
        monkeypatch.delenv("DSTPU_TELEMETRY")
        assert isinstance(build_admission(eng), AdmissionController)

    def test_levels_catalog(self):
        assert BROWNOUT_LEVELS[0] == "normal"
        assert len(BROWNOUT_LEVELS) == 5


# ------------------------------------------------------------------ #
# rejection-record back-compat (satellite 2)
# ------------------------------------------------------------------ #


class TestRejectionBackCompat:
    def test_engine_records_default_retry_after_none(self):
        from deepspeed_tpu.telemetry.loadgen import _tiny_engine
        eng, _ = _tiny_engine(max_seqs=2, num_blocks=16)
        eng._reject(5, "deadline_exceeded", deadline_s=0.1)
        rec = eng.rejections[5]
        assert rec["reason"] == "deadline_exceeded"
        assert rec["retry_after_s"] is None       # structured default
        assert rec["deadline_s"] == 0.1           # extra fields intact

    def test_report_reader_tolerates_legacy_records(self):
        """A record written WITHOUT the retry_after_s key (an old
        producer) must still classify and balance in the report."""
        from deepspeed_tpu.telemetry.loadgen import (PoissonArrivals,
                                                     WorkloadMix,
                                                     _tiny_engine,
                                                     build_requests,
                                                     run_open_loop)
        eng, mcfg = _tiny_engine(max_seqs=4, num_blocks=32)
        mix = WorkloadMix(prompt_lens=(8,), prompt_probs=(1.0,),
                          gen_lens=(4,), gen_probs=(1.0,),
                          vocab_size=mcfg.vocab_size)
        reqs = build_requests(PoissonArrivals(50.0, seed=1), mix, 6,
                              seed=1, uid_base=100)
        res = run_open_loop(eng, reqs)
        assert res.report["requests"]["balance_ok"]
        # forge a legacy record for a never-offered uid and re-read
        eng.rejections[999] = {"uid": 999, "reason": "draining",
                               "time": 0.0}
        assert eng.rejections[999].get("retry_after_s") is None


# ------------------------------------------------------------------ #
# loadgen retry discipline (driver-level, forced door)
# ------------------------------------------------------------------ #


@pytest.fixture(scope="module")
def tiny_engine():
    from deepspeed_tpu.telemetry.loadgen import _tiny_engine
    eng, mcfg = _tiny_engine(max_seqs=4, num_blocks=48)
    return eng, mcfg


class TestRetryDiscipline:
    def test_retry_budget_exhaustion_balances(self, tiny_engine):
        """A door that admits nothing: every request retries up to the
        budget then exhausts; the report classifies every uid exactly
        once as rejected_admission and the balance invariant holds."""
        from deepspeed_tpu.telemetry.loadgen import (PoissonArrivals,
                                                     WorkloadMix,
                                                     build_requests,
                                                     run_open_loop)
        eng, mcfg = tiny_engine
        ctrl = AdmissionController(eng, window_s=1.0, qw_slo_s=0.1,
                                   tick_s=1e9)   # control law frozen
        ctrl.window = 0                           # admit nothing
        mix = WorkloadMix(prompt_lens=(8,), prompt_probs=(1.0,),
                          gen_lens=(4,), gen_probs=(1.0,),
                          vocab_size=mcfg.vocab_size)
        reqs = build_requests(PoissonArrivals(200.0, seed=2), mix, 10,
                              seed=2, uid_base=200)
        res = run_open_loop(eng, reqs, admission=ctrl, retry_budget=2,
                            retry_base_s=0.01)
        rep = res.report
        assert rep["requests"]["completed"] == 0
        assert rep["requests"]["rejected_admission"] == 10
        assert rep["requests"]["balance_ok"]
        assert rep["retries"]["exhausted"] == 10
        assert rep["retries"]["attempts"] == 20   # budget x offers
        assert rep["retries"]["budget"] == 2
        for r in reqs:                            # typed + hinted
            rec = eng.rejections[r.uid]
            assert rec["reason"] == "admission_overload"
            assert rec["retry_after_s"] is not None

    def test_class_shed_at_level4(self, tiny_engine):
        """L4 sheds klass=1 at the door regardless of headroom; klass=0
        still admits and completes."""
        from deepspeed_tpu.telemetry.loadgen import (PoissonArrivals,
                                                     WorkloadMix,
                                                     build_requests,
                                                     run_open_loop)
        eng, mcfg = tiny_engine
        ctrl = AdmissionController(eng, window_s=1.0, qw_slo_s=0.1,
                                   tick_s=1e9)
        ctrl.level = 4
        lowmix = WorkloadMix(prompt_lens=(8,), prompt_probs=(1.0,),
                             gen_lens=(4,), gen_probs=(1.0,),
                             batch_frac=1.0,      # all klass=1
                             vocab_size=mcfg.vocab_size)
        reqs = build_requests(PoissonArrivals(100.0, seed=3), lowmix,
                              8, seed=3, uid_base=300)
        assert all(r.klass == 1 for r in reqs)
        rep = run_open_loop(eng, reqs, admission=ctrl,
                            retry_budget=0).report
        assert rep["requests"]["rejected_admission"] == 8
        assert rep["requests"]["completed"] == 0
        assert rep["requests"]["balance_ok"]
        himix = WorkloadMix(prompt_lens=(8,), prompt_probs=(1.0,),
                            gen_lens=(4,), gen_probs=(1.0,),
                            vocab_size=mcfg.vocab_size)
        hi = build_requests(PoissonArrivals(100.0, seed=4), himix, 4,
                            seed=4, uid_base=350)
        rep2 = run_open_loop(eng, hi, admission=ctrl,
                             retry_budget=0).report
        assert rep2["requests"]["completed"] == 4

    def test_armed_door_is_invisible_at_steady_load(self, tiny_engine):
        """Well under capacity with a healthy controller armed: token
        streams are those of the unarmed door (``admission=None``, the
        DSTPU_ADMISSION=0 path), nothing is refused, the brownout ladder
        never moves, and the armed pass compiles nothing."""
        from deepspeed_tpu.analysis import RecompileTripwire
        from deepspeed_tpu.telemetry.loadgen import (PoissonArrivals,
                                                     WorkloadMix,
                                                     build_requests,
                                                     run_open_loop)
        eng, mcfg = tiny_engine
        mix = WorkloadMix(prompt_lens=(8,), prompt_probs=(1.0,),
                          gen_lens=(4,), gen_probs=(1.0,),
                          vocab_size=mcfg.vocab_size)

        def reqs():
            return build_requests(PoissonArrivals(20.0, seed=6), mix, 12,
                                  seed=6, uid_base=600)

        off = run_open_loop(eng, reqs())          # also the warm-up
        ctrl = AdmissionController(eng, window_s=0.5, qw_slo_s=30.0,
                                   tick_s=0.05)
        ctrl.prime()
        tw = RecompileTripwire()
        with tw:
            on = run_open_loop(eng, reqs(), admission=ctrl,
                               retry_budget=2, retry_base_s=0.01)
        assert on.streams == off.streams and all(on.streams.values())
        rep = on.report
        assert rep["requests"]["completed"] == 12
        assert rep["requests"]["rejected_admission"] == 0
        assert rep["requests"]["balance_ok"]
        assert rep["admission"]["transitions"] == 0 and ctrl.level == 0
        assert tw.fresh_compiles == 0


# ------------------------------------------------------------------ #
# the in-process spike gate + parity + compile discipline
# ------------------------------------------------------------------ #


@pytest.mark.slow
class TestSpikeGate:
    def test_spike_on_vs_off_parity_and_compiles(self):
        """The full-tier miniature of the overload drill: same seeded
        spike schedule served uncontrolled then through the armed
        door. RELATIVE gates (CI hosts are noisy): controller-on
        goodput >= controller-off, the controller visibly engages,
        both breakdowns balance, armed-vs-off token streams are
        bit-identical at steady load, and the armed pass adds 0 fresh
        compiles."""
        from deepspeed_tpu.analysis import RecompileTripwire
        from deepspeed_tpu.telemetry.loadgen import (PoissonArrivals,
                                                     SpikeArrivals,
                                                     WorkloadMix,
                                                     _tiny_engine,
                                                     build_requests,
                                                     run_open_loop)
        eng, mcfg = _tiny_engine(max_seqs=8, num_blocks=96)
        slots = eng.config.max_seqs
        mix = WorkloadMix(prompt_lens=(16,), prompt_probs=(1.0,),
                          gen_lens=(8,), gen_probs=(1.0,),
                          vocab_size=mcfg.vocab_size)
        # warmup (compiles) + capacity estimate, max_live-pinned
        run_open_loop(eng, build_requests(PoissonArrivals(500.0, seed=0),
                                          mix, 10, seed=0, uid_base=1),
                      max_live=slots)
        cap = run_open_loop(
            eng, build_requests(PoissonArrivals(1e4, seed=1), mix, 32,
                                seed=1, uid_base=1000),
            max_live=slots).report["rates_rps"]["completed"] or 50.0
        deadline_s = max(0.25, 8.0 / cap)
        dmix = WorkloadMix(prompt_lens=(16,), prompt_probs=(1.0,),
                           gen_lens=(8,), gen_probs=(1.0,),
                           deadline_frac=1.0, deadline_s=deadline_s,
                           vocab_size=mcfg.vocab_size)
        base = 0.7 * cap
        n = min(600, max(48, int(base * 1.0 + 2.5 * cap * 1.0)))
        proc = SpikeArrivals(base, 2.5 * cap / base, 0.5, 1.0, seed=3)
        off = run_open_loop(
            eng, build_requests(proc, dmix, n, seed=3, uid_base=2000)
        ).report
        ctrl = AdmissionController(eng, window_s=0.5,
                                   qw_slo_s=deadline_s / 4,
                                   tick_s=0.05, hysteresis_s=0.5,
                                   retry_cap_s=deadline_s)
        for lvl in (3, 0):    # pre-warm browned-out program shapes
            ctrl.apply_level(lvl)
            run_open_loop(eng, build_requests(
                PoissonArrivals(0.5 * cap, seed=20 + lvl), mix, 8,
                seed=20 + lvl, uid_base=3000 + lvl * 100),
                max_live=slots)
        ctrl.prime()
        tw = RecompileTripwire()
        with tw:
            on = run_open_loop(
                eng, build_requests(proc, dmix, n, seed=3,
                                    uid_base=4000),
                admission=ctrl, retry_budget=2,
                retry_base_s=0.05).report
        fresh = tw.fresh_compiles
        assert fresh == 0
        on_g = on["rates_rps"]["goodput"] or 0.0
        off_g = off["rates_rps"]["goodput"] or 0.0
        assert on_g >= off_g                      # holds the knee side
        assert on["requests"]["balance_ok"]
        assert off["requests"]["balance_ok"]
        assert (on["requests"]["rejected_admission"] > 0
                or on["admission"]["transitions"] > 0)
        assert on["admission"]["rejected"] == ctrl.rejected
        # armed-vs-off token parity at steady (sub-knee) load: the
        # DSTPU_ADMISSION=0 door must be bit-identical, and an armed
        # idle controller must not change streams either
        ctrl.prime()
        steady = build_requests(PoissonArrivals(0.3 * cap, seed=5),
                                mix, 24, seed=5, uid_base=5000)
        a = run_open_loop(eng, steady, admission=ctrl, retry_budget=8,
                          retry_base_s=0.01)
        b = run_open_loop(eng, build_requests(
            PoissonArrivals(0.3 * cap, seed=5), mix, 24, seed=5,
            uid_base=5000), max_live=slots)
        assert a.streams == b.streams
        assert all(a.streams.values())
