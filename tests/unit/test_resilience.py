"""Resilience layer — fault injection, self-healing checkpoints,
preemption-aware elasticity, step watchdog (docs/resilience.md).

The crash/resume acceptance bar: a mid-save injected crash (torn
``state.npz``) followed by restart resumes from the newest VALID tag with
identical ``global_steps`` and optimizer state, and ``latest`` is only
ever updated after a fully-validated tag exists on disk.
"""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.checkpoint.engine_checkpoint import (
    LATEST_FILE,
    QUARANTINE_SUFFIX,
    STATE_FILE,
    find_valid_tag,
    publish_latest,
    validate_checkpoint_dir,
)
from deepspeed_tpu.models.gpt2 import GPT2Config, make_model
from deepspeed_tpu.resilience import (
    FAULT_SITES,
    FaultInjector,
    InjectedFault,
    RestartLedger,
    StepWatchdog,
    set_fault_injector,
)


@pytest.fixture(autouse=True)
def _clean_injector():
    yield
    set_fault_injector(None)


def _engine(lr=1e-2):
    cfg_model = GPT2Config.tiny(dtype=jnp.float32)
    model, init_fn, loss_fn = make_model(cfg_model)
    params = init_fn(jax.random.PRNGKey(0), batch_size=2, seq_len=17)
    engine, _, _, _ = dstpu.initialize(
        loss_fn=loss_fn, params=params, config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": lr}},
            "steps_per_print": 1000,
            "checkpoint": {"retry_backoff_s": 0.01},
        })
    return engine


def _batch(engine, seed=0):
    rng = np.random.RandomState(seed)
    B = engine.config.train_batch_size
    return {"tokens": jnp.asarray(rng.randint(0, 512, size=(B, 18)),
                                  jnp.int32)}


def _params_snapshot(engine):
    return [np.array(jax.device_get(x))
            for x in jax.tree_util.tree_leaves(engine.state.params)]


def _opt_snapshot(engine):
    return [np.array(jax.device_get(x))
            for x in jax.tree_util.tree_leaves(engine.state.opt_state)]


# ------------------------------------------------------------------------- #
# fault injector mechanics
# ------------------------------------------------------------------------- #

class TestFaultInjector:
    def test_unknown_site_rejected(self):
        with pytest.raises(ValueError):
            FaultInjector(site="nope")

    def test_raise_mode_and_times(self):
        inj = FaultInjector(site="pre_save", mode="raise", times=2)
        for _ in range(2):
            with pytest.raises(InjectedFault):
                inj.maybe_fire("pre_save")
        inj.maybe_fire("pre_save")            # exhausted: no-op
        inj.maybe_fire("mid_save")            # different site: no-op

    def test_skip_counts_arrivals(self):
        inj = FaultInjector(site="pre_save", mode="raise", skip=2)
        inj.maybe_fire("pre_save")
        inj.maybe_fire("pre_save")
        with pytest.raises(InjectedFault):
            inj.maybe_fire("pre_save")

    def test_step_gating(self):
        inj = FaultInjector(site="step", mode="raise", at_step=3)
        inj.maybe_fire("step", step=0)
        inj.maybe_fire("step", step=2)
        with pytest.raises(InjectedFault):
            inj.maybe_fire("step", step=3)

    def test_once_file_disarms(self, tmp_path):
        marker = str(tmp_path / "fired")
        inj = FaultInjector(site="pre_save", mode="raise", once_file=marker)
        with pytest.raises(InjectedFault):
            inj.maybe_fire("pre_save")
        assert os.path.exists(marker)
        inj2 = FaultInjector(site="pre_save", mode="raise", once_file=marker)
        inj2.maybe_fire("pre_save")           # marker present: disarmed

    def test_env_protocol(self, monkeypatch):
        monkeypatch.setenv("DSTPU_FAULT_SITE", "collective")
        monkeypatch.setenv("DSTPU_FAULT_MODE", "raise")
        monkeypatch.setenv("DSTPU_FAULT_TIMES", "7")
        inj = FaultInjector.from_env()
        assert inj.site == "collective" and inj.mode == "raise"
        assert inj.times == 7


# ------------------------------------------------------------------------- #
# self-healing checkpoints
# ------------------------------------------------------------------------- #

class TestSelfHealingCheckpoints:
    def test_mid_save_crash_resumes_previous_tag(self, tmp_path):
        """THE acceptance bar: torn mid-save -> restart resumes the newest
        valid tag with identical global_steps and optimizer state."""
        e = _engine()
        e.train_batch(_batch(e, 0))
        e.train_batch(_batch(e, 1))
        e.save_checkpoint(str(tmp_path))                  # global_step2
        params_at_2 = _params_snapshot(e)
        opt_at_2 = _opt_snapshot(e)

        e.train_batch(_batch(e, 2))                       # -> step 3
        set_fault_injector(FaultInjector(site="mid_save", mode="raise"))
        with pytest.raises(InjectedFault):
            e.save_checkpoint(str(tmp_path))              # torn global_step3
        set_fault_injector(None)

        # the crash left a torn tmp dir, an intact previous tag, and an
        # untouched latest pointer
        tmps = [d for d in os.listdir(tmp_path) if ".tmp-" in d]
        assert tmps, "torn tmp dir should remain for forensics"
        assert (tmp_path / LATEST_FILE).read_text() == "global_step2"
        assert not (tmp_path / "global_step3").exists()

        e2 = _engine()
        path, _ = e2.load_checkpoint(str(tmp_path))
        assert path is not None and path.endswith("global_step2")
        assert e2.global_steps == 2
        for a, b in zip(params_at_2, _params_snapshot(e2)):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(opt_at_2, _opt_snapshot(e2)):
            np.testing.assert_array_equal(a, b)
        # training continues
        assert np.isfinite(float(e2.train_batch(_batch(e2, 2))))

    def test_pre_save_crash_leaves_store_untouched(self, tmp_path):
        e = _engine()
        e.train_batch(_batch(e, 0))
        e.save_checkpoint(str(tmp_path))
        before = sorted(os.listdir(tmp_path))
        e.train_batch(_batch(e, 1))
        set_fault_injector(FaultInjector(site="pre_save", mode="raise"))
        with pytest.raises(InjectedFault):
            e.save_checkpoint(str(tmp_path))
        set_fault_injector(None)
        assert sorted(os.listdir(tmp_path)) == before

    def test_post_save_pre_latest_crash_keeps_old_pointer(self, tmp_path):
        """Crash after the tag is durable but before publish: the save is
        UNCOMMITTED — resume comes from the previous latest."""
        e = _engine()
        e.train_batch(_batch(e, 0))
        e.save_checkpoint(str(tmp_path))                  # global_step1
        e.train_batch(_batch(e, 1))
        set_fault_injector(FaultInjector(site="post_save_pre_latest",
                                         mode="raise"))
        with pytest.raises(InjectedFault):
            e.save_checkpoint(str(tmp_path))
        set_fault_injector(None)
        # tag 2 is on disk and VALID, but latest still commits tag 1
        ok, _ = validate_checkpoint_dir(str(tmp_path / "global_step2"))
        assert ok
        assert (tmp_path / LATEST_FILE).read_text() == "global_step1"
        e2 = _engine()
        path, _ = e2.load_checkpoint(str(tmp_path))
        assert path.endswith("global_step1") and e2.global_steps == 1

    def test_checksum_mismatch_falls_back_and_quarantines(self, tmp_path):
        e = _engine()
        e.train_batch(_batch(e, 0))
        e.save_checkpoint(str(tmp_path))                  # global_step1
        params_at_1 = _params_snapshot(e)
        e.train_batch(_batch(e, 1))
        e.save_checkpoint(str(tmp_path))                  # global_step2
        # bit-rot the newest tag's state file
        state = tmp_path / "global_step2" / STATE_FILE
        blob = bytearray(state.read_bytes())
        blob[len(blob) // 2] ^= 0xFF
        state.write_bytes(bytes(blob))

        e2 = _engine()
        path, _ = e2.load_checkpoint(str(tmp_path))
        assert path.endswith("global_step1")
        assert e2.global_steps == 1
        for a, b in zip(params_at_1, _params_snapshot(e2)):
            np.testing.assert_array_equal(a, b)
        # the corrupt tag is quarantined and the pointer healed
        assert not (tmp_path / "global_step2").exists()
        assert any(QUARANTINE_SUFFIX in d for d in os.listdir(tmp_path))
        assert (tmp_path / LATEST_FILE).read_text() == "global_step1"

    def test_explicit_corrupt_tag_raises(self, tmp_path):
        e = _engine()
        e.train_batch(_batch(e, 0))
        e.save_checkpoint(str(tmp_path))
        state = tmp_path / "global_step1" / STATE_FILE
        state.write_bytes(b"garbage")
        e2 = _engine()
        with pytest.raises(ValueError, match="failed validation"):
            e2.load_checkpoint(str(tmp_path), tag="global_step1")

    def test_publish_refuses_invalid_tag(self, tmp_path):
        os.makedirs(tmp_path / "broken_tag")
        with pytest.raises(RuntimeError, match="refusing to publish"):
            publish_latest(str(tmp_path), "broken_tag")
        assert not (tmp_path / LATEST_FILE).exists()

    def test_save_retries_transient_io_errors(self, tmp_path, monkeypatch):
        e = _engine()
        e.train_batch(_batch(e, 0))
        real_savez = np.savez
        fails = {"n": 2}

        def flaky_savez(*a, **kw):
            if fails["n"] > 0:
                fails["n"] -= 1
                raise OSError("transient write blip")
            return real_savez(*a, **kw)

        monkeypatch.setattr(np, "savez", flaky_savez)
        path = e.save_checkpoint(str(tmp_path))
        assert fails["n"] == 0
        ok, reason = validate_checkpoint_dir(path)
        assert ok, reason

    def test_save_retry_budget_bounded(self, tmp_path, monkeypatch):
        e = _engine()
        e.train_batch(_batch(e, 0))
        calls = {"n": 0}

        def dead_savez(*a, **kw):
            calls["n"] += 1
            raise OSError("disk on fire")

        monkeypatch.setattr(np, "savez", dead_savez)
        with pytest.raises(OSError):
            e.save_checkpoint(str(tmp_path))
        assert calls["n"] == e.config.checkpoint.save_retries + 1

    def test_find_valid_tag_ordering(self, tmp_path):
        e = _engine()
        for i in range(3):
            e.train_batch(_batch(e, i))
            e.save_checkpoint(str(tmp_path))
        assert find_valid_tag(str(tmp_path)) == "global_step3"
        # prefer the pointer when it validates, even if older
        assert find_valid_tag(str(tmp_path),
                              preferred="global_step1") == "global_step1"


# ------------------------------------------------------------------------- #
# engine fault sites
# ------------------------------------------------------------------------- #

class TestEngineFaultSites:
    def test_step_site_fires_at_step_n(self):
        e = _engine()
        e.train_batch(_batch(e, 0))
        set_fault_injector(FaultInjector(site="step", mode="raise",
                                         at_step=2))
        assert np.isfinite(float(e.train_batch(_batch(e, 1))))  # step 1->2
        with pytest.raises(InjectedFault):
            e.train_batch(_batch(e, 2))                          # step 2: fire


# ------------------------------------------------------------------------- #
# preemption grace (in-process + end-to-end through the elastic agent)
# ------------------------------------------------------------------------- #

class TestPreemption:
    def _preemptible_engine(self, save_dir):
        cfg_model = GPT2Config.tiny(dtype=jnp.float32)
        model, init_fn, loss_fn = make_model(cfg_model)
        params = init_fn(jax.random.PRNGKey(0), batch_size=2, seq_len=17)
        engine, _, _, _ = dstpu.initialize(
            loss_fn=loss_fn, params=params, config={
                "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
                "steps_per_print": 1000,
                "resilience": {"preemption": {"enabled": True,
                                              "save_dir": str(save_dir)}},
            })
        return engine

    def test_request_triggers_urgent_save_and_elastic_exit(self, tmp_path):
        from deepspeed_tpu.elasticity.elastic_agent import (
            MEMBERSHIP_CHANGE_EXIT)
        e = self._preemptible_engine(tmp_path / "ck")
        try:
            e.train_batch(_batch(e, 0))
            e.preemption.request()
            with pytest.raises(SystemExit) as exc:
                e.train_batch(_batch(e, 1))
            assert exc.value.code == MEMBERSHIP_CHANGE_EXIT
        finally:
            if e.preemption is not None:
                e.preemption.uninstall()
        # the urgent checkpoint covers the step that was just completed
        e2 = _engine()
        path, _ = e2.load_checkpoint(str(tmp_path / "ck"))
        assert path is not None and e2.global_steps == 2

    def test_real_sigterm_sets_flag(self, tmp_path):
        e = self._preemptible_engine(tmp_path / "ck")
        try:
            assert not e.preemption.preempted
            os.kill(os.getpid(), signal.SIGTERM)
            assert e.preemption.wait(timeout=5.0)
        finally:
            e.preemption.uninstall()

    def test_uninstall_restores_handlers(self, tmp_path):
        before = signal.getsignal(signal.SIGTERM)
        e = self._preemptible_engine(tmp_path / "ck")
        assert signal.getsignal(signal.SIGTERM) != before
        e.preemption.uninstall()
        assert signal.getsignal(signal.SIGTERM) == before


WORKER_SCRIPT = r"""
import json, os, signal, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp, numpy as np
import deepspeed_tpu as dstpu
from deepspeed_tpu.models.gpt2 import GPT2Config, make_model

save_dir = os.environ["WK_SAVE_DIR"]
progress = os.environ["WK_PROGRESS_FILE"]
stop_at = int(os.environ.get("WK_STEPS", "6"))
sigterm_step = int(os.environ.get("WK_SELF_SIGTERM_STEP", "-1"))

cfg = GPT2Config.tiny(dtype=jnp.float32)
_, init_fn, loss_fn = make_model(cfg)
params = init_fn(jax.random.PRNGKey(0), batch_size=2, seq_len=17)
engine, _, _, _ = dstpu.initialize(
    loss_fn=loss_fn, params=params, config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "steps_per_print": 1000,
        "resilience": {"preemption": {"enabled": True,
                                      "save_dir": save_dir}},
    })
engine.load_checkpoint(save_dir)
while engine.global_steps < stop_at:
    rng = np.random.RandomState(engine.global_steps)
    batch = {"tokens": jnp.asarray(
        rng.randint(0, 512, size=(engine.config.train_batch_size, 18)),
        jnp.int32)}
    if engine.global_steps + 1 == sigterm_step:
        os.kill(os.getpid(), signal.SIGTERM)   # delivered before this step
    engine.train_batch(batch)                  # step boundary handles it
    with open(progress, "w") as f:
        json.dump({"global_steps": engine.global_steps}, f)
sys.exit(0)
"""


class TestElasticPreemptionEndToEnd:
    def _env(self, tmp_path, **extra):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)          # 1 CPU device: fastest
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(dstpu.__file__)))
        env.update({
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": repo_root + os.pathsep + env.get("PYTHONPATH", ""),
            "WK_SAVE_DIR": str(tmp_path / "ck"),
            "WK_PROGRESS_FILE": str(tmp_path / "progress.json"),
        })
        env.update({k: str(v) for k, v in extra.items()})
        return env

    def test_sigterm_final_checkpoint_and_clean_resume(self, tmp_path):
        """Worker preempted mid-run checkpoints, exits 99; the elastic
        agent restarts it; the resumed run continues from the SAME
        global_steps and finishes — zero lost steps."""
        from deepspeed_tpu.elasticity import run_elastic
        script = tmp_path / "worker.py"
        script.write_text(WORKER_SCRIPT)
        ledger_path = str(tmp_path / "ledger.json")
        rc = run_elastic(
            [sys.executable, str(script)],
            {"max_train_batch_size": 2000, "micro_batch_sizes": [2],
             "min_gpus": 1, "max_gpus": 8, "version": 0.1},
            max_restarts=3, min_restart_interval_s=0.0,
            backoff_base_s=0.01, ledger_path=ledger_path,
            env=self._env(tmp_path, WK_SELF_SIGTERM_STEP=3, WK_STEPS=6),
        )
        assert rc == 0
        progress = json.loads((tmp_path / "progress.json").read_text())
        assert progress["global_steps"] == 6
        events = json.loads(open(ledger_path).read())["events"]
        kinds = [ev["event"] for ev in events]
        assert "restart" in kinds and "success" in kinds
        restart = next(ev for ev in events if ev["event"] == "restart")
        assert restart["membership_change"] is True and restart["rc"] == 99
        # the preemption checkpoint landed BEFORE the restart: step 3 (the
        # step in flight when SIGTERM arrived) completed and saved — it is
        # the worker's ONLY checkpoint, and the resumed run continued from
        # exactly there (3 -> 6 with zero lost or repeated steps)
        from deepspeed_tpu.checkpoint.engine_checkpoint import find_valid_tag
        assert find_valid_tag(str(tmp_path / "ck")) == "global_step3"

    def test_crash_loop_budget_stops_restarts(self, tmp_path):
        from deepspeed_tpu.elasticity import run_elastic
        script = tmp_path / "crash.py"
        script.write_text("import sys; sys.exit(1)\n")
        ledger_path = str(tmp_path / "ledger.json")
        t0 = time.time()
        rc = run_elastic(
            [sys.executable, str(script)],
            {"max_train_batch_size": 2000, "micro_batch_sizes": [2],
             "min_gpus": 1, "max_gpus": 8, "version": 0.1},
            max_restarts=100, min_restart_interval_s=0.0,
            backoff_base_s=0.01, crash_loop_budget=3,
            ledger_path=ledger_path)
        assert rc == 1
        assert time.time() - t0 < 30
        events = json.loads(open(ledger_path).read())["events"]
        giveup = [ev for ev in events if ev["event"] == "giveup"]
        assert giveup and giveup[0]["reason"] == "crash_loop"
        # budget of 3 fast failures: far fewer than max_restarts launches
        assert sum(ev["event"] == "launch" for ev in events) == 3


# ------------------------------------------------------------------------- #
# step watchdog
# ------------------------------------------------------------------------- #

class TestStepWatchdog:
    def _dog(self, **kw):
        kw.setdefault("check_interval_s", 3600)   # tick manually
        kw.setdefault("min_median_samples", 2)
        kw.setdefault("min_stall_s", 0.01)
        kw.setdefault("stall_factor", 2.0)
        return StepWatchdog(**kw)

    def test_stall_diagnosis_names_last_collective(self):
        from deepspeed_tpu.comm.comms_logging import note_collective
        wd = self._dog()
        try:
            for i in range(3):
                wd.step_start(i)
                wd.step_end(i)
            note_collective("all_reduce", 4096, 8, log_name="grad_sync")
            wd.step_start(3)
            wd.phase("compiled_step")
            time.sleep(0.05)
            diag = wd.check_once()
            assert diag is not None
            assert diag["step"] == 3
            assert diag["last_phase"] == "compiled_step"
            assert diag["last_collective"]["op"] == "all_reduce"
            assert diag["last_collective"]["log_name"] == "grad_sync"
            # one report per step, not one per tick
            assert wd.check_once() is None
        finally:
            wd.stop()

    def test_no_stall_within_budget(self):
        wd = self._dog(min_stall_s=60.0)
        try:
            for i in range(3):
                wd.step_start(i)
                wd.step_end(i)
            wd.step_start(3)
            assert wd.check_once() is None
        finally:
            wd.stop()

    def test_idle_engine_never_stalls(self):
        wd = self._dog()
        try:
            for i in range(3):
                wd.step_start(i)
                wd.step_end(i)
            time.sleep(0.05)
            assert wd.check_once() is None     # not in a step
        finally:
            wd.stop()

    def test_heartbeat_file_written(self, tmp_path):
        hb = str(tmp_path / "hb.json")
        wd = self._dog(heartbeat_file=hb)
        try:
            wd.step_start(0)
            wd._heartbeat()
            blob = json.loads(open(hb).read())
            assert blob["in_step"] == 0
            assert blob["last_phase"] == "step"
        finally:
            wd.stop()

    def test_engine_wires_watchdog_from_config(self):
        cfg_model = GPT2Config.tiny(dtype=jnp.float32)
        model, init_fn, loss_fn = make_model(cfg_model)
        params = init_fn(jax.random.PRNGKey(0), batch_size=2, seq_len=17)
        engine, _, _, _ = dstpu.initialize(
            loss_fn=loss_fn, params=params, config={
                "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
                "resilience": {"watchdog": {"enabled": True,
                                            "check_interval_s": 3600}},
            })
        try:
            engine.train_batch(_batch(engine, 0))
            engine.train_batch(_batch(engine, 1))
            assert len(engine._watchdog._durations) == 2
            assert engine._watchdog._step is None      # idle between steps
        finally:
            engine._watchdog.stop()


# ------------------------------------------------------------------------- #
# restart ledger
# ------------------------------------------------------------------------- #

class TestRestartLedger:
    def test_append_and_reload(self, tmp_path):
        path = str(tmp_path / "ledger.json")
        led = RestartLedger(path)
        led.record("launch", pid=1)
        led.record("restart", rc=99)
        led2 = RestartLedger(path)            # survives supervisor restart
        assert [ev["event"] for ev in led2.events] == ["launch", "restart"]

    def test_pathless_ledger_in_memory(self):
        led = RestartLedger(None)
        led.record("launch")
        assert len(led.events) == 1


# ------------------------------------------------------------------------- #
# the CI fault drill (subset: keep tier-1 fast; bin/dstpu_faultdrill runs
# every site)
# ------------------------------------------------------------------------- #

class TestFaultDrill:
    def test_drill_recovers_torn_save(self, tmp_path):
        from deepspeed_tpu.resilience.faultdrill import main
        rc = main(["--sites", "mid_save,post_save_pre_latest",
                   "--workdir", str(tmp_path)])
        assert rc == 0

    @pytest.mark.slow
    def test_serve_drill_hard_crash_and_sigterm(self, tmp_path):
        # one hard-crash site (journal recovery) + the cooperative
        # SIGTERM drain (manifest recovery); bin/dstpu_faultdrill
        # --mode serve runs every serve site
        from deepspeed_tpu.resilience.faultdrill import main
        rc = main(["--mode", "serve", "--sites", "mid_commit,sigterm",
                   "--workdir", str(tmp_path)])
        assert rc == 0

    def test_sites_cover_the_documented_set(self):
        from deepspeed_tpu.resilience import (DISAGG_FAULT_SITE,
                                              SERVE_FAULT_SITES,
                                              TRAIN_FAULT_SITES)
        assert TRAIN_FAULT_SITES == (
            "pre_save", "mid_save", "post_save_pre_latest", "collective",
            "step")
        assert SERVE_FAULT_SITES == (
            "pre_dispatch", "mid_commit", "during_prefill_chunk",
            "during_cow_copy")
        assert DISAGG_FAULT_SITE == "during_handoff_gather"
        assert FAULT_SITES == (TRAIN_FAULT_SITES + SERVE_FAULT_SITES
                               + (DISAGG_FAULT_SITE,))
