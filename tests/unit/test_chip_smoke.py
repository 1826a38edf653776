"""``chip_smoke.py`` on the CPU mesh: the stage functions the chip run
drives, at toy width; the platform refusal; the compile-cache helper; the
peak-FLOP/s table's refusal of unknown devices."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from deepspeed_tpu.inference.v2 import RaggedInferenceConfig  # noqa: E402
from deepspeed_tpu.models.gpt2 import GPT2Config  # noqa: E402
from deepspeed_tpu.models.llama import LlamaConfig  # noqa: E402
from deepspeed_tpu.telemetry.loadgen import WorkloadMix  # noqa: E402
from deepspeed_tpu.utils import compile_cache  # noqa: E402

# the full-width configurations with every width cut and the structure
# kept: bf16 params and moments, remat qkv_out, GQA, int8 linear pool,
# max prompt + max generation = one block
TOY_GPT2 = GPT2Config(vocab_size=512, max_seq_len=65, num_layers=1,
                      num_heads=4, hidden_size=64,
                      param_dtype=jnp.bfloat16, remat=True,
                      remat_policy="qkv_out", flash_block_q=128,
                      flash_block_k=128)
TOY_LLAMA = LlamaConfig(vocab_size=512, max_seq_len=128, num_layers=1,
                        num_heads=4, num_kv_heads=2, hidden_size=64,
                        intermediate_size=128, dtype=jnp.bfloat16,
                        param_dtype=jnp.bfloat16)
TOY_MIX = WorkloadMix(prompt_lens=(16, 32, 48), prompt_probs=(0.3, 0.4, 0.3),
                      gen_lens=(8, 12, 16), gen_probs=(0.3, 0.4, 0.3),
                      vocab_size=512)


def toy_serve_config(**kw):
    base = dict(max_seqs=4, chunk_size=48, block_size=64, num_blocks=8,
                max_blocks_per_seq=1, decode_loop_steps=8, dtype="bfloat16",
                kv_cache_dtype="int8", prefill_chunk_cap=0,
                max_batch_tokens=0)
    base.update(kw)
    return RaggedInferenceConfig(**base)


class TestStages:
    @pytest.mark.parametrize("zero_stage,n_dev", [
        (0, 1), pytest.param(3, 4, marks=pytest.mark.full)])
    def test_train_stage(self, zero_stage, n_dev):
        cfg = chip_smoke.train_config(micro=2, zero_stage=zero_stage,
                                      data=n_dev)
        # toy leaves sit under the persistence threshold and would stay
        # replicated; 0 shards them all, as the 1.3B leaves are
        cfg["zero_optimization"]["stage3_param_persistence_threshold"] = 0
        # other tests' module fixtures stay alive: a stage is judged by
        # what it ADDS to the live arrays
        before = chip_smoke.live_array_bytes()
        rep = chip_smoke.train_stage(TOY_GPT2, cfg,
                                     devices=jax.devices()[:n_dev])
        assert rep["mesh"]["data"] == n_dev and rep["batch"] == 2 * n_dev
        assert rep["losses"][-1] < rep["losses"][0]
        assert rep["mosaic_calls"] == 0          # interpreted off the chip
        assert sorted(rep["params_bytes_per_device"]) == list(range(n_dev))
        if n_dev > 1:
            chip_smoke._even_shares(rep["opt_bytes_per_device"], n_dev,
                                    "optimizer state")
            assert rep["collectives"]["all-gather"] > 0
        # nothing the engine made outlives the stage
        assert chip_smoke.free_stage("train")["live_array_bytes"] <= before

    def test_serve_stage(self):
        # other tests' module fixtures stay alive: a stage is judged by
        # what it ADDS to the live arrays
        before = chip_smoke.live_array_bytes()
        rep = chip_smoke.serve_stage(
            TOY_LLAMA, toy_serve_config(), TOY_MIX, n_requests=10,
            reference={"attention_impl": "dense"}, parity_requests=2,
            parity_tokens=8)
        assert rep["sampled"] == 3 and rep["warm_fresh_compiles"] == 0
        assert set(rep["mosaic_calls"].values()) == {0}
        for path in ("parity_pipelined", "parity_fused_loop"):
            assert rep[path]["worst_gap_sigma"] <= chip_smoke.TOP1_TOL_SIGMA
        assert chip_smoke.free_stage("serve")["live_array_bytes"] <= before

    @pytest.mark.full
    def test_replica_stage_places_each_engine_on_its_device(self):
        devs = jax.devices()[:2]
        rep = chip_smoke.replica_stage(TOY_LLAMA, toy_serve_config(),
                                       TOY_MIX, n_requests=6, devices=devs)
        for (rid, where), dev in zip(rep["replicas"].items(), devs):
            assert where["params"] == where["kv_pool"] == [dev.id], rid
            assert where["steps"] > 0

    def test_even_shares_rejects_everything_on_device_0(self):
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke._even_shares({0: 100}, 4, "pool")
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke._even_shares({0: 70, 1: 10, 2: 10, 3: 10}, 4, "pool")
        chip_smoke._even_shares({0: 25, 1: 25, 2: 26, 3: 24}, 4, "pool")


def test_refuses_without_a_tpu_and_names_the_platform():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout                 # no result line


class TestCompileCache:
    KEYS = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")

    @pytest.fixture(autouse=True)
    def _restore(self):
        saved = {k: getattr(jax.config, k) for k in self.KEYS}
        yield
        for k, v in saved.items():
            jax.config.update(k, v)

    def test_unset_uses_the_fixed_in_repo_path(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.enable_compile_cache() == \
            os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            compile_cache.REPO_CACHE_DIR
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0

    def test_set_from_outside_is_left_alone(self, monkeypatch, tmp_path):
        # JAX read the variable into its config at import; the helper must
        # not replace that value with a directory of its own
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)

    @pytest.mark.full
    def test_program_writes_where_the_variable_points(self, tmp_path):
        code = ("import jax, jax.numpy as jnp\n"
                "from deepspeed_tpu.utils.compile_cache import "
                "enable_compile_cache\n"
                "print(enable_compile_cache())\n"
                "jax.jit(lambda x: x * 2 + 1)(jnp.ones((8, 8)))"
                ".block_until_ready()\n")
        cache = tmp_path / "placed"
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, cwd=str(tmp_path),
            env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
                 "JAX_COMPILATION_CACHE_DIR": str(cache)})
        assert r.returncode == 0, r.stderr[-2000:]
        assert r.stdout.strip() == str(cache)
        assert os.listdir(cache)                  # the step program landed
        assert os.listdir(tmp_path) == ["placed"]  # and nothing else did

    def test_no_other_file_places_the_cache(self):
        offenders = []
        for root, dirs, files in os.walk(REPO):
            dirs[:] = [d for d in dirs
                       if d not in (".git", "tests", "chiprun_out",
                                    ".jax_cache", "__pycache__")]
            for name in files:
                path = os.path.join(root, name)
                if name.endswith(".py") and not path.endswith(
                        os.path.join("utils", "compile_cache.py")):
                    with open(path, encoding="utf-8") as f:
                        if "compilation_cache" in f.read():
                            offenders.append(os.path.relpath(path, REPO))
        assert offenders == []


def test_peak_flops_refuses_an_unknown_device():
    from deepspeed_tpu.profiling import flops_profiler
    with pytest.raises(KeyError, match="cpu"):
        flops_profiler.device_peak_flops()        # the CPU mesh: no row
    assert flops_profiler.utilization(1e12) is None
    assert "cpu" not in flops_profiler.PEAK_FLOPS
    json.dumps(flops_profiler.PEAK_FLOPS)         # a plain table
