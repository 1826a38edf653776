"""Test harness configuration.

The analogue of the reference's ``tests/unit/common.py`` ``DistributedTest``:
the reference forks N real processes per test class; in JAX SPMD the same
multi-device coverage comes from a virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count=8``) in ONE process — every
DP/TP/SP/EP/PP configuration is exercised as real SPMD sharding over those
devices (SURVEY.md §4 implication).

Tiers: tier-1 is ``-m 'not slow'`` under six xdist workers, ``--dist
loadfile`` and the driver's time limit (the command is README's, Tests);
the whole suite is the same without ``-m``. A FILE is what ``loadfile``
schedules: none may weigh more than a twentieth of the suite's summed
seconds (ROADMAP Queue 3 item 9 holds the table and the rule).
"""

import os
import shutil
import tempfile

# The tests compile through the repo's own persistent cache, as every other
# entry point does: the same toy programs recur from test to test, from
# worker to worker and from run to run. ONE fixed directory outside the
# checkout (the driver copies the tree, and the benchmark's TPU entries
# live in the checkout's own .jax_cache); a value from outside still wins.
# To empty it: rm -rf "$(python -c 'import tempfile; print(tempfile.gettempdir())')/dstpu_tests_jax_cache"
#
# NOT bounded by jax_compilation_cache_max_size: with it set, every put of
# jax 0.9's LRUCache lists the directory and reads every entry's access
# time under ONE file lock (~60 us an entry, measured: 0.17 s a put at 3,000
# entries, and a tier-1 run writes ~13,500), six workers queueing behind
# it. Without it a put is one in-place write of a few KB with no lock; a
# reader that meets a torn entry warns and compiles
# (jax_raise_persistent_cache_errors stays false). The bound is
# pytest_configure's below: past CACHE_LIMIT_BYTES the directory is emptied
# before the workers start.
CACHE_LIMIT_BYTES = 2 << 30
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "dstpu_tests_jax_cache"))

# jax may already be imported (but not backend-initialized) by the session
# environment, so plain env vars can be too late; jax.config wins either way.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from deepspeed_tpu.utils.jax_compat import request_cpu_devices  # noqa: E402

request_cpu_devices(8)

from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

# a jax imported before this file read the variable before it was set
jax.config.update("jax_compilation_cache_dir",
                  os.environ["JAX_COMPILATION_CACHE_DIR"])
enable_compile_cache()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_topology():
    """Each test builds its own mesh; clear the module-level registry."""
    yield
    from deepspeed_tpu.parallel import topology
    topology._TOPOLOGY = None


@pytest.fixture
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs

# The slow tier by name (``file::Class::test``): tests that were too heavy
# for tier-1 when they were listed and are deselected by ``-m 'not slow'``.
# A test that moves to another file is renamed here with it, or it falls
# into tier-1 in silence (the stale-entry warning below says so on a whole
# collection). PR 63 took six entries out (the train engine's ZeRO stages,
# accumulation and bf16 step, the ragged engine's prefill / decode / fused
# loop against the full forward: what the benchmark's cells run), each
# under 10 s on a warm cache; ROADMAP Queue 3 item 9 lists the 85 more that
# pass in under 5 s and the two that fail.
_FULL_TESTS = frozenset([
    "test_checkpoint.py::test_load_old_format_version",
    "test_compression.py::TestEngineIntegration::test_training_with_compression",
    "test_elasticity.py::TestEngineIntegration::test_elastic_batch_applied",
    "test_hf_loader.py::TestGPT2Parity::test_logits_match_transformers",
    "test_hybrid_engine.py::TestCachedRollout::test_cached_matches_uncached_greedy",
    "test_inference_v2.py::TestEvoformerChunked::test_chunked_grad_matches_fused",
    "test_inference_v2.py::TestEvoformerKernel::test_grad_parity_recompute_bwd",
    "test_inference_v2.py::TestEvoformerKernel::test_noncanonical_bias_falls_back",
    "test_inference_v2.py::TestEvoformerKernel::test_unaligned_seq_padding",
    "test_inference_v2.py::TestKVInt8::test_engine_int8_kernel_matches_dense",
    "test_inference_v2.py::TestOnDeviceSampling::test_generate_sampled_oversubscribed_pool",
    "test_inference_v2.py::TestOnDeviceSampling::test_sampled_loop_runs_fused_and_reproducible",
    "test_kernels.py::TestFusedXent::test_ignore_index",
    "test_models.py::TestLlamaRaggedParity::test_mixtral_prefill_parity",
    "test_moe.py::test_grouped_gemm_matches_dropless_capacity",
    "test_parallel.py::test_ulysses_gqa_groups_split_across_ranks",
    "test_parallel.py::test_ulysses_gqa_native_width",
    "test_parallel.py::test_ulysses_matches_local_attention",
    "test_pipeline.py::test_pipeline_boundary_windows_parity",
    "test_pipeline.py::test_pipeline_engine_tied_grads_flow",
    "test_pipeline.py::test_pipeline_param_residency_total_over_p",
    "test_zeropp.py::TestZeroPlusPlus::test_stage2_falls_back",
    "test_autotuning.py::TestAutotuner::test_tune_end_to_end",
    "test_checkpoint.py::test_onebit_comm_state_excluded_from_checkpoint",
    "test_checkpoint.py::test_save_load_roundtrip",
    "test_diffusion.py::test_sd_pipeline_text_to_image_smoke",
    "test_diffusion.py::test_unet_shapes_and_grad",
    "test_diffusion.py::test_vae_roundtrip_shapes",
    "test_engine.py::test_forward_backward_step_trio",
    "test_engine.py::test_fp16_dynamic_loss_scale",
    "test_engine.py::test_global_samples_counter",
    "test_engine.py::test_lr_schedule_applied",
    "test_hf_loader.py::TestBuildHfEngine::test_quantized_engine_runs",
    "test_hf_loader.py::TestLlamaParity::test_generate_through_hybrid_engine",
    "test_hf_loader.py::TestLlamaParity::test_logits_match_transformers",
    "test_hf_loader.py::TestMoEParity::test_qwen2_moe_norm_topk_variants",
    "test_hf_loader.py::TestQwen2MoeRaggedRunner::test_shared_expert_in_ragged_decode",
    "test_hf_loader.py::TestQwenV1::test_qwen_checkpoint_serves",
    "test_hybrid_engine.py::TestHybridEngine::test_train_generate_train",
    "test_inference.py::test_bert_classification_head_through_v1",
    "test_inference.py::test_bert_encoder_through_v1_engine",
    "test_inference.py::test_generate_matches_stepwise_argmax",
    "test_inference.py::test_v1_engine_zoo",
    "test_inference_v2.py::TestEvoformer::test_bias_shapes_and_grad",
    "test_inference_v2.py::TestFalconPhiRaggedRunners::test_falcon_decode_matches_full_forward",
    "test_inference_v2.py::TestFalconPhiRaggedRunners::test_phi_decode_matches_full_forward",
    "test_inference_v2.py::TestKVInt8::test_engine_int8_decode_loop_linear_layout",
    "test_inference_v2.py::TestKVInt8::test_engine_int8_pause_resume",
    "test_paged_attention.py::TestKVInt8Kernel::test_kernel_direct_int8_parity",
    "test_inference_v2.py::TestKVOffloadRestore::test_pause_evict_resume_token_exact",
    "test_inference_v2.py::TestOPTRaggedRunner::test_decode_matches_full_forward",
    "test_inference_v2.py::TestOnDeviceSampling::test_decode_batch_eos_freeze_accounting",
    "test_inference_v2.py::TestOnDeviceSampling::test_sampled_topk1_equals_greedy",
    "test_paged_attention.py::TestPagedFlashKernel::test_engine_tokens_identical_dense_vs_kernel",
    "test_paged_attention.py::TestPagedFlashKernel::test_gqa_and_chunk_parity",
    "test_paged_attention.py::TestPagedFlashKernel::test_long_context_8k",
    "test_inference_v2.py::TestRaggedEngineParity::test_decode_greedy_eos_truncates",
    "test_inference_v2.py::TestRaggedEngineParity::test_fused_decode_loop_linear_layout",
    "test_inference_v2.py::TestRaggedEngineParity::test_interleaved_sequences_isolated",
    "test_inference_v2.py::TestRaggedEngineParity::test_oversubscribed_pool_autopauses_and_completes",
    "test_inference_v2.py::TestRaggedEngineParity::test_oversubscribed_pool_with_decode_loop_enabled",
    "test_inference_v2.py::TestWOQRunner::test_woq_llama_generate_close_to_fp",
    "test_kernels.py::TestFusedXent::test_model_config_routes_fused",
    "test_kernels.py::TestFusedXent::test_sharded_wrapper_matches_chunked",
    "test_kernels_flash.py::TestShardedFlash::test_batch_and_head_sharded",
    "test_kernels_flash.py::TestShardedFlash::test_grad_matches_reference",
    "test_kernels_flash.py::TestShardedFlash::test_lse_output_grad",
    "test_linear_quant.py::TestFpQuantizer::test_exact_for_representable",
    "test_linear_quant.py::TestFpQuantizer::test_roundtrip_error",
    "test_models.py::TestBert::test_mlm_forward_and_mask",
    "test_models.py::TestLlama::test_forward_shapes_gqa",
    "test_models.py::TestLlama::test_trains_through_engine",
    "test_models.py::TestLlamaRaggedParity::test_llama_prefill_decode_parity",
    "test_models.py::TestMixtral::test_experts_contribute",
    "test_models.py::TestMixtral::test_forward_and_loss",
    "test_models.py::TestNewArchFamilies::test_trains_through_engine",
    "test_models.py::test_bloom_neox_gptj_train",
    "test_moe.py::test_experts_tp_matches_plain",
    "test_moe.py::test_grouped_gemm_grad_flows",
    "test_moe.py::test_moe_ep_both_orderings_run",
    "test_moe.py::test_moe_ep_grad_flows",
    "test_moe.py::test_moe_ep_grouped_feeds_ragged_dot",
    "test_moe.py::test_moe_ep_grouped_grad_flows",
    "test_moe.py::test_moe_ep_grouped_k1_and_auxloss",
    "test_moe.py::test_moe_ep_grouped_matches_capacity",
    "test_moe.py::test_moe_ep_grouped_with_experts_tp",
    "test_moe.py::test_moe_ep_matches_single_group",
    "test_moe.py::test_moe_ep_zero2_trains",
    "test_moe.py::test_moe_layer_forward",
    "test_moe.py::test_qwen2_moe_shared_expert",
    "test_offload.py::test_cpu_offload_checkpoint_roundtrip",
    "test_offload.py::test_cpu_offload_matches_resident",
    "test_offload.py::test_nvme_offload_checkpoint_roundtrip",
    "test_offload.py::test_nvme_offload_matches_resident",
    "test_offload.py::test_param_offload_nvme_matches_resident",
    "test_offload.py::test_param_offload_streams_and_matches_resident",
    "test_offload.py::test_param_streaming_grad_parity",
    "test_offload.py::test_param_streaming_in_step",
    "test_onebit.py::TestOnebitAllreduce::test_error_feedback_unbiased",
    "test_onebit.py::TestOnebitEngine::test_training_through_freeze_boundary",
    "test_parallel.py::test_ring_attention_kernel_grad",
    "test_parallel.py::test_tp_training_matches_no_tp",
    "test_pipeline.py::test_pipeline_engine_matches_unpipelined",
    "test_pipeline.py::test_pipeline_module_checkpoint_roundtrip",
    "test_pipeline.py::test_pipeline_stacked_moe_ep_composed",
    "test_pipeline.py::test_pipeline_stacked_moe_ep_engine_trains",
    "test_zeropp.py::TestHpzMics::test_hpz_matches_plain_stage3",
    "test_zeropp.py::TestHpzMics::test_training_with_inner_sharding",
    "test_zeropp.py::TestQuantizedCollectives::test_gather_roundtrip_and_grad",
    "test_zeropp.py::TestZeroPlusPlus::test_qwz_qgz_training_matches_baseline",
    "test_zeropp.py::test_fused_xent_inside_manual_seam",
])


def pytest_configure(config):
    """In the controller alone, before its workers start: the files go out
    in the collection's order, and what the tests leave in the temp
    directory from run to run is bounded (a whole run writes ~280 MB, and
    entries of programs that have since changed are never read again)."""
    if hasattr(config, "workerinput"):
        return
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False     # see _HEAVY_FIRST
    cache = jax.config.jax_compilation_cache_dir
    try:
        size = sum(e.stat().st_size for e in os.scandir(cache))
    except OSError:
        return
    if size > CACHE_LIMIT_BYTES:
        shutil.rmtree(cache, ignore_errors=True)


# ``--dist loadfile`` hands out whole files, two to a worker and then one as
# one ends, and by default in the order of their case COUNTS: the heavy
# files with few cases (the two of test_tpu_compile_train.py, 180 s) came
# last, and the last 110 s of PR 63's first runs were one worker working
# off its two while five sat idle. pytest_configure below turns xdist's
# reordering off (its own ``--no-loadscope-reorder``, so that nobody has to
# pass it) and the collection puts the files above ~100 s of the newest
# table (ROADMAP Queue 3 item 9) first, heaviest first; the others follow
# in their own order. A name that is not there any more does nothing.
_HEAVY_FIRST = (
    "test_regions.py", "test_minicpm_sala.py", "test_nemotron_h.py",
    "test_mellum.py", "test_paged_decode_kernel.py",
    "test_tpu_compile_train.py", "test_solar_open2.py",
    "test_tpu_compile_clients.py", "test_inference_v2.py", "test_afmoe.py",
    "test_kimi_linear.py", "test_grouped_ffn_engine.py",
    "test_grouped_ffn_bf16.py", "test_pangu_ultra_moe.py",
    "test_grouped_ffn.py", "test_resilience.py", "test_kernels_flash.py",
    "test_tpu_compile_state.py", "test_zeropp.py", "test_olmoe.py",
    "test_kernels_fp6.py")


def pytest_collection_modifyitems(config, items):
    rank = {name: i for i, name in enumerate(_HEAVY_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))
    matched = set()
    for item in items:
        base = item.nodeid.split('[')[0].replace('tests/unit/', '')
        if base in _FULL_TESTS:
            item.add_marker(pytest.mark.full)
            matched.add(base)
        # tier-1 selects -m 'not slow' under the driver's time limit: what
        # is listed above OR marked ``full`` in-source is ``slow`` too
        if item.get_closest_marker("full") and \
                not item.get_closest_marker("slow"):
            item.add_marker(pytest.mark.slow)
    # a renamed/deleted test must not SILENTLY fall out of the slow tier
    # into tier-1: only meaningful when the whole suite was collected
    stale = _FULL_TESTS - matched
    if stale and len(items) > 400:
        import warnings
        warnings.warn("stale _FULL_TESTS entries (renamed tests?): "
                      + ", ".join(sorted(stale)))
