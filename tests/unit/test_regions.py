"""Device regions (``telemetry/trace.py``: ``REGIONS``, ``region``): the
vocabulary is closed, every step program the cells run carries its regions
in the compiled HLO's ``op_name``, and a scope changes an instruction's
metadata and never the instruction: with ``region`` swapped for a null
context the compiled program is the same text once ``metadata={...}`` is
cut. The program itself has no switch; the swap is this test's.

Coverage is counted over the compiled instructions whose ``op_name`` is
a path of the program (``jit(f)/.../mul``; parameters, constants, tuples
and bitcasts left out): what XLA inserts of its own (broadcasts of
constants, copies, the CPU's fusion wrappers, a reducer's body) carries
none or its own instruction's name, as on the chip, where
``benchmark/regions.py`` files it under ``xla_inserted`` or ``unscoped``.
"""

import contextlib
import importlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.analysis import serve_program_calls
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceConfig)
from deepspeed_tpu.telemetry import trace

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")
#: the table of source locations that ``stack_frame_id`` points into
_FRAMES = re.compile(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", re.S)
_NAME = re.compile(r"%([A-Za-z_][\w\-]*?)(?:\.\d+)*\b(?![\w.\-])")
_REGION = re.compile(re.escape(trace.REGION_MARK) + r"(\w+)")
_TRIVIAL = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
#: what every serve step carries, whatever the family
_STEP = {"embed", "norm", "residual", "head", "kv_write"}
#: modules known to open regions (any other that imports ``region`` is
#: found too); ``telemetry.trace`` itself is one more
_SITES = ("inference.v2.llama_runner", "inference.v2.model_runner",
          "moe.sharded_moe", "ops.kernels.grouped_ffn", "runtime.engine",
          "models.gpt2", "models._lm_utils")
_PROGRAMS = ("step_greedy", "step_greedy_fb", "decode_loop", "flush_ring")


def _dense():
    from deepspeed_tpu.models.llama import Llama, LlamaConfig
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="xla")
    params = Llama(cfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params, dict(max_seqs=2, chunk_size=8, block_size=8,
                             num_blocks=32, max_blocks_per_seq=8,
                             attention_impl="dense")


def _moe():
    from deepspeed_tpu.models.mixtral import Mixtral, MixtralConfig
    cfg = MixtralConfig.tiny(
        num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=4,
        intermediate_size=32, num_experts=8, experts_top_k=2, qk_norm=True,
        dtype=jnp.float32, param_dtype=jnp.float32, attention_impl="xla")
    key = jax.random.PRNGKey(0)
    params = Mixtral(cfg).init({"params": key, "gating": key},
                               jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params, dict(max_seqs=4, chunk_size=8, block_size=8,
                             num_blocks=40, max_blocks_per_seq=8)


def _kda():
    from benchmark.model_types import solar_open2 as mt
    from deepspeed_tpu.models.solar_open2 import SolarOpen2Config
    cfg = SolarOpen2Config.tiny(experts_held=4, dtype=jnp.float32,
                                param_dtype=jnp.float32)
    return cfg, mt.init_params(cfg, 3), dict(
        max_seqs=4, chunk_size=16, block_size=16, num_blocks=24,
        max_blocks_per_seq=6)


def _mla():
    from benchmark.model_types import pangu_ultra_moe as mt
    from deepspeed_tpu.models.pangu_ultra_moe import PanguUltraMoEConfig
    cfg = PanguUltraMoEConfig.tiny(experts_held=4, experts_first=2,
                                   dtype=jnp.float32, param_dtype=jnp.float32)
    return cfg, mt.init_params(cfg, 3), dict(
        max_seqs=4, chunk_size=16, block_size=16, num_blocks=24,
        max_blocks_per_seq=6, prefill_chunk_cap=0)


def _ssm():
    from benchmark.model_types import nemotron_h as mt
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig
    cfg = NemotronHConfig.tiny(experts_held=4, experts_first=2,
                               dtype=jnp.float32, param_dtype=jnp.float32)
    return cfg, mt.init_params(cfg, 3), dict(
        max_seqs=4, chunk_size=16, block_size=16, num_blocks=24,
        max_blocks_per_seq=6, prefill_chunk_cap=0)


def _conv():
    from benchmark.model_types import lfm2_moe as mt
    from deepspeed_tpu.models.lfm2 import Lfm2Config
    cfg = Lfm2Config.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    return cfg, mt.init_params(cfg, 3), dict(
        max_seqs=4, chunk_size=16, block_size=16, num_blocks=24,
        max_blocks_per_seq=6, prefill_chunk_cap=0)


#: layer kind -> (model, the regions its step adds to ``_STEP``)
_KINDS = {
    "dense": (_dense, {"attn_proj", "attn_core", "ffn_dense"}),
    "moe": (_moe, {"attn_proj", "attn_core", "moe_route", "moe_experts"}),
    "kda": (_kda, {"attn_proj", "attn_core", "linear_attn", "moe_route",
                   "moe_experts", "moe_shared"}),
    "mla": (_mla, {"mla_proj", "mla_core", "ffn_dense", "moe_route",
                   "moe_experts", "moe_shared"}),
    "ssm": (_ssm, {"attn_proj", "attn_core", "ssm", "moe_route",
                   "moe_experts", "moe_shared"}),
    "conv": (_conv, {"attn_proj", "attn_core", "conv_mixer", "ffn_dense",
                     "moe_route", "moe_experts"}),
}


def _serve_programs(kind):
    """{program: compiled HLO text} of a fresh tiny engine."""
    cfg, params, icfg = _KINDS[kind][0]()
    eng = InferenceEngineV2(cfg, params, RaggedInferenceConfig(
        decode_loop_steps=4, dtype="float32", **icfg))
    calls = serve_program_calls(eng, _PROGRAMS)
    if eng.runner.kv_planes == 1:
        # a latent cache's ring is sequence-major, [L, 1, S, R, W]
        fn, (planes, ring, *rest), static = calls["flush_ring"]
        S, R = eng.config.max_seqs, ring.shape[0]
        ring = jnp.zeros((eng.runner.kv_layers, 1, S, R,
                          eng.runner.head_dim), ring.dtype)
        calls["flush_ring"] = (fn, (planes, ring, *rest), static)
    return {name: fn.lower(*args, **static).compile().as_text()
            for name, (fn, args, static) in calls.items()}


@contextlib.contextmanager
def _unscoped(monkeypatch):
    """Every call site's ``region`` swapped for a null context, and the
    traces made under the real one forgotten."""
    for name in _SITES:
        importlib.import_module("deepspeed_tpu." + name)
    sites = [m for name, m in list(sys.modules.items())
             if name.startswith("deepspeed_tpu.")
             and getattr(m, "region", None) is trace.region]
    assert len(sites) > len(_SITES), [m.__name__ for m in sites]
    with monkeypatch.context() as mp:
        for m in sites:
            mp.setattr(m, "region", lambda name: contextlib.nullcontext())
        jax.clear_caches()
        yield
    jax.clear_caches()


def _named(text):
    """(instructions whose op_name is a path, those under a region, the
    regions seen) of a compiled HLO text."""
    total = scoped = 0
    seen = set()
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        name = _OP_NAME.search(line)
        if not m or m.group(1) in _TRIVIAL or not name \
                or "/" not in name.group(1):
            continue
        total += 1
        found = _REGION.findall(name.group(1))
        scoped += bool(found)
        seen.update(found)
    return total, scoped, seen


def _strip(text):
    """The compiled text without what a scope may touch: the metadata,
    the source-location table, and XLA's numbering of instruction names
    (``%select_n.146``: each name is renumbered by first appearance, so
    two texts are equal when the same instructions feed each other in the
    same order)."""
    seen = {}

    def renumber(m):
        base = m.group(1)
        return seen.setdefault(m.group(0), f"%{base}#{len(seen)}")
    return _NAME.sub(renumber, _METADATA.sub("", _FRAMES.sub("\n", text)))


def test_the_vocabulary_is_closed():
    assert len(trace.REGIONS) == len(set(trace.REGIONS)) <= 24
    for name in trace.REGIONS:
        assert re.fullmatch(r"[a-z][a-z0-9_]*", name), name
    with pytest.raises(KeyError, match="REGIONS"):
        trace.region("attention")
    with jax.named_scope("outer"), trace.region("norm"):
        pass                        # a context manager, nothing more


def test_a_region_outside_the_table_raises_when_the_program_is_traced():
    def f(x):
        with trace.region("layer_norm"):
            return x + 1
    with pytest.raises(KeyError):
        jax.jit(f).lower(jnp.zeros(3))


@pytest.fixture
def metadata_keyed():
    """JAX's persistent compile cache leaves the metadata out of its key,
    so the scoped program's entry (op_names and all) would answer for the
    unscoped one, whose text is what these tests read: key the two apart
    while they compile. The cache itself stays on, each under its key."""
    name = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, name)
    jax.config.update(name, True)
    yield
    jax.config.update(name, was)


@pytest.mark.parametrize("kind", sorted(_KINDS))
def test_serve_programs_carry_their_regions_and_nothing_else_changes(
        kind, monkeypatch, metadata_keyed):
    scoped = _serve_programs(kind)
    with _unscoped(monkeypatch):
        bare = _serve_programs(kind)
    step = _STEP | _KINDS[kind][1] | {"sample"}
    want = {"step_greedy": step, "step_greedy_fb": step | {"loop_carry"},
            "decode_loop": step | {"loop_carry"}, "flush_ring": {"kv_write"}}
    for name, text in scoped.items():
        total, under, seen = _named(text)
        assert want[name] <= seen <= set(trace.REGIONS), \
            (name, want[name] - seen)
        assert under >= 0.9 * total, (name, under, total)
        assert _named(bare[name])[2] == set(), name
        assert _strip(text) == _strip(bare[name]), \
            f"{kind} {name}: the scoped program is not the unscoped one"


