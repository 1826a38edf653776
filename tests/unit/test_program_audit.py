"""Program auditor (ISSUE 4): static verification of the serving stack's
structural claims — collective budgets, donation, host-sync hygiene and
the recompile tripwire (deepspeed_tpu/analysis/program_audit.py).

These are the machine-checked versions of PR 2/3's claims: exactly 2
per-layer TP all-reduces + 1 pre-sampling logits gather, zero collectives
at tp=1, zero host callbacks in the greedy-feedback decode program, KV
pool donated into the ring flush. A refactor that silently regresses comm
volume or donation fails HERE even while token-parity tests still pass.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.analysis import (CollectiveBudget, RecompileTripwire,
                                    assert_budget, audit_fn,
                                    audit_serve_programs,
                                    serve_program_calls)
from deepspeed_tpu.analysis.program_audit import _subjaxprs
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceConfig)
from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config
from deepspeed_tpu.utils.jax_compat import shard_map

L = 2          # layers of every tiny model below


def _gpt2_engine(tp=1, **cfg_kw):
    mcfg = GPT2Config(vocab_size=96, max_seq_len=128, num_layers=L,
                      num_heads=4, hidden_size=64, dtype=jnp.float32)
    params = GPT2(mcfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    base = dict(max_seqs=4, chunk_size=8, block_size=8, num_blocks=64,
                max_blocks_per_seq=16, dtype="float32",
                attention_impl="dense", decode_loop_steps=4, tp_size=tp)
    base.update(cfg_kw)
    return InferenceEngineV2(mcfg, params, RaggedInferenceConfig(**base))


def _llama_engine(tp=1, **cfg_kw):
    from deepspeed_tpu.models.llama import Llama, LlamaConfig
    mcfg = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="xla")
    params = Llama(mcfg).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32))["params"]
    base = dict(max_seqs=2, chunk_size=8, block_size=8, num_blocks=64,
                max_blocks_per_seq=16, dtype="float32",
                attention_impl="dense", decode_loop_steps=4, tp_size=tp)
    base.update(cfg_kw)
    return InferenceEngineV2(mcfg, params, RaggedInferenceConfig(**base))


@pytest.fixture(scope="module")
def gpt2_reports_tp1():
    return audit_serve_programs(_gpt2_engine(tp=1))


@pytest.fixture(scope="module")
def gpt2_reports_tp2():
    return audit_serve_programs(_gpt2_engine(tp=2))


class TestCollectiveBudgets:
    """PR 2's comm accounting as regression tests at tp in {1, 2}."""

    def test_tp1_programs_have_zero_collectives(self, gpt2_reports_tp1):
        for name in ("step", "step_greedy", "step_greedy_fb",
                     "decode_loop", "flush_ring"):
            rep = gpt2_reports_tp1[name]
            assert rep.total_collectives == 0, rep.summary()
            assert rep.host_callbacks == 0, rep.summary()
            # the no-op budget formalism catches anything planted later
            assert_budget(rep, CollectiveBudget(
                f"tp1-{name}", num_layers=L))

    def test_tp2_step_two_allreduce_per_layer(self, gpt2_reports_tp2):
        # GPT-2 ties its unembed to wte (replicated) -> NO logits gather;
        # the budget is exactly the two row-parallel partial-sum reduces
        budget = CollectiveBudget("tp2-step", num_layers=L,
                                  per_layer={"all_reduce": 2})
        for name in ("step", "step_greedy", "step_greedy_fb"):
            assert_budget(gpt2_reports_tp2[name], budget)

    def test_tp2_fused_decode_loop_scan_weighted(self, gpt2_reports_tp2):
        # the n-step fused loop executes its body's collectives n times;
        # decode_loop_steps=4 -> 4 x 2L all-reduces, still zero gathers
        assert_budget(gpt2_reports_tp2["decode_loop"], CollectiveBudget(
            "tp2-decode-loop", num_layers=L, steps=4,
            per_layer={"all_reduce": 2}))

    def test_tp2_ring_flush_head_local(self, gpt2_reports_tp2):
        # flush work is head-local by design: zero collectives
        assert_budget(gpt2_reports_tp2["flush_ring"],
                      CollectiveBudget("tp2-flush", num_layers=L))

    def test_tp2_llama_untied_lmhead_gather(self):
        # untied lm_head is vocab-sharded -> per-layer 2 all-reduces PLUS
        # exactly ONE pre-sampling logits all-gather per step
        reports = audit_serve_programs(
            _llama_engine(tp=2), programs=("step", "decode_loop"))
        assert_budget(reports["step"], CollectiveBudget(
            "tp2-llama-step", num_layers=L, per_layer={"all_reduce": 2},
            per_program={"all_gather": 1}))
        assert_budget(reports["decode_loop"], CollectiveBudget(
            "tp2-llama-loop", num_layers=L, steps=4,
            per_layer={"all_reduce": 2}, per_program={"all_gather": 1}))

    def test_tp2_quantized_comm_rides_int8(self):
        # tp_quantized_comm swaps each psum for int8 value + f32 scale
        # all-gathers — the comm dtype makes the ZeRO++/EQuARX path
        # visible to the auditor
        rep = audit_serve_programs(
            _gpt2_engine(tp=2, tp_quantized_comm=True),
            programs=("step",))["step"]
        assert rep.count(kind="all_reduce") == 0, rep.summary()
        assert rep.count(kind="all_gather", dtype="int8") == 2 * L, \
            rep.summary()

    def test_planted_extra_allreduce_fails_with_diff(self,
                                                     gpt2_reports_tp2):
        # the acceptance tripwire: a third per-layer all-reduce violates
        # the budget and the failure message carries the expected/got diff
        with pytest.raises(AssertionError) as e:
            assert_budget(gpt2_reports_tp2["step"], CollectiveBudget(
                "three-per-layer", num_layers=L,
                per_layer={"all_reduce": 3}))
        msg = str(e.value)
        assert "expected 6" in msg and "got 4" in msg
        assert "all_reduce[model]" in msg


@pytest.fixture(scope="module")
def gpt2_reports_tp2_overlap():
    return audit_serve_programs(_gpt2_engine(
        tp=2, tp_comm_overlap="rs_ag_chunked", tp_comm_chunks=2))


class TestOverlapBudgets:
    """ISSUE 6: with the decomposed schedule on, every per-layer
    all-reduce site must audit as exactly k ring reduce-scatter + k ring
    all-gather hops (k = chunks*(tp-1)) — NO residual psum, no stray
    ppermutes (the walker canonicalizes ring hops, so any ppermute left
    in the report is un-ringed traffic and fails the budget)."""

    # tp=2, chunks=2 -> k = 2 hops per phase per site, 2 sites per layer
    PER_LAYER = {"reduce_scatter": 4, "all_gather": 4}

    def test_tp2_step_decomposed_schedule(self, gpt2_reports_tp2_overlap):
        budget = CollectiveBudget("tp2-overlap-step", num_layers=L,
                                  per_layer=self.PER_LAYER)
        for name in ("step", "step_greedy", "step_greedy_fb"):
            rep = gpt2_reports_tp2_overlap[name]
            assert_budget(rep, budget)
            # the decomposition is total: zero monolithic psums remain
            assert rep.count(kind="all_reduce") == 0, rep.summary()

    def test_tp2_decode_loop_scan_weighted(self, gpt2_reports_tp2_overlap):
        assert_budget(gpt2_reports_tp2_overlap["decode_loop"],
                      CollectiveBudget("tp2-overlap-loop", num_layers=L,
                                       steps=4, per_layer=self.PER_LAYER))

    def test_tp2_flush_still_head_local(self, gpt2_reports_tp2_overlap):
        assert_budget(gpt2_reports_tp2_overlap["flush_ring"],
                      CollectiveBudget("tp2-overlap-flush", num_layers=L))

    def test_tp2_rs_ag_unchunked_schedule(self):
        # rs_ag (chunks=1): tp-1 = 1 hop per phase per site
        rep = audit_serve_programs(
            _gpt2_engine(tp=2, tp_comm_overlap="rs_ag"),
            programs=("step",))["step"]
        assert_budget(rep, CollectiveBudget(
            "tp2-rsag-step", num_layers=L,
            per_layer={"reduce_scatter": 2, "all_gather": 2}))

    def test_tp2_quantized_ring_dtype_split(self):
        # EQuARX-grade: every hop carries int8 values + an f32 per-chunk
        # scale plane — budgeted separately via the kind@dtype keys
        rep = audit_serve_programs(
            _gpt2_engine(tp=2, tp_comm_overlap="rs_ag_chunked",
                         tp_comm_chunks=2, tp_quantized_comm=True),
            programs=("step",))["step"]
        assert rep.count(kind="all_reduce") == 0, rep.summary()
        assert_budget(rep, CollectiveBudget(
            "tp2-overlap-int8-step", num_layers=L,
            per_layer={"reduce_scatter@int8": 4,
                       "reduce_scatter@float32": 4,
                       "all_gather@int8": 4,
                       "all_gather@float32": 4}))

    def test_tp2_llama_overlap_keeps_logits_gather(self):
        # the one pre-sampling vocab gather stays a single real all_gather
        # on top of the per-layer ring hops
        reports = audit_serve_programs(
            _llama_engine(tp=2, tp_comm_overlap="rs_ag_chunked",
                          tp_comm_chunks=2), programs=("step",))
        assert_budget(reports["step"], CollectiveBudget(
            "tp2-llama-overlap-step", num_layers=L,
            per_layer=self.PER_LAYER, per_program={"all_gather": 1}))

    def test_quantized_llama_mixes_pinned_and_plain_keys(self):
        # the full quantized-ring llama budget: pinned int8/f32 keys for
        # the per-layer hops COMPOSE with the pre-sampling logits gather
        # (same kind, f32) — the gather merges into the f32 pinned key's
        # per_program count, and a plain sibling key only absorbs dtypes
        # no pinned key claims (no double-counting)
        rep = audit_serve_programs(
            _llama_engine(tp=2, tp_comm_overlap="rs_ag_chunked",
                          tp_comm_chunks=2, tp_quantized_comm=True),
            programs=("step",))["step"]
        assert_budget(rep, CollectiveBudget(
            "tp2-llama-overlap-int8-step", num_layers=L,
            per_layer={"reduce_scatter@int8": 4,
                       "reduce_scatter@float32": 4,
                       "all_gather@int8": 4,
                       "all_gather@float32": 4},
            per_program={"all_gather@float32": 1}))
        # the pinned int8 key + a plain "all_gather" sibling must not
        # re-absorb the pinned hops: with the int8 hops claimed, the
        # plain key sees only the unpinned f32 sites (L*4 scale hops + 1
        # logits gather) — under the old aggregate-everything semantics
        # this mix was unsatisfiable (the plain key double-counted the
        # int8 hops)
        mixed = CollectiveBudget(
            "mixed", num_layers=L,
            per_layer={"all_gather@int8": 4, "reduce_scatter@int8": 4,
                       "reduce_scatter@float32": 4},
            per_program={"all_gather": L * 4 + 1})
        assert mixed.check(rep) == [], "\n".join(mixed.check(rep))

    def test_planted_ring_hop_fails_with_diff(self):
        # acceptance tripwire: one extra hop planted inside a ring region
        # must trip the decomposed budget with an expected/got diff
        import deepspeed_tpu.comm as comm
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))

        def _sabotage_ring_reduce_scatter(x):
            return jax.lax.ppermute(x, "model", [(0, 1), (1, 0)])

        planted = jax.jit(_sabotage_ring_reduce_scatter)

        def prog(x):
            y = comm.decomposed_all_reduce(x, axis_name="model", chunks=2)
            return y + planted(y)

        f = shard_map(prog, mesh=mesh, in_specs=P(None), out_specs=P(None),
                      check_vma=False)
        rep = audit_fn(jax.jit(f), jnp.ones((8,), jnp.float32))
        with pytest.raises(AssertionError) as e:
            assert_budget(rep, CollectiveBudget(
                "planted-hop", per_layer={"reduce_scatter": 2,
                                          "all_gather": 2}))
        msg = str(e.value)
        assert "reduce_scatter[model]" in msg
        assert "expected 2" in msg and "got 3" in msg


def _warm_hit_engine(tp):
    eng = _gpt2_engine(tp=tp, prefix_cache=True)
    # block_size=8: 10 shared + 8 unique = 2 FULL blocks per prompt —
    # block 0 is a clean hit, block 1 agrees for 2 tokens (CoW)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, 96, 10).tolist()
    eng.put([0], [shared + rng.integers(1, 96, 8).tolist()],
            _greedy=True)
    eng.put([1], [shared + rng.integers(1, 96, 8).tolist()],
            _greedy=True)
    st = eng.prefix_stats
    assert st["matched_blocks"] > 0 and st["cow_copies"] > 0, st
    return eng


@pytest.fixture(scope="module", params=[1, 2], ids=["tp1", "tp2"])
def prefix_hit_engine(request):
    return request.param, _warm_hit_engine(request.param)


class TestPrefixCacheBudgets:
    """ISSUE 5 satellite: a prefix-cache HIT serves fewer chunks through
    the SAME compiled step programs — the hit path's collective counts
    must equal the miss path's (zero at tp=1, the canonical 2-per-layer
    all-reduces at tp=2), and the one new device program (the CoW block
    copy) is head-local: zero collectives, zero host callbacks."""

    def test_hit_prefill_budget_equals_miss_path(self, prefix_hit_engine):
        tp, eng = prefix_hit_engine
        per_layer = {} if tp == 1 else {"all_reduce": 2}
        reps = audit_serve_programs(eng, programs=("step", "step_greedy"))
        for name in ("step", "step_greedy"):
            assert_budget(reps[name], CollectiveBudget(
                f"tp{tp}-prefix-{name}", num_layers=L,
                per_layer=per_layer))

    def test_cow_copy_program_head_local(self, prefix_hit_engine):
        tp, eng = prefix_hit_engine
        rep = audit_fn(eng.kv_cache._copy_jit, eng._kv_data,
                       jnp.int32(0), jnp.int32(1), name=f"cow-copy-tp{tp}")
        assert rep.total_collectives == 0, rep.summary()
        assert rep.host_callbacks == 0, rep.summary()


class TestHostSyncHygiene:
    """PR 3's 'zero host round-trips on the steady decode path': the
    compiled programs must contain no host callbacks/infeed."""

    def test_greedy_feedback_program_no_host_callbacks(
            self, gpt2_reports_tp1, gpt2_reports_tp2):
        for reports in (gpt2_reports_tp1, gpt2_reports_tp2):
            rep = reports["step_greedy_fb"]
            assert rep.host_callbacks == 0, rep.summary()

    def test_auditor_detects_callbacks(self):
        def with_cb(x):
            y = jax.pure_callback(
                lambda v: np.asarray(v) * 2,
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return y + 1

        rep = audit_fn(with_cb, jnp.ones((4,), jnp.float32))
        assert rep.host_callbacks == 1


class TestDonation:
    """'KV pool donated' as a machine check: the lowered program must
    mark the pool argument as a buffer donor / aliased output."""

    def test_flush_ring_donates_pool_tp1(self, gpt2_reports_tp1):
        assert gpt2_reports_tp1["flush_ring"].donates, \
            gpt2_reports_tp1["flush_ring"].summary()

    def test_flush_ring_donates_pool_tp2(self, gpt2_reports_tp2):
        # sharded lowerings record donation as jax.buffer_donor (the
        # alias is resolved later by the compiler) — still auditable
        assert gpt2_reports_tp2["flush_ring"].donates, \
            gpt2_reports_tp2["flush_ring"].summary()

    def test_donation_parse_roundtrip(self):
        f = jax.jit(lambda a, b: (a + b, a - b), donate_argnums=(1,))
        rep = audit_fn(f, jnp.ones((4,)), jnp.ones((4,)),
                       name="donated")
        assert rep.donated_args == (1,)
        g = jax.jit(lambda a, b: a + b)
        assert not audit_fn(g, jnp.ones((4,)), jnp.ones((4,))).donates


class TestAuditorCore:
    """Kind mapping, axis attribution and scan weighting on synthetic
    shard_mapped programs (independent of the serving stack)."""

    def _mesh(self):
        return Mesh(np.asarray(jax.devices()[:2]), ("model",))

    def test_kind_mapping_and_axes(self):
        mesh = self._mesh()

        def body(x):
            y = jax.lax.psum(x, "model")
            g = jax.lax.all_gather(x, "model")
            s = jax.lax.psum_scatter(y, "model", tiled=True)
            p = jax.lax.ppermute(s, "model", [(0, 1), (1, 0)])
            return g.sum() + p.sum()

        f = shard_map(body, mesh=mesh, in_specs=P("model"),
                      out_specs=P(), check_vma=False)
        rep = audit_fn(jax.jit(f), jnp.ones((8,), jnp.float32))
        assert rep.count(kind="all_reduce", axis="model") == 1
        assert rep.count(kind="all_gather", axis="model") == 1
        assert rep.count(kind="reduce_scatter", axis="model") == 1
        assert rep.count(kind="ppermute", axis="model") == 1
        assert rep.total_collectives == 4
        # the summary names the axis role (parallel/topology.AXIS_ROLES)
        assert "tensor-parallel" in rep.summary()

    def test_scan_bodies_are_trip_weighted(self):
        mesh = self._mesh()

        def body(x):
            def step(c, _):
                return jax.lax.psum(c, "model"), ()
            out, _ = jax.lax.scan(step, x, None, length=5)
            return out

        f = shard_map(body, mesh=mesh, in_specs=P("model"),
                      out_specs=P("model"), check_vma=False)
        rep = audit_fn(jax.jit(f), jnp.ones((8,), jnp.float32))
        assert rep.count(kind="all_reduce") == 5

    def test_budget_flags_unbudgeted_axis(self):
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
        f = shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                      in_specs=P("data"), out_specs=P(), check_vma=False)
        rep = audit_fn(jax.jit(f), jnp.ones((8,), jnp.float32))
        with pytest.raises(AssertionError, match="unbudgeted axis"):
            assert_budget(rep, CollectiveBudget("model-only"))


def _live_equations(jaxpr):
    """Every equation XLA will see, sub-jaxprs included: dead code (the
    per-plane slices a caller makes only for their shapes) is dropped
    first, as jit drops it."""
    from jax._src.interpreters import partial_eval as pe
    live, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    for eqn in live.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from _live_equations(sub)


class TestPagedPoolIsReadInPlace:
    """ISSUE 26: on the paged layout (several blocks a sequence) the Pallas
    kernel's K and V operands are the WHOLE pool, and no step program
    builds anything of a pool plane's size except the pool's own in-place
    updates. A per-plane operand makes XLA copy that plane out of the pool
    first (2 L planes = the pool read and written once a step: 26-44 % of
    serve device time on the chip, PERF.md PR 26), and every token-parity
    test still passes, so the structure is held here."""

    #: what may yield a plane or more: the scatter that writes this step's
    #: rows into the donated pool, free views of it, and the containers
    #: that carry it through (their bodies are walked on their own)
    ALLOWED = {"scatter", "reshape", "jit", "scan", "while", "cond"}

    @pytest.fixture(scope="class")
    def engine(self):
        # a pool plane (257 blocks x 8 tokens x 64 lanes) larger than any
        # weight or activation of the tiny model, so "a plane or more"
        # singles the pool out
        return _gpt2_engine(attention_impl="paged_flash", num_blocks=256,
                            max_blocks_per_seq=4)

    @pytest.mark.parametrize(
        "program", ["step_greedy", "step_greedy_fb", "decode_loop"])
    def test_kernel_operands_are_the_pool(self, engine, program):
        import functools
        assert engine.config.max_blocks_per_seq > 1
        pool = engine._kv_data
        plane = pool.shape[2] * pool.shape[3]
        fn, args, static = serve_program_calls(engine, (program,))[program]
        eqns = list(_live_equations(
            jax.make_jaxpr(functools.partial(fn, **static))(*args).jaxpr))
        kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
        assert len(kernels) == L
        for e in kernels:
            sizes = [v.aval.size for v in e.invars]
            assert sizes.count(pool.size) == 2, sizes   # K and V
            assert plane not in sizes, sizes
        big = sorted({(e.primitive.name, v.aval.shape)
                      for e in eqns for v in e.outvars
                      if v.aval.size >= plane
                      and e.primitive.name not in self.ALLOWED})
        assert not big, f"{program} builds plane-sized values: {big}"


class TestRecompileTripwire:
    """A warm serve-pipeline run must not miss the jit cache."""

    def test_warm_pipeline_zero_fresh_compiles(self):
        eng = _gpt2_engine(tp=1, serve_pipeline_depth=2)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 96, 6).tolist() for _ in range(2)]
        uids = [0, 1]
        with RecompileTripwire() as cold:
            first = eng.put(uids, prompts, _greedy=True)
            eng.decode_pipelined(uids, [first[u] for u in uids], 4)
        assert cold.fresh_compiles > 0      # the signal actually fires
        with RecompileTripwire() as warm:
            eng.decode_pipelined(
                uids, [rng.integers(1, 96) for _ in uids], 4)
        assert warm.fresh_compiles == 0, (
            f"{warm.fresh_compiles} jit cache misses on a warm pipeline "
            f"run — a shape/dtype/static-arg leak in the serve loop")
