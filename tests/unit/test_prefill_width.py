"""A prefill step is as wide as the prompts it holds (ISSUE 29).

The scheduler caps the rows of a step that carry more than one token at
``RaggedInferenceConfig.prefill_rows`` (from the chunk width alone), and
``engine_v2._plan_step`` runs such a step in the one
``[prefill_rows, effective_chunk]`` program instead of padding it to 16
slots. Under test: the cap and what it leaves alone (decode rows, the
token budget, ageing), the shapes a ``put`` compiles, the count of programs
the benchmark's warm-up walks (the parent's), the bucket a mixed step takes,
and that the tokens do not depend on which step carried a chunk.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceConfig)
from deepspeed_tpu.inference.v2.drain import load_replay_state
from deepspeed_tpu.inference.v2.kv_cache import BlockedKVCache
from deepspeed_tpu.inference.v2.scheduler import (PREFILL_AGING_STEPS,
                                                  SplitFuseScheduler)
from deepspeed_tpu.inference.v2.state_manager import StateManager
from deepspeed_tpu.models import mixtral
from deepspeed_tpu.models.gpt2 import GPT2, GPT2Config

V = 96


def _cfg(**kw):
    base = dict(max_seqs=32, chunk_size=8, block_size=4, num_blocks=512,
                max_blocks_per_seq=16, dtype="float32", decode_loop_steps=4)
    base.update(kw)
    return RaggedInferenceConfig(**base)


def _dense():
    mcfg = GPT2Config(vocab_size=V, max_seq_len=128, num_layers=2,
                      num_heads=2, hidden_size=32, dtype=jnp.float32)
    params = GPT2(mcfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    return mcfg, params


def _moe():
    mcfg = mixtral.MixtralConfig.tiny(dtype=jnp.float32, vocab_size=V)
    assert mcfg.num_experts == 4
    _, init_fn, _ = mixtral.make_model(mcfg)
    return mcfg, init_fn(jax.random.PRNGKey(0), seq_len=16)


@pytest.fixture(scope="module")
def models():
    return {"dense": _dense(), "moe": _moe()}


def _prompts(n, seed=5, shared=0, lens=(13, 5, 20, 9, 17)):
    rng = np.random.default_rng(seed)
    head = rng.integers(1, V, shared).tolist()
    return [head + rng.integers(1, V, lens[i % len(lens)]).tolist()
            for i in range(n)]


def _scheduler(cfg):
    sm = StateManager(cfg, BlockedKVCache(cfg, 2, 2, 16, jnp.float32))
    return sm, SplitFuseScheduler(cfg, sm)


# ------------------------------------------------------------------ #
# the cap
# ------------------------------------------------------------------ #


@pytest.mark.parametrize("kw, rows", [
    (dict(chunk_size=8), 2),
    (dict(chunk_size=256), 2),
    (dict(chunk_size=512, prefill_chunk_cap=0), 4),
    (dict(chunk_size=512), 2),                      # capped to 256
    (dict(chunk_size=1024, prefill_chunk_cap=0), 8),
    (dict(chunk_size=512, prefill_chunk_cap=0, max_seqs=2), 2),
    # the sequence axis rounds the chunk UP, and the rows follow it
    (dict(chunk_size=510, prefill_chunk_cap=0, seq_size=4,
          attention_impl="dense"), 4),
])
def test_prefill_rows_come_from_the_chunk_width_alone(kw, rows):
    cfg = _cfg(**kw)
    assert cfg.prefill_rows == rows
    assert cfg.prefill_rows == min(max(2, cfg.effective_chunk // 128),
                                   cfg.max_seqs)


@pytest.mark.parametrize("chunk, cap, prefills, decodes, budget", [
    (8, 256, 1, 0, 64), (8, 256, 7, 0, 64), (8, 256, 30, 0, 64),
    (8, 256, 5, 9, 64), (512, 0, 3, 0, 4096), (512, 0, 16, 0, 4096),
    (512, 0, 16, 12, 4096),
    # the token budget stays an upper limit: it splits a chunk mid-way
    (8, 256, 7, 3, 11), (512, 0, 16, 0, 1024),
])
def test_at_most_prefill_rows_rows_carry_a_chunk(chunk, cap, prefills,
                                                 decodes, budget):
    cfg = _cfg(chunk_size=chunk, prefill_chunk_cap=cap, block_size=64,
               max_blocks_per_seq=32, num_blocks=2048,
               max_batch_tokens=budget)
    assert cfg.token_budget == budget
    sm, sched = _scheduler(cfg)
    rng = np.random.default_rng(prefills)
    for u in range(decodes):
        sm.put_tokens(u, [1])
    for u in range(100, 100 + prefills):
        # every third prompt leaves a single token for its last step
        sm.put_tokens(u, range(chunk + 1 if u % 3 == 0
                               else int(rng.integers(2, 3 * chunk))))
    want = {u: s.in_flight for u, s in sm.sequences.items()}
    got = dict.fromkeys(want, 0)
    steps = 0
    while any(s.in_flight for s in sm.sequences.values()):
        items = sched.schedule()
        sm.step += 1
        steps += 1
        wide = [it for it in items if len(it.tokens) > 1]
        assert 1 <= len(items) <= cfg.max_seqs
        assert len(wide) <= cfg.prefill_rows
        if steps > 1:
            # no decode row is left: a prompt's last token, alone, rides
            # as a prefill row, so the step still fits the one program
            assert len(items) <= cfg.prefill_rows
        assert sum(len(it.tokens) for it in items[decodes * (steps == 1):]
                   ) <= cfg.token_budget
        if steps == 1:
            # decode rows: all of them, first, whatever the prefills take
            assert [it.seq.uid for it in items[:decodes]] \
                == list(range(decodes))
            if budget >= cfg.prefill_rows * chunk:
                assert len(wide) == min(prefills, cfg.prefill_rows)
        for it in items:
            it.seq.last_sched = sm.step
            got[it.seq.uid] += len(it.tokens)
    assert got == want                  # every token scheduled exactly once


def test_fresh_prefills_beyond_the_cap_wait_in_longest_first_order():
    cfg = _cfg(max_seqs=4)
    sm, sched = _scheduler(cfg)
    for uid, n in ((1, 5), (2, 20), (3, 11), (4, 7)):
        sm.put_tokens(uid, range(n))
    assert [it.seq.uid for it in sched.schedule()] == [2, 3]
    # 2 has 12 left, 3 has 3: the waiting 4 and 1 now outrank 3
    assert [it.seq.uid for it in sched.schedule()] == [2, 4]


@pytest.mark.parametrize("arrivals", [1, 3, 8])
def test_an_aged_prefill_is_not_starved_under_the_cap(arrivals):
    """``arrivals`` fresh long prompts a step, more than the cap admits,
    always outrank the short one on longest-first: ageing still lifts it
    to the front within ``PREFILL_AGING_STEPS``."""
    cfg = _cfg(max_seqs=16, max_blocks_per_seq=64, num_blocks=4096)
    sm, sched = _scheduler(cfg)
    sm.put_tokens(1000, range(4))
    uid, at = 0, None
    for step in range(1, 4 * PREFILL_AGING_STEPS):
        for _ in range(arrivals):
            uid += 1
            sm.put_tokens(uid, range(16))
        sm.step = step
        items = sched.schedule()
        assert sum(len(it.tokens) > 1 for it in items) <= cfg.prefill_rows
        for it in items:
            it.seq.last_sched = step
        if any(it.seq.uid == 1000 for it in items):
            at = step
            break
    assert at is not None, "the short prefill starved"
    assert at <= PREFILL_AGING_STEPS + 2
    assert items[0].seq.uid == 1000 or arrivals == 1


# ------------------------------------------------------------------ #
# the shapes a put compiles
# ------------------------------------------------------------------ #


@pytest.fixture(scope="module")
def put_engine(models):
    return InferenceEngineV2(*models["dense"], _cfg())


@pytest.mark.parametrize("n", [1, 2, 5, 16])
def test_a_put_of_any_size_runs_the_one_prefill_program(put_engine, n):
    eng = put_engine
    uids = list(range(n))
    before = dict(eng.pipeline_stats)
    out = eng.put(uids, _prompts(n, seed=n), _greedy=True)
    assert sorted(out) == uids
    R, C = eng.config.prefill_rows, eng.config.effective_chunk
    assert (R, C) == (2, 8)
    assert [k for k in eng._staging if k[1] > 1] == [(R, C)]
    # the one other shape a put can meet: last tokens alone, [16, 1]
    assert set(eng._staging) <= {(R, C), (16, 1)}
    assert eng.runner._step_greedy._cache_size() == len(eng._staging)
    assert eng.runner._step_greedy_fb._cache_size() == 0
    steps = eng.pipeline_stats["prefill_steps"] - before["prefill_steps"]
    rows = eng.pipeline_stats["prefill_rows"] - before["prefill_rows"]
    planned = eng.pipeline_stats["prefill_tokens_planned"] \
        - before["prefill_tokens_planned"]
    assert planned == steps * R * C
    assert steps <= rows <= steps * R
    # prompts of 9 and 17 tokens leave one token last: still a prefill
    # row to the scheduler, but not one that carries a chunk
    assert rows == sum(len(p) // C + (len(p) % C > 1)
                       for p in _prompts(n, seed=n))
    for u in uids:
        eng.flush(u)


def test_the_benchmarks_warm_up_walk_compiles_the_parents_count(models):
    """``benchmark/jobs/open_loop.py::warm_up`` on a 64-slot engine: one
    prefill shape, and per decode bucket (64, 32, 16) the unfed and the fed
    step plus the fed step that follows a larger bucket. The parent
    (ea3ab25, the same walk) counts 4 + 5 as well: its prefill shape was
    [16, C] where this one is [prefill_rows, C]."""
    from benchmark.jobs.open_loop import warm_up

    class Ctx:
        @staticmethod
        def param(key):
            return {"admit_max": 16}[key]

    eng = InferenceEngineV2(*models["dense"], _cfg(
        max_seqs=64, block_size=8, max_blocks_per_seq=8, num_blocks=516,
        max_batch_tokens=128))
    warm_up(Ctx(), eng, V)
    r = eng.runner
    counts = {name: getattr(r, name)._cache_size()
              for name in ("_step", "_step_greedy", "_step_greedy_fb",
                           "_step_sample_fb", "_decode_loop_ring")}
    assert counts == {"_step": 0, "_step_greedy": 4, "_step_greedy_fb": 5,
                      "_step_sample_fb": 0, "_decode_loop_ring": 0}
    assert sorted(eng._staging) == [(2, 8), (16, 1), (32, 1), (64, 1)]
    st = eng.pipeline_stats
    # 64 prompts of 24 tokens = 192 whole chunks, two a step: no padding
    assert st["prefill_rows"] == 192 and st["prefill_steps"] == 96
    assert st["prefill_tokens_real"] == st["prefill_tokens_planned"] == 1536


@pytest.mark.parametrize("decodes, slots", [(1, 2), (2, 16), (5, 16),
                                            (17, 32)])
def test_a_step_mixing_decode_rows_with_a_chunk_keeps_its_bucket(
        models, decodes, slots):
    """One chunk row beside ``decodes`` single-token rows: the
    [prefill_rows, C] program while the rows fit it, else the smallest
    decode bucket that holds them, at the chunk's width."""
    eng = InferenceEngineV2(*models["dense"], _cfg())
    uids = list(range(decodes))
    first = eng.put(uids, _prompts(decodes), _greedy=True)
    for u in uids:
        eng.state.put_tokens(u, [int(first[u])])
    eng.state.put_tokens(99, _prompts(1, seed=3)[0])
    before = dict(eng.pipeline_stats)
    plan = eng._plan_step(greedy=True)
    assert plan.tokens.shape == (slots, eng.config.effective_chunk)
    assert [len(it.tokens) for it in plan.sched] == [1] * decodes + [8]
    st = eng.pipeline_stats
    assert st["prefill_steps"] - before["prefill_steps"] == 1
    assert st["prefill_rows"] - before["prefill_rows"] == 1
    assert st["prefill_tokens_planned"] \
        - before["prefill_tokens_planned"] == slots * 8


# ------------------------------------------------------------------ #
# the tokens do not depend on which step carried a chunk
# ------------------------------------------------------------------ #


def _serve(eng, prompts, groups, n_pipe=3, n_fused=4):
    """put (in ``groups``) + decode_pipelined + decode_batch."""
    uids = list(range(len(prompts)))
    first = {}
    for part in groups:
        first.update(eng.put(part, [prompts[u] for u in part],
                             _greedy=True))
    got = {u: [int(first[u])] for u in uids}
    for decode, n in ((eng.decode_pipelined, n_pipe),
                      (eng.decode_batch, n_fused)):
        outs = decode(uids, [got[u][-1] for u in uids], n)
        for u in uids:
            got[u].extend(int(t) for t in outs[u])
    return got


@pytest.mark.parametrize("model", ["dense", "moe"])
def test_tokens_match_the_synchronous_engine_with_the_prefix_cache_on(
        models, model):
    """Five prompts sharing a 12-token head (three cached blocks), put as
    one group through the depth-2 pipeline, against the synchronous engine
    (``serve_pipeline_depth=0``) fed one prompt a put, where every chunk
    rides a step of its own."""
    prompts = _prompts(5, seed=11, shared=12)
    uids = list(range(5))
    kw = dict(max_seqs=8, prefix_cache=True)
    piped = InferenceEngineV2(*models[model], _cfg(**kw))
    sync = InferenceEngineV2(*models[model],
                             _cfg(serve_pipeline_depth=0, **kw))
    got = _serve(piped, prompts, [uids[:1], uids[1:]])
    want = _serve(sync, prompts, [[u] for u in uids])
    assert got == want
    assert piped.prefix_stats["matched_tokens"] > 0
    st = piped.pipeline_stats
    assert st["prefill_rows"] <= st["prefill_steps"] * 2
    assert st["prefill_rows"] > st["prefill_steps"]   # some steps held two


@pytest.mark.parametrize("model", ["dense", "moe"])
def test_tokens_survive_a_drain_and_replay(models, model, tmp_path):
    prompts = _prompts(5, seed=13, shared=12)
    uids = list(range(5))
    kw = dict(max_seqs=8, prefix_cache=True)
    want = _serve(InferenceEngineV2(*models[model],
                                    _cfg(serve_pipeline_depth=0, **kw)),
                  prompts, [uids], n_pipe=7, n_fused=4)
    src = InferenceEngineV2(*models[model], _cfg(**kw))
    first = src.put(uids, prompts, _greedy=True)
    got = {u: [int(first[u])] for u in uids}
    outs = src.decode_pipelined(uids, [got[u][-1] for u in uids], 3)
    for u in uids:
        got[u].extend(int(t) for t in outs[u])
    path = str(tmp_path / "manifest.json")
    src.drain(path)
    dst = InferenceEngineV2(*models[model], _cfg(**kw))
    # five sequences re-enter as prompt + generated: one [2, C] program
    out = dst.replay(load_replay_state(path, None))
    assert [k for k in dst._staging if k[1] > 1] == [(2, 8)]
    for u in uids:
        got[u].append(int(out[u]))
    for decode, n in ((dst.decode_pipelined, 3), (dst.decode_batch, 4)):
        outs = decode(uids, [got[u][-1] for u in uids], n)
        for u in uids:
            got[u].extend(int(t) for t in outs[u])
    assert got == want
