"""What the served families' test files share, once (not a test file: no
``test_`` name, nothing collected).

A family's file (``test_olmoe.py`` ... ``test_lfm2.py``) keeps ONE row, a
:class:`Family` (its model type under ``benchmark/model_types``, its tiny
config, its tolerance, what its toy engine differs in), and the tests that
are its own (its mixer by hand, its kernels' shapes, its counters). The
engine builder, the reference's logits, the prompts and the BODIES of the
tests the families have in common live here. A body asserts what every
family asserts and hands back the engine (or its counters) so that the
family's test asserts its own counters beside the call.

The next family: a row, a ``model`` fixture, the shared tests as three-line
calls, and its own cases. Not a copy of the newest file.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceConfig)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

#: the toy engine every family is served by unless its row says otherwise:
#: 24 blocks of 16 rows, 6 a sequence, a fused loop of 4, float32, prefill
#: chunks as long as ``chunk_size`` says
ENGINE = dict(chunk_size=64, max_seqs=4, block_size=16, num_blocks=24,
              max_blocks_per_seq=6, decode_loop_steps=4, dtype="float32",
              prefill_chunk_cap=0)


def chunk_and_decode(test):
    """A prompt prefilled in one chunk or in three, decoded through the
    fused loop or step by step."""
    return pytest.mark.parametrize(
        "chunk", [64, 16], ids=["one-chunk", "three-chunks"])(
            pytest.mark.parametrize("decode", ["fused", "pipelined"])(test))


#: what a model whose cache cannot be snapshot, rewound or sharded refuses:
#: ``(feature, construction options, call arguments)``; ``None`` = refused at
#: construction, ``"model"`` = called with ``(cfg, params)``
REFUSALS = [
    ("prefix_cache", dict(prefix_cache=True), None),
    ("spec_decode", dict(spec_decode="ngram"), None),
    ("kv_cache_dtype='int8'", dict(kv_cache_dtype="int8"), None),
    ("tp_size > 1", dict(tp_size=2, max_seqs=2), None),
    ("seq_size > 1", dict(seq_size=2, max_seqs=2), None),
    ("ep_size > 1", dict(ep_size=2, max_seqs=2), None),
    ("handoff_out", {}, ([1],)), ("handoff_in", {}, ({},)),
    ("drain", {}, ()), ("replay", {}, ({},)),
    ("attach_draft", {}, "model"), ("decode_spec", {}, ([1], [3], 2)),
    ("pause", {}, (1,)), ("resume", {}, (1,))]
CONSTRUCTION_REFUSALS = [(f, kw) for f, kw, call in REFUSALS if call is None]
CALL_ARGS = {f: call for f, _, call in REFUSALS if call is not None}


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


def decode_tokens(eng, uid, last, n, decode="fused"):
    """``n`` greedy tokens after ``last`` through the fused loop or step by
    step, as ints."""
    step = eng.decode_batch if decode == "fused" else eng.decode_pipelined
    return [int(t) for t in step([uid], [last], n)[uid]]


def toy_engine(cfg, params, chunk=None, **kw):
    """An engine over :data:`ENGINE`'s pool with ``kw`` in place of its
    keys and ``chunk`` (if given) as its ``chunk_size``."""
    kw = dict(ENGINE, **kw)
    if chunk is not None:
        kw["chunk_size"] = chunk
    return InferenceEngineV2(cfg, params, RaggedInferenceConfig(**kw))


class Family:
    """One family's row: ``mt`` its module under ``benchmark/model_types``
    (``init_params``, ``reference_logits``), ``tiny`` its toy config,
    ``tol`` the float32 distance to the reference it is held to, and the
    keys of :data:`ENGINE` its toy engine differs in."""

    def __init__(self, mt, tiny, tol=2e-4, **engine_kw):
        self.mt, self.tiny, self.tol = mt, tiny, tol
        self.engine_kw = engine_kw
        self._reference = {}

    def model(self, seed=3):
        cfg = self.tiny()
        return cfg, self.mt.init_params(cfg, seed)

    def engine(self, cfg, params, chunk=None, **kw):
        return toy_engine(cfg, params, chunk, **dict(self.engine_kw, **kw))

    def ref_logits(self, cfg, params, tokens, at):
        """The plain reference's logits of ONE sequence at positions
        ``at``; its jitted forward is built once a config."""
        if cfg not in self._reference:
            self._reference[cfg] = self.mt.reference_logits(cfg)
        out = self._reference[cfg](params, jnp.asarray([tokens]),
                                   jnp.asarray([at]))
        return np.asarray(out)[0]

    # ---------------------- the engine against the reference ------------- #

    def walk(self, eng, model, uid, prompt, decode="fused", loops=(8,)):
        """Prefill ``prompt``, decode ``sum(loops)`` tokens (a fused loop
        an entry of ``loops``, or as many single steps), then one more
        position: the prefill's and the last position's logits within
        ``tol`` of the reference's forward over the whole sequence, every
        served token the reference's best."""
        cfg, params = model
        lg = np.asarray(eng.put([uid], [prompt])[uid])
        want = self.ref_logits(cfg, params, prompt, [len(prompt) - 1])[0]
        assert np.abs(lg - want).max() < self.tol
        tok = int(np.argmax(lg))
        if decode == "fused":
            toks = []
            for n in loops:
                toks += decode_tokens(eng, uid, toks[-1] if toks else tok, n)
        else:
            toks = decode_tokens(eng, uid, tok, sum(loops), "pipelined")
        seq = prompt + [tok] + toks
        want = self.ref_logits(cfg, params, seq,
                               list(range(len(prompt), len(seq))))
        assert toks == np.argmax(want[:-1], -1).tolist()
        # what the decode left in the cache: the next position's logits
        lg = np.asarray(eng.put([uid], [[toks[-1]]])[uid])
        assert np.abs(lg - want[-1]).max() < self.tol

    def serve_against_reference(self, model, chunk, decode, loops=(8,)):
        """:meth:`walk` over a 37-token prompt by a fresh engine at
        ``chunk``; returns the engine for the family's own counters."""
        eng = self.engine(*model, chunk)
        self.walk(eng, model, 7, prompt_of(37), decode, loops)
        return eng

    def serve_through_the_kernels(self, model, **kw):
        """The engine with the Pallas attention paths forced (interpreted
        here): a chunked prefill, 4 steps of the fused loop and 2 step by
        step against the reference."""
        cfg, params = model
        prompt = prompt_of(21, seed=4)
        eng = self.engine(cfg, params, 16, attention_impl="paged_flash", **kw)
        lg = np.asarray(eng.put([3], [prompt])[3])
        want = self.ref_logits(cfg, params, prompt, [len(prompt) - 1])[0]
        assert np.abs(lg - want).max() < self.tol
        tok = int(np.argmax(lg))
        toks = decode_tokens(eng, 3, tok, 4)
        toks += decode_tokens(eng, 3, toks[-1], 2, "pipelined")
        seq = prompt + [tok] + toks
        want = self.ref_logits(cfg, params, seq,
                               list(range(len(prompt), len(seq))))
        assert toks == np.argmax(want[:-1], -1).tolist()
        return eng

    def flax_model_reads_the_runners_tree(self, net, model):
        """The flax module's forward gives the reference's logits on the
        served tree, at every position of a 12-token prompt."""
        cfg, params = model
        prompt = prompt_of(12, seed=8)
        with jax.default_matmul_precision("highest"):
            got = net(cfg).apply({"params": params}, jnp.asarray([prompt]))[0]
        want = self.ref_logits(cfg, params, prompt, list(range(len(prompt))))
        assert float(np.abs(np.asarray(got) - want).max()) < self.tol

    def two_sequences_decode_as_alone(self, model, lengths=(21, 33, 18),
                                      after_flush=None):
        """Sequences 1 and 2 of different lengths in one batch of an engine
        with two slots, then a third in the slot the first one left: each
        decodes (5 tokens) what it decodes alone in a fresh engine (the
        toy engine of the logits tests: its programs are compiled).
        ``after_flush(eng, slot)`` sees the engine between the first's
        flush and the third's arrival."""
        cfg, params = model
        prompts = {u: prompt_of(n, seed=u)
                   for u, n in zip((1, 2, 3), lengths)}

        def first_of(logits):
            return int(np.argmax(np.asarray(logits)))

        def alone(uid):
            eng = self.engine(cfg, params, 16)
            tok = first_of(eng.put([uid], [prompts[uid]])[uid])
            return [tok] + decode_tokens(eng, uid, tok, 4)

        eng = self.engine(cfg, params, 16, max_seqs=2)
        out = eng.put([1, 2], [prompts[1], prompts[2]])
        first = {u: first_of(out[u]) for u in (1, 2)}
        got = eng.decode_batch([1, 2], [first[1], first[2]], 4)
        for u in (1, 2):
            assert [first[u]] + [int(t) for t in got[u]] == alone(u)
        slot = eng.state.sequences[1].state_slot
        eng.flush(1)
        if after_flush is not None:
            after_flush(eng, slot)
        tok = first_of(eng.put([3], [prompts[3]])[3])
        assert [tok] + decode_tokens(eng, 3, tok, 4) == alone(3)
        return eng, slot

    def decode_through_the_conv_kernel(self, model, monkeypatch):
        """The decode steps' short convolution through the in-place Pallas
        call (forced and interpreted here; on the chip platform and shape
        pick it) after a chunked prefill: 4 steps of the fused loop and 5
        step by step give the jnp path's tokens and leave its pool, states
        and carried inputs alike. (Alike to float32 rounding: inside a step
        program XLA's CPU backend contracts the taps' multiply-adds where
        it fuses them and not in the interpreted body;
        ``test_short_conv.py`` holds the call alone to the jnp path bit for
        bit, and so did the chip, PERF.md PR 46.) Returns the counters of
        the jnp run and of the kernel's."""
        from deepspeed_tpu.ops.kernels import short_conv
        cfg, params = model
        prompts = {5: prompt_of(21, seed=4), 6: prompt_of(9, seed=5)}

        def serve():
            eng = self.engine(cfg, params, 16)
            first = {u: int(np.argmax(np.asarray(lg)))
                     for u, lg in eng.put(list(prompts),
                                          list(prompts.values())).items()}
            out = eng.decode_batch([5, 6], [first[5], first[6]], 4)
            toks = {u: [first[u]] + [int(t) for t in out[u]]
                    for u in prompts}
            # one sequence alone: the other rows of its bucket are idle
            toks[6] += decode_tokens(eng, 6, toks[6][-1], 5, "pipelined")
            pool = jax.device_get((eng._kv_data.state, eng._kv_data.conv))
            return toks, pool, dict(eng.pipeline_stats)

        want_toks, want_pool, plain = serve()
        monkeypatch.setattr(short_conv, "decode_uses_kernel",
                            lambda *a, **k: True)
        toks, pool, forced = serve()
        assert toks == want_toks
        for got, want in zip(jax.tree_util.tree_leaves(pool),
                             jax.tree_util.tree_leaves(want_pool)):
            assert np.allclose(got, want, rtol=1e-4, atol=1e-5)
        return plain, forced

    def refusal(self, model, feature, kw, call):
        """The message ``feature`` is refused by: a construction option by
        ``config.validate`` (``call`` None), a call by the engine."""
        cfg, params = model
        if call is None:
            with pytest.raises(ValueError) as err:
                self.engine(cfg, params, **kw)
        else:
            eng = self.engine(cfg, params)
            eng.put([1], [prompt_of(9)])
            with pytest.raises(NotImplementedError) as err:
                getattr(eng, feature)(*((cfg, params) if call == "model"
                                        else call))
        return str(err.value)


# ------------------------- shares of a sparse layer ----------------------- #


def share_of(whole_cfg, moe, first, held,
             stacks=("wi_gate", "wi_up", "wo")):
    """``(cfg, moe tree)`` of the share holding experts ``first`` to
    ``first + held`` of the uncut layer's."""
    cfg = dataclasses.replace(whole_cfg, experts_first=first,
                              experts_held=held)
    return cfg, dict(moe, **{n: moe[n][first:first + held] for n in stacks})


def shares_add_up(parts, refs, uncut, once=0.0):
    """Guide section 4: every share does work, the engine's part is the
    reference's, and the parts (plus what is counted ``once``) are the
    uncut layer, in the engine's sparse block and in the reference alike."""
    for part, ref in zip(parts, refs):
        assert float(jnp.abs(part).max()) > 1e-3
        assert float(jnp.abs(part - ref).max()) < 1e-5
    assert float(jnp.abs(sum(parts) + once - uncut).max()) < 1e-5
    assert float(jnp.abs(sum(refs) + once - uncut).max()) < 1e-5


# ---------------------------- registry and loader ------------------------- #


def benchmark_config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name)) as f:
        return json.load(f)


def published(name, reduced):
    """The catalog's ``config`` as the configuration file ``name`` carries
    it, the ``reduced`` keys back at their published values."""
    d = benchmark_config(name)
    for key in reduced:
        d[key] = d[key + "_published"]
    return d


def catalog_row(name):
    """The catalog's row whose ``name`` is ``name``."""
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f) if r["name"] == name)


def hf_refuses(base, change, match):
    """``config_from_hf`` takes ``base`` and refuses it with ``change``."""
    from deepspeed_tpu.models.registry import config_from_hf
    config_from_hf(base)
    with pytest.raises(ValueError, match=match):
        config_from_hf(dict(base, **change))


def hf_trunk(params, stem="model", embed="embed_tokens", norm="norm"):
    """The three leaves every family's checkpoint names outside its
    layers."""
    return {f"{stem}.{embed}.weight": params["embed"]["embedding"],
            f"{stem}.{norm}.weight": params["final_norm"]["scale"],
            "lm_head.weight": params["lm_head"]["kernel"].T}


def hf_projections(state, pre, tree, names, suffix="_proj"):
    """``{pre}.{n}_proj.weight`` of each flax ``Dense`` ``n`` of ``tree``."""
    for n in names:
        state[f"{pre}.{n}{suffix}.weight"] = tree[f"{n}_proj"]["kernel"].T


def hf_experts(state, pre, moe, names):
    """Per-expert matrices ``{pre}.{e}.{theirs}.weight`` of the stacks
    ``ours`` of ``moe``, ``names`` = ``((ours, theirs), ...)``."""
    for ours, theirs in names:
        for e, w in enumerate(moe[ours]):
            state[f"{pre}.{e}.{theirs}.weight"] = w.T


def loader_reaches_every_leaf(arch, state, hf_cfg, params):
    """``state``, a checkpoint named as the family's are, converts to the
    tree the runner serves, leaf for leaf."""
    from deepspeed_tpu.checkpoint.hf_loader import (SPECIAL_HANDLERS,
                                                    convert_hf_state)
    got = convert_hf_state(arch, SPECIAL_HANDLERS[arch](state, hf_cfg))
    want = jax.tree_util.tree_leaves_with_path(params)
    have = dict(jax.tree_util.tree_leaves_with_path(got))
    assert len(have) == len(want)
    for path, leaf in want:
        assert np.array_equal(np.asarray(have[path]), leaf), path
