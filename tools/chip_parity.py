"""A configuration of the benchmark at its published widths on the chip,
against its plain reference; and what each limit of its cell's ``correct``
catches.

    chiprun -- python tools/chip_parity.py --config openpangu-ultra-moe-718b
    chiprun -- python tools/chip_parity.py --config kimi-linear-48b-a3b
    chiprun -- python tools/chip_parity.py --config nemotron-3-nano-30b-a3b
    chiprun -- python tools/chip_parity.py --config mellum2-12b-a2.5b
    chiprun -- python tools/chip_parity.py --config minicpm-sala-9b [--prompt-blocks 128]
    chiprun -- python tools/chip_parity.py --config lfm2-24b-a2b
    chiprun -- python tools/chip_parity.py --config olmo-hybrid-7b
    chiprun -- python tools/chip_parity.py --config jamba2-3b
    python tools/chip_parity.py --config solar-open2-250b --rehearse   # CPU, toy

Outside any timed window. The model type's hooks come from
``benchmark/model_types/<model_type>.py`` (``model_config``,
``init_params``, ``reference_logits``), the cache geometry from the
configuration's cell. Three parts, each a JSON line (and all of them in
``chiprun_out/chip_parity.<config>.json``):

* ``engine``: the configuration served through ``InferenceEngineV2`` on
  the cell's geometry: 4 seeded prompts whose contexts cross a block
  boundary while decoding (``put``, several prefill chunks), 22
  single-token steps through the cache, 8 steps of the fused
  ``decode_batch`` loop, then single-token steps that read the rows it
  flushed: 32 positions a sequence (``WALKS`` gives a model type a
  longer walk where its cache needs one to show: Mellum's prompts are
  2,048 tokens, past its window and its window pool's first wrap, and
  its loops five of 128 steps, so the last rows read what five flushes
  wrote into BOTH pools). The engine's LOGITS against the
  reference's float32 forward over the whole sequence, in deviations of the
  reference's row, and the served tokens by the cell's own rule.
* ``hidden`` (``--hidden``): the residual stream after the first SPARSE
  layer (leading dense layers included), where a wrong expert still
  shows: an engine of those layers whose final norm has scale one and
  whose head is the identity serves the RMS-normalised stream as its
  "logits"; against the reference's, and against the reference with its
  smallest expert left out (``top_k - 1``). The positions that control
  moves beyond the tolerance are the ones where the comparison sees an
  expert (all of them where a chip holds every expert, few where it holds
  a share): there must be some, and the engine must agree with the full
  reference AT them, or the part fails. (Where the reference's router
  holds two experts within a rounding of each other a bfloat16 engine
  picks the other one and the position reads tens of percent: PERF.md
  section 6, PR 27.)
* ``variants``: the reference against itself with ONE thing wrong at a
  time (``VARIANTS``, by model type; always every weight matrix rounded to
  float8 e4m3, the nearest precision below the bfloat16 the configurations
  state), over as many sequences and positions as the cell's ``correct``
  compares. Each wrong model "serves" its own best token at the compared
  positions; reported is the cell's own pair of numbers. A wrong model must
  fail one of the cell's limits.

Tolerances for ``ok``: logits within LOGIT_TOL = 0.06 deviations of the
row in the median position and 0.3, the figure the cells allow the served
token, in the worst (``LOGIT_TOLS`` by model type where the router's flips
show more often); the served tokens correct by the cell's rule and the
float8 reference not; with ``--hidden``, HIDDEN_TOL = 3 % of the stream's
length in the median position (bfloat16 rounds at 0.2 % a value).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

HIDDEN_TOL = 0.03
LOGIT_TOL = 0.06
LOGIT_TOL_WORST = 0.3
#: (median, worst) where a chip holds a QUARTER of each layer's experts,
#: over seven sparse layers at routed weights x 2.446: of the router's
#: near-ties at the top-8's edge that a bfloat16 stream turns the other
#: way (about one token and layer in ten), 44 % add or drop one of this
#: chip's experts and every later position of the sequence reads the
#: state that token left; with the routed scale 0 in engine and reference
#: alike the stream after all eight layers is 0.64 % off in the median and
#: 0.74 % at the worst of 128 positions (my chip runs, PR 40)
LOGIT_TOLS = {"kimi_linear": (0.15, 2.0),
              # a chip holds a HALF of each layer's experts (a swap at the
              # top-6's edge adds or drops one of its own every second
              # time), five sparse layers at routed weights x 2.5; the
              # state-space layers carry every such token's trace on
              # (my chip runs, PR 44: the readings are in PERF.md)
              "nemotron_h": (0.2, 3.0),
              # the attention draw is sharp on purpose (a score of deviation
              # 3, times 1.63 on a full layer: benchmark/model_types/
              # mellum.py) and sharp attention amplifies a bfloat16
              # stream's rounding layer by layer, eight layers deep, with a
              # half of each layer's experts held beside it: the engine
              # reads 0.59 in the median and 1.04 at the worst of 128
              # positions, the engine with R one block too few 2.52 and
              # 3.50, the float8 reference 2.34 in the median (my chip
              # runs, PR 48: the readings are in PERF.md); each limit lies
              # between its two readings
              "mellum": (1.2, 2.0),
              # the same sharp attention draw over two softmax layers at
              # 64-lane heads, and seven convolution mixers of deviation 1
              # whose gates MULTIPLY a bfloat16 stream's rounding (B * u,
              # C * conv), nine layers deep with every expert held: the
              # engine reads 0.70 in the median and 2.22 at the worst of
              # 128 positions, the float8 reference 3.05 in the median
              # (my chip runs, PR 59); over a FLAT draw the same engine
              # reads the first two families' figures (PERF.md section 6)
              "lfm2_moe": (1.5, 4.0),
              # a dense model, so no expert is ever swapped; but the
              # attention draw is sharp and every branch reads an
              # UNNORMED stream (the family norms a branch's output, not
              # its input), eight layers deep in bfloat16: the engine
              # reads 0.092 in the median and 0.132 at the worst of 128
              # positions, the float8 reference 1.83 in the median and
              # the mildest wrong model (beta without its 2) 2.02 (my chip
              # run, PR 65); each limit lies between its two readings
              "olmo_hybrid": (0.4, 0.8),
              # a dense model again, 28 layers deep (every other served
              # configuration is cut to 4-13), under the sharp attention
              # draw, a head TIED to an embedding of deviation
              # 1/sqrt(hidden) and a recurrence whose step size passes
              # two bfloat16 matmuls: the engine reads 0.204 in the median
              # and 0.267 at the worst of 128 positions; the float8
              # reference 3.49 in the median and the mildest wrong model
              # (a rotary code applied) 2.34 (my chip run, PR 68); each
              # limit lies between its two readings
              "jamba": (0.7, 1.4)}
POSITIONS = 32
SINGLE_BEFORE, FUSED = 22, 8
#: by model type, where the default walk does not reach what the family
#: adds: the prompt in whole blocks (default: the cell's shortest
#: prompt), the steps of a fused loop and how many loops
WALKS = {"mellum": {"prompt_blocks": 8, "fused": 128, "loops": 5},
         # two of the cell's 256-step loops: the second selects from
         # compressed keys and reads rows the first loop's flush wrote
         "minicpm_sala": {"fused": 256, "loops": 2},
         # two of the cell's 128-step loops: the rows after them read
         # carried convolution inputs that passed two flushes
         "lfm2_moe": {"fused": 128, "loops": 2},
         # one of the cell's 256-step loops: the rows after it read the
         # K/V rows its flush wrote (64-row windows of 3,840 lanes) and
         # the state 256 in-place updates left
         "olmo_hybrid": {"fused": 256, "loops": 1},
         # two of the cell's 128-step loops: the rows after them read the
         # state 256 in-place updates left and K/V rows two flushes wrote
         "jamba": {"fused": 128, "loops": 2}}

#: the reference's own keyword for each wrong model, by model type
VARIANTS = {
    "olmoe": {"one_expert_left_out": {"top_k": 7}},
    "solar_open2": {
        "bf16_state": {"state_dtype": "bfloat16"},
        "no_l2_norm": {"l2_norm": False},
        "beta_without_its_2": {"beta_scale": 1.0},
        "softmax_router": {"router": "softmax"},
        "rotary_left_on": {"rope_theta": 10000.0}},
    "pangu_ultra_moe": {
        "rope_left_off_the_shared_key": {"rope_on_key": False},
        "rope_theta_1e4": {"rope_theta": 10000.0},
        "scale_128": {"scale": 128 ** -0.5},
        "latent_norm_left_out": {"latent_norm": False},
        "branch_norms_left_out": {"sandwich": False},
        "routed_scaling_1": {"routed_scaling": 1.0}},
    "kimi_linear": {
        "rotary_applied": {"rope_theta": 10000.0},
        "latent_norm_left_out": {"latent_norm": False},
        "beta_doubled": {"beta_scale": 2.0},
        "routed_scaling_1": {"routed_scaling": 1.0},
        "latent_layers_run_as_kda": {"latent_as_kda": True}},
    "nemotron_h": {
        "d_x_term_left_out": {"skip_term": False},
        "gate_after_the_grouped_norm": {"gate_first": False},
        "conv_bias_left_out": {"conv_bias": False},
        "rotary_applied": {"rope_theta": 10000.0}},
    "mellum": {
        "window_left_out_of_the_sliding_layers": {"window_on": "none"},
        "window_applied_to_the_full_layers": {"window_on": "all"},
        "plain_rotary_on_the_full_layers": {"yarn_on": False},
        "attention_factor_left_out": {"attention_factor_on": False},
        "head_norm_left_out": {"head_norm": False}},
    "lfm2_moe": {
        "silu_on_the_convolution": {"conv_silu": True},
        "four_taps": {"conv_window": 4},
        # the compared positions start at the prompt's last (a whole
        # number of blocks less one); a loop of 128 steps later the first
        # flush, and every 128 after it
        "carried_inputs_zeroed_at_a_flush": {"conv_reset": "flush"},
        "selection_by_the_unbiased_score": {"select_biased": False},
        "renormalisation_left_out": {"renorm": False},
        "rotary_paired_as_at_128_lanes": {"rope_pair_dim": 128}},
    "olmo_hybrid": {
        "beta_without_its_2": {"beta_scale": 1.0},
        "a_decay_a_channel_drawn_independently": {"channel_decay": True},
        "norms_moved_to_the_branch_inputs": {"norm_at": "input"},
        "rotary_switched_on": {"rope_theta": 10000.0}},
    "jamba": {
        "inner_norms_left_out": {"inner_norms": False},
        "one_decay_a_channel_for_all_its_states": {"shared_decay": True},
        "bf16_state": {"state_dtype": "bfloat16"},
        # as lfm2_moe's lost carry: a loop of the cell's steps after the
        # first compared position the first flush, and every loop after
        "state_dropped_at_a_flush": {"state_reset": "flush"},
        "conv_bias_left_out": {"conv_bias": False},
        "rotary_applied": {"rope_theta": 10000.0}},
    "minicpm_sala": {
        "dense_attention_in_place_of_the_selection": {"selection": "dense"},
        "topk_32": {"topk": 32},
        "forced_window_left_out": {"window_size": 0},
        "block_0_left_out": {"init_blocks": 0},
        "rotary_put_on_the_sparse_layers": {"sparse_rope": True},
        "rotary_taken_off_the_lightning_layers": {"lightning_rope": False},
        "lambda_1": {"decay_one": True},
        "mup_factors_left_out": {"mup": False}},
}


def serve_rows(engine, prompts, vocab_rows=None, fused=FUSED, loops=1):
    """Per sequence: the [POSITIONS, width] rows ``put`` returned (the
    last prompt position, then single-token steps before and after
    ``loops`` fused loops of ``fused`` steps) and the tokens fed,
    teacher-forced on the served argmax."""
    import numpy as np
    uids = list(range(len(prompts)))
    rows = {u: [] for u in uids}
    streams = {u: list(p) for u, p in enumerate(prompts)}

    def pick(u, row):
        rows[u].append(np.asarray(row, np.float32))
        return int(np.argmax(row[:vocab_rows]))

    def single():
        out = engine.put(uids, [[nxt[u]] for u in uids])
        for u in uids:
            streams[u].append(nxt[u])
            nxt[u] = pick(u, out[u])

    out = engine.put(uids, prompts)
    nxt = {u: pick(u, out[u]) for u in uids}
    for _ in range(SINGLE_BEFORE):
        single()
    for _ in range(loops):
        out = engine.decode_batch(uids, [nxt[u] for u in uids], fused)
        for u in uids:
            streams[u] += [nxt[u]] + [int(t) for t in out[u][:-1]]
            nxt[u] = int(out[u][-1])
    while len(rows[uids[0]]) < POSITIONS:
        single()
    return rows, streams


def row_positions(prompt_len, fused=FUSED):
    """Positions of ``serve_rows``'s rows in the served stream, ``fused``
    tokens in all through its loops."""
    first = [prompt_len - 1 + i for i in range(1 + SINGLE_BEFORE)]
    start = first[-1] + fused + 1
    return first + [start + i for i in range(POSITIONS - len(first))]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a configuration of BENCHMARK.json that a serve "
                         "cell runs")
    ap.add_argument("--seed", type=int, default=3400000731)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--hidden", action="store_true")
    ap.add_argument("--parts", default="engine,variants")
    ap.add_argument("--prompt-blocks", type=int, default=None,
                    help="whole blocks of context the prompts fill "
                         "(default: the model type's walk, else the "
                         "cell's shortest prompt)")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    elif jax.devices()[0].platform != "tpu":
        print(f"needs a TPU; JAX found {jax.devices()}", file=sys.stderr)
        return 3
    from benchmark.common import load_json, load_manifest
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    if not args.rehearse:
        enable_compile_cache()
    # the configuration's serve cell (its last, where it has several) and
    # the whole blocks of context that cell's shortest prompt fills: the
    # prompts here cross the next boundary while decoding
    entry = [w for w in load_manifest()["workloads"]
             if w["config"] == args.config][-1]
    cell_name = entry["name"]
    dims = load_json("configs", args.config + ".json")
    if args.rehearse:
        dims.update(dims["rehearse"])
    mt = importlib.import_module(
        f"benchmark.model_types.{dims['model_type']}")
    cfg = mt.model_config(dims)
    if args.rehearse:
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = mt.init_params(cfg, args.seed)
    cell = load_json("cells", cell_name + ".json")
    spec = cell["correct"]
    block = cell["engine"]["block_size"]
    walk = {"fused": FUSED, "loops": 1, **WALKS.get(dims["model_type"], {})}
    whole_blocks = args.prompt_blocks or walk.get("prompt_blocks") \
        or max(1, min(load_json("traffic", entry["traffic"] + ".json")[
            "prompt_lens"]) // block)
    if args.rehearse:
        block, walk["fused"] = 64, min(walk["fused"], FUSED)
    fused_total = walk["fused"] * walk["loops"]
    edge = whole_blocks * block
    lens = [edge - 30, edge - 20, edge - 10, edge + 5]
    blocks_a_seq = -(-(lens[-1] + POSITIONS + fused_total) // block)
    icfg = RaggedInferenceConfig(**dict(
        cell["engine"], max_seqs=8, block_size=block,
        max_blocks_per_seq=blocks_a_seq,
        num_blocks=4 * blocks_a_seq + 2,
        chunk_size=512 if not args.rehearse else 48, max_batch_tokens=0,
        decode_loop_steps=walk["fused"],
        dtype="bfloat16" if not args.rehearse else "float32"))
    rs = np.random.RandomState(args.seed % (2 ** 31))
    prompts = [list(map(int, rs.randint(1, cfg.vocab_size, n)))
               for n in lens]
    # the model type's reference with its fixed dimensions, open to one
    # more keyword: a wrong model
    if hasattr(mt, "reference_logits_with"):
        # a model type whose reference runs a sequence at a time
        logits_fn = functools.partial(mt.reference_logits_with, cfg)
    else:
        inner = mt.reference_logits(cfg).__wrapped__
        reference = importlib.import_module(inner.func.__module__)

        def logits_fn(**wrong):
            return jax.jit(functools.partial(
                inner.func, **{**inner.keywords, **wrong}))

    result = {"config": args.config, "cell": cell_name, "seed": args.seed,
              "device": jax.devices()[0].device_kind, "prompt_lens": lens}
    parts = set(args.parts.split(","))
    ok = True

    def padded(streams):
        T = max(len(s) for s in streams.values())
        toks = np.zeros((len(streams), T), np.int32)
        for u, s in streams.items():
            toks[u, :len(s)] = s        # right padding: causal, unseen
        at = np.stack([row_positions(n, fused_total)
                       for n in lens]).astype(np.int32)
        return jnp.asarray(toks), jnp.asarray(at)

    def cell_rule(ref, tokens_served):
        """The cell's ``correct`` rule on served tokens [B, n]."""
        got = np.take_along_axis(ref, tokens_served[..., None], -1)[..., 0]
        g = (ref.max(-1) - got) / ref.std(-1)
        return {"worst_gap_sigma": float(g.max()),
                "same_top1_share": float((g == 0).mean()),
                "correct": bool(
                    g.max() <= spec["tolerance_sigma"]
                    and (g == 0).mean() >= spec["min_same_top1_share"])}

    def float8_in_place(tree):
        """Every matrix rounded to float8 e4m3 and back, without a second
        copy of the weights beside the first (8 GB twice does not fit):
        down in one program, the tree dropped, up in another. Two programs
        because inside one XLA keeps the excess precision and the round
        trip rounds nothing. Who rounds draws the weights again from the
        seed afterwards."""
        kinds = jax.tree_util.tree_map(lambda x: x.dtype, tree)
        small = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float8_e4m3fn) if x.ndim >= 2 else x,
            t))(tree)
        jax.tree_util.tree_map(lambda x: x.delete(), tree)
        return jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x, d: x.astype(d), t, kinds))(small)

    # ---- the hidden state after the first sparse layer ---- #
    if args.hidden:
        C = cfg.hidden_size
        kinds = list(getattr(cfg, "ffn_kinds", None) or ["moe"])
        n_lay = kinds.index("moe") + 1
        one = dataclasses.replace(cfg, num_layers=n_lay, **{
            k: getattr(cfg, k)[:n_lay] for k in ("layer_kinds", "ffn_kinds")
            if getattr(cfg, k, None)})
        p_one = {"embed": params["embed"],
                 **{f"layer_{i}": params[f"layer_{i}"]
                    for i in range(n_lay)},
                 "final_norm": {"scale": jnp.ones((C,), jnp.float32)},
                 "lm_head": {"kernel": jnp.eye(C, dtype=cfg.param_dtype)}}
        eng = InferenceEngineV2(one, p_one, icfg)
        rows, streams = serve_rows(eng, prompts, vocab_rows=C,
                                   fused=walk["fused"], loops=walk["loops"])
        del eng
        toks, at = padded(streams)
        dims_kw = {k: v for k, v in inner.keywords.items()}

        def normed_hidden(**wrong):
            @jax.jit
            def f(p, toks, at):
                x = reference.hidden_states(p, toks, layers=n_lay,
                                            **{**dims_kw, **wrong})
                x = jnp.take_along_axis(x, at[..., None], axis=1)
                return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                                         + cfg.rms_eps)
            return np.asarray(f(params, toks, at), np.float32)

        ref = normed_hidden()
        less = normed_hidden(top_k=cfg.experts_top_k - 1) \
            if "top_k" in dims_kw else None
        served = np.stack([np.stack(rows[u]) for u in sorted(rows)])

        def rel(a, b):
            return np.linalg.norm(a - b, axis=-1) \
                / np.linalg.norm(b, axis=-1)

        err = rel(served, ref)
        result["hidden"] = {
            "layers": n_lay, "positions": int(err.size),
            "tolerance": HIDDEN_TOL,
            "served_rel_err_median": float(np.median(err)),
            "served_rel_err_p90": float(np.percentile(err, 90)),
            "served_rel_err_max": float(err.max()),
            "positions_over_tolerance": int((err > HIDDEN_TOL).sum())}
        ok_hidden = np.median(err) <= HIDDEN_TOL
        if less is not None:
            # where leaving an expert out shows, the engine must not
            miss = rel(less, ref)
            seen = miss > HIDDEN_TOL
            result["hidden"].update(
                one_expert_left_out_rel_err_median=float(np.median(miss)),
                positions_where_it_shows=int(seen.sum()),
                left_out_rel_err_median_there=float(
                    np.median(miss[seen])) if seen.any() else None,
                served_rel_err_median_there=float(
                    np.median(err[seen])) if seen.any() else None)
            ok_hidden = ok_hidden and seen.any() \
                and np.median(err[seen]) <= HIDDEN_TOL
        print(json.dumps({"hidden": result["hidden"]}), flush=True)
        ok = ok and bool(ok_hidden)

    # ---- the engine's logits against the reference's ---- #
    if "engine" in parts:
        eng = InferenceEngineV2(cfg, params, icfg)
        rows, streams = serve_rows(eng, prompts, fused=walk["fused"],
                                   loops=walk["loops"])
        stats = {k: v for k, v in eng.pipeline_stats.items()
                 if k.startswith(("latent_", "mla_", "decode_kv_rows",
                                  "state_", "linear_attn_", "window_",
                                  "sparse_"))}
        del eng
        toks, at = padded(streams)
        ref = np.asarray(logits_fn()(params, toks, at), np.float32)
        served = np.stack([np.stack(rows[u]) for u in sorted(rows)])
        sigma = ref.std(-1)
        err = np.abs(served - ref).max(-1) / sigma
        params = float8_in_place(params)
        low = np.asarray(logits_fn()(params, toks, at), np.float32)
        # the rounded tree goes before the weights are drawn again: two
        # trees of 10.4 GB do not fit beside each other
        jax.tree_util.tree_map(lambda x: x.delete(), params)
        params = mt.init_params(cfg, args.seed)
        tol, tol_worst = LOGIT_TOLS.get(dims["model_type"],
                                        (LOGIT_TOL, LOGIT_TOL_WORST))
        result["engine"] = {
            "positions": int(err.size),
            "logit_err_sigma_median": float(np.median(err)),
            "logit_err_sigma_worst": float(err.max()),
            "logit_err_sigma_by_row": [float(np.median(err[:, t]))
                                       for t in (0, 1, 22, 23, 31)],
            "same_top1_share": float(
                (served.argmax(-1) == ref.argmax(-1)).mean()),
            "served_by_the_cells_rule": cell_rule(ref, served.argmax(-1)),
            "float8_reference_by_the_cells_rule":
                cell_rule(ref, low.argmax(-1)),
            "counters": stats,
            "tolerance_sigma_median": tol,
            "tolerance_sigma_worst": tol_worst}
        print(json.dumps({"engine": result["engine"]}), flush=True)
        e = result["engine"]
        ok = ok and bool(
            np.median(err) <= tol and err.max() <= tol_worst
            and e["served_by_the_cells_rule"]["correct"]
            and not e["float8_reference_by_the_cells_rule"]["correct"])

    # ---- the reference with one thing wrong ---- #
    if "variants" in parts:
        n, B = int(spec["tokens"]), int(spec["sequences"])
        if args.rehearse:
            n = min(n, POSITIONS)
        T = (edge if whole_blocks > 1 else 128) + n
        if args.rehearse and dims["model_type"] == "minicpm_sala":
            T = 384 + n         # past the toy dense_len, a CPU's size
        toks = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, T)), jnp.int32)
        at = jnp.asarray(np.tile(np.arange(T - n, T), (B, 1)), jnp.int32)
        base = np.asarray(logits_fn()(params, toks, at), np.float32)
        result["variants_positions"] = [B, n]
        result["variants"] = {}
        wrongs = dict(VARIANTS.get(dims["model_type"], {}),
                      float8_weights={"weights": "float8_e4m3fn"})
        for name, wrong in wrongs.items():
            wrong, tree = dict(wrong), params
            if wrong.pop("weights", None):        # the last of them
                tree = params = float8_in_place(params)
            if "state_dtype" in wrong:
                wrong["state_dtype"] = jnp.bfloat16
            for lost in ("conv_reset", "state_reset"):
                if wrong.get(lost) == "flush":
                    # the compared positions are a stream's first n
                    # served: a loop of the cell's steps after the first,
                    # a flush
                    steps = cell["engine"]["decode_loop_steps"]
                    wrong[lost] = (T - n + steps, steps)
            lg = np.asarray(logits_fn(**wrong)(tree, toks, at), np.float32)
            result["variants"][name] = dict(
                cell_rule(base, lg.argmax(-1)),
                logit_err_sigma_median=float(np.median(
                    np.abs(lg - base).max(-1) / base.std(-1))))
            print(json.dumps({"variant": name,
                              **result["variants"][name]}), flush=True)
    result["ok"] = bool(ok)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"chip_parity.{args.config}.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"ok": result["ok"]}), flush=True)
    return 0 if result["ok"] or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
