"""One reader a kernel, its sizes in the cell's own file (PR 58).

Twenty-nine per-model reader files became nine family entries: what
differed between them (the kernel's traced name, the model's sizes, how
many layers run it) stands in each cell's ``kernels`` block and reaches
the reader as ``cell.*``. The retired files' bodies are kept as data
(``retired_readers.json``: name, heir, cells, body without ``reads``);
for every (ancestor, cell) pair the heir reads, over observations built
from the cell's own file, the float the ancestor read.
"""

import json
import os

import pytest

from benchmark import kernel_cost, readers, ssm_cost
from benchmark.common import load_json, load_manifest

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = load_manifest()
PEAK = kernel_cost.peaks("TPU v5 lite")
with open(os.path.join(HERE, "retired_readers.json")) as f:
    RETIRED = json.load(f)
PAIRS = [(name, cell) for name, r in RETIRED.items() for cell in r["cells"]]
#: what ``closed_loop.py`` exports from each configuration as run (the
#: ``attention`` block of a traced run's observations; my chip runs, PR 57)
ATTENTION = {
    "serve-offline-rollout": (12, 2, 128, 28),
    "serve-olmoe-rollout": (16, 16, 128, 8),
    "serve-solar2-rollout": (64, 8, 128, 4),
    "serve-pangu-rollout-long": (128, 1, 576, 5),
    "serve-kimi-linear-rollout-long": (32, 1, 576, 8),
    "serve-nemotron3-nano-rollout-long": (32, 2, 128, 13),
    "serve-mellum2-rollout-long": (32, 4, 128, 8),
    "serve-minicpm-sala-rollout-32k": (32, 2, 128, 8)}


def _retired_ungated_ffn_cost(rows, experts_hit, hidden, width, itemsize=2):
    """``ssm_cost.ungated_ffn_cost`` as it stood until PR 58, which
    ``grouped_moe_roofline.nemotron`` named: two matrices an expert."""
    return {"flops": 4.0 * rows * hidden * width,
            "bytes": float(2 * experts_hit * hidden * width * itemsize
                           + 2 * rows * hidden * itemsize)}


def observations(cell_name):
    """A traced round at round numbers, under the cell's OWN names: every
    kernel of its ``kernels`` block in ``trace.ops`` with one call a layer
    and step, the paged decode kernel under the job's pattern, the
    counters and ``attention.*`` a closed-loop job exports."""
    cell = load_json("cells", cell_name + ".json")
    q, kv, d, layers = ATTENTION[cell_name]
    steps = cell["engine"]["decode_loop_steps"] * cell["trace_rounds"]
    slots = cell["clients"]
    ops, counts = {}, {}
    for i, (family, k) in enumerate(sorted(cell["kernels"].items())):
        name = k.get("op", f"closed_call-bf16_{slots}_{q}_{kv * d}")
        per_step = steps if family != "linear_attn_prefill" else 5
        ops[name] = 0.25 + 0.125 * i
        counts[name] = k.get("layers", 6) * per_step
    # a refill step's call of a kernel under another shape: never matched
    ops["grouped_ffn_decode-bf16_18880_4096"] = 1.0
    counts["grouped_ffn_decode-bf16_18880_4096"] = 16
    return {"cell": cell, "peak": PEAK, "refill_s": 4.0, "window_s": 40.0,
            "attention": {"q_heads": q, "kv_heads": kv, "head_dim": d,
                          "kv_row": kv * d, "layers": layers},
            "traced": {"decode_context_tokens": slots * steps * 3000,
                       "decode_steps": steps, "rounds": cell["trace_rounds"],
                       "pipeline": {
                           "moe_rows_routed": float(slots * 8 * steps * 5),
                           "moe_experts_hit": float(60 * steps * 5),
                           "linear_attn_prefill_kernel_tokens": 61437.0,
                           "prefill_rows": 3.0}},
            "trace": {"n_devices": 1, "busy_s": 4.0, "ops": ops,
                      "op_counts": counts}}


@pytest.mark.parametrize("ancestor,cell", PAIRS,
                         ids=[f"{a}-{c}" for a, c in PAIRS])
def test_an_heir_reads_what_its_ancestor_read(ancestor, cell, monkeypatch):
    monkeypatch.setattr(ssm_cost, "ungated_ffn_cost",
                        _retired_ungated_ffn_cost, raising=False)
    retired = RETIRED[ancestor]
    heir = next(m for m in MANIFEST["per_layer"]
                if m["name"] == retired["heir"])
    assert cell in heir["workloads"]
    obs = observations(cell)
    was = readers.read(retired["body"], obs)
    now = readers.read(load_json("layer_metrics", heir["name"] + ".json"),
                       obs)
    assert was is not None and 0.0 < was and was == now


def test_the_heirs_lists_are_their_ancestors_cells_and_nothing_else():
    assert len(RETIRED) == 29 and len(PAIRS) == 30
    by_heir = {}
    for r in RETIRED.values():
        by_heir.setdefault(r["heir"], set()).update(r["cells"])
    assert len(by_heir) == 9
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    for heir, cells in by_heir.items():
        assert set(entries[heir]["workloads"]) == cells, heir
    # one heir keeps an ancestor's name and file; no other name stays
    assert [a for a in RETIRED if a in entries] \
        == ["paged_attn_roofline.rollout"]


CLOSED = [w["name"] for w in MANIFEST["workloads"]
          if load_json("cells", w["name"] + ".json")["kind"]
          == "closed_loop"]


@pytest.mark.parametrize("cell", CLOSED)
def test_a_cells_kernels_block_is_read_and_whole(cell):
    """Every key a listed reader names under ``cell.kernels`` is in THIS
    cell's file, every entry of the block is read by a reader that lists
    the cell (an entry nobody reads would rot), and each has its why."""
    block = load_json("cells", cell + ".json")["kernels"]
    named = set()
    for m in MANIFEST["per_layer"]:
        if cell in m["workloads"]:
            spec = load_json("layer_metrics", m["name"] + ".json")
            named |= {k for k in readers.keys_of(spec)
                      if k.startswith("cell.kernels.")}
    assert named, cell
    for key in named:
        assert readers.lookup({"cell": {"kernels": block}}, key) \
            is not None, key
    stated = {f"cell.kernels.{family}.{k}" for family, entry in block.items()
              for k in entry if k != "why"}
    assert stated == named
    assert all(entry.get("why") for entry in block.values())


def test_a_placeholder_resolves_in_a_ratios_keys_or_the_reader_reads_nothing():
    spec = {"reducer": "ratio", "num": ["trace.ops.{cell.kernels.k.op}"],
            "den": ["trace.busy_s"], "scale": 100.0}
    obs = {"cell": {"kernels": {"k": {"op": "kernel-f32_9_9"}}},
           "trace": {"busy_s": 4.0, "ops": {"kernel-f32_9_9": 1.0,
                                            "None": 3.0}}}
    assert readers.read(spec, obs) == 25.0
    assert readers.keys_of(spec) == ["cell.kernels.k.op", "trace.busy_s"]
    # a cell that does not state the kernel, a trace without it: nothing,
    # and never the operation that happens to be called "None"
    assert readers.read(spec, dict(obs, cell={"kernels": {}})) is None
    assert readers.read(spec, dict(obs, cell={})) is None
    assert readers.read(spec, dict(obs, trace={"busy_s": 4.0,
                                               "ops": {}})) is None
    assert readers.resolve(obs, "a.{cell.kernels.k.op}.b") \
        == "a.kernel-f32_9_9.b"
    assert readers.resolve(obs, "no.placeholder") == "no.placeholder"
    assert readers.resolve(obs, "{cell.kernels.other.op}") is None


def test_a_rooflines_pattern_that_resolves_to_nothing_reads_nothing():
    spec = load_json("layer_metrics", "grouped_moe_roofline.rollout.json")
    obs = observations("serve-olmoe-rollout")
    assert readers.read(spec, obs) is not None
    # a cell of the list that forgot its block: nothing to read, where
    # the parent's str(None) landed in the pattern and merely failed to
    # match
    obs["trace"]["ops"]["None"] = 1.0
    obs["trace"]["op_counts"]["None"] = 1
    assert readers.read(spec, dict(obs, cell={"kernels": {}})) is None


def test_two_traced_rounds_count_a_layers_rows_once_a_layer():
    """What ``per`` = the layers that keep K/V buys over the retired
    ``calls_share``: at two traced rounds a layer is called twice as
    often and its rows are still counted once a layer over the stretch's
    own context tokens, where 1/128 of the calls seen counted them
    twice."""
    cell = "serve-nemotron3-nano-rollout-long"
    heir = load_json("layer_metrics", "paged_attn_roofline.rollout.json")
    was = RETIRED["paged_attn_roofline.nemotron"]["body"]
    one = observations(cell)
    two = observations(cell)
    name = "closed_call-bf16_256_32_256"
    for key in ("ops", "op_counts"):
        two["trace"][key][name] = 2 * one["trace"][key][name]
    two["traced"]["decode_context_tokens"] *= 2
    assert readers.read(heir, two) == pytest.approx(readers.read(heir, one))
    assert readers.read(was, two) == pytest.approx(
        2 * readers.read(was, one))
