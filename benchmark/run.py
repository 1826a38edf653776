"""Run one cell of ``BENCHMARK.json`` once, in this process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Refuses to start without a TPU holding the chips the cell asks for (no CPU
fallback), builds the weights on the device from the seed, warms only this
cell's shapes through the persistent compile cache, measures for
``--seconds``, checks the outputs against the plain reference outside the
window, and prints the result as the last line of standard output.

``--rehearse`` runs the same control flow on the CPU at the tiny sizes in
the files' ``rehearse`` blocks (four virtual devices): the line then says
``platform: cpu`` and carries no time, rate or share, only counts. With
``--trace 1`` a rehearsal also walks the traced stretch, with no profiler
under it, so that the job fills every observation a reader names.

The harness holds no per-cell code: a cell is ``cells/<name>.json`` naming
a job kind (a module of ``benchmark/jobs``), a configuration
(``configs/<name>.json``, with its ``model_type`` module and plain
reference) and a traffic file (``traffic/<name>.json``); its metrics are
those ``BENCHMARK.json`` lists for it, each per-layer one read by
``layer_metrics/<name>.json``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _metrics_of(manifest, section: str, cell: str):
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


def read_trace(path: str):
    """One reading of a profile (``.xplane.pb``): ``reduce_trace``'s sums
    (window, busy, operations, collectives: computed as without the
    program's spans) with the idle gaps named by what the host was doing,
    ``<bench span>/<innermost program span>`` (``program_spans``), and
    the device's seconds by region of the step programs (``regions``:
    nothing where the program has none). Returns ``obs["trace"]`` and the
    printed ``breakdown``."""
    from benchmark import program_spans, reduce_trace, regions
    trace = reduce_trace.load(path)
    reduced = reduce_trace.reduce(trace)
    reduced["idle_s"] = reduced["window_s"] - reduced["busy_s"]
    named = program_spans.name_gaps(program_spans.from_trace(trace))
    reduced.update(named["trace"])
    t0 = time.perf_counter()
    reduced.update(regions.from_trace(trace, path))
    reduced["regions_read_s"] = time.perf_counter() - t0
    return reduced, {
        "device_ops": reduced["device_ops"],
        "idle_gaps": named["breakdown"]["idle_gaps"]}


def run_cell(argv=None):
    """Run one cell as the command line says; returns the result line and
    the job's observations (what the per-layer readers read)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") \
            + " --xla_force_host_platform_device_count=4"
        # a CPU entry must never land in the chip's persistent cache
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    from benchmark import readers, reduce_trace
    from benchmark.common import (CompileClock, Ctx, fail, load_cell,
                                  load_json, load_manifest, say)
    manifest = load_manifest()
    entry, cell, config, traffic = load_cell(manifest, args.workload)

    import jax
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    want = "cpu" if args.rehearse else "tpu"
    if device["platform"] != want or len(devs) < entry["chips"]:
        fail(f"cell {entry['name']} needs {entry['chips']} {want} device(s);"
             f" JAX found {device}")
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = None if args.rehearse else enable_compile_cache()
    seconds = args.seconds if args.seconds is not None \
        else float(manifest["run_seconds"])
    ctx = Ctx(cell_name=entry["name"], cell=cell, config=config,
              traffic=traffic, seed=args.seed, seconds=seconds,
              trace=bool(args.trace), rehearse=args.rehearse,
              t_process=T_PROCESS)
    if args.rehearse:
        ctx.seconds = float(cell.get("rehearse", {}).get("seconds", 1.0))
    ctx.compiles = CompileClock()
    say("device", device)
    say("cell", {"name": entry["name"], "kind": cell["kind"],
                 "seed": args.seed, "seconds": ctx.seconds,
                 "trace": ctx.trace, "compile_cache": cache_dir})
    ctx.mark("import")

    job = importlib.import_module(f"benchmark.jobs.{cell['kind']}")
    result = job.run(ctx)
    obs = result["obs"]
    obs["cell"] = cell   # its ``kernels`` block: names and sizes for readers
    obs["setup"] = dict(ctx.compiles.snapshot(), phase_s=ctx.phase_s,
                        at_window_open=ctx.setup_compiles)
    obs["memory_peak_bytes"] = ctx.memory_peak_bytes
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    line = {"correct": all(result["checks"].values()),
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": {}, "device": device, "checks": result["checks"],
            "phase_s": ctx.phase_s}
    say("phase_s", ctx.phase_s)

    if args.rehearse:
        # a rehearsal: counts only, never under a device metric's name
        line["rehearsal"] = {"programs": obs["setup"]["programs"],
                             "attempted": result["attempted"]}
    elif ctx.trace:
        from benchmark import kernel_cost
        obs["peak"] = kernel_cost.peaks(device["kind"])
        reduced, line["breakdown"] = read_trace(
            reduce_trace.find_xplane(ctx.trace_dir))
        obs["trace"] = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        say("trace", {k: reduced.get(k) for k in (
            "idle_named_share", "clock_offset_s", "device_programs",
            "idle_by_phase", "region_named_share", "regions",
            "regions_read_s")})
        if reduced["busy_s"] <= 0:
            line["correct"] = False
        for m in _metrics_of(manifest, "per_layer", entry["name"]):
            spec = load_json("layer_metrics", m["name"] + ".json")
            value = readers.read(spec, obs)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
    else:
        values = dict(result["end_to_end"], setup_s=ctx.setup_s)
        for m in _metrics_of(manifest, "end_to_end", entry["name"]):
            line["metrics"][m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
    # each number ``correct`` compared beside its limit: the line's last
    # key and the last lines of standard error
    line["compared"] = result.get("compared", {})
    for name, pair in line["compared"].items():
        print(f"[benchmark] compared {name}: {pair['value']!r} "
              f"limit {pair['limit']!r}", file=sys.stderr, flush=True)
    return line, obs


def main(argv=None) -> int:
    line, _obs = run_cell(argv)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
